"""Sweep requests per dispatcher flush over the traced window's mines,
from the program's counters (``MiningMetrics``)."""


def read(record):
    ops = [op for op in record.get("ops", []) if "flushes" in op]
    flushes = sum(op["flushes"] for op in ops)
    if not flushes:
        return None
    return sum(op["requests"] for op in ops) / flushes
