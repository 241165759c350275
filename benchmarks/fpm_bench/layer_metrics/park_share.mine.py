"""Share of the scheduler's worker-seconds spent parked during the
traced window's complete mines: worker-seconds in the program's ``park``
spans over workers times the mines' wall time, in %."""


def read(record):
    park = total = 0.0
    for op in record.get("ops", []):
        if "spans" not in op or op.get("dropped"):
            return None
        t0, t1 = op["t0"], op["t1"]
        total += record["n_workers"] * (t1 - t0)
        for name, _lane, a, b in op["spans"]:
            if name == "park":
                park += max(0.0, min(b, t1) - max(a, t0))
    return 100.0 * park / total if total else None
