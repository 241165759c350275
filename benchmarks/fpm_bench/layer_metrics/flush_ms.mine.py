"""Mean duration of the sweep dispatcher's ``flush`` spans in the traced
window's mines, in ms: host preparation, device launches and the wait
for the counts of one batched launch."""


def read(record):
    durations = [b - a for op in record.get("ops", [])
                 for name, _lane, a, b in op.get("spans", ())
                 if name == "flush"]
    if not durations:
        return None
    return 1000.0 * sum(durations) / len(durations)
