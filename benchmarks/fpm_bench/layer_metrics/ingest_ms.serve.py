"""Mean duration of the program's ``ingest`` spans in the traced window,
in ms: packing a batch into a fresh arena segment."""


def read(record):
    lo, hi = record["window"]
    durations = [b - a for name, _lane, a, b in record.get("spans", ())
                 if name == "ingest" and lo <= a and b <= hi]
    if not durations:
        return None
    return 1000.0 * sum(durations) / len(durations)
