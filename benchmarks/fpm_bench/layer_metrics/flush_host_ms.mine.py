"""Host time of a sweep dispatcher flush, in ms: the ``flush.prepare``
(index and tid arrays, mirror sync) and ``flush.launch`` (the jitted
calls) spans of the traced window's mines over their ``flush`` spans."""

HOST = ("flush.prepare", "flush.launch")


def read(record):
    host = 0.0
    flushes = launches = 0
    for op in record.get("ops", []):
        for name, _lane, a, b in op.get("spans", ()):
            if name == "flush":
                flushes += 1
            elif name in HOST:
                host += b - a
                launches += name == "flush.launch"
    if not flushes or not launches:
        return None
    return 1000.0 * host / flushes
