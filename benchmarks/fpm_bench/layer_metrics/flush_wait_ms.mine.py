"""Time a sweep dispatcher flush waits for the device, in ms: the
``flush.wait`` spans (the copy of each launch's counts back to the
host) of the traced window's mines over their ``flush`` spans. A
backend that computes on the host records launches and no wait."""


def read(record):
    wait = 0.0
    flushes = launches = 0
    for op in record.get("ops", []):
        for name, _lane, a, b in op.get("spans", ()):
            if name == "flush":
                flushes += 1
            elif name == "flush.wait":
                wait += b - a
            elif name == "flush.launch":
                launches += 1
    if not flushes or not launches:
        return None
    return 1000.0 * wait / flushes
