"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of device operation intervals) / window."""


def read(record):
    dev = record.get("device")
    if not dev or not dev.get("window_s"):
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
