"""Device time of the ``gather_intersect_many`` kernel per complete mine of the
traced window, in ms, from the profiler's device events."""

KERNEL = "gather_intersect_many"


def read(record):
    dev = record.get("device", {})
    mines = len(record.get("ops", []))
    if not mines or not dev.get("kernel_seen", {}).get(KERNEL):
        return None
    return 1000.0 * dev["kernel_s"][KERNEL] / mines
