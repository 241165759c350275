"""Host time on the level driver's critical path with no sweep task in
flight, per complete mine of the traced window, in ms: the mine's wall
time less its ``driver`` lane's ``level.spawn`` and ``level.barrier``
spans (candidate generation, planning, collection and the runtime's
start and close are what is left)."""

IN_FLIGHT = ("level.spawn", "level.barrier")


def read(record):
    serial = []
    for op in record.get("ops", []):
        if "spans" not in op or op.get("dropped"):
            return None
        t0, t1 = op["t0"], op["t1"]
        in_flight = [max(0.0, min(b, t1) - max(a, t0))
                     for name, lane, a, b in op["spans"]
                     if lane == "driver" and name in IN_FLIGHT]
        if not in_flight:
            return None
        serial.append(t1 - t0 - sum(in_flight))
    if not serial:
        return None
    return 1000.0 * sum(serial) / len(serial)
