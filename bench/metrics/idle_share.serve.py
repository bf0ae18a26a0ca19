"""Share of the time inside the server's ticks (``step`` spans) in which no
operation ran on the device (from the device trace)."""


def read(record):
    t = record.trace
    if t is None:
        return None
    busy, inside = t.busy_in("step")
    if inside <= 0:
        return None
    return 100.0 * (1.0 - busy / inside)
