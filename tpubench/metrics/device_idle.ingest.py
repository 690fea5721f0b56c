"""Share of the measured window of an ingest cell in which no operation
ran on the chip."""


def read(rd):
    busy = rd.busy_ns()
    if busy is None:
        return None
    lo, hi = rd.window_ns
    return 100.0 * (1.0 - busy / (hi - lo))
