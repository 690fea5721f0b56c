"""Documents applied (device state advanced, meter record written) over
all the time of the window, the chunk in flight at the deadline
included."""


def read(out):
    return out.docs / out.window_s
