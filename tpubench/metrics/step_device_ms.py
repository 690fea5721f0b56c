"""Device milliseconds per chunk of the jitted fleet step (the trace's
``jit_step`` program events in the measured window)."""

PROGRAM = "jit_step"


def read(rd):
    return rd.program_ms(PROGRAM)
