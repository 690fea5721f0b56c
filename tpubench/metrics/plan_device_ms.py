"""Device milliseconds per plan request of the planner's solve program
(the trace's ``jit__plan_jit`` program events in the measured window)."""

PROGRAM = "jit__plan_jit"


def read(rd):
    return rd.program_ms(PROGRAM)
