"""Host milliseconds per plan request in the program's ``plan.solve``
span: the vectorized solve (``core.shp.plan_ntier_arrays``: dispatch,
device solve and the copy back)."""
from harness import program


def read(rd):
    return program.span_ms(rd, "plan.solve")
