"""Host milliseconds per plan request in the program's ``plan.pack``
span: stacking the request's cost models and resolving each tenant's
constraints (``core.shp.solve_ntier_batch``)."""
from harness import program


def read(rd):
    return program.span_ms(rd, "plan.pack")
