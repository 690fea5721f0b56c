"""Host milliseconds per plan request in the program's ``plan.unpack``
span: naming each plan's strategy and writing the per-tenant results
back (``streams.planner``)."""
from harness import program


def read(rd):
    return program.span_ms(rd, "plan.unpack")
