"""Host milliseconds per chunk in the program's ``ingest.route`` span:
``StreamRouter.route`` scattering a mixed batch into bucket-dense
arrays."""
from harness import program


def read(rd):
    return program.span_ms(rd, "ingest.route")
