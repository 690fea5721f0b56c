"""Host milliseconds per chunk in the program's ``ingest.fetch`` span:
copying the step's write masks, evictions and reservoir ids to the host
for the meter."""
from harness import program


def read(rd):
    return program.span_ms(rd, "ingest.fetch")
