"""Host milliseconds per chunk in the program's ``ingest.stage`` span:
staging the chunk's host batches onto the device
(``StreamEngine._stage_batches``)."""
from harness import program


def read(rd):
    return program.span_ms(rd, "ingest.stage")
