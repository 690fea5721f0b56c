"""Host milliseconds per chunk in the program's ``ingest.wait`` span:
waiting for the fleet step's outputs (``jax.block_until_ready``), the
step's device time that the host did not overlap with other work."""
from harness import program


def read(rd):
    return program.span_ms(rd, "ingest.wait")
