"""Host milliseconds per chunk in the program's ``ingest.meter`` span:
the host meter's ``FleetMeter.record_update`` calls."""
from harness import program


def read(rd):
    return program.span_ms(rd, "ingest.meter")
