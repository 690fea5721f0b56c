"""Host milliseconds per chunk in ``FleetMeter.record_update`` (the
benchmark's span around each call, summed over the chunk's buckets)."""


def read(rd):
    times = rd.spans.get("meter.record_update")
    if not times or not rd.units:
        return None
    return 1e3 * sum(times) / rd.units
