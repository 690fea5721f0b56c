"""Share of the routed batches' slots that hold a document: the rest is
padding to each bucket's power-of-two width."""


def read(rd):
    slots = rd.counters.get("route_slots", 0.0)
    if not slots:
        return None
    return 100.0 * rd.counters.get("route_useful", 0.0) / slots
