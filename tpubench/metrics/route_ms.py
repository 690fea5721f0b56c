"""Mean host milliseconds per chunk in ``StreamRouter.route`` (the
benchmark's span around the call)."""


def read(rd):
    times = rd.spans.get("router.route")
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
