"""Read the program's own spans: the per-name tallies that
``repro.obs.timers.span`` keeps in the process (``timers.recent``), which
the readers reach because a run drives the program in its own process.

Every span read here opens once per unit of its cell: the ``ingest.*``
spans once per chunk (``StreamEngine``), the ``plan.*`` spans once per
request of a fleet of one tier depth (``planner.plan_fleet_mixed``). No
span of those names opens after the window, so the newest ``units``
records are the window's. A program without these spans (an older one)
gives nothing to read."""
from __future__ import annotations

from typing import Optional


def span_ms(rd, name: str) -> Optional[float]:
    """Mean host ms per unit of the program span ``name`` over the
    window's ``rd.units`` units, or None where the program has none."""
    if not rd.units:
        return None
    try:
        from repro.obs import timers
    except ImportError:
        return None
    recent = getattr(timers, "recent", None)
    durs = recent(name, rd.units) if recent is not None else []
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
