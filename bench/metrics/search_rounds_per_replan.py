"""Bisection rounds per re-plan (``repro.core.api.SEARCH_COUNTS
["rounds"]``: one per ``attempt_many`` / ``attempt`` call).

The counter runs from process start, and the harness runs one cell per
process: set-up re-plans every pool member once, the window whole cycles
of the pool, and the traced stretch one more re-plan, whose rounds are
its ``repro.search.round`` spans.  Without those, the count over
(window re-plans + pool) is the window's mean.  This rests on the
``replan`` driver's set-up re-planning each pool member exactly once;
a driver that re-plans more there skews the reading."""
import sys

from bench.lib import spans


def read(r):
    counts = getattr(sys.modules.get("repro.core.api"), "SEARCH_COUNTS",
                     None)
    out = spans.traced_run(r)
    if counts is None or out is None or not r.get("replans"):
        return None
    traced = out["spans"].get(spans.SEARCH[0], {}).get("count", 0)
    return (counts["rounds"] - traced) / (r["replans"] + r["traffic"]["pool"])
