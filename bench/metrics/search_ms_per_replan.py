"""Self time of the policy search per traced re-plan, in ms: the
``repro.search.round`` spans (one bisection round each, opened by
core.api.bisect_theta around SJF-BCO's attempt) less the spans nested
in them, i.e. engine construction, freezing each branch's schedule and
the best-kappa choice."""
from bench.lib import spans


def read(r):
    return spans.per_replan_ms(r, spans.SEARCH, "self_s")
