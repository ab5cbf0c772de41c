"""Self time of the columnar placement engine's host walk per traced
re-plan, in ms: the ``repro.columnar.{place,score,apply}`` spans less the
device programs' spans nested in them."""
from bench.lib import spans


def read(r):
    return spans.per_replan_ms(r, spans.WALK, "self_s")
