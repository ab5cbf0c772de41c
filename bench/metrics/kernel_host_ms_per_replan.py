"""Host time of the device programs per traced re-plan, in ms: self time
of ``repro.placement.{pick_orders,score_probes,rank,recheck}`` (operand
preparation and padding, the NumPy stable rankings, the float64 re-check
of screened rows), without the launch and fetch nested in them."""
from bench.lib import spans


def read(r):
    return spans.per_replan_ms(r, spans.KERNEL_HOST, "self_s")
