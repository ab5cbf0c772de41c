"""Host-device round trips per traced re-plan, in ms: the
``repro.placement.launch`` spans (the jit call on NumPy operands:
host-to-device copies and enqueue) plus the ``repro.placement.fetch``
spans (``np.asarray`` of the outputs: the wait for the device and the
device-to-host copies)."""
from bench.lib import spans


def read(r):
    return spans.per_replan_ms(r, spans.ROUNDTRIP, "total_s")
