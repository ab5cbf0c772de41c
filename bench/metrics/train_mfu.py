"""Model FLOP/s utilization of the training window, in %: the forward
and backward FLOPs the model requires (counted from its shapes by
``bench.lib.flops``, no recomputation) times the steps, over chips x the
chip's bf16 peak x the window."""
from bench.lib.flops import vlm_train_flops
from bench.lib.peaks import peak


def read(r):
    if not r.get("steps"):
        return None
    work = vlm_train_flops(r["config"]["model"], r["global_batch"],
                           r["seq"]) * r["steps"]
    return 100.0 * work / (r["chips"] * peak(r["device_kind"], "bf16_flops")
                           * r["window_s"])
