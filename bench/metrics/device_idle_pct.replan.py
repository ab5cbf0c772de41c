"""Idle share of the device over the traced re-plan, in %: 1 - busy /
window, busy being the union of the device's operations (trace)."""


def read(r):
    t = r.get("trace")
    if not t or not r.get("replans"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
