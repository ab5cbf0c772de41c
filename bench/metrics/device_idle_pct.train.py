"""Idle share of the devices over the traced training steps, in %:
1 - busy / window, averaged over the chips (trace)."""


def read(r):
    t = r.get("trace")
    if not t or not r.get("steps"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
