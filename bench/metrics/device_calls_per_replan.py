"""Device round trips per re-plan (``kernels.placement.DISPATCH_COUNTS
["device"]`` over the window): one per columnar step that dispatched."""


def read(r):
    if not r.get("replans") or not r.get("dispatch_device"):
        return None
    return r["dispatch_device"] / r["replans"]
