"""Share of the rows dispatched to the device programs that were
re-decided in float64 on the host (screened near a threshold), in %."""


def read(r):
    if not r.get("dispatch_rows"):
        return None
    return 100.0 * r["dispatch_rechecked"] / r["dispatch_rows"]
