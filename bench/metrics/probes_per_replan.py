"""Contention-engine probes per re-plan (``core.contention.EVAL_COUNTS
["probes"]`` over the window): the rho-hat estimates the search asked for."""


def read(r):
    if not r.get("replans"):
        return None
    return r["probes"] / r["replans"]
