"""Device-idle time of the traced stretch under no ``repro.`` span, in %
of the stretch: the idle time that the program's spans leave
unexplained."""
from bench.lib import spans


def read(r):
    out = spans.traced_run(r)
    if out is None:
        return None
    return 100.0 * out["idle_unspanned_s"] / out["window_s"]
