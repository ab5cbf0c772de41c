"""Host time per step in the input pipeline (``launch.train.batch_at``,
the benchmark's span around it), in ms, over the window's steps."""


def read(r):
    spans = r.get("spans", {}).get("batch_at")
    if not spans or not r.get("batch_at_window"):
        return None
    first, end = r["batch_at_window"]
    return 1000.0 * sum(spans[first:end]) / (end - first)
