"""The host spans of a traced run, by layer, on the device trace's clock.

The program opens ``repro.*`` spans at its layer boundaries
(``src/repro/spans.py``); the benchmark opens ``bench.*`` spans around each
call into the program.  This module reads both from a ``jax.profiler``
trace, with the host line (thread) each ran on, and reduces them:

* ``span_table``: per span name, how many spans overlap the window, their
  time inside it (``total_s``) and their self time (``self_s``): the
  time minus the union of the spans nested in it on the same line;
* ``idle_unspanned_s``: device-idle time in the window under no
  ``repro.`` span, i.e. idle time the program's spans do not explain;
* ``reduce``: ``bench.lib.trace.reduce_trace`` over both prefixes, so its
  idle gaps go to the innermost span of either, plus the two above.

The per-layer readers take a run's numbers from ``traced_run``, which
finds the trace the harness wrote for that run.  The names below are
spelled here and not imported from the program, so the readers also run
against a program that opens no such span: they then read None.

Two limits of finding the trace file instead of being handed it (the
harness's summary keeps no span table):

* ``traced_run`` looks under ``harness.OUT``, the ``.bench_out`` of the
  checkout that holds the harness module, while the harness writes under
  the ``.bench_out`` beside the ``BENCHMARK.json`` it runs.  The two are
  one directory for ``bench/run.py`` in a checkout; for a
  ``BENCHMARK.json`` elsewhere (the CPU tests' trees) the readers read
  None unless ``harness.OUT`` is pointed there.
* The trace is reduced here a second time, over both prefixes, so the
  result line's ``breakdown.idle_gaps`` (``bench.*`` spans only) and
  ``python3 -m bench.lib.spans`` can name different spans for one gap.

    python3 -m bench.lib.spans <trace log dir>

prints the reduction of the newest trace under a log directory as JSON.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import sys

from bench.lib import trace as tr

PREFIXES = ("bench.", "repro.")
PROGRAM = "repro."
TRACED, REPLAN = "bench.traced", "bench.replan"

#: The layers of a re-plan, by the program's span names, and whose time
#: each layer's metric reads.
SEARCH = ("repro.search.round",)
WALK = ("repro.columnar.place", "repro.columnar.score",
        "repro.columnar.apply")
KERNEL_HOST = ("repro.placement.pick_orders", "repro.placement.score_probes",
               "repro.placement.rank", "repro.placement.recheck")
ROUNDTRIP = ("repro.placement.launch", "repro.placement.fetch")


def load(path: str) -> dict:
    """``bench.lib.trace.load_events`` (host operations stand in for a
    device when the trace has none), with ``spans`` of both prefixes as
    ``(name, t0, t1, line)``."""
    from jax.profiler import ProfileData

    events = tr.load_events(path, cpu_ops=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans += [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9, (plane.name, i))
                      for e in line.events if e.name.startswith(PREFIXES)]
    spans.sort(key=lambda s: (s[1], -s[2]))
    return dict(events, spans=spans)


def span_table(spans, window: tuple[float, float]) -> dict:
    """``{name: {"count", "total_s", "self_s"}}`` over the spans that
    overlap ``window``, clipped to it.  A span's children are the spans
    nested in it on its own line."""
    t0, t1 = window
    table: dict[str, dict] = {}

    def close(entry):
        (name, a, b, _), children = entry
        inside = tr.clip([(a, b)], t0, t1)
        if not inside:
            return
        total = tr.length(inside)
        row = table.setdefault(name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += total
        row["self_s"] += total - tr.length(tr.union(
            tr.clip(children, t0, t1)))

    by_line = collections.defaultdict(list)
    for s in spans:
        by_line[s[3]].append(s)
    for line in by_line.values():
        stack: list = []
        for s in sorted(line, key=lambda s: (s[1], -s[2])):
            while stack and stack[-1][0][2] < s[2]:
                close(stack.pop())           # ended before s, or overlaps
            if stack:
                stack[-1][1].append((s[1], s[2]))
            stack.append((s, []))
        while stack:
            close(stack.pop())
    return table


def idle_unspanned_s(events: dict, window: tuple[float, float]) -> float:
    """Idle time of the first device inside ``window`` that no
    ``repro.`` span covers (the device ``reduce_trace`` attributes)."""
    t0, t1 = window
    first = events["devices"][sorted(events["devices"])[0]]
    gaps = tr.subtract([(t0, t1)],
                       tr.union(tr.clip([(a, b) for _, a, b in first],
                                        t0, t1)))
    covered = tr.union(tr.clip([(a, b) for n, a, b, _ in events["spans"]
                                if n.startswith(PROGRAM)], t0, t1))
    return tr.length(tr.subtract(gaps, covered))


def reduce(events: dict, window: tuple[float, float]) -> dict:
    """``reduce_trace`` with idle gaps put down to the innermost span of
    either prefix, plus ``spans``, ``idle_unspanned_s`` and the number of
    ``bench.replan`` spans in the window (``replans``)."""
    out = tr.reduce_trace(events, window)
    table = span_table(events["spans"], window)
    return dict(out, spans=table,
                idle_unspanned_s=idle_unspanned_s(events, window),
                replans=table.get(REPLAN, {}).get("count", 0))


def traced_window(events: dict) -> tuple[float, float]:
    """The interval of the ``bench.traced`` span, as
    ``bench.lib.trace.span_window`` gives it."""
    for name, a, b, _ in events["spans"]:
        if name == TRACED:
            return a, b
    raise ValueError(f"no host span {TRACED!r} in the trace")


_CACHE: dict = {}


def traced_run(r: dict) -> dict | None:
    """``reduce`` over the traced stretch of the run whose readings are
    ``r``: the newest trace under the harness's output directory, taken
    only if its ``bench.traced`` span is the one ``r["trace"]`` reduced.
    None without such a trace, without a traced re-plan, or where the
    program opened no ``repro.`` span."""
    from bench import harness

    if not r.get("trace"):
        return None
    paths = glob.glob(str(harness.OUT / "trace" / "*" / "plugins" /
                          "profile" / "*" / "*.xplane.pb"))
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        events = load(path)
        window = traced_window(events)
        _CACHE[key] = (window, reduce(events, window))
    (t0, t1), out = _CACHE[key]
    if t1 - t0 != r["trace"]["window_s"] or not out["replans"] or \
            not any(n.startswith(PROGRAM) for n in out["spans"]):
        return None
    return out


def per_replan_ms(r: dict, names, kind: str) -> float | None:
    """Sum of ``kind`` (``"self_s"`` or ``"total_s"``) over the spans
    ``names`` in the traced stretch, in ms per traced re-plan."""
    out = traced_run(r)
    if out is None:
        return None
    s = sum(out["spans"].get(n, {}).get(kind, 0.0) for n in names)
    return 1000.0 * s / out["replans"]


def main(argv=None) -> int:
    log_dir, = sys.argv[1:] if argv is None else argv
    events = load(tr.find_xplane(log_dir))
    out = reduce(events, traced_window(events))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
