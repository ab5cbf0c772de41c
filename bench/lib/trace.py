"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the numbers
the benchmark reports.

* Device operations are the events of each device plane's ``XLA Ops``
  line (a TPU's ``/device:TPU:<n>`` planes).  A trace recorded on the CPU
  backend has no device plane; ``cpu_ops=True`` takes the host events
  that carry an ``hlo_op`` stat instead (used to test this code).
* Host spans are the benchmark's own ``TraceAnnotation`` events, whose
  names start with ``bench.``.
* Busy time of a device is the union of its operations' intervals inside
  the window; the idle share is 1 - busy / window.
* A collective is an operation whose name holds one of ``COLLECTIVES``.
  An asynchronous pair (``...-start`` then ``...-done``) counts from the
  start's beginning to the done's end.  Its exposed time is the part of
  the collectives' union during which no other operation runs on that
  device.
* Idle gaps on the first device are attributed to the innermost host span
  that covers each gap's midpoint.
"""
from __future__ import annotations

import collections
import glob
import os

OP_LINES = ("XLA Ops",)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "ppermute", "psum")
SPAN_PREFIX = "bench."


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_events(path: str, cpu_ops: bool = False) -> dict:
    """``{"devices": {plane: [(name, t0, t1)]}, "spans": [(name, t0, t1)]}``
    with times in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    devices: dict[str, list] = {}
    spans: list = []
    host_ops: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops += [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(iv)
                    elif cpu_ops and e.duration_ns > 0 and any(
                            k == "hlo_op" for k, _ in e.stats):
                        host_ops.append(iv)
    devices = {k: v for k, v in devices.items() if v}
    if cpu_ops and not devices and host_ops:
        devices["/host:CPU"] = host_ops
    for ops in devices.values():
        ops.sort(key=lambda e: e[1])
    spans.sort(key=lambda e: e[1])
    return {"devices": devices, "spans": spans}


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint union of (t0, t1) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base, cut) -> list[tuple[float, float]]:
    """The parts of disjoint ``base`` not covered by disjoint ``cut``."""
    out, j = [], 0
    for a, b in base:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def clip(intervals, t0: float, t1: float):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def collective_intervals(ops) -> list[tuple[float, float]]:
    """Collective intervals of one device; an async ``-start``/``-done``
    pair spans from the start's beginning to the done's end."""
    out, open_starts = [], collections.defaultdict(collections.deque)
    for name, a, b in ops:
        if not is_collective(name):
            continue
        base = name.split(".")[0]
        if base.endswith("-start"):
            open_starts[base[:-len("-start")]].append(a)
        elif base.endswith("-done") and open_starts[base[:-len("-done")]]:
            out.append((open_starts[base[:-len("-done")]].popleft(), b))
        else:
            out.append((a, b))
    return out


def reduce_trace(events: dict, window: tuple[float, float],
                 top: int = 10) -> dict:
    """Busy, idle, per-operation and collective time inside ``window``
    (seconds on the trace's clock), averaged over the devices."""
    t0, t1 = window
    devs = sorted(events["devices"])
    if not devs:
        raise ValueError("the trace holds no device operation")
    busy = coll = exposed = 0.0
    by_op: dict[str, float] = collections.defaultdict(float)
    first_busy = None
    for dev in devs:
        ops = events["devices"][dev]
        all_u = union(clip([(a, b) for _, a, b in ops], t0, t1))
        busy += length(all_u)
        for name, a, b in ops:
            for x, y in clip([(a, b)], t0, t1):
                by_op[name] += (y - x) / len(devs)
        c_u = union(clip(collective_intervals(ops), t0, t1))
        comp_u = union(clip([(a, b) for n, a, b in ops
                             if not is_collective(n)], t0, t1))
        coll += length(c_u)
        exposed += length(subtract(c_u, comp_u))
        if first_busy is None:
            first_busy = all_u
    n = len(devs)
    gaps = subtract([(t0, t1)], first_busy)
    by_gap: dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [s for s in events["spans"] if s[1] <= mid <= s[2]]
        name = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
            else "(no benchmark span)"
        by_gap[name] += b - a
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": t1 - t0, "busy_s": busy / n, "devices": n,
            "collective_s": coll / n, "collective_exposed_s": exposed / n,
            "device_ops": [[k, v] for k, v in rank(by_op)],
            "idle_gaps": [[k, v] for k, v in rank(by_gap)]}


def span_window(events: dict, name: str) -> tuple[float, float]:
    """The interval of the host span ``name`` (the traced stretch)."""
    for s, a, b in events["spans"]:
        if s == name:
            return a, b
    raise ValueError(f"no host span {name!r} in the trace")
