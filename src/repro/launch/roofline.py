"""Roofline-term extraction from compiled XLA artifacts.

Three terms per (arch, shape, mesh), in seconds, from the peak rates of
the target chip (``repro.launch.mesh.peak_rates``, keyed by device kind;
v5e: 197e12 FLOP/s, 819e9 B/s HBM, 50e9 B/s per ICI link):

  compute    = HLO_FLOPs            / (chips * peak FLOP/s)
  memory     = HLO_bytes            / (chips * HBM bytes/s)
  collective = collective_bytes     / (chips * ICI bytes/s)

FLOPs/bytes come from ``compiled.cost_analysis()``.  Collective bytes are
not in cost_analysis: we parse the post-SPMD HLO text and sum the operand
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.  (Sizes in the partitioned module are already
per-participant, so the sum is the per-device traffic injected onto the
fabric; DCN-crossing ops are attributed by replica-group span when the
mesh has a pod axis.)
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from repro.launch.mesh import peak_rates

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g. "f32[16,128]{1,0} all-reduce(" — capture the *output* shape of the op
_OP_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\b(" + "|".join(_COLLECTIVES) +
    r")(-start|-done)?\(")


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, float]
    count_by_kind: dict[str, int]     # dynamic counts (loop-expanded)
    dcn_bytes: float = 0.0            # pod-crossing share (multi-pod mesh)

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))

    @property
    def ici_bytes(self) -> float:
        return self.total_bytes - self.dcn_bytes

    @property
    def total_count(self) -> int:
        return int(sum(self.count_by_kind.values()))


# replica_groups=[16,32]<=[2,16,16]T(1,0,2)  (iota format)  or  {{0,1},{2,3}}
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_RG_LIST_RE = re.compile(r"replica_groups=\{\{([\d,{} ]+)\}\}")


def _crosses_pod(line: str, pod_size: int) -> bool:
    """Does this collective's replica grouping mix devices from different
    pods?  Pod p owns ids [p*pod_size, (p+1)*pod_size).  This is the TPU
    analogue of the paper's inter-server (b^e) vs intra-server (b^i) link
    distinction: pod-crossing collectives ride DCN."""
    m = _RG_IOTA_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims)))
        arr = ids.reshape(dims)
        if m.group(4):
            arr = arr.transpose([int(t) for t in m.group(4).split(",")])
        groups = arr.reshape(g, s)
        pods = groups // pod_size
        return bool((pods != pods[:, :1]).any())
    m = _RG_LIST_RE.search(line)
    if m:
        for grp in m.group(1).split("},{"):
            ids = [int(x) for x in grp.replace("{", "").replace("}", "")
                   .split(",") if x.strip()]
            if ids and len({i // pod_size for i in ids}) > 1:
                return True
    return False


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s+\(.*\)\s*->.*\{\s*$")
# the while operand may be a bare name or carry the full printed tuple
# type (XLA version dependent) — match non-greedily up to "), condition="
_WHILE_RE = re.compile(
    r"while\(.*?\),\s*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_KNOWN_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> dict[str, str]:
    """computation name -> body text (top-level '{...}' blocks)."""
    comps: dict[str, str] = {}
    lines = hlo_text.splitlines()
    name, buf, entry = None, [], None
    for ln in lines:
        m = _COMP_RE.match(ln)
        if m and not ln.startswith(" "):
            name = m.group(1)
            if ln.startswith("ENTRY"):
                entry = name
            buf = []
            continue
        if name is not None:
            if ln.startswith("}"):
                comps[name] = "\n".join(buf)
                name = None
            else:
                buf.append(ln)
    if entry is not None:
        comps["__entry__"] = comps.get(entry, "")
        comps["__entry_name__"] = entry
    return comps


def _trip_count(cond_text: str) -> int:
    """Heuristic scan trip count: the largest int constant in the loop
    condition (the compare bound)."""
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    return max(consts) if consts else 1


def _multipliers(comps: dict[str, str]) -> dict[str, int]:
    """Dynamic execution multiplier per computation (loop nesting aware)."""
    entry = comps.get("__entry_name__")
    mult: dict[str, int] = {entry: 1} if entry else {}
    frontier = [entry] if entry else []
    seen = set(frontier)
    while frontier:
        cur = frontier.pop()
        body = comps.get(cur, "")
        m_cur = mult.get(cur, 1)
        for wm in _WHILE_RE.finditer(body):
            cond, wbody = wm.group(1), wm.group(2)
            # prefer XLA's own annotation; fall back to the cond heuristic
            line_end = body.find("\n", wm.end())
            tm = _KNOWN_TRIP_RE.search(
                body[wm.end(): line_end if line_end != -1 else len(body)])
            trip = int(tm.group(1)) if tm else _trip_count(comps.get(cond, ""))
            for child in (cond, wbody):
                mult[child] = max(mult.get(child, 0), m_cur * trip)
                if child not in seen:
                    seen.add(child)
                    frontier.append(child)
        for cm in _CALL_RE.finditer(body):
            child = cm.group(1)
            mult[child] = max(mult.get(child, 0), m_cur)
            if child not in seen:
                seen.add(child)
                frontier.append(child)
    return mult


_SHAPE_RE = re.compile(r"%([\w\.\-]+)\s*=\s*([a-z0-9]+)\[([\d,]*)\]")
# optional "f32[64,64]{1,0} " operand-type prefix: some XLA versions print
# typed operands ("dot(f32[..] %a, ..)"), others bare names ("dot(%a, ..)")
_TYPE_PREFIX = r"(?:[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?\s+)?"
_DOT_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\bdot\(" + _TYPE_PREFIX +
    r"%([\w\.\-]+),")
_OPND_RE = re.compile(r"[(,]\s*" + _TYPE_PREFIX + r"%([\w\.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]+)\}")


def _dims(s: str) -> list[int]:
    return [int(d) for d in s.split(",")] if s else []


_GTE_RE = re.compile(
    r"%([\w\.\-]+)\s*=\s*[^=]*get-tuple-element\((?:\([^)]*\)\s*)?"
    r"%([\w\.\-]+)\),\s*index=(\d+)")
_ROOT_TUPLE_RE = re.compile(r"ROOT\s+%[\w\.\-]+\s*=\s*\([^=]*tuple\(([^)]*)\)")


def _invariant_names(body: str) -> set[str]:
    """Names of loop-INVARIANT values in a while body: get-tuple-elements of
    the loop parameter that are passed through unchanged to the root tuple.
    These are weights/closures — assumed fabric/VMEM-resident across
    iterations, so their operand bytes are charged once, not per trip.
    (A scanned layer stack is still charged correctly: the per-iteration
    dynamic-slice output IS counted; only the full stacked array is not.)"""
    gtes: dict[int, str] = {}
    for m in _GTE_RE.finditer(body):
        gtes[int(m.group(3))] = m.group(1)
    rm = _ROOT_TUPLE_RE.search(body)
    if not rm:
        return set()
    # operands may be typed ("f32[8,8]{1,0} %w") or bare ("%w")
    operands = [o.strip().split()[-1].lstrip("%")
                for o in rm.group(1).split(",") if o.strip()]
    inv = set()
    for idx, name in gtes.items():
        if idx < len(operands) and operands[idx] == name:
            inv.add(name)
    return inv


def loop_cost_correction(hlo_text: str) -> tuple[float, float]:
    """(extra_flops, extra_bytes): XLA's cost_analysis counts a while body
    exactly ONCE (verified empirically), so a 126-layer scanned stack is
    undercounted 126x.  We re-count dot FLOPs (2 * |out| * contraction) and
    op bytes (outputs + resolvable operands of top-level ops, matching
    cost_analysis's fusion-boundary accounting) inside loop computations and
    add (trip - 1) copies.  Loop-invariant operands are charged once."""
    comps = _split_computations(hlo_text)
    mult = _multipliers(comps)
    comps.pop("__entry_name__", None)
    comps.pop("__entry__", None)
    # computations entered via calls= (fusions): count their dots for flops,
    # but exclude them from bytes (cost_analysis charges fusion boundaries).
    called = set()
    for body in comps.values():
        called.update(_CALL_RE.findall(body))

    extra_flops = 0.0
    extra_bytes = 0.0
    for name, body in comps.items():
        m = mult.get(name, 1)
        if m <= 1:
            continue
        shapes = {nm: (dt, _dims(dd))
                  for nm, dt, dd in _SHAPE_RE.findall(body)}
        invariant = _invariant_names(body)
        for line in body.splitlines():
            dm = _DOT_RE.search(line)
            if dm:
                out_dt, out_dims, lhs_name = dm.group(1), dm.group(2), dm.group(3)
                out_n = 1
                for d in _dims(out_dims):
                    out_n *= d
                contract = 1
                cm = _LHS_CONTRACT_RE.search(line)
                if cm and lhs_name in shapes:
                    lhs_dims = shapes[lhs_name][1]
                    for ci in _dims(cm.group(1)):
                        if ci < len(lhs_dims):
                            contract *= lhs_dims[ci]
                extra_flops += (m - 1) * 2.0 * out_n * contract
            if name not in called:
                sm = _SHAPE_RE.search(line)
                if sm and "parameter(" not in line and " = (" not in line:
                    dt, dd = sm.group(2), _dims(sm.group(3))
                    if dt in _DTYPE_BYTES:
                        n = 1
                        for d in dd:
                            n *= d
                        out_b = n * _DTYPE_BYTES[dt]
                        if "dynamic-update-slice" in line:
                            # in-place slice write: charge the update slice,
                            # not the whole buffer (operands also skipped)
                            upd = _OPND_RE.findall(line)
                            out_b = 0
                            if len(upd) >= 2 and upd[1] in shapes:
                                udt, udd = shapes[upd[1]]
                                un = 1
                                for d in udd:
                                    un *= d
                                out_b = 2 * un * _DTYPE_BYTES.get(udt, 4)
                            extra_bytes += (m - 1) * out_b
                            continue
                        opnd_b = 0
                        is_fusion = "fusion(" in line
                        for opname in _OPND_RE.findall(line):
                            if opname in invariant:
                                continue
                            if opname in shapes:
                                odt, odd = shapes[opname]
                                if odt in _DTYPE_BYTES:
                                    on = 1
                                    for d in odd:
                                        on *= d
                                    ob = on * _DTYPE_BYTES[odt]
                                    if is_fusion:
                                        # fused kernels read ~output-sized
                                        # windows of big (sliced) buffers
                                        ob = min(ob, out_b)
                                    opnd_b += ob
                        extra_bytes += (m - 1) * (out_b + opnd_b)
    return extra_flops, extra_bytes


def bytes_breakdown(hlo_text: str, top: int = 15) -> list[dict]:
    """Largest loop-expanded HBM-traffic contributors (the §Perf profiling
    view for memory-bound pairs)."""
    comps = _split_computations(hlo_text)
    mult = _multipliers(comps)
    comps.pop("__entry_name__", None)
    comps.pop("__entry__", None)
    called = set()
    for body in comps.values():
        called.update(_CALL_RE.findall(body))
    rows = []
    for name, body in comps.items():
        m = mult.get(name, 1)
        if m <= 1 or name in called:
            continue
        shapes = {nm: (dt, _dims(dd))
                  for nm, dt, dd in _SHAPE_RE.findall(body)}
        invariant = _invariant_names(body)
        for line in body.splitlines():
            sm = _SHAPE_RE.search(line)
            if not sm or "parameter(" in line or " = (" in line:
                continue
            dt, dd = sm.group(2), _dims(sm.group(3))
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dd:
                n *= d
            total = n * _DTYPE_BYTES[dt]
            if "dynamic-update-slice" in line:
                upd = _OPND_RE.findall(line)
                total = 0
                if len(upd) >= 2 and upd[1] in shapes:
                    udt, udd = shapes[upd[1]]
                    un = 1
                    for d in udd:
                        un *= d
                    total = 2 * un * _DTYPE_BYTES.get(udt, 4)
                rows.append({"comp": name, "op": sm.group(1), "mult": m,
                             "bytes": total * (m - 1),
                             "line": line.strip()[:110]})
                continue
            out_b0 = total
            is_fusion = "fusion(" in line
            for opname in _OPND_RE.findall(line):
                if opname in invariant or opname not in shapes:
                    continue
                odt, odd = shapes[opname]
                if odt in _DTYPE_BYTES:
                    on = 1
                    for d in odd:
                        on *= d
                    ob = on * _DTYPE_BYTES[odt]
                    if is_fusion:
                        ob = min(ob, out_b0)
                    total += ob
            rows.append({"comp": name, "op": sm.group(1), "mult": m,
                         "bytes": total * (m - 1),
                         "line": line.strip()[:110]})
    rows.sort(key=lambda r: -r["bytes"])
    return rows[:top]


def collective_breakdown(hlo_text: str, top: int = 12) -> list[dict]:
    """Per-op-line collective contributions (loop-expanded), largest first.
    The §Perf profiling view: 'which collective, in which loop, costs what'."""
    comps = _split_computations(hlo_text)
    mult = _multipliers(comps)
    comps.pop("__entry_name__", None)
    comps.pop("__entry__", None)
    out = []
    for name, body in comps.items():
        m = mult.get(name, 1)
        for om in _OP_RE.finditer(body):
            dtype, dims, kind, suffix = (om.group(1), om.group(2),
                                         om.group(3), om.group(4))
            if suffix == "-done" or dtype not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            line_start = body.rfind("\n", 0, om.start()) + 1
            opname = body[line_start:om.start()].strip().split(" ")[0]
            out.append({"comp": name, "op": opname, "kind": kind,
                        "shape": f"{dtype}[{dims}]", "mult": m,
                        "bytes": n * _DTYPE_BYTES[dtype] * m})
    out.sort(key=lambda r: -r["bytes"])
    return out[:top]


def parse_collectives(hlo_text: str, pod_size: int = 0) -> CollectiveStats:
    """Sum collective operand bytes, expanding while-loop trip counts so a
    collective inside the scanned layer stack counts once per layer.
    With ``pod_size`` > 0 (multi-pod mesh), pod-crossing collectives are
    tallied separately as DCN traffic — the paper's b^e vs b^i split."""
    comps = _split_computations(hlo_text)
    mult = _multipliers(comps)
    entry = comps.pop("__entry_name__", None)
    comps.pop("__entry__", None)
    bytes_by: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    count_by: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    dcn = 0.0
    for name, body in comps.items():
        m = mult.get(name, 1 if name == entry else 0)
        if m == 0:
            m = 1  # unreferenced computation (conservative)
        for om in _OP_RE.finditer(body):
            dtype, dims, kind, suffix = (om.group(1), om.group(2),
                                         om.group(3), om.group(4))
            if suffix == "-done" or dtype not in _DTYPE_BYTES:
                continue  # count async pairs once (at -start)
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            b = n * _DTYPE_BYTES[dtype] * m
            bytes_by[kind] += b
            count_by[kind] += m
            if pod_size:
                line_start = body.rfind("\n", 0, om.start()) + 1
                line_end = body.find("\n", om.end())
                line = body[line_start:line_end if line_end > 0 else None]
                if _crosses_pod(line, pod_size):
                    dcn += b
    return CollectiveStats(bytes_by, count_by, dcn_bytes=dcn)


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float              # PER-DEVICE FLOPs (XLA cost_analysis runs
                                  # on the partitioned module; == global/chips)
    hlo_bytes: float              # per-device HBM traffic
    collective_bytes: float       # per-device fabric traffic
    collectives: CollectiveStats
    model_flops: float            # 6*N*D (or 6*N_active*D) per step, GLOBAL
    per_device_hbm_peak: float    # from memory_analysis, bytes
    device_kind: str              # the target chip (keys its peak rates)

    @property
    def t_compute(self) -> float:
        # == global_FLOPs / (chips * peak): cost_analysis is already /chip
        return self.hlo_flops / peak_rates(self.device_kind)["flops_bf16"]

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / peak_rates(self.device_kind)["hbm_bw"]

    @property
    def t_collective(self) -> float:
        peaks = peak_rates(self.device_kind)
        dcn = self.collectives.dcn_bytes if self.collectives else 0.0
        ici = self.collective_bytes - dcn
        return ici / peaks["ici_bw"] + dcn / peaks["dcn_bw"]

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste detector."""
        return self.model_flops / max(self.hlo_flops * self.chips, 1.0)

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_flops_ratio,
            "hbm_peak_bytes": self.per_device_hbm_peak,
        }


def cost_terms(compiled) -> tuple[float, float]:
    """(flops, bytes) from compiled.cost_analysis(); tolerant of missing
    keys on some backends."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    return flops, byts


def memory_peak(compiled) -> float:
    """Per-device HBM requirement: live args + outputs + temporaries."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return 0.0
    total = (getattr(ma, "argument_size_in_bytes", 0)
             + getattr(ma, "output_size_in_bytes", 0)
             + getattr(ma, "temp_size_in_bytes", 0)
             - getattr(ma, "alias_size_in_bytes", 0))
    return float(max(total, getattr(ma, "peak_memory_in_bytes", 0)))


def model_step_flops(cfg, shape) -> float:
    """6*N*D for a train step (fwd 2ND + bwd 4ND); 2*N*D for pure forward
    (prefill); 2*N_active per generated token for decode."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch      # decode: one token each
