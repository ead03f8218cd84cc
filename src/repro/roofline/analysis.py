"""Roofline terms from a compiled (dry-run) executable.

  compute    = HLO_FLOPs_global   / (chips * peak_FLOP/s)
  memory     = HLO_bytes_global   / (chips * HBM_bw)
  collective = collective_bytes_global / (chips * ICI_link_bw)

`compiled.cost_analysis()` reports the PER-DEVICE partitioned program, so we
multiply by the device count to get globals (the spec formula then divides
by chips again — i.e. the terms are per-chip seconds, which is what a
balanced SPMD program takes).  Collective bytes are not in cost_analysis;
we parse the optimized HLO and sum operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute (skipping
`-done` halves of async pairs so nothing is double-counted).
"""

from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional

from repro.roofline.hw import HwSpec

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_BRACKET_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """`compiled.cost_analysis()` normalized across jax versions: newer
    backends return a per-device LIST of property dicts (possibly empty),
    older ones a single dict.  Always returns a dict."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_BRACKET_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 2  # unknown -> conservative


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Per-device ICI link bytes of each collective kind in the module.

    Optimized-HLO `as_text()` prints operands as bare %names, so we work
    from the RESULT shape plus the replica-group size S, with the standard
    ring-algorithm serialization volumes per participating device:

        all-gather:          (S-1)/S * result_bytes
        reduce-scatter:      (S-1)   * result_bytes   (input = S * result)
        all-reduce:          2(S-1)/S * result_bytes
        all-to-all:          (S-1)/S * result_bytes
        collective-permute:  result_bytes

    `-done` halves of async pairs are skipped (the `-start` carries the
    shape), so nothing is double-counted.
    """
    totals: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s+(\(?[a-z0-9].*?)\s+([a-z0-9-]+)\(", stripped)
        if not m:
            continue
        op = m.group(2)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op == c + "-start":
                kind = c
                break
        if kind is None:
            continue
        result = m.group(1)
        size = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(result))
        s = _group_size(stripped)
        if kind == "all-gather":
            vol = size * (s - 1) / s
        elif kind == "reduce-scatter":
            vol = size * (s - 1)
        elif kind == "all-reduce":
            vol = size * 2 * (s - 1) / s
        elif kind == "all-to-all":
            vol = size * (s - 1) / s
        else:  # collective-permute
            vol = size
        totals[kind] += vol
    return {k: int(v) for k, v in totals.items()}


# --------------------------------------------------------------------------
# Loop-aware collective accounting.
#
# jax.lax.scan lowers to an HLO while loop, and XLA's cost/byte analyses (and
# a naive text scan) count the body ONCE instead of trip_count times.  We
# parse the module's computation graph, recover each while's trip count from
# the constant in its condition computation, and multiply every collective
# found inside a body by the product of enclosing trip counts.
# --------------------------------------------------------------------------

_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s+\(.*\)\s*->.*\{$")
_WHILE_RE = re.compile(r"while\(.*?\),\s*condition=%([\w.\-]+),\s*body=%([\w.\-]+)")
_CALL_RE = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    current = None
    entry = None
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _COMP_HEADER_RE.match(s)
        if m and s.endswith("{"):
            current = m.group(1)
            comps[current] = []
            if s.startswith("ENTRY"):
                entry = current
            continue
        if s == "}":
            current = None
            continue
        if current is not None:
            comps[current].append(s)
    if entry is not None:
        comps["__entry__"] = comps[entry]
    return comps


def _trip_count(cond_lines: List[str]) -> int:
    consts = [int(c) for ln in cond_lines for c in _CONST_RE.findall(ln)]
    consts = [c for c in consts if c > 0]
    return max(consts) if consts else 1


def _line_collective_bytes(stripped: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    m = re.search(r"=\s+(\(?[a-z0-9].*?)\s+([a-z0-9-]+)\(", stripped)
    if not m:
        return out
    op = m.group(2)
    kind = None
    for c in _COLLECTIVES:
        if op == c or op == c + "-start":
            kind = c
            break
    if kind is None:
        return out
    size = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(m.group(1)))
    s = _group_size(stripped)
    if kind == "all-gather":
        vol = size * (s - 1) / s
    elif kind == "reduce-scatter":
        vol = size * (s - 1)
    elif kind == "all-reduce":
        vol = size * 2 * (s - 1) / s
    elif kind == "all-to-all":
        vol = size * (s - 1) / s
    else:
        vol = size
    out[kind] = vol
    return out


def collective_bytes_loop_aware(hlo_text: str) -> Dict[str, int]:
    """Per-device link bytes with while-loop trip counts applied."""
    comps = _split_computations(hlo_text)
    if "__entry__" not in comps:
        return collective_bytes_from_hlo(hlo_text)

    # whiles per computation: (cond, body)
    whiles: Dict[str, List] = {}
    calls: Dict[str, List[str]] = {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        whiles[name] = []
        calls[name] = []
        for ln in lines:
            for cond, body in _WHILE_RE.findall(ln):
                whiles[name].append((cond, body))
            calls[name].extend(_CALL_RE.findall(ln))

    entry_lines = comps["__entry__"]
    entry_name = None
    for name, lines in comps.items():
        if name != "__entry__" and lines is entry_lines:
            entry_name = name
            break

    mult: Dict[str, float] = {entry_name: 1.0}
    import collections as _c

    queue = _c.deque([entry_name])
    seen = set()
    while queue:
        cur = queue.popleft()
        if cur in seen:
            continue
        seen.add(cur)
        base = mult.get(cur, 1.0)
        for cond, body in whiles.get(cur, []):
            tc = _trip_count(comps.get(cond, []))
            mult[body] = max(mult.get(body, 0.0), base * tc)
            mult[cond] = max(mult.get(cond, 0.0), base * tc)
            queue.append(body)
            queue.append(cond)
        for callee in calls.get(cur, []):
            if callee in comps:
                mult[callee] = max(mult.get(callee, 0.0), base)
                queue.append(callee)

    totals: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        m = mult.get(name, 1.0 if name == entry_name else 0.0)
        if m <= 0:
            continue
        for ln in lines:
            for kind, vol in _line_collective_bytes(ln).items():
                totals[kind] += vol * m
    return {k: int(v) for k, v in totals.items()}


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_global: float
    bytes_global: float
    collective_bytes_global: float
    collective_breakdown: Dict[str, int]
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    raw_hlo_flops_per_device: float = 0.0  # cost_analysis verbatim (loop
    raw_hlo_bytes_per_device: float = 0.0  # bodies counted once — see model.py)
    model_flops: float = 0.0
    usefulness: float = 0.0  # MODEL_FLOPS / HLO_FLOPs
    peak_memory_per_device: float = 0.0
    note: str = ""
    variant: str = "baseline"

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def roofline_fraction(self) -> float:
        """How much of the bound time is the useful-compute time."""
        t_useful = self.model_flops / max(self.flops_global, 1.0) * self.t_compute
        return t_useful / max(self.bound_time, 1e-30)

    def to_json(self) -> str:
        return json.dumps(asdict(self))


def roofline_from_compiled(
    compiled,
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    n_devices: int,
    hw: HwSpec,
    model_flops: float = 0.0,
    hlo_text: Optional[str] = None,
    note: str = "",
    variant: str = "baseline",
    analytic_flops: Optional[float] = None,
    analytic_bytes: Optional[float] = None,
) -> RooflineReport:
    cost = cost_analysis_dict(compiled)
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes_loop_aware(text)
    coll_dev = float(sum(coll.values()))

    # cost_analysis counts while bodies once; prefer the validated analytic
    # model when supplied (see roofline/model.py + tests/test_roofline.py).
    flops_g = analytic_flops if analytic_flops else flops_dev * n_devices
    bytes_g = analytic_bytes if analytic_bytes else bytes_dev * n_devices
    coll_g = coll_dev * n_devices

    t_compute = flops_g / (n_devices * hw.peak_flops_bf16)
    t_memory = bytes_g / (n_devices * hw.hbm_bw)
    t_collective = coll_g / (n_devices * hw.ici_link_bw)

    dominant = max(
        (("compute", t_compute), ("memory", t_memory), ("collective", t_collective)),
        key=lambda kv: kv[1],
    )[0]

    peak_mem = 0.0
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            peak_mem = float(
                getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                - getattr(ma, "alias_size_in_bytes", 0)
            )
    except Exception:
        pass

    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        n_devices=n_devices,
        flops_global=flops_g,
        bytes_global=bytes_g,
        raw_hlo_flops_per_device=flops_dev,
        raw_hlo_bytes_per_device=bytes_dev,
        collective_bytes_global=coll_g,
        collective_breakdown=coll,
        t_compute=t_compute,
        t_memory=t_memory,
        t_collective=t_collective,
        dominant=dominant,
        model_flops=model_flops,
        usefulness=(model_flops / flops_g) if flops_g else 0.0,
        peak_memory_per_device=peak_mem,
        note=note,
        variant=variant,
    )
