"""Roofline-grade analysis of compiled (post-SPMD) HLO text.

Why this exists: XLA's ``compiled.cost_analysis()`` counts a ``while`` body
exactly once, so any scan-over-layers model (the only way to keep 95-layer
HLO compact) under-reports flops/bytes by ~the layer count.  This module
re-derives per-device totals from ``compiled.as_text()``:

  * computations + per-op result shapes are parsed line-by-line;
  * a call graph (fusion `calls=`, while `body=/condition=`, `to_apply=`,
    conditional branches) assigns each computation a multiplier;
  * while trip counts come from ``trip_scope`` markers ("<tag>_trip<N>") that
    the model code embeds in op metadata, with a fallback to the constant in
    the loop condition;
  * flops: 2 * prod(result dims) * prod(contracting dims) for every dot;
  * bytes: operand + result sizes of every op at fusion boundaries
    (reads + writes ~= HBM traffic);
  * collective bytes: operand sizes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, with ring-adjusted
    wire bytes reported alongside the raw spec-mandated sum.

Validated against cost_analysis() on unrolled models in
tests/test_hlo_analysis.py.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict


def xla_cost_analysis(compiled) -> dict:
    """Version-tolerant ``compiled.cost_analysis()``: newer jax returns one
    properties dict, older versions a one-element list of dicts (and some
    builds return None for empty programs).  Always returns a dict."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e3m4": 1, "f8e8m0fnu": 1, "token": 0, "opaque": 0,
}

COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s+=\s+(.+?)\s+([a-z][a-z0-9\-]*)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.+\s+\{")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_TRIP_RE = re.compile(r"_trip(\d+)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_CALL_ATTR_RE = re.compile(
    r"(calls|body|condition|to_apply|branch_computations|true_computation|"
    r"false_computation)=(\{[^}]*\}|%[\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPNAME_META_RE = re.compile(r'op_name="([^"]*)"')

_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "after-all", "partition-id", "replica-id", "iota",
             # control flow: the body/branch computations account their own
             # traffic; the op itself moves nothing (carries are aliased)
             "while", "conditional", "call"}

_SLICE_KINDS = {"dynamic-slice", "slice", "gather"}

# XLA:CPU lowers bf16 dots as f32 dots with materialized converts of the
# operands; the TPU MXU consumes bf16 natively with f32 accumulate, so
# convert-only traffic is a host-backend artifact and is not charged.
_TRIVIAL_KINDS = {"parameter", "constant", "bitcast", "reshape", "convert",
                  "tuple", "get-tuple-element", "broadcast"}


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    shapes: list          # list of (dtype, dims) for result (tuple flattened)
    operands: list        # operand value names
    line: str

    def result_bytes(self) -> float:
        return sum(_DTYPE_BYTES.get(dt, 4) * _prod(dims)
                   for dt, dims in self.shapes)


def _prod(dims):
    out = 1
    for d in dims:
        out *= d
    return out


def _parse_shapes(type_str: str):
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _SHAPE_RE.finditer(type_str)]


@dataclasses.dataclass
class Computation:
    name: str
    ops: dict            # name -> Op
    order: list          # op names in order


def parse_hlo(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line.strip())
            if m and "->" in line:
                cur = Computation(m.group(1), {}, [])
            continue
        if line.startswith("}") or line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, type_str, kind, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest.split(")")[0])
        op = Op(name=name, kind=kind, shapes=_parse_shapes(type_str),
                operands=operands, line=line.rstrip())
        cur.ops[name] = op
        cur.order.append(name)
    return comps


# ------------------------------------------------------------------
def _call_edges(op: Op):
    """Yields (attr, computation_name) for computations referenced by op."""
    for m in _CALL_ATTR_RE.finditer(op.line):
        attr, val = m.groups()
        if val.startswith("{"):
            for name in re.findall(r"%([\w.\-]+)", val):
                yield attr, name
        else:
            yield attr, val[1:]


def _while_trip(op: Op, comps: dict[str, Computation],
                warnings: list) -> int:
    meta = _OPNAME_META_RE.search(op.line)
    if meta:
        tags = _TRIP_RE.findall(meta.group(1))
        if tags:
            return int(tags[-1])
    # fallback: constant bound in the loop condition
    cond_name = next((c for a, c in _call_edges(op) if a == "condition"), None)
    if cond_name and cond_name in comps:
        cond = comps[cond_name]
        consts = {o.name: o for o in cond.ops.values() if o.kind == "constant"}
        for o in cond.ops.values():
            if o.kind == "compare":
                for operand in o.operands:
                    if operand in consts:
                        mm = re.search(r"constant\((\d+)\)",
                                       consts[operand].line)
                        if mm:
                            return int(mm.group(1))
    warnings.append(f"while {op.name}: trip count unknown, assuming 1")
    return 1


def _dot_flops(op: Op, comp: Computation) -> float:
    out_elems = _prod(op.shapes[0][1])
    m = _CONTRACT_RE.search(op.line)
    contract = 1
    if m and op.operands:
        lhs = comp.ops.get(op.operands[0])
        if lhs is not None:
            dims = lhs.shapes[0][1]
            for idx in (int(x) for x in m.group(1).split(",") if x):
                if idx < len(dims):
                    contract *= dims[idx]
    return 2.0 * out_elems * contract


def _param_index(op: Op) -> int | None:
    m = re.search(r"parameter\((\d+)\)", op.line)
    return int(m.group(1)) if m else None


def _op_bytes(op: Op, comp: Computation, comps: dict) -> float:
    """HBM traffic estimate for one boundary op (reads + writes).

    Slicing ops (and fusions that only slice an operand) are charged the
    *slice* size, and in-place dynamic-update-slice roots are charged the
    update size — matching XLA's buffer aliasing inside while loops.  Without
    this, scan-over-layers models are overcharged ~the full weight stack per
    layer (measured 400x inflation on an 8B train step).
    """
    if op.kind == "convert":
        return 0.0
    if op.kind == "dynamic-slice" or op.kind == "slice" or op.kind == "gather":
        return 2.0 * op.result_bytes()
    if op.kind == "dynamic-update-slice":
        upd = comp.ops.get(op.operands[1]) if len(op.operands) > 1 else None
        upd_bytes = upd.result_bytes() if upd else op.result_bytes()
        return 2.0 * upd_bytes
    if op.kind == "scatter":
        upd = comp.ops.get(op.operands[-1]) if op.operands else None
        return 2.0 * (upd.result_bytes() if upd else op.result_bytes())
    if op.kind == "fusion":
        callee = next((c for a, c in _call_edges(op) if a == "calls"), None)
        if callee in comps:
            return _fusion_bytes(op, comp, comps[callee])
    operand_bytes = sum(
        comp.ops[o].result_bytes() for o in op.operands
        if o in comp.ops and comp.ops[o].kind != "constant")
    return op.result_bytes() + operand_bytes


def _fusion_bytes(op: Op, comp: Computation, fc: Computation) -> float:
    if all(o.kind in _TRIVIAL_KINDS for o in fc.ops.values()):
        return 0.0  # pure dtype/layout-metadata fusion (host-backend artifact)
    params: dict[int, Op] = {}
    consumers: dict[str, list[Op]] = defaultdict(list)
    dus_ops: list[Op] = []
    for o in fc.ops.values():
        if o.kind == "parameter":
            idx = _param_index(o)
            if idx is not None:
                params[idx] = o
        for opr in o.operands:
            consumers[opr].append(o)
        if o.kind == "dynamic-update-slice":
            dus_ops.append(o)

    # In-place updates: charge 2x the update slice, alias the base buffer
    # (XLA aliases dus buffers in while bodies), and remove the full buffer
    # from the fusion's written-result accounting.
    result_bytes = op.result_bytes()
    aliased_param_names: set[str] = set()
    for dus in dus_ops:
        upd = fc.ops.get(dus.operands[1]) if len(dus.operands) > 1 else None
        if upd is None:
            continue
        base = fc.ops.get(dus.operands[0]) if dus.operands else None
        hops = 0
        while base is not None and base.kind in ("bitcast", "copy", "convert") \
                and base.operands and hops < 8:
            base = fc.ops.get(base.operands[0])
            hops += 1
        if base is not None and base.kind == "parameter":
            aliased_param_names.add(base.name)
            result_bytes -= dus.result_bytes()          # not fully written
            result_bytes += 2.0 * upd.result_bytes()    # rmw of the slice
    result_bytes = max(result_bytes, 0.0)

    total = result_bytes
    for i, opr_name in enumerate(op.operands):
        if opr_name not in comp.ops:
            continue
        full = comp.ops[opr_name].result_bytes()
        p = params.get(i)
        if p is None:
            total += full
            continue
        if p.name in aliased_param_names:
            continue  # in-place buffer, no read of the full extent
        uses = consumers.get(p.name, [])
        if uses and all(u.kind in _SLICE_KINDS for u in uses):
            total += sum(u.result_bytes() for u in uses)
        else:
            total += full
    return total


def _pre_convert_bytes(op: Op, comp: Computation, comps: dict) -> float:
    """Effective payload of a collective operand: when the operand is a
    bare convert (or a convert-only fusion), charge the *source* bytes --
    XLA:CPU upcasts bf16 to f32 around reductions, which the TPU backend
    does not materialize on the wire."""
    cur = op
    for _ in range(4):
        if cur.kind == "convert" and cur.operands:
            nxt = comp.ops.get(cur.operands[0])
        elif cur.kind == "fusion":
            callee = next((c for a, c in _call_edges(cur) if a == "calls"),
                          None)
            fc = comps.get(callee)
            if fc is None or not all(o.kind in _TRIVIAL_KINDS
                                     for o in fc.ops.values()):
                break
            nxt = comp.ops.get(cur.operands[0]) if cur.operands else None
        else:
            break
        if nxt is None:
            break
        if nxt.result_bytes() < cur.result_bytes():
            cur = nxt
        else:
            break
    return cur.result_bytes()


def _group_size(op: Op, default: int) -> int:
    m = _GROUPS_RE.search(op.line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPLICIT_RE.search(op.line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


@dataclasses.dataclass
class HLOAnalysis:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: float = 0.0        # raw operand sizes (spec formula)
    collective_wire_bytes: float = 0.0   # ring-adjusted on-wire estimate
    per_collective: dict = dataclasses.field(default_factory=dict)
    while_trips: dict = dataclasses.field(default_factory=dict)
    warnings: list = dataclasses.field(default_factory=list)

    def as_dict(self):
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "collective_bytes": self.collective_bytes,
            "collective_wire_bytes": self.collective_wire_bytes,
            "per_collective": dict(self.per_collective),
            "warnings": list(self.warnings),
        }


def analyze(text: str, n_devices: int = 1,
            fused_scopes: tuple = ()) -> HLOAnalysis:
    """Analyze post-SPMD HLO text; all numbers are PER-DEVICE.

    fused_scopes: named_scope tags whose interior byte traffic is discounted
    (flops and collectives still counted) -- used to project the measured
    jnp lowering onto the implemented Pallas kernels, whose working set is
    VMEM-resident (e.g. "flash_fusible")."""
    comps = parse_hlo(text)
    res = HLOAnalysis()

    # entry computation = the one never referenced by others
    referenced = set()
    for comp in comps.values():
        for op in comp.ops.values():
            for _, callee in _call_edges(op):
                referenced.add(callee)
    entries = [c for c in comps if c not in referenced]
    if not entries:
        res.warnings.append("no entry computation found")
        return res

    # propagate multipliers through the call graph
    mult: dict[str, float] = defaultdict(float)
    fused_only: dict[str, bool] = defaultdict(lambda: True)
    for e in entries:
        mult[e] = 1.0
        fused_only[e] = False
    # iterate to fixpoint (call graphs are DAGs; bounded passes)
    for _ in range(len(comps) + 2):
        changed = False
        for cname, comp in comps.items():
            if mult[cname] == 0:
                continue
            for op in comp.ops.values():
                trip = None
                for attr, callee in _call_edges(op):
                    if callee not in comps:
                        continue
                    factor = 1.0
                    if attr in ("body", "condition"):
                        if trip is None:
                            trip = _while_trip(op, comps, res.warnings)
                            res.while_trips[op.name] = trip
                        factor = float(trip)
                    new = mult[cname] * factor
                    is_fusion_edge = (attr == "calls" and op.kind == "fusion")
                    if new > mult[callee] + 1e-9:
                        mult[callee] = new
                        changed = True
                    if not is_fusion_edge and fused_only[callee]:
                        fused_only[callee] = False
                        changed = True
        if not changed:
            break

    # accumulate per-op costs
    for cname, comp in comps.items():
        m = mult[cname]
        if m == 0:
            continue
        boundary = not fused_only[cname]
        for op in comp.ops.values():
            if op.kind == "dot":
                res.flops += m * _dot_flops(op, comp)
            elif op.kind == "convolution":
                # rough: 2 * out_elems * (in_ch * prod(kernel)) unknown from
                # text -> use 2*out_elems and warn (models avoid conv ops)
                res.flops += m * 2.0 * _prod(op.shapes[0][1])
                res.warnings.append(f"convolution {op.name}: approximate flops")
            kind = op.kind.replace("-start", "")
            if kind in COLLECTIVE_KINDS:
                operand_bytes = sum(
                    _pre_convert_bytes(comp.ops[o], comp, comps)
                    for o in op.operands if o in comp.ops)
                if operand_bytes == 0:
                    operand_bytes = op.result_bytes()
                n = _group_size(op, n_devices)
                if kind == "all-reduce":
                    wire = 2.0 * (n - 1) / max(n, 1) * operand_bytes
                elif kind == "collective-permute":
                    wire = operand_bytes
                elif kind == "all-gather":
                    # operand is the shard; on-wire each device sends its
                    # shard to n-1 peers in a ring: (n-1) * shard
                    wire = (n - 1) * operand_bytes
                else:  # reduce-scatter, all-to-all: operand is full buffer
                    wire = (n - 1) / max(n, 1) * operand_bytes
                res.collective_bytes += m * operand_bytes
                res.collective_wire_bytes += m * wire
                agg = res.per_collective.setdefault(
                    kind, {"count": 0.0, "bytes": 0.0})
                agg["count"] += m
                agg["bytes"] += m * operand_bytes
            if boundary and op.kind not in _FREE_OPS:
                if fused_scopes:
                    meta = _OPNAME_META_RE.search(op.line)
                    if meta and any(t in meta.group(1) for t in fused_scopes):
                        continue
                res.bytes_accessed += m * _op_bytes(op, comp, comps)
    return res


# ------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one device kind."""
    flops_bf16: float              # FLOP/s
    hbm_bw: float                  # B/s
    ici_bw: float                  # B/s per ICI link (ring collectives
    #                                use one link per direction)
    source: str


# keyed by `jax.Device.device_kind`; a kind that is not here has no
# peaks, and asking for them is an error rather than a silent default
PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8 / 4,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s interconnect per "
               "chip over its 4 ICI links (2D torus)"),
}

# HLO lowered on CPU host devices (the 512-device dry runs, the kernel
# roofline on a CPU) stands in for a chip the CPU has no peaks for; its
# roofline is modelled for this kind, and says so in its records
CPU_MODELLED_KIND = "TPU v5 lite"


def modelled_kind(device) -> str:
    """The `PEAKS` key for a program compiled for `device`: the device's
    own kind on an accelerator (an unknown one then raises in `peaks`),
    `CPU_MODELLED_KIND` where the HLO was lowered for the CPU."""
    if device.platform == "cpu":
        return CPU_MODELLED_KIND
    return device.device_kind


def peaks(device_kind: str) -> DevicePeaks:
    """The peak table entry for a device kind (raises for unknown)."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    collective_wire_s: float
    model_flops: float = 0.0
    hlo_flops: float = 0.0
    peak_flops: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Optimistic (full-overlap) step time = max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPS-per-chip / peak over the bottleneck-implied step time."""
        if self.step_time_s == 0:
            return 0.0
        return (self.model_flops / self.peak_flops) / self.step_time_s


def roofline(analysis: HLOAnalysis, device_kind: str,
             model_flops_per_device: float = 0.0) -> RooflineTerms:
    """Roofline terms of `analysis` against the peaks of `device_kind`
    (a `PEAKS` key: the kind the program is modelled for, which need not
    be the kind that compiled it)."""
    pk = peaks(device_kind)
    return RooflineTerms(
        compute_s=analysis.flops / pk.flops_bf16,
        memory_s=analysis.bytes_accessed / pk.hbm_bw,
        collective_s=analysis.collective_bytes / pk.ici_bw,
        collective_wire_s=analysis.collective_wire_bytes / pk.ici_bw,
        model_flops=model_flops_per_device,
        hlo_flops=analysis.flops,
        peak_flops=pk.flops_bf16,
    )
