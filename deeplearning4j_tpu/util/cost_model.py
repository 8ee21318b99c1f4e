"""Cost attribution: per-layer FLOPs / bytes / device-time accounting + MFU.

PR 4's telemetry answers "is training healthy?"; this layer answers *where*
the FLOPs, bytes, and milliseconds go — the per-op cost-model discipline TVM
(PAPERS.md, arxiv 1802.04799) uses to drive optimization, applied to the
whole-step XLA program. Following the Julia-to-TPU paper's lead, the static
numbers are EXTRACTED FROM THE COMPILATION ARTIFACT itself rather than
re-derived by hand: after ``jit(step).lower().compile()`` (the AOT warmup
path, docs/COMPILE_CACHE.md) the compiled executable exposes

- ``cost_analysis()``   — whole-program FLOPs / transcendentals / bytes,
- ``memory_analysis()`` — argument / output / temp / code buffer sizes,
- ``as_text()``         — the optimized HLO, whose per-instruction
  ``metadata={op_name=...}`` carries the ``jax.named_scope`` path.

The network classes thread ``named_scope("layer:<tag>")`` around every layer
apply (nn/multilayer.py, nn/computation_graph.py), so forward ops surface as
``jvp(layer:<tag>)`` and their backward transposes as
``transpose(jvp(layer:<tag>))`` — one regex recovers (layer, direction) for
every instruction, and a small per-opcode cost model (dot = 2·M·N·K,
convolution = 2·out·kh·kw·ci/g, elementwise = 1 flop/element — XLA's own
HloCostAnalysis conventions) turns the instruction stream into a per-layer
table whose FLOP column sums back to the executable's own
``cost_analysis()`` total (tests assert within 5%).

Runtime attribution reuses the same artifact: the instruction→layer map
built here resolves the HLO-instruction-named XPlane events the JAX
profiler records (util/profiler.py ``xplane_mapped_ms``), yielding a
per-layer fwd/bwd device-time table on real executions.

For backends where ``cost_analysis()``/``as_text()`` are unavailable the
nets fall back to analytic formulas keyed off the layer confs (conv / dense
/ LSTM / attention), and every row carries ``source: xla|analytic`` so
nothing is silently estimated.

Reported via ``net.cost_report()``, the ``/costs`` JSON route
(util/ui_server.py), the ``cost`` group on StatsListener records, and the
``train.examples_per_sec`` / ``train.model_flops_utilization`` telemetry
gauges. MFU = achieved FLOP/s over the ``DL4J_TPU_PEAK_FLOPS`` knob
(config.py). docs/OBSERVABILITY.md#cost-attribution--mfu.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# scope helpers (the contract between the nets and the HLO parser)
# ---------------------------------------------------------------------------

_TAG_BAD = re.compile(r"[^A-Za-z0-9_.\-]")

OPTIMIZER_ROW = "(optimizer)"
UNTAGGED_ROW = "(untagged)"


def sanitize_tag(tag: str) -> str:
    """Layer tags must survive the op_name path verbatim: no '/', no spaces,
    nothing the metadata quoting could mangle."""
    return _TAG_BAD.sub("_", str(tag))


def layer_scope(tag: str):
    """``named_scope`` wrapper every layer apply runs under — trace-time
    only, zero cost in the compiled program."""
    import jax

    return jax.named_scope("layer:" + sanitize_tag(tag))


def optimizer_scope():
    """Scope for the updater loop: optimizer FLOPs (Adam moments etc.) get
    their own row instead of polluting a layer's."""
    import jax

    return jax.named_scope("opt:update")


_LAYER_RE = re.compile(r"layer:([A-Za-z0-9_.\-]+)")


def _resolve_op_name(op_name: str) -> Tuple[Optional[str], str]:
    """(layer tag | OPTIMIZER_ROW | None, 'fwd'|'bwd') from one metadata
    op_name path. Backward ops are the transposed jvp primals."""
    if "opt:update" in op_name:
        return OPTIMIZER_ROW, "fwd"
    m = _LAYER_RE.search(op_name)
    tag = m.group(1) if m else None
    return tag, ("bwd" if "transpose(" in op_name else "fwd")


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%?(\S+?)\s*=\s*(.+)$")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*\))?\s*->.*\{\s*$")
_OPCODE_RE = re.compile(r"^(.+?)\s([a-z][a-zA-Z0-9_\-]*)\((.*)$")
_METADATA_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")

# XLA HloCostAnalysis conventions: these unary ops count as transcendentals
# (per output element), not flops.
_TRANSCENDENTAL = {
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "rsqrt", "sqrt", "cbrt", "tanh", "sine", "cosine", "tan",
    "atan2", "power", "erf", "expm1",
}
# ...and these count 1 flop per output element (select and convert DO count
# — calibrated against this jaxlib's HloCostAnalysis).
_ELEMENTWISE_FLOP = {
    "add", "subtract", "multiply", "divide", "remainder", "maximum",
    "minimum", "abs", "negate", "sign", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "clamp", "compare",
    "select", "convert", "is-finite", "and", "or", "xor", "not",
    "shift-left", "shift-right-arithmetic", "shift-right-logical",
}
# ops whose cost multiplies by their to_apply reducer computation's per-call
# flops; the reducer bodies themselves are NOT directly counted.
_REDUCERS = {"reduce", "reduce-window", "select-and-scatter", "scatter"}
# computation callers: never cost-counted themselves (their called
# computations' instructions are), but they DO appear as runtime thunk
# events and carry the boundary memory traffic.
_CALLERS = {"fusion", "call", "while", "conditional", "async-start"}
# pure data movement / bookkeeping: zero flops.
_ZERO_FLOP = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "copy", "copy-start", "copy-done", "broadcast",
    "reshape", "transpose", "slice", "concatenate", "pad", "reverse",
    "gather", "dynamic-slice", "dynamic-update-slice", "iota",
    "rng", "rng-bit-generator", "rng-get-and-update-state", "sort",
    "custom-call", "after-all", "partition-id", "replica-id", "domain",
    "optimization-barrier", "infeed", "outfeed", "send", "recv",
    "get-dimension-size",
}


@dataclasses.dataclass
class HloInstr:
    name: str
    opcode: str
    out_elems: int            # total elements across tuple leaves
    out_elems_primary: int    # elements of the first tuple leaf
    out_bytes: int
    operand_elems: List[int]
    operand_bytes: int
    flops: float
    transcendentals: float
    reducer_units: float      # reduce-family: multiplies the reducer's cost
    layer: Optional[str]      # raw tag from own metadata (None if untagged)
    direction: str            # 'fwd' | 'bwd'
    calls: List[str]


def _shapes_of(segment: str) -> List[Tuple[str, List[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt not in _DTYPE_BYTES and dt not in ("token", "opaque"):
            continue
        out.append((dt, [int(d) for d in dims.split(",") if d] if dims else []))
    return out


def _elems(dims: List[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _bytes_of(shapes: List[Tuple[str, List[int]]]) -> int:
    return sum(_DTYPE_BYTES.get(dt, 0) * _elems(dims) for dt, dims in shapes)


def _split_operands(rest: str) -> Tuple[str, str]:
    """Split the text after ``opcode(`` into (operands, attributes) at the
    matching close paren."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i], rest[i + 1:]
    return rest, ""


def _dot_flops(out_elems: int, operands: List[Tuple[str, List[int]]],
               attrs: str) -> float:
    """2 * output elements * contracted elements (HloCostAnalysis kDot)."""
    if not operands:
        return 0.0
    lhs = operands[0][1]
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", attrs)
    contracted = 1
    if m and m.group(1):
        for d in m.group(1).split(","):
            i = int(d)
            if i < len(lhs):
                contracted *= lhs[i]
    elif lhs:
        contracted = lhs[-1]
    return 2.0 * out_elems * contracted


def _window_dims(attrs: str, key: str, n: int, default: int) -> List[int]:
    m = re.search(key + r"=([0-9x]+)", attrs)
    if not m:
        return [default] * n
    vals = [int(v) for v in m.group(1).split("x")]
    return vals if len(vals) == n else [default] * n


def _window_pads(attrs: str, n: int) -> List[Tuple[int, int]]:
    m = re.search(r"pad=([0-9_x]+)", attrs)
    if not m:
        return [(0, 0)] * n
    pads = []
    for part in m.group(1).split("x"):
        lo, _, hi = part.partition("_")
        pads.append((int(lo), int(hi or lo)))
    return pads if len(pads) == n else [(0, 0)] * n


def _conv_flops(out_dims: List[int], operands: List[Tuple[str, List[int]]],
                attrs: str) -> float:
    """XLA HloCostAnalysis::HandleConvolution: 2 FLOPs per multiply-add over
    the VALID tap positions only — strided/base-dilated gradient
    convolutions (conv backward under stride > 1) touch a fraction of the
    naive out x kernel-window product, and XLA's total counts exactly that
    fraction; this mirrors its per-spatial-dimension valid-position walk."""
    if len(operands) < 2:
        return 0.0
    lhs, rhs = operands[0][1], operands[1][1]
    m = re.search(r"dim_labels=([^, ]+)", attrs)
    if not m:  # naive fallback: whole kernel at every output element
        kern = 1
        for d in rhs[:-1]:
            kern *= d
        out = 1
        for d in out_dims:
            out *= d
        return 2.0 * out * kern
    spec = m.group(1)
    lhs_spec, rest = spec.split("_", 1)
    rhs_spec, out_spec = rest.split("->")
    nsp = sum(ch.isdigit() for ch in lhs_spec)
    size = _window_dims(attrs, "size", nsp, 1)
    stride = _window_dims(attrs, "stride", nsp, 1)
    lhs_dil = _window_dims(attrs, "lhs_dilate", nsp, 1)
    rhs_dil = _window_dims(attrs, "rhs_dilate", nsp, 1)
    pads = _window_pads(attrs, nsp)
    fgc_m = re.search(r"feature_group_count=(\d+)", attrs)
    bgc_m = re.search(r"batch_group_count=(\d+)", attrs)
    fgc = int(fgc_m.group(1)) if fgc_m else 1
    bgc = int(bgc_m.group(1)) if bgc_m else 1
    valid_total = 1
    for d in range(nsp):
        out_size = out_dims[out_spec.index(str(d))]
        in_size = lhs[lhs_spec.index(str(d))]
        bd, wd = lhs_dil[d], rhs_dil[d]
        pl, _ph = pads[d]
        dilated_in = (in_size - 1) * bd + 1
        cnt = 0
        for ki in range(size[d]):
            kidx = ki * wd
            for o in range(out_size):
                ri = o * stride[d] + kidx - pl
                if ri < 0 or ri >= dilated_in:
                    continue
                if bd > 1 and ri % bd:
                    continue
                cnt += 1
        valid_total *= cnt
    in_feat_per_group = lhs[lhs_spec.index("f")] // max(1, fgc)
    out_feat = out_dims[out_spec.index("f")]
    batch = lhs[lhs_spec.index("b")] // max(1, bgc)
    return 2.0 * in_feat_per_group * out_feat * batch * valid_total


def _instr_costs(opcode: str, out_shapes: List[Tuple[str, List[int]]],
                 out_elems: int, out_primary: int,
                 operands: List[Tuple[str, List[int]]],
                 attrs: str) -> Tuple[float, float, float]:
    """(flops, transcendentals, reducer_units) for one instruction, matching
    XLA's own conventions (calibrated against this jaxlib's HloCostAnalysis)
    closely enough that the module-wide sum lands within the 5%
    reconciliation tolerance (tests/test_cost_model.py). ``reducer_units``
    is the per-reducer-call count for the reduce family: their final flops
    = units x the to_apply computation's per-call cost."""
    if opcode == "dot":
        return _dot_flops(out_elems, operands, attrs), 0.0, 0.0
    if opcode == "convolution":
        out_dims = out_shapes[0][1] if out_shapes else []
        return _conv_flops(out_dims, operands, attrs), 0.0, 0.0
    if opcode in _TRANSCENDENTAL:
        return 0.0, float(out_elems), 0.0
    if opcode in _ELEMENTWISE_FLOP:
        return float(out_elems), 0.0, 0.0
    if opcode == "reduce":
        # variadic reduce: N data operands + N scalar inits
        data = sum(_elems(dims) for _, dims in operands) - len(operands) // 2
        n = max(1, len(operands) // 2)
        return 0.0, 0.0, float(max(0, data // n - out_primary))
    if opcode in ("reduce-window", "select-and-scatter"):
        m = re.search(r"size=([0-9x]+)", attrs)
        win = 1
        if m:
            for d in m.group(1).split("x"):
                win *= int(d)
        return 0.0, 0.0, float(out_primary * max(1, win - 1))
    if opcode == "scatter":
        return 0.0, 0.0, float(
            sum(_elems(d) for _, d in operands[1:]) // 2)
    return 0.0, 0.0, 0.0


def parse_hlo_module(text: str) -> Tuple[Dict[str, List[HloInstr]], str]:
    """Parse one optimized-HLO module text into
    {computation name: [HloInstr]}, plus the entry computation's name."""
    comps: Dict[str, List[HloInstr]] = {}
    cur: Optional[List[HloInstr]] = None
    entry = ""
    for line in text.splitlines():
        if not line.strip():
            continue
        cm = _COMP_RE.match(line)
        if cm:
            cur = comps.setdefault(cm.group(2), [])
            if cm.group(1):
                entry = cm.group(2)
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        im = _INSTR_RE.match(line)
        if not im:
            continue
        name, rhs = im.group(1), im.group(2)
        om = _OPCODE_RE.match(rhs)
        if not om:
            continue
        type_str, opcode, rest = om.group(1), om.group(2), om.group(3)
        operands_str, attrs = _split_operands(rest)
        out_shapes = _shapes_of(type_str)
        out_elems = sum(_elems(d) for _, d in out_shapes)
        operands = _shapes_of(operands_str)
        mm = _METADATA_RE.search(attrs)
        layer, direction = (None, "fwd")
        if mm:
            layer, direction = _resolve_op_name(mm.group(1))
        out_primary = _elems(out_shapes[0][1]) if out_shapes else 0
        flops, transc, units = (0.0, 0.0, 0.0)
        if opcode not in _CALLERS and opcode not in _ZERO_FLOP:
            flops, transc, units = _instr_costs(
                opcode, out_shapes, out_elems, out_primary, operands, attrs)
        calls = _CALLS_RE.findall(attrs) \
            if (opcode in _CALLERS or opcode in _REDUCERS
                or opcode == "sort") else []
        cur.append(HloInstr(
            name=name, opcode=opcode, out_elems=out_elems,
            out_elems_primary=out_primary,
            out_bytes=_bytes_of(out_shapes),
            operand_elems=[_elems(d) for _, d in operands],
            operand_bytes=_bytes_of(operands),
            flops=flops, transcendentals=transc, reducer_units=units,
            layer=layer, direction=direction, calls=calls))
    return comps, entry


@dataclasses.dataclass
class HloAttribution:
    """Per-layer static costs + the instruction→(layer, dir) map used for
    runtime XPlane grouping."""

    by_layer: Dict[Tuple[str, str], Dict[str, float]]
    flops_total: float
    transcendentals_total: float
    bytes_total: float
    inst_map: Dict[str, Tuple[str, str]]


def attribute_hlo(text: str) -> HloAttribution:
    """Group every instruction's estimated cost by (layer tag, direction).
    Caller instructions (fusion/call/while) are never cost-counted — their
    called computations' bodies are — but they resolve to the majority layer
    of their bodies so byte traffic and runtime thunk events attribute."""
    comps, entry = parse_hlo_module(text)

    # resolve callers bottom-up: a computation's dominant (layer, dir) by
    # flops (then transcendentals, then element count as tie-breakers)
    comp_dom: Dict[str, Tuple[Optional[str], str]] = {}

    def dominant(comp: str, seen=None) -> Tuple[Optional[str], str]:
        if comp in comp_dom:
            return comp_dom[comp]
        seen = seen or set()
        if comp in seen or comp not in comps:
            return (None, "fwd")
        seen.add(comp)
        votes: Dict[Tuple[Optional[str], str], float] = {}
        for ins in comps[comp]:
            key, weight = (ins.layer, ins.direction), \
                (ins.flops + ins.transcendentals + ins.reducer_units
                 + 1e-6 * ins.out_elems)
            if ins.opcode in _CALLERS:
                for callee in ins.calls:
                    ck = dominant(callee, seen)
                    votes[ck] = votes.get(ck, 0.0) + _comp_weight(
                        comps.get(callee, ()))
                continue
            votes[key] = votes.get(key, 0.0) + weight
        tagged = {k: v for k, v in votes.items() if k[0] is not None}
        best = max(tagged or votes or {(None, "fwd"): 0.0},
                   key=lambda k: (tagged or votes).get(k, 0.0))
        comp_dom[comp] = best
        return best

    def _comp_weight(instrs) -> float:
        return sum(i.flops + i.transcendentals + i.reducer_units
                   + 1e-6 * i.out_elems for i in instrs)

    # computations referenced via to_apply (reducers / comparators): their
    # cost is charged at the call site (units x per-call flops), so their
    # bodies — and anything they reach through fusions — must not ALSO be
    # counted directly
    applied: set = set()
    for instrs in comps.values():
        for ins in instrs:
            if ins.opcode not in _CALLERS:
                applied.update(ins.calls)
    stack = list(applied)
    while stack:
        c = stack.pop()
        for ins in comps.get(c, ()):
            for callee in ins.calls:
                if callee not in applied:
                    applied.add(callee)
                    stack.append(callee)

    def per_call_cost(cname: str, seen: Optional[set] = None) -> float:
        """Flops of ONE invocation of a computation, recursing through the
        fusions/calls XLA wraps reducer bodies in."""
        seen = set() if seen is None else seen
        if cname in seen:
            return 0.0
        seen.add(cname)
        total = 0.0
        for i in comps.get(cname, ()):
            if i.opcode in _CALLERS:
                total += sum(per_call_cost(c, seen) for c in i.calls)
            elif i.reducer_units:
                pc = per_call_cost(i.calls[0], seen) if i.calls else 1.0
                total += i.reducer_units * max(1.0, pc)
            else:
                total += i.flops
        return total

    def effective_flops(ins: HloInstr) -> float:
        if ins.reducer_units:
            return ins.reducer_units * max(
                1.0, per_call_cost(ins.calls[0]) if ins.calls else 1.0)
        return ins.flops

    by_layer: Dict[Tuple[str, str], Dict[str, float]] = {}
    inst_map: Dict[str, Tuple[str, str]] = {}
    flops_total = transc_total = bytes_total = 0.0

    def row(layer: Optional[str], direction: str) -> Dict[str, float]:
        key = (layer or UNTAGGED_ROW, direction)
        r = by_layer.get(key)
        if r is None:
            r = by_layer[key] = {"flops": 0.0, "transcendentals": 0.0,
                                 "bytes": 0.0}
        return r

    for cname, instrs in comps.items():
        if cname in applied:
            continue
        for ins in instrs:
            layer, direction = ins.layer, ins.direction
            if ins.opcode in _CALLERS and layer is None:
                # inherit the body's dominant attribution
                doms = [dominant(c) for c in ins.calls] or [(None, "fwd")]
                layer, direction = doms[0]
            if ins.opcode not in _CALLERS:
                eff = effective_flops(ins)
                r = row(layer, direction)
                r["flops"] += eff
                r["transcendentals"] += ins.transcendentals
                flops_total += eff
                transc_total += ins.transcendentals
            # memory traffic is a thunk-boundary quantity: count it on
            # entry-computation instructions only (inner fused ops never
            # touch HBM — that is what fusion is for)
            if cname == entry \
                    and ins.opcode not in ("parameter", "constant", "tuple",
                                           "get-tuple-element"):
                b = ins.out_bytes + ins.operand_bytes
                row(layer, direction)["bytes"] += b
                bytes_total += b
            inst_map[ins.name] = (layer or UNTAGGED_ROW, direction)
    return HloAttribution(by_layer=by_layer, flops_total=flops_total,
                          transcendentals_total=transc_total,
                          bytes_total=bytes_total, inst_map=inst_map)


# ---------------------------------------------------------------------------
# compiled-executable access
# ---------------------------------------------------------------------------


class CostAnalysisUnavailable(RuntimeError):
    """The backend exposes no XLA cost analysis for this executable —
    callers fall back to the analytic formulas (source: analytic)."""


def compiled_totals(compiled) -> Dict[str, float]:
    """Whole-program totals from the executable's own analyses:
    ``cost_analysis()`` (flops / transcendentals / bytes accessed) and
    ``memory_analysis()`` (argument / output / temp / generated code)."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # unimplemented on this backend/runtime
        raise CostAnalysisUnavailable(repr(e)) from None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict) or "flops" not in ca:
        raise CostAnalysisUnavailable(f"no flops in cost_analysis: {ca!r}")
    out = {
        "flops": float(ca.get("flops", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
        out["peak_bytes"] = int(
            getattr(ma, "temp_size_in_bytes", 0)
            + getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0))
    except Exception:
        pass
    return out


def compiled_text(compiled) -> str:
    try:
        text = compiled.as_text()
    except Exception as e:
        raise CostAnalysisUnavailable(repr(e)) from None
    if not text or "ENTRY" not in text:
        raise CostAnalysisUnavailable("no HLO text on this backend")
    return text


# ---------------------------------------------------------------------------
# analytic fallback (source: analytic)
# ---------------------------------------------------------------------------


def analytic_layer_flops(lyr, in_shape, params: int) -> float:
    """Forward FLOPs per EXAMPLE for one layer conf — closed-form formulas
    for the matmul-shaped layers (dense / conv / recurrent / attention),
    a positions·params generic for everything else. in_shape excludes the
    batch dim. Backward is 2x forward (each weight is touched once for dx
    and once for dW — the standard backprop cost model)."""
    cls = type(lyr).__name__
    out_shape = tuple(lyr.output_shape(tuple(in_shape)))
    in_elems = _elems(list(in_shape))
    out_elems = _elems(list(out_shape))
    if cls in ("DenseLayer", "OutputLayer"):
        return 2.0 * in_elems * lyr.n_out
    if cls == "ConvolutionLayer":
        kh, kw = lyr.kernel_size
        c_in = lyr.n_in or in_shape[-1]
        return 2.0 * out_elems * kh * kw * c_in
    if cls == "SeparableConvolution2D":
        kh, kw = lyr.kernel_size
        c_in = lyr.n_in or in_shape[-1]
        pos = out_elems // max(1, out_shape[-1])
        depth = 2.0 * pos * c_in * lyr.depth_multiplier * kh * kw
        point = 2.0 * pos * c_in * lyr.depth_multiplier * lyr.n_out
        return depth + point
    if cls == "Deconvolution2D":
        kh, kw = lyr.kernel_size
        c_in = lyr.n_in or in_shape[-1]
        pos = in_elems // max(1, c_in)
        return 2.0 * pos * kh * kw * c_in * lyr.n_out
    if cls in ("LSTM", "GravesLSTM", "GRU", "SimpleRnn"):
        T = in_shape[0] if len(in_shape) >= 2 else 1
        F = in_shape[-1]
        H = lyr.n_out
        gates = {"LSTM": 4, "GravesLSTM": 4, "GRU": 3, "SimpleRnn": 1}[cls]
        return T * (2.0 * gates * H * (F + H) + 10.0 * H)
    if cls in ("RnnOutputLayer",):
        T = in_shape[0] if len(in_shape) >= 2 else 1
        return 2.0 * T * in_shape[-1] * lyr.n_out
    if "Attention" in cls and hasattr(lyr, "n_heads"):
        S = in_shape[0] if len(in_shape) >= 2 else 1
        D = lyr.n_in or in_shape[-1]
        hd = getattr(lyr, "n_heads", 1) * (getattr(lyr, "head_size", None)
                                           or max(1, lyr.n_out // max(
                                               1, lyr.n_heads)))
        proj = 2.0 * S * D * hd * 3 + 2.0 * S * hd * lyr.n_out
        attn = 4.0 * S * S * hd
        return proj + attn
    if cls == "EmbeddingLayer":
        return 0.0
    if params:
        # generic matmul-dominated estimate: 2 flops per weight per output
        # position (time/spatial positions of the output)
        positions = max(1, out_elems // max(1, out_shape[-1]))
        return 2.0 * params * positions
    return float(out_elems)  # paramless elementwise/pool layers


def analytic_rows(entries, batch: int) -> List["CostRow"]:
    """``entries``: [(tag, layer conf, in_shape excl. batch, param count)].
    Produces the source=analytic table (XLA cost analysis unavailable)."""
    rows = []
    for tag, lyr, in_shape, params in entries:
        fwd = analytic_layer_flops(lyr, in_shape, params) * batch
        out_shape = tuple(lyr.output_shape(tuple(in_shape)))
        byt = 4.0 * (batch * _elems(list(in_shape))
                     + batch * _elems(list(out_shape)) + params)
        rows.append(CostRow(
            layer=sanitize_tag(tag), params=params, flops_fwd=fwd,
            flops_bwd=2.0 * fwd, bytes_accessed=3.0 * byt,
            source="analytic"))
    return rows


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CostRow:
    layer: str
    params: int = 0
    flops_fwd: float = 0.0
    flops_bwd: float = 0.0
    transcendentals: float = 0.0
    bytes_accessed: float = 0.0
    device_time_fwd_s: Optional[float] = None
    device_time_bwd_s: Optional[float] = None
    source: str = "xla"

    @property
    def flops(self) -> float:
        return self.flops_fwd + self.flops_bwd

    @property
    def device_time_s(self) -> Optional[float]:
        if self.device_time_fwd_s is None and self.device_time_bwd_s is None:
            return None
        return (self.device_time_fwd_s or 0.0) + (self.device_time_bwd_s
                                                  or 0.0)

    def to_dict(self) -> dict:
        return {
            "layer": self.layer, "params": self.params,
            "flops_fwd": self.flops_fwd, "flops_bwd": self.flops_bwd,
            "flops": self.flops, "transcendentals": self.transcendentals,
            "bytes_accessed": self.bytes_accessed,
            "device_time_fwd_s": self.device_time_fwd_s,
            "device_time_bwd_s": self.device_time_bwd_s,
            "device_time_s": self.device_time_s,
            "source": self.source,
        }


# canonical dtype keys for the per-dtype peak table; every alias a conf
# compute_dtype or an env author might spell maps to one of these
_PEAK_DTYPE_ALIASES = {
    "bf16": "bf16", "bfloat16": "bf16",
    "fp32": "fp32", "f32": "fp32", "float32": "fp32",
    "fp16": "fp16", "f16": "fp16", "float16": "fp16",
    "int8": "int8", "i8": "int8",
    "fp64": "fp64", "f64": "fp64", "float64": "fp64",
}


def _canon_peak_dtype(dtype) -> Optional[str]:
    if dtype is None:
        return None
    return _PEAK_DTYPE_ALIASES.get(str(dtype).strip().lower())


def peak_flops_from_env(dtype=None) -> Optional[float]:
    """DL4J_TPU_PEAK_FLOPS (config.py): the chip's peak FLOP/s. Accepts a
    bare number (``1.97e14``) or a per-dtype table
    (``bf16=1.97e14,fp32=9.85e13`` — TPU peaks differ ~2x by dtype, so a
    bf16 run must not compute MFU against the fp32 roof). ``dtype`` is the
    run's compute dtype ("bfloat16"/"float32"/... — aliases normalize);
    with a table and no matching entry (or no dtype given) nothing is
    guessed and no MFU is reported. Unset or unparsable → None."""
    v = os.environ.get("DL4J_TPU_PEAK_FLOPS")
    if not v or not v.strip():
        return None
    v = v.strip()
    if "=" in v:
        table = {}
        for part in v.split(","):
            part = part.strip()
            if not part:
                continue
            key, _, val = part.partition("=")
            ck = _canon_peak_dtype(key)
            try:
                f = float(val)
            except ValueError:
                continue
            if ck is not None and f > 0:
                table[ck] = f
        # no dtype: a single-entry table is unambiguous; otherwise fall
        # back to the fp32 entry (the historical bare-number meaning). An
        # UNKNOWN dtype never guesses — no MFU beats a wrong MFU.
        if dtype is None:
            if len(table) == 1:
                return next(iter(table.values()))
            return table.get("fp32")
        ck = _canon_peak_dtype(dtype)
        return None if ck is None else table.get(ck)
    try:
        f = float(v)
    except ValueError:
        return None
    return f if f > 0 else None


@dataclasses.dataclass
class CostReport:
    """Per-layer cost table + whole-step totals + utilization.

    ``devices``: how many devices the analyzed executable spans. XLA's
    ``cost_analysis()`` on a GSPMD-partitioned module reports PER-DEVICE
    totals — ``totals`` (and ``flops_per_step``) keep that per-device
    meaning so the profiled-time reconciliation stays exact, while
    ``totals_global``/``flops_per_step_global`` scale by ``devices`` for
    the whole-program numbers."""

    rows: List[CostRow]
    totals: Dict[str, float]
    batch: int
    params_total: int
    source: str                           # 'xla' | 'analytic'
    model: str = ""
    step_time_s: Optional[float] = None   # measured wall per step
    device_time_s: Optional[float] = None  # attributed device time per step
    peak_flops: Optional[float] = None
    devices: int = 1

    @property
    def flops_per_step(self) -> float:
        return float(self.totals.get("flops", 0.0)) or sum(
            r.flops for r in self.rows)

    @property
    def flops_per_step_global(self) -> float:
        return self.flops_per_step * max(1, self.devices)

    @property
    def totals_global(self) -> Dict[str, float]:
        n = max(1, self.devices)
        return {k: v * n for k, v in self.totals.items()}

    @property
    def examples_per_sec(self) -> Optional[float]:
        if not self.step_time_s:
            return None
        return self.batch / self.step_time_s

    @property
    def achieved_flops_per_sec(self) -> Optional[float]:
        if not self.step_time_s:
            return None
        return self.flops_per_step / self.step_time_s

    @property
    def mfu(self) -> Optional[float]:
        """Model FLOPs utilization: achieved FLOP/s over the configured
        peak (DL4J_TPU_PEAK_FLOPS — per-dtype aware: cost_report() passes
        its conf's compute dtype into peak_flops_from_env). None unless
        both are known."""
        a = self.achieved_flops_per_sec
        if a is None or not self.peak_flops:
            return None
        return a / self.peak_flops

    @property
    def optimizer_update_share(self) -> Optional[float]:
        """Fraction of attributed per-step device time spent in the
        optimizer update phase (the ``(optimizer)`` row from the
        ``opt:update`` scope) — the number the fused donated apply
        (docs/KERNELS.md#fused-optimizer-apply) is built to shrink.
        None without a profiled run (``profile=True``)."""
        total = 0.0
        opt = 0.0
        seen = False
        for r in self.rows:
            t = r.device_time_s
            if t is None:
                continue
            seen = True
            total += t
            if r.layer == OPTIMIZER_ROW:
                opt += t
        if not seen or total <= 0.0:
            return None
        return opt / total

    def to_dict(self) -> dict:
        return {
            "model": self.model,
            "batch": self.batch,
            "params_total": self.params_total,
            "source": self.source,
            "totals": dict(self.totals),
            "devices": self.devices,
            "totals_global": self.totals_global,
            "flops_per_step": self.flops_per_step,
            "flops_per_step_global": self.flops_per_step_global,
            "step_time_s": self.step_time_s,
            "device_time_s": self.device_time_s,
            "examples_per_sec": self.examples_per_sec,
            "achieved_flops_per_sec": self.achieved_flops_per_sec,
            "peak_flops": self.peak_flops,
            "model_flops_utilization": self.mfu,
            "optimizer_update_share": self.optimizer_update_share,
            "layers": [r.to_dict() for r in self.rows],
        }

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def summary(self) -> str:
        """Human table: one row per layer, totals + MFU footer."""
        def fmt(v, unit=""):
            if v is None:
                return "-"
            if v == 0:
                return "0"
            mag = int(math.floor(math.log10(abs(v)) / 3)) if abs(v) >= 1 \
                else 0
            mag = max(0, min(mag, 5))
            suffix = ["", "K", "M", "G", "T", "P"][mag]
            return f"{v / 1000 ** mag:.2f}{suffix}{unit}"

        lines = [f"{'layer':<34}{'params':>10}{'fwd FLOPs':>12}"
                 f"{'bwd FLOPs':>12}{'bytes':>10}{'t_fwd ms':>10}"
                 f"{'t_bwd ms':>10}  source"]
        for r in self.rows:
            tf = "-" if r.device_time_fwd_s is None \
                else f"{r.device_time_fwd_s * 1e3:.3f}"
            tb = "-" if r.device_time_bwd_s is None \
                else f"{r.device_time_bwd_s * 1e3:.3f}"
            lines.append(
                f"{r.layer:<34}{fmt(r.params):>10}{fmt(r.flops_fwd):>12}"
                f"{fmt(r.flops_bwd):>12}{fmt(r.bytes_accessed):>10}"
                f"{tf:>10}{tb:>10}  {r.source}")
        lines.append(
            f"TOTAL: {fmt(self.flops_per_step)}FLOP/step over B={self.batch}"
            f" ({fmt(float(self.params_total))} params, source={self.source})")
        if self.devices > 1:
            lines.append(
                f"  sharded over {self.devices} devices: totals above are "
                f"PER-DEVICE; global {fmt(self.flops_per_step_global)}"
                "FLOP/step")
        if self.step_time_s:
            lines.append(
                f"  step {self.step_time_s * 1e3:.2f} ms wall -> "
                f"{fmt(self.examples_per_sec)} ex/s, "
                f"{fmt(self.achieved_flops_per_sec)}FLOP/s achieved")
        if self.mfu is not None:
            lines.append(f"  MFU {100.0 * self.mfu:.2f}% of peak "
                         f"{fmt(self.peak_flops)}FLOP/s "
                         "(DL4J_TPU_PEAK_FLOPS)")
        share = self.optimizer_update_share
        if share is not None:
            lines.append(
                f"  optimizer update phase: {100.0 * share:.2f}% of "
                "attributed device time")
        return "\n".join(lines)


def rows_from_attribution(attrib: HloAttribution,
                          params_by_tag: Dict[str, int],
                          layer_times: Optional[Dict[Tuple[str, str], float]]
                          = None) -> List[CostRow]:
    """Merge the HLO attribution with the net's params-per-tag map (tags the
    compiler fused away entirely still get a zero row) and optional runtime
    per-(layer, dir) device seconds."""
    tags: List[str] = list(params_by_tag)
    for (tag, _d) in attrib.by_layer:
        if tag not in tags:
            tags.append(tag)
    if layer_times:
        for (tag, _d) in layer_times:
            if tag not in tags:
                tags.append(tag)
    # deterministic order: net layers first, then optimizer/untagged
    tail = [t for t in (OPTIMIZER_ROW, UNTAGGED_ROW) if t in tags]
    tags = [t for t in tags if t not in tail] + tail
    rows = []
    for tag in tags:
        fwd = attrib.by_layer.get((tag, "fwd"), {})
        bwd = attrib.by_layer.get((tag, "bwd"), {})
        row = CostRow(
            layer=tag, params=params_by_tag.get(tag, 0),
            flops_fwd=fwd.get("flops", 0.0), flops_bwd=bwd.get("flops", 0.0),
            transcendentals=(fwd.get("transcendentals", 0.0)
                             + bwd.get("transcendentals", 0.0)),
            bytes_accessed=fwd.get("bytes", 0.0) + bwd.get("bytes", 0.0),
            source="xla")
        if layer_times is not None:
            row.device_time_fwd_s = layer_times.get((tag, "fwd"), 0.0)
            row.device_time_bwd_s = layer_times.get((tag, "bwd"), 0.0)
        rows.append(row)
    return rows


def layer_times_from_xplane(logdir: str,
                            inst_map: Dict[str, Tuple[str, str]],
                            steps: int = 1) -> Dict[Tuple[str, str], float]:
    """Per-(layer, dir) device seconds for ONE step: group the profiler's
    HLO-instruction-named XPlane events through the compiled module's
    instruction map (outermost-mapped dedup — util/profiler.py), divided by
    the number of traced steps."""
    from deeplearning4j_tpu.util.profiler import xplane_mapped_ms

    def resolve(name: str):
        base = name
        while base.endswith(".clone"):
            base = base[:-len(".clone")]
        base = re.sub(r"\.clone\.\d+$", "", base)
        return inst_map.get(base)

    ms = xplane_mapped_ms(logdir, resolve)
    n = max(1, steps)
    return {key: v / 1e3 / n for key, v in ms.items()}


def profile_compiled_step(compiled, state_args, data_args, steps: int = 3,
                          inst_map: Optional[Dict[str, Tuple[str, str]]]
                          = None):
    """Measure the AOT-compiled train step on COPIES of the live training
    state. The executable donates its state operands, so every call rebinds
    the returned buffers — the model's own params/opt-state are never passed
    in and never invalidated, and the model does not advance.

    Returns ``(step_time_s, layer_times, device_time_s)``: steady-state wall
    seconds per step, and — when ``inst_map`` is given — a JAX-profiler
    traced run grouped per (layer, direction) through the compiled module's
    instruction map (:func:`layer_times_from_xplane`)."""
    import shutil
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp

    def copy(t):
        return jax.tree_util.tree_map(jnp.array, t)

    p, s, o, it, key = (copy(a) for a in state_args)

    def run():
        nonlocal p, s, o, it, key
        p, s, o, loss, it, key = compiled(p, s, o, it, key, *data_args)
        return loss

    loss = None
    for _ in range(2):  # warm: the executable is pre-built, this warms caches
        loss = run()
    jax.block_until_ready(loss)
    t0 = _time.perf_counter()
    for _ in range(max(1, steps)):
        loss = run()
    jax.block_until_ready(loss)
    step_time = (_time.perf_counter() - t0) / max(1, steps)
    layer_times = device_time = None
    if inst_map is not None:
        logdir = tempfile.mkdtemp(prefix="dl4j_cost_")
        try:
            jax.profiler.start_trace(logdir)
            try:
                for _ in range(max(1, steps)):
                    loss = run()
                jax.block_until_ready(loss)
            finally:
                jax.profiler.stop_trace()
            layer_times = layer_times_from_xplane(logdir, inst_map,
                                                  max(1, steps))
            device_time = sum(layer_times.values()) or None
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    return step_time, layer_times, device_time


# ---------------------------------------------------------------------------
# publish registry (the /costs route + StatsListener `cost` group)
# ---------------------------------------------------------------------------

_published: Dict[str, dict] = {}
_published_lock = threading.Lock()


def publish_report(name: str, report: CostReport) -> CostReport:
    """Register a report under ``name`` for the UI server's ``/costs`` route
    and the StatsListener ``cost`` group. Also pushes the utilization
    gauges so /metrics shows them without a fit loop running."""
    with _published_lock:
        _published[str(name)] = report.to_dict()
    from deeplearning4j_tpu.util import telemetry as tm

    if tm.enabled():
        if report.examples_per_sec is not None:
            tm.gauge("train.examples_per_sec", report.examples_per_sec,
                     model=str(name))
        if report.mfu is not None:
            tm.gauge("train.model_flops_utilization", report.mfu,
                     model=str(name))
    return report


def published_reports() -> Dict[str, dict]:
    with _published_lock:
        return {k: dict(v) for k, v in _published.items()}


def clear_published() -> None:
    with _published_lock:
        _published.clear()


def cost_stats_group() -> Optional[dict]:
    """Compact per-report summary for StatsListener records: totals and
    utilization only — the full per-layer table stays on /costs."""
    reps = published_reports()
    if not reps:
        return None
    return {
        name: {
            "flops_per_step": r.get("flops_per_step"),
            "batch": r.get("batch"),
            "params_total": r.get("params_total"),
            "source": r.get("source"),
            "step_time_s": r.get("step_time_s"),
            "examples_per_sec": r.get("examples_per_sec"),
            "model_flops_utilization": r.get("model_flops_utilization"),
            "layers": len(r.get("layers", ())),
        }
        for name, r in reps.items()
    }
