"""Roofline analysis from compiled (post-SPMD, per-device) HLO text.

``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified
empirically), which under scan-over-layers undercounts by ~n_layers. This
module re-derives the three roofline terms by walking the HLO call graph:

  * FLOPs: every ``dot`` (2 x out-elements x contracted size), anywhere in
    the graph, multiplied by the enclosing while trip counts (from
    ``backend_config known_trip_count`` — emitted for lax.scan).
  * HBM bytes: operand+output bytes of top-scope ops in non-fusion
    computations (fusion internals live in VMEM/registers; the fusion call
    itself counts its operands+outputs), x trip counts.
  * Collective bytes: per-device ring-algorithm wire bytes per op kind,
    split ICI vs DCN by whether the replica group crosses a pod boundary.

Hardware constants: per-chip compute and HBM peaks live in ``PEAKS``,
keyed by the ``device_kind`` JAX reports; ICI is modelled at ~50 GB/s per
link and DCN at 2.5 GB/s per chip for pod-crossing collectives
(documented assumptions).

Kernel-scope accounting: regions tagged with ``jax.named_scope`` that lower
to single Pallas kernels on the TPU target (flash attention, SSD scan, PS
aggregation, quantization) can be treated as fused: their internal ops
contribute FLOPs but not HBM bytes (they live in VMEM on TPU); their
boundary tensors are produced/consumed by untagged ops and therefore still
counted exactly once. Pass ``kernel_scopes=(...)`` to enable — the delta
between reference accounting and kernel accounting is the measured value of
writing the Pallas kernels.
"""
from __future__ import annotations

import gzip
import json
import math
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

# Published per-chip peaks, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}
# the chip the dry-run and the kernel autotuner model
TARGET_KIND = "TPU v5 lite"
ICI_BW = 50e9                # bytes/s / link (we model 1 effective link)
DCN_BW = 2.5e9               # bytes/s / chip for cross-pod traffic

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(%[\w.\-]+|ENTRY)\s*=\s*(.+?)\s([a-z][\w\-]*)\((.*)$")
_HDR_RE = re.compile(r"^(ENTRY\s+)?(%[\w.\-]+)\s*\(.*->.*\{\s*$")


def _parse_shape(txt: str) -> Tuple[int, List[Tuple[str, Tuple[int, ...]]]]:
    """Return (total_bytes, [(dtype, dims), ...]) for a type string
    (handles tuples)."""
    arrays = []
    total = 0
    for m in _SHAPE_RE.finditer(txt):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        shape = tuple(int(d) for d in dims.split(",") if d)
        n = 1
        for d in shape:
            n *= d
        total += n * DTYPE_BYTES[dt]
        arrays.append((dt, shape))
    return total, arrays


@dataclass
class Op:
    name: str
    opcode: str
    out_bytes: int
    out_arrays: List[Tuple[str, Tuple[int, ...]]]
    operands: List[str]
    line: str
    is_root: bool = False


@dataclass
class Computation:
    name: str
    ops: List[Op] = field(default_factory=list)
    by_name: Dict[str, Op] = field(default_factory=dict)


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: Dict[str, float] = field(default_factory=dict)   # per op kind
    ici_bytes: float = 0.0
    dcn_bytes: float = 0.0

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        self.ici_bytes += other.ici_bytes * mult
        self.dcn_bytes += other.dcn_bytes * mult
        for k, v in other.coll.items():
            self.coll[k] = self.coll.get(k, 0.0) + v * mult


def _operand_frags(s: str) -> List[str]:
    """Raw operand fragments from the call-args text (up to the closing
    paren). Newer XLA annotates operands with their full type, e.g.
    ``dot(f32[8,16]{1,0} %Arg_0.1, f32[16,4]{1,0} %Arg_1.2)`` — the
    commas inside ``[dims]`` and ``{layout}`` must not split, so depth is
    tracked across all three bracket kinds, not just parens."""
    depth = 0
    out = []
    cur = []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if ch == ")" and depth == 0:
                break
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    out.append("".join(cur))
    return out


def _split_operands(s: str) -> List[str]:
    """Operand names from the call-args text (up to the closing paren)."""
    names = []
    for frag in _operand_frags(s):
        m = re.search(r"(%[\w.\-]+)", frag)
        names.append(m.group(1) if m else "")
    return names


def parse_module(txt: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name = None
    for line in txt.splitlines():
        if cur is None:
            m = _HDR_RE.match(line)
            if m:
                cur = Computation(m.group(2))
                if m.group(1):
                    entry_name = m.group(2)
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        is_root = "ROOT " in line[:12]
        m = _OP_RE.match(line)
        if not m:
            # root-instruction shorthand: "ROOT %x = ..."
            m = _OP_RE.match(line.replace("ROOT ", "", 1))
            if not m:
                continue
        name, typ, opcode, rest = m.groups()
        out_bytes, arrays = _parse_shape(typ)
        operands = _split_operands(rest)
        op = Op(name, opcode, out_bytes, arrays, operands, line, is_root)
        cur.ops.append(op)
        cur.by_name[name] = op
    if entry_name:
        comps["__entry__"] = comps[entry_name]
    return comps


def _operand_bytes(comp: Computation, comps, op: Op) -> int:
    tot = 0
    for o in op.operands:
        src = comp.by_name.get(o)
        if src is not None:
            tot += src.out_bytes
    return tot


def _fusion_io_bytes(comps, comp: Computation, op: Op) -> int:
    """Effective HBM traffic of a fusion call: parameters consumed only by
    (dynamic-)slice/gather ops count the slice size, not the full buffer
    (scan residual stacks!); a dynamic-update-slice root counts the update
    size, not the full aliased output."""
    body_names = _called(op, "calls")
    body = comps.get(body_names[0]) if body_names else None
    if body is None:
        return _operand_bytes(comp, comps, op) + op.out_bytes

    # ---- inputs ----
    total_in = 0
    params: Dict[int, Op] = {}
    for bop in body.ops:
        if bop.opcode == "parameter":
            m = re.search(r"parameter\((\d+)\)", bop.line)
            if m:
                params[int(m.group(1))] = bop
    passthrough = ("bitcast", "copy", "reshape", "transpose", "convert")

    def terminal_consumers(pname, depth=0):
        """Consumers of pname, walked through pass-through ops."""
        outs = []
        for b in body.ops:
            if pname not in b.operands:
                continue
            if b.opcode in passthrough and depth < 4:
                outs.extend(terminal_consumers(b.name, depth + 1))
            else:
                outs.append(b)
        return outs

    for idx, o in enumerate(op.operands):
        src = comp.by_name.get(o)
        full = src.out_bytes if src is not None else 0
        p = params.get(idx)
        if p is None:
            total_in += full
            continue
        consumers = terminal_consumers(p.name)
        slicing = [b for b in consumers
                   if b.opcode in ("dynamic-slice", "slice", "gather")]
        # a param consumed ONLY as the overwritten buffer (operand 0) of
        # dynamic-update-slice is aliased in place: 0 read bytes (the
        # update slice is charged on the output side)
        dus_targets = [b for b in consumers
                       if b.opcode == "dynamic-update-slice"
                       and b.operands and b.operands[0] == p.name]
        if consumers and len(dus_targets) == len(consumers):
            continue
        if consumers and len(slicing) + len(dus_targets) == len(consumers):
            total_in += sum(b.out_bytes for b in slicing)
        elif consumers and len(slicing) == len(consumers):
            total_in += sum(b.out_bytes for b in slicing)
        else:
            total_in += full
    # ---- output ----
    total_out = op.out_bytes
    root = next((b for b in body.ops if b.is_root), None)
    if root is not None:
        roots = [root]
        if root.opcode == "tuple":
            roots = [body.by_name[o] for o in root.operands
                     if o in body.by_name]
        eff = 0
        for r in roots:
            if r.opcode == "dynamic-update-slice" and len(r.operands) >= 2:
                upd = body.by_name.get(r.operands[1])
                eff += upd.out_bytes if upd is not None else r.out_bytes
            else:
                eff += r.out_bytes
        total_out = min(total_out, eff) if eff else total_out
    return total_in + total_out


def _dot_flops(comp: Computation, op: Op) -> float:
    out_elems = 0
    for dt, shape in op.out_arrays:
        n = 1
        for d in shape:
            n *= d
        out_elems += n
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
    cdims = [int(x) for x in m.group(1).split(",") if x] if m else []
    lhs = comp.by_name.get(op.operands[0]) if op.operands else None
    shape = None
    if lhs is not None and lhs.out_arrays:
        shape = lhs.out_arrays[0][1]
    else:
        # typed-operand form (newer XLA): the lhs annotation carries the
        # shape inline — parse it instead of the symbol table
        m2 = re.search(r"\s" + re.escape(op.opcode) + r"\((.*)$", op.line)
        if m2:
            frags = _operand_frags(m2.group(1))
            if frags:
                _, arrays = _parse_shape(frags[0])
                if arrays:
                    shape = arrays[0][1]
    csize = 1
    if shape is not None:
        for d in cdims:
            if d < len(shape):
                csize *= shape[d]
    return 2.0 * out_elems * csize


def _trip_count(op: Op) -> int:
    m = re.search(r'known_trip_count[^\d]*(\d+)', op.line)
    return int(m.group(1)) if m else 1


def _called(op: Op, attr: str) -> List[str]:
    m = re.search(attr + r"=(%[\w.\-]+)", op.line)
    if m:
        return [m.group(1)]
    m = re.search(attr + r"=\{([^}]*)\}", op.line)
    if m:
        return re.findall(r"%[\w.\-]+", m.group(1))
    return []


def _group_info(op: Op, n_pod_chips: int = 256) -> Tuple[int, bool]:
    """(group_size, crosses_pod)."""
    line = op.line
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        first = [int(x) for x in m.group(1).split(",") if x.strip()]
        crosses = (max(first) // n_pod_chips) != (min(first) // n_pod_chips) \
            if first else False
        return max(1, len(first)), crosses
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](T\(([\d,]+)\))?",
                  line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        total = int(np.prod(dims))
        ids = np.arange(total).reshape(dims)
        if m.group(5):
            perm = [int(x) for x in m.group(5).split(",")]
            ids = ids.transpose(perm)
        groups = ids.reshape(g, s)
        pods = groups // n_pod_chips
        crosses = bool((pods.max(axis=1) != pods.min(axis=1)).any())
        return s, crosses
    return 2, False


def _wire_payload(comp: Computation, op: Op) -> int:
    """Operand bytes for a collective, corrected for convert-hoisting:
    the CPU backend upcasts bf16 dots to f32 and hoists the convert ABOVE
    gathers/reduces; a TPU compilation keeps the wire format narrow. Walk
    each operand through convert/copy/bitcast chains and charge the
    narrowest dtype seen."""
    total = 0
    for o in op.operands:
        src = comp.by_name.get(o)
        if src is None:
            continue
        bytes_here = src.out_bytes
        seen = 0
        cur = src
        while cur is not None and cur.opcode in ("convert", "copy",
                                                 "bitcast") and seen < 4:
            nxt = comp.by_name.get(cur.operands[0]) if cur.operands else None
            if nxt is not None and 0 < nxt.out_bytes < bytes_here:
                bytes_here = nxt.out_bytes
            cur = nxt
            seen += 1
        total += bytes_here
    return total


def _collective_cost(comp: Computation, op: Op) -> Tuple[float, bool, str]:
    """(wire_bytes_per_device, crosses_pod, kind)."""
    kind = op.opcode.replace("-start", "")
    size, crosses = _group_info(op)
    in_bytes = _wire_payload(comp, op)
    payload = max(in_bytes, 1)
    if kind == "all-gather":
        wire = (size - 1) * payload
    elif kind == "reduce-scatter":
        wire = payload * (size - 1) / size
    elif kind == "all-reduce":
        wire = 2.0 * payload * (size - 1) / size
    elif kind == "all-to-all":
        wire = payload * (size - 1) / size
    else:  # collective-permute
        wire = payload
    return wire, crosses, kind


def comp_cost(comps: Dict[str, Computation], name: str,
              in_fusion: bool, memo: Dict,
              kernel_scopes: Tuple[str, ...] = ()) -> Cost:
    key = (name, in_fusion)
    if key in memo:
        return memo[key]
    c = Cost()
    comp = comps.get(name)
    if comp is None:
        memo[key] = c
        return c

    def in_kernel(op: Op) -> bool:
        return any(ks in op.line for ks in kernel_scopes)

    for op in comp.ops:
        oc = op.opcode
        if kernel_scopes and in_kernel(op) and oc not in (
                "while", "fusion", "call", "conditional"):
            # fused on TPU: FLOPs count, HBM bytes don't
            if oc == "dot":
                c.flops += _dot_flops(comp, op)
            continue
        if oc in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast", "iota", "after-all", "partition-id",
                  "replica-id"):
            continue
        if oc == "fusion":
            for sub in _called(op, "calls"):
                c.add(comp_cost(comps, sub, True, memo, kernel_scopes))
            if not in_fusion:
                io = _fusion_io_bytes(comps, comp, op)
                if kernel_scopes:
                    # XLA fuses across scope boundaries; attribute by the
                    # tagged fraction of the fusion body's ops.
                    subs = _called(op, "calls")
                    body = comps.get(subs[0]) if subs else None
                    if body is not None and body.ops:
                        real = [b for b in body.ops
                                if b.opcode != "parameter"]
                        if real:
                            tagged = sum(
                                1 for b in real
                                if any(ks in b.line for ks in kernel_scopes))
                            io = io * (1.0 - tagged / len(real))
                c.bytes += io
            continue
        if oc == "while":
            trip = _trip_count(op)
            for sub in _called(op, "body"):
                c.add(comp_cost(comps, sub, in_fusion, memo, kernel_scopes), trip)
            for sub in _called(op, "condition"):
                c.add(comp_cost(comps, sub, in_fusion, memo, kernel_scopes), trip)
            continue
        if oc == "conditional":
            subs = _called(op, "branch_computations") or \
                (_called(op, "true_computation")
                 + _called(op, "false_computation"))
            if subs:
                costs = [comp_cost(comps, s, in_fusion, memo, kernel_scopes) for s in subs]
                # one branch executes; take the max-flops branch
                c.add(max(costs, key=lambda x: (x.flops, x.bytes)))
            continue
        if oc in ("call", "async-start", "custom-call"):
            for sub in _called(op, "to_apply") + _called(op, "calls"):
                c.add(comp_cost(comps, sub, in_fusion, memo, kernel_scopes))
            if not in_fusion:
                c.bytes += _operand_bytes(comp, comps, op) + op.out_bytes
            continue
        base = oc.replace("-start", "")
        if base in COLLECTIVES:
            wire, crosses, kind = _collective_cost(comp, op)
            c.coll[kind] = c.coll.get(kind, 0.0) + wire
            if crosses:
                c.dcn_bytes += wire
            else:
                c.ici_bytes += wire
            if not in_fusion:
                c.bytes += _operand_bytes(comp, comps, op) + op.out_bytes
            continue
        if oc == "dot":
            c.flops += _dot_flops(comp, op)
            if not in_fusion:
                c.bytes += _operand_bytes(comp, comps, op) + op.out_bytes
            continue
        if oc == "convolution":
            m = re.search(r"dim_labels=", op.line)
            out_elems = sum(int(np.prod(s)) for _, s in op.out_arrays)
            in_b = _operand_bytes(comp, comps, op)
            c.flops += 2.0 * out_elems * max(1, in_b // max(op.out_bytes, 1))
            if not in_fusion:
                c.bytes += in_b + op.out_bytes
            continue
        # generic elementwise / reduce / slice / dus / copy / reshape ...
        if not in_fusion:
            if oc in ("dynamic-slice", "slice", "gather"):
                c.bytes += 2 * op.out_bytes          # read slice + write
            elif oc == "dynamic-update-slice" and len(op.operands) >= 2:
                upd = comp.by_name.get(op.operands[1])
                ub = upd.out_bytes if upd is not None else op.out_bytes
                c.bytes += 2 * ub                    # read + write the slice
            else:
                c.bytes += _operand_bytes(comp, comps, op) + op.out_bytes
    memo[key] = c
    return c


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def analyze_hlo_text(txt: str, kernel_scopes: Tuple[str, ...] = (),
                     device_kind: str = TARGET_KIND) -> Dict:
    """Per-device FLOPs, HBM and collective bytes of a compiled program,
    and the roofline times against ``device_kind``'s peaks. A kind with
    no entry in ``PEAKS`` gets the counts only (times are None)."""
    comps = parse_module(txt)
    cost = comp_cost(comps, "__entry__", False, {}, kernel_scopes)
    peaks = PEAKS.get(device_kind)
    return {
        "device_kind": device_kind,
        "flops_per_device": cost.flops,
        "hbm_bytes_per_device": cost.bytes,
        "ici_bytes_per_device": cost.ici_bytes,
        "dcn_bytes_per_device": cost.dcn_bytes,
        "collective_bytes_by_kind": dict(cost.coll),
        "compute_s": cost.flops / peaks["flops"] if peaks else None,
        "memory_s": cost.bytes / peaks["hbm_bw"] if peaks else None,
        "collective_s": (cost.ici_bytes / ICI_BW + cost.dcn_bytes / DCN_BW
                         if peaks else None),
    }


def analyze_file(path: str, kernel_scopes: Tuple[str, ...] = ()) -> Dict:
    p = Path(path)
    txt = gzip.open(p, "rt").read() if p.suffix == ".gz" else p.read_text()
    return analyze_hlo_text(txt, kernel_scopes)

# scopes that lower to single Pallas kernels on the TPU target
KERNEL_SCOPES = ("pallas_flash_attention", "pallas_ssd_scan",
                 "pallas_ps_aggregate", "pallas_quantize")


def model_flops(cfg, shape) -> float:
    """Analytic 'useful' FLOPs per step (global, all chips).

    train:   6·N_active·T + 12·L_attn·B·S²·H·hd·(causal 1/2)
    prefill: 2·N_active·T +  4·L_attn·B·S²·H·hd·(1/2)
    decode:  2·N_active·B +  4·L_attn·B·S_cache·H·hd
    (SSM layers contribute their SSD term instead of S².)
    """
    n_act = cfg.n_active_params()
    B, S = shape.global_batch, shape.seq_len
    kind = shape.kind
    # attention layer census
    if cfg.family == "ssm":
        l_attn = 0
    elif cfg.family == "hybrid":
        l_attn = cfg.n_layers // cfg.attn_period
    elif cfg.family == "encdec":
        l_attn = 3 * cfg.n_layers  # enc self + dec self + cross
    else:
        l_attn = cfg.n_layers
    h_hd = (cfg.n_heads * cfg.hd) if cfg.n_heads else 0

    def ssd_flops(tokens):
        if cfg.ssm is None:
            return 0.0
        import repro.models.mamba as mam
        d_in, nh, gn, _ = mam.mamba_dims(cfg)
        q = cfg.ssm.chunk_size
        n = cfg.ssm.d_state
        p = cfg.ssm.head_dim
        n_ssm = (cfg.n_layers if cfg.family == "ssm"
                 else cfg.n_layers - cfg.n_layers // cfg.attn_period)
        per_tok = 2 * q * gn + 2 * q * nh * p + 4 * nh * p * n
        mult = {"train": 3.0, "prefill": 1.0, "decode": 1.0}[kind]
        return mult * n_ssm * tokens * per_tok

    if kind == "train":
        t = B * S
        if cfg.family == "encdec":
            t = B * S  # enc half + dec half
        return 6.0 * n_act * t + 12.0 * l_attn * B * S * S * h_hd * 0.5 \
            + ssd_flops(t)
    if kind == "prefill":
        t = B * S
        return 2.0 * n_act * t + 4.0 * l_attn * B * S * S * h_hd * 0.5 \
            + ssd_flops(t)
    # decode
    return 2.0 * n_act * B + 4.0 * l_attn * B * S * h_hd + ssd_flops(B)


def roofline_row(rec: Dict, hlo_analysis: Dict, cfg, shape,
                 n_chips: int) -> Dict:
    mf = model_flops(cfg, shape)
    fpd = hlo_analysis["flops_per_device"]
    terms = {
        "compute_s": hlo_analysis["compute_s"],
        "memory_s": hlo_analysis["memory_s"],
        "collective_s": hlo_analysis["collective_s"],
    }
    dom = max(terms, key=terms.get)
    bound_s = max(terms.values())
    ideal_s = mf / n_chips / PEAKS[TARGET_KIND]["flops"]
    return {
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dom.replace("_s", ""),
        "model_flops": mf,
        "hlo_flops_per_device": fpd,
        "useful_ratio": round(mf / n_chips / max(fpd, 1), 4),
        "roofline_frac": round(ideal_s / max(bound_s, 1e-12), 4),
        "ici_GB": round(hlo_analysis["ici_bytes_per_device"] / 1e9, 3),
        "dcn_GB": round(hlo_analysis["dcn_bytes_per_device"] / 1e9, 3),
        "hbm_GB": round(hlo_analysis["hbm_bytes_per_device"] / 1e9, 3),
    }


def breakdown(txt_or_path, kernel_scopes: Tuple[str, ...] = (),
              top: int = 15) -> List[Dict]:
    """Per-top-level-op cost attribution (×trip counts) — the 'profile'
    used by the §Perf hypothesis loop."""
    p = Path(str(txt_or_path))
    if p.exists():
        txt = gzip.open(p, "rt").read() if p.suffix == ".gz" \
            else p.read_text()
    else:
        txt = str(txt_or_path)
    comps = parse_module(txt)
    entry = comps.get("__entry__")
    rows = []
    memo: Dict = {}
    for op in entry.ops:
        c = Cost()
        oc = op.opcode
        if oc in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast", "iota"):
            continue
        if oc == "while":
            trip = _trip_count(op)
            for sub in _called(op, "body"):
                c.add(comp_cost(comps, sub, False, memo, kernel_scopes),
                      trip)
        elif oc == "fusion":
            for sub in _called(op, "calls"):
                c.add(comp_cost(comps, sub, True, memo, kernel_scopes))
            c.bytes += _fusion_io_bytes(comps, entry, op)
        elif oc.replace("-start", "") in COLLECTIVES:
            wire, crosses, kind = _collective_cost(entry, op)
            c.coll[kind] = wire
            c.ici_bytes, c.dcn_bytes = (0, wire) if crosses else (wire, 0)
            c.bytes += _operand_bytes(entry, comps, op) + op.out_bytes
        elif oc == "dot":
            c.flops += _dot_flops(entry, op)
            c.bytes += _operand_bytes(entry, comps, op) + op.out_bytes
        else:
            c.bytes += _operand_bytes(entry, comps, op) + op.out_bytes
        m = re.search(r'op_name="([^"]+)"', op.line)
        rows.append({
            "op": op.name, "opcode": oc,
            "where": (m.group(1)[-70:] if m else ""),
            "flops": c.flops, "GB": round(c.bytes / 1e9, 2),
            "ici_GB": round(c.ici_bytes / 1e9, 2),
            "dcn_GB": round(c.dcn_bytes / 1e9, 2),
        })
    rows.sort(key=lambda r: -r["GB"])
    return rows[:top]
