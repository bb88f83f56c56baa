"""HLO analysis: trip-count-aware FLOP / byte / collective accounting.

XLA's ``compiled.cost_analysis()`` visits every computation ONCE — a
48-layer scan (``while`` loop) body is counted a single time, understating
FLOPs and bytes by ~n_layers, and collective operand sizes are not reported
at all. This module walks the post-SPMD per-device HLO text itself:

  * builds a per-computation symbol table (instruction -> output shape),
  * counts dot FLOPs (2 · |output| · contracted dims) wherever they appear
    (including inside fusions),
  * accounts bytes at fusion granularity (operands + outputs of top-level
    instructions — XLA's own bytes-accessed convention),
  * sums operand bytes of every collective (all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute, sync or async),
  * multiplies everything inside a ``while`` body by the loop trip count
    (recovered from the loop condition's comparison constant),

yielding the three roofline terms. All quantities are per-device (the HLO is
the per-device module); totals scale by chip count.
"""

from __future__ import annotations

import dataclasses
import re

# TPU v5e per-chip hardware constants (assignment-specified).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1, "token": 0, "opaque": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HEADER_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->\s*.*\{\s*$")

# HBM-byte accounting follows a TPU fusion model: only ops that would
# materialize a buffer on a well-fused TPU pipeline count; elementwise
# chains are assumed fused into their producers/consumers. CPU HLO barely
# fuses, so summing every op's operands would overstate HBM traffic by an
# order of magnitude.
_BYTES_OPS = {
    "dot": "io",                     # operands + output (weights stream HBM)
    "convolution": "io",
    "fusion": "io",
    "gather": "o",
    "scatter": "io",
    "dynamic-slice": "o",
    "dynamic-update-slice": "u",     # update operand (in-place on TPU)
    "copy": "io",
    "sort": "io",
    "reduce": "o",
    "reduce-window": "o",
    "cholesky": "io", "triangular-solve": "io",
    "rng-bit-generator": "o",
}


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Instr:
    name: str
    out_bytes: int
    out_dims: list          # dims of (first) output shape
    opcode: str
    operands: list          # operand instruction names
    attrs: str
    operand_txt: str = ""   # raw operand text (constant values live here)


def _split_top_level(s: str) -> list:
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return [x.strip() for x in out if x.strip()]


def _parse_instr(line: str) -> Instr | None:
    m = _INSTR_RE.match(line)
    if not m:
        return None
    name, rhs = m.group(1), m.group(2)
    # --- output shape ---
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape_txt, rest = rhs[:i + 1], rhs[i + 1:]
    else:
        sm = re.match(r"^[a-z][a-z0-9]*\[[\d,]*\](?:\{[^}]*\})?", rhs)
        if not sm:
            return None
        shape_txt, rest = sm.group(0), rhs[sm.end():]
    om = _SHAPE_RE.search(shape_txt)
    out_dims = [int(d) for d in om.group(2).split(",") if d] if om else []
    # --- opcode + operand list ---
    rest = rest.strip()
    opm = re.match(r"^([\w\-]+)\(", rest)
    if not opm:
        return None
    opcode = opm.group(1)
    depth, j = 0, opm.end() - 1
    for j in range(opm.end() - 1, len(rest)):
        depth += rest[j] == "("
        depth -= rest[j] == ")"
        if depth == 0:
            break
    operand_txt = rest[opm.end():j]
    attrs = rest[j + 1:]
    operands = []
    for tok in _split_top_level(operand_txt):
        nm = re.search(r"%([\w.\-]+)\s*$", tok)
        if nm:
            operands.append(nm.group(1))
    return Instr(name, _shape_bytes(shape_txt), out_dims, opcode, operands,
                 attrs, operand_txt)


def _parse_computations(hlo: str) -> dict:
    comps: dict = {}
    name = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        hm = _HEADER_RE.match(line.strip())
        if hm and line.strip().endswith("{"):
            name = hm.group(2)
            comps[name] = {"instrs": {}, "entry": bool(hm.group(1))}
            continue
        if line.strip() == "}":
            name = None
            continue
        if name is None:
            continue
        ins = _parse_instr(line)
        if ins is not None:
            comps[name]["instrs"][ins.name] = ins
    return comps


def _dims_attr(attrs: str, key: str) -> list:
    m = re.search(key + r"=\{([\d,]*)\}", attrs)
    if not m:
        return []
    return [int(d) for d in m.group(1).split(",") if d]


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_by_kind: dict = dataclasses.field(default_factory=dict)
    unresolved_loops: int = 0
    # profile breakdowns (per-device): where the bytes/flops/collectives live
    bytes_by_opcode: dict = dataclasses.field(default_factory=dict)
    coll_by_shape: dict = dataclasses.field(default_factory=dict)

    @property
    def coll_bytes(self) -> float:
        return float(sum(self.coll_by_kind.values()))

    def scaled(self, k: float) -> "HloCost":
        return HloCost(self.flops * k, self.bytes * k,
                       {kk: v * k for kk, v in self.coll_by_kind.items()},
                       self.unresolved_loops,
                       {kk: v * k for kk, v in self.bytes_by_opcode.items()},
                       {kk: v * k for kk, v in self.coll_by_shape.items()})

    def __iadd__(self, o: "HloCost"):
        self.flops += o.flops
        self.bytes += o.bytes
        for k, v in o.coll_by_kind.items():
            self.coll_by_kind[k] = self.coll_by_kind.get(k, 0) + v
        for k, v in o.bytes_by_opcode.items():
            self.bytes_by_opcode[k] = self.bytes_by_opcode.get(k, 0) + v
        for k, v in o.coll_by_shape.items():
            self.coll_by_shape[k] = self.coll_by_shape.get(k, 0) + v
        self.unresolved_loops += o.unresolved_loops
        return self


def _collective_kind(opcode: str) -> str | None:
    base = opcode[:-6] if opcode.endswith("-start") else opcode
    base = base[:-5] if base.endswith("-done") else base
    return base if base in _COLLECTIVES else None


def analyze_hlo(hlo: str) -> HloCost:
    comps = _parse_computations(hlo)

    def operand_bytes(comp, ins: Instr) -> int:
        table = comps[comp]["instrs"]
        return sum(table[o].out_bytes for o in ins.operands if o in table)

    # Loop trip counts: lax.scan lowers to `while` whose condition computation
    # ends in `compare(iter, K)` with K a scalar constant. Resolve K through
    # the condition computation's symbol table (constant -> name -> compare
    # operand); fall back to the max scalar constant in the computation.
    cond_consts: dict = {}
    for cname, comp in comps.items():
        consts: dict = {}
        compare_consts: list = []
        for ins in comp["instrs"].values():
            if ins.opcode == "constant":
                mc = re.fullmatch(r"\s*(\d+)\s*", ins.operand_txt or "")
                if mc:
                    consts[ins.name] = int(mc.group(1))
        for ins in comp["instrs"].values():
            if ins.opcode == "compare":
                for op in ins.operands:
                    if op in consts:
                        compare_consts.append(consts[op])
        if compare_consts:
            cond_consts[cname] = compare_consts
        elif consts:
            cond_consts[cname] = list(consts.values())
    # Raw-text fallback (constants inlined into the compare line).
    cur = None
    for raw in hlo.splitlines():
        hm = _HEADER_RE.match(raw.strip())
        if hm and raw.strip().endswith("{"):
            cur = hm.group(2)
            continue
        if raw.strip() == "}":
            cur = None
            continue
        if cur is not None and cur not in cond_consts:
            for m in re.finditer(r"constant\((\d+)\)", raw):
                cond_consts.setdefault(cur, []).append(int(m.group(1)))

    def fusion_operand_bytes(comp_name: str, ins: Instr) -> int:
        """Bytes a fusion actually READS per operand.

        A scan-over-layers body receives the full stacked (n_layers, ...)
        parameter arrays but reads only the current layer's slice: when a
        fusion operand's corresponding parameter inside the called
        computation feeds ONLY dynamic-slice/slice/gather ops, charge the
        sliced size instead of the full array — that is what TPU HBM
        streams. Everything else is charged at full operand size."""
        table = comps[comp_name]["instrs"]
        m = re.search(r"calls=%?([\w.\-]+)", ins.attrs)
        sub = comps.get(m.group(1)) if m else None
        if sub is None:
            return sum(table[o].out_bytes for o in ins.operands
                       if o in table)
        # parameter index -> instruction, and a consumer map
        params = {}
        for si in sub["instrs"].values():
            if si.opcode == "parameter":
                pm = re.fullmatch(r"\s*(\d+)\s*", si.operand_txt or "")
                if pm:
                    params[int(pm.group(1))] = si.name
        consumers: dict = {}
        for si in sub["instrs"].values():
            for op in si.operands:
                consumers.setdefault(op, []).append(si)
        total = 0
        for idx, oname in enumerate(ins.operands):
            full = table[oname].out_bytes if oname in table else 0
            pname = params.get(idx)
            cons = consumers.get(pname, []) if pname else []
            if cons and all(c.opcode in ("dynamic-slice", "slice", "gather")
                            for c in cons):
                total += min(full, sum(c.out_bytes for c in cons))
            else:
                total += full
        return total

    def visit(comp_name: str, depth: int = 0,
              flops_only: bool = False) -> HloCost:
        cost = HloCost()
        if comp_name not in comps or depth > 24:
            return cost
        for ins in comps[comp_name]["instrs"].values():
            kind = _collective_kind(ins.opcode)
            if kind and not ins.opcode.endswith("-done") \
                    and not flops_only:
                b = operand_bytes(comp_name, ins)
                cost.coll_by_kind[kind] = cost.coll_by_kind.get(kind, 0) + b
                skey = f"{kind}:{int(b)}"
                cost.coll_by_shape[skey] = cost.coll_by_shape.get(skey, 0) + b
            if ins.opcode == "dot":
                table = comps[comp_name]["instrs"]
                lhs = table.get(ins.operands[0]) if ins.operands else None
                contracted = 1
                if lhs is not None:
                    for d in _dims_attr(ins.attrs, "lhs_contracting_dims"):
                        if d < len(lhs.out_dims):
                            contracted *= lhs.out_dims[d]
                out_elems = 1
                for d in ins.out_dims:
                    out_elems *= d
                cost.flops += 2.0 * out_elems * contracted
            if not flops_only and ins.opcode in _BYTES_OPS:
                mode = _BYTES_OPS[ins.opcode]
                if ins.opcode == "fusion":
                    nb = ins.out_bytes + fusion_operand_bytes(comp_name, ins)
                elif mode == "io":
                    nb = ins.out_bytes + operand_bytes(comp_name, ins)
                elif mode == "o":
                    nb = ins.out_bytes
                else:               # "u" — DUS: update operand only
                    table = comps[comp_name]["instrs"]
                    if len(ins.operands) >= 2 and ins.operands[1] in table:
                        nb = table[ins.operands[1]].out_bytes
                    else:
                        nb = ins.out_bytes
                cost.bytes += nb
                cost.bytes_by_opcode[ins.opcode] = \
                    cost.bytes_by_opcode.get(ins.opcode, 0) + nb
            # --- descend ---
            if ins.opcode == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", ins.attrs)
                if m:
                    sub = visit(m.group(1), depth + 1, flops_only=True)
                    cost.flops += sub.flops
            elif ins.opcode == "while":
                mb = re.search(r"body=%?([\w.\-]+)", ins.attrs)
                mc = re.search(r"condition=%?([\w.\-]+)", ins.attrs)
                if mb:
                    trips = None
                    if mc:
                        vals = cond_consts.get(mc.group(1), [])
                        trips = max(vals) if vals else None
                    if trips is None:
                        trips = 1
                        cost.unresolved_loops += 1
                    sub = visit(mb.group(1), depth + 1, flops_only)
                    cost += sub.scaled(trips)
            elif ins.opcode in ("call", "async-start"):
                m = re.search(r"to_apply=%?([\w.\-]+)", ins.attrs)
                if m:
                    cost += visit(m.group(1), depth + 1, flops_only)
            elif ins.opcode == "conditional":
                for m in re.finditer(r"%([\w.\-]+)", ins.attrs):
                    if m.group(1) in comps:
                        cost += visit(m.group(1), depth + 1, flops_only)
        return cost

    entry = next((n for n, c in comps.items() if c["entry"]), None)
    return visit(entry) if entry else HloCost()


# Backwards-compatible collective summary --------------------------------


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    total_bytes: int
    unresolved_loops: int


def collective_bytes(hlo: str) -> CollectiveStats:
    c = analyze_hlo(hlo)
    return CollectiveStats(c.coll_by_kind, int(c.coll_bytes),
                           c.unresolved_loops)


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    flops: float                  # total HLO dot-FLOPs (global, all devices)
    hbm_bytes: float              # total bytes accessed (global)
    coll_bytes: float             # total collective bytes (global)
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    bound: str
    model_flops: float = 0.0

    @property
    def dominant_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_fraction(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    def row(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "chips": self.chips,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "bound": self.bound,
            "model_flops": self.model_flops,
            "useful_fraction": self.useful_fraction,
        }


def roofline_from_cost(cost: HloCost, chips: int, *,
                       model_flops: float = 0.0) -> Roofline:
    """Per-device HloCost -> global three-term roofline."""
    flops = cost.flops * chips
    hbm = cost.bytes * chips
    coll = cost.coll_bytes * chips
    compute_s = flops / (chips * PEAK_FLOPS_BF16)
    memory_s = hbm / (chips * HBM_BW)
    collective_s = coll / (chips * ICI_BW)
    bound = max((("compute", compute_s), ("memory", memory_s),
                 ("collective", collective_s)), key=lambda kv: kv[1])[0]
    return Roofline(flops, hbm, coll, chips, compute_s, memory_s,
                    collective_s, bound, model_flops)


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D train (N = active params), 2·N·tokens inference,
    PLUS the causal-attention score/value FLOPs (2·2·b·s²·h·hd·½ forward) —
    at 32k context the attention term dominates the weight term, so leaving
    it out would make the useful-fraction metric meaningless for the
    prefill/long-context cells."""
    n_active = active_param_count(cfg)
    tokens = shape.global_batch * shape.seq_len
    b, s = shape.global_batch, shape.seq_len
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    attn_fwd = 2.0 * 2.0 * b * s * s * h * hd * 0.5   # QK^T + PV, causal
    if cfg.family == "ssm":
        attn_fwd = 0.0
    elif cfg.family == "hybrid":
        # only the shared block invocations attend
        from repro.models import lm as lm_mod
        attn_fwd *= lm_mod.n_shared_invocations(cfg)
    else:
        attn_fwd *= cfg.n_layers
    if shape.kind == "train":
        return 6.0 * n_active * tokens + 3.0 * attn_fwd
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens + attn_fwd
    # decode: 1 new token attends to the full cache
    attn_dec = 2.0 * 2.0 * b * s * h * hd
    if cfg.family == "ssm":
        attn_dec = 0.0
    elif cfg.family == "hybrid":
        from repro.models import lm as lm_mod
        attn_dec *= lm_mod.n_shared_invocations(cfg)
    else:
        attn_dec *= cfg.n_layers
    return 2.0 * n_active * shape.global_batch + attn_dec


def _spec_leaves_with_paths(cfg):
    import jax

    from repro.models import lm as lm_mod
    from repro.models.params import ParamSpec
    specs = lm_mod.lm_param_specs(cfg)
    flat, _ = jax.tree.flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return [([str(getattr(p, "key", "")) for p in path], s)
            for path, s in flat]


def param_count(cfg) -> int:
    import math
    return sum(math.prod(s.shape) for _, s in _spec_leaves_with_paths(cfg))


def active_param_count(cfg) -> int:
    """Active params per token (MoE: top_k of the routed expert stack +
    the rest, shared experts and leading dense layers included)."""
    import math
    total = param_count(cfg)
    if cfg.family != "moe" or cfg.n_experts == 0:
        return total
    expert = sum(
        math.prod(s.shape) for keys, s in _spec_leaves_with_paths(cfg)
        if keys[:2] == ["blocks", "ffn"] and keys[2:] in (["wi"], ["wo"]))
    active_expert = expert * cfg.top_k / cfg.n_experts
    return int(total - expert + active_expert)
