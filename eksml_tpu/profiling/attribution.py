"""HLO cost attribution: name every fusion by model component.

Round-5 post-mortem (VERDICT weak #3): the banked profile attributed
86.78% of device time to "other" with top ops named "5"/"2"/"23", and
three optimizations projected from that trace landed step-time-neutral.
The trace was unreadable because XLA fusion names carry no model
semantics — the semantics live in the per-op ``metadata={op_name=...}``
source paths, which record the flax module path and every
``jax.named_scope`` active when the op was traced.

This module closes that gap without hardware: it parses the compiled
HLO text (``jax.stages.Compiled.as_text()``), assigns each instruction
a *modeled cost* (roofline proxy: bytes touched + flops at the chip's
arithmetic intensity), resolves each instruction's component from its
``op_name`` path (transpose-aware, so ``roi-fwd`` and ``roi-bwd`` are
distinct), and aggregates — producing

- :func:`attribution_map`: HLO instruction name → component, the table
  ``tools/trace_summary.py`` uses to resolve trace event names ("5",
  "fusion.23") into ``rpn-nms`` / ``roi-bwd`` / ``fpn-conv-bwd`` …;
- :func:`component_table`: per-component modeled-cost breakdown with a
  bounded "other" bucket — the compile-time attribution the round-5
  trace could not provide (asserted ≤30% in tests/test_profiling.py).

The op_name scopes it keys on are threaded through ``models/*``,
``ops/*`` and ``train.py`` via ``jax.named_scope`` (grep SCOPE_RULES
below for the contract).  Pure text processing: no jax import, safe to
run on a banked ``hlo.txt`` artifact from any backend.
"""

from __future__ import annotations

import json
import re
from typing import Dict, List, Optional, Tuple

from eksml_tpu.fsio import atomic_write_json

# bytes per element for HLO shape tokens
DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

# Roofline arithmetic intensity used to fold flops into the byte-cost
# proxy: v5e bf16 peak 197 Tflop/s over ~819 GB/s HBM ≈ 240 flop/byte.
# Only the RATIO matters (it decides how much a conv outweighs an
# equally-sized elementwise op); attribution percentages are insensitive
# to factor-of-2 errors here.
FLOPS_PER_BYTE = 240.0

# Opcodes that are pure structure — no data touched at runtime (or the
# cost is counted inside the called computation instead).  The *-done
# halves of async collectives are here too: the traffic is counted on
# the matching *-start, and a done carrying the full output shape
# would double every async collective's bytes.
_CONTAINER_OPS = frozenset((
    "fusion", "call", "while", "conditional", "tuple",
    "get-tuple-element", "parameter", "constant", "bitcast",
    "after-all", "opt-barrier",
    "all-reduce-done", "all-gather-done", "collective-permute-done",
    "reduce-scatter-done", "all-to-all-done",
))

# Collective opcodes → the "allreduce" component regardless of scope
# (XLA inserts them from shardings; they carry no model op_name).
_COLLECTIVE_OPS = frozenset((
    "all-reduce", "reduce-scatter", "all-gather", "collective-permute",
    "all-to-all", "all-reduce-start", "all-gather-start",
    "collective-permute-start", "reduce-scatter-start",
    "all-to-all-start",
))


def is_collective_opcode(opcode: str) -> bool:
    """True for inter-chip collective opcodes — the predictor prices
    these against link bandwidth (ICI), not HBM (predict.py)."""
    return opcode in _COLLECTIVE_OPS


# ---- replica_groups parsing (the communication observatory's input) --
#
# Every collective instruction names its exact participant sets.  Two
# spellings exist in compiled HLO:
#   explicit  replica_groups={{0,1},{2,3}}
#   iota      replica_groups=[4,2]<=[2,2,2]T(0,2,1)
# The iota (v2) form means: enumerate 0..prod(dims)-1, reshape to
# `dims`, transpose by `perm` (T(...) — identity when absent), then
# reshape to G groups of N.  collective-permute spells its topology as
# source_target_pairs={{s,t},...} instead — each pair is a 2-group.
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
    r"(?:T\(([0-9,]+)\))?")
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=\{((?:\{[0-9, ]*\}(?:, ?)?)*)\}")
_PAIRS_RE = re.compile(
    r"source_target_pairs=\{((?:\{\d+, ?\d+\}(?:, ?)?)+)\}")
_GROUP_RE = re.compile(r"\{([0-9, ]*)\}")


def _iota_groups(g: int, n: int, dims: List[int],
                 perm: Optional[List[int]]
                 ) -> Tuple[Tuple[int, ...], ...]:
    """Decode the iota form: iota(prod(dims)) → reshape(dims) →
    transpose(perm) → reshape(g, n).  Pure index arithmetic — no
    array dependency."""
    total = 1
    for d in dims:
        total *= d
    if perm is None:
        flat = list(range(total))
    else:
        tdims = [dims[p] for p in perm]
        strides = [1] * len(dims)
        for i in range(len(dims) - 2, -1, -1):
            strides[i] = strides[i + 1] * dims[i + 1]
        flat = []
        idx = [0] * len(tdims)
        for _ in range(total):
            flat.append(sum(idx[a] * strides[perm[a]]
                            for a in range(len(perm))))
            for a in range(len(tdims) - 1, -1, -1):
                idx[a] += 1
                if idx[a] < tdims[a]:
                    break
                idx[a] = 0
    return tuple(tuple(flat[i * n:(i + 1) * n]) for i in range(g))


def parse_collective_groups(
        line: str) -> Optional[Tuple[Tuple[int, ...], ...]]:
    """One HLO collective line → its exact device-id groups, or None
    when the line carries no group info (``replica_groups={}``, or a
    hand-rolled fixture without the attribute) — callers synthesize a
    plan-sized contiguous group in that case (predict.py)."""
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        g, n = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        perm = ([int(d) for d in m.group(4).split(",")]
                if m.group(4) else None)
        return _iota_groups(g, n, dims, perm)
    m = _REPLICA_GROUPS_RE.search(line)
    if m:
        groups = tuple(
            tuple(int(x) for x in grp.replace(" ", "").split(",")
                  if x)
            for grp in _GROUP_RE.findall(m.group(1)))
        groups = tuple(g for g in groups if g)
        return groups or None
    m = _PAIRS_RE.search(line)
    if m:
        pairs = tuple(
            tuple(int(x) for x in grp.replace(" ", "").split(","))
            for grp in _GROUP_RE.findall(m.group(1)))
        return pairs or None
    return None

# op_name scope → component.  First match wins; searched on the
# lowercased path.  ``bwd_split=True`` components get a "-bwd" suffix
# when the path shows a transpose context (the backward pass).  Scope
# segments may be wrapped in transform labels — ``vmap(rpn_nms)/``,
# ``checkpoint(backbone)/`` — so boundaries accept parens as well as
# path separators.  The scope side of this contract is the set of
# jax.named_scope annotations in models/*, ops/* and train.py — keep
# the two in sync.
SCOPE_RULES: Tuple[Tuple[str, str, bool], ...] = (
    # (component, path regex, bwd_split)
    ("optimizer", r"(^|[/(])optimizer($|[/)])", False),
    ("roi", r"(^|[/(])roi_align($|[/)])", True),
    ("rpn-nms", r"(^|[/(])(rpn_nms|nms)($|[/)])", False),
    ("matching", r"(^|[/(])matching($|[/)])", False),
    ("sampling", r"(^|[/(])sampling($|[/)])", False),
    ("loss", r"(^|[/(])(loss|rpn_loss|frcnn_loss|mask_loss)($|[/)])",
     False),
    ("input-norm", r"(^|[/(])input_norm($|[/)])", False),
    ("fpn-conv", r"(^|[/(])fpn($|[/)])", True),
    ("backbone", r"(^|[/(])backbone($|[/)])", True),
    ("rpn-head", r"(^|[/(])rpn($|[/)])", True),
    ("box-head", r"(^|[/(])(fastrcnn|cascade\d*)($|[/)])", True),
    ("mask-head", r"(^|[/(])maskrcnn($|[/)])", True),
    ("mask-targets", r"(^|[/(])mask_targets($|[/)])", False),
    # JoyAI-LLM-Flash's scopes (models/lm/model.py)
    ("mla-proj", r"(^|[/(])mla($|[/)])", True),
    ("mla-core", r"(^|[/(])mla_core($|[/)])", True),
    ("moe-route", r"(^|[/(])moe_route($|[/)])", False),
    ("moe-dispatch", r"(^|[/(])moe_dispatch($|[/)])", True),
    ("moe-experts", r"(^|[/(])moe_experts($|[/)])", True),
    ("moe-combine", r"(^|[/(])moe_combine($|[/)])", True),
    ("moe-shared", r"(^|[/(])moe_shared($|[/)])", True),
    ("dense-mlp", r"(^|[/(])dense_mlp($|[/)])", True),
    ("mtp", r"(^|[/(])mtp($|[/)])", True),
    ("lm-loss", r"(^|[/(])lm_loss($|[/)])", False),
    # the looped model's scopes (models/lm/ouro.py)
    ("loop-attn", r"(^|[/(])loop_attn($|[/)])", True),
    ("loop-attn-core", r"(^|[/(])loop_attn_core($|[/)])", True),
    ("loop-mlp", r"(^|[/(])loop_mlp($|[/)])", True),
    ("loop-head", r"(^|[/(])loop_head($|[/)])", True),
    ("loop-exit", r"(^|[/(])loop_exit($|[/)])", False),
    # Laguna's attention scopes (models/lm/laguna.py; its expert layer
    # and loss carry JoyAI's)
    ("gqa-proj", r"(^|[/(])gqa($|[/)])", True),
    ("gqa-core-full", r"(^|[/(])gqa_core_full($|[/)])", True),
    ("gqa-core-window", r"(^|[/(])gqa_core_window($|[/)])", True),
)
_SCOPE_RULES_C = tuple((comp, re.compile(pat), bwd)
                       for comp, pat, bwd in SCOPE_RULES)

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(sorted(DTYPE_BYTES, key=len, reverse=True))
    + r")\[([0-9,]*)\]")
# params may contain nested parens (tuple-typed while-body params), so
# match greedily to the LAST ') ->' on the line
_COMP_HDR_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->", re.M)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+"
    r"([\w\-]+)\(")
_META_RE = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLS_SINGLE_RE = re.compile(
    r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_CALLS_LIST_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_ROOT_RE = re.compile(r"\s*ROOT\b")


class Instr:
    __slots__ = ("name", "opcode", "op_name", "calls", "operands",
                 "cost", "flops", "bytes", "groups", "out_bytes",
                 "param_number", "is_root")

    def __init__(self, name, opcode, op_name, calls, operands, cost,
                 flops, nbytes, groups=None, out_bytes=0.0,
                 param_number=None, is_root=False):
        self.name = name
        self.opcode = opcode
        self.op_name = op_name          # metadata path ("" if absent)
        self.calls = calls              # called computation names
        self.operands = operands        # operand instruction names
        self.cost = cost                # modeled roofline cost (bytes-eq)
        self.flops = flops
        self.bytes = nbytes
        self.groups = groups            # exact replica_groups (or None)
        self.out_bytes = out_bytes      # OUTPUT shape bytes only — the
        #                                 buffer this op defines (the
        #                                 liveness unit in memory.py);
        #                                 `bytes` above sums every shape
        #                                 on the line (cost proxy)
        self.param_number = param_number  # parameter(N) index or None
        self.is_root = is_root          # computation ROOT marker


def _shape_elems_bytes(tokens: List[Tuple[str, str]]) -> int:
    total = 0
    for dtype, dims in tokens:
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def _elems(token: Tuple[str, str]) -> int:
    n = 1
    if token[1]:
        for d in token[1].split(","):
            n *= int(d)
    return n


def _modeled_flops(opcode: str, line: str,
                   shapes: List[Tuple[str, str]]) -> float:
    """Best-effort flop estimate from the instruction line alone.

    convolution: out_elems × (kernel_elems / out_channels) × 2 — the
    per-output-element MAC count, with out_channels read from the
    output's last dim (NHWC convention; the grad-wrt-kernel conv
    misreads this by the batch factor, which the roofline fold
    tolerates).  dot: 2 × out_elems × K with K the product of the lhs
    contracting dims.  Everything else: 1 flop per output element.
    """
    if not shapes:
        return 0.0
    out = shapes[0]
    out_elems = _elems(out)
    if opcode == "convolution" and len(shapes) >= 3:
        kernel = shapes[2]
        cout = int(kernel[1].split(",")[-1]) if kernel[1] else 1
        return 2.0 * out_elems * (_elems(kernel) / max(1, cout))
    if opcode == "dot" and len(shapes) >= 2:
        lhs = shapes[1]
        m = re.search(r"lhs_contracting_dims=\{([0-9,]+)\}", line)
        k = 1
        if m and lhs[1]:
            dims = lhs[1].split(",")
            for i in m.group(1).split(","):
                i = int(i)
                if i < len(dims):
                    k *= int(dims[i])
        return 2.0 * out_elems * k
    return float(out_elems)


def parse_hlo(text: str) -> Tuple[Dict[str, List[Instr]], Optional[str]]:
    """HLO text → ({computation name: [Instr]}, entry computation name).

    Tolerant line-oriented parsing of the stable parts of the format
    (name/shape/opcode/metadata/calls); anything unrecognized is
    skipped rather than raised on — a truncated artifact should still
    attribute what it can.
    """
    comps: Dict[str, List[Instr]] = {}
    entry = None
    cur: Optional[List[Instr]] = None
    for line in text.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr and line.rstrip().endswith("{"):
            name = hdr.group(2)
            cur = comps.setdefault(name, [])
            if hdr.group(1):
                entry = name
            continue
        m = _INSTR_RE.match(line)
        if m is None or cur is None:
            continue
        name, _shape, opcode = m.group(1), m.group(2), m.group(3)
        shapes = _SHAPE_RE.findall(line)
        meta = _META_RE.search(line)
        op_name = meta.group(1).replace('\\"', '"') if meta else ""
        calls = _CALLS_SINGLE_RE.findall(line)
        for grp in _CALLS_LIST_RE.findall(line):
            calls += [c.strip().lstrip("%") for c in grp.split(",")]
        operands = []
        paren = line[m.end():]
        # operand names sit inside the first (...) group; a rough split
        # at "), " suffices because we only use operands for neighbor
        # inheritance (never for cost)
        operands = _OPERAND_RE.findall(paren.split("metadata=")[0])
        if opcode in _CONTAINER_OPS:
            cost = flops = nbytes = 0.0
        else:
            nbytes = float(_shape_elems_bytes(shapes))
            flops = _modeled_flops(opcode, line, shapes)
            cost = nbytes + flops / FLOPS_PER_BYTE
        groups = (parse_collective_groups(line)
                  if opcode in _COLLECTIVE_OPS else None)
        # output-only bytes (group 2 is the result shape, possibly a
        # tuple) — the buffer footprint memory.py tracks; distinct from
        # `nbytes`, which also sums operand shapes on the line
        out_bytes = float(_shape_elems_bytes(_SHAPE_RE.findall(_shape)))
        param_number = None
        if opcode == "parameter":
            pm = re.match(r"\s*(\d+)", line[m.end():])
            if pm:
                param_number = int(pm.group(1))
        is_root = bool(_ROOT_RE.match(line))
        cur.append(Instr(name, opcode, op_name, calls, operands, cost,
                         flops, nbytes, groups, out_bytes, param_number,
                         is_root))
    return comps, entry


def resolve_component(op_name: str, opcode: str = "") -> Optional[str]:
    """op_name metadata path (+ opcode) → component name, or None."""
    if opcode in _COLLECTIVE_OPS:
        return "allreduce"
    if not op_name:
        return None
    path = op_name.lower()
    is_bwd = "transpose(" in path
    # the ROOT module's transform labels — jvp(MaskRCNN),
    # transpose(jvp(MaskRCNN)) — would otherwise collide with the mask
    # HEAD module (flax name "maskrcnn"); strip the wrapped class name
    path = path.replace("jvp(maskrcnn)", "jvp()")
    for comp, pat, bwd_split in _SCOPE_RULES_C:
        if pat.search(path):
            if comp == "roi":
                return "roi-bwd" if is_bwd else "roi-fwd"
            if bwd_split and is_bwd:
                return comp + "-bwd"
            return comp
    return None


class HloAttribution:
    """Parsed + attributed module; the shared engine behind
    :func:`attribution_map` and :func:`component_table`."""

    def __init__(self, hlo_text: str):
        self.comps, self.entry = parse_hlo(hlo_text)
        if not self.comps:
            raise ValueError("no HLO computations found — is this the "
                             "output of Compiled.as_text()?")
        # computation → (total leaf cost, component vote dict)
        self._comp_cost: Dict[str, float] = {}
        self._comp_votes: Dict[str, Dict[str, float]] = {}
        for name in self.comps:
            self._walk(name)
        # per-instruction resolved component: local metadata + fusion
        # votes + neighbor inheritance first, then a top-down pass that
        # pushes the CALL SITE's component into metadata-free called
        # computations (XLA's scatter/sort expanders emit whole while
        # bodies with no op_name — observed: the ROIAlign backward
        # scatter-add loop — while the calling instruction keeps the
        # scope)
        self.instr_component: Dict[str, str] = {}
        resolved = {name: self._attribute_computation(name, instrs)
                    for name, instrs in self.comps.items()}
        inherit: Dict[str, Optional[str]] = {}
        seen = set()
        queue = [n for n in ((self.entry,) if self.entry else ())]
        queue += [n for n in self.comps if n != self.entry]
        while queue:
            comp = queue.pop(0)
            if comp in seen or comp not in self.comps:
                continue
            seen.add(comp)
            inh = inherit.get(comp)
            for ins in self.comps[comp]:
                c = resolved[comp].get(ins.name) or inh
                self.instr_component[ins.name] = c or "other"
                for callee in ins.calls:
                    if callee not in inherit and c:
                        inherit[callee] = c
                    if callee not in seen:
                        queue.insert(0, callee)

    # -- cost/vote aggregation (bottom-up over called computations) ---

    def _walk(self, comp_name: str, _stack=()) -> Tuple[float, Dict]:
        if comp_name in self._comp_cost:
            return self._comp_cost[comp_name], self._comp_votes[comp_name]
        if comp_name in _stack or comp_name not in self.comps:
            return 0.0, {}
        total, votes = 0.0, {}
        for ins in self.comps[comp_name]:
            cost = ins.cost
            sub_votes = None
            if ins.calls:
                for callee in ins.calls:
                    c, v = self._walk(callee, _stack + (comp_name,))
                    cost += c
                    if sub_votes is None:
                        sub_votes = dict(v)
                    else:
                        for k, val in v.items():
                            sub_votes[k] = sub_votes.get(k, 0) + val
            comp = resolve_component(ins.op_name, ins.opcode)
            if comp is not None:
                votes[comp] = votes.get(comp, 0.0) + cost
            elif sub_votes:
                for k, val in sub_votes.items():
                    votes[k] = votes.get(k, 0.0) + val
            total += cost
        self._comp_cost[comp_name] = total
        self._comp_votes[comp_name] = votes
        return total, votes

    def _instr_cost(self, ins: Instr) -> float:
        """Leaf cost plus the full cost of any called computations —
        what this instruction 'spends' at runtime."""
        return ins.cost + sum(self._comp_cost.get(c, 0.0)
                              for c in ins.calls)

    def _instr_component(self, ins: Instr) -> Optional[str]:
        comp = resolve_component(ins.op_name, ins.opcode)
        if comp is not None:
            return comp
        # container (fusion/while/…): dominant component of the body
        votes: Dict[str, float] = {}
        for callee in ins.calls:
            for k, v in self._comp_votes.get(callee, {}).items():
                votes[k] = votes.get(k, 0.0) + v
        if votes:
            return max(votes.items(), key=lambda kv: kv[1])[0]
        return None

    def _attribute_computation(self, name: str,
                               instrs: List[Instr]
                               ) -> Dict[str, Optional[str]]:
        resolved: Dict[str, Optional[str]] = {
            i.name: self._instr_component(i) for i in instrs}
        # Neighbor inheritance: XLA drops metadata from some rewritten
        # instructions (observed: the grad-wrt-kernel convolution loses
        # its op_name while its consumer bitcast keeps it).  Unresolved
        # instructions take the component of their first resolved
        # consumer, then of their first resolved operand — two passes
        # bound the walk.
        by_name = {i.name: i for i in instrs}
        consumers: Dict[str, List[str]] = {}
        for i in instrs:
            for op in i.operands:
                if op in by_name:
                    consumers.setdefault(op, []).append(i.name)
        for _ in range(2):
            for i in instrs:
                if resolved.get(i.name) is not None:
                    continue
                for user in consumers.get(i.name, ()):
                    if resolved.get(user):
                        resolved[i.name] = resolved[user]
                        break
                else:
                    for op in i.operands:
                        if resolved.get(op):
                            resolved[i.name] = resolved[op]
                            break
        return resolved

    # -- public surfaces ----------------------------------------------

    def attribution_map(self) -> Dict[str, str]:
        """Every instruction name (all computations) → component.
        Keys are bare HLO names ('fusion.5'), matching what trace
        event names derive from."""
        return dict(self.instr_component)

    def component_table(self, top_n: int = 10) -> dict:
        """Modeled-cost breakdown by component over the whole module,
        plus the top-N entry instructions with their resolution —
        the 'what should I optimize' table."""
        costs: Dict[str, float] = {}
        for name, instrs in self.comps.items():
            for ins in instrs:
                if ins.cost <= 0:
                    continue
                comp = self.instr_component.get(ins.name) or "other"
                costs[comp] = costs.get(comp, 0.0) + ins.cost
        total = sum(costs.values()) or 1.0
        table = {k: round(100.0 * v / total, 2)
                 for k, v in sorted(costs.items(), key=lambda kv: -kv[1])}
        top = []
        if self.entry:
            ranked = sorted(self.comps[self.entry],
                            key=self._instr_cost, reverse=True)
            for ins in ranked[:top_n]:
                cost = self._instr_cost(ins)
                if cost <= 0:
                    continue
                top.append({
                    "name": ins.name, "opcode": ins.opcode,
                    "component": self.instr_component.get(ins.name,
                                                          "other"),
                    "modeled_pct": round(100.0 * cost / total, 2),
                })
        return {
            "component_pct": table,
            "other_pct": table.get("other", 0.0),
            "top_instructions": top,
            "modeled_total_bytes_eq": round(total, 1),
        }


def attribution_map(hlo_text: str) -> Dict[str, str]:
    return HloAttribution(hlo_text).attribution_map()


def component_table(hlo_text: str, top_n: int = 10) -> dict:
    return HloAttribution(hlo_text).component_table(top_n)


def write_attribution_artifact(hlo_text: str, path: str,
                               extra: Optional[dict] = None) -> dict:
    """Bank {map, component_table, …} as ONE json artifact —
    ``tools/trace_summary.py --attribution`` consumes the map to name
    trace events; the table answers 'where does modeled cost go'
    without any trace at all."""
    attr = HloAttribution(hlo_text)
    payload = {
        "map": attr.attribution_map(),
        "component_table": attr.component_table(),
    }
    if extra:
        payload.update(extra)
    atomic_write_json(path, payload)
    return payload
