"""HBM observatory: liveness-based peak-memory prediction over HLO.

The perf gate prices every *second* hermetically (roofline step-time,
replica_groups-exact comms) but, until this module, not a single byte
of live HBM — the ROADMAP's headline memory claims ("68.5MB/device is
the memory plan", tensor-sharded serving "fits one host's HBM") were
discoverable only by paying a full compile on the chip and
OOMing.  This module closes that gap over the SAME parsed HLO the
attribution/comms pipeline already walks (``attribution.parse_hlo`` on
``Compiled.as_text()``), with no hardware and no jax import.

Liveness rule (scheduled modules carry ``is_scheduled=true``, so
instruction order IS the schedule):

- every instruction *defines* its output buffer (output-shape bytes
  only) at its position and the buffer is *freed after its last use*;
- entry parameters are caller-owned: live for the whole program;
- the ROOT's buffers live to the end (they are the outputs);
- pure-aliasing opcodes (tuple / get-tuple-element / bitcast / while /
  the ``*-done`` halves of async collectives / opt-barrier) define no
  storage — uses of their result count as uses of the underlying
  buffers, so a get-tuple-element chain keeps its source alive;
- donation (the ``input_output_alias`` module header) credits the
  donated argument's bytes against the aliased output's definition —
  XLA reuses the argument buffer in place;
- fusions/calls are priced at the call site: the fusion's output
  charges there, and the callee's *transient* peak (its internal
  temporaries, computed once per computation and memoized) spikes at
  the call instruction without outliving it.

Peak = max over instructions of (live bytes + this definition +
callee transient).  The live set AT the peak instruction is attributed
per component through ``resolve_component`` — parameter buffers split
into params / optimizer / batch via the caller-supplied
``input_groups`` leaf counts, collective-produced buffers become
``comms-staging``, everything else lands on its model component
(``backbone``, ``roi-bwd``, …).

Blind spots (documented in ARCHITECTURE.md §HBM observatory): XLA may
rematerialize or reorder under memory pressure, so this is an
upper-ish bound, not an allocator replay; scoped-VMEM Pallas buffers
are not priced; the runtime's reserved HBM slice is not subtracted
from capacity.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from eksml_tpu.profiling.attribution import (
    HloAttribution, Instr, is_collective_opcode)

# Prometheus-style gauge names for the live counterpart (satellite):
# published from device.memory_stats() at fit log steps — best-effort,
# silently absent on backends that do not report (CPU returns None).
HBM_IN_USE_GAUGE = "eksml_train_hbm_bytes_in_use"
HBM_PEAK_GAUGE = "eksml_train_hbm_peak_bytes"

# Opcodes whose result is a view of (one of) their operands — they
# define no storage; liveness flows through to the underlying buffers.
# ``while`` is here because XLA aliases the loop state input/output
# in place; the per-iteration double-buffering shows up as the body's
# transient instead.
_ALIAS_OPS = frozenset((
    "tuple", "get-tuple-element", "bitcast", "while", "opt-barrier",
    "after-all",
    "all-reduce-done", "all-gather-done", "collective-permute-done",
    "reduce-scatter-done", "all-to-all-done", "copy-done",
))

# one `{out_index}: (param_number, {param_index}, kind)` pair inside
# the input_output_alias header attribute
_ALIAS_PAIR_RE = re.compile(
    r"\{([0-9, ]*)\}:\s*\((\d+),\s*\{[0-9, ]*\},?\s*[\w-]*\)")

_TIMELINE_POINTS = 64


def parse_input_output_alias(hlo_text: str) -> Dict[Tuple[int, ...], int]:
    """Module header ``input_output_alias={ {0}: (1, {}, may-alias) }``
    → {output index tuple: parameter number}.  The whole-output alias
    spells its index as the empty tuple.  Missing header → {}."""
    for line in hlo_text.splitlines():
        if not line.startswith("HloModule"):
            continue
        if "input_output_alias=" not in line:
            return {}
        seg = line.split("input_output_alias=", 1)[1]
        out: Dict[Tuple[int, ...], int] = {}
        for m in _ALIAS_PAIR_RE.finditer(seg):
            idx = tuple(int(x) for x in
                        m.group(1).replace(" ", "").split(",") if x)
            out[idx] = int(m.group(2))
        return out
    return {}


def _underlying_map(instrs: List[Instr]) -> Dict[str, Tuple[str, ...]]:
    """name → the real storage buffer names its value occupies, with
    alias opcodes resolved through (a tuple's value spans ALL its
    elements' buffers; a get-tuple-element keeps its whole source
    tuple pinned — element-precise tuple liveness is out of scope,
    an accepted over-approximation)."""
    by_name = {i.name: i for i in instrs}
    cache: Dict[str, Tuple[str, ...]] = {}

    def resolve(name: str) -> Tuple[str, ...]:
        got = cache.get(name)
        if got is not None:
            return got
        ins = by_name.get(name)
        if ins is None or ins.opcode not in _ALIAS_OPS:
            cache[name] = (name,)
            return cache[name]
        cache[name] = ()            # cycle guard (SSA makes this moot)
        seen: Dict[str, None] = {}
        for op in ins.operands:
            for u in resolve(op):
                seen[u] = None
        cache[name] = tuple(seen)
        return cache[name]

    for i in instrs:
        resolve(i.name)
    return cache


def _find_root(instrs: List[Instr]) -> Optional[Instr]:
    for ins in instrs:
        if ins.is_root:
            return ins
    return instrs[-1] if instrs else None


class _TransientWalker:
    """Memoized per-computation transient peak: the internal
    temporaries a fusion/call/while body holds beyond its operands and
    its own output (both priced at the call site)."""

    def __init__(self, comps: Dict[str, List[Instr]]):
        self.comps = comps
        self._cache: Dict[str, float] = {}

    def transient(self, comp_name: str, _stack: Tuple[str, ...] = ()
                  ) -> float:
        got = self._cache.get(comp_name)
        if got is not None:
            return got
        if comp_name in _stack or comp_name not in self.comps:
            return 0.0
        instrs = self.comps[comp_name]
        under = _underlying_map(instrs)
        root = _find_root(instrs)
        last_use: Dict[str, int] = {}
        for idx, ins in enumerate(instrs):
            for op in ins.operands:
                for u in under.get(op, (op,)):
                    last_use[u] = idx
        live = 0.0
        peak = 0.0
        charged: Dict[str, float] = {}
        free_at: Dict[int, List[str]] = {}
        for name, idx in last_use.items():
            free_at.setdefault(idx, []).append(name)
        stack = _stack + (comp_name,)
        for idx, ins in enumerate(instrs):
            tr = sum(self.transient(c, stack) for c in ins.calls)
            if (ins.opcode == "parameter" or ins.opcode in _ALIAS_OPS
                    or ins is root):
                charge = 0.0     # operands/output are caller-priced
            else:
                charge = ins.out_bytes
            peak = max(peak, live + charge + tr)
            charged[ins.name] = charge
            live += charge
            for name in free_at.get(idx, ()):
                live -= charged.get(name, 0.0)
        self._cache[comp_name] = peak
        return peak


def analyze_memory(hlo_text: str,
                   attr: Optional[HloAttribution] = None,
                   input_groups: Optional[Sequence[Tuple[str, int]]]
                   = None) -> Dict[str, Any]:
    """Liveness walk over the entry computation → the ``hbm`` record
    (sans capacity — the predictor joins that from the chip spec).

    ``input_groups`` labels entry parameters by flattened-leaf count in
    signature order — e.g. ``[("params", 312), ("optimizer", 624),
    ("batch", 7)]`` from ``lower_train_step`` — so parameter buffers
    attribute to params/optimizer/batch instead of one "inputs" pool.
    """
    attr = attr if attr is not None else HloAttribution(hlo_text)
    entry = attr.entry or next(iter(attr.comps))
    instrs = attr.comps[entry]
    if not instrs:
        return {"peak_hbm_bytes": 0, "live_at_peak_by_component": {},
                "timeline": [], "n_instructions": 0}
    under = _underlying_map(instrs)
    by_name = {i.name: i for i in instrs}
    root = _find_root(instrs)
    end = len(instrs)

    last_use: Dict[str, int] = {}
    for idx, ins in enumerate(instrs):
        for op in ins.operands:
            for u in under.get(op, (op,)):
                last_use[u] = idx
    # entry params are caller-owned; ROOT buffers are the outputs
    for ins in instrs:
        if ins.opcode == "parameter":
            last_use[ins.name] = end
    if root is not None:
        for u in under.get(root.name, (root.name,)):
            last_use[u] = end
        last_use[root.name] = end

    # donation: output index → producer buffer, credited param bytes
    params_by_number = {ins.param_number: ins for ins in instrs
                        if ins.opcode == "parameter"
                        and ins.param_number is not None}
    root_elems = (root.operands if root is not None
                  and root.opcode == "tuple" else None)
    credits: Dict[str, float] = {}
    for out_idx, pnum in parse_input_output_alias(hlo_text).items():
        pins = params_by_number.get(pnum)
        if pins is None or root is None:
            continue
        if out_idx and root_elems and out_idx[0] < len(root_elems):
            target = root_elems[out_idx[0]]
        else:
            target = root.name
        for u in under.get(target, (target,)):
            # credit the first underlying buffer once — nested tuple
            # indices beyond the leading one are collapsed (blind spot)
            credits[u] = credits.get(u, 0.0) + pins.out_bytes
            break

    # parameter buffers → input_groups labels by signature order
    param_label: Dict[str, str] = {}
    params_sorted = sorted(
        (i for i in instrs if i.opcode == "parameter"),
        key=lambda i: (i.param_number if i.param_number is not None
                       else 1 << 30))
    if input_groups:
        k = 0
        for gname, count in input_groups:
            for _ in range(int(count)):
                if k >= len(params_sorted):
                    break
                param_label[params_sorted[k].name] = str(gname)
                k += 1
        tail = str(input_groups[-1][0])
        for i in range(k, len(params_sorted)):
            param_label[params_sorted[i].name] = tail
    else:
        for p in params_sorted:
            param_label[p.name] = "inputs"

    walker = _TransientWalker(attr.comps)
    free_at: Dict[int, List[str]] = {}
    for name, idx in last_use.items():
        if idx < end:
            free_at.setdefault(idx, []).append(name)

    def charge_of(ins: Instr) -> float:
        if ins.opcode in _ALIAS_OPS:
            return 0.0
        raw = ins.out_bytes
        credit = min(raw, credits.get(ins.name, 0.0))
        return raw - credit

    donated = 0.0
    live = 0.0
    peak = -1.0
    peak_idx = 0
    peak_transient = 0.0
    timeline_raw: List[float] = []
    charged: Dict[str, float] = {}
    for idx, ins in enumerate(instrs):
        tr = sum(walker.transient(c) for c in ins.calls)
        charge = charge_of(ins)
        if credits.get(ins.name) and ins.opcode not in _ALIAS_OPS:
            donated += ins.out_bytes - charge
        spike = live + charge + tr
        timeline_raw.append(spike)
        if spike > peak:
            peak, peak_idx, peak_transient = spike, idx, tr
        charged[ins.name] = charge
        live += charge
        for name in free_at.get(idx, ()):
            live -= charged.get(name, 0.0)

    # second pass: reconstruct the live set AT the peak instruction
    alive: Dict[str, float] = {}
    for idx, ins in enumerate(instrs[:peak_idx]):
        c = charged.get(ins.name, 0.0)
        if c > 0:
            alive[ins.name] = c
        for name in free_at.get(idx, ()):
            alive.pop(name, None)
    peak_ins = instrs[peak_idx]
    own = charged.get(peak_ins.name, 0.0)
    if own > 0:
        alive[peak_ins.name] = alive.get(peak_ins.name, 0.0) + own

    by_comp: Dict[str, float] = {}
    for name, c in alive.items():
        ins = by_name.get(name)
        if ins is None:
            continue
        if ins.opcode == "parameter":
            comp = param_label.get(name, "inputs")
        elif is_collective_opcode(ins.opcode):
            comp = "comms-staging"
        else:
            comp = attr.instr_component.get(name) or "other"
        by_comp[comp] = by_comp.get(comp, 0.0) + c
    if peak_transient > 0:
        comp = attr.instr_component.get(peak_ins.name) or "other"
        by_comp[comp] = by_comp.get(comp, 0.0) + peak_transient

    return {
        "peak_hbm_bytes": int(peak if peak > 0 else 0),
        "peak_instruction": peak_ins.name,
        "peak_opcode": peak_ins.opcode,
        "peak_index": peak_idx,
        "donated_bytes": int(donated),
        "parameter_bytes": int(sum(p.out_bytes for p in params_sorted)),
        "live_at_peak_by_component": {
            k: int(v) for k, v in
            sorted(by_comp.items(), key=lambda kv: -kv[1])},
        "timeline": _downsample_timeline(timeline_raw, peak_idx),
        "n_instructions": end,
    }


def _downsample_timeline(vals: List[float], peak_idx: int,
                         n: int = _TIMELINE_POINTS
                         ) -> List[Dict[str, int]]:
    """≤n evenly-spaced (index, live_bytes) samples, peak always
    included — enough shape for the run_report sparkline without
    banking one row per instruction."""
    if not vals:
        return []
    total = len(vals)
    step = max(1, total // n)
    picked = sorted(set(range(0, total, step)) | {peak_idx, total - 1})
    return [{"index": i, "live_bytes": int(vals[i])} for i in picked]


def top_components(hbm: Dict[str, Any], n: int = 3) -> str:
    """'backbone 12.3MB, params 8.1MB, roi-bwd 4.0MB' — the naming
    half of every memory verdict message."""
    comps = (hbm or {}).get("live_at_peak_by_component") or {}
    parts = [f"{k} {v / 1e6:.1f}MB"
             for k, v in list(comps.items())[:n]]
    return ", ".join(parts) if parts else "no attribution"


def publish_hbm_gauges(device: Any) -> Optional[Dict[str, int]]:
    """Best-effort live gauges from ``device.memory_stats()``.

    TPU backends report ``bytes_in_use`` / ``peak_bytes_in_use``; CPU
    returns None and some plugins omit the keys or raise — every one
    of those is a SILENT no-op (test-pinned), because a missing gauge
    must never take down a training loop.  Returns the published
    values (for the predicted-vs-measured fit-log line) or None."""
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    in_use = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use")
    if in_use is None and peak is None:
        return None
    from eksml_tpu import telemetry
    reg = telemetry.default_registry()
    out: Dict[str, int] = {}
    if in_use is not None:
        reg.gauge(HBM_IN_USE_GAUGE,
                  "live HBM bytes in use on local device 0"
                  ).set(float(in_use))
        out["bytes_in_use"] = int(in_use)
    if peak is not None:
        reg.gauge(HBM_PEAK_GAUGE,
                  "peak HBM bytes in use on local device 0"
                  ).set(float(peak))
        out["peak_bytes"] = int(peak)
    return out
