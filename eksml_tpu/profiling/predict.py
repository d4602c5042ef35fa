"""AOT roofline prediction: compiled train-step HLO → step time in ms.

Chip time is budgeted, so most PRs land without a chip measurement.
This module is the hermetic stand-in between chip runs: lower the REAL
train step for a named TPU target on CPU (``JAX_PLATFORMS=cpu`` — XLA emits the same program
structure it would ship to the chip), feed the optimized HLO through
the existing attribution parser (attribution.py), and price every
instruction against the target chip's roofline:

- compute ops:    ``t = max(flops / peak_flops, bytes / hbm_bw)``
- collectives:    ``t = bytes × ring_factor(k) / link_bw`` with ``k``
  and the link (ICI / DCN / the mixed staged composition) read from
  the instruction's exact ``replica_groups`` (ISSUE 19,
  :func:`price_collective`) — an all-reduce moves ``2(k-1)/k`` of its
  payload per link, a reduce-scatter/all-gather ``(k-1)/k``.  A
  groupless line falls back to a contiguous group of the sharding
  plan's size (PR 6 ``comm_sizes``) through the same path.

Summing per resolved component (SCOPE_RULES) yields a predicted step
time that is *component-attributed*: a regression names the component
that moved ("backbone-bwd predicted +34%"), not a bare number.

The absolute number is a model, not a measurement — so it ships with
its own honesty check: :func:`calibrate` fits one scale factor per
rung against the banked hardware artifacts (``artifacts/roi_ab_r5.json``,
``bench_rung_1344_b4.json``) and reports how far the per-rung factors
spread from their common fit.  If the model scaled geometry correctly
the factors agree; the spread IS the model error, and it is printed in
every gate run (tools/perf_gate.py) and pinned in
tests/test_perf_gate.py.

Consumers: ``tools/perf_gate.py`` (the CI gate) and ``Trainer.fit``
(the ``eksml_train_predicted_step_time_ms`` gauge).
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, List, Optional, Tuple

from eksml_tpu.profiling import memory
from eksml_tpu.profiling.attribution import (HloAttribution,
                                             is_collective_opcode)

log = logging.getLogger(__name__)

#: the gauge Trainer.fit publishes at the first step compile — ONE
#: definition for trainer and tests
PREDICTED_GAUGE = "eksml_train_predicted_step_time_ms"

# Chip spec table for the roofline terms.  Peak flops are the vendor
# bf16 systolic numbers (benchmark/peaks.json holds the same); f32 runs
# the MXU at half rate.  Link bandwidths are per-chip aggregate ICI
# and the per-host DCN NIC share — the model only needs them to the
# ~2× level (the calibration scale factor absorbs constant error; the
# per-rung spread it cannot absorb is reported as model error).
CHIP_SPECS: Dict[str, Dict[str, Any]] = {
    "v5e": {
        "peak_flops": {"bfloat16": 197e12, "float32": 98.5e12},
        "hbm_bytes_per_sec": 819e9,
        "ici_bytes_per_sec": 200e9,   # 1600 Gbps aggregate
        "dcn_bytes_per_sec": 25e9,
        "hbm_bytes": 16e9,            # 16 GB per chip (capacity gate)
    },
    "v4": {
        "peak_flops": {"bfloat16": 275e12, "float32": 137.5e12},
        "hbm_bytes_per_sec": 1228e9,
        "ici_bytes_per_sec": 300e9,   # 2400 Gbps
        "dcn_bytes_per_sec": 25e9,
        "hbm_bytes": 32e9,
    },
    "v6e": {
        "peak_flops": {"bfloat16": 918e12, "float32": 459e12},
        "hbm_bytes_per_sec": 1640e9,
        "ici_bytes_per_sec": 448e9,   # 3584 Gbps
        "dcn_bytes_per_sec": 25e9,
        "hbm_bytes": 32e9,
    },
}

# jax device_kind → spec name (the strings benchmark/peaks.json keys
# on).  A kind that is not here — "cpu" included — is an error: no
# caller may price a program for a chip it did not run on.
DEVICE_KIND_TO_TARGET = {
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v4": "v4",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}

DEFAULT_TARGET = "v5e"


def load_json(path: str) -> Optional[Dict]:
    """Swallow-errors JSON loader — ONE definition for the calibration
    pairing here and tools/perf_gate.py (a missing or truncated
    artifact reads as absent, never a crash)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def chip_spec(target: str) -> Dict[str, Any]:
    if target not in CHIP_SPECS:
        raise ValueError(
            f"unknown TPU target {target!r}; known: "
            f"{sorted(CHIP_SPECS)}")
    return CHIP_SPECS[target]


def target_for_device_kind(kind: Optional[str]) -> str:
    if kind not in DEVICE_KIND_TO_TARGET:
        raise ValueError(
            f"no chip spec for device kind {kind!r}; known: "
            f"{sorted(DEVICE_KIND_TO_TARGET)}")
    return DEVICE_KIND_TO_TARGET[kind]


def _ring_factor(opcode: str, k: int) -> float:
    """Fraction of the payload each link carries in a ring schedule of
    ``k`` participants.  k=1 → 0 (no traffic)."""
    if k <= 1:
        return 0.0
    if opcode.startswith("all-reduce"):
        return 2.0 * (k - 1) / k
    if opcode.startswith("collective-permute"):
        return 1.0
    # all-gather / reduce-scatter / all-to-all
    return float(k - 1) / k


def hierarchical_allreduce_split(nbytes: float, k: int,
                                 slice_devices: int,
                                 ici: float, dcn: float
                                 ) -> Tuple[float, float]:
    """The three-phase hierarchical all-reduce price split by link:
    → (ici_seconds, dcn_seconds).  ICI carries the in-slice
    reduce-scatter + all-gather over the ``per`` in-slice devices;
    DCN carries the all-reduce of the 1/per-sized partials over the
    ``s = k // per`` slices."""
    per = max(1, int(slice_devices))
    s = max(1, int(k) // per)
    rs = nbytes * _ring_factor("reduce-scatter", per) / ici
    ar = (nbytes / per) * _ring_factor("all-reduce", s) / dcn
    ag = nbytes * _ring_factor("all-gather", per) / ici
    return rs + ag, ar


def hierarchical_allreduce_seconds(nbytes: float, k: int,
                                   slice_devices: int,
                                   ici: float, dcn: float) -> float:
    """Three-phase price of one cross-slice gradient all-reduce under
    the hierarchical exchange (TRAIN.SHARDING.EXCHANGE=
    "hierarchical"): reduce-scatter over the ``per`` in-slice devices
    on ICI, all-reduce of the 1/per-sized partials over the ``s =
    k // per`` slices on DCN, all-gather back on ICI.  Strictly below
    the flat ring (``2(k-1)/k`` of the payload at DCN speed) whenever
    per > 1 — the full gradient never rides the thin link, only one
    slice-reduced copy does."""
    ici_s, dcn_s = hierarchical_allreduce_split(
        nbytes, k, slice_devices, ici, dcn)
    return ici_s + dcn_s


def _group_topology(groups, slice_devices
                    ) -> Tuple[str, int, int, int]:
    """Exact replica_groups → (link, k, ns, per).

    ``link`` classifies which wire the collective rides, purely from
    whether its groups straddle slice boundaries under the slice-major
    device order build_mesh pins (``device_id // slice_devices`` is
    the slice index):

    - ``ici``   — every group stays within one slice;
    - ``dcn``   — groups straddle slices with ONE device per slice
                  (pure cross-slice traffic, e.g. the staged DCN
                  all-reduce of the hierarchical exchange);
    - ``mixed`` — groups straddle slices with >1 device per slice
                  (the flat lowering's single ring over everything —
                  how it is priced is the ``exchange`` knob's job).

    ``k`` is the widest group, ``ns`` the most slices any group
    spans, ``per`` the in-slice device count of a mixed group
    (``k // ns``).  ``slice_devices`` None/0 = single slice:
    everything is ICI."""
    k = max((len(g) for g in groups), default=1)
    if not slice_devices or int(slice_devices) <= 0:
        return "ici", k, 1, k
    per_slice = int(slice_devices)
    ns, max_per, straddles = 1, 1, False
    for g in groups:
        counts: Dict[int, int] = {}
        for d in g:
            s = int(d) // per_slice
            counts[s] = counts.get(s, 0) + 1
        if counts:
            ns = max(ns, len(counts))
            max_per = max(max_per, max(counts.values()))
        if len(counts) > 1:
            straddles = True
    if not straddles:
        return "ici", k, 1, k
    if max_per == 1:
        return "dcn", k, ns, 1
    return "mixed", k, ns, max(1, k // ns)


def classify_group_link(groups, slice_devices) -> str:
    """replica_groups → "ici" / "dcn" / "mixed" (see
    :func:`_group_topology` for the rule)."""
    return _group_topology(groups, slice_devices)[0]


def price_collective(opcode: str, nbytes: float, groups,
                     slice_devices: Optional[int],
                     ici: float, dcn: float,
                     exchange: str = "flat"
                     ) -> Tuple[float, float, float, str, int]:
    """ONE collective's exact-group price →
    (seconds, ici_seconds, dcn_seconds, link, group_size).

    The only link decision on any pricing path — there is no opcode
    heuristic and no ``k > slice_devices`` rule anywhere: an in-slice
    group prices at ICI however wide it is, a one-device-per-slice
    group prices at DCN, and a mixed group (straddling with in-slice
    width) prices per the ``exchange`` knob — ``hierarchical`` as the
    staged composition (all-reduce: the pinned three-phase
    ICI-RS/DCN-AR/ICI-AG; other ops: in-slice phase on ICI + the
    1/per-sized cross-slice phase on DCN), ``flat`` as one ring
    bounded by the slowest link (the counterfactual the multi-slice
    gate prices the SAME HLO against)."""
    link, k, ns, per = _group_topology(groups, slice_devices)
    if link == "ici":
        t = nbytes * _ring_factor(opcode, k) / ici
        return t, t, 0.0, link, k
    if link == "dcn":
        t = nbytes * _ring_factor(opcode, k) / dcn
        return t, 0.0, t, link, k
    if exchange == "hierarchical":
        if opcode.startswith("all-reduce"):
            ici_s, dcn_s = hierarchical_allreduce_split(
                nbytes, k, per, ici, dcn)
        else:
            ici_s = nbytes * _ring_factor(opcode, per) / ici
            dcn_s = (nbytes / per) * _ring_factor(opcode, ns) / dcn
        return ici_s + dcn_s, ici_s, dcn_s, link, k
    t = nbytes * _ring_factor(opcode, k) / dcn
    return t, 0.0, t, link, k


def comm_sizes_for_mesh(mesh_shape: Dict[str, int]) -> Dict[str, int]:
    """Sharding-plan mesh → per-collective participant counts.

    all-gather / reduce-scatter are the param/grad layout moves: they
    ride the STORAGE axes — ``fsdp`` under the fsdp plan, ``model``
    under tensor, and their product under 2d (the plan's
    compute_params/storage_grads constraint pair gathers and scatters
    over every axis the leaf is stored on).  all-reduce is the
    gradient sum over all replicas — ``data × fsdp × model``, times
    the ``slice`` axis when the mesh has one (plan_mesh emits it under
    the hierarchical exchange; batch rows ride every mesh axis,
    sharding.py batch_spec — the strategies change the storage layout,
    never the replica count).  A mesh without a slice axis prices
    exactly as before."""
    fsdp = int(mesh_shape.get("fsdp", 1))
    data = int(mesh_shape.get("data", 1))
    model = int(mesh_shape.get("model", 1))
    slices = int(mesh_shape.get("slice", 1))
    return {
        "all-gather": fsdp * model,
        "reduce-scatter": fsdp * model,
        "all-reduce": data * fsdp * model * slices,
        "collective-permute": 2,
        "all-to-all": max(data * fsdp * model * slices, 1),
    }


def _comm_k(comm_sizes: Dict[str, int], opcode: str) -> int:
    for prefix, k in comm_sizes.items():
        if opcode.startswith(prefix):
            return int(k)
    return 1


def section_of(component: str) -> str:
    """Component → fwd/bwd/comms/optimizer bucket (the headline
    split).  Unresolved "other" cost rides fwd — it is almost always
    input plumbing XLA stripped metadata from."""
    if component == "allreduce":
        return "comms"
    if component == "optimizer":
        return "optimizer"
    if component.endswith("-bwd"):
        return "bwd"
    return "fwd"


def predict_from_hlo(hlo_text: str, target: str = DEFAULT_TARGET,
                     precision: str = "bfloat16",
                     comm_sizes: Optional[Dict[str, int]] = None,
                     slice_devices: Optional[int] = None,
                     exchange: str = "flat",
                     input_groups: Optional[List] = None
                     ) -> Dict[str, Any]:
    """Compiled-HLO text → predicted step time for ``target``.

    Per-instruction roofline summed per attributed component; see the
    module docstring for the cost terms.  Collectives are priced from
    their EXACT ``replica_groups`` (attribution.py parses both the
    explicit and the iota spelling): a group that stays within one
    slice rides ICI however wide it is, a one-device-per-slice group
    rides DCN, and a mixed group prices per ``exchange`` —
    ``hierarchical`` as the staged composition, ``flat`` as one ring
    at the slowest link (:func:`price_collective`; no opcode
    heuristic on any pricing path).  A collective line WITHOUT group
    info (hand-rolled fixtures, ``replica_groups={}``) synthesizes
    one contiguous group of the sharding-plan size from
    ``comm_sizes`` (:func:`comm_sizes_for_mesh`; absent, 2-way) and
    goes through the same group-based path — under slice-major device
    order a contiguous ring straddles slices exactly when it is wider
    than one slice, so groupless pricing matches the historical
    behavior.  ``slice_devices=None`` = single slice, everything
    rides ICI and ``exchange`` is inert — single-slice predictions
    are bit-identical either way (the banked calibration artifacts
    depend on that).

    Besides the totals the prediction carries the communication
    observatory: ``collectives`` (one identity row per priced
    collective — opcode, payload, group topology, link class,
    component, per-link ms, exposed ms) and ``comms_ms`` (the
    ici/dcn/exposed rollup).  Exposed time walks each async
    ``*-start``/``*-done`` pair against the non-collective compute
    scheduled between them: what fits in that window is overlappable,
    the rest is exposed on the critical path; a sync collective (no
    start/done — every CPU lowering) is fully exposed.  The
    ``exposed_dcn_ms`` figure is the hermetic before/after metric for
    a future DCN-overlap optimization.

    The prediction also carries the HBM observatory (``hbm`` section):
    liveness-based peak bytes over the same parsed module, the live
    set at the peak attributed per component, and capacity headroom
    against the chip spec's ``hbm_bytes`` — see
    ``eksml_tpu/profiling/memory.py``.  ``input_groups`` (optional
    ``[(label, leaf_count), ...]`` in entry-signature order, from
    ``lower_*_step`` meta) splits parameter buffers into
    params/optimizer/batch for that attribution."""
    spec = chip_spec(target)
    peak = float(spec["peak_flops"].get(precision)
                 or spec["peak_flops"]["bfloat16"])
    hbm = float(spec["hbm_bytes_per_sec"])
    ici = float(spec["ici_bytes_per_sec"])
    dcn = float(spec["dcn_bytes_per_sec"])
    if comm_sizes is None:
        comm_sizes = {"all-": 2, "reduce-scatter": 2,
                      "collective-permute": 2}

    attr = HloAttribution(hlo_text)
    comp_sec: Dict[str, float] = {}
    comp_costs: Dict[str, Dict[str, float]] = {}
    totals = {"flops": 0.0, "hbm_bytes": 0.0, "collective_bytes": 0.0}
    own_sec: Dict[str, float] = {}   # per-instruction seconds
    ledger: List[Dict[str, Any]] = []          # per-collective rows
    ledger_by_name: Dict[str, Dict[str, Any]] = {}
    for instrs in attr.comps.values():
        for ins in instrs:
            if ins.cost <= 0:
                continue
            comp = attr.instr_component.get(ins.name) or "other"
            row = comp_costs.setdefault(
                comp, {"flops": 0.0, "bytes": 0.0,
                       "collective_bytes": 0.0,
                       "ici_ms": 0.0, "dcn_ms": 0.0})
            if is_collective_opcode(ins.opcode):
                groups, src = ins.groups, "hlo"
                if not groups:
                    # groupless line: ONE contiguous group of the
                    # plan size, through the same group-based path
                    groups = (tuple(range(
                        _comm_k(comm_sizes, ins.opcode))),)
                    src = "synthesized"
                t, ici_s, dcn_s, link, k = price_collective(
                    ins.opcode, ins.bytes, groups, slice_devices,
                    ici, dcn, exchange=exchange)
                totals["collective_bytes"] += ins.bytes
                row["collective_bytes"] += ins.bytes
                row["ici_ms"] += ici_s * 1e3
                row["dcn_ms"] += dcn_s * 1e3
                lrow = {
                    "name": ins.name, "opcode": ins.opcode,
                    "component": comp, "bytes": int(ins.bytes),
                    "group_size": k, "num_groups": len(groups),
                    "link": link, "groups_source": src,
                    "predicted_ms": t * 1e3,
                    "ici_ms": ici_s * 1e3, "dcn_ms": dcn_s * 1e3,
                    # sync until a matching *-done proves otherwise
                    "overlap_ms": 0.0, "exposed_ms": t * 1e3,
                }
                ledger.append(lrow)
                ledger_by_name[ins.name] = lrow
            else:
                t = max(ins.flops / peak, ins.bytes / hbm)
                totals["flops"] += ins.flops
                totals["hbm_bytes"] += ins.bytes
                row["flops"] += ins.flops
                row["bytes"] += ins.bytes
            own_sec[ins.name] = t
            comp_sec[comp] = comp_sec.get(comp, 0.0) + t

    # ---- exposed-comms walk ------------------------------------------
    # Per-computation seconds (bottom-up, cycle-guarded) so a fusion /
    # while between a *-start and its *-done contributes its REAL
    # modeled time to the overlap window, not its zero container cost.
    comp_total: Dict[str, float] = {}

    def _comp_seconds(cname: str, _stack=()) -> float:
        if cname in comp_total:
            return comp_total[cname]
        if cname in _stack or cname not in attr.comps:
            return 0.0
        tot = 0.0
        for i in attr.comps[cname]:
            tot += own_sec.get(i.name, 0.0)
            for callee in i.calls:
                tot += _comp_seconds(callee, _stack + (cname,))
        comp_total[cname] = tot
        return tot

    for instrs in attr.comps.values():
        open_windows: Dict[str, float] = {}
        for ins in instrs:
            if (ins.opcode.endswith("-start")
                    and ins.name in ledger_by_name):
                open_windows[ins.name] = 0.0
            elif ins.opcode.endswith("-done"):
                for op in ins.operands:
                    if op in open_windows:
                        window = open_windows.pop(op)
                        lrow = ledger_by_name[op]
                        t = lrow["predicted_ms"]
                        lrow["overlap_ms"] = min(window * 1e3, t)
                        lrow["exposed_ms"] = max(
                            0.0, t - window * 1e3)
                        break
            elif not is_collective_opcode(ins.opcode):
                # only independent compute overlaps a collective;
                # another collective would contend for the same link
                spend = own_sec.get(ins.name, 0.0) + sum(
                    _comp_seconds(c) for c in ins.calls)
                if spend > 0:
                    for name in open_windows:
                        open_windows[name] += spend
        # a *-start with no *-done in this computation stays fully
        # exposed (the conservative reading of a truncated artifact)

    comms_ms = {"ici_ms": 0.0, "dcn_ms": 0.0,
                "exposed_ms": 0.0, "exposed_dcn_ms": 0.0}
    for lrow in ledger:
        comms_ms["ici_ms"] += lrow["ici_ms"]
        comms_ms["dcn_ms"] += lrow["dcn_ms"]
        comms_ms["exposed_ms"] += lrow["exposed_ms"]
        if lrow["predicted_ms"] > 0:
            comms_ms["exposed_dcn_ms"] += (
                lrow["exposed_ms"]
                * lrow["dcn_ms"] / lrow["predicted_ms"])
        for key in ("predicted_ms", "ici_ms", "dcn_ms",
                    "overlap_ms", "exposed_ms"):
            lrow[key] = round(lrow[key], 4)
    ledger.sort(key=lambda r: (-r["exposed_ms"], -r["predicted_ms"],
                               r["name"]))
    for crow in comp_costs.values():
        crow["ici_ms"] = round(crow["ici_ms"], 4)
        crow["dcn_ms"] = round(crow["dcn_ms"], 4)

    components_ms = {c: round(t * 1e3, 4) for c, t in
                     sorted(comp_sec.items(), key=lambda kv: -kv[1])}
    sections_ms: Dict[str, float] = {"fwd": 0.0, "bwd": 0.0,
                                     "comms": 0.0, "optimizer": 0.0}
    for comp, t in comp_sec.items():
        sections_ms[section_of(comp)] += t * 1e3
    total_ms = sum(comp_sec.values()) * 1e3

    # ---- HBM observatory: liveness peak over the same parsed module --
    hbm_rec = memory.analyze_memory(hlo_text, attr=attr,
                                    input_groups=input_groups)
    capacity = float(spec["hbm_bytes"])
    peak_bytes = hbm_rec.get("peak_hbm_bytes", 0)
    hbm_rec["capacity"] = {
        "hbm_bytes": int(capacity),
        "headroom_bytes": int(capacity - peak_bytes),
        "utilization_pct": round(100.0 * peak_bytes / capacity, 2),
        "fits": bool(peak_bytes <= capacity),
    }

    return {
        "target": target,
        "precision": precision,
        "predicted_step_time_ms": round(total_ms, 4),
        "sections_ms": {k: round(v, 4) for k, v in
                        sections_ms.items()},
        "components_ms": components_ms,
        "component_costs": comp_costs,
        "comms_ms": {k: round(v, 4) for k, v in comms_ms.items()},
        "collectives": ledger,
        "totals": {k: round(v, 1) for k, v in totals.items()},
        "comm_sizes": dict(comm_sizes),
        "hbm": hbm_rec,
    }


def predict_for_compiled(hlo_text: str,
                         device_kind: Optional[str] = None,
                         mesh_shape: Optional[Dict[str, int]] = None,
                         precision: str = "bfloat16",
                         num_slices: int = 1,
                         exchange: str = "flat",
                         input_groups: Optional[List] = None
                         ) -> Dict[str, Any]:
    """ONE pricing entry point for an already-compiled program: derive
    the target from the device kind, the collective participant counts
    from the mesh, and the per-slice device count from ``num_slices``
    (collectives spanning slices price against DCN — as one flat ring
    or as the three-phase hierarchical exchange, per ``exchange``).
    Whoever prices a compiled program (the trainer's gauge) prices
    through this one path — a second hand-maintained invocation block
    would silently diverge on exactly the pricing inputs calibration
    depends on."""
    target = target_for_device_kind(device_kind)
    mesh_shape = dict(mesh_shape or {})
    slice_devices = None
    if num_slices and int(num_slices) > 1:
        total = 1
        for v in mesh_shape.values():
            total *= int(v)
        slice_devices = max(1, total // int(num_slices))
    return predict_from_hlo(
        hlo_text, target=target, precision=precision,
        comm_sizes=comm_sizes_for_mesh(mesh_shape),
        slice_devices=slice_devices, exchange=exchange,
        input_groups=input_groups)


# ---- AOT lowering of the real train step (CPU, no hardware) ---------


def lower_train_step(cfg, batch_size: int, image_size=None,
                     pad_hw: Optional[Tuple[int, int]] = None,
                     strategy: str = "replicated",
                     fsdp_axis: int = 2,
                     model_axis: int = 2,
                     num_slices: int = 1,
                     exchange: str = "flat"
                     ) -> Tuple[str, Dict[str, Any]]:
    """AOT-lower + compile the real train step; → (hlo_text, meta).

    The program ``make_synthetic_train_step`` builds: model from cfg,
    synthetic batch at the padded canvas, jitted init, optimizer, and
    — under a sharded strategy — the sharding plan's just-in-time
    gather / storage-grad constraints over a
    ``(1, fsdp_axis, model_axis)`` mesh of host-platform devices
    (``fsdp`` sizes only the fsdp axis, ``tensor`` only the model
    axis, ``2d`` both — the model-axis collectives land in the HLO
    and get priced).  ``num_slices > 1`` prepends a ``slice`` mesh
    axis (``(num_slices, 1, fsdp, model)``) so the lowered program is
    the multi-slice one — with ``exchange="hierarchical"`` the plan's
    staged storage_grads constraints shape the gradient exchange into
    the ICI-RS / DCN-AR / ICI-AG schedule the three-phase pricing
    models.  Only compiles; never executes a step, so it runs on any
    backend (the gate runs it under ``JAX_PLATFORMS=cpu``).

    ``meta`` carries the comm sizes for :func:`predict_from_hlo` plus
    the geometry, so a banked prediction is self-describing.
    """
    import jax
    import jax.numpy as jnp

    from eksml_tpu.data.loader import make_synthetic_batch
    from eksml_tpu.models import MaskRCNN
    from eksml_tpu.train import (cast_params_for_storage,
                                 make_optimizer,
                                 make_synthetic_train_step)

    shape = tuple(pad_hw) if pad_hw else image_size
    model = MaskRCNN.from_config(cfg)
    rng = jax.random.PRNGKey(0)
    tx, _ = make_optimizer(cfg)

    from eksml_tpu.parallel.sharding import STRATEGIES

    if strategy not in STRATEGIES:
        # ONE strategy inventory (sharding.STRATEGIES) — a strategy
        # added there must never read as unsupported here
        raise ValueError(
            f"lower_train_step supports {STRATEGIES}, got "
            f"{strategy!r}")
    plan = None
    mesh_shape: Dict[str, int] = {}
    ns = max(1, int(num_slices))
    if ns > 1 and strategy == "replicated":
        raise ValueError(
            "multi-slice lowering needs a sharded strategy — "
            "replicated has no mesh to carry the slice axis")
    if strategy != "replicated":
        from eksml_tpu.parallel import build_mesh
        from eksml_tpu.parallel.sharding import ShardingPlan

        f = fsdp_axis if strategy in ("fsdp", "2d") else 1
        m = model_axis if strategy in ("tensor", "2d") else 1
        need = ns * f * m
        devices = jax.devices()
        if len(devices) < need:
            raise ValueError(
                f"{strategy} lowering needs {need} devices, have "
                f"{len(devices)} — set XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={need} before jax loads "
                "(tools/perf_gate.py does)")
        if ns > 1:
            mesh = build_mesh(
                (ns, 1, f, m), ("slice", "data", "fsdp", "model"),
                devices[:need], num_slices=ns)
            plan = ShardingPlan(strategy, mesh, exchange=exchange)
        else:
            mesh = build_mesh((1, f, m), ("data", "fsdp", "model"),
                              devices[:need], num_slices=1)
            plan = ShardingPlan(strategy, mesh)
        mesh_shape = dict(mesh.shape)

    # per-chip batch semantics under a plan (the trainer's
    # contract): batch rows ride EVERY mesh axis (sharding.py
    # batch_spec — the strategies change the storage layout, never
    # the replica count); the replicated path is the historical
    # single-device program whose numbers the banked r5 artifacts
    # measured
    n_mesh = 1
    for v in mesh_shape.values():
        n_mesh *= int(v)
    global_bs = batch_size * (n_mesh if plan is not None else 1)
    batch = make_synthetic_batch(cfg, batch_size=global_bs,
                                 image_size=shape)
    batch = {k: jnp.asarray(v) for k, v in batch.items()
             if k not in ("image_scale", "image_id")}

    def init_fn(r, b):
        return model.init(r, b, r)["params"]

    if plan is not None:
        batch = jax.device_put(batch, plan.batch_sharding())
        params, param_sh = plan.init_sharded(init_fn, rng, batch)
    else:
        params = jax.jit(init_fn)(rng, batch)
    params = cast_params_for_storage(
        params, getattr(cfg.TRAIN, "PARAM_DTYPE", "float32"))
    if plan is not None:
        opt_state, opt_sh = plan.init_sharded(tx.init, params,
                                              deterministic=True)
    else:
        opt_state = tx.init(params)

    # the model's real forward, backward and update (train.py)
    step = make_synthetic_train_step(
        model, tx, plan,
        param_sh if plan is not None else None,
        opt_sh if plan is not None else None)
    hlo = step.lower(params, opt_state, batch, rng).compile().as_text()

    # entry-signature parameter grouping for the HBM observatory:
    # (params, opt_state, batch, rng) flatten in argument order, one
    # HLO entry parameter per leaf — leaf COUNTS are sharding-proof
    # where leaf bytes would not be (memory.analyze_memory)
    input_groups = [
        ["params", len(jax.tree.leaves(params))],
        ["optimizer", len(jax.tree.leaves(opt_state))],
        ["batch", len(jax.tree.leaves(batch)) + 1],  # + the rng key
    ]

    meta = {
        "strategy": strategy,
        "batch_size": batch_size,
        "image_size": (list(pad_hw) if pad_hw else image_size),
        "precision": str(cfg.TRAIN.PRECISION),
        "param_dtype": str(getattr(cfg.TRAIN, "PARAM_DTYPE",
                                   "float32")),
        "remat": bool(getattr(cfg.TRAIN, "REMAT", False)),
        "comm_sizes": comm_sizes_for_mesh(mesh_shape),
        "mesh_shape": mesh_shape,
        "num_slices": ns,
        "slice_devices": (max(1, n_mesh // ns)
                          if plan is not None else 1),
        "exchange": (exchange if ns > 1 else "flat"),
        "input_groups": input_groups,
    }
    return hlo, meta


def lower_predict_step(cfg, batch_size: int,
                       pad_hw: Tuple[int, int]
                       ) -> Tuple[str, Dict[str, Any]]:
    """AOT-lower + compile the real PREDICT step at one serving
    (bucket, batch) rung; → (hlo_text, meta).

    The same program construction the serving engine warms
    (eksml_tpu/serve/engine.py: ``jit(model.apply(…, method=predict))
    .lower(...).compile()``), so the priced program is the program the
    server dispatches.  Params are abstract (``ShapeDtypeStruct`` via
    ``eval_shape``) — nothing is materialized, only compiled; runs on
    any backend (the gate runs it under ``JAX_PLATFORMS=cpu``).
    """
    import jax
    import jax.numpy as jnp

    from eksml_tpu.models import MaskRCNN

    model = MaskRCNN.from_config(cfg)
    bh, bw = int(pad_hw[0]), int(pad_hw[1])
    img_dtype = (jnp.uint8
                 if getattr(cfg.PREPROC, "DEVICE_NORMALIZE", False)
                 else jnp.float32)
    imgs = jax.ShapeDtypeStruct((batch_size, bh, bw, 3), img_dtype)
    hw = jax.ShapeDtypeStruct((batch_size, 2), jnp.float32)
    rng = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(
        lambda r, im, h: model.init(r, im, h,
                                    method=MaskRCNN.predict),
        rng, imgs, hw)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        shapes["params"])

    fn = jax.jit(lambda p, im, h: model.apply(
        {"params": p}, im, h, method=MaskRCNN.predict))
    hlo = fn.lower(params, imgs, hw).compile().as_text()
    meta = {
        "kind": "predict",
        "batch_size": int(batch_size),
        "pad_hw": [bh, bw],
        "precision": str(cfg.TRAIN.PRECISION),
        "device_normalize": bool(getattr(cfg.PREPROC,
                                         "DEVICE_NORMALIZE", False)),
        # single-device inference program: no collectives to price
        "comm_sizes": {},
        "mesh_shape": {},
        "input_groups": [
            ["params", len(jax.tree.leaves(params))],
            ["batch", 2],      # images + true-hw
        ],
    }
    return hlo, meta


# ---- prediction comparison (the gate's FAIL logic) ------------------


def compare_predictions(fresh: Dict[str, Any], base: Dict[str, Any],
                        max_regress_pct: float = 10.0,
                        min_share_pct: float = 5.0
                        ) -> Tuple[bool, Dict[str, Any]]:
    """(ok, verdict) for one fresh-vs-banked prediction pair.

    FAILs on a total predicted-step-time regression beyond
    ``max_regress_pct``, or on any component holding ≥``min_share_pct``
    of the baseline regressing beyond 2× the bound (a big component
    regression must not hide behind an unrelated improvement).  The
    verdict always carries the per-component diff — the gate's message
    names the worst mover, never just the bare total."""
    ft = float(fresh["predicted_step_time_ms"])
    bt = float(base["predicted_step_time_ms"])
    verdict: Dict[str, Any] = {
        "fresh_ms": round(ft, 3), "baseline_ms": round(bt, 3),
        "max_regress_pct": max_regress_pct,
    }
    if bt <= 0:
        verdict["error"] = "baseline prediction is <= 0 ms — rebank it"
        return False, verdict
    total_pct = (ft / bt - 1.0) * 100.0
    verdict["total_regress_pct"] = round(total_pct, 2)

    fc = fresh.get("components_ms", {})
    bc = base.get("components_ms", {})
    diffs = []
    for comp in sorted(set(fc) | set(bc)):
        b = float(bc.get(comp, 0.0))
        f = float(fc.get(comp, 0.0))
        share = 100.0 * max(b, f) / bt
        if share < 1.0:
            continue
        pct = ((f / b - 1.0) * 100.0) if b > 0 else None
        diffs.append({"component": comp,
                      "baseline_ms": round(b, 3),
                      "fresh_ms": round(f, 3),
                      "share_pct": round(share, 1),
                      "regress_pct": (round(pct, 1)
                                      if pct is not None else "new")})
    diffs.sort(key=lambda d: -(d["fresh_ms"] - d["baseline_ms"]))
    verdict["components"] = diffs

    def _worst() -> str:
        for d in diffs:
            if d["fresh_ms"] > d["baseline_ms"]:
                delta = d["regress_pct"]
                delta = (f"+{delta}%" if isinstance(delta, float)
                         else "new")
                return (f"{d['component']} predicted {delta} "
                        f"({d['baseline_ms']}ms -> {d['fresh_ms']}ms)")
        return "no single component regressed (uniform drift)"

    if total_pct > max_regress_pct:
        verdict["error"] = (
            f"predicted step time regressed {total_pct:+.1f}% "
            f"({bt:.2f}ms -> {ft:.2f}ms); worst component: {_worst()}")
        return False, verdict
    for d in diffs:
        b, f = d["baseline_ms"], d["fresh_ms"]
        if b <= 0:
            # brand-new component: no ratio exists, so the 2x-bound
            # check can't see it — a big one hiding behind an
            # unrelated win is exactly the masked class
            if f > 0 and d["share_pct"] >= min_share_pct:
                verdict["error"] = (
                    f"new component {d['component']} predicted "
                    f"{f}ms ({d['share_pct']}% of the step) while "
                    f"the total moved only {total_pct:+.1f}% — a "
                    "masked regression")
                return False, verdict
            continue
        # share_pct is max(b, f)/baseline-total: a component that
        # EXPLODED from a tiny baseline holds its fresh share, and
        # judging by the baseline share alone would wave it through
        if (d["share_pct"] >= min_share_pct
                and (f / b - 1.0) * 100.0 > 2.0 * max_regress_pct):
            verdict["error"] = (
                f"component {d['component']} predicted "
                f"{(f / b - 1) * 100:+.1f}% ({b}ms -> {f}ms, "
                f"{d['share_pct']}% of the step) while the total "
                f"moved only {total_pct:+.1f}% — a masked regression")
            return False, verdict
    return True, verdict


# ---- calibration against banked hardware measurements ---------------

#: (artifact file, run name inside it or None for a flat record,
#:  prediction-bank rung key) — the committed r5 evidence the model is
#: calibrated against.  Measurements are full-width hardware runs; the
#: committed predictions are smoke-width lowerings, so the absolute
#: scale factor is large and meaningless alone — its CONSISTENCY
#: across rungs is the honesty metric (see calibrate()).
R5_CALIBRATION_SOURCES = (
    ("roi_ab_r5.json", "roi_ab_bwd_pallas_512", "512_b4"),
    ("roi_ab_r5.json", "roi_ab_bwd_pallas_1344", "1344_b4"),
    ("bench_rung_1344_b4.json", None, "1344_b4"),
)


def calibration_points(artifacts_dir: str,
                       strategy: str = "replicated",
                       precision: str = "bfloat16") -> List[Dict]:
    """Pair banked hardware measurements with banked predictions.

    Two pairing routes:
    - the pinned r5 sources above, matched to
      ``perf_pred_<rung>_<strategy>_<precision>.json``;
    - any ``bench_rung_*.json`` that already CARRIES a
      ``predicted_step_time_ms`` (a record that holds predicted next
      to measured) — such a round calibrates with no pinned table.
    """
    points: List[Dict] = []
    for fname, run_name, rung in R5_CALIBRATION_SOURCES:
        rec = load_json(os.path.join(artifacts_dir, fname))
        if rec is None:
            continue
        if run_name is not None:
            rec = next((r for r in rec.get("runs", ())
                        if r.get("run") == run_name), None)
            if rec is None:
                continue
        elif rec.get("predicted_step_time_ms"):
            # the flat artifact carries its own (measured-width)
            # prediction — the glob route below pairs it; pairing it
            # AGAIN here against the banked smoke-width prediction
            # would count the same measurement twice and skew the fit
            continue
        measured = rec.get("step_time_ms")
        if not measured or measured <= 0 or rec.get("error"):
            continue
        pred_path = os.path.join(
            artifacts_dir, f"perf_pred_{rung}_{strategy}_"
                           f"{precision}.json")
        pred = load_json(pred_path)
        if not pred or not pred.get("predicted_step_time_ms"):
            continue
        src = f"{fname}:{run_name or 'flat'}"
        points.append({
            "rung": rung,
            "measured_ms": float(measured),
            "measured_source": src,
            "predicted_ms": float(pred["predicted_step_time_ms"]),
            "predicted_source": os.path.basename(pred_path),
            # full-width measurement vs SMOKE-width banked prediction
            "fit_group": "smoke",
        })
    import glob

    for path in sorted(glob.glob(os.path.join(artifacts_dir,
                                              "bench_rung_*.json"))):
        rec = load_json(path)
        if not rec:
            continue
        measured = rec.get("step_time_ms")
        predicted = rec.get("predicted_step_time_ms")
        # forward_only: the 3-step micro rung is
        # dispatch-overhead-dominated, and its scale factor would
        # systematically skew the train-step fit
        if (measured and measured > 0 and predicted and predicted > 0
                and rec.get("status") != "error"
                and not rec.get("forward_only")):
            points.append({
                "rung": rec.get("operating_point",
                                os.path.basename(path)),
                "measured_ms": float(measured),
                "measured_source": os.path.basename(path),
                "predicted_ms": float(predicted),
                "predicted_source": "embedded",
                # the record priced the measured-width compiled HLO
                "fit_group": "measured",
            })
    return points


def calibrate(points: List[Dict]) -> Dict[str, Any]:
    """Fit one scale factor per rung; report how far they spread.

    ``scale_i = measured_i / predicted_i``; the common fit is the
    geometric mean WITHIN each ``fit_group`` — smoke-width banked
    predictions carry a channel-width scale that measured-width
    embedded predictions do not, and pooling them would report that
    known width gap as model error.  ``model_error_pct`` = the largest
    per-rung deviation from its own group's fit — 0 means the model
    ranks and scales geometries exactly as the hardware does, and any
    honest use of the predictions (gating RATIOS, never absolutes) is
    safe within that error.  ``scale`` is the smoke-bank group's fit
    (the one tools/perf_gate.py's banked baselines live at); every
    group's fit is in ``scales``."""
    import math

    out: Dict[str, Any] = {"n_points": len(points), "points": []}
    usable = [p for p in points
              if p["predicted_ms"] > 0 and p["measured_ms"] > 0]
    if not usable:
        out["note"] = ("no calibration points — bank predictions for "
                       "the measured rungs (tools/perf_gate.py "
                       "--update-baseline) or land a hardware round")
        out["scale"] = None
        out["model_error_pct"] = None
        return out
    groups: Dict[str, List[Dict]] = {}
    for p in usable:
        groups.setdefault(p.get("fit_group", "smoke"), []).append(p)
    out["scales"] = {}
    errs = []
    for gname in sorted(groups):
        gpts = groups[gname]
        scales = [p["measured_ms"] / p["predicted_ms"] for p in gpts]
        common = math.exp(sum(math.log(s) for s in scales)
                          / len(scales))
        out["scales"][gname] = round(common, 2)
        for p, s in zip(gpts, scales):
            err = (s / common - 1.0) * 100.0
            errs.append(abs(err))
            out["points"].append({
                **{k: p[k] for k in ("rung", "measured_ms",
                                     "predicted_ms",
                                     "measured_source")},
                "fit_group": gname,
                "scale": round(s, 2),
                "deviation_pct": round(err, 2),
            })
    out["scale"] = out["scales"].get(
        "smoke", next(iter(out["scales"].values())))
    out["model_error_pct"] = round(max(errs), 2)
    if len(usable) < 2:
        out["note"] = ("single calibration point: scale is exact by "
                       "construction; model error needs >=2 rungs")
    return out


#: per-link communication gauges published next to PREDICTED_GAUGE —
#: prediction comms_ms key → (gauge name, help)
PREDICTED_COMMS_GAUGES = {
    "ici_ms": ("eksml_train_predicted_comms_ici_ms",
               "roofline-predicted per-step collective time on the "
               "in-slice ICI links (replica_groups-exact pricing)"),
    "dcn_ms": ("eksml_train_predicted_comms_dcn_ms",
               "roofline-predicted per-step collective time on the "
               "cross-slice DCN links (replica_groups-exact pricing)"),
    "exposed_ms": ("eksml_train_predicted_comms_exposed_ms",
                   "predicted collective time NOT hidden behind "
                   "compute scheduled inside async start/done "
                   "windows — the overlap headroom metric"),
}


def publish_predicted_gauge(pred: Dict[str, Any]) -> None:
    """Set the ``eksml_train_predicted_step_time_ms`` gauge — plus the
    per-link communication gauges when the prediction carries the
    observatory rollup — from a prediction.  ONE definition of names +
    help for trainer and tests."""
    from eksml_tpu import telemetry

    reg = telemetry.default_registry()
    reg.gauge(
        PREDICTED_GAUGE,
        "roofline-predicted step time for this run's compiled train "
        "step on the target chip (eksml_tpu/profiling/predict.py)"
    ).set(float(pred["predicted_step_time_ms"]))
    comms = pred.get("comms_ms")
    if comms:
        for key, (name, help_text) in PREDICTED_COMMS_GAUGES.items():
            reg.gauge(name, help_text).set(float(comms.get(key, 0.0)))
