"""Summarize a jax.profiler trace into a per-op-family time breakdown.

Answers VERDICT r2 next #5: where does the train step actually go —
backbone/FPN convs, ROIAlign forward, ROIAlign backward, NMS, resnet
head — so the Pallas-backward go/no-go is decided on data, not vibes.

Reads the TensorBoard-format ``*.trace.json.gz`` the profiler writes
under ``<dir>/plugins/profile/<run>/`` and aggregates device-lane event
durations by family (regex over XLA fusion/custom-call names).

Component attribution (VERDICT r5 weak #3: fusion names like "5"/"23"
put 86.78% of device time in "other"): pass ``--attribution`` (the
``profile/attribution.json`` artifact that
``eksml_tpu.profiling.write_attribution_artifact`` writes) or
``--hlo`` (a raw ``Compiled.as_text()`` dump) and every event name is
first resolved through the compiled module's instruction→component map
(eksml_tpu/profiling), yielding a ``component_pct`` table — rpn-nms /
roi-bwd / fpn-conv-bwd / optimizer / allreduce … — alongside the
legacy name-regex families.

Cross-host span merge (ISSUE 5): with ``--merge`` the positional
argument is a training LOGDIR holding the per-host span traces the
telemetry tracer flushes (``trace-host<i>.json``,
eksml_tpu/telemetry/tracing.py).  Host clocks are re-aligned on step
boundaries (the median per-step offset of each host's ``train_step``
span against host 0 — NTP skew cannot corrupt the timeline), the
events merge into ONE Chrome-trace document (``pid`` = host), and the
summary names the slowest steps with the dominant span on the
slowest host — "step 412 was slow because host 3 sat 1.9 s in
data_wait" instead of a bare ``hosts/lagging`` index.

Usage::

    python tools/trace_summary.py profile --out artifacts/profile_summary_r3.json
    python tools/trace_summary.py profile --attribution profile/attribution.json
    python tools/trace_summary.py <logdir> --merge --out merged_trace.json
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys

# op-name regex → family, first match wins.  XLA fusion names carry the
# dominant op (e.g. "fusion.123" with metadata, or "%convolution.45");
# pallas kernels keep their kernel name.
FAMILIES = (
    ("roi_align_bwd", r"roi.?align.*(bwd|backward|grad|transpose)|"
                      r"(bwd|backward|grad).*roi.?align"),
    ("roi_align_fwd", r"roi.?align"),
    ("nms", r"non.?max|nms"),
    ("conv", r"conv"),
    ("matmul", r"dot|gemm|matmul|einsum"),
    ("allreduce", r"all.?reduce|psum|reduce.?scatter|all.?gather|"
                  r"collective"),
    ("copy", r"copy|transpose|reshape|bitcast"),
    ("reduce", r"reduce|cumsum|sort|top.?k"),
    ("scatter_gather", r"scatter|gather|dynamic.?slice|dynamic.?update"),
)


def _load_trace_events(trace_dir: str):
    pats = [os.path.join(trace_dir, "**", "*.trace.json.gz"),
            os.path.join(trace_dir, "**", "*.trace.json")]
    paths = [p for pat in pats for p in glob.glob(pat, recursive=True)]
    if not paths:
        raise FileNotFoundError(
            f"no *.trace.json(.gz) under {trace_dir!r} — run with "
            "--profile first")
    path = max(paths, key=os.path.getmtime)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f).get("traceEvents", []), path


def load_component_map(attribution_path: str | None = None,
                       hlo_path: str | None = None) -> dict:
    """Instruction-name → component lookup with trace-name aliases.

    Trace event names drift from HLO instruction names (observed r5:
    events named "5" for "fusion.5", with or without a leading '%') —
    so each map entry also registers its bare numeric suffix as an
    alias when that suffix is unambiguous across instructions.
    """
    if attribution_path:
        with open(attribution_path) as f:
            payload = json.load(f)
        base = payload.get("map", payload)
    elif hlo_path:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from eksml_tpu.profiling import attribution_map

        with open(hlo_path) as f:
            base = attribution_map(f.read())
    else:
        return {}
    out = dict(base)
    suffix: dict = {}
    for name, comp in base.items():
        m = re.match(r"^[\w\-]+\.(\d+)$", name)
        if m:
            suffix.setdefault(m.group(1), set()).add(comp)
    for sfx, comps in suffix.items():
        if len(comps) == 1 and sfx not in out:
            out[sfx] = next(iter(comps))
    return out


def _resolve_component(name: str, cmap: dict) -> str | None:
    n = name.strip().lstrip("%")
    if n in cmap:
        return cmap[n]
    # events sometimes carry a scope prefix ("cluster/fusion.5")
    tail = n.rsplit("/", 1)[-1]
    return cmap.get(tail)


def summarize(trace_dir: str, top_n: int = 15,
              component_map: dict | None = None) -> dict:
    events, path = _load_trace_events(trace_dir)
    # device lanes: TPU/accelerator op events carry "dur" (µs) and live
    # on pids whose process_name mentions the device; host python lanes
    # are excluded so the breakdown is device time, not dispatch time
    pid_names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev["pid"]] = ev.get("args", {}).get("name", "")
    device_pids = {pid for pid, name in pid_names.items()
                   if re.search(r"tpu|device|/device|xla", name, re.I)
                   and not re.search(r"host|python", name, re.I)}

    fam_us: dict = {}
    comp_us: dict = {}
    op_us: dict = {}
    op_comp: dict = {}
    total = 0.0
    cmap = component_map or {}
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        if device_pids and ev.get("pid") not in device_pids:
            continue
        name = ev.get("name", "")
        dur = float(ev["dur"])
        total += dur
        op_us[name] = op_us.get(name, 0.0) + dur
        if cmap:
            comp = _resolve_component(name, cmap) or "other"
            comp_us[comp] = comp_us.get(comp, 0.0) + dur
            op_comp[name] = comp
        for fam, pat in FAMILIES:
            if re.search(pat, name, re.I):
                fam_us[fam] = fam_us.get(fam, 0.0) + dur
                break
        else:
            fam_us["other"] = fam_us.get("other", 0.0) + dur

    if total == 0:
        raise ValueError(
            f"no device-lane events found in {path!r} (pids matched: "
            f"{sorted(device_pids)}) — truncated capture or unexpected "
            "lane naming")
    fam_pct = {k: round(100 * v / total, 2)
               for k, v in sorted(fam_us.items(), key=lambda kv: -kv[1])}
    top_ops = [{"name": k, "us": round(v, 1),
                "pct": round(100 * v / total, 2),
                **({"component": op_comp[k]} if k in op_comp else {})}
               for k, v in sorted(op_us.items(),
                                  key=lambda kv: -kv[1])[:top_n]]
    out = {"trace": path, "total_device_us": round(total, 1),
           "family_pct": fam_pct, "top_ops": top_ops}
    if cmap:
        out["component_pct"] = {
            k: round(100 * v / total, 2)
            for k, v in sorted(comp_us.items(), key=lambda kv: -kv[1])}
        out["component_other_pct"] = out["component_pct"].get("other",
                                                              0.0)
    return out


# ---------------------------------------------------------------------
# cross-host span-trace merge (trace-host<i>.json from the telemetry
# tracer) — ISSUE 5
# ---------------------------------------------------------------------

STEP_SPAN = "train_step"  # the per-step anchor span the fit loop emits
# step-tagged spans of OTHER threads: they overlap the loop's own
# spans (device_step runs from one step's completion on the device to
# the next), so they are part of the timeline and no part of a step's
# host wall or of its dominant span
OVERLAPPING_SPANS = frozenset({"device_step"})


def load_host_traces(logdir: str) -> tuple:
    """``({host_id: [events]}, {host_id: reason})`` from every
    ``trace-host<i>.json`` under ``logdir``.

    Skip-and-warn, never abort: a host killed mid-flush leaves a
    truncated/torn trace file, and a host that died before its first
    flush leaves none at all — exactly the runs whose cross-host
    timeline matters most.  Unreadable files are skipped with a
    stderr warning; hosts that the run's ``events-host<i>.jsonl``
    files prove existed but that left no trace are reported missing.
    Only a logdir with NO readable trace at all raises."""
    out: dict = {}
    skipped: dict = {}
    for path in sorted(glob.glob(
            os.path.join(logdir, "trace-host*.json"))):
        m = re.search(r"trace-host(\d+)\.json$", path)
        if not m:
            continue
        host = int(m.group(1))
        try:
            with open(path) as f:
                doc = json.load(f)
            events = doc.get("traceEvents", []) \
                if isinstance(doc, dict) else None
        except (json.JSONDecodeError, OSError) as e:
            # torn write from a killed process — keep the other hosts
            skipped[host] = f"unreadable ({type(e).__name__}: {e})"
            continue
        if not isinstance(events, list):
            skipped[host] = "malformed (no traceEvents list)"
            continue
        out[host] = events
    # hosts the run demonstrably had (their event files exist) but
    # whose span trace never landed — name them instead of silently
    # rendering a timeline that pretends they weren't there
    for path in glob.glob(os.path.join(logdir, "events-host*.jsonl")):
        m = re.search(r"events-host(\d+)\.jsonl$", path)
        if m and int(m.group(1)) not in out \
                and int(m.group(1)) not in skipped:
            skipped[int(m.group(1))] = "missing trace-host file"
    for host in sorted(skipped):
        print(f"warning: skipping host {host}: {skipped[host]} — "
              "merging the remaining hosts", file=sys.stderr)
    if not out:
        raise FileNotFoundError(
            f"no readable trace-host<i>.json under {logdir!r} — run "
            "with TELEMETRY.TRACING.ENABLED=True (or trigger a "
            "/debugz/profile capture) first"
            + (f"; skipped: {skipped}" if skipped else ""))
    return out, skipped


def _step_anchors(events) -> dict:
    """{step: earliest ts} of the per-step anchor spans."""
    anchors: dict = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("name") != STEP_SPAN:
            continue
        step = (ev.get("args") or {}).get("step")
        if step is None:
            continue
        ts = float(ev["ts"])
        if step not in anchors or ts < anchors[step]:
            anchors[step] = ts
    return anchors


def merge_host_traces(logdir: str, slow_top: int = 5) -> dict:
    """Merge per-host span traces into one step-aligned timeline.

    Alignment: per host, the median over common steps of (host0's
    anchor ts − this host's anchor ts) becomes the host's clock
    offset.  Step boundaries are collective in SPMD training, so the
    median offset IS the clock skew; wall-clock (NTP) disagreement
    drops out entirely.
    """
    traces, skipped = load_host_traces(logdir)
    ref_host = min(traces)
    ref_anchor = _step_anchors(traces[ref_host])

    merged = []
    offsets = {}
    covered: dict = {}    # step -> {host} (hosts with the anchor span)
    step_durs: dict = {}  # step -> {host: Σ step-attributed span µs}
    span_max: dict = {}   # (step, host) -> (name, dur µs) longest one
    for host, events in sorted(traces.items()):
        anchors = _step_anchors(events)
        common = sorted(set(anchors) & set(ref_anchor))
        if host == ref_host or not common:
            offset = 0.0
        else:
            deltas = sorted(ref_anchor[s] - anchors[s] for s in common)
            offset = deltas[len(deltas) // 2]
        offsets[host] = offset
        for ev in events:
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = round(float(ev["ts"]) + offset, 3)
            ev["pid"] = host
            merged.append(ev)
            if ev.get("ph") != "X":
                continue
            step = (ev.get("args") or {}).get("step")
            if step is None or ev.get("name") in OVERLAPPING_SPANS:
                continue
            step = int(step)
            dur = float(ev.get("dur", 0.0))
            if ev.get("name") == STEP_SPAN:
                covered.setdefault(step, set()).add(host)
            # per-step host wall = the SUM of the loop's sequential
            # step-attributed spans, not the train_step dispatch
            # alone: on an accelerator the dispatch returns
            # immediately and the blocking lands in data_wait /
            # host_metrics — ranking by dispatch would structurally
            # hide input starvation, the main thing to catch
            cur = step_durs.setdefault(step, {})
            cur[host] = cur.get(host, 0.0) + dur
            best = span_max.get((step, host))
            if best is None or dur > best[1]:
                span_max[(step, host)] = (ev["name"], dur)
    merged.sort(key=lambda e: e.get("ts", 0.0))

    # per-step wall time = the slowest host's total (the synchronous-
    # SPMD bound); only anchor-covered steps count (a lone
    # host_metrics span from a partial capture is not a step)
    steps = []
    for step in sorted(covered):
        by_host = {h: d for h, d in step_durs[step].items()
                   if h in covered[step]}
        if not by_host:
            continue
        slow_host = max(by_host, key=by_host.get)
        steps.append({"step": step,
                      "ms": round(by_host[slow_host] / 1000.0, 3),
                      "host": slow_host,
                      "hosts": len(by_host)})
    slow_steps = []
    if steps:
        mean_ms = sum(s["ms"] for s in steps) / len(steps)
        for s in sorted(steps, key=lambda s: -s["ms"])[:slow_top]:
            entry = dict(s)
            entry["vs_mean"] = round(s["ms"] / mean_ms, 2) \
                if mean_ms > 0 else 0.0
            dom = span_max.get((s["step"], s["host"]))
            if dom is not None:
                entry["dominant_span"] = dom[0]
                entry["dominant_ms"] = round(dom[1] / 1000.0, 3)
            slow_steps.append(entry)

    return {
        "hosts": sorted(traces),
        "skipped_hosts": {str(h): r
                          for h, r in sorted(skipped.items())},
        "host_offsets_us": {str(h): round(o, 1)
                            for h, o in offsets.items()},
        "steps_covered": len(steps),
        "mean_step_ms": (round(sum(s["ms"] for s in steps)
                               / len(steps), 3) if steps else 0.0),
        "slow_steps": slow_steps,
        "traceEvents": merged,
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("trace_dir")
    p.add_argument("--out", default=None)
    p.add_argument("--top", type=int, default=15)
    p.add_argument("--attribution", default=None,
                   help="profile/attribution.json (profiling."
                        "write_attribution_artifact): resolve event "
                        "names to model "
                        "components (eksml_tpu/profiling)")
    p.add_argument("--hlo", default=None,
                   help="raw Compiled.as_text() dump to build the "
                        "component map from (alternative to "
                        "--attribution)")
    p.add_argument("--merge", action="store_true",
                   help="treat the positional arg as a training "
                        "logdir and merge its trace-host<i>.json "
                        "span files into one step-aligned cross-host "
                        "timeline (telemetry tracing, ISSUE 5)")
    args = p.parse_args(argv)
    try:
        if args.merge:
            summary = merge_host_traces(args.trace_dir)
        else:
            cmap = load_component_map(args.attribution, args.hlo)
            summary = summarize(args.trace_dir, args.top,
                                component_map=cmap)
    except (FileNotFoundError, ValueError, OSError) as e:
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        return 1
    if args.merge:
        # stdout gets the human-relevant verdict; the (large) merged
        # timeline only lands where --out asks for it
        printed = {k: v for k, v in summary.items()
                   if k != "traceEvents"}
        print(json.dumps(printed, indent=1))
    else:
        print(json.dumps(summary, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps(summary, indent=1) + "\n")
        os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
