"""From a profiler trace (``.xplane.pb``) to device busy time, idle
share, per-operation time and the grouped time of the Pallas custom
calls.  The benchmark's own reduction: no PR that claims a gain can
change it.  Checked on ``benchmark/fixtures/*.xplane.pb``.

Layout of a TPU trace as ``jax.profiler.ProfileData`` reads it (seen
by hand on this chip, PR 24): one plane per chip named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO instruction, named by the instruction's whole text
(``%fusion.37 = f32[..] fusion(..)``), and its line ``XLA Modules`` one
event per execution of a compiled program (``jit__train_step(<id>)``);
host threads are lines of ``/host:CPU``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
FEED_ANNOTATION = "bench_feed_next"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def hlo_module_name(hlo_text: str):
    """``HloModule jit__train_step, ..`` -> ``jit__train_step``: the
    name the ``XLA Modules`` line gives the program's executions
    (``jit__train_step(<id>)``), or None."""
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else None


def hlo_instructions(hlo_text: str):
    """({instruction: named-scope path}, {names of tpu_custom_call
    instructions}) of a compiled module's text."""
    scopes, custom = {}, set()
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        s = _OP_NAME.search(line)
        if s:
            scopes[name] = s.group(1)
        if ('custom_call_target="tpu_custom_call"' in line
                and "custom-call(" in line):
            custom.add(name)
    return scopes, custom


def instruction_name(event_name: str) -> str:
    """``%fusion.37 = f32[..] fusion(..)`` -> ``fusion.37``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def union_seconds(intervals):
    """Length of the union of (start_ns, end_ns) intervals, seconds."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals, lo, hi):
    """The idle (start_ns, end_ns) stretches of [lo, hi] not covered."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class TraceSummary:
    devices: int = 0
    steps: int = 0                 # whole program executions in the window
    window_s: float = 0.0
    busy_s: float = 0.0            # mean over the devices
    op_seconds: dict = field(default_factory=dict)   # summed, per device mean
    custom_call_s: float = 0.0     # per device mean
    custom_call_events: int = 0
    idle_gaps: list = field(default_factory=list)    # [(label, seconds)]

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s if self.window_s else None

    def top_ops(self, n=10, scopes=None):
        rows = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        scopes = scopes or {}
        return [[f"{k} [{scopes[k]}]"[:180] if k in scopes else k, v]
                for k, v in rows]


HOST_EVENT_MIN_NS = 1_000_000     # host events shorter than 1 ms label no gap


def host_label(gap, host_events):
    """What the host was doing in the idle ``gap`` (start_ns, end_ns):
    the shortest host event that covers half of it or more, as
    ``<thread>:<event>``, or None.  Shortest, because a thread's outer
    loop covers everything."""
    s, e = gap
    covering = [(he - hs, name) for name, hs, he in host_events
                if min(e, he) - max(s, hs) >= 0.5 * (e - s)]
    return min(covering)[1] if covering else None


def place_label(gap, runs):
    """Where the idle ``gap`` (start_ns, end_ns) lies among ``runs``,
    the (start_ns, end_ns) of the step program's executions: all a
    trace of the device alone can say of it.  Between two executions
    the chip waits for the host (a dispatch, a batch, the log step's
    sync); inside one, the program itself leaves it idle."""
    s, e = gap
    if any(rs <= s and e <= re for rs, re in runs):
        return "inside a step's execution"
    return "between two steps' executions"


def summarize(device_events: dict, host_feed=(), custom_calls=frozenset(),
              modules=None, top_gaps=10, step_module=None,
              host_events=(), host_traced=True) -> TraceSummary:
    """``device_events``: {device: [(instruction, start_ns, dur_ns)]}
    of the ops line; ``modules``: {device: [(name, start_ns, dur_ns)]}
    of whole program executions; ``host_feed``: [(start_ns, end_ns)]
    during which the benchmark's feed waited in the loader's ``next``.
    ``step_module``: the step program's module name; executions of any
    other program (a log step's small jits) are no steps and do not
    set the window.  ``host_events``: [(thread:event, start_ns,
    end_ns)] of the host's longer events, which label the idle gaps
    further.  ``host_traced`` False: the trace holds the device's
    planes alone (``harness.Capture`` since PR 32), so nothing is said
    of the host and a gap is labelled by ``place_label``.  The window
    runs from the first whole execution's
    start to the last one's end (first op to last op where no
    execution was recorded), and only operations inside it count."""
    out = TraceSummary(devices=len(device_events))
    if modules and step_module:
        modules = {k: [ev for ev in evs
                       if ev[0].split("(", 1)[0] == step_module]
                   for k, evs in modules.items()}
    runs = [(s, s + d) for evs in (modules or {}).values()
            for _, s, d in evs]
    spans = runs or [(s, s + d) for evs in device_events.values()
                     for _, s, d in evs]
    if not spans:
        return out
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    device_events = {k: [(nm, s, d) for nm, s, d in evs
                         if s >= lo and s + d <= hi]
                     for k, evs in device_events.items()}
    out.window_s = (hi - lo) / 1e9
    if modules:
        out.steps = min(len(v) for v in modules.values())
    n = len(device_events)
    all_gaps = []
    for evs in device_events.values():
        ivals = [(s, s + d) for _, s, d in evs]
        out.busy_s += union_seconds(ivals) / n
        for name, _, d in evs:
            out.op_seconds[name] = out.op_seconds.get(name, 0.0) + d / 1e9 / n
            if name in custom_calls:
                out.custom_call_s += d / 1e9 / n
                out.custom_call_events += 1
        all_gaps += gaps(ivals, lo, hi)
    labelled = {}
    for s, e in sorted(all_gaps, key=lambda g: g[0] - g[1])[:top_gaps]:
        if host_traced:
            fed = sum(max(0, min(e, fe) - max(s, fs))
                      for fs, fe in host_feed)
            label = ("feed waits in the loader's next" if fed >= 0.5 * (e - s)
                     else "host not in the loader's next")
            doing = host_label((s, e), host_events)
            if doing:
                label += f", host in {doing}"
        else:
            label = (place_label((s, e), runs) if runs
                     else "idle, host not traced")
        labelled.setdefault(label, []).append((e - s) / 1e9)
    out.idle_gaps = sorted(
        ([f"{k} (longest of {len(v)})", max(v)] for k, v in labelled.items()),
        key=lambda r: -r[1])
    return out


def read_xplane(path):
    """(ops {device: [(instruction, start_ns, dur_ns)]}, modules
    {device: [(name, start_ns, dur_ns)]}, [(start, end)] of the feed
    annotation, [(thread:event, start, end)] of the host's events of
    1 ms or more) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, feed, host = {}, {}, [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (instruction_name(e.name), int(e.start_ns),
                         int(e.duration_ns)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread = (line.name or "thread").split("/")[0]
                for e in line.events:
                    span = (int(e.start_ns), int(e.start_ns + e.duration_ns))
                    if e.name == FEED_ANNOTATION:
                        feed.append(span)
                    elif e.duration_ns >= HOST_EVENT_MIN_NS:
                        host.append((f"{thread}:{e.name}"[:80], *span))
    return ops, modules, feed, host


def summarize_file(path, custom_calls=frozenset(),
                   step_module=None) -> TraceSummary:
    """A trace with no host event at all was taken with the host
    tracer off."""
    ops, modules, feed, host = read_xplane(path)
    return summarize(ops, feed, custom_calls, modules,
                     step_module=step_module, host_events=host,
                     host_traced=bool(feed or host))
