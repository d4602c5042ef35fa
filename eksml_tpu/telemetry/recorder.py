"""Flight recorder: step-correlated structured events for post-mortems.

Every resilience transition (PRs 1-3) perturbs the metric stream but
left no trace IN it: a SIGTERM, a NaN rollback, a checkpoint walk-back
or a pool rebuild had to be reconstructed from grep'ing pod logs that
Kubernetes may already have rotated away.  The recorder is a bounded
in-memory ring of ``{"time", "kind", "step", ...}`` events, mirrored
line-by-line to ``<logdir>/events-host<i>.jsonl`` (one file per host on
the shared filesystem, same contract as the quarantine ledger), so:

- the hang watchdog appends the ring's tail to every hang report (what
  happened BEFORE the stall is usually the diagnosis);
- ``tools/run_report.py`` renders the fleet-wide incident timeline from
  the mirrored files next to ``metrics.jsonl``;
- the OpenMetrics exporter exposes ``eksml_flight_events_total{kind=}``
  counters (default registry), so incident *rates* are scrapeable even
  without the files.

Publishing is decoupled from plumbing: subsystems call the module-level
:func:`event`, which forwards to the installed per-process recorder
(``Trainer`` installs one per host) and no-ops when none is installed —
library consumers (eval_ckpt, unit tests) pay nothing.

Event kinds in use (grep anchors, not an enum — new subsystems add
their own): ``sigterm``, ``preempt_exit``, ``nan_observed``,
``rollback``, ``quarantine``, ``pool_rebuild``, ``pool_degraded``,
``starvation``, ``watchdog_dump``, ``checkpoint_save``,
``checkpoint_skipped``, ``checkpoint_restore``,
``checkpoint_fallback``, ``checkpoint_quarantined``, ``run_start``,
``compile_start``/``compile_done`` and ``eval_start``/``eval_done``
(the phases the goodput ledger would otherwise misattribute to
``host_overhead`` — the ``*_done`` events carry the measured
duration).
"""

from __future__ import annotations

import collections
import json
import logging
import os
import threading
import time
from typing import Dict, List, Optional

from eksml_tpu.telemetry.registry import default_registry

log = logging.getLogger(__name__)


def events_path_for(logdir: Optional[str], host_id: int) -> Optional[str]:
    """Per-host event file under the run dir (appends stay host-local
    on the shared filesystem, like the quarantine ledger)."""
    if not logdir:
        return None
    os.makedirs(logdir, exist_ok=True)
    return os.path.join(logdir, f"events-host{host_id}.jsonl")


class FlightRecorder:
    def __init__(self, capacity: int = 256, path: Optional[str] = None,
                 host_id: int = 0):
        self.capacity = max(8, int(capacity))
        self.path = path
        self.host_id = host_id
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._file = open(path, "a") if path else None
        self.dropped_writes = 0

    def record(self, kind: str, step: Optional[int] = None,
               **fields) -> Dict:
        entry = {"time": time.time(), "kind": str(kind),
                 "host": self.host_id}
        if step is not None:
            entry["step"] = int(step)
        for k, v in fields.items():
            # events must stay JSON-serializable whatever a caller
            # hands in (exception objects, paths, numpy scalars).
            # allow_nan=False in the PROBE too: a NaN/Inf float field
            # must take the repr() fallback here, not blow up the
            # strict final serialization below and silently drop the
            # exact incident event a post-mortem needs
            try:
                json.dumps(v, allow_nan=False)
                entry[k] = v
            except (TypeError, ValueError):
                entry[k] = repr(v)
        line = json.dumps(entry, allow_nan=False)
        with self._lock:
            self._ring.append(entry)
            if self._file is not None:
                # one write per line + flush: events are rare and each
                # one is post-mortem evidence — it must hit the shared
                # fs BEFORE whatever comes next (the process may be
                # about to exit or hang)
                try:
                    self._file.write(line + "\n")
                    self._file.flush()
                except OSError:
                    self.dropped_writes += 1
        default_registry().counter(
            "eksml_flight_events",
            "flight-recorder events by kind",
            labels={"kind": str(kind)}).inc()
        # event sinks (goodput ledger): notified OUTSIDE the ring lock
        # — a sink must never extend the recorder's critical section,
        # and a broken one must never cost the incident event
        for sink in list(_event_sinks):
            try:
                sink(entry)
            except Exception:  # noqa: BLE001 — observability only
                log.exception("flight-event sink failed for %r", kind)
        return entry

    def tail(self, n: Optional[int] = None) -> List[Dict]:
        with self._lock:
            out = list(self._ring)
        return out if n is None else out[-n:]

    def report(self, n: int = 20) -> str:
        """Human-readable tail — the watchdog hang-report section."""
        events = self.tail(n)
        if not events:
            return "no events recorded"
        lines = [f"last {len(events)} event(s), newest last:"]
        for e in events:
            ts = time.strftime("%H:%M:%S", time.localtime(e["time"]))
            extras = ", ".join(
                f"{k}={v}" for k, v in e.items()
                if k not in ("time", "kind", "step", "host"))
            step = f" step={e['step']}" if "step" in e else ""
            lines.append(
                f"  {ts} {e['kind']}{step}"
                + (f" ({extras})" if extras else ""))
        if self.dropped_writes:
            lines.append(f"  [{self.dropped_writes} event write(s) "
                         "failed — mirror file incomplete]")
        return "\n".join(lines)

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                finally:
                    self._file = None


# -- per-process default recorder -------------------------------------

_recorder: Optional[FlightRecorder] = None
# listeners on EVERY recorded event (any recorder instance):
# ``fn(entry_dict)``.  The goodput ledger attributes watchdog-reported
# hang seconds through this hook — no new instrumentation at the
# emission sites.
_event_sinks: List = []
_install_lock = threading.Lock()


def add_event_sink(fn) -> None:
    """Register an event listener (idempotent per function object)."""
    with _install_lock:
        if fn not in _event_sinks:
            _event_sinks.append(fn)


def remove_event_sink(fn) -> None:
    with _install_lock:
        try:
            _event_sinks.remove(fn)
        except ValueError:
            pass


def install(recorder: Optional[FlightRecorder]) -> Optional[FlightRecorder]:
    """Install (or with ``None``, remove) the process recorder;
    returns the previous one so callers can restore it."""
    global _recorder
    with _install_lock:
        prev, _recorder = _recorder, recorder
    return prev


def get() -> Optional[FlightRecorder]:
    return _recorder


def event(kind: str, step: Optional[int] = None, **fields
          ) -> Optional[Dict]:
    """Publish one event through the installed recorder (no-op without
    one).  Never raises: telemetry must not take down training."""
    rec = _recorder
    if rec is None:
        return None
    try:
        return rec.record(kind, step=step, **fields)
    except Exception:  # noqa: BLE001 — observability is best-effort
        log.exception("flight-recorder event %r failed", kind)
        return None
