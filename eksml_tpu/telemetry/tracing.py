"""Distributed span tracing + anomaly-triggered on-demand profiling.

PR 4's telemetry can *detect* a straggling host (``hosts/lagging``,
flight-recorder events) but cannot explain *where inside the step* the
time went, and the PR 3 HLO attribution is static — a transient stall
(slow H2D, GC pause, checkpoint write, pool rebuild) is invisible the
moment it ends.  This module is the time-domain layer, following the
span model of Dapper (Sigelman et al., 2010) and the capture-on-demand
workflow of the TPU/XProf profiler:

- **Spans** (:func:`span`): ~µs-overhead wall-clock intervals
  recorded into a bounded per-host ring (:class:`Tracer`), each
  carrying ``step``/``host`` attributes so it joins against
  flight-recorder events and metric rows.  A live span is ALSO a
  ``jax.profiler.TraceAnnotation`` of the same name (``step`` and the
  attrs as its stats), entered on the thread that does the work: while
  a profiler session is open (``/debugz/profile``, the benchmark's
  capture) the host lines of the ``.xplane.pb`` carry the program's
  phases on the profiler's clock, beside the device's ``XLA Ops``;
  outside a session the annotation is a flag check.  With no tracer
  installed (or ``enabled=False``) the module-level API is a TRUE
  no-op: it returns one shared null context manager and allocates
  nothing — no annotation either.
- **Step completion stamps** (:class:`StepStamper`): one daemon thread
  that blocks on each step's loss in turn inside a ``device_step``
  span, so every step has a completion time without a sync in the
  step loop.  Exists only while a traced ``fit`` runs.
- **Trace files**: :meth:`Tracer.flush` writes the ring as
  Chrome-trace-event/Perfetto-compatible JSON to
  ``<logdir>/trace-host<i>.json`` (``pid`` = host, ``tid`` = thread),
  so ``chrome://tracing``, Perfetto, and
  ``tools/trace_summary.py --merge`` (cross-host timeline) all read
  it directly.
- **On-demand capture** (:class:`ProfileTrigger`): a thread-safe
  request box between the exporter's ``/debugz/profile?steps=N``
  endpoint (or the anomaly detector) and the fit loop, guarded by a
  cooldown and a max-captures-per-run budget so a flapping alert (or
  a curious operator in a loop) cannot turn the profiler into the
  incident.
- **Anomaly trigger** (:class:`AnomalyDetector`): fires the same
  capture automatically when a rolling step-time p95 regression or a
  persistent straggler survives K consecutive log intervals — the
  trace of a production incident exists *before* anyone is paged.

Everything fails soft: tracing must never take down training.  ``jax``
is imported lazily (first live span); where it cannot be imported, or
an annotation cannot be entered, spans stay ring-only.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import queue
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

log = logging.getLogger(__name__)


def trace_path_for(logdir: Optional[str], host_id: int) -> Optional[str]:
    """Per-host span trace file under the run dir (same contract as
    the flight recorder's ``events-host<i>.jsonl``)."""
    if not logdir:
        return None
    os.makedirs(logdir, exist_ok=True)
    return os.path.join(logdir, f"trace-host{host_id}.json")


# ``jax.profiler`` once a live span has asked for it; False where it
# cannot be imported (spans are ring-only from then on)
_profiler = None


def _annotation(name: str, step: Optional[int], attrs: Optional[Dict],
                step_trace: bool):
    """The span's twin on the profiler's clock, entered, or None."""
    global _profiler
    if _profiler is None:
        try:
            import jax.profiler as _profiler
        except Exception:  # noqa: BLE001 — ring-only from here on
            log.warning("jax.profiler not importable: spans stay "
                        "ring-only", exc_info=True)
            _profiler = False
    if not _profiler:
        return None
    stats = dict(attrs) if attrs else {}
    if step is not None:
        stats["step"] = int(step)
    try:
        if step_trace:
            ann = _profiler.StepTraceAnnotation(
                name, step_num=stats.get("step", 0), **stats)
        else:
            ann = _profiler.TraceAnnotation(name, **stats)
        ann.__enter__()
        return ann
    except Exception:  # noqa: BLE001 — observability only
        log.debug("profiler annotation %r failed", name, exc_info=True)
        return None


class _Span:
    """One active span; records a complete ('X') event on exit and is
    a profiler annotation of the same name in between."""

    __slots__ = ("_tracer", "name", "step", "attrs", "_step_trace",
                 "_ann", "_t0")

    def __init__(self, tracer: "Tracer", name: str,
                 step: Optional[int], attrs: Optional[Dict],
                 step_trace: bool = False):
        self._tracer = tracer
        self.name = name
        self.step = step
        self.attrs = attrs
        self._step_trace = step_trace

    def __enter__(self) -> "_Span":
        self._ann = _annotation(self.name, self.step, self.attrs,
                                self._step_trace)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:  # noqa: BLE001 — observability only
                log.debug("profiler annotation %r failed on exit",
                          self.name, exc_info=True)
        self._tracer._complete(self.name, self._t0, t1,
                               self.step, self.attrs)


class _NullSpan:
    """Shared do-nothing span — the disabled-mode fast path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


NULL_SPAN = _NullSpan()


class Tracer:
    """Bounded, thread-safe ring of Chrome-trace span events.

    Timestamps are wall-clock microseconds derived from ONE
    ``(time.time, perf_counter)`` epoch pair taken at construction —
    monotonic within the process, roughly wall-aligned across hosts
    (the merge tool refines the alignment on step boundaries, so NTP
    skew does not corrupt the cross-host timeline).
    """

    def __init__(self, capacity: int = 4096,
                 path: Optional[str] = None, host_id: int = 0,
                 enabled: bool = True):
        self.capacity = max(16, int(capacity))
        self.path = path
        self.host_id = int(host_id)
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._epoch_wall_us = time.time() * 1e6
        self._epoch_perf = time.perf_counter()
        self.spans_recorded = 0

    # -- recording -----------------------------------------------------

    def span(self, name: str, step: Optional[int] = None,
             attrs: Optional[Dict] = None, step_trace: bool = False):
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, step, attrs, step_trace)

    def _ts_us(self, perf_t: float) -> float:
        return self._epoch_wall_us + (perf_t - self._epoch_perf) * 1e6

    def _complete(self, name: str, t0: float, t1: float,
                  step: Optional[int], attrs: Optional[Dict]) -> None:
        args: Dict = {"host": self.host_id}
        if step is not None:
            args["step"] = int(step)
        if attrs:
            args.update(attrs)
        ev = {"name": str(name), "ph": "X",
              "ts": round(self._ts_us(t0), 3),
              "dur": round((t1 - t0) * 1e6, 3),
              "pid": self.host_id,
              "tid": threading.get_ident() % 2 ** 31,
              "args": args}
        with self._lock:
            self._ring.append(ev)
            self.spans_recorded += 1
        # span sink (goodput ledger): notified OUTSIDE the ring lock —
        # a sink must never extend this hot-path critical section, and
        # it must never take down the traced code
        sink = _span_sink
        if sink is not None:
            try:
                sink(str(name), t1 - t0, step)
            except Exception:  # noqa: BLE001 — observability only
                log.exception("span sink failed for %r", name)

    def instant(self, name: str, step: Optional[int] = None,
                **attrs) -> None:
        """Zero-duration marker event (capture start/stop etc.)."""
        if not self.enabled:
            return
        args: Dict = {"host": self.host_id}
        if step is not None:
            args["step"] = int(step)
        args.update(attrs)
        ev = {"name": str(name), "ph": "i", "s": "g",
              "ts": round(self._ts_us(time.perf_counter()), 3),
              "pid": self.host_id,
              "tid": threading.get_ident() % 2 ** 31,
              "args": args}
        with self._lock:
            self._ring.append(ev)

    # -- output --------------------------------------------------------

    def snapshot(self) -> List[Dict]:
        with self._lock:
            return list(self._ring)

    def flush(self, path: Optional[str] = None) -> Optional[str]:
        """Write the ring (plus process metadata) as one Chrome-trace
        JSON document.  Atomic (write-then-rename): a reader polling
        for the file must never parse a torn write.  Never raises —
        a full disk must not take down the step loop."""
        path = path or self.path
        if not path:
            return None
        events = self.snapshot()
        meta = [{"name": "process_name", "ph": "M", "pid": self.host_id,
                 "args": {"name": f"host{self.host_id}"}}]
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
            return path
        except OSError:
            log.warning("could not write span trace %s", path,
                        exc_info=True)
            return None

    def close(self) -> None:
        self.flush()


# -- module-level installed tracer (same pattern as the recorder) ------

_tracer: Optional[Tracer] = None
# optional listener on completed spans: ``fn(name, dur_s, step)``.
# The goodput ledger classifies run wall-clock through this hook
# instead of adding its own hot-path instrumentation.  With no tracer
# installed (tracing disabled) no spans complete and the sink never
# fires — the ledger's documented coarse mode.
_span_sink = None
_install_lock = threading.Lock()


def install_span_sink(fn) -> Optional[object]:
    """Install (or with ``None``, remove) the span sink; returns the
    previous one so callers can restore it (fit installs the goodput
    meter's for the duration of the loop)."""
    global _span_sink
    with _install_lock:
        prev, _span_sink = _span_sink, fn
    return prev


def install_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or with ``None``, remove) the process tracer; returns
    the previous one so callers can restore it."""
    global _tracer
    with _install_lock:
        prev, _tracer = _tracer, tracer
    return prev


def get_tracer() -> Optional[Tracer]:
    return _tracer


def span(name: str, step: Optional[int] = None,
         attrs: Optional[Dict] = None, step_trace: bool = False):
    """Context manager timing one named interval through the installed
    tracer, in the ring and (inside a profiler session) in the
    profiler's trace.  ``step_trace`` makes the annotation a
    ``StepTraceAnnotation`` (the step's dispatch: the profiler groups
    its per-step analysis by it).  Without a tracer (or with tracing
    disabled) this returns the SHARED null span — no allocation, no
    annotation, no lock, ~100 ns."""
    t = _tracer
    if t is None or not t.enabled:
        return NULL_SPAN
    return _Span(t, name, step, attrs, step_trace)


def complete_span(name: str, t0: float, t1: float,
                  step: Optional[int] = None, **attrs) -> None:
    """Record an already-measured interval (``time.perf_counter``
    endpoints) as a span — for intervals that start on another thread
    than the one that ends them (the serve batcher's ``queue_wait``),
    which no context manager can hold.  Ring-only: an annotation has to
    be entered and left on one thread.  No-op without an installed
    tracer."""
    t = _tracer
    if t is None or not t.enabled:
        return
    t._complete(name, t0, t1, step, attrs or None)


class StepStamper:
    """Per-step completion times without a sync in the step loop.

    ``fit`` hands each step's loss (a device scalar the step returns,
    never donated) with its step number to :meth:`stamp`; one daemon
    thread takes them in order and blocks on each inside a
    ``device_step`` span.  The span starts at the later of the previous
    step's completion and the hand-over and ends when step ``n`` is
    done on the device, so consecutive ends are device step times; it
    lands in the ring and, inside a profiler session, on the
    profiler's clock.  Created only by a traced ``fit``."""

    _DONE = object()
    ERROR_EXIT_TIMEOUT_SEC = 30.0

    def __init__(self, wait: Callable[[object], object]):
        self._wait = wait
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="step-stamper")
        self._thread.start()

    def stamp(self, step: int, value) -> None:
        self._q.put((step, value))

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is self._DONE:
                return
            step, value = item
            try:
                with span("device_step", step=step):
                    self._wait(value)
            except Exception:  # noqa: BLE001 — the loop reports it
                # a step that failed on the device fails the step
                # loop's own sync too, with the traceback that matters
                log.debug("device_step %s: wait failed", step,
                          exc_info=True)

    def close(self, failed: bool = False) -> None:
        """Stamp what is queued (the device drains), then stop.  On the
        way out of an error (``failed``) the wait is limited: a wedged
        device must not hang the exit, and the thread is a daemon."""
        self._q.put(self._DONE)
        self._thread.join(self.ERROR_EXIT_TIMEOUT_SEC if failed else None)
        if self._thread.is_alive():
            log.warning("step stamper still waiting on the device "
                        "after %.0fs — left behind",
                        self.ERROR_EXIT_TIMEOUT_SEC)


# -- on-demand profile capture ----------------------------------------


class ProfileTrigger:
    """Thread-safe request box between capture *requesters* (the
    ``/debugz/profile`` endpoint, the anomaly detector) and the
    capture *executor* (the fit loop, which owns ``jax.profiler``).

    Guard rails — both enforced here so every requester shares them:

    - ``cooldown_sec`` between captures (measured from capture end),
      so a flapping anomaly cannot chain captures back to back;
    - ``max_captures`` per process lifetime, so a long run cannot
      slowly fill the shared filesystem with trace dumps.
    """

    def __init__(self, cooldown_sec: float = 300.0,
                 max_captures: int = 3, default_steps: int = 3,
                 max_steps: int = 50,
                 clock: Callable[[], float] = time.monotonic):
        self.cooldown_sec = float(cooldown_sec)
        self.max_captures = int(max_captures)
        self.default_steps = int(default_steps)
        self.max_steps = int(max_steps)
        self._clock = clock
        self._lock = threading.Lock()
        self._pending: Optional[Dict] = None
        self._active = False
        self._last_end: Optional[float] = None
        self.captures_started = 0
        self.rejected = 0

    def request(self, steps: Optional[int] = None,
                reason: str = "manual") -> Tuple[bool, str]:
        """Ask for a capture of ``steps`` post-request steps.  Returns
        ``(accepted, detail)``; never raises."""
        try:
            n = int(steps) if steps else self.default_steps
        except (TypeError, ValueError):
            return self._reject(f"invalid steps value {steps!r}")
        if n <= 0:
            return self._reject(f"steps must be positive, got {n}")
        n = min(n, self.max_steps)
        with self._lock:
            if self._pending is not None:
                return self._reject_locked("a capture is already "
                                           "pending")
            if self._active:
                return self._reject_locked("a capture is in progress")
            if self.captures_started >= self.max_captures:
                return self._reject_locked(
                    f"max captures per run reached "
                    f"({self.max_captures})")
            now = self._clock()
            if (self._last_end is not None
                    and now - self._last_end < self.cooldown_sec):
                wait = self.cooldown_sec - (now - self._last_end)
                return self._reject_locked(
                    f"cooldown: {wait:.0f}s until the next capture "
                    "window")
            self._pending = {"steps": n, "reason": str(reason),
                             "requested_at": time.time()}
            return True, f"accepted: {n} step(s) ({reason})"

    def _reject(self, detail: str) -> Tuple[bool, str]:
        with self._lock:
            return self._reject_locked(detail)

    def _reject_locked(self, detail: str) -> Tuple[bool, str]:
        self.rejected += 1
        return False, detail

    def take(self) -> Optional[Dict]:
        """Consume the pending request (the fit loop calls this at a
        step boundary); marks a capture active."""
        with self._lock:
            req, self._pending = self._pending, None
            if req is not None:
                self._active = True
                self.captures_started += 1
            return req

    def finish(self) -> None:
        """Capture done — start the cooldown clock."""
        with self._lock:
            self._active = False
            self._last_end = self._clock()

    def status(self) -> Dict:
        with self._lock:
            return {
                "pending": self._pending is not None,
                "active": self._active,
                "captures_started": self.captures_started,
                "max_captures": self.max_captures,
                "cooldown_sec": self.cooldown_sec,
                "rejected": self.rejected,
            }


# -- anomaly detection -------------------------------------------------


class AnomalyDetector:
    """Turns the per-log-interval scalars the fit loop already has
    into capture triggers.  Two independent signals, each requiring
    ``k_intervals`` CONSECUTIVE anomalous log intervals (one blip is
    noise; a persistent one is an incident):

    - **step-time regression**: the interval's mean step time exceeds
      ``p95_factor`` × the rolling p95 of the last ``window`` healthy
      intervals (the baseline excludes the current observation and
      stops absorbing samples while a streak is building, so a slow
      regression cannot normalize itself).
    - **persistent straggler**: the SAME host is ``hosts/lagging``
      while the max/mean spread exceeds ``spread_factor`` (without
      the spread gate, argmax over near-identical hosts is a random
      host index and would "persist" spuriously at world size 1).
    """

    def __init__(self, k_intervals: int = 3, p95_factor: float = 1.5,
                 spread_factor: float = 1.5, window: int = 32,
                 min_history: int = 8):
        self.k = max(1, int(k_intervals))
        self.p95_factor = float(p95_factor)
        self.spread_factor = float(spread_factor)
        self.min_history = max(4, int(min_history))
        self._history: collections.deque = collections.deque(
            maxlen=max(self.min_history, int(window)))
        self._slow_streak = 0
        self._lag_host: Optional[int] = None
        self._lag_streak = 0
        self.fired = 0

    @staticmethod
    def _p95(values) -> float:
        s = sorted(values)
        idx = min(len(s) - 1, int(round(0.95 * (len(s) - 1))))
        return s[idx]

    def observe(self, step_time_ms: float,
                lagging_host: Optional[int] = None,
                spread_ratio: Optional[float] = None) -> Optional[str]:
        """Feed one log interval; returns a reason string when an
        anomaly has persisted ``k_intervals`` intervals, else None."""
        reason = None
        v = float(step_time_ms)

        # signal 1: rolling p95 regression
        if len(self._history) >= self.min_history:
            baseline = self._p95(self._history)
            if baseline > 0 and v > self.p95_factor * baseline:
                self._slow_streak += 1
            else:
                self._slow_streak = 0
        if self._slow_streak >= self.k:
            reason = (f"step_time_p95_regression: {v:.0f}ms > "
                      f"{self.p95_factor:.2f}x rolling p95 "
                      f"{self._p95(self._history):.0f}ms for "
                      f"{self._slow_streak} intervals")
        # only healthy intervals feed the baseline — a building streak
        # must not drag the p95 up underneath itself
        if self._slow_streak == 0:
            self._history.append(v)

        # signal 2: persistent straggler
        if (lagging_host is not None and spread_ratio is not None
                and float(spread_ratio) > self.spread_factor):
            h = int(lagging_host)
            if h == self._lag_host:
                self._lag_streak += 1
            else:
                self._lag_host, self._lag_streak = h, 1
        else:
            self._lag_host, self._lag_streak = None, 0
        if reason is None and self._lag_streak >= self.k:
            reason = (f"persistent_straggler: host {self._lag_host} "
                      f"lagging {self._lag_streak} intervals "
                      f"(spread {float(spread_ratio):.2f}x)")

        if reason is not None:
            self.fired += 1
            self._slow_streak = 0
            self._lag_host, self._lag_streak = None, 0
        return reason


# -- thread stacks (the /debugz/stacks payload) ------------------------


def format_thread_stacks() -> str:
    """All live threads' stacks as text — the same shape the hang
    watchdog writes to its reports, served on demand."""
    frames = sys._current_frames()
    threads = {t.ident: t for t in threading.enumerate()}
    lines = [f"{len(frames)} thread(s) at "
             f"{time.strftime('%Y-%m-%d %H:%M:%S %z')}", ""]
    for ident, frame in frames.items():
        t = threads.get(ident)
        name = t.name if t else f"unknown-{ident}"
        daemon = getattr(t, "daemon", "?")
        lines.append(f"--- thread {name} (ident={ident}, "
                     f"daemon={daemon}) ---")
        lines.extend(l.rstrip("\n")
                     for l in traceback.format_stack(frame))
        lines.append("")
    return "\n".join(lines)
