"""Engine profiling plane: step phases, retrace sentinel, memory marks.

The obs plane can trace a request across the fleet (reqtrace) and scrape
a cluster (cluster), but neither answers *where inside one engine step
the time goes* — the question every perf item (speculation that must
pay, churn vs steady, SLO scheduling) hinges on. Three arms:

- :class:`StepProfiler` — the ``BatchGenerator`` / ``SingleStreamEngine``
  step loops stamp each pass into named phases (``admit`` with
  ``admit_launch`` and ``admit_land`` inside it, ``pages``,
  ``guide``, ``dispatch``, ``sync``, ``sync_counts``, ``emit``, and the
  speculative ``spec_propose`` / ``spec_verify`` / ``spec_accept``; the
  scheduler adds ``idle_park`` between passes and times the parts of its
  own pass around ``engine.step()``: ``sched_admit``, ``deliver``,
  ``retire`` and, for everything else it does between two steps,
  ``pass_rest``).
  Each sampled step feeds the
  per-phase ``prof.phase_ms.*`` histograms and a bounded ring of recent
  step records. Sampling every Nth step (``--prof-sample``, default
  coarse) keeps the steady-state cost inside the existing <= 3% obs
  budget: an unsampled step pays one integer increment at ``step_begin``
  and two attribute checks per ``phase()`` call site. A sampled step's
  ring record carries the step's start on both host clocks
  (``t_unix_ns``, ``t_perf_s``) and each phase's offset from it
  (``at_ms``), so it can be laid on a trace or a client's timeline. A
  scheduler pass longer than :data:`SLOW_PASS_MS` leaves its parts in a
  second ring (``slow_passes``, with the thread's CPU time and the
  step's longest device fetch, and which fetch that was, beside the
  parts) and in
  ``prof.slow_pass_ms``: what the program keeps about a pause of
  seconds. Phase stamping is
  host-side driver code only — never inside a jitted body (cakelint
  CK-JIT), and the step/phase calls run on the engine-owner thread
  (CK-THREAD); the ring and report path are lock-guarded for handler
  readers. ``dispatch`` prices the async dispatch call itself; the
  device compute lands in ``sync`` (the host fetch). ``pages`` nests
  inside ``dispatch``, ``guide`` inside ``emit``, ``admit_launch`` and
  ``admit_land`` inside ``admit`` — sub-phases attribute their parents'
  time, they don't extend the step total.

- :class:`RetraceSentinel` — the runtime twin of cakelint CK-JIT, the
  way ``runtime/threadcheck`` twins CK-THREAD: a ``jax.monitoring``
  duration listener counts XLA backend compiles (``prof.compiles``).
  Engines wrap their decode dispatches in :meth:`RetraceSentinel.
  decode_phase`; once :meth:`RetraceSentinel.mark_steady` has been
  called (the serve scheduler marks it after a warmup step budget), any
  compile landing inside a decode dispatch is a *retrace finding* —
  ``prof.retraces`` plus a bounded findings list — warned by default,
  raised as :class:`RetraceError` under ``CAKE_PROF_STRICT=1``. The
  compile-count pins the test suites assert offline (constrain/kvpool
  no-retrace tests) become a live production invariant.

- :func:`memory_watermarks` — device live/peak bytes where the backend
  exposes ``memory_stats()`` (graceful no-op otherwise — CPU returns
  nothing), host RSS/peak from ``/proc/self/status``, and the kvpool
  page gauges stitched in so one report carries the whole memory story.

- :func:`capture_start` / :func:`capture_stop` — the one place that
  opens a ``jax.profiler`` trace, in the process that holds the chip:
  ``POST /debug/trace`` on the serving port and the master path's
  ``--profile`` both call them. A capture stamps every step (stride 1),
  runs the span tracer with ``xla_annotations=True`` so every phase is a
  ``prof.<phase>`` ``TraceAnnotation`` on the engine thread's line of
  the trace's host plane, one open at a time: the innermost phase's, so
  that a device idle gap is named by the LEAF of the host's work under
  it (``obs/trace._leaf_annotation``; ``prof.admit`` there is the
  tick's self time, not its landing's). It writes the program's own
  spans, nested as ever, beside the profile
  (``spans.trace.json``). One capture at a time; a capture nobody stops
  is stopped after :data:`CAPTURE_MAX_S`. Off, it costs nothing: no
  thread, and ``jax.profiler`` is not imported before the first start.

:func:`report` assembles the arms into the JSON served at
``GET /debug/prof`` (serve replicas, statusd pages, and the gateway's
fleet-merged view) and rendered by ``obs/top.py``; a serving process
adds its ``startup`` times (:func:`set_startup`). When the tracer is
started (``--trace`` or a capture), phases additionally record
``prof.*`` spans — on every step, sampled or not — so one Perfetto file
shows request spans with the engine phases nested under them.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque

from cake_tpu.obs import metrics as obs_metrics
from cake_tpu.obs import trace as obs_trace

log = logging.getLogger("cake_tpu.obs.prof")

# Default step-sampling stride: coarse enough that the steady-state cost
# is one counter increment per step, fine enough that a minute of serving
# banks hundreds of phase breakdowns.
SAMPLE_DEFAULT = 64

# A scheduler pass (admit, engine step, deliver, retire) longer than this
# is a stall worth keeping: a decode block is ~0.2 s on the chip and the
# longest admission ~0.15 s, so nothing healthy comes near it.
SLOW_PASS_MS = 1000.0

# A capture that nobody stops is stopped by the program (a forgotten
# profiler fills memory and slows the host for as long as it runs).
CAPTURE_MAX_S = 30.0

# The declared phase vocabulary (catalog: prof.phase_ms.*). Call sites
# may only stamp these names — a typo'd phase would silently fork a
# series exactly the way the metric catalog exists to prevent.
PHASES = (
    "admit",         # admission / arrival-drain tick (prefill chunk)
    "admit_launch",  # in admit: take an arrival, dispatch a prefill chunk
    "admit_land",    # in admit: first token fetched, sampled, spliced
    "pages",         # kvpool gather/scatter host prep (page-map upload)
    "guide",         # constrain guide/mask advance (host DFA cursor)
    "dispatch",      # device dispatch call (async: enqueue cost only)
    "sync",          # device sync + host fetch (where compute lands)
    "sync_counts",   # an expert model's per-block counts: their fetch
    "emit",          # detok / Token fan-out / bookkeeping
    "idle_park",     # scheduler parked waiting for work
    "sched_admit",   # scheduler pass: queue -> engine.enqueue (+ preempt)
    "deliver",       # scheduler pass: emitted row -> session event queues
    "retire",        # scheduler pass: close out ended sessions
    "pass_rest",     # scheduler pass: all else between two engine steps
    "spec_propose",  # speculative draft proposal (host n-gram walk)
    "spec_verify",   # speculative verify dispatch
    "spec_accept",   # accept/rollback: accept program + bank fetch
)


class RetraceError(RuntimeError):
    """A steady-state decode dispatch recompiled under CAKE_PROF_STRICT=1."""


class _NullPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_PHASE = _NullPhase()


class _Phase:
    """One stamped phase: accumulates wall ms into the sampled step's
    record (inside one) + the phase histogram, and (tracer started)
    records a ``prof.<name>`` span so the phase lands on the Perfetto
    timeline under whatever request span encloses it. ``ms`` keeps the
    phase's length for a caller that wants it (the scheduler's pass)."""

    __slots__ = ("_prof", "_name", "_args", "_t0", "_span", "ms")

    def __init__(self, prof: "StepProfiler", name: str, args: dict):
        self._prof = prof
        self._name = name
        self._args = args
        self.ms = 0.0

    def __enter__(self):
        self._span = obs_trace.span("prof." + self._name, **self._args)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms = (time.perf_counter() - self._t0) * 1e3
        self._span.__exit__(*exc)
        self._prof._record_phase(self._name, self._t0, self.ms)
        return False


class StepProfiler:
    """Sampled per-step phase breakdown for the engine step loops.

    ``step_begin``/``phase``/``step_end`` run on the engine-owner thread
    (the current-step record is thread-local, so loopback fleets with
    several in-process engines don't race each other); the ring and the
    histograms behind :meth:`phases` are safe for handler threads.
    """

    _GUARDED_BY = {"_ring": "_lock", "_slow": "_lock"}

    def __init__(self, sample_every: int = SAMPLE_DEFAULT, ring: int = 64):
        self.sample_every = max(0, sample_every)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(1, ring))
        self._slow: deque = deque(maxlen=32)  # slow scheduler passes
        self._tl = threading.local()  # .count, .cur, .t0
        self._sampled = obs_metrics.counter("prof.sampled_steps")
        self._slow_n = obs_metrics.counter("prof.slow_passes")
        self._slow_ms = obs_metrics.counter("prof.slow_pass_ms")
        # phase histograms are created lazily per name; cached so the
        # sampled-step cost is a dict hit, not a registry lock
        self._hists: dict[str, object] = {}

    # -- knobs ----------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self.sample_every > 0

    def set_sample(self, every: int) -> None:
        """Re-point the sampling stride (``--prof-sample`` at launch, a
        capture at run time; 0 disables)."""
        self.sample_every = max(0, int(every))

    # -- engine-thread stamping ----------------------------------------------
    def step_begin(self, engine: str = "batch") -> None:
        """Open one engine step; every ``sample_every``-th call (per
        engine thread) opens a sampled record the inner ``phase()``
        stamps land in. MUST be paired with ``step_end`` (try/finally)."""
        tl = self._tl
        n = getattr(tl, "count", 0)
        tl.count = n + 1
        if not self.sample_every or n % self.sample_every:
            return
        tl.t0 = time.perf_counter()
        # the step's start on both host clocks, and where each phase
        # began inside it: enough to lay the record on a device trace
        # (whose host plane runs on perf_counter's clock) or on a
        # client's timeline
        tl.cur = {"engine": engine, "step": n, "t_unix_ns": time.time_ns(),
                  "t_perf_s": tl.t0, "phases": {}, "at_ms": {}}

    def phase(self, name: str, **args):
        """Context manager stamping one phase of the current step
        (``args`` ride the phase's span). Outside a sampled step: a bare
        ``prof.<name>`` span while the tracer runs, else the shared
        no-op (two attribute checks)."""
        if getattr(self._tl, "cur", None) is None:
            if not obs_trace.tracer().enabled:
                return _NULL_PHASE
            return obs_trace.span("prof." + name, **args)
        return _Phase(self, name, args)

    def pass_part(self, name: str) -> _Phase:
        """Context manager timing one part of a scheduler pass
        (``sched_admit``/``deliver``/``retire``/``pass_rest``), which lies
        outside any
        engine step: always timed (the caller reads ``ms`` for its
        slow-pass record), into the part's phase histogram, and a
        ``prof.<name>`` span while the tracer runs."""
        return _Phase(self, name, {})

    def note_pass(self, total_ms: float, parts: dict, queued: int,
                  running: int, cpu_ms: float = 0.0,
                  fetch_ms: float = 0.0, fetch_of: str = "") -> None:
        """One scheduler pass ended after ``total_ms`` (parked time left
        out). A pass over :data:`SLOW_PASS_MS` adds its length to
        ``prof.slow_pass_ms`` and leaves a record -- when, how long, in
        which part, how much was waiting -- in the ``slow_passes`` ring.
        ``parts`` holds ``admit_ms``, ``step_ms``, ``deliver_ms``; the
        rest of the pass (retire, sweeps, the stats snapshot, the wait
        for the scheduler's lock) is ``rest_ms``. Beside the parts,
        whether the engine's thread ran or waited: ``cpu_ms`` is the CPU
        time the thread got during the pass (``time.thread_time``),
        ``fetch_ms`` the longest wait for the device inside the engine's
        step and ``fetch_of`` which fetch that was (``block:<steps>``,
        ``admit_land:<bucket>``, ``counts:<blocks>``; empty: none).
        ``fetch_ms`` ~ ``total_ms``: the runtime or the device held
        the pass; ``cpu_ms`` ~ ``total_ms``: Python ran; neither: the
        thread was descheduled or waited for a lock."""
        if total_ms < SLOW_PASS_MS:
            return
        self._slow_n.inc()
        self._slow_ms.inc(total_ms)
        rec = {"t_unix_ns": time.time_ns() - int(total_ms * 1e6),
               "total_ms": round(total_ms, 3)}
        rec.update((k, round(v, 3)) for k, v in parts.items())
        rec["rest_ms"] = round(max(0.0, total_ms - sum(parts.values())), 3)
        rec["cpu_ms"], rec["fetch_ms"] = round(cpu_ms, 3), round(fetch_ms, 3)
        rec["fetch_of"] = fetch_of
        rec["queued"], rec["running"] = queued, running
        # the ring dies with the process and a benchmark run keeps only
        # the server's log: say there which part of the pass held it
        log.warning("slow scheduler pass: %s", rec)
        with self._lock:
            self._slow.append(rec)

    def _hist(self, name: str):
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = obs_metrics.histogram(
                f"prof.phase_ms.{name}")
        return h

    def _record_phase(self, name: str, t0: float, dt_ms: float) -> None:
        cur = getattr(self._tl, "cur", None)
        if cur is not None:
            cur["phases"][name] = round(
                cur["phases"].get(name, 0.0) + dt_ms, 4)
            # offset of the phase's first stamp in this step
            cur["at_ms"].setdefault(
                name, round((t0 - cur["t_perf_s"]) * 1e3, 4))
        self.observe_ms(name, dt_ms)

    def step_end(self) -> None:
        tl = self._tl
        cur = getattr(tl, "cur", None)
        if cur is None:
            return
        tl.cur = None
        cur["total_ms"] = round((time.perf_counter() - tl.t0) * 1e3, 4)
        self._sampled.inc()
        with self._lock:
            self._ring.append(cur)

    def observe_ms(self, name: str, dt_ms: float) -> None:
        """Record one out-of-step phase sample (the scheduler's
        ``idle_park`` waits and the parts of its pass happen between
        steps, not inside one)."""
        if self.enabled:
            self._hist(name).observe(dt_ms)

    # -- report ---------------------------------------------------------------
    def recent_steps(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def slow_passes(self) -> list[dict]:
        with self._lock:
            return list(self._slow)

    def phases(self) -> dict:
        """Per-phase histogram snapshots (count/mean/p50/p99), keyed by
        the bare phase name."""
        out = {}
        for name, h in sorted(self._hists.items()):
            snap = h.snapshot()
            if snap.get("count"):
                out[name] = snap
        return out

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._slow.clear()
        for h in self._hists.values():
            h.reset()
        self._sampled.reset()
        self._slow_n.reset()
        self._slow_ms.reset()


class RetraceSentinel:
    """Runtime CK-JIT twin: count XLA compiles, flag steady-state
    decode-phase compiles as retrace findings."""

    _GUARDED_BY = {"_findings": "_lock"}

    def __init__(self):
        self.compiles = obs_metrics.counter("prof.compiles")
        self.retraces = obs_metrics.counter("prof.retraces")
        self._lock = threading.Lock()
        self._findings: deque = deque(maxlen=32)
        self._steady = False
        self._installed = False
        self._tl = threading.local()  # .depth: inside a decode dispatch

    def install(self) -> None:
        """Register the ``jax.monitoring`` duration listener (idempotent;
        a jax without the API leaves the sentinel a no-op). The listener
        is process-permanent — jax has no per-listener removal — so it
        consults this singleton's live state on every event."""
        if self._installed:
            return
        try:
            from jax import monitoring
        except Exception:  # pragma: no cover - jax always present here
            return
        monitoring.register_event_duration_secs_listener(self._on_duration)
        self._installed = True

    # -- engine-side markers --------------------------------------------------
    def decode_phase(self):
        """Context manager marking 'this thread is inside a decode
        dispatch' — compiles observed in here after ``mark_steady`` are
        retraces. (Compiles are synchronous on the dispatching thread,
        so a thread-local depth is the correct scope.)"""
        return _DecodeRegion(self._tl)

    def mark_steady(self) -> None:
        """Warmup is over: from now on a decode-phase compile is a
        finding. The serve scheduler calls this after its warmup step
        budget (``CAKE_PROF_WARM_STEPS``); tests call it directly."""
        self._steady = True

    @property
    def steady(self) -> bool:
        return self._steady

    def reset(self) -> None:
        """Back to warmup (tests): clears steady, findings, counters."""
        self._steady = False
        with self._lock:
            self._findings.clear()
        self.compiles.reset()
        self.retraces.reset()

    def findings(self) -> list[dict]:
        with self._lock:
            return list(self._findings)

    # -- listener -------------------------------------------------------------
    def _on_duration(self, event: str, dur: float, **kw) -> None:
        if not event.endswith("backend_compile_duration"):
            return
        self.compiles.inc()
        if not self._steady or not getattr(self._tl, "depth", 0):
            return
        self.retraces.inc()
        finding = {
            "event": event,
            "compile_ms": round(dur * 1e3, 3),
            "ts": time.time(),
        }
        with self._lock:
            self._findings.append(finding)
        msg = ("steady-state decode dispatch recompiled "
               f"({dur * 1e3:.1f} ms): a shape/dtype/static-arg varied "
               "after warmup — the no-retrace invariant the offline "
               "compile-count pins assert is broken live")
        if os.environ.get("CAKE_PROF_STRICT", "0") == "1":
            raise RetraceError(msg)
        log.warning("prof.retraces: %s", msg)


class _DecodeRegion:
    __slots__ = ("_tl",)

    def __init__(self, tl):
        self._tl = tl

    def __enter__(self):
        self._tl.depth = getattr(self._tl, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        self._tl.depth -= 1
        return False


# -- memory watermarks --------------------------------------------------------

def _host_rss() -> tuple[int | None, int | None]:
    """(rss_bytes, peak_bytes) from /proc/self/status; (None, None) when
    unavailable (non-Linux)."""
    try:
        with open("/proc/self/status") as f:
            txt = f.read()
    except OSError:
        return None, None
    out = {}
    for key in ("VmRSS", "VmHWM"):
        i = txt.find(key + ":")
        if i >= 0:
            try:
                out[key] = int(txt[i:].split(None, 2)[1]) * 1024
            except (ValueError, IndexError):
                pass
    return out.get("VmRSS"), out.get("VmHWM")


def memory_watermarks() -> dict:
    """Device peak/live bytes (backends exposing ``memory_stats``), host
    RSS/peak, and the kvpool page gauges — refreshed into the ``prof.mem_*``
    gauges so /metrics scrapes carry the same numbers as /debug/prof."""
    out: dict = {}
    reg = obs_metrics.registry()
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if stats:
        live = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use")
        dev = {k: v for k, v in (("bytes_in_use", live),
                                 ("peak_bytes_in_use", peak))
               if v is not None}
        if "bytes_limit" in stats:
            dev["bytes_limit"] = stats["bytes_limit"]
        if dev:
            out["device"] = dev
        if live is not None:
            reg.gauge("prof.mem_device_bytes").set(live)
        if peak is not None:
            reg.gauge("prof.mem_device_peak_bytes").set(peak)
    rss, peak = _host_rss()
    if rss is not None:
        out["host"] = {"rss_bytes": rss, "peak_bytes": peak}
        reg.gauge("prof.mem_host_rss_bytes").set(rss)
        if peak is not None:
            reg.gauge("prof.mem_host_peak_bytes").set(peak)
    kv = reg.snapshot(prefix="kvpool.")
    if kv:
        out["kvpool"] = {k.split(".", 1)[1]: v.get("value")
                         for k, v in kv.items() if v.get("type") == "gauge"}
    return out


# -- the capture control ------------------------------------------------------

class CaptureBusy(RuntimeError):
    """A second ``start`` while a capture is open (HTTP: 409)."""


class CaptureIdle(RuntimeError):
    """A ``stop`` with no capture open (HTTP: 409)."""


def _clocks() -> dict:
    """Both host clocks at one instant: the parent of a traced run keeps
    its timeline on ``perf_counter`` (system-wide on Linux), the device
    trace counts from when it opened, and this pair ties them."""
    return {"unix_ns": time.time_ns(), "perf_s": time.perf_counter()}


class Capture:
    """Start and stop tracing in the process that holds the chip: the
    ``jax.profiler`` trace, the span tracer (as ``TraceAnnotation``s) and
    stride-1 phase stamping, together. One capture at a time."""

    def __init__(self, profiler: StepProfiler):
        self._profiler = profiler
        # serializes start and stop, each of which may take seconds (the
        # profiler collects the device's trace when it stops); ``_open``
        # is written under it and read bare by ``active``, so that a
        # ``GET /debug/prof`` never waits for a profiler call
        self._lock = threading.Lock()
        self._open: dict | None = None
        # where captures are written: ``--profile DIR`` when the process
        # was launched with it, else a fresh temporary directory each
        self.directory: str | None = None

    @property
    def active(self) -> bool:
        return self._open is not None

    def start(self, auto_stop: bool = True) -> dict:
        """Open a capture; answers ``{"dir", "unix_ns", "perf_s"}``
        stamped right after the profiler opened. ``auto_stop=False`` is
        for a caller whose own ``finally`` stops it (``--profile``)."""
        with self._lock:
            if self._open is not None:
                raise CaptureBusy("a capture is already open "
                                  f"(into {self._open['dir']})")
            import jax.profiler

            directory = self.directory
            if directory is None:
                import tempfile

                directory = tempfile.mkdtemp(prefix="cake-trace-")
            os.makedirs(directory, exist_ok=True)
            tr = obs_trace.tracer()
            state = {"dir": directory, "stride": self._profiler.sample_every,
                     "tracer_was_on": tr.enabled,
                     "annotations_were_on": tr.xla_annotations,
                     "steps0": self._profiler._sampled.value, "timer": None}
            self._profiler.set_sample(1)
            if tr.enabled:
                # ``--trace`` runs the tracer for the whole process: keep
                # its buffer, only pass its spans through to the profile
                tr.xla_annotations = True
            else:
                tr.start(xla_annotations=True)
            try:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # runtime spans, no frames
                options.host_tracer_level = 2
                jax.profiler.start_trace(directory, profiler_options=options)
            except Exception:
                self._restore(state)
                raise
            opened = _clocks()
            if auto_stop:
                state["timer"] = threading.Timer(CAPTURE_MAX_S,
                                                 self._auto_stop)
                state["timer"].daemon = True
                state["timer"].name = "cake-capture-autostop"
                state["timer"].start()
            self._open = state
        log.info("capture opened into %s", directory)
        return dict(opened, dir=directory)

    def _restore(self, state: dict) -> None:
        tr = obs_trace.tracer()
        if state["tracer_was_on"]:
            tr.xla_annotations = state["annotations_were_on"]
        else:
            tr.stop()
        self._profiler.set_sample(state["stride"])

    def stop(self) -> dict:
        """Close the capture; answers ``{"dir", "unix_ns", "perf_s",
        "steps", "spans", "dropped"}``, the clocks stamped just before
        the profiler closed. The program's own spans of the capture are
        written beside the profile (``spans.trace.json``; its
        ``otherData.perf_origin_s`` is the ``perf_counter`` its ``ts``
        count from)."""
        with self._lock:
            state = self._open
            if state is None:
                raise CaptureIdle("no capture is open")
            if state["timer"] is not None:
                state["timer"].cancel()
            import jax.profiler

            # the stride and the tracer go back first: the profiler can
            # take a minute to stop (it collects the device's trace), and
            # the program's spans should end where the clocks are stamped
            closed = _clocks()
            self._restore(state)
            tr = obs_trace.tracer()
            out = dict(closed, dir=state["dir"],
                       steps=self._profiler._sampled.value - state["steps0"],
                       spans=tr.event_count(), dropped=tr.dropped)
            try:
                jax.profiler.stop_trace()
            finally:
                self._open = None
            took = time.perf_counter() - closed["perf_s"]
            try:
                tr.write_chrome_trace(
                    os.path.join(state["dir"], "spans.trace.json"))
            except OSError as e:
                log.error("could not write the capture's spans: %s", e)
            if not state["tracer_was_on"]:
                tr.clear()
        log.info("capture closed: %d steps, %d spans into %s (the "
                 "profiler took %.1f s to stop)", out["steps"],
                 out["spans"], out["dir"], took)
        return out

    def _auto_stop(self) -> None:
        try:
            self.stop()
            log.warning("capture stopped by the program after %.0f s: "
                        "nobody stopped it", CAPTURE_MAX_S)
        except CaptureIdle:
            pass  # stopped by its owner as the timer fired
        except Exception:  # a timer thread has nobody to report to
            log.exception("stopping an abandoned capture failed")


# -- process singletons + report ----------------------------------------------

_PROFILER = StepProfiler()
_SENTINEL = RetraceSentinel()
_CAPTURE = Capture(_PROFILER)
_STARTUP: dict = {}


def profiler() -> StepProfiler:
    return _PROFILER


def sentinel() -> RetraceSentinel:
    return _SENTINEL


def capture() -> Capture:
    return _CAPTURE


def capture_start(auto_stop: bool = True) -> dict:
    return _CAPTURE.start(auto_stop=auto_stop)


def capture_stop() -> dict:
    return _CAPTURE.stop()


def set_startup(**seconds: float) -> None:
    """A serving process reports the parts of its start-up once:
    ``params_s`` (checkpoint to device), ``engine_s`` (engine build),
    ``warm_s`` (scheduler start: priming and warm admissions) and
    ``loaded_s`` (main to serving, what the log's "model loaded in"
    says). They appear as ``startup`` in :func:`report`."""
    _STARTUP.clear()
    _STARTUP.update((k, round(v, 3)) for k, v in seconds.items())


def report() -> dict:
    """The /debug/prof body: all the arms in one JSON document."""
    p, s = _PROFILER, _SENTINEL
    return {
        "sample_every": p.sample_every,
        "sampled_steps": p._sampled.value,
        "phases": p.phases(),
        "recent_steps": p.recent_steps(),
        "slow_passes": p.slow_passes(),
        "capturing": _CAPTURE.active,
        "compiles": s.compiles.value,
        "retraces": s.retraces.value,
        "steady": s.steady,
        "findings": s.findings(),
        "memory": memory_watermarks(),
        **({"startup": dict(_STARTUP)} if _STARTUP else {}),
    }
