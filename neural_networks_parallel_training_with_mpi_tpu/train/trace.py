"""Distributed host-side tracing: Perfetto-ready span timelines.

The telemetry channel (``train/telemetry.py``, DESIGN.md §7) answers
*what* happened — per-step metrics, heartbeat, flight recorder.  This
module answers *where time went*: a lightweight span API
(``with trace.span("dispatch"): ...``) writing a bounded per-process
``trace-p{P}-i{I}.jsonl`` under ``--trace_dir`` (append, atomic lines; while a
loop marks laps the records wait in memory and reach the file at a lap's
boundary, see "Laps and stalls").  Every record carries the
cross-process correlation triple:

* ``process_id`` — this host process's rank (``NNPT_PROCESS_ID``, the
  DESIGN §10 world env channel, falling back to ``jax.process_index()``);
* ``run_id`` — one id for the whole JOB, stable across supervisor
  relaunches (``NNPT_RUN_ID``: set by ``train.resilience.supervise`` for
  its children, by the operator for multi-host worlds — like
  ``COORDINATOR_ADDRESS`` — or self-generated for a bare run);
* ``incarnation`` — which supervisor attempt this process is
  (``NNPT_INCARNATION``: 0 for the first launch, k for the k-th
  relaunch).

Because timestamps are unix epoch seconds, ``tools/trace_report.py``
(stdlib-only, like ``ckpt_fsck``) can merge the per-process files of a
supervised multi-process run — including files from DIFFERENT
incarnations after a crash-relaunch — onto ONE Chrome/Perfetto timeline
where the relaunch gap is visible, plus a per-phase time-share summary.

Span vocabulary (the fixed vocabulary the report tool groups by):

==============  ========================================================
``load``        host batch assembly (the loader's ``next()``)
``dispatch``    submitting one compiled step (async — host-side cost)
``fetch``       a ``device_get`` on step output (telemetry/monitor/log)
``eval``        a held-out evaluation pass
``ckpt``        a checkpoint save call (sync write or async staging)
``ckpt_write``  the async writer thread's actual disk write
``rollback``    anomaly/SDC rollback: restore + re-place
``admit`` / ``prefill`` / ``decode`` / ``land`` / ``retire``
                the serving scheduler's tick phases (serve/scheduler.py);
                ``land`` (serve/paged_kv.py ``land``) is the host's wait
                for finished streams' rows, behind the tick's programs:
                its length is the device's lead, not a loss
``prefill/prepare`` / ``prefill/submit`` / ``prefill/first_token``
                inside ``prefill`` (serve/paged_kv.py ``prefill_step``):
                host bookkeeping and uploads, the program call, the
                first token's one program on the last chunk
``decode/prepare`` / ``decode/submit`` / ``decode/finish``
                inside ``decode`` (``PagedDecodeServer.dispatch``): block
                supply checks and uploads, the program call, the position
                loop and the take of finished streams' rows (no wait)
``queue_wait``  serving inter-tick gap with requests queued but no slot
``sched_bubble``
                serving inter-tick gap with decoding streams in flight
                (the scheduler loop, not the model, owned that time)
``compile:<n>`` a ledger-observed XLA compile (utils/compile_ledger.py)
``stall``       one lap of a loop (a scheduler tick, a train step) that ran
                long, recorded after the fact by :class:`LapWatch` over
                the lap's whole length, with where it stood and what the
                thread was doing (attributes below)
==============  ========================================================

A span record names its ``parent``: the span of the same (main) thread that
was open when it was entered.

**Laps and stalls.**  A loop marks the start of each iteration with
:meth:`LapWatch.lap` (``Scheduler.tick``: ``serve_tick``; ``Trainer.fit``:
``train_step``), tracer or not, main thread only.  At each boundary the watch
takes the lap's wall time, the **self time** of every span that closed in it
(duration less what its child spans covered), the time under no span, and
what the thread and the process did meanwhile: thread CPU seconds, context
switches and major faults (``getrusage(RUSAGE_THREAD)``), process CPU
seconds (``time.process_time``), the run-queue delay of
``/proc/thread-self/schedstat`` where the kernel has it, garbage-collection
pauses (``gc.callbacks``), the seconds the tracer spent writing.  The last
``LAP_RING`` laps stay in memory (``watch.ring``: number, unix start, wall,
largest span and its self time).  A lap is a **stall** when its wall time
exceeds ``max(STALL_FLOOR_S, STALL_RATIO x the median of the last
STALL_MEDIAN_LAPS laps)``, once ``STALL_WARMUP_LAPS`` laps have gone by.  The
floor is what keeps a healthy landing out: a serving host that has queued
programs ahead waits for all of them in ``land`` when a row is due, up to
0.29 s measured (``STALL_FLOOR_S``).  The OS's counters cost a system
call each, so a boundary reads them only once the last reading is older than
a fifth of the floor: a stall's ``cpu_s``, ``cpu_other_s``, ``run_delay_s``,
switches and faults are taken over its lap and at most that much before it.  A
stall is counted (``watch.stalls``, ``watch.stall_s``: the excess over the
median), kept for one line on stderr when the loop ends
(:meth:`LapWatch.end`), and, where a tracer is installed, recorded as a span
``stall`` (start and duration the lap's) with: ``loop``, ``n``, ``median_s``,
``excess_s``, ``where`` (the span with the largest self time; ``"between
laps"`` when more of the lap lay after its last span, which in serving is the
caller's time between two ticks; ``"no span"`` when more lay elsewhere
outside every span), ``where_s``, ``cpu_s``, ``cpu_other_s`` (the process's
CPU seconds less the thread's: other threads), ``run_delay_s``, ``nvcsw``,
``nivcsw``, ``majflt``, ``gc_s``, ``gc_gen``, ``trace_write_s``,
``compiles``, ``t_now`` (the caller's clock at the lap's start), ``t_perf``
(``perf_counter`` there), read only then ``loadavg`` and ``psi_cpu`` /
``psi_io`` / ``psi_mem`` (the ``some avg10`` of ``/proc/pressure/*``), and
``cause``, the first of these that holds:

==============  ========================================================
``compile``     a ``compile:<n>`` span closed in the lap
``gc``          ``gc_s`` is at least half of ``excess_s``
``page_fault``  ``majflt`` > 0 and ``cpu_s`` under half the wall time
``python``      ``cpu_s`` at least half the wall time: the thread was
                running, the program's own work or a C call that spins
``descheduled`` ``run_delay_s`` at least half of ``excess_s`` (without
                schedstat: ``nivcsw`` > 0 and ``cpu_s`` under half the
                wall time): runnable, no core
``gil``         ``cpu_other_s`` at least half of ``excess_s``
``waiting``     none of these: off the CPU by its own call; under
                ``land``, ``fetch``, ``decode/submit``, ``prefill/submit``
                that is the device or its runtime, with ``trace_write_s``
                the disk
==============  ========================================================

**No write inside a span.**  While a loop marks laps, closing a span appends
its record to a list and returns; the list reaches the file at a lap's
boundary, at most once a second or every ``FLUSH_RECORDS`` records (the
lap's ``trace_write_s``), at ``close()``, at interpreter exit and from
:func:`flush` on the hard-exit paths (``telemetry.emergency_dump``).  A
process killed outright loses at most the last second of its spans.  A
tracer under which no loop laps (a supervisor, a router, a tool) has no
boundary to write at and writes each record as it is made.

**The profiler mirror.**  Every span also enters a
``jax.profiler.TraceAnnotation("nnpt:<name>")``, whether or not a
:class:`Tracer` is installed, so a ``jax.profiler`` capture holds the
program's own phases on the same clock as the device's operations and
each idle gap of the device can be put down to the span it fell in
(``benchmark/reducers/host_phases.py``).  The train step's ``dispatch``
sits inside a ``StepTraceAnnotation("nnpt:train_step", step_num=k)``
(:func:`step_annotation`).  The JSONL record stays conditional on the
tracer.  This module is the only caller of ``TraceAnnotation``.

Besides spans, a tracer can emit **flow points** (:func:`flow`): the
Chrome s/t/f arrow chain that links spans by an id.  The serving
scheduler threads each request id through admit -> every prefill chunk
-> its first decode tick -> retire (a point per phase change), so
``tools/trace_report.py``'s merged Perfetto timeline draws one request's whole life as a connected arrow
path across the per-tick phase spans (and, once blocks hand off across
replicas, across processes).

Relationship to the XLA profiler (``--xla_trace_dir`` →
``utils.profiling.trace``): the profiler captures *device* activity —
per-op HLO timelines, one heavyweight capture window, leader-gated,
viewed in TensorBoard/XProf.  This module captures *host* phases —
always-on-able, cross-process, crash-surviving — and mirrors them into
that capture.  Run both on a real chip: host spans say which phase
starved the device; the XLA trace says what the device did inside it.

Cost with no tracer installed: one small object, one annotation (a no-op
in the profiler's C++ while no capture runs) and one tuple in a bounded
deque per span; per lap, two clock reads and a walk over the lap's spans,
and the OS's counters (two or three system calls) at most once every fifth
of the floor (microseconds, PERF.md section 6); nothing is written.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import gc
import json
import os
import resource
import statistics
import threading
import time
from typing import Any, Dict, List, Optional

try:    # the mirror needs jax; the span files do not, and a stdlib-only
    # process may load this module alone (tests/test_goodput.py's child)
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
except ImportError:
    StepTraceAnnotation = TraceAnnotation = None

RUN_ID_ENV = "NNPT_RUN_ID"
INCARNATION_ENV = "NNPT_INCARNATION"
PROCESS_ID_ENV = "NNPT_PROCESS_ID"  # the DESIGN §10 world env channel

# prefix of every span's mirror in a jax.profiler capture
ANNOTATION_PREFIX = "nnpt:"

# bounded trace discipline: after this many records the file stops
# growing and the footer reports how many spans were dropped — a
# runaway serving loop must not fill the disk the way an unbounded
# logger would
DEFAULT_MAX_EVENTS = 100_000

# while a loop marks laps the pending records reach the file at a lap's
# boundary, when this many seconds have gone by or this many records wait
FLUSH_SECONDS = 1.0
FLUSH_RECORDS = 4096

# a record's line; an attribute json cannot spell is written as its repr,
# since the line is made long after the span that carried it closed
_encode = json.JSONEncoder(default=repr).encode


def run_identity() -> Dict[str, Any]:
    """The (process_id, run_id, incarnation) triple for THIS process.
    Env-first (the supervisor/operator channel); process_id falls back
    to ``jax.process_index()`` when the env channel is unset (TPU pods
    auto-configure their world), then 0."""
    pid_env = os.environ.get(PROCESS_ID_ENV)
    if pid_env is not None and pid_env != "":
        process_id = int(pid_env)
    else:
        try:
            import jax

            process_id = int(jax.process_index())
        except Exception:
            process_id = 0
    run_id = os.environ.get(RUN_ID_ENV) or ""
    if not run_id:
        run_id = f"run-{int(time.time())}-{os.getpid()}"
    try:
        incarnation = int(os.environ.get(INCARNATION_ENV) or 0)
    except ValueError:
        incarnation = 0
    return {"process_id": process_id, "run_id": run_id,
            "incarnation": incarnation}


class Tracer:
    """Per-process span writer.  One file per (process, incarnation) so
    a supervised relaunch never clobbers its predecessor's timeline;
    thread-safe (the async checkpoint writer emits from its own
    thread)."""

    def __init__(self, dirpath: str, process_id: int, run_id: str,
                 incarnation: int, max_events: int = DEFAULT_MAX_EVENTS):
        os.makedirs(dirpath, exist_ok=True)
        self.process_id = int(process_id)
        self.run_id = str(run_id)
        self.incarnation = int(incarnation)
        self.max_events = int(max_events)
        self.path = os.path.join(
            dirpath, f"trace-p{self.process_id}-i{self.incarnation}.jsonl")
        self._ident = {"p": self.process_id, "run": self.run_id,
                       "inc": self.incarnation}
        # _lock guards the counters and the pending list (every thread that
        # closes a span); _io_lock the file, so that no closing thread ever
        # waits for the disk
        self._lock = threading.Lock()
        self._io_lock = threading.Lock()
        self._f: Optional[Any] = open(self.path, "a")
        self._pending: List[Dict[str, Any]] = []
        self._lines: List[str] = []     # made from them, not yet written
        # True from a loop's first lap to its end: records wait in
        # ``_pending`` for the next boundary
        self._buffered = False
        self._t_written = time.perf_counter()
        self.write_s = 0.0          # seconds spent serialising and writing
        self.events = 0
        self.dropped = 0
        self._emit({"kind": "meta", "t": round(time.time(), 6),
                    "pid": os.getpid(), **self._ident})
        atexit.register(self.flush)

    def _emit(self, rec: Dict[str, Any], bounded: bool = False) -> None:
        # bound check + counter update under the SAME lock as the append:
        # the async checkpoint writer emits from its own thread, and an
        # unsynchronized check-then-increment could overshoot the bound
        # or miscount the footer
        with self._lock:
            if bounded:
                if self.events >= self.max_events:
                    self.dropped += 1
                    return
                self.events += 1
            if self._f is None:
                return
            self._pending.append(rec)
            if self._buffered:
                return
        self.flush()

    def flush(self, wait_s: float = -1, write: bool = True) -> None:
        """Make the pending records into lines and, under ``write``, hand
        the lines to the file.  ``wait_s`` bounds the wait for a writer
        that is stuck in the disk (the hard-exit paths must not hang on
        it); the default waits."""
        if not self._io_lock.acquire(timeout=wait_s):
            return
        try:
            t0 = time.perf_counter()
            with self._lock:
                batch, self._pending = self._pending, []
            self._lines += [_encode(r) + "\n" for r in batch]
            if write:
                if self._lines and self._f is not None:
                    self._f.write("".join(self._lines))
                    self._f.flush()
                self._lines.clear()
                self._t_written = time.perf_counter()
            self.write_s += time.perf_counter() - t0
        finally:
            self._io_lock.release()

    def at_lap(self, t_perf: float) -> None:
        """A lap's boundary (:meth:`LapWatch.lap`): from here on records
        wait in memory.  They are made into lines here, a lap's worth at a
        time, and the lines reach the file here when a second has gone by
        or ``FLUSH_RECORDS`` of them wait."""
        self._buffered = True
        if self._pending:
            self.flush(write=(
                t_perf - self._t_written >= FLUSH_SECONDS
                or len(self._lines) + len(self._pending) >= FLUSH_RECORDS))

    def end_laps(self) -> None:
        """The lapping loop ended: write what waits, and write through
        again."""
        self._buffered = False
        self.flush()

    def record_span(self, name: str, t_unix: float, dur_s: float,
                    attrs: Dict[str, Any],
                    parent: Optional[str] = None) -> None:
        rec = {"kind": "span", "name": name, "t": round(t_unix, 6),
               "dur": round(dur_s, 6), **self._ident}
        thread = threading.current_thread()
        if thread is not threading.main_thread():
            rec["thread"] = thread.name
        if parent is not None:
            rec["parent"] = parent
        if attrs:
            rec.update(attrs)
        self._emit(rec, bounded=True)
        if _SPAN_LISTENERS:
            for fn in tuple(_SPAN_LISTENERS):
                try:
                    fn(name, t_unix, dur_s, attrs)
                except Exception:
                    pass

    def flow(self, name: str, flow_id: Any, phase: str, **attrs) -> None:
        """One point of a Perfetto FLOW — an arrow chain linking spans
        across ticks/threads/processes by ``flow_id``.  ``phase``:
        ``"s"`` start, ``"t"`` step, ``"f"`` finish (the Chrome
        trace-event flow vocabulary).  The serving scheduler threads a
        request id through admit -> each prefill chunk -> first decode
        tick -> retire this way, so one request's life is one connected
        arrow path across the per-tick phase spans."""
        if phase not in ("s", "t", "f"):
            raise ValueError(f"flow phase must be s/t/f, got {phase!r}")
        self._emit({"kind": "flow", "name": name, "id": str(flow_id),
                    "fph": phase, "t": round(time.time(), 6),
                    **self._ident, **attrs}, bounded=True)

    def close(self) -> None:
        atexit.unregister(self.flush)
        with self._lock:
            if self._f is None:
                return
            self._pending.append(
                {"kind": "meta", "t": round(time.time(), 6),
                 "events": self.events, "dropped": self.dropped,
                 "final": True, **self._ident})
        self.flush()
        with self._io_lock, self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


# ---------------------------------------------------------------------------
# module-level active tracer + the cheap span() entrypoint
# ---------------------------------------------------------------------------

_ACTIVE: Optional[Tracer] = None

# span listeners: callables ``fn(name, t_unix, dur_s, attrs)`` invoked for
# every recorded span, from whichever thread recorded it.  This is how
# ``utils/goodput.py``'s in-process meter observes the span stream without
# re-reading the trace file; the disabled-path cost is one empty-list
# truthiness check inside record_span.  Listener exceptions are swallowed —
# accounting must never take down the traced process.
_SPAN_LISTENERS: list = []


def add_listener(fn) -> None:
    """Register a span listener (idempotent)."""
    if fn not in _SPAN_LISTENERS:
        _SPAN_LISTENERS.append(fn)


def remove_listener(fn) -> None:
    """Unregister a span listener; missing listeners are ignored."""
    try:
        _SPAN_LISTENERS.remove(fn)
    except ValueError:
        pass


# the main thread's open spans, innermost last, and its closed ones as
# (name, self seconds, perf_counter at exit): what a lap reads.
# Other threads' spans are recorded but neither nested nor read by laps.
_MAIN_IDENT = threading.main_thread().ident
_get_ident = threading.get_ident
_OPEN: list = []
_CLOSED: collections.deque = collections.deque(maxlen=1024)


class _Span:
    __slots__ = ("name", "attrs", "_t_unix", "_t0", "_mirror", "_child_s")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self._mirror = annotation(self.name)
        self._mirror.__enter__()
        if _get_ident() == _MAIN_IDENT:
            self._child_s = 0.0     # what spans entered inside this one cover
            _OPEN.append(self)
        else:
            self._child_s = None
        self._t_unix = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        dur = t1 - self._t0
        self._mirror.__exit__(*exc)
        parent = None
        child_s = self._child_s
        if child_s is not None:
            if _OPEN.pop() is not self:     # closed out of order
                _OPEN[:] = [sp for sp in _OPEN if sp is not self]
            if _OPEN:
                outer = _OPEN[-1]
                outer._child_s += dur
                parent = outer.name
            _CLOSED.append((self.name, dur - child_s, t1))
        tracer = _ACTIVE
        if tracer is not None:
            tracer.record_span(self.name, self._t_unix, dur, self.attrs,
                               parent)
        return False


def span(name: str, **attrs):
    """``with trace.span("dispatch", step=k): ...`` — mirrored into a
    running ``jax.profiler`` capture as ``nnpt:<name>``; recorded to the
    JSONL timeline only while a tracer is installed."""
    return _Span(name, attrs)


def annotation(name: str):
    """The bare profiler mirror of a span, for a phase whose JSONL record
    is written after the fact (the scheduler's between-tick gaps): the
    caller enters and exits it."""
    if TraceAnnotation is None:
        return contextlib.nullcontext()
    return TraceAnnotation(ANNOTATION_PREFIX + name)


def step_annotation(step: int):
    """``nnpt:train_step`` around one train step's host work, as a
    ``StepTraceAnnotation`` so profile viewers group the device's
    operations by step."""
    if StepTraceAnnotation is None:
        return contextlib.nullcontext()
    return StepTraceAnnotation(ANNOTATION_PREFIX + "train_step",
                               step_num=step)


def flow(name: str, flow_id: Any, phase: str, **attrs) -> None:
    """Emit one flow point (see :meth:`Tracer.flow`); no-op when no
    tracer is installed — per-request flow tracing costs nothing on an
    untraced serving process."""
    tracer = _ACTIVE
    if tracer is not None:
        tracer.flow(name, flow_id, phase, **attrs)


def active() -> Optional[Tracer]:
    return _ACTIVE


def install(tracer: Optional[Tracer]) -> None:
    global _ACTIVE
    _ACTIVE = tracer


def flush() -> None:
    """Hand the active tracer's pending records to the file, from a path
    that ends in ``os._exit`` (an injected crash, the hang watchdog, a lost
    peer), where no exit hook runs.  Never waits long for a writer that is
    itself stuck in the disk."""
    tracer = _ACTIVE
    if tracer is not None:
        tracer.flush(wait_s=2.0)


def traced_iter(name: str, it, before=None):
    """Wrap an iterator so each ``next()`` is a span (the trainer's
    ``load`` phase), with ``before()`` called ahead of each (the trainer's
    lap mark).  The wrapper closes the inner iterator
    deterministically (the loader's prefetch-worker release contract)."""

    def gen():
        inner = iter(it)
        try:
            while True:
                if before is not None:
                    before()
                with span(name):
                    try:
                        item = next(inner)
                    except StopIteration:
                        return
                yield item
        finally:
            close = getattr(inner, "close", None)
            if close is not None:
                close()

    return gen()


# ---------------------------------------------------------------------------
# laps and stalls (module docstring, "Laps and stalls")
# ---------------------------------------------------------------------------

# No lap under the floor is a stall, and none under this many medians.  The
# floor lies above the longest healthy lap there is: nothing holds a serving
# host to the device between two landings, so it dispatches as far ahead as
# the runtime queues programs, and the tick that lands a finished row then
# waits in ``land`` for all of them (30 decode programs, 0.25-0.29 s of a
# 9.4 ms tick, where an answer ends every 60-200 ticks: PERF.md section 6,
# PR 37).  That wait is the device's lead, not a loss.
STALL_FLOOR_S = 0.5
STALL_RATIO = 8.0
STALL_WARMUP_LAPS = 16      # laps that go by before one is judged
STALL_MEDIAN_LAPS = 64      # the median is over this many of the last laps
LAP_RING = 256              # laps kept in memory a loop
STALLS_KEPT = 32            # stall records kept a loop

# cumulative garbage-collection seconds and collections of this process, and
# the last of them as (perf_counter at end, generation, seconds)
_GC_TOTAL = [0.0, 0]
_GC_T0 = [0.0]
_GC_LOG: collections.deque = collections.deque(maxlen=256)
# /proc/thread-self/schedstat of the main thread, held open: None before the
# first lap, -1 where the kernel has no such file
_SCHEDSTAT_FD: Optional[int] = None


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        _GC_T0[0] = time.perf_counter()
        return
    t1 = time.perf_counter()
    _GC_TOTAL[0] += t1 - _GC_T0[0]
    _GC_TOTAL[1] += 1
    _GC_LOG.append((t1, info["generation"], t1 - _GC_T0[0]))


def _forget_schedstat() -> None:
    global _SCHEDSTAT_FD     # a forked child's main thread is another task
    _SCHEDSTAT_FD = None


def _start_probes() -> None:
    """Once a process, at its first lap, on the main thread."""
    global _SCHEDSTAT_FD
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
        os.register_at_fork(after_in_child=_forget_schedstat)
    try:
        _SCHEDSTAT_FD = os.open("/proc/thread-self/schedstat", os.O_RDONLY)
    except OSError:
        _SCHEDSTAT_FD = -1


def _readings() -> tuple:
    """(thread CPU s, process CPU s, voluntary switches, involuntary
    switches, major faults, run-queue delay ns or None) of the calling
    thread: cumulative, differenced by a stall.  Two or three system calls,
    which is why a lap does not always make them (``LapWatch.lap``)."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    delay = None
    if _SCHEDSTAT_FD >= 0:
        delay = int(os.pread(_SCHEDSTAT_FD, 96, 0).split()[1])
    return (ru.ru_utime + ru.ru_stime, time.process_time(), ru.ru_nvcsw,
            ru.ru_nivcsw, ru.ru_majflt, delay)


def _proc_float(path: str, field: str = "") -> Optional[float]:
    """The first number of ``path``, or the one after ``field`` on its
    first line; None where the kernel has no such file."""
    try:
        with open(path) as f:
            line = f.readline()
        return float(line.split(field, 1)[1].split()[0] if field
                     else line.split()[0])
    except (OSError, ValueError, IndexError):
        return None


def stall_cause(rec: Dict[str, Any]) -> str:
    """The written rule (module docstring), first match wins."""
    wall, excess, cpu = rec["wall_s"], rec["excess_s"], rec["cpu_s"]
    if rec["compiles"]:
        return "compile"
    if rec["gc_s"] >= 0.5 * excess:
        return "gc"
    if rec["majflt"] > 0 and cpu < 0.5 * wall:
        return "page_fault"
    if cpu >= 0.5 * wall:
        return "python"
    if (rec["nivcsw"] > 0 if rec["run_delay_s"] is None
            else rec["run_delay_s"] >= 0.5 * excess):
        return "descheduled"
    if rec["cpu_other_s"] >= 0.5 * excess:
        return "gil"
    return "waiting"


def stall_line(rec: Dict[str, Any]) -> str:
    """One stall as one line of a log."""
    delay = rec["run_delay_s"]
    out = (f"[trace] stall: {rec['loop']} {rec['n']}, {rec['wall_s']:.2f} s "
           f"(median {rec['median_s']:.4f}), in {rec['where']} "
           f"{rec['where_s']:.2f} s, {rec['cause']}: cpu {rec['cpu_s']:.2f} s"
           f", other threads {rec['cpu_other_s']:.2f} s, run delay "
           f"{'n/a' if delay is None else format(delay, '.2f') + ' s'}, "
           f"{rec['nvcsw']} voluntary and {rec['nivcsw']} involuntary "
           f"switches, {rec['majflt']} major faults")
    if rec["gc_s"]:
        out += f", gc {rec['gc_s']:.2f} s (gen {rec['gc_gen']})"
    if rec["trace_write_s"]:
        out += f", trace write {rec['trace_write_s']:.2f} s"
    for key, label in (("psi_cpu", "cpu"), ("psi_io", "io"),
                       ("psi_mem", "memory")):
        if rec[key]:
            out += f", {label} pressure {rec[key]:g} %"
    if rec["loadavg"] is not None:
        out += f", load {rec['loadavg']:g}"
    return out


class LapWatch:
    """The laps of one loop, and the stalls among them (module docstring,
    "Laps and stalls").  The loop's owner makes one, calls :meth:`lap` at
    the top of each iteration and :meth:`end` when the loop is over; the
    thresholds are arguments so that a test can plant a short stall."""

    def __init__(self, loop: str, floor_s: float = STALL_FLOOR_S,
                 ratio: float = STALL_RATIO,
                 warmup: int = STALL_WARMUP_LAPS):
        self.loop = loop
        self.floor_s, self.ratio, self.warmup = floor_s, ratio, warmup
        self.laps = 0
        self.stalls = 0
        self.stall_s = 0.0      # the stalls' seconds over the median
        # (n, unix start, wall s, largest span, its self s) of the last laps
        self.ring: collections.deque = collections.deque(maxlen=LAP_RING)
        # the last stalls' records, and how many :meth:`end` has given out
        self.records: collections.deque = collections.deque(
            maxlen=STALLS_KEPT)
        self._reported = 0
        self._open: Optional[tuple] = None
        # the OS's counters as last read, and when: read anew at a boundary
        # once they are older than a fifth of the floor, so that a stall's
        # differences span little more than its lap and a short lap pays no
        # system call
        self._os: Optional[tuple] = None
        self._t_os = 0.0

    def lap(self, n: int, t_now: Optional[float] = None) -> None:
        """Iteration ``n`` starts here, and the one before it ends;
        ``t_now`` is the caller's own clock, where it has one.  Off the
        main thread this does nothing."""
        if _get_ident() != _MAIN_IDENT:
            return
        if _SCHEDSTAT_FD is None:
            _start_probes()
        tracer = _ACTIVE
        t = time.perf_counter()
        if self._open is not None:
            self._close(t, tracer)
        if self._os is None or t - self._t_os >= 0.2 * self.floor_s:
            self._os, self._t_os = _readings(), t
        self._open = (n, t, time.time(), t_now, _GC_TOTAL[0],
                      tracer.write_s if tracer is not None else 0.0)
        if tracer is not None:
            tracer.at_lap(t)    # its seconds fall in the lap that opens

    def _close(self, t: float, tracer: Optional[Tracer]) -> None:
        n, t0, t_unix, t_now, gc_s, write_s = self._open
        wall = t - t0
        where, where_s = None, 0.0
        for closed in reversed(_CLOSED):    # this lap's spans, newest first
            if closed[2] <= t0:
                break
            if closed[1] > where_s:
                where, where_s = closed[:2]
        self.ring.append((n, t_unix, wall, where, where_s))
        self.laps += 1
        if wall <= self.floor_s or self.laps <= self.warmup:
            return
        median = statistics.median(
            [lap[2] for lap in list(self.ring)[-STALL_MEDIAN_LAPS - 1:-1]])
        if wall <= self.ratio * median:
            return
        # ---- a stall: off the hot path from here on ----------------------
        mine = [c for c in _CLOSED if t0 < c[2] <= t]
        compiles = sum(1 for c in mine if c[0].startswith("compile:"))
        tail = t - mine[-1][2] if mine else 0.0
        bare = max(0.0, wall - sum(c[1] for c in mine) - tail)
        if tail >= max(where_s, bare):
            where, where_s = "between laps", tail
        elif bare > where_s:
            where, where_s = "no span", bare
        then, now = self._os, _readings()
        self._os, self._t_os = now, t
        cpu = now[0] - then[0]
        gens = [g for t1, g, _s in _GC_LOG if t0 < t1 <= t]
        rec = {
            "loop": self.loop, "n": n, "wall_s": round(wall, 6),
            "median_s": round(median, 6),
            "excess_s": round(wall - median, 6),
            "where": where, "where_s": round(where_s, 6),
            "cpu_s": round(cpu, 6),
            "cpu_other_s": round(max(0.0, now[1] - then[1] - cpu), 6),
            "run_delay_s": (None if now[5] is None or then[5] is None
                            else round((now[5] - then[5]) / 1e9, 6)),
            "nvcsw": now[2] - then[2], "nivcsw": now[3] - then[3],
            "majflt": now[4] - then[4],
            "gc_s": round(_GC_TOTAL[0] - gc_s, 6),
            "gc_gen": max(gens) if gens else None,
            "trace_write_s": round(
                (tracer.write_s if tracer is not None else 0.0) - write_s, 6),
            "compiles": compiles, "t_now": t_now, "t_perf": round(t0, 6),
            "loadavg": _proc_float("/proc/loadavg"),
            "psi_cpu": _proc_float("/proc/pressure/cpu", "avg10="),
            "psi_io": _proc_float("/proc/pressure/io", "avg10="),
            "psi_mem": _proc_float("/proc/pressure/memory", "avg10="),
        }
        rec["cause"] = stall_cause(rec)
        self.stalls += 1
        self.stall_s += wall - median
        self.records.append(rec)
        if tracer is not None:
            tracer.record_span(
                "stall", t_unix, wall,
                {k: v for k, v in rec.items() if k != "wall_s"})

    def end(self) -> List[str]:
        """The loop is over: the open lap is dropped unjudged (what follows
        a loop is not an iteration of it), the tracer writes through again,
        and the stalls not yet given out come back as one line each, for
        the caller's log."""
        self._open = None
        if _ACTIVE is not None:
            _ACTIVE.end_laps()
        fresh, self._reported = self.stalls - self._reported, self.stalls
        if not fresh:
            return []
        lines = [stall_line(rec) for rec in list(self.records)[-fresh:]]
        if fresh > len(lines):
            lines.append(f"[trace] stall: {self.loop}: {fresh - len(lines)} "
                         "more, not listed")
        return lines


# ---------------------------------------------------------------------------
# run lifecycle: one call installs the tracer AND the compile ledger
# ---------------------------------------------------------------------------

def dir_from_config(cfg) -> Optional[str]:
    """Resolve the effective trace directory from a TrainConfig-shaped
    object: ``--trace_dir`` wins; bare ``--trace`` rides
    ``--telemetry_dir`` (a ``trace/`` subdir, so one run directory holds
    the whole observability bundle)."""
    trace_dir = getattr(cfg, "trace_dir", None)
    if trace_dir:
        return trace_dir
    if getattr(cfg, "trace", False):
        tdir = getattr(cfg, "telemetry_dir", None)
        if not tdir:
            raise ValueError(
                "--trace needs --telemetry_dir (spans land in its trace/ "
                "subdir) or an explicit --trace_dir")
        return os.path.join(tdir, "trace")
    return None


def start_run(dirpath: str, max_events: int = DEFAULT_MAX_EVENTS,
              ledger: bool = True) -> Tracer:
    """Create + install the process tracer for ``dirpath`` and (by
    default) the compile ledger next to it (``compiles-p{P}-i{I}.jsonl``
    in the same directory).  Returns the tracer; ``stop_run()`` closes
    both."""
    ident = run_identity()
    tracer = Tracer(dirpath, ident["process_id"], ident["run_id"],
                    ident["incarnation"], max_events=max_events)
    install(tracer)
    if ledger:
        from ..utils import compile_ledger

        compile_ledger.install(compile_ledger.Ledger(
            os.path.join(dirpath,
                         f"compiles-p{ident['process_id']}"
                         f"-i{ident['incarnation']}.jsonl"),
            **ident))
    return tracer


def stop_run(tracer: Optional[Tracer] = None) -> None:
    """Close + uninstall the tracer (and the compile ledger, if one is
    installed).  With an explicit ``tracer``, only uninstalls when that
    tracer is still the active one — a later ``start_run`` wins."""
    global _ACTIVE
    from ..utils import compile_ledger

    target = tracer if tracer is not None else _ACTIVE
    if target is not None:
        target.close()
    if target is _ACTIVE:
        _ACTIVE = None
        led = compile_ledger.active()
        if led is not None:
            led.close()
            compile_ledger.install(None)
