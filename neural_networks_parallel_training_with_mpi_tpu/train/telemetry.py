"""Training telemetry: on-device step metrics, flight recorder, MFU
accounting, and the run-health heartbeat.

The reference's only observable is a per-epoch loss ``print`` (SURVEY.md
§5.5); the previous layer here was a host-side ``StepTimer`` plus a
leader-only JSONL.  Neither can explain *why* a step is slow, what the
skip-guard/rollback machinery (DESIGN.md §6) actually did, or how close a
run sits to hardware peak — the operating metrics of production TPU
training (per-step MFU and compiled-step telemetry; Yoo et al.
arXiv:2204.06514, Hessel et al. arXiv:2104.06272).  Four pillars:

1. **On-device step metrics** — the DP / DP x SP / GSPMD train steps can
   return a small metrics vector next to the loss (``with_metrics=True``):
   global grad norm, param norm, update/param ratio, and the skip-guard
   CUMULATIVE rejection counter (sample-loss-proof), all computed
   inside the jitted step from values the step
   already owns.  The grad norm REUSES the skip-guard's reduction via
   ``Optimizer.update_with_norm`` — one norm pass, not two — and the
   update math is untouched, so params are bitwise-identical with metrics
   on vs off (tests/test_telemetry.py pins this).  Futures are fetched at
   dispatch boundaries at the same lag-2 discipline ``ResilienceMonitor``
   uses, so the async pipeline is never forced to sync; measured overhead
   at the CPU-bench transformer scale (4L/d256/T128/B64, interleaved
   A/B pairs): +0.7% best rep / +1.8% median on the single-core
   8-virtual-device host — an upper bound that serializes every
   replica's norm work onto one core (DESIGN.md §7;
   tests/test_telemetry.py::test_telemetry_happy_path_overhead).
2. **Flight recorder** — a bounded ring of the last N step records and
   events (skips, rollbacks, faults), dumped as ``postmortem.json`` on
   crash (unhandled exception or an injected ``crash`` fault), rollback,
   anomaly abort (exit 44), hang (watchdog), and SIGTERM — so a relaunch
   log can point at WHAT the run was doing when it died
   (``train.resilience.supervise`` prints the pointer).
3. **MFU / FLOPs accounting** — analytic per-step matmul/conv FLOPs from
   the model config (``Module.fwd_flops``: MLP, ConvNet, Transformer incl.
   attention + CE head, GQA-, SwiGLU- and MoE-top-k-aware; ``ce_chunk``
   changes memory, not the analytic FLOPs) against the backend peak-FLOPs
   table below.  MFU exists on a TPU only: off-TPU the records carry
   ``mfu: null``, and a TPU kind missing from the table is an error.
4. **Run-health heartbeat** — a leader-written, atomically-replaced
   ``heartbeat.json`` (step, dispatch timestamp, steps/sec EMA, last
   metrics snapshot) refreshed per dispatch (throttled to
   ``_HEARTBEAT_MIN_INTERVAL_S``), consumed by
   ``train.resilience.supervise`` for external hang detection (a wedged
   child is killed and retried as exit 42) and rendered by
   ``tools/metrics_summary.py``.

Layout under ``--telemetry_dir``::

    metrics.jsonl     per-step records (step, loss, grad_norm, param_norm,
                      update_ratio, skipped, step_time_ms, samples/sec, mfu)
                      plus kind="rollup" sketch snapshots (serialized
                      utils/sketches.py state on the --rollup_every
                      cadence, merged fleet-wide by tools/obs_agg.py)
                      and kind="alert" records (EMA z-score anomalies on
                      loss/grad_norm/samples-per-sec; observe-and-
                      annotate — nothing acts on them)
    heartbeat-<role>-p<P>.json
                      freshest run-health snapshot (atomic replace), one
                      file per role ("train"/"rl"/"serve") and process —
                      two programs sharing one dir can no longer blind
                      the staleness monitor by last-writer-winning over a
                      single heartbeat.json (readers fall back from the
                      legacy shared name to the freshest qualified file)
    postmortem.json   flight-recorder dump, written on abnormal events

The stream is SHARED with the serving runtime: serve/scheduler.py writes
``kind="serve"`` tick records and ``kind="serve_req"`` per-request
completions into the same metrics.jsonl schema and beats its own
role-qualified heartbeat (through :class:`Heartbeat`), so the
supervisor's stale-heartbeat monitor and tools/metrics_summary.py treat
a serving process exactly like a training run.

Everything is zero-cost when ``telemetry_dir`` is unset, and file writes
are leader-only (multi-host safe).
"""

from __future__ import annotations

import collections
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.optim import GuardedState, Optimizer, global_norm
from ..utils import goodput as goodput_lib
from ..utils.logging import is_leader, log
from ..utils.sketches import EmaZScore, ErrorBudget, Gauge, QuantileSketch
from . import trace as trace_lib

Pytree = Any

# keys every on-device metrics dict carries (the jitted step returns
# exactly these; ops consumers and tests key off this tuple)
METRIC_KEYS = ("loss", "grad_norm", "param_norm", "update_ratio", "skipped")

# heartbeat writes are throttled: a dispatch-bound micro-model can run
# thousands of dispatches/sec and the heartbeat must never become the
# bottleneck it is meant to watch
_HEARTBEAT_MIN_INTERVAL_S = 0.5

# ---------------------------------------------------------------------------
# Pillar 3: FLOPs / MFU accounting
# ---------------------------------------------------------------------------

# Peak dense bf16 FLOPs/s per chip by device_kind substring (public specs).
PEAK_FLOPS = (
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12), ("v5e", 197e12), ("v5", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
)


def peak_flops_per_chip(device_kind: str) -> Optional[float]:
    """Accelerator peak dense bf16 FLOPs/s by device-kind substring; None
    for a device that is not a TPU (a CPU host).  A TPU kind the table
    does not know is an error, never a default."""
    kind = (device_kind or "").lower()
    for key, val in PEAK_FLOPS:
        if key in kind:
            return val
    if "tpu" in kind:
        raise ValueError(
            f"no peak FLOPs/s entry for TPU device_kind {device_kind!r}: "
            "add it to train.telemetry.PEAK_FLOPS with its source")
    return None


def telemetry_peak_flops(device_kind: str, platform: str) -> Optional[float]:
    """The MFU denominator for the telemetry stream: the chip's peak on a
    TPU, None anywhere else — off-TPU records carry ``mfu: null`` instead
    of a utilization against an invented peak."""
    if platform != "tpu":
        return None
    return peak_flops_per_chip(device_kind)


def train_step_flops(model, batch_shape: Tuple[int, ...]) -> Optional[float]:
    """Analytic matmul/conv FLOPs of ONE optimizer step on a batch of
    ``batch_shape``: forward + ~2x forward for the backward (the standard
    convention).  None for unaccounted architectures.  Accounting lives on
    the models themselves (``Module.fwd_flops`` — transformer counts qkv/
    out/FFN/attention scores+values and the CE/LM head, honoring GQA's
    narrower qkv projection, SwiGLU's gate matmul and MoE's top-k experts
    + router; ``ce_chunk`` only changes peak memory, never the math)."""
    fwd = model.fwd_flops(tuple(batch_shape))
    return None if fwd is None else 3.0 * fwd


# ---------------------------------------------------------------------------
# Pillar 1: the on-device metrics vector (called INSIDE the jitted steps)
# ---------------------------------------------------------------------------

def update_with_metrics(optimizer: Optimizer, grads: Pytree,
                        opt_state: Pytree, params: Pytree,
                        loss: jax.Array
                        ) -> Tuple[Pytree, Pytree, Dict[str, jax.Array]]:
    """Apply ``optimizer.update`` AND compute the telemetry metrics vector
    in one pass — pure jax, safe inside ``shard_map`` bodies and GSPMD
    global-view steps alike, PROVIDED ``grads`` are fully reduced (every
    shard holding a leaf sees the identical full gradient; the same
    precondition the skip guard documents).

    The global grad norm is computed once here and handed to the guard via
    ``Optimizer.update_with_norm`` when the optimizer is guarded — the
    guard then skips its own reduction, so metrics + guard together cost
    ONE norm pass.  The update math is byte-identical to the metrics-off
    step (same inputs, same expressions), which is what keeps params
    bitwise-equal with telemetry on vs off.
    """
    gnorm = global_norm(grads)
    if optimizer.update_with_norm is not None:
        new_params, new_opt = optimizer.update_with_norm(
            grads, opt_state, params, gnorm)
    else:
        new_params, new_opt = optimizer.update(grads, opt_state, params)
    return new_params, new_opt, metrics_vector(loss, gnorm, new_params,
                                               params, new_opt)


def metrics_vector(loss: jax.Array, grad_norm: jax.Array,
                   new_params: Pytree, old_params: Pytree,
                   new_opt: Pytree) -> Dict[str, jax.Array]:
    """Assemble the ``METRIC_KEYS`` dict from an already-applied update —
    the single construction point shared by :func:`update_with_metrics`
    (replicated/GSPMD paths, whole-tree grad norm) and the
    sharded-update paths (``parallel.update_sharding``/zero1, grad norm
    from psum'd scattered-shard squares).  ``new_params``/``old_params``
    must be the FULL (gathered) trees so the param/update norms are
    local math, identical on every replica."""
    pnorm = global_norm(new_params)
    unorm = global_norm(jax.tree_util.tree_map(
        lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
        new_params, old_params))
    if isinstance(new_opt, GuardedState):
        # CUMULATIVE rejections, not a per-step delta: the host samples
        # the stream (metrics_every, and k>1 dispatches report only their
        # last step), and a sampled cumulative counter cannot lose fires
        # that happened between samples — the host differences it
        skipped = new_opt.skipped.astype(jnp.float32)
    else:
        skipped = jnp.zeros((), jnp.float32)
    return {
        "loss": loss.astype(jnp.float32),
        "grad_norm": grad_norm,
        "param_norm": pnorm,
        "update_ratio": unorm / jnp.maximum(pnorm, 1e-12),
        "skipped": skipped,
    }


# ---------------------------------------------------------------------------
# Pillar 2: flight recorder
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Bounded ring of the last N step records + events; dumps
    ``postmortem.json`` on abnormal events.  Recording is cheap (deque
    append of small dicts); dumping is leader-only."""

    def __init__(self, size: int, path: Optional[str]):
        self.size = int(size)
        self.path = path
        self.records: collections.deque = collections.deque(
            maxlen=max(1, self.size))
        self.enabled = bool(path) and self.size > 0
        self.dumps = 0
        self._pending_reason: Optional[str] = None

    def record(self, rec: Dict[str, Any]) -> None:
        if not self.enabled:
            return
        self.records.append(rec)
        if self._pending_reason is not None and rec.get("kind") == "step":
            # a dump armed by an event (rollback) waits for one post-event
            # step record so the postmortem's tail STRADDLES the event
            reason, self._pending_reason = self._pending_reason, None
            self.dump(reason)

    def event(self, kind: str, step: int, **detail) -> None:
        self.record({"kind": "event", "event": kind, "step": int(step),
                     "t_unix": round(time.time(), 3), **detail})

    def arm_dump(self, reason: str) -> None:
        """Dump after the NEXT step record lands (straddling dump); if no
        further record ever lands, close()/abnormal-exit dumps instead."""
        self._pending_reason = reason

    def dump(self, reason: str) -> Optional[str]:
        if not (self.enabled and is_leader()):
            return None
        self._pending_reason = None
        doc = {
            "reason": reason,
            "written_unix": round(time.time(), 3),
            "written_iso": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
            "n_records": len(self.records),
            "records": list(self.records),
        }
        # per-device memory AT DEATH: the number an OOM/hang postmortem
        # is usually missing (best-effort — the runtime may be gone, and
        # the dump must still land)
        try:
            mem = device_memory_summary(full=True)
        except Exception:
            mem = None
        if mem:
            doc["device_memory"] = mem
        _atomic_write_json(self.path, doc)
        self.dumps += 1
        log(f"[telemetry] postmortem ({reason}) -> {self.path}")
        return self.path


# ---------------------------------------------------------------------------
# Pillar 4: heartbeat
# ---------------------------------------------------------------------------

def device_memory_summary(full: bool = False) -> Optional[Dict[str, Any]]:
    """Per-device memory snapshot for the heartbeat (compact: live +
    peak bytes) and the flight-recorder postmortem (``full=True``:
    everything the backend reports) — so an OOM/hang postmortem shows
    per-device memory at death.  None where the backend reports nothing
    (XLA:CPU).  A failing read raises: only the postmortem dump, whose
    runtime may already be too broken to answer, catches it."""
    from ..utils.profiling import device_memory_stats

    stats = device_memory_stats()
    if not stats:
        return None
    if full:
        return stats
    return {dev: {k: v for k, v in s.items()
                  if k in ("bytes_in_use", "peak_bytes_in_use")}
            for dev, s in stats.items()}


def _atomic_write_json(path: str, doc: Dict[str, Any]) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, path)  # readers never observe a torn file


def heartbeat_filename(role: str, process_id: Optional[int] = None) -> str:
    """Per-role/per-process heartbeat file name:
    ``heartbeat-<role>-p<P>.json``.  Two programs sharing one
    ``--telemetry_dir`` (a trainer and a serving replica, or two
    serving replicas with distinct ``NNPT_PROCESS_ID``) used to
    last-writer-win over ONE ``heartbeat.json``, blinding the
    supervisor's staleness monitor to whichever wrote second; now each
    writer owns its file and generic readers (``read_heartbeat``,
    tools/metrics_summary.py, tools/obs_agg.py) fall back from the
    legacy shared name to the freshest qualified one — while the
    supervisor's hang monitor watches exactly its child's file.
    Delegates to the stdlib-only ``resilience.heartbeat_filename``
    (the naming's single source), with the process id resolved through
    ``trace.run_identity`` so the jax fallback applies."""
    if process_id is None:
        process_id = trace_lib.run_identity()["process_id"]
    from .resilience import heartbeat_filename as _hb_name

    return _hb_name(role, process_id)


def read_heartbeat(path: str) -> Optional[Dict[str, Any]]:
    """Load a heartbeat document.  Back-compat: when ``path`` is the
    legacy shared ``heartbeat.json`` (or a telemetry dir) and only
    role-qualified files exist, the FRESHEST of those is returned —
    callers keyed to the old layout keep working against per-role
    writers."""
    from .resilience import find_heartbeats

    candidates = [path] if os.path.isfile(path) else (
        find_heartbeats(path if os.path.isdir(path)
                        else os.path.dirname(path) or "."))
    best: Optional[Dict[str, Any]] = None
    best_m = None
    for p in candidates:
        try:
            m = os.stat(p).st_mtime
            with open(p) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if best_m is None or m > best_m:
            best, best_m = doc, m
    return best


# staleness helper lives in resilience (stdlib-only, so the generic
# supervisor never imports this jax-heavy module); canonical re-export
from .resilience import heartbeat_age_s  # noqa: E402


class Heartbeat:
    """Leader-written run-health snapshot, refreshed per dispatch
    (throttled) with NO device sync — everything in it is host state."""

    def __init__(self, path: Optional[str]):
        self.path = path
        self.enabled = bool(path) and is_leader()
        self._last_write = 0.0
        self._final = False
        self.last_step = 0  # newest step ever beaten (alive() reuses it)
        self.ema_steps_per_sec: Optional[float] = None

    def beat(self, step: Optional[int], last_metrics: Optional[Dict[str, Any]],
             force: bool = False, final: bool = False, **extra) -> None:
        """``step=None`` (the out-of-loop ``alive()`` beats) reuses the
        newest step already beaten — checkpoint/eval phases must never
        rewrite the step backwards.  Once the FINAL beat is written,
        later non-final beats only refresh the file's mtime (the
        staleness signal) and leave the final content intact."""
        if not self.enabled:
            return
        now = time.time()
        if not force and now - self._last_write < _HEARTBEAT_MIN_INTERVAL_S:
            return
        self._last_write = now
        if self._final and not final:
            try:
                os.utime(self.path)  # fresh, but the final record stands
            except OSError:
                pass
            return
        step = self.last_step if step is None else int(step)
        self.last_step = step  # plain assignment: a rollback rewinds it
        self._final = self._final or final
        doc = {
            "step": step,
            "t_unix": round(now, 3),
            "t_iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(now)),
            "pid": os.getpid(),
            "steps_per_sec_ema": self.ema_steps_per_sec,
            "last_metrics": last_metrics,
            **extra,
        }
        # per-device live/peak memory where the backend reports it —
        # writes are already throttled, so this stays off the hot path
        mem = device_memory_summary()
        if mem:
            doc["device_memory"] = mem
        if final:
            doc["final"] = True
        _atomic_write_json(self.path, doc)

    def observe_rate(self, inst_steps_per_sec: float) -> None:
        e = self.ema_steps_per_sec
        self.ema_steps_per_sec = (inst_steps_per_sec if e is None
                                  else 0.9 * e + 0.1 * inst_steps_per_sec)


# ---------------------------------------------------------------------------
# The orchestrating object the Trainer drives
# ---------------------------------------------------------------------------

# process-global active telemetry, so out-of-band failure paths (the
# injected ``crash`` fault's pre-_exit hook, the hang watchdog's timeout
# callback) can dump the flight recorder without threading a reference
_ACTIVE: Optional["Telemetry"] = None


def emergency_dump(reason: str) -> Optional[str]:
    """Best-effort postmortem dump from wherever the process is dying
    (utils.faults' injected crash, the watchdog's hang handler), and the
    span tracer's last write (train/trace.py ``flush``).

    Deliberately does NOT drain the lag queue: on the hang path the queued
    futures are exactly what is stuck, and a ``device_get`` here would
    block the watchdog's exit forever.  The dump carries what was already
    fetched — which under the lag-2 discipline is everything up to ~2
    dispatches before the stall."""
    # the tracer's pending spans first, telemetry on or off: these paths end
    # in ``os._exit``, where no exit hook hands them to the file
    trace_lib.flush()
    t = _ACTIVE
    if t is None or not t.enabled:
        return None
    try:
        t.recorder.event(
            "emergency", t._newest_step(),
            detail=reason, unfetched_dispatches=len(t._queue))
        return t.recorder.dump(reason)
    except Exception:
        return None


class Telemetry:
    """Per-run telemetry driver: owns the lag-2 fetch queue, the metrics
    JSONL, the heartbeat and the flight recorder.  All methods are no-ops
    when ``telemetry_dir`` is unset."""

    def __init__(self, cfg, model, feature_shape: Tuple[int, ...],
                 n_devices: int, device_kind: str, platform: str,
                 kind: str = "step",
                 flops_per_row: Optional[float] = None):
        """``kind`` stamps every metrics record (``"step"`` for the LM
        trainer, ``"rl"`` for the Anakin learner — tools/metrics_summary
        renders each kind's view); ``flops_per_row`` overrides the
        per-row MFU numerator for workloads whose step is not one
        fwd+bwd per row (the RL step's T actor forwards + ppo_epochs
        fwd/bwd live in ``rl.anakin.anakin_step_flops``)."""
        global _ACTIVE

        self.enabled = bool(cfg.telemetry_dir)
        self.dir = cfg.telemetry_dir
        self.kind = kind
        # the step loop's lap watch, set by the loop that owns one
        # (``Trainer.fit``): its counters ride every record
        self.laps: Optional[trace_lib.LapWatch] = None
        self._stalls_seen = 0
        # the heartbeat/rollup role tag: "train" for the LM trainer's
        # kind="step" stream, else the kind itself ("rl", "serve")
        self.role = "train" if kind == "step" else kind
        self._flops_override = flops_per_row
        self.metrics_every = max(0, int(cfg.metrics_every))
        self.rollup_every = max(0, int(getattr(cfg, "rollup_every", 0)))
        self.alerts_enabled = bool(getattr(cfg, "alerts", True))
        self._queue: List[tuple] = []  # (step, epoch, out, n_steps, rows, t)
        self._last_t: Optional[float] = None
        self.last_record: Optional[Dict[str, Any]] = None
        self.skipped_total = 0        # newest observed cumulative counter
        self._resync_skips = False    # set on rollback: counter rewound
        self.alerts_fired = 0
        self.rollups_written = 0
        # streaming SLO sketches (utils/sketches.py): cumulative per
        # incarnation, snapshotted into kind="rollup" records so
        # tools/obs_agg.py can merge fleet percentiles without raw
        # samples.  Detectors are the kind="alert" sources: loss /
        # grad-norm spikes (EMA z above) and throughput collapse (below)
        self._sketches = {k: QuantileSketch() for k in (
            "loss", "grad_norm", "step_time_ms", "samples_per_sec",
            "mfu")}
        self._gauges = {k: Gauge() for k in ("steps_per_sec", "mfu")}
        self._detectors = {
            "loss": EmaZScore("loss", direction="above"),
            "grad_norm": EmaZScore("grad_norm", direction="above"),
            "samples_per_sec": EmaZScore("samples_per_sec",
                                         direction="below"),
        }
        self._records_seen = 0
        self._last_rollup_step = 0
        if not self.enabled:
            self.recorder = FlightRecorder(0, None)
            self.heartbeat = Heartbeat(None)
            self._jsonl = None
            self.goodput_meter = None
            self._goodput_budget = None
            return
        if is_leader():
            os.makedirs(self.dir, exist_ok=True)
        self.metrics_path = os.path.join(self.dir, "metrics.jsonl")
        self.heartbeat_path = os.path.join(self.dir,
                                           heartbeat_filename(self.role))
        self.postmortem_path = os.path.join(self.dir, "postmortem.json")
        self.recorder = FlightRecorder(int(cfg.flight_recorder),
                                       self.postmortem_path)
        self.heartbeat = Heartbeat(self.heartbeat_path)
        self._jsonl = (open(self.metrics_path, "a")
                       if is_leader() else None)
        self._t0 = time.perf_counter()
        # per-ROW step FLOPs (every accounted model is linear in batch),
        # so per-dispatch FLOPs = rows * this; workload-specific callers
        # (the RL learner) hand in their own honest accounting instead
        self.flops_per_row = (self._flops_override
                              if self._flops_override is not None
                              else train_step_flops(model, (1,) + tuple(
                                  feature_shape)))
        peak = telemetry_peak_flops(device_kind, platform)
        self.peak_total = (None if peak is None
                           else peak * max(1, n_devices))
        # goodput accounting (utils/goodput.py): an online meter riding
        # the trace span-listener seam, snapshotted as kind="goodput"
        # records on the rollup cadence, with per-step anatomy joined
        # from the compile ledger's XLA cost analysis.  --goodput 0
        # disables (the bench's A/B arm); no tracer installed = the
        # meter just never hears a span and reports idle.
        self.peak_bw_total = (goodput_lib.peak_bytes_per_s(
            device_kind, platform) * max(1, n_devices))
        self.goodput_meter: Optional[goodput_lib.GoodputMeter] = None
        self._goodput_budget: Optional[ErrorBudget] = None
        self._goodput_frac_min = float(getattr(cfg, "goodput_target", 0.5))
        self._goodput_prev: Optional[Tuple[int, Dict[str, Any]]] = None
        if bool(getattr(cfg, "goodput", True)):
            self.goodput_meter = goodput_lib.GoodputMeter()
            trace_lib.add_listener(self.goodput_meter.on_span)
            if self.alerts_enabled:
                # attainment SLO: >= 90% of rollup windows should meet
                # the goodput-fraction floor; sustained misses burn the
                # budget at >= 2x and fire goodput_burn_rate
                self._goodput_budget = ErrorBudget(
                    "goodput", target=0.9,
                    window=50, min_events=5, cooldown=10)
        _ACTIVE = self

    # ---- hot path --------------------------------------------------------

    def on_dispatch(self, step: int, epoch: int, before: int, out,
                    n_steps: int, rows: int) -> None:
        """Called once per dispatch, right after submission.  ``out`` is
        the dispatch's device future: the on-device metrics dict when the
        step builder carries metrics, else the bare loss scalar.  Fetching
        happens at lag 2 (the monitor's discipline): the ``device_get``
        only ever waits on a dispatch whose successor is already
        submitted, so one dispatch stays in flight."""
        if not self.enabled:
            return
        now = time.perf_counter()
        if self._last_t is not None and now > self._last_t:
            self.heartbeat.observe_rate(n_steps / (now - self._last_t))
        crossed = (self.metrics_every > 0 and
                   step // self.metrics_every > before // self.metrics_every)
        if crossed:
            self._queue.append((step, epoch, out, n_steps, rows,
                                self._last_t, now))
            if len(self._queue) >= 2:
                # the popped entry's successor is already submitted, so
                # this device_get never drains the pipeline (the monitor's
                # lag-2 discipline)
                self._fetch(self._queue.pop(0))
        self._last_t = now
        self.heartbeat.beat(step, self.last_record,
                            skipped_total=self.skipped_total)

    def _fetch(self, entry) -> None:
        step, epoch, out, n_steps, rows, t_prev, t_disp = entry
        with trace_lib.span("fetch", what="metrics", step=int(step)):
            fetched = jax.device_get(out)
        if isinstance(fetched, dict):
            rec = {k: float(v) for k, v in fetched.items()}
        else:
            rec = {"loss": float(fetched)}
        rec.update(step=int(step), epoch=int(epoch),
                   kind=self.kind,
                   t=round(time.perf_counter() - self._t0, 6))
        if t_prev is not None and t_disp > t_prev:
            dt = (t_disp - t_prev) / max(1, n_steps)  # dispatch-to-dispatch
            rec["step_time_ms"] = round(dt * 1e3, 4)
            rec["samples_per_sec"] = round(rows / (t_disp - t_prev), 2)
            if self.flops_per_row is not None:
                rows_per_step = rows / max(1, n_steps)
                rec["mfu"] = (None if self.peak_total is None else
                              self.flops_per_row * rows_per_step / dt
                              / self.peak_total)
        if "skipped" in rec:
            # 'skipped' is the guard's cumulative rejection counter;
            # difference it against the last observed value so fires
            # between sampled records (metrics_every > 1, mid-dispatch
            # steps of a k>1 scan) surface too.  A rollback restores an
            # OLDER counter — resync the watermark without an event.
            cum = int(rec["skipped"])
            if self._resync_skips or cum < self.skipped_total:
                self._resync_skips = False
            elif cum > self.skipped_total:
                self.recorder.event("skip", step,
                                    fires=cum - self.skipped_total,
                                    grad_norm=rec.get("grad_norm"))
            self.skipped_total = cum
        laps = self.laps
        if laps is not None:
            # steps that ran long and their seconds over the median step
            # (train/trace.py "Laps and stalls"), each new one an event
            rec["stalls"] = laps.stalls
            rec["stall_s"] = round(laps.stall_s, 6)
            new = laps.stalls - self._stalls_seen
            self._stalls_seen = laps.stalls
            if new:
                for stall in list(laps.records)[-new:]:
                    self.recorder.event("stall", stall["n"], **stall)
        self.last_record = rec
        self.recorder.record(rec)
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()
        self._observe(rec, step)

    # ---- streaming sketches, rollups, alerts -----------------------------

    def _observe(self, rec: Dict[str, Any], step: int) -> None:
        """Feed the fetched record into the sketch layer + anomaly
        detectors and emit rollup/alert records on their cadences.
        Host-side arithmetic on already-fetched floats — nothing here
        touches a device."""
        self._records_seen += 1
        for key, sketch in self._sketches.items():
            v = rec.get(key)
            if isinstance(v, (int, float)):
                sketch.add(v)
        ema = self.heartbeat.ema_steps_per_sec
        if ema is not None:
            self._gauges["steps_per_sec"].set(ema)
        if isinstance(rec.get("mfu"), (int, float)):
            self._gauges["mfu"].set(rec["mfu"])
        if self.alerts_enabled:
            for key, det in self._detectors.items():
                v = rec.get(key)
                if isinstance(v, (int, float)):
                    alert = det.observe(v, step=step)
                    if alert:
                        self._emit_alert(alert, step)
        if (self.rollup_every > 0
                and (step // self.rollup_every
                     > self._last_rollup_step // self.rollup_every)):
            self._last_rollup_step = step
            self._write_rollup(step)

    def _emit_alert(self, alert: Dict[str, Any], step: int) -> None:
        """One ``kind="alert"`` record into the metrics stream + a
        flight-recorder event.  Observe-and-annotate only: nothing here
        feeds back into training decisions — the supervisor logs these
        next to its relaunch reasoning, and the rollback/abort policy
        stays ``ResilienceMonitor``'s."""
        self.alerts_fired += 1
        rec = {"kind": "alert", "role": self.role, "step": int(step),
               "t": round(time.perf_counter() - self._t0, 6),
               "t_unix": round(time.time(), 3), **alert}
        self.recorder.event("alert", step, alert=alert.get("alert"),
                            value=alert.get("value"), z=alert.get("z"))
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()
        log(f"[telemetry] ALERT {alert.get('alert')} at step {step} "
            f"(value {alert.get('value')})")

    def _write_rollup(self, step: int) -> None:
        """Snapshot the SERIALIZED sketch state (not point stats) as a
        ``kind="rollup"`` record, stamped with the (process, run,
        incarnation) identity so ``tools/obs_agg.py`` can pick the
        newest snapshot per writer and merge fleet percentiles.
        Sketches are cumulative over this incarnation — the aggregator
        takes the latest record per identity, never a sum of
        records."""
        if self._jsonl is None:
            return
        ident = trace_lib.run_identity()
        rec = {
            "kind": "rollup", "role": self.role, "step": int(step),
            "t": round(time.perf_counter() - self._t0, 6),
            "t_unix": round(time.time(), 3),
            "p": ident["process_id"], "run": ident["run_id"],
            "inc": ident["incarnation"],
            "sketches": {k: s.to_dict()
                         for k, s in self._sketches.items() if s.n},
            "counters": {"metrics_records": self._records_seen,
                         "skipped_total": int(self.skipped_total),
                         "alerts": self.alerts_fired},
            "gauges": {k: g.to_dict() for k, g in self._gauges.items()
                       if g.last is not None},
        }
        self.rollups_written += 1
        self._jsonl.write(json.dumps(rec) + "\n")
        self._jsonl.flush()
        self._write_goodput(step, ident)

    def _step_anatomy(self) -> Optional[Dict[str, Any]]:
        """Join the compile ledger's XLA cost analysis (flops / bytes
        accessed, recorded at compile time) with the measured step time
        and the meter's host-span seconds into a roofline position +
        MFU-gap breakdown.  None when any leg of the join is missing
        (off-TPU: no chip peak; no ledger, no cost analysis from this
        backend, no measured step yet)."""
        from ..utils import compile_ledger

        led = compile_ledger.active()
        last = self.last_record or {}
        step_ms = last.get("step_time_ms")
        if (self.peak_total is None or led is None
                or not isinstance(step_ms, (int, float))):
            return None
        flops = by = None
        for e in reversed(led.events):
            if e.get("flops"):
                flops, by = e.get("flops"), e.get("bytes_accessed")
                break
        if not flops:
            return None
        # host cost per step: the meter's dispatch/load/fetch span
        # seconds differenced over the steps since the last rollup
        host_s = 0.0
        if self.goodput_meter is not None and self._goodput_prev:
            prev_step, prev_host = self._goodput_prev
            cur = self.goodput_meter.snapshot()["host_seconds"]
            dsteps = max(1, self._last_rollup_step - prev_step)
            host_s = max(0.0, sum(cur.values())
                         - sum(prev_host.values())) / dsteps
        return goodput_lib.step_anatomy(
            flops=flops, bytes_accessed=by, step_s=float(step_ms) / 1e3,
            host_s=host_s, peak_flops=self.peak_total,
            peak_bw=self.peak_bw_total)

    def _write_goodput(self, step: int, ident: Dict[str, Any]) -> None:
        """One ``kind="goodput"`` record next to each rollup: cumulative
        per-category seconds (the aggregator takes the newest per
        identity, like the sketches), plus the step anatomy.  The burn
        alert reuses the PR 14 ErrorBudget: each rollup whose goodput
        fraction is under ``--goodput_target`` consumes error budget."""
        if self.goodput_meter is None or self._jsonl is None:
            return
        snap = self.goodput_meter.snapshot()
        anatomy = self._step_anatomy()
        rec = goodput_lib.goodput_record(snap, role=self.role,
                                         step=step, ident=ident,
                                         anatomy=anatomy)
        self._jsonl.write(json.dumps(rec) + "\n")
        self._jsonl.flush()
        self._goodput_prev = (int(step), snap["host_seconds"])
        # no spans heard = tracing is off: the meter sees only idle and
        # a burn alert would be noise, not signal
        if self._goodput_budget is not None and snap["spans"] > 0:
            frac = snap["goodput_fraction"] or 0.0
            alert = self._goodput_budget.observe(
                frac < self._goodput_frac_min)
            if alert:
                self._emit_alert(
                    {**alert, "goodput_fraction": frac,
                     "goodput_target": self._goodput_frac_min}, step)

    # ---- events ----------------------------------------------------------

    def on_rollback(self, step: int, rollbacks: int) -> None:
        """Flush in-flight records (they belong to the abandoned timeline
        but really executed), log the event, dump now AND arm a second
        dump after the next step record so the postmortem's tail straddles
        the rollback."""
        if not self.enabled:
            return
        self.flush(final=False)
        self.recorder.event("rollback", step, rollbacks=rollbacks)
        self.recorder.dump("rollback")
        self.recorder.arm_dump("rollback")
        self._last_t = None  # the restore stall is not a step time
        # the restored GuardedState carries an older cumulative skip
        # counter; resync the watermark at the next record, no event
        self._resync_skips = True
        # alive() beats between the rollback and the next dispatch must
        # report the restored step, not the abandoned timeline's
        self.heartbeat.last_step = int(step)

    def on_abnormal_exit(self, exc: BaseException) -> None:
        from .resilience import AnomalyAbort

        if not self.enabled:
            return
        reason = ("anomaly_abort" if isinstance(exc, AnomalyAbort)
                  else f"crash: {type(exc).__name__}: {exc}")
        self.recorder.event("abort" if isinstance(exc, AnomalyAbort)
                            else "crash", self._newest_step(), detail=str(exc))
        try:
            # device-side crashes poison the queued futures: draining
            # them re-raises.  This runs inside fit's finally, where a
            # second raise would MASK the original exception and skip the
            # dump — swallow it; the dump below carries what was fetched.
            self.flush(final=False)
        except Exception:
            pass
        self.recorder.dump(reason)

    def on_sdc(self, record: Dict[str, Any]) -> None:
        """A silent-data-corruption incident (train/trainer.py's
        fingerprint monitor): write the full record into the telemetry
        stream (``kind: "sdc"`` in metrics.jsonl — tools/sdc_report.py
        renders these), log a flight-recorder event, and dump a
        postmortem — an SDC is exactly the event class the black box
        exists for, whether or not the run survives it."""
        if not self.enabled:
            return
        rec = {"kind": "sdc",
               "t": round(time.perf_counter() - self._t0, 6), **record}
        self.recorder.event(
            "sdc", int(record.get("step", -1)),
            verdict=record.get("verdict"), action=record.get("action"),
            leaves=record.get("leaves"), devices=record.get("devices"))
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()
        self.recorder.dump("sdc")
        # straddle: re-dump after the next step record so the postmortem
        # tail shows whether the run kept training past the incident
        self.recorder.arm_dump("sdc")

    def on_topology(self, step: int, change: Dict[str, Any]) -> None:
        """An elastic topology change (train/trainer.py's preflight): the
        run resumed on a different world than the one that saved its
        checkpoint.  Not a failure — no postmortem — but it IS the moment
        the effective batch/accumulation semantics may have changed, so
        the record goes into the metrics stream (``kind: "topology"``,
        rendered by tools/metrics_summary.py) and the flight-recorder
        ring (a later postmortem should show the run was degraded)."""
        if not self.enabled:
            return
        rec = {"kind": "topology", "step": int(step),
               "t": round(time.perf_counter() - self._t0, 6), **change}
        self.recorder.event(
            "topology", int(step),
            from_devices=(change.get("from_world") or {}).get("n_devices"),
            to_devices=(change.get("to_world") or {}).get("n_devices"),
            policy=change.get("policy"),
            batch_size=change.get("batch_size"),
            accum_steps=change.get("accum_steps"))
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()

    def on_preempted(self, signum: int, step: int) -> None:
        if not self.enabled:
            return
        self.recorder.event("sigterm", step, signum=signum)
        self.recorder.dump(f"sigterm (signal {signum})")

    def _newest_step(self) -> int:
        if self._queue:
            return int(self._queue[-1][0])
        return int((self.last_record or {}).get("step", -1))

    def alive(self) -> None:
        """Refresh the heartbeat OUTSIDE the dispatch loop — long
        host-side phases (checkpoint writes, eval passes) emit no
        dispatches, and without these beats the supervisor's external
        stale-heartbeat monitor would kill a healthy run in its tail.
        Throttled like every beat; ``step=None`` keeps the newest step
        already beaten (never rewrites it backwards)."""
        if self.enabled:
            self.heartbeat.beat(None, self.last_record,
                                skipped_total=self.skipped_total)

    # ---- lifecycle -------------------------------------------------------

    def flush(self, final: bool = True, step: Optional[int] = None) -> None:
        """Drain the lag queue (safe: by the time flush runs, the futures
        are either complete or about to be blocked on anyway).  ``step``:
        the trainer's global step for the final heartbeat — needed in the
        heartbeat-only mode (``metrics_every=0``) where no record ever
        carries one."""
        if not self.enabled:
            return
        while self._queue:
            self._fetch(self._queue.pop(0))
        if final:
            if step is None:
                step = int((self.last_record or {}).get("step", 0))
            if self.rollup_every > 0 and self._records_seen:
                # terminal snapshot regardless of cadence: the
                # aggregator must see the run's complete sketches
                self._write_rollup(step)
            self.heartbeat.beat(step, self.last_record, force=True,
                                final=True,
                                skipped_total=self.skipped_total)

    def close(self) -> None:
        global _ACTIVE

        if _ACTIVE is self:
            _ACTIVE = None
        if self.goodput_meter is not None:
            trace_lib.remove_listener(self.goodput_meter.on_span)
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None
