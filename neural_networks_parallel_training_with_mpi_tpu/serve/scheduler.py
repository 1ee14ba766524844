"""Continuous-batching scheduler: the service loop over the paged server.

``PagedDecodeServer`` is mechanism (slots, blocks, one compiled step);
this module is policy — the part a production decode service needs on
top of the library loop the repo had before this subsystem:

* **Bounded wait queue**: ``submit()`` enqueues (FIFO) up to
  ``queue_depth``; beyond that requests are REJECTED (counted, and the
  caller told), because an unbounded queue just converts overload into
  unbounded latency.
* **Per-tick admit/retire**: every :meth:`Scheduler.tick` admits from
  the queue head while a slot + the prompt's blocks + the token budget
  allow, runs at most one chunked prefill chunk, advances all decoding
  streams one batched step, and then retires the streams whose rows have
  reached the host — requests join and leave mid-flight, never stalling
  the batch.  A stream that finishes frees its slot and blocks in the tick
  of its last step and is returned by the NEXT tick, after that tick's
  programs are queued (``PagedDecodeServer.land``: the host waits for a
  finished row one program behind, never with the device's queue empty);
  by the same tick when nothing else runs.  ``t_done`` is the landing.
  Admission is head-of-line (no skip-ahead): simple, and what makes the
  no-starvation property provable — the queue head cannot be bypassed
  forever by luckier requests.
* **Chunked prefill interleaved with decode**: a long prompt is written
  ``prefill_chunk`` positions per tick, so admission of a 10k-token
  prompt costs in-flight streams bounded added latency per tick instead
  of one giant stall (the continuous-batching contract).
* **SLO-aware eviction**: every request carries a deadline
  (``t_submit + slo_ms``; no SLO = +inf).  When the pool cannot supply a
  stream's next block, the LATEST-deadline stream is evicted — its
  blocks freed, the request requeued at the FRONT of the queue (original
  arrival time and deadline kept).  The earliest-deadline stream is
  never evicted while others exist, so the oldest obligation always
  makes progress: under any closed arrival sequence the system drains
  (the fuzz test's no-starvation/no-leak invariant).
* **Serving telemetry**: ``kind="serve"`` tick records (queue/pool
  state plus attended/padded/kernel key counters — the decode work the
  fused paged-attention kernel skips, measurable per tick) and
  ``kind="serve_req"`` per-request completion records (TTFT/ITL) go into
  the same ``metrics.jsonl`` stream PR 2's trainer writes, and the
  heartbeat is the same atomic snapshot under the role-qualified name
  ``heartbeat-serve-p<P>.json`` (two programs sharing one dir no longer
  collide) — ``train.resilience.supervise(heartbeat_path=...)`` and
  ``tools/metrics_summary.py`` work on a serving process unchanged
  through the back-compat fallback read.
* **Fleet plane** (DESIGN.md §7): ``rollup_every`` snapshots the
  streaming quantile sketches (TTFT/ITL/total, queue depth, block
  utilization, tokens/s — ``utils/sketches.py``) as ``kind="rollup"``
  records ``tools/obs_agg.py`` merges across replicas into fleet
  percentiles; deadline misses burn an SLO error budget whose
  burn-rate alerts land as ``kind="alert"`` records (observe-and-
  annotate); with a tracer installed, each request id threads an
  admit -> prefill -> decode -> retire Perfetto FLOW across the tick
  spans (``train/trace.py``), one point per phase change — the
  primitive a cross-replica block handoff will ride.
"""

from __future__ import annotations

import collections
import json
import math
import os
import sys
import time
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional

from ..models.transformer import Transformer
from ..train import telemetry as telemetry_lib
from ..train import trace as trace_lib
from ..train.telemetry import Heartbeat
from ..utils import goodput as goodput_lib
from ..utils.logging import log
from ..utils.sketches import ErrorBudget, Gauge, QuantileSketch
from .paged_kv import PagedDecodeServer

Pytree = Any


@dataclass
class ServeConfig:
    """Geometry + policy knobs of the serving runtime."""
    slots: int = 8                 # concurrent streams in the batched step
    num_blocks: int = 128          # KV pool blocks (block 0 is the sink)
    block_size: int = 16           # cache positions per block
    max_len: Optional[int] = None  # per-stream cap (default model max)
    queue_depth: int = 64          # bounded wait queue; beyond = rejected
    prefill_chunk: int = 32        # prompt positions prefilled per tick
    token_budget: int = 0          # max committed (prompt+max_new) tokens
    #                                in flight; 0 disables the gate
    default_slo_ms: Optional[float] = None  # deadline for SLO-less submits
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    kv_quant: bool = False
    attn_impl: str = "auto"        # 'auto': 'fused' where a TPU runs the
    #                                per-head K/V row at a lane-dense
    #                                kv_heads * head_dim, else 'gathered'
    #                                (serve/paged_kv.resolve_attn_impl);
    #                                'gathered' (parity reference) and
    #                                'fused' (Pallas paged-attention
    #                                kernel: reads the pool in place,
    #                                stops at each stream's true length)
    #                                force one
    prefix_cache: bool = False     # share identical prompt-prefix blocks
    #                                across streams (refcounts + copy-on-
    #                                write; serve/paged_kv.py): a cached
    #                                prefix admits without re-prefilling,
    #                                so TTFT collapses to the suffix
    telemetry_dir: Optional[str] = None
    metrics_every: int = 25        # ticks between kind="serve" records
    # fleet-plane rollups (utils/sketches.py): every N ticks emit a
    # kind="rollup" record carrying SERIALIZED quantile-sketch state
    # (TTFT/ITL/total, queue depth, block utilization, tokens/s) +
    # cumulative counters, stamped with the (process, run, incarnation)
    # identity so tools/obs_agg.py can merge fleet percentiles without
    # raw samples.  0 = off (a final rollup still writes on close when
    # any cadence was configured)
    rollup_every: int = 0
    # SLO burn-rate alerting over deadline misses (kind="alert"
    # records; observe-and-annotate — the scheduler never acts on
    # them).  Only requests WITH a deadline count toward the budget.
    alerts: bool = True
    slo_target: float = 0.99       # SLO: fraction of deadlines met
    slo_burn_threshold: float = 2.0  # alert at >= this x budget burn
    # goodput accounting (utils/goodput.py): meter the tick-phase spans
    # plus the inter-tick queue_wait/sched_bubble gap spans into
    # kind="goodput" records on the rollup cadence (file stream only —
    # needs telemetry_dir); the fleet dashboard shows the serve role's
    # goodput fraction next to train's
    goodput: bool = True
    goodput_target: float = 0.5    # fraction floor for the burn alert
    # span tracing + compile ledger (train/trace.py): per-tick
    # admit/prefill/decode/retire spans and the serve programs' compile
    # events under this dir; None = ride any tracer the enclosing
    # process already installed (or off)
    trace_dir: Optional[str] = None
    completed_history: int = 1024  # completed Requests kept for stats();
    #                                older ones (and their unconsumed
    #                                results) are pruned so a long-lived
    #                                serving process cannot grow without
    #                                bound
    replica: Optional[int] = None  # fleet replica index (serve/fleet.py):
    #                                stamps rollup records and qualifies
    #                                per-request flow-trace ids so two
    #                                replicas of one process identity can
    #                                never collide on a merged timeline
    #                                (the scheduler-local rid restarts at
    #                                0 in every replica)
    # disaggregated serving role (DESIGN.md §11): 'unified' (default —
    # this scheduler prefills AND decodes, every pre-existing path),
    # 'prefill' (chunked prefill only: a completed prefill EXPORTS the
    # stream — block contents + first sampled token — for handoff to a
    # decode replica instead of decoding it here; take_handoffs()
    # drains the exports), or 'decode' (accepts handoffs via inject()).
    # Either role still serves plain submits end-to-end when asked
    # (``unified=True`` on submit) — the degraded fallback an empty
    # peer pool routes through.  Telemetry roles become
    # 'serve-prefill'/'serve-decode' so a hot prefill pool is visible
    # per-role in tools/obs_agg.py, never averaged into decode numbers.
    role: str = "unified"


@dataclass
class Request:
    """One request's lifecycle; the scheduler keeps it (with timings)
    after completion so load generators can read TTFT/ITL off it."""
    rid: int
    prompt: List[int]
    max_new: int
    t_submit: float
    deadline: float                       # t_submit + slo_ms, or +inf
    slo_ms: Optional[float] = None
    t_first: Optional[float] = None       # first output token sampled
    t_done: Optional[float] = None
    evictions: int = 0
    unified: bool = False                 # serve end-to-end regardless of
    #                                       the scheduler's role (degraded
    #                                       single-pool fallback)

    @property
    def ttft_ms(self) -> Optional[float]:
        if self.t_first is None:
            return None
        return (self.t_first - self.t_submit) * 1e3

    @property
    def itl_ms(self) -> Optional[float]:
        """Mean inter-token latency over the decode phase."""
        if self.t_done is None or self.t_first is None:
            return None
        return ((self.t_done - self.t_first)
                / max(1, self.max_new - 1)) * 1e3

    @property
    def deadline_missed(self) -> Optional[bool]:
        if self.t_done is None:
            return None
        return bool(math.isfinite(self.deadline)
                    and self.t_done > self.deadline)


class _ServeTelemetry:
    """Serving metrics through the PR 2 channel: kind="serve" /
    "serve_req" records into metrics.jsonl + the role-qualified
    heartbeat, plus the fleet plane's kind="rollup" sketch snapshots
    and kind="alert" SLO burn-rate records (utils/sketches.py).

    The in-memory sketch/counter/gauge state is ALWAYS maintained (host
    arithmetic, bounded O(1/eps) memory): the fleet router's placement
    signal is :meth:`rollup_record` — the same serialized-sketch record
    the file stream carries — and a replica must be routable whether or
    not an operator pointed a ``telemetry_dir`` at it.  File/heartbeat
    IO stays gated on ``telemetry_dir``."""

    # the quantile-sketched serving series: latency percentiles are THE
    # serving SLO numbers and only compose fleet-wide through sketches
    SKETCH_KEYS = ("ttft_ms", "itl_ms", "total_ms", "queue_depth",
                   "block_utilization", "tokens_per_sec")

    def __init__(self, cfg: "ServeConfig"):
        dirpath = cfg.telemetry_dir
        self.enabled = bool(dirpath)
        self.metrics_every = max(1, int(cfg.metrics_every))
        self.rollup_every = max(0, int(cfg.rollup_every))
        self.replica = cfg.replica
        # role-qualified telemetry identity: unified keeps the historic
        # "serve" role; disaggregated roles split into serve-prefill /
        # serve-decode so per-role fleet rollups fall out of the
        # aggregator's existing role grouping
        role = getattr(cfg, "role", "unified") or "unified"
        self.role = "serve" if role == "unified" else f"serve-{role}"
        self._jsonl = None
        self.heartbeat = Heartbeat(None)
        self.alerts_fired = 0
        self.rollups_written = 0
        self._t0 = time.perf_counter()
        self._last_tokens = 0
        self._last_t = self._t0
        self._sketches = {k: QuantileSketch() for k in self.SKETCH_KEYS}
        self._gauges = {k: Gauge() for k in ("tokens_per_sec",
                                             "queue_depth",
                                             "block_utilization")}
        self._counters: Dict[str, int] = {}
        self._budget = (ErrorBudget("slo", target=cfg.slo_target,
                                    burn_threshold=cfg.slo_burn_threshold)
                        if cfg.alerts else None)
        # goodput accounting: the span-listener meter hears the tick
        # phases + the inter-tick queue_wait/sched_bubble gap spans and
        # is snapshotted as kind="goodput" next to each rollup.  File
        # stream only, so it stays gated on telemetry_dir like the rest
        # of the IO (the router's placement signal doesn't need it).
        self.goodput_meter: Optional[goodput_lib.GoodputMeter] = None
        self._goodput_budget: Optional[ErrorBudget] = None
        self._goodput_frac_min = float(getattr(cfg, "goodput_target", 0.5))
        if self.enabled and bool(getattr(cfg, "goodput", True)):
            self.goodput_meter = goodput_lib.GoodputMeter()
            trace_lib.add_listener(self.goodput_meter.on_span)
            if cfg.alerts:
                self._goodput_budget = ErrorBudget(
                    "goodput", target=0.9,
                    window=50, min_events=5, cooldown=10)
        if not self.enabled:
            return
        os.makedirs(dirpath, exist_ok=True)
        self.metrics_path = os.path.join(dirpath, "metrics.jsonl")
        self._jsonl = open(self.metrics_path, "a")
        self.heartbeat = Heartbeat(os.path.join(
            dirpath, telemetry_lib.heartbeat_filename(self.role)))

    def _write(self, rec: Dict[str, Any]) -> None:
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()

    def on_tick(self, tick: int, snap: Dict[str, Any]) -> None:
        # per-tick sketch feed (host floats, no device traffic): queue
        # and pool state distributions, not just their sampled points
        self._sketches["queue_depth"].add(snap["queue_depth"])
        self._sketches["block_utilization"].add(
            snap["block_utilization"])
        if tick % self.metrics_every:
            # the heartbeat still refreshes (throttled internally): the
            # supervisor's staleness monitor watches mtime, not records
            self.heartbeat.beat(tick, None)
            self._maybe_rollup(tick)
            return
        now = time.perf_counter()
        rec = {"kind": "serve", "step": int(tick),
               "t": round(now - self._t0, 6), **snap}
        dt = now - self._last_t
        if dt > 0:
            tps = round((snap["tokens_out"] - self._last_tokens) / dt, 2)
            rec["tokens_per_sec"] = tps
            self._sketches["tokens_per_sec"].add(tps)
            self._gauges["tokens_per_sec"].set(tps)
        self._gauges["queue_depth"].set(snap["queue_depth"])
        self._gauges["block_utilization"].set(snap["block_utilization"])
        for key in ("admitted", "rejected", "evicted", "completed",
                    "tokens_out", "handed_off", "injected"):
            if key in snap:
                self._counters[key] = int(snap[key])
        self._last_tokens = snap["tokens_out"]
        self._last_t = now
        self._write(rec)
        self.heartbeat.beat(tick, rec)
        self._maybe_rollup(tick)

    def on_request_done(self, req: Request, n_generated: int) -> None:
        total_ms = round((req.t_done - req.t_submit) * 1e3, 3)
        ttft, itl = round(req.ttft_ms, 3), round(req.itl_ms, 3)
        self._write({
            "kind": "serve_req", "rid": req.rid,
            "t": round(time.perf_counter() - self._t0, 6),
            "prompt_tokens": len(req.prompt),
            "new_tokens": int(n_generated),
            "ttft_ms": ttft,
            "itl_ms": itl,
            "total_ms": total_ms,
            "evictions": req.evictions,
            "deadline_missed": req.deadline_missed,
        })
        self._sketches["ttft_ms"].add(ttft)
        self._sketches["itl_ms"].add(itl)
        self._sketches["total_ms"].add(total_ms)
        self._counters["requests"] = self._counters.get("requests", 0) + 1
        if math.isfinite(req.deadline):
            # only SLO-carrying requests burn (or bank) the budget
            missed = bool(req.deadline_missed)
            self._counters["deadline_total"] = (
                self._counters.get("deadline_total", 0) + 1)
            if missed:
                self._counters["deadline_missed"] = (
                    self._counters.get("deadline_missed", 0) + 1)
            if self._budget is not None:
                alert = self._budget.observe(missed)
                if alert and self.enabled:
                    self._emit_alert(alert, rid=req.rid)

    def on_handoff(self, ttft_ms: float) -> None:
        """A prefill-role handoff: the prefill side OWNS the TTFT number
        (the first token was sampled here), so it lands in this
        replica's sketch — the decode side records only decode-phase
        ITL for injected streams."""
        self._sketches["ttft_ms"].add(round(ttft_ms, 3))

    def _emit_alert(self, alert: Dict[str, Any], **extra) -> None:
        self.alerts_fired += 1
        rec = {"kind": "alert", "role": self.role,
               "t": round(time.perf_counter() - self._t0, 6),
               "t_unix": round(time.time(), 3), **alert, **extra}
        self._write(rec)
        log(f"[serve] ALERT {alert.get('alert')} "
            f"(burn rate {alert.get('burn_rate')}x of the "
            f"{alert.get('target')} SLO budget)")

    def rollup_record(self, tick: int,
                      snap: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
        """The ``kind="rollup"`` record for this scheduler RIGHT NOW —
        the identical serialized-sketch document the telemetry file
        stream carries (tools/obs_agg.py merges it), which is also THE
        fleet router's placement signal (``Scheduler.load_report``): one
        telemetry path, not two.  With ``snap`` (a live
        :meth:`Scheduler._snapshot`), the occupancy gauges refresh first
        and the record carries a ``now`` sub-dict of instantaneous
        queue/pool state — rollup cadence must not stale an admission
        decision."""
        if snap is not None:
            self._gauges["queue_depth"].set(snap["queue_depth"])
            self._gauges["block_utilization"].set(
                snap["block_utilization"])
        # identity cached per writer: run_identity() FABRICATES a fresh
        # run id when NNPT_RUN_ID is unset, and a per-record call would
        # split one scheduler's cumulative rollups across several
        # "writers" in the aggregator — which then SUMS the same
        # cumulative counters once per fabricated id
        if not hasattr(self, "_ident"):
            self._ident = trace_lib.run_identity()
        ident = self._ident
        counters = dict(self._counters)
        counters["alerts"] = self.alerts_fired
        if self._budget is not None:
            counters["slo_events"] = self._budget.events
            counters["slo_misses"] = self._budget.misses
        rec = {
            "kind": "rollup", "role": self.role, "step": int(tick),
            "t": round(time.perf_counter() - self._t0, 6),
            "t_unix": round(time.time(), 3),
            "p": ident["process_id"], "run": ident["run_id"],
            "inc": ident["incarnation"],
            "sketches": {k: s.to_dict()
                         for k, s in self._sketches.items() if s.n},
            "counters": counters,
            "gauges": {k: g.to_dict() for k, g in self._gauges.items()
                       if g.last is not None},
        }
        if self.replica is not None:
            rec["replica"] = int(self.replica)
        if snap is not None:
            rec["now"] = {k: snap[k] for k in
                          ("queue_depth", "live", "prefilling",
                           "free_blocks", "block_utilization",
                           "committed_tokens") if k in snap}
        return rec

    def _maybe_rollup(self, tick: int, final: bool = False) -> None:
        if self.rollup_every <= 0:
            return
        if not final and tick % self.rollup_every:
            return
        rec = self.rollup_record(tick)
        self.rollups_written += 1
        self._write(rec)
        self._write_goodput(tick)

    def _write_goodput(self, tick: int) -> None:
        """One ``kind="goodput"`` record next to each serve rollup
        (cumulative per incarnation — the aggregator takes the newest
        per identity); sustained goodput-fraction misses burn the same
        ErrorBudget contract as the train role."""
        if self.goodput_meter is None:
            return
        snap = self.goodput_meter.snapshot()
        rec = goodput_lib.goodput_record(
            snap, role=self.role, step=tick,
            ident=getattr(self, "_ident", None) or trace_lib.run_identity())
        if self.replica is not None:
            rec["replica"] = int(self.replica)
        self._write(rec)
        if self._goodput_budget is not None and snap["spans"] > 0:
            frac = snap["goodput_fraction"] or 0.0
            alert = self._goodput_budget.observe(
                frac < self._goodput_frac_min)
            if alert:
                self._emit_alert({**alert, "goodput_fraction": frac,
                                  "goodput_target":
                                      self._goodput_frac_min})

    def close(self, tick: int, snap: Optional[Dict[str, Any]] = None
              ) -> None:
        if not self.enabled:
            return
        final_rec = None
        if snap is not None:
            # the drain can end off the metrics_every cadence; the final
            # record must carry the terminal counters regardless
            final_rec = {"kind": "serve", "step": int(tick),
                         "t": round(time.perf_counter() - self._t0, 6),
                         "final": True, **snap}
            self._write(final_rec)
            for key in ("admitted", "rejected", "evicted", "completed",
                        "tokens_out", "handed_off", "injected"):
                if key in snap:
                    self._counters[key] = int(snap[key])
        self._maybe_rollup(tick, final=True)
        self.heartbeat.beat(tick, final_rec, force=True, final=True)
        if self.goodput_meter is not None:
            trace_lib.remove_listener(self.goodput_meter.on_span)
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None


class Scheduler:
    """The continuous-batching service loop (see module docstring).

    ``now_fn`` injects the clock: tests and the fuzz harness drive a
    virtual clock so deadline policy is deterministic; production uses
    ``time.monotonic``."""

    def __init__(self, model: Transformer, params: Pytree,
                 cfg: Optional[ServeConfig] = None, now_fn=time.monotonic):
        # fresh default per instance: ServeConfig is a plain mutable
        # dataclass, and a shared default instance would leak one
        # caller's tweaks into every later default-constructed Scheduler
        self.cfg = cfg = ServeConfig() if cfg is None else cfg
        if cfg.role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role must be 'unified', 'prefill' or "
                             f"'decode', got {cfg.role!r}")
        self.now = now_fn
        # install the span tracer + compile ledger BEFORE the server
        # builds its programs, so their compiles land in the ledger; an
        # already-active tracer (an enclosing run) is never displaced
        self._tracer = None
        if cfg.trace_dir and trace_lib.active() is None:
            self._tracer = trace_lib.start_run(cfg.trace_dir)
        self.server = PagedDecodeServer(
            model, params, slots=cfg.slots, num_blocks=cfg.num_blocks,
            block_size=cfg.block_size, max_len=cfg.max_len,
            temperature=cfg.temperature, top_k=cfg.top_k,
            top_p=cfg.top_p, seed=cfg.seed, kv_quant=cfg.kv_quant,
            attn_impl=cfg.attn_impl, prefix_cache=cfg.prefix_cache,
            prefill_chunk=cfg.prefill_chunk)
        self.queue: Deque[Request] = collections.deque()
        self.reqs: Dict[int, Request] = {}      # every request ever seen
        self._srv_rid: Dict[int, int] = {}      # scheduler rid -> server
        self._sched_rid: Dict[int, int] = {}    # server rid -> scheduler
        self._prefilling: Deque[int] = collections.deque()
        self._results: Dict[int, List[int]] = {}
        self._done_order: Deque[int] = collections.deque()
        self._next_rid = 0
        self.tick_no = 0
        self.admitted = 0
        self.rejected = 0
        self.evicted = 0
        self.completed = 0
        self.tokens_out = 0
        # disaggregated-handoff state: exports a prefill-role tick
        # produced, waiting for the worker loop to take them; counters
        # for both directions of the handoff
        self._handoffs: List[Dict[str, Any]] = []
        self.handed_off = 0
        self.injected = 0
        # decode-step key accounting (host arithmetic, zero device
        # traffic): attended = what the math needs, padded = what the
        # gathered path reduces over, kernel = whole blocks the fused
        # kernel walks — attended/padded is the measured skipped work
        self.attended_keys = 0
        self.padded_keys = 0
        self.kernel_keys = 0
        self.walked_keys = 0
        # expert-load counters of a model that routes without drops
        # (paged_kv.EXPERT_COUNTERS; {} otherwise): cumulative, as of the
        # step behind which the newest landed row was taken, stamped on the
        # ``retire`` span of every tick that landed one for whoever listens
        # to spans
        self.expert_counters: Dict[str, int] = dict(
            self.server.expert_counters)
        # the same for the attention counters of a model with window and
        # full layers (paged_kv.ATTENTION_COUNTERS; {} otherwise)
        self.attention_counters: Dict[str, int] = dict(
            self.server.attention_counters)
        # and for the counters of a model with recurrent state
        # (paged_kv.SSM_COUNTERS; {} otherwise)
        self.ssm_counters: Dict[str, int] = dict(self.server.ssm_counters)
        self.telemetry = _ServeTelemetry(cfg)
        # per-request flow-trace ids must stay unique across the fleet's
        # merged timeline: prefix the scheduler-local rid with this
        # process's identity (free when no tracer is installed) AND the
        # replica index when one is set — N replica processes launched
        # from one operator shell can share a process id, and their
        # scheduler-local rids all count from 0
        rep = "" if cfg.replica is None else f"R{int(cfg.replica)}-"
        self._flow_prefix = (
            f"p{trace_lib.run_identity()['process_id']}-{rep}r")
        # inter-tick gap attribution (utils/goodput.py): at the end of
        # each tick remember the wall-clock and WHY the next gap would
        # not be idle — requests queued with no live stream (queue_wait:
        # admission capacity, not the model, is the bottleneck) vs
        # streams mid-decode (sched_bubble: the loop owns the time).
        # The next tick retro-emits that gap as a span, so the goodput
        # category set prices scheduler dead time instead of dropping it.
        self._gap_wall: Optional[float] = None
        self._gap_state: Optional[str] = None
        # the gap's mirror in a jax.profiler capture (train/trace.py):
        # entered at a tick's end, left at the next tick's start
        self._gap_mirror = None
        # streams whose prefill just ended, kept while a tracer is
        # installed: their flow gets its decode point in the next decode
        # span (a point per phase change, not per stream per tick)
        self._flow_to_decode: List[int] = []
        # one lap a tick, from a tick's start to the next one's (the
        # caller's time between ticks included): a tick that ran long is
        # recorded with where it stood (train/trace.py "Laps and stalls")
        self._laps = trace_lib.LapWatch("serve_tick")

    # ---- client surface ------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int,
               slo_ms: Optional[float] = None,
               unified: bool = False) -> Optional[int]:
        """Enqueue a request; returns its id, or None when the bounded
        queue is full (the request is REJECTED — overload sheds load
        instead of growing latency without bound).  Raises for requests
        the server could never hold (over ``max_len`` / pool capacity),
        mirroring ``PagedDecodeServer.try_admit``'s loud refusal.
        ``unified=True`` pins the request to end-to-end service on THIS
        scheduler regardless of its role — the degraded fallback a
        router uses when the peer pool is empty."""
        prompt_ids = [int(t) for t in prompt_ids]
        p = len(prompt_ids)
        if p == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens {max_new_tokens} < 1")
        if p + max_new_tokens > self.server.max_len:
            raise ValueError(f"prompt {p} + {max_new_tokens} exceeds "
                             f"max_len {self.server.max_len}")
        if (self.server.blocks_for(p + max_new_tokens)
                > self.server.allocator.capacity):
            raise ValueError("request needs more KV blocks than the pool "
                             "owns: unservable at any load")
        if len(self.queue) >= self.cfg.queue_depth:
            self.rejected += 1
            return None
        slo = self.cfg.default_slo_ms if slo_ms is None else slo_ms
        now = self.now()
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt_ids,
                      max_new=int(max_new_tokens), t_submit=now,
                      deadline=(now + slo / 1e3 if slo is not None
                                else math.inf),
                      slo_ms=slo, unified=bool(unified))
        self.reqs[rid] = req
        self.queue.append(req)
        return rid

    def done(self, rid: int) -> bool:
        if rid in self._results:
            return True
        if rid in self._srv_rid or any(r.rid == rid for r in self.queue):
            return False
        raise KeyError(f"request {rid}: unknown or already consumed")

    def result(self, rid: int) -> List[int]:
        """Prompt + generated ids (pops the tokens; timings stay
        readable via :meth:`stats`)."""
        return self._results.pop(rid)

    def stats(self, rid: int) -> Request:
        return self.reqs[rid]

    def in_flight(self) -> int:
        return len(self._srv_rid)

    def pending(self) -> int:
        return len(self.queue)

    # ---- the service loop ----------------------------------------------
    def tick(self) -> List[int]:
        """One scheduler tick: admit/prefill/decode/land/retire.  Returns
        the rids whose results reached the host during this tick."""
        self.tick_no += 1
        self._laps.lap(self.tick_no, self.now())
        self._leave_gap()
        tracer = trace_lib.active()
        if tracer is not None and self._gap_state is not None:
            gap = time.time() - self._gap_wall
            if gap >= 1e-4:  # sub-100us gaps are loop overhead, not waits
                tracer.record_span(self._gap_state, self._gap_wall, gap,
                                   {"tick": self.tick_no})
        with trace_lib.span("admit", tick=self.tick_no):
            self._admit()
        with trace_lib.span("prefill", tick=self.tick_no):
            self._prefill_tick()
        decoding = self.server.any_active()
        if decoding:
            with trace_lib.span("decode", tick=self.tick_no):
                self._grow_or_evict()
                # flow step at a stream's FIRST decode tick: the arrow
                # that links its admit->prefill path into the decode
                # spans (retire closes the chain)
                for rid in self._flow_to_decode:
                    if rid in self._srv_rid:
                        trace_lib.flow(
                            "req", f"{self._flow_prefix}{rid}", "t",
                            rid=rid, stage="decode", tick=self.tick_no)
                self._flow_to_decode.clear()
                acct = self.server.keys_accounting()
                self.attended_keys += acct["attended_keys"]
                self.padded_keys += acct["padded_keys"]
                self.kernel_keys += acct["kernel_keys"]
                self.walked_keys += acct["walked_keys"]
                self.server.dispatch()
        # with this tick's chunk and step queued on the device, wait for
        # the rows taken a program earlier (a tick that only lands rows
        # opens no ``decode`` span)
        done_now = self._land(opened=decoding)
        self.telemetry.on_tick(self.tick_no, self._snapshot())
        self._gap_wall = time.time()
        self._gap_state = ("sched_bubble" if self._srv_rid
                           else ("queue_wait" if self.queue else None))
        if self._gap_state is not None:
            self._gap_mirror = trace_lib.annotation(self._gap_state)
            self._gap_mirror.__enter__()
        return done_now

    def _land(self, every: bool = False, opened: bool = False) -> List[int]:
        """Bring the finished streams' rows that are due to the host
        (``PagedDecodeServer.land``: those with a later program queued
        behind them; all of them once nothing is, or under ``every``) and
        retire their requests: ``t_done`` is the landing, never a dispatch.
        The ``retire`` span (opened for a decode tick too, ``opened``)
        carries the server's cumulative counters, fresh as of the step
        behind which the newest landed row was taken."""
        landed = self.server.land(every)
        if not (landed or opened):
            return []
        with trace_lib.span("retire", tick=self.tick_no) as retire:
            done = [self._retire(srv_rid) for srv_rid in landed]
            if landed:
                self.expert_counters = dict(self.server.expert_counters)
                self.attention_counters = dict(
                    self.server.attention_counters)
                self.ssm_counters = dict(self.server.ssm_counters)
                retire.attrs.update(
                    self.expert_counters, **self.attention_counters,
                    **self.ssm_counters,
                    rows_landed=self.server.rows_landed,
                    rows_landed_behind=self.server.rows_landed_behind)
        return done

    def _leave_gap(self) -> None:
        if self._gap_mirror is not None:
            self._gap_mirror.__exit__(None, None, None)
            self._gap_mirror = None

    def run_until_drained(self, max_ticks: int = 100_000) -> List[int]:
        """Tick until queue + in-flight are empty; returns completion
        order.  ``max_ticks`` is a hard stop so a policy bug shows up as
        a loud failure, not a hang."""
        order: List[int] = []
        for _ in range(max_ticks):
            if not (self.queue or self._srv_rid):
                return order
            order += self.tick()
        raise RuntimeError(
            f"not drained after {max_ticks} ticks: queue="
            f"{len(self.queue)} in_flight={len(self._srv_rid)}")

    def close(self) -> None:
        self._leave_gap()
        self._land(every=True)      # no tick follows: nothing stays in flight
        for line in self._laps.end():
            log(line, every_process=True, file=sys.stderr)
        self.telemetry.close(self.tick_no, self._snapshot())
        if self._tracer is not None:
            trace_lib.stop_run(self._tracer)
            self._tracer = None

    # ---- fleet surface (serve/fleet.py) --------------------------------
    def load_report(self) -> Dict[str, Any]:
        """This replica's live load signal for a fleet router: the
        ``kind="rollup"`` record the telemetry stream already emits
        (serialized utils/sketches state — TTFT/ITL percentiles, queue
        depth, block utilization) refreshed with a ``now`` sub-dict of
        instantaneous occupancy, plus the admission capacity the router
        needs (``free_slots``).  One record shape everywhere: the router
        parses the same document tools/obs_agg.py merges."""
        rec = self.telemetry.rollup_record(self.tick_no, self._snapshot())
        rec["now"]["free_slots"] = self.server.free_slots()
        rec["now"]["in_flight"] = len(self._srv_rid)
        rec["now"]["slots"] = self.cfg.slots
        rec["now"]["queue_cap"] = self.cfg.queue_depth
        rec["now"]["tokens_at_risk"] = self.tokens_at_risk()
        rec["now"]["role"] = self.cfg.role
        rec["now"]["handoffs_ready"] = len(self._handoffs)
        return rec

    def take_handoffs(self) -> List[Dict[str, Any]]:
        """Drain the handoff exports a prefill-role scheduler has
        produced since the last call: one ``{"rid", "payload",
        "slo_ms", "ttft_ms", "prompt_tokens"}`` descriptor per stream
        whose prefill completed.  The caller (the fleet worker loop /
        InprocReplica) forwards each to the router, which owns the
        record from that commit point on."""
        out, self._handoffs = self._handoffs, []
        return out

    def inject(self, payload: Dict[str, Any],
               slo_ms: Optional[float] = None) -> Optional[int]:
        """Admit a handed-off stream directly into decode: imports the
        exported block contents + first sampled token
        (:meth:`PagedDecodeServer.import_stream`) and registers the
        request as decoding — no queue, no prefill duty.  Returns a
        request id, or None when a slot or the blocks are unavailable
        (nothing consumed; the router retries elsewhere or later).
        ``t_first`` is stamped now — the REAL time-to-first-token lives
        on the prefill side (the router composes end-to-end timings);
        this side's numbers price the decode phase only."""
        srv_rid = self.server.import_stream(payload)
        if srv_rid is None:
            return None
        now = self.now()
        slo = self.cfg.default_slo_ms if slo_ms is None else slo_ms
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid,
                      prompt=[int(t) for t in payload["prompt"]],
                      max_new=int(payload["max_new"]), t_submit=now,
                      deadline=(now + slo / 1e3 if slo is not None
                                else math.inf),
                      slo_ms=slo, t_first=now)
        self.reqs[rid] = req
        self._srv_rid[rid] = srv_rid
        self._sched_rid[srv_rid] = rid
        self.injected += 1
        trace_lib.flow("req", f"{self._flow_prefix}{rid}", "t",
                       rid=rid, stage="inject", tick=self.tick_no)
        if trace_lib.active() is not None:
            self._flow_to_decode.append(rid)
        # (a degenerate single-token handoff is already complete: its row is
        # in flight, and the next tick reports it like any other)
        return rid

    def tokens_at_risk(self) -> int:
        """Tokens of consumed work an unannounced kill would discard
        right now: prefilled + generated across every in-flight stream
        (queued requests carry zero — nothing has been spent on them).
        The advance-notice drain exists to take this to zero before the
        process dies; a chaos campaign's ``tokens_lost`` for a SIGKILL
        arm is exactly this quantity at the moment of the kill."""
        total = 0
        for rid, srv_rid in self._srv_rid.items():
            req = self.reqs[rid]
            if not self.server.holds(srv_rid):  # finished, its row in flight
                total += len(req.prompt) + req.max_new
                continue
            st = self.server._streams[srv_rid]
            slot = self.server._slot_of[srv_rid]
            prefilled, p = st.prefilled, len(req.prompt)
            generated = (int(self.server._pos_host[slot]) - p + 1
                         if prefilled >= p else 0)
            total += prefilled + max(0, generated)
        return total

    def drain(self) -> List[Dict[str, Any]]:
        """Stop serving and hand every unfinished request back for
        requeue: evicts all in-flight streams (their blocks release, the
        allocator's ``assert_drained`` holds afterwards) and empties the
        wait queue, returning one descriptor per request in ORIGINAL
        submission order — ``{"rid", "prompt", "max_new", "slo_ms",
        "prefilled", "generated"}``.  ``prefilled``/``generated`` are the
        consumed-token state at drain time (observability: how much work
        the drain discards); the tokens themselves are NOT carried —
        greedy decode is deterministic, so re-admission on any replica
        with the same params reproduces them exactly (pinned by
        tests/test_serve_sched.py).  Completed-but-unconsumed results
        stay readable via :meth:`result`."""
        out: List[Dict[str, Any]] = []
        self._land(every=True)      # finished is finished: not handed back
        for rid in list(self._srv_rid):
            srv_rid = self._srv_rid.pop(rid)
            self._sched_rid.pop(srv_rid)
            req = self.reqs[rid]
            st = self.server._streams[srv_rid]
            slot = self.server._slot_of[srv_rid]
            prefilled, p = st.prefilled, len(req.prompt)
            # generated-so-far: position p holds the first sampled token
            # once prefill completes, then one per decode step
            generated = (int(self.server._pos_host[slot]) - p + 1
                         if prefilled >= p else 0)
            self.server.evict(srv_rid)
            if rid in self._prefilling:
                self._prefilling.remove(rid)
            req.t_first = None      # TTFT restarts on re-admission
            out.append({"rid": rid, "prompt": list(req.prompt),
                        "max_new": req.max_new, "slo_ms": req.slo_ms,
                        "prefilled": prefilled,
                        "generated": max(0, generated),
                        "t_submit": req.t_submit,
                        "evictions": req.evictions})
        # handoffs exported but never taken by the worker loop: the
        # stream is gone from the server, but the REQUEST must not
        # vanish — hand it back as undone work (full re-prefill on
        # whichever replica the router picks next)
        for h in self._handoffs:
            req = self.reqs[h["rid"]]
            req.t_first = None
            out.append({"rid": req.rid, "prompt": list(req.prompt),
                        "max_new": req.max_new, "slo_ms": req.slo_ms,
                        "prefilled": 0, "generated": 0,
                        "t_submit": req.t_submit,
                        "evictions": req.evictions})
        self._handoffs = []
        for req in self.queue:
            out.append({"rid": req.rid, "prompt": list(req.prompt),
                        "max_new": req.max_new, "slo_ms": req.slo_ms,
                        "prefilled": 0, "generated": 0,
                        "t_submit": req.t_submit,
                        "evictions": req.evictions})
        self.queue.clear()
        out.sort(key=lambda d: (d["t_submit"], d["rid"]))
        return out

    def quiesce(self) -> List[Dict[str, Any]]:
        """:meth:`drain` plus the proof: evict everything, then assert
        the allocator really is empty before the caller exits.  The one
        call shared by every worker shutdown path — the advance-notice
        preemption drain, the decommission handshake, and the orphaned
        worker whose control plane died (stdin EOF) — so "exited
        cleanly" always MEANS "leaked no blocks"."""
        out = self.drain()
        self.server.assert_drained()
        return out

    # ---- internals -----------------------------------------------------
    def _committed_tokens(self) -> int:
        """In-flight committed (prompt + max_new) tokens, refcount-aware:
        token positions resident in a SHARED block are physical once, so
        each extra reference's worth is discounted (the server's
        block-granular upper bound) instead of charged per stream —
        otherwise a token budget would reject admissions whose residency
        the cache already holds."""
        raw = sum(len(self.reqs[rid].prompt) + self.reqs[rid].max_new
                  for rid, srv_rid in self._srv_rid.items()
                  if self.server.holds(srv_rid))
        return max(0, raw - self.server.shared_token_discount())

    def _admit(self) -> None:
        while self.queue:
            req = self.queue[0]
            p = len(req.prompt)
            if self.server.free_slots() == 0:
                return
            # normal admission overcommits (blocks for the prompt + first
            # token only — growth is on demand; that overcommit IS the
            # capacity win).  A request that already got evicted proved
            # overcommit fails for it right now: hold it at the head
            # until the pool can cover its FULL need, else it would
            # thrash admit->grow->evict while the same streams hold the
            # pool.  Both needs are REFCOUNT-AWARE: a prefix match onto
            # in-use blocks consumes no free block (admit_need subtracts
            # them, and adds the reserved CoW fork block for a mid-block
            # match boundary).
            need = self.server.admit_need(req.prompt, req.max_new,
                                          full_residency=bool(
                                              req.evictions))
            if self.server.free_blocks < need:
                return
            if (self.cfg.token_budget > 0
                    and self._committed_tokens() + p + req.max_new
                    > self.cfg.token_budget):
                return
            srv_rid = self.server.try_admit(req.prompt, req.max_new)
            if srv_rid is None:
                return
            self.queue.popleft()
            self._srv_rid[req.rid] = srv_rid
            self._sched_rid[srv_rid] = req.rid
            self._prefilling.append(req.rid)
            self.admitted += 1
            # flow START (or re-start after an eviction's re-admission)
            trace_lib.flow("req", f"{self._flow_prefix}{req.rid}", "s",
                           rid=req.rid, stage="admit",
                           prompt_tokens=p, tick=self.tick_no)

    def _prefill_tick(self) -> None:
        """At most one prefill chunk per tick (interleaving: decoding
        streams advance every tick regardless of admission work)."""
        if not self._prefilling:
            return
        rid = self._prefilling[0]
        srv_rid = self._srv_rid[rid]
        trace_lib.flow("req", f"{self._flow_prefix}{rid}", "t",
                       rid=rid, stage="prefill", tick=self.tick_no)
        if self.server.prefill_step(srv_rid, self.cfg.prefill_chunk):
            self._prefilling.popleft()
            req = self.reqs[rid]
            req.t_first = self.now()
            if trace_lib.active() is not None:
                self._flow_to_decode.append(rid)
            # (a single-token request is finished here: its row is taken,
            # and the tick's landing reports it)
            if (self.cfg.role == "prefill" and not req.unified
                    and self.server.holds(srv_rid)):
                # disaggregated handoff: the stream leaves this replica
                # at the prefill->decode boundary.  Export FIRST (read-
                # only), then release — under prefix_cache the owned
                # prompt blocks were registered during prefill, so the
                # release parks them cached-free and the content stays
                # resident for future prefix hits
                self._export_handoff(rid, srv_rid)

    def _export_handoff(self, rid: int, srv_rid: int) -> None:
        req = self.reqs[rid]
        payload = self.server.export_stream(srv_rid)
        self._srv_rid.pop(rid)
        self._sched_rid.pop(srv_rid)
        self.server.evict(srv_rid)
        ttft = round((req.t_first - req.t_submit) * 1e3, 3)
        self.handed_off += 1
        self.telemetry.on_handoff(ttft)
        self._handoffs.append({
            "rid": rid, "payload": payload, "slo_ms": req.slo_ms,
            "ttft_ms": ttft, "prompt_tokens": len(req.prompt)})
        trace_lib.flow("req", f"{self._flow_prefix}{rid}", "t",
                       rid=rid, stage="handoff", tick=self.tick_no)

    def _grow_or_evict(self) -> None:
        """Supply every decoding stream's next block, evicting
        latest-deadline streams under exhaustion.  The earliest-deadline
        stream is never evicted while another in-flight stream exists —
        the oldest obligation always progresses."""
        while self.server.ensure_blocks():
            victim = self._pick_victim()
            if victim is None:
                # unreachable when submit()'s capacity guard holds: a
                # sole stream owns every non-free block, and the pool
                # covers any single stream end to end
                raise RuntimeError("block exhaustion with no evictable "
                                   "stream (capacity guard violated)")
            self._evict(victim)

    def _pick_victim(self) -> Optional[int]:
        inflight = [self.reqs[rid] for rid, srv_rid in self._srv_rid.items()
                    if self.server.holds(srv_rid)]
        if len(inflight) <= 1:
            return None
        key = lambda r: (r.deadline, r.t_submit, r.rid)   # noqa: E731
        protected = min(inflight, key=key)
        victim = max(inflight, key=key)
        if victim.rid == protected.rid:
            return None
        return victim.rid

    def _evict(self, rid: int) -> None:
        srv_rid = self._srv_rid.pop(rid)
        self._sched_rid.pop(srv_rid)
        self.server.evict(srv_rid)
        if rid in self._prefilling:
            self._prefilling.remove(rid)
        req = self.reqs[rid]
        req.evictions += 1
        req.t_first = None          # TTFT restarts: tokens are recomputed
        self.queue.appendleft(req)  # front: original arrival order kept
        self.evicted += 1
        log(f"[serve] evicted rid={rid} (deadline "
            f"{'inf' if math.isinf(req.deadline) else round(req.deadline, 3)}"
            f"); requeued at front")

    def _retire(self, srv_rid: int) -> int:
        rid = self._sched_rid.pop(srv_rid)
        self._srv_rid.pop(rid)
        req = self.reqs[rid]
        req.t_done = self.now()
        trace_lib.flow("req", f"{self._flow_prefix}{rid}", "f",
                       rid=rid, stage="retire", tick=self.tick_no)
        if req.t_first is None:
            req.t_first = req.t_done
        toks = self.server.result(srv_rid)
        self._results[rid] = toks
        n_gen = len(toks) - len(req.prompt)
        self.completed += 1
        self.tokens_out += n_gen
        self.telemetry.on_request_done(req, n_gen)
        # bounded retention: stats()/result() stay readable for the last
        # completed_history completions (plenty for a load generator's
        # post-completion read), then both the Request and any
        # never-consumed result are pruned — a service that runs for
        # days must not grow per-request state without bound
        self._done_order.append(rid)
        while len(self._done_order) > max(1, self.cfg.completed_history):
            old = self._done_order.popleft()
            self.reqs.pop(old, None)
            self._results.pop(old, None)
        return rid

    def _snapshot(self) -> Dict[str, Any]:
        prefix: Dict[str, Any] = {}
        if self.cfg.prefix_cache:
            ps = self.server.prefix_stats()
            prefix = dict(ps)
            # hit rate over prompt TOKENS (not requests): the fraction
            # of admitted prompt work served from resident blocks — the
            # number RadixAttention-style stores are judged on
            prefix["prefix_hit_rate"] = (
                round(ps["prefix_hit_tokens"]
                      / ps["prompt_tokens_admitted"], 4)
                if ps["prompt_tokens_admitted"] else None)
        return {
            **prefix,
            "queue_depth": len(self.queue),
            "live": len(self._srv_rid),
            "prefilling": len(self._prefilling),
            "free_blocks": self.server.free_blocks,
            "block_utilization": round(self.server.block_utilization(), 4),
            "committed_tokens": self._committed_tokens(),
            "admitted": self.admitted,
            "rejected": self.rejected,
            "evicted": self.evicted,
            "completed": self.completed,
            "tokens_out": self.tokens_out,
            "handed_off": self.handed_off,
            "injected": self.injected,
            "attended_keys": self.attended_keys,
            "padded_keys": self.padded_keys,
            "kernel_keys": self.kernel_keys,
            # finished streams' rows brought to the host, and those of them
            # that had a later program queued behind the wait
            "rows_landed": self.server.rows_landed,
            "rows_landed_behind": self.server.rows_landed_behind,
            # prefill chunk programs dispatched, and those of them whose
            # head ran (a prompt's last chunk)
            "prefill_chunks": self.server.prefill_chunks,
            "prefill_heads": self.server.prefill_heads,
            # ticks that ran long, and their seconds over the median tick
            "stalls": self._laps.stalls,
            "stall_s": round(self._laps.stall_s, 6),
            **self.expert_counters,
            **self.attention_counters,
            **self.ssm_counters,
            "attended_ratio": (
                round(self.attended_keys / self.padded_keys, 4)
                if self.padded_keys else None),
            # 1.0 under 'gathered'; under the fused kernel the share of
            # the table's width its page walk reads
            "walked_keys_share": (
                round(self.walked_keys / self.padded_keys, 4)
                if self.padded_keys else None),
        }
