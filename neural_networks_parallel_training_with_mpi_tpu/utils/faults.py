"""Deterministic fault injection for resilience testing.

Drives the full skip -> rollback -> restart -> converge story end to end
(tests/test_resilience.py) without flaky timing: every fault fires at an
exact global step, on every replica identically.

Spec grammar (``--faults`` / the ``NNPT_FAULTS`` env var), comma-separated::

    kind@start[-end][?opt[&opt...]]

kinds
    ``nan``      poison the batch so the step's loss (and hence every
                 gradient) is NaN — the canonical bad batch the guarded
                 update must reject.  Implemented by NaN-ing the batch's
                 ``mask`` leaf (float on every dataset, multiplied into
                 every loss term), so it works for int token batches too.
    ``crash``    die abruptly (``os._exit(1)``) — a segfault/OOM stand-in
                 the supervisor must relaunch.
    ``sigterm``  send SIGTERM to this process — a preemption stand-in the
                 graceful-shutdown path must absorb (exit 0 + checkpoint).

I/O faults against the checkpoint durability layer (DESIGN.md §8 — the
first two need the trainer's ``checkpoint_dir``, threaded through
``apply``):

    ``torn_ckpt``    arm the checkpoint writer so its NEXT snapshot write
                     publishes the payload but dies (SIGKILL) before the
                     manifest commit marker — the torn-write state restore
                     must treat as uncommitted and fall back past.
    ``corrupt_ckpt`` flip bytes in the middle of the newest committed
                     snapshot's largest payload file (bit rot / partial
                     overwrite stand-in) — restore must quarantine the
                     generation and fall back.
    ``ckpt_ioerr``   arm the checkpoint writer to raise OSError on its
                     next write (full disk / lost mount stand-in) — the
                     async error channel must surface it on the caller's
                     thread, with older snapshots intact.

Silent-data-corruption faults against the replica-consistency layer
(DESIGN.md §9 — these perturb the TRAIN STATE, so the trainer threads it
through :meth:`FaultPlan.apply_state`):

    ``bitflip``      flip one bit in ONE replica shard of a (named or
                     deterministically chosen) replicated param leaf —
                     the cosmic-ray / flaky-HBM stand-in the on-device
                     fingerprint must detect, localize to the exact
                     shard, triage as transient by replay, and heal.
                     Options: ``param=SUBSTR`` (leaf path substring;
                     default: pick by ``start %% n_candidates``),
                     ``shard=K`` (default 1), ``bit=B`` (default 12 — a
                     float32 mantissa bit, so the value stays finite).
    ``desync``       perturb one shard of a replicated OPTIMIZER-state
                     leaf (add ``eps=V``, default 1e-3) — a lost/garbled
                     update stand-in, transient like ``bitflip``.  With
                     the ``det`` option the perturbation instead moves
                     INTO the jitted step function (every replica but the
                     first drifts a little more every step from
                     ``start``): the replay triage then reproduces the
                     divergence and must abort with EXIT_SDC (45) —
                     the deterministic-software-bug verdict.

Capacity-loss faults against the elastic restart layer (DESIGN.md §10 —
these drive the supervisor's probe-and-shrink policy end to end; all
three honor ``proc=K`` to pick the victim process in a multi-host
world):

    ``peer_kill``    SIGKILL this process mid-run — no cleanup, no
                     goodbye: the dead-host stand-in.  Survivors must
                     fail fast (bounded collectives / watchdog -> exit
                     42/43) and their elastic supervisor must probe and
                     relaunch at the shrunken world.
    ``peer_hang``    wedge this process in an uninterruptible host-side
                     sleep — the frozen-host stand-in whose PEERS must
                     convert the stalled collective into exit 43 (the
                     victim's own watchdog may also fire, exit 42).
    ``device_loss``  this process reports losing a local device: dump a
                     postmortem and exit 43 (EXIT_PEER) — the runtime-
                     lost-a-chip stand-in the supervisor retries or,
                     under ``--elastic`` with repeated losses, degrades
                     through a topology probe.

Fleet faults against a serving-fleet WORKER (serve/fleet.py's
``worker_main`` consumes these via :meth:`FaultPlan.fire_if_due`; the
"step" counter is the worker's accepted-submit count, and ``proc=K``
matches the worker's ``--replica`` id rather than a jax process index):

    ``replica_kill`` SIGKILL this replica on its Nth accepted submit —
                     the mid-scale-out / mid-load dead-replica stand-in:
                     the router must requeue its in-flight requests onto
                     siblings and the supervisor must relaunch it under
                     its own budget, without cascading.
    ``stall_drain``  ignore drain/decommission requests while the window
                     is open — the wedged-shutdown stand-in: the
                     autopilot's drain timeout must escalate (retire +
                     kill) instead of waiting forever, and the ledger
                     must still requeue the stalled replica's in-flight
                     work exactly once.

Disaggregated-handoff faults (DESIGN.md §11 — a PREFILL worker counts
handoff events, a DECODE worker counts inject ops; both honor
``proc=K`` against ``--replica``):

    ``handoff_kill``      SIGKILL the prefill worker on its Nth handoff
                          BEFORE the commit line reaches the wire — the
                          router never saw the record, so the request
                          must requeue for a full re-prefill elsewhere,
                          exactly once.
    ``handoff_kill_post`` SIGKILL the prefill worker just AFTER the
                          commit line — the router owns the record;
                          decode must proceed without repaying prefill.
    ``decode_kill``       SIGKILL the decode worker right after acking
                          its Nth inject — decode death mid-stream; the
                          router re-injects from its ledger record
                          (re-decode only, no re-prefill).
    ``handoff_stall``     swallow the Nth inject op (no ack, no stream)
                          — the wedged-handoff stand-in the router's
                          handoff timeout must abort and retry with
                          jittered backoff.

Control-plane faults (the DRIVER fires these — the chaos
``fleet_ctrlplane`` scenario polls
:meth:`FaultPlan.fire_if_due` with the router's COMPLETED count as the
step; the victim is the operator process itself, which a worker-side
hook can never reach):

    ``router_kill``  SIGKILL the router/supervisor process on its Nth
                     completion — workers orphan (stdin EOF) and drain
                     through the notice channel's discipline; the next
                     incarnation replays the write-ahead request ledger
                     (serve/wal.py) and owes every unfinished request.
    ``fleet_kill``   SIGKILL the ENTIRE fleet process group on the Nth
                     completion — router, prefill and decode pools,
                     committed handoff records in flight.  Relaunch
                     must re-admit exactly once per journaled phase
                     with byte-identical tokens.

Preemption / degradation faults (PR 18 — consumed by BOTH the Trainer's
``apply`` path and a fleet worker's ``fire_if_due``/``slow_penalty_ms``
polls, so one grammar drives the training and serving arms of the chaos
campaigns):

    ``preempt``      advance-notice preemption: deliver SIGUSR1 to this
                     process with ``grace=S`` seconds of warning (the
                     injected twin of a cloud maintenance notice — the
                     real-world seam is the same signal sent by
                     ``GroupSupervisor.notify_preempt`` or an operator).
                     A trainer answers with a coordinated final
                     checkpoint and exits 47 (decommission — goodput
                     prices the tail as ``drain``, not rollback); a
                     serving worker stops admitting, finishes in-flight
                     work inside the grace window, and exits 47 so the
                     autopilot backfills BEFORE the capacity disappears.
    ``slow``         degrade, don't die: inject ``ms=M`` milliseconds of
                     latency per step/tick while the window is open —
                     the slow-but-alive replica stand-in the autopilot's
                     health eviction must detect and replace.

options
    ``grace=S``   ``preempt`` only: seconds between the notice and the
                  deadline (default 2.0) — the window the victim has to
                  checkpoint/drain before the platform would hard-kill.
    ``ms=M``      ``slow`` only: injected latency per step/tick in
                  milliseconds (default 50.0).
    ``max=N``     fire at most N times over this process's lifetime
                  (in-memory counter) — lets a NaN window be *passable*
                  after a rollback replays it.
    ``once=PATH`` fire at most once per PATH lifetime: the marker file is
                  created at fire time, and the fault never fires while it
                  exists — survives a process restart, so a supervised
                  relaunch does not re-crash at the same step.
    ``param=``/``shard=``/``bit=``/``eps=``/``det``
                  SDC-fault knobs, see ``bitflip``/``desync`` above.
    ``proc=K``    fire only on process index K (default: every process) —
                  selects the victim of the capacity-loss kinds in a
                  multi-host world.

Steps are the Trainer's global step counter *about to be executed*; with
``--steps_per_dispatch k > 1`` the granularity is the dispatch boundary
(the fault applies to the whole k-step group whose first step falls in the
window).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import sys
from pathlib import Path
from typing import Dict, List, Optional

ENV_VAR = "NNPT_FAULTS"
KINDS = ("nan", "crash", "sigterm", "torn_ckpt", "corrupt_ckpt",
         "ckpt_ioerr", "bitflip", "desync", "peer_kill", "peer_hang",
         "device_loss", "replica_kill", "stall_drain", "preempt", "slow",
         "handoff_kill", "handoff_kill_post", "decode_kill",
         "handoff_stall", "router_kill", "fleet_kill")
# kinds that perturb the train state (FaultPlan.apply_state) rather than
# the batch/process (FaultPlan.apply)
STATE_KINDS = ("bitflip", "desync")
# kinds a serving-fleet worker polls via FaultPlan.fire_if_due — never
# fired by the Trainer's apply/apply_state paths
FLEET_KINDS = ("replica_kill", "stall_drain", "handoff_kill",
               "handoff_kill_post", "decode_kill", "handoff_stall")
# kinds the EXPERIMENT DRIVER polls (bench --ctrlplane, the chaos
# fleet_ctrlplane scenario): the victim is the router/supervisor
# process itself, which cannot SIGKILL itself from inside its own
# service loop and still model an external control-plane death — so
# the driver owning the fleet's process group fires these when the
# router's completion count reaches the window.  ``router_kill@N``
# kills ONLY the operator process (workers orphan and drain via the
# notice channel's discipline); ``fleet_kill@N`` kills the whole
# process group mid-load.  Recovery is the WAL replay (serve/wal.py).
DRIVER_KINDS = ("router_kill", "fleet_kill")


def _process_index() -> int:
    """This process's world rank (0 when jax is absent/uninitialized) —
    lazy so parsing stays jax-free."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


@dataclasses.dataclass
class _Fault:
    kind: str
    start: int
    end: int                      # inclusive
    max_fires: Optional[int] = None
    once_marker: Optional[str] = None
    param: Optional[str] = None   # bitflip/desync: leaf-path substring
    shard: int = 1                # bitflip/desync: victim replica shard
    bit: int = 12                 # bitflip: bit index within the element
    eps: float = 1e-3             # desync: perturbation magnitude
    det: bool = False             # desync: deterministic in-step variant
    proc: Optional[int] = None    # fire only on this process index
    grace: float = 2.0            # preempt: notice-to-deadline seconds
    ms: float = 50.0              # slow: injected latency per step/tick
    fires: int = 0

    def should_fire(self, step: int) -> bool:
        if not (self.start <= step <= self.end):
            return False
        if self.max_fires is not None and self.fires >= self.max_fires:
            return False
        if self.once_marker and Path(self.once_marker).exists():
            return False
        return True

    def mark_fired(self) -> None:
        self.fires += 1
        if self.once_marker:
            p = Path(self.once_marker)
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text("fired\n")


def _parse_one(item: str) -> _Fault:
    head, _, opts = item.partition("?")
    kind, _, window = head.partition("@")
    kind = kind.strip()
    if kind not in KINDS:
        raise ValueError(f"unknown fault kind {kind!r} in {item!r} "
                         f"(choices: {', '.join(KINDS)})")
    if not window:
        raise ValueError(f"fault {item!r} lacks '@step' (e.g. 'nan@5-8')")
    lo, _, hi = window.partition("-")
    start = int(lo)
    end = int(hi) if hi else start
    if end < start:
        raise ValueError(f"fault window {window!r} ends before it starts")
    fault = _Fault(kind, start, end)
    if kind == "preempt":
        # a preemption notice is an EDGE, not a level: one notice per
        # spec unless max= explicitly asks for repeats (repeats are
        # idempotent at the receiver, but a one-shot default keeps
        # due_spec callers honest)
        fault.max_fires = 1
    for opt in filter(None, opts.split("&")):
        key, _, val = opt.partition("=")
        if key == "max":
            fault.max_fires = int(val)
        elif key == "once":
            if not val:
                raise ValueError(f"once= needs a marker path in {item!r}")
            fault.once_marker = val
        elif key == "param":
            fault.param = val
        elif key == "shard":
            fault.shard = int(val)
        elif key == "bit":
            fault.bit = int(val)
        elif key == "eps":
            fault.eps = float(val)
        elif key == "det":
            fault.det = True
        elif key == "proc":
            fault.proc = int(val)
        elif key == "grace":
            fault.grace = float(val)
            if fault.grace < 0:
                raise ValueError(f"grace= must be >= 0 in {item!r}")
            if kind != "preempt":
                raise ValueError(
                    f"option 'grace' only applies to preempt, not {kind!r}")
        elif key == "ms":
            fault.ms = float(val)
            if fault.ms < 0:
                raise ValueError(f"ms= must be >= 0 in {item!r}")
            if kind != "slow":
                raise ValueError(
                    f"option 'ms' only applies to slow, not {kind!r}")
        else:
            raise ValueError(f"unknown fault option {key!r} in {item!r}")
    if fault.det and kind != "desync":
        raise ValueError(f"option 'det' only applies to desync, not {kind!r}")
    return fault


def _corrupt_newest(ckpt_dir: Optional[str], step: int) -> None:
    """``corrupt_ckpt``: XOR 8 bytes in the middle of the newest committed
    snapshot's largest payload file — deterministic bit rot the manifest
    checksums must catch at the next restore."""
    import jax

    from . import checkpoint as ckpt_lib
    from . import ckpt_manifest

    if jax.process_index() != 0:
        # leader-only: on a shared filesystem an even process count would
        # XOR the same bytes twice and self-cancel the injected rot
        return
    if not ckpt_dir:
        print(f"[faults] corrupt_ckpt at step {step}: no checkpoint_dir "
              "configured, nothing to corrupt", file=sys.stderr, flush=True)
        return
    snaps = ckpt_lib._snapshot_dirs(Path(ckpt_dir), committed=True)
    if not snaps:
        print(f"[faults] corrupt_ckpt at step {step}: no committed "
              "snapshot yet, nothing to corrupt", file=sys.stderr,
              flush=True)
        return
    _, snap = snaps[-1]
    victim = max(ckpt_manifest.payload_files(snap),
                 key=lambda p: p.stat().st_size)
    size = victim.stat().st_size
    with open(victim, "r+b") as f:
        f.seek(size // 2)
        chunk = f.read(8)
        f.seek(size // 2)
        f.write(bytes(b ^ 0xFF for b in chunk))
    print(f"[faults] injected corruption at step {step}: flipped "
          f"{len(chunk)} bytes in {snap.name}/{victim.name}",
          file=sys.stderr, flush=True)


def _replicated_float_leaves(tree):
    """(name, leaf) for fully-replicated float leaves with >= 2 local
    shards — the candidate victims for the SDC fault kinds.  Replication
    detection is utils.consistency's (lazy import: this module stays
    jax-free until a fault actually fires)."""
    import jax.numpy as jnp

    from . import consistency

    for name, leaf in consistency._leaf_paths(tree):
        if (consistency._is_replicated(leaf)
                and len(leaf.addressable_shards) >= 2
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            yield name, leaf


def flip_bit_in_shard(leaf, shard_idx: int, bit: int,
                      elem: Optional[int] = None):
    """Rebuild a replicated leaf with one bit flipped in ONE replica
    shard (default element: the middle of the flat buffer) — physically
    diverged shards behind a sharding that still claims replication,
    which is exactly what a hardware SDC looks like.  Also used directly
    by tests/distributed_child.py's cross-host sweep."""
    import numpy as np

    from . import consistency

    shards = leaf.addressable_shards
    shard_idx %= len(shards)
    datas = [np.array(s.data) for s in shards]
    victim = datas[shard_idx]
    width = victim.dtype.itemsize * 8
    flat = victim.view(f"uint{width}").reshape(-1)
    elem = flat.shape[0] // 2 if elem is None else elem % flat.shape[0]
    flat[elem] ^= np.asarray(1 << (bit % width), flat.dtype)
    return consistency.rebuild_replicated_leaf(leaf, datas)


def perturb_shard(leaf, shard_idx: int, eps: float):
    """Rebuild a replicated leaf with ``eps`` added to every element of
    ONE replica shard (the ``desync`` kind's lost/garbled-update
    stand-in)."""
    import numpy as np

    from . import consistency

    shards = leaf.addressable_shards
    shard_idx %= len(shards)
    datas = [np.array(s.data) for s in shards]
    datas[shard_idx] = (datas[shard_idx]
                        + np.asarray(eps, datas[shard_idx].dtype)).astype(
        datas[shard_idx].dtype)
    return consistency.rebuild_replicated_leaf(leaf, datas)


def _replace_leaf(tree, name: str, new_leaf):
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [new_leaf if jax.tree_util.keystr(path) == name else leaf
              for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def wrap_step_with_desync(step_fn, mesh, start: int, eps: float):
    """The DETERMINISTIC desync (``desync@N?det``): wrap a train step so
    that, from global step ``start`` on, every device but the first adds
    ``eps * device_index`` to the first float param leaf INSIDE the jitted
    program — a stand-in for a shard_map out_spec that lies about
    replication or a miscompiled collective.  Because the bug lives in
    the step function, the SDC replay triage reproduces it and must
    return the deterministic verdict (abort, EXIT_SDC)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)

    def perturb(state):
        lin = None
        for a in axes:
            i = lax.axis_index(a)
            lin = i if lin is None else lin * lax.axis_size(a) + i
        scale = jnp.where(state.step >= start, jnp.float32(eps),
                          jnp.float32(0.0))
        flat, treedef = jax.tree_util.tree_flatten(state.params)
        for k, leaf in enumerate(flat):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                flat[k] = leaf + (scale * lin.astype(jnp.float32)
                                  ).astype(leaf.dtype)
                break
        return state._replace(
            params=jax.tree_util.tree_unflatten(treedef, flat))

    mapped = jax.jit(jax.shard_map(perturb, mesh=mesh, in_specs=(P(),),
                                   out_specs=P(), check_vma=False))

    def wrapped(state, batch):
        state, out = step_fn(state, batch)
        return mapped(state), out

    return wrapped


class FaultPlan:
    """Parsed fault schedule; the Trainer calls :meth:`apply` once per
    dispatch with the global step about to run and the (device-placed)
    batch, and receives the possibly-poisoned batch back.  State-kind
    faults (``bitflip``/``desync``) go through :meth:`apply_state`
    instead; the deterministic desync is consumed at step-build time via
    :meth:`det_desync`."""

    def __init__(self, faults: List[_Fault]):
        self.faults = faults

    @staticmethod
    def parse(spec: str) -> Optional["FaultPlan"]:
        spec = (spec or "").strip()
        if not spec:
            return None
        return FaultPlan([_parse_one(s.strip())
                          for s in spec.split(",") if s.strip()])

    @staticmethod
    def from_config(cfg_spec: str = "") -> Optional["FaultPlan"]:
        """Config spec wins; falls back to the ``NNPT_FAULTS`` env var (the
        channel a supervisor-launched child inherits)."""
        return FaultPlan.parse(cfg_spec or os.environ.get(ENV_VAR, ""))

    def det_desync(self) -> Optional[_Fault]:
        """The deterministic in-step desync spec, if any (consumed by the
        Trainer at step-build time — it cannot fire from apply_state)."""
        for f in self.faults:
            if f.kind == "desync" and f.det:
                return f
        return None

    def apply_state(self, step: int, state, what: str = "train state"):
        """Fire any due state-kind faults (``bitflip``/``desync``) against
        the device-placed train state; returns the possibly-corrupted
        state.  Single-process injection (the multi-host sweep injects via
        :func:`flip_bit_in_shard` directly in tests/distributed_child.py).
        """
        for f in self.faults:
            if (f.kind not in STATE_KINDS or f.det
                    or (f.proc is not None
                        and _process_index() != f.proc)
                    or not f.should_fire(step)):
                continue
            target = (state.params if f.kind == "bitflip"
                      else state.opt_state)
            cands = list(_replicated_float_leaves(target))
            if not cands:
                print(f"[faults] {f.kind} at step {step}: no replicated "
                      f"float leaves in {what} to corrupt", file=sys.stderr,
                      flush=True)
                continue
            f.mark_fired()
            if f.param:
                named = [c for c in cands if f.param in c[0]]
                if not named:
                    raise ValueError(
                        f"{f.kind} param={f.param!r} matches no replicated "
                        f"float leaf (candidates: "
                        f"{[n for n, _ in cands]})")
                name, leaf = named[0]
            else:
                name, leaf = cands[f.start % len(cands)]
            if f.kind == "bitflip":
                new_leaf = flip_bit_in_shard(leaf, f.shard, f.bit)
                detail = f"bit {f.bit}"
            else:
                new_leaf = perturb_shard(leaf, f.shard, f.eps)
                detail = f"eps {f.eps}"
            print(f"[faults] injected {f.kind} at step {step}: {detail} in "
                  f"shard {f.shard % len(leaf.addressable_shards)} of "
                  f"{name}", file=sys.stderr, flush=True)
            target = _replace_leaf(target, name, new_leaf)
            state = (state._replace(params=target)
                     if f.kind == "bitflip"
                     else state._replace(opt_state=target))
        return state

    def due_spec(self, kind: str, step: int,
                 proc: Optional[int] = None) -> Optional[_Fault]:
        """Like :meth:`fire_if_due`, but returns the fired spec itself so
        callers can read its knobs (a fleet worker needs ``preempt``'s
        ``grace``); None when nothing is due."""
        for f in self.faults:
            if f.kind != kind:
                continue
            if (f.proc is not None and proc is not None
                    and f.proc != proc):
                continue
            if not f.should_fire(step):
                continue
            f.mark_fired()
            return f
        return None

    def fire_if_due(self, kind: str, step: int,
                    proc: Optional[int] = None) -> bool:
        """Generic due-check for callers that own their own fault
        semantics (the fleet worker's :data:`FLEET_KINDS`): True — and
        the fault is marked fired — iff a matching spec is due at
        ``step``.  ``proc`` is the CALLER's identity (a fleet worker
        passes its ``--replica`` id, not jax's process index), matched
        against the spec's ``proc=`` option when both are set."""
        return self.due_spec(kind, step, proc=proc) is not None

    def slow_penalty_ms(self, step: int,
                        proc: Optional[int] = None) -> float:
        """Summed injected latency (ms) due at ``step`` from ``slow``
        specs — polled per tick by a fleet worker (the degraded-replica
        stand-in sleeps this much extra every engine pass while the
        window is open).  Unlike the one-shot kinds this fires on every
        poll inside the window; ``max=N`` still bounds total fires."""
        ms = 0.0
        for f in self.faults:
            if f.kind != "slow":
                continue
            if (f.proc is not None and proc is not None
                    and f.proc != proc):
                continue
            if not f.should_fire(step):
                continue
            f.mark_fired()
            ms += f.ms
        return ms

    def apply(self, step: int, batch: Dict,
              ckpt_dir: Optional[str] = None) -> Dict:
        for f in self.faults:
            if (f.kind in STATE_KINDS or f.kind in FLEET_KINDS
                    or f.kind in DRIVER_KINDS):
                continue  # apply_state's / fire_if_due's / driver's job
            if f.proc is not None and _process_index() != f.proc:
                continue  # another process is the victim
            if not f.should_fire(step):
                continue
            f.mark_fired()
            if f.kind == "peer_kill":
                # die like a dead host: SIGKILL, no cleanup, no goodbye —
                # the peers' containment (bounded collectives/watchdog)
                # and the elastic supervisor are what is under test
                print(f"[faults] injected peer_kill at step {step}: "
                      "SIGKILL (dead-host stand-in)", file=sys.stderr,
                      flush=True)
                os.kill(os.getpid(), signal.SIGKILL)
            if f.kind == "peer_hang":
                print(f"[faults] injected peer_hang at step {step}: "
                      "wedging this process (frozen-host stand-in)",
                      file=sys.stderr, flush=True)
                import time

                while True:  # peers must contain; our watchdog may fire
                    time.sleep(3600)
            if f.kind == "device_loss":
                print(f"[faults] injected device_loss at step {step}: "
                      "reporting a lost local device, exiting 43",
                      file=sys.stderr, flush=True)
                try:
                    from ..train import telemetry

                    telemetry.emergency_dump(
                        f"device_loss@{step} (injected)")
                except Exception:
                    pass
                from ..train.resilience import EXIT_PEER

                os._exit(EXIT_PEER)
            if f.kind in ("torn_ckpt", "ckpt_ioerr"):
                from . import checkpoint as ckpt_lib

                print(f"[faults] armed {f.kind} for the next checkpoint "
                      f"write (step {step})", file=sys.stderr, flush=True)
                ckpt_lib.inject_io_fault(f.kind)
                continue
            if f.kind == "corrupt_ckpt":
                _corrupt_newest(ckpt_dir, step)
                continue
            if f.kind == "crash":
                print(f"[faults] injected crash at step {step}",
                      file=sys.stderr, flush=True)
                sys.stderr.flush()
                try:
                    # a real segfault could not do this, but the injected
                    # stand-in exercises the flight recorder's black-box
                    # contract: die WITH a postmortem for the supervisor's
                    # relaunch log to point at (train.telemetry)
                    from ..train import telemetry

                    telemetry.emergency_dump(f"crash@{step} (injected)")
                except Exception:
                    pass
                os._exit(1)
            if f.kind == "sigterm":
                print(f"[faults] injected SIGTERM at step {step}",
                      file=sys.stderr, flush=True)
                os.kill(os.getpid(), signal.SIGTERM)
                continue  # the loop's shutdown flag breaks at the NEXT step
            if f.kind == "preempt":
                # advance-notice preemption: SIGUSR1 to self, exactly the
                # signal GroupSupervisor.notify_preempt / an operator
                # would deliver — the graceful-shutdown path must answer
                # with a final checkpoint and the DECOMMISSION exit (47),
                # pricing the tail as drain instead of rollback+replay
                print(f"[faults] injected preemption notice at step "
                      f"{step} (grace {f.grace:.1f}s)", file=sys.stderr,
                      flush=True)
                from ..train import resilience as res_lib

                res_lib.write_preempt_notice(grace_s=f.grace)
                os.kill(os.getpid(), signal.SIGUSR1)
                continue  # the loop's notice flag breaks at the NEXT step
            if f.kind == "slow":
                # degrade, don't die: the straggler stand-in — per-step
                # injected host latency while the window is open
                import time

                time.sleep(f.ms / 1e3)
                continue
            # nan: multiplying by NaN keeps the leaf's placement/sharding
            # (a fresh full_like would force a reshard inside the step);
            # NaN*0 == NaN, so padded rows poison the loss sum too
            print(f"[faults] injected NaN batch at step {step}",
                  file=sys.stderr, flush=True)
            batch = dict(batch)
            if "mask" in batch:
                batch["mask"] = batch["mask"] * float("nan")
            else:  # no mask leaf: poison every float leaf directly
                import jax.numpy as jnp

                batch = {k: (v * float("nan")
                             if jnp.issubdtype(v.dtype, jnp.floating) else v)
                         for k, v in batch.items()}
        return batch
