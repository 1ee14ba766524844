"""Training resilience: anomaly policy, preemption-safe exit, supervisor.

The reference's only failure mode is "hang forever in ``comm.gather``"
(SURVEY.md §5.3).  The watchdog (``utils/watchdog.py``) already converts a
lost peer into a loud exit; this module defends the *state itself* and the
*job*:

* :func:`ops.optim.with_skip_guard` (wired by the Trainer) rejects
  non-finite / over-threshold updates inside the jitted step — a single bad
  batch can no longer poison the replicated params.
* :class:`ResilienceMonitor` is the host-side anomaly policy: it watches
  the (one-step-lagged) loss stream the train loop already fetches, and
  after ``rollback_after`` consecutive bad steps asks for a rollback to the
  last checkpoint; after ``max_rollbacks`` rollbacks it aborts with
  :class:`AnomalyAbort` (exit code :data:`EXIT_ANOMALY`).
* :class:`GracefulShutdown` turns SIGTERM/SIGINT (TPU preemption, scheduler
  eviction) into a flag the step loop checks at the next boundary: final
  checkpoint, exit 0 — an external restart loses at most one step.
* :func:`supervise` is the crash-restart supervisor: relaunch on crash with
  exponential backoff and bounded restarts, interpreting the exit-code
  contract below to decide retry-vs-stop.

* :class:`SDCPolicy` is the silent-data-corruption strike ledger
  (DESIGN.md §9): the trainer's fingerprint monitor charges each
  transient, healed divergence to the device (or peer host) it was
  localized to; a device exceeding the strike budget — or a divergence
  the replay triage proves DETERMINISTIC — raises :class:`SDCAbort`
  (exit code :data:`EXIT_SDC`, no retry: a relaunch would replay a
  software bug, and a chip past its strike budget needs draining, not
  another restart).

* Elastic degraded-capacity restart (DESIGN.md §10): with
  ``elastic=True``, :func:`supervise` reacts to REPEATED peer-loss exits
  (43, and hangs-after-peer-loss 42) by probing the surviving topology
  (bounded — ``parallel.mesh.probe_world`` or :func:`default_probe`) and
  relaunching the child at the probed, shrunken world instead of looping
  forever through a ``world_setup`` that can never re-form the old one.
  When the probe finds fewer than ``min_devices``, the supervisor parks
  and re-polls with backoff until either capacity returns or the restart
  budget runs out, then exits :data:`EXIT_CAPACITY` (46, no-retry).

Exit-code contract (also consumed by ``tools/supervise.py``):

===========  ============================================  =========
code         meaning                                       supervisor
===========  ============================================  =========
0            run completed (or exited cleanly on SIGTERM)  stop
42           watchdog: no step progress (hang)             retry
43           peer loss: a collective raised/timed out or   retry
             world formation failed (typed, mesh errors)
44           anomaly abort: rollback budget exhausted      stop
45           SDC abort: deterministic replica divergence   stop
             or per-device strike budget exhausted
46           capacity abort: healthy devices stayed below  stop
             --min_devices for the whole restart budget
47           intentional decommission: the autopilot (or   stop
             an operator) drained and retired this child
             on purpose — relaunching would undo the
             scale-in, and the exit must not burn the
             restart budget
other        crash (segfault, OOM, fault injection, ...)   retry
===========  ============================================  =========
"""

from __future__ import annotations

import math
import os
import random
import signal
import subprocess
import sys
import time
from typing import Callable, List, Optional, Sequence, Tuple

EXIT_OK = 0
EXIT_HANG = 42      # utils.watchdog.HangWatchdog
EXIT_PEER = 43      # a collective raised/timed out, or world formation
                    # failed (parallel.mesh typed errors)
EXIT_ANOMALY = 44   # ResilienceMonitor exhausted its rollback budget
EXIT_SDC = 45       # deterministic replica divergence / SDC strike budget
EXIT_CAPACITY = 46  # healthy capacity stayed below --min_devices
EXIT_DECOMMISSION = 47  # intentional decommission: drained + retired on
                        # purpose (serve.autopilot scale-in / rollout)

# exit codes the supervisor must NOT retry: 0 is success; 44 and 45 are
# deterministic training failures that a relaunch would only replay; 46
# means the hardware floor cannot be met — relaunching cannot create
# chips; 47 is a decommission the control plane ASKED for — a relaunch
# would undo the scale-in and burn budget on a healthy exit
_NO_RETRY = (EXIT_OK, EXIT_ANOMALY, EXIT_SDC, EXIT_CAPACITY,
             EXIT_DECOMMISSION)

# exit codes that count toward the elastic peer-loss streak: explicit
# peer loss, and hangs (a dead peer often presents as a stalled
# collective killed by the watchdog/heartbeat monitor, exit 42)
_PEER_LOSS_CODES = (EXIT_PEER, EXIT_HANG)


class AnomalyAbort(RuntimeError):
    """Training diverged past the rollback budget; maps to exit 44."""


class CapacityAbort(RuntimeError):
    """The healthy world is smaller than ``--min_devices`` and cannot be
    relaunched into compliance; maps to exit 46 — the supervisor does not
    retry (a relaunch cannot create chips; an operator/autoscaler must)."""


# substrings that mark a raised exception as peer/transport loss — the
# failure class the CLI converts to EXIT_PEER so (a) the supervisor's
# exit-code contract sees 43 instead of an anonymous crash and (b) the
# elastic streak counts it.  Name-based plus message-based: the concrete
# types (XlaRuntimeError, gloo's RuntimeError) live in jaxlib and vary by
# version, and this module must not import them.
_PEER_ERROR_TYPES = ("XlaRuntimeError", "CollectiveTimeout",
                     "WorldFormationError", "CoordinatorUnreachable",
                     "PeerMissing")
# multi-word / suffixed phrases only: a bare "peer"/"connection"/
# "unavailable" would misread ordinary crashes (a FileNotFoundError whose
# path contains "peer", a "CUDA unavailable" backend error) as peer loss
# and burn the restart budget — or worse, the elastic shrink streak — on
# a bug a relaunch can never fix
_PEER_ERROR_MARKERS = ("gloo", "all-reduce", "allreduce",
                       "broken pipe", "connection reset",
                       "connection refused", "connection closed",
                       "closed by peer", "lost peer", "connect failed",
                       "failed to connect", "recv failure", "recv error",
                       "deadline exceeded", "unavailable:",
                       "socket closed", "socket timeout",
                       "barrier timed out", "heartbeat timed out",
                       "coordinator unreachable", "peer down")
# ...and statuses that are NEVER transport, checked first: an OOM also
# arrives as XlaRuntimeError, and reading it as peer loss feeds the
# elastic shrink streak — where the default global-batch policy then
# GROWS per-device rows, making the relaunch OOM harder, in a loop
_NON_PEER_MARKERS = ("resource_exhausted", "out of memory",
                     "out-of-memory", "invalid_argument",
                     "failed_precondition", "permission_denied")


def is_peer_error(exc: BaseException) -> bool:
    """Does this exception look like a lost/unreachable peer rather than
    a software crash?  Used by the CLI to map an escaped collective/
    world-formation failure to exit 43.  Deliberately biased toward
    classifying AS peer loss: both classes are retried, and the only
    behavioral difference is that 43 counts toward the elastic
    probe-and-shrink streak — the correct reaction to a repeated
    ambiguous transport failure anyway.  Non-transport statuses
    (RESOURCE_EXHAUSTED, INVALID_ARGUMENT, ...) beat the type match:
    they name a deterministic local failure even when the carrier type
    is the same XlaRuntimeError a dead peer raises."""
    msg = str(exc).lower()
    if any(m in msg for m in _NON_PEER_MARKERS):
        return False
    for klass in type(exc).__mro__:
        if klass.__name__ in _PEER_ERROR_TYPES:
            return True
    return any(m in msg for m in _PEER_ERROR_MARKERS)


class SDCAbort(RuntimeError):
    """Silent data corruption the run must not survive: the replay triage
    proved the divergence deterministic (a software bug a relaunch would
    replay), or one device blew its transient-strike budget (hardware
    that needs draining).  Maps to exit 45 — the supervisor does not
    retry."""


class SDCPolicy:
    """Per-device strike ledger for TRANSIENT (replay-clean, healed)
    divergences.  ``record(devices)`` charges one strike to each named
    device and returns the devices now over budget (empty == keep going).
    One flaky step is weather; the same chip diverging ``strikes`` times
    is a failing part."""

    def __init__(self, strikes: int = 3):
        if strikes < 1:
            raise ValueError(f"sdc strike budget must be >= 1, got "
                             f"{strikes}")
        self.strikes = strikes
        self.counts: dict = {}
        self.incidents = 0   # fingerprint mismatches observed
        self.healed = 0      # transient incidents healed in-process

    def record(self, devices: Sequence[str]) -> List[str]:
        self.incidents += 1
        for d in devices:
            self.counts[d] = self.counts.get(d, 0) + 1
        return [d for d in devices if self.counts[d] >= self.strikes]


class ResilienceMonitor:
    """Host-side anomaly policy over the step-loss stream.

    A step is *bad* when its loss is non-finite, or — with
    ``spike_factor > 0`` — exceeds ``spike_factor`` times the exponential
    moving average of recent good losses (the EMA warms up over
    ``warmup`` good steps before spike detection arms, so the noisy first
    steps of a fresh init cannot trip it).

    ``observe`` returns ``"ok"``, ``"bad"`` (bad, under the consecutive
    threshold), ``"rollback"`` (restore the last checkpoint and keep
    going) or ``"abort"`` (rollback budget exhausted — raise
    :class:`AnomalyAbort`).  A rollback resets the EMA: the restored
    params re-warm it.
    """

    def __init__(self, rollback_after: int, max_rollbacks: int = 2,
                 spike_factor: float = 0.0, ema_beta: float = 0.9,
                 warmup: int = 5):
        if rollback_after < 1:
            raise ValueError(f"rollback_after must be >= 1, got "
                             f"{rollback_after}")
        self.rollback_after = rollback_after
        self.max_rollbacks = max_rollbacks
        self.spike_factor = spike_factor
        self.ema_beta = ema_beta
        self.warmup = warmup
        self.consecutive = 0   # bad steps since the last good one
        self.rollbacks = 0     # rollbacks performed so far
        self.bad_steps = 0     # total bad steps observed
        self._ema: Optional[float] = None
        self._n_good = 0

    def observe(self, loss: float) -> str:
        bad = not math.isfinite(loss)
        if (not bad and self.spike_factor > 0 and self._ema is not None
                and self._n_good >= self.warmup):
            bad = loss > self.spike_factor * max(self._ema, 1e-12)
        if not bad:
            self.consecutive = 0
            self._ema = (loss if self._ema is None
                         else self.ema_beta * self._ema
                         + (1.0 - self.ema_beta) * loss)
            self._n_good += 1
            return "ok"
        self.bad_steps += 1
        self.consecutive += 1
        if self.consecutive < self.rollback_after:
            return "bad"
        self.consecutive = 0
        if self.rollbacks >= self.max_rollbacks:
            return "abort"
        self.rollbacks += 1
        self._ema = None
        self._n_good = 0
        return "rollback"

    def stats(self) -> dict:
        return {"bad_steps": self.bad_steps, "rollbacks": self.rollbacks}


class GracefulShutdown:
    """SIGTERM/SIGINT -> a flag the step loop polls at dispatch boundaries.

    ``with GracefulShutdown() as stop:`` installs handlers (previous
    handlers are restored on exit); ``stop.requested`` turns True on the
    first signal.  A second TERMINATION signal falls through to the
    previous handler semantics via a hard re-raise — so an operator's
    double-Ctrl-C still kills a wedged run.  Signal handlers only exist on
    the main thread; elsewhere the context is an inert no-op (trainers
    driven from worker threads keep working, without preemption safety).

    ``notice_signals`` (default SIGUSR1, :data:`PREEMPT_SIGNAL`) are the
    ADVANCE-NOTICE channel: a cloud maintenance event or the supervisor's
    :meth:`GroupSupervisor.notify_preempt` announces the preemption
    ``grace_s`` seconds before the platform would hard-kill.  A notice
    sets ``requested`` (same dispatch-boundary checkpoint path) plus
    ``noticed``, and reads the grace window from the notice file
    (:func:`read_preempt_notice`) or :data:`PREEMPT_GRACE_ENV`.  The
    owner exits :data:`EXIT_DECOMMISSION` instead of 0 — terminal at the
    supervisor, priced as ``drain`` by the goodput ledger — because the
    capacity is GOING AWAY: a relaunch would land on a doomed node, and
    "job finished" would be a lie.  Notices are idempotent (a repeated
    SIGUSR1 never escalates to a kill)."""

    def __init__(self, signals: Sequence[int] = (signal.SIGTERM,
                                                 signal.SIGINT),
                 notice_signals: Sequence[int] = (signal.SIGUSR1,)):
        self._signals = tuple(signals) + tuple(
            s for s in notice_signals if s not in signals)
        self._notice = frozenset(notice_signals)
        self._previous: dict = {}
        self.requested = False
        self.noticed = False
        self.grace_s: Optional[float] = None
        self.signum: Optional[int] = None
        self._escalated = False

    def _handler(self, signum, frame):
        if signum in self._notice:
            first = not self.noticed
            self.noticed = True
            self.requested = True
            if self.signum is None:
                self.signum = signum
            if first:
                rec = read_preempt_notice() or {}
                try:
                    self.grace_s = float(
                        rec.get("grace_s")
                        or os.environ.get(PREEMPT_GRACE_ENV) or 2.0)
                except (TypeError, ValueError):
                    self.grace_s = 2.0
                print(f"[resilience] preemption notice (signal {signum}, "
                      f"grace {self.grace_s:.1f}s): finishing the current "
                      "step, writing a final checkpoint, exiting "
                      f"{EXIT_DECOMMISSION} (decommission)",
                      file=sys.stderr, flush=True)
            return
        if self._escalated:
            # second termination signal: restore + re-raise so the
            # default/previous disposition (usually: die now) takes over
            prev = self._previous.get(signum, signal.SIG_DFL)
            signal.signal(signum, prev)
            signal.raise_signal(signum)
            return
        self._escalated = True
        self.requested = True
        self.signum = signum
        print(f"[resilience] caught signal {signum}: finishing the current "
              "step, writing a final checkpoint, exiting 0", file=sys.stderr,
              flush=True)

    def __enter__(self) -> "GracefulShutdown":
        for s in self._signals:
            try:
                self._previous[s] = signal.signal(s, self._handler)
            except ValueError:  # not the main thread: no handlers, no-op
                self._previous.pop(s, None)
                break
        return self

    def __exit__(self, *exc) -> None:
        for s, prev in self._previous.items():
            try:
                signal.signal(s, prev)
            except ValueError:
                pass
        self._previous.clear()


# ---------------------------------------------------------------------------
# the advance-notice preemption channel (PR 18)
# ---------------------------------------------------------------------------
# Real platforms announce most capacity loss: a maintenance event or spot
# preemption arrives with a grace window before the hard kill.  The seam
# is deliberately dumb — a signal plus an optional notice file — so the
# injected twin (utils/faults.py kind ``preempt``) and the real thing
# (an operator or node agent running ``kill -USR1``) are byte-identical
# from the victim's point of view.

PREEMPT_SIGNAL = signal.SIGUSR1
# where the machine-readable half of the notice lands (JSON: t_unix,
# grace_s); a supervisor stamps this into the child env so both ends
# agree on the path
PREEMPT_NOTICE_ENV = "NNPT_PREEMPT_NOTICE"
# fallback grace window (seconds) when the signal arrives with no file
PREEMPT_GRACE_ENV = "NNPT_PREEMPT_GRACE_S"


def preempt_notice_path(env: Optional[dict] = None) -> Optional[str]:
    return (env if env is not None else os.environ).get(PREEMPT_NOTICE_ENV)


def write_preempt_notice(path: Optional[str] = None, *,
                         grace_s: float = 2.0) -> Optional[str]:
    """Write the notice file (``{"t_unix", "grace_s"}``) — the sender's
    half of the advance-notice channel.  ``path`` defaults to this
    process's own :data:`PREEMPT_NOTICE_ENV`; best-effort and silent when
    no path is configured (the signal alone still carries the notice,
    with :data:`PREEMPT_GRACE_ENV` / the 2 s default as the window)."""
    import json

    path = path or preempt_notice_path()
    if not path:
        return None
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps({"t_unix": round(time.time(), 3),
                                "grace_s": float(grace_s)}) + "\n")
        os.replace(tmp, path)
    except OSError:
        return None
    return path


def read_preempt_notice(path: Optional[str] = None) -> Optional[dict]:
    """The receiver's half: parse the notice file, or None when absent /
    unreadable (a signal with no file is still a valid notice)."""
    import json

    path = path or preempt_notice_path()
    if not path:
        return None
    try:
        with open(path) as f:
            rec = json.loads(f.read())
        return rec if isinstance(rec, dict) else None
    except (OSError, ValueError):
        return None


def strip_supervisor_flags(argv: Sequence[str]) -> List[str]:
    """Remove the supervisor-only flags (``--supervise [N]``,
    ``--supervise_backoff [S]``, ``--supervise_backoff_max [S]``) from an
    argv so the supervised child runs the plain training entrypoint
    (handles both ``--flag value`` and ``--flag=value`` forms).  The
    elastic flags (``--elastic``, ``--min_devices``) deliberately STAY:
    the child enforces the capacity floor itself (exit 46) even when its
    supervisor is a dumb generic wrapper."""
    flags = ("--supervise", "--supervise_backoff", "--supervise_backoff_max")
    out: List[str] = []
    skip = False
    for tok in argv:
        if skip:
            skip = False
            continue
        if tok in flags:
            skip = True
            continue
        if any(tok.startswith(f + "=") for f in flags):
            continue
        out.append(tok)
    return out


# world-configuration env keys the degraded relaunch rewrites (mirrors
# parallel/mesh.py's channel; duplicated as STRINGS so this module stays
# importable on jax-less ops hosts)
_COORD_ENV_KEYS = ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS")
_NUM_PROCESSES_ENV = "NNPT_NUM_PROCESSES"
_PROCESS_ID_ENV = "NNPT_PROCESS_ID"
DEGRADED_ENV = "NNPT_ELASTIC_DEGRADED"  # marks a shrunken-world child
# trace correlation channel (train/trace.py; duplicated as strings so
# this module stays importable on jax-less ops hosts): the supervisor
# stamps every child with ONE job-stable run id and its attempt number,
# so tools/trace_report.py can merge the per-incarnation trace files of
# a crashed-and-relaunched run onto one timeline
RUN_ID_ENV = "NNPT_RUN_ID"
INCARNATION_ENV = "NNPT_INCARNATION"


def _append_event(path: Optional[str], rec: dict) -> None:
    """Append one supervisor lifecycle record to the ``events_path``
    JSONL (launch / exit / hang_kill / relaunch / stopped / gave_up).
    This is the goodput layer's join key for inter-incarnation time:
    ``utils/goodput.py`` prices the gap between an exit event and the
    next incarnation's first span as ``relaunch_gap`` (or ``drain`` on
    a terminal exit 47).  Best-effort: accounting must never take down
    the supervisor."""
    if not path:
        return
    import json

    try:
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
    except OSError:
        pass


def degrade_env(env: dict, probe: dict) -> dict:
    """Rewrite a child environment to the probed (shrunken) world: the
    coordinator rendezvous is dropped entirely and the child forms a
    single-process local world.  Returns the same dict, mutated.

    Only collapse-to-single-process is supported — every shipped probe
    (``probe_world``'s local fallback, :func:`default_probe`) reports
    ``n_processes=1`` when degraded; a degraded but still-multi-process
    world would need surviving-rank reassignment no local probe can
    answer (which rank dropped out?), so that case raises instead of
    relaunching a child with a stale, possibly out-of-range
    ``NNPT_PROCESS_ID``."""
    n_proc = int(probe.get("n_processes", 1))
    if n_proc > 1:
        raise ValueError(
            "degraded multi-process worlds are unsupported (probe "
            f"reported n_processes={n_proc}): surviving peer ranks "
            "cannot be reassigned from a local probe")
    for k in _COORD_ENV_KEYS:
        env.pop(k, None)
    env[_NUM_PROCESSES_ENV] = "1"
    env[_PROCESS_ID_ENV] = "0"
    env[DEGRADED_ENV] = str(int(probe.get("n_devices", 0)))
    return env


_PROBE_LOCAL_SRC = (
    "import jax, json; print('PROBE_WORLD|' + json.dumps("
    "{'n_processes': jax.process_count(), "
    "'n_devices': jax.device_count(), "
    "'local_devices': jax.local_device_count()}))"
)


def default_probe(timeout_s: float = 60.0,
                  env: Optional[dict] = None) -> Optional[dict]:
    """LOCAL capacity probe for the generic supervisor: a subprocess (jax
    only there — this module stays importable without it) reports this
    host's healthy device count under a hard timeout.  Coordinator env
    keys are stripped so the probe can never block on a dead rendezvous;
    the coordinator-aware probe is ``parallel.mesh.probe_world`` (the
    integrated CLI wires that one).  Returns the probe dict or None.

    A local probe of a formerly-multi-process world is by definition a
    DEGRADED view (mirroring ``probe_world``'s ``degraded =
    bool(coordinator_address)``): it reports ``degraded=True`` whenever
    the environment had configured a bigger world, so the supervisor's
    elastic path actually rewrites the child env instead of looping the
    dead rendezvous forever."""
    import os

    env = dict(os.environ if env is None else env)
    had_world = (any(k in env for k in _COORD_ENV_KEYS)
                 or int(env.get(_NUM_PROCESSES_ENV) or 1) > 1)
    for k in _COORD_ENV_KEYS:
        env.pop(k, None)
    try:
        out = subprocess.run([sys.executable, "-c", _PROBE_LOCAL_SRC],
                             capture_output=True, text=True, env=env,
                             timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None
    for line in out.stdout.splitlines():
        if line.startswith("PROBE_WORLD|"):
            import json

            res = json.loads(line.split("|", 1)[1])
            res["degraded"] = had_world
            return res
    return None


def heartbeat_filename(role: str, process_id: Optional[int] = None
                       ) -> str:
    """Per-role/per-process heartbeat file name:
    ``heartbeat-<role>-p<P>.json`` (see ``train.telemetry``'s module
    docstring for the collision this naming fixes).  Lives HERE,
    stdlib-only, so the supervisor can derive its child's exact watch
    target without importing the jax-heavy telemetry module;
    ``process_id`` defaults to the DESIGN §10 world env channel."""
    import os

    if process_id is None:
        try:
            process_id = int(os.environ.get(_PROCESS_ID_ENV) or 0)
        except ValueError:
            process_id = 0
    return f"heartbeat-{role}-p{int(process_id)}.json"


def find_heartbeats(dirpath: str) -> List[str]:
    """Every heartbeat file in a telemetry dir: the legacy shared
    ``heartbeat.json`` plus the per-role/process
    ``heartbeat-<role>-p<P>.json`` forms ``train.telemetry`` writes
    since the fleet observability plane (two programs sharing one dir
    used to last-writer-win over one file)."""
    import glob
    import os

    return sorted(glob.glob(os.path.join(dirpath, "heartbeat*.json")))


def heartbeat_age_s(path: str, now: Optional[float] = None
                    ) -> Optional[float]:
    """Seconds since the telemetry heartbeat was last refreshed
    (mtime-based: train.telemetry's atomic replace bumps it on every
    write), or None if absent.  ``path`` may be an exact heartbeat
    file, a telemetry DIRECTORY (freshest of all heartbeats within), or
    the legacy GENERIC ``<dir>/heartbeat.json`` — only that generic
    name falls back to the freshest ``heartbeat*.json`` sibling, so a
    supervisor configured against the pre-fleet layout keeps watching a
    child that writes the per-role name.  A missing ROLE-QUALIFIED
    path deliberately does NOT fall back: the external hang monitor
    must watch its own child's file, and answering with a co-resident
    process's fresher heartbeat would mask exactly the hung-writer case
    the per-role naming exists to expose.  Lives HERE, stdlib-only,
    because the generic supervisor (tools/supervise.py) wraps arbitrary
    commands on hosts that may not even have JAX installed — it must
    never pull in the jax-importing telemetry module; telemetry
    re-exports this."""
    import os

    candidates = [path]
    if os.path.isdir(path):
        candidates = find_heartbeats(path)
    elif (not os.path.exists(path)
          and os.path.basename(path) == "heartbeat.json"):
        candidates = find_heartbeats(os.path.dirname(path) or ".")
    best: Optional[float] = None
    for p in candidates:
        try:
            mtime = os.stat(p).st_mtime
        except OSError:
            continue
        best = mtime if best is None else max(best, mtime)
    if best is None:
        return None
    return max(0.0, (time.time() if now is None else now) - best)


_ckpt_manifest_mod = None


def _ckpt_manifest():
    """utils/ckpt_manifest.py loaded BY FILE PATH (cached) — the regular
    relative import would execute utils/__init__, whose prng/logging pull
    jax; this module stays importable on the jax-less ops hosts the
    generic supervisor (tools/supervise.py) is meant for, same trick as
    tools/ckpt_fsck.py."""
    global _ckpt_manifest_mod
    if _ckpt_manifest_mod is None:
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "utils", "ckpt_manifest.py")
        spec = importlib.util.spec_from_file_location(
            "_nnpt_ckpt_manifest", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _ckpt_manifest_mod = mod
    return _ckpt_manifest_mod


def _restore_target(ckpt_dir: str):
    """(step, n_bad, path): newest snapshot passing FULL manifest
    verification, plus how many NEWER generations fail it — exactly the
    set the child's restore will quarantine on its way down the chain.
    Walks newest-first and stops hashing at the first verified generation
    (restore's own discipline: with multi-GB snapshots, sha256ing every
    older generation would add minutes of supervisor downtime per
    relaunch for one log line).  The verification itself is
    utils.ckpt_manifest — stdlib-only, same logic tools/ckpt_fsck.py runs
    — so the supervisor reports what a relaunch will actually resume
    from, not what merely exists on disk."""
    cm = _ckpt_manifest()
    bad = 0
    for step, path in reversed(cm.snapshot_steps(ckpt_dir)):
        if cm.verify(path):
            bad += 1
        else:
            return step, bad, path
    return None, bad, None


def alerts_between(path: Optional[str], start_pos: int
                   ) -> Tuple[List[dict], int]:
    """``kind="alert"`` records appended to a metrics JSONL past byte
    ``start_pos`` (the supervisor remembers the size before each launch,
    so the scan covers exactly one child's lifetime), plus the new end
    position.  Stdlib-only and bounded: reads only the appended tail.
    A file that SHRANK (fresh dir reused) rescans from 0."""
    import os

    if not path:
        return [], start_pos
    try:
        size = os.path.getsize(path)
    except OSError:
        return [], start_pos
    if size < start_pos:
        start_pos = 0
    if size == start_pos:
        return [], size
    out: List[dict] = []
    try:
        with open(path) as f:
            f.seek(start_pos)
            for line in f:
                line = line.strip()
                if not line or '"alert"' not in line:
                    continue
                try:
                    import json

                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail line of a live run
                if isinstance(rec, dict) and rec.get("kind") == "alert":
                    out.append(rec)
    except OSError:
        return [], start_pos
    return out, size


def _run_child(cmd: Sequence[str], env: Optional[dict],
               heartbeat_path: Optional[str], heartbeat_timeout: float,
               log: Callable[[str], None],
               forward_signals: Sequence[int] = ()) -> int:
    """One child launch.  Without a heartbeat watch (or signals to
    forward) this is a plain blocking call.  With a heartbeat, the
    supervisor polls the telemetry ``heartbeat.json`` (train.telemetry
    writes it atomically per dispatch) and a child whose heartbeat goes
    stale is killed and reported as :data:`EXIT_HANG` — the EXTERNAL
    complement to the in-process ``utils.watchdog.HangWatchdog``,
    covering the failure mode where the whole host process (watchdog
    thread included) is frozen.

    ``forward_signals`` (the advance-notice seam): while the child runs,
    each listed signal delivered to the SUPERVISOR is re-sent to the
    child — a platform's preemption notice usually lands on the
    top-level pid, and the doomed child is the one that must checkpoint.

    The monitor ARMS at the child's first heartbeat write (mtime newer
    than the launch) — the same discipline as the in-process watchdog's
    first-``pat()`` arming: the first step's XLA/Mosaic compile can take
    arbitrarily long and must never be killed as a hang, and a leftover
    heartbeat from a previous run must not count either.  The symmetric
    cost: a child frozen BEFORE its first dispatch is not caught by this
    monitor (nor by the in-process one)."""
    hb = bool(heartbeat_path and heartbeat_timeout > 0)
    if not hb and not forward_signals:
        return subprocess.call(list(cmd), env=env)
    child = subprocess.Popen(list(cmd), env=env)
    restore: dict = {}

    def _forward(signum, frame):
        log(f"[supervise] forwarding signal {signum} (preemption "
            f"notice) to child {child.pid}")
        try:
            child.send_signal(signum)
        except OSError:
            pass

    for s in forward_signals:
        try:
            restore[s] = signal.signal(s, _forward)
        except ValueError:   # not the main thread: no forwarding
            break
    try:
        started = time.time()
        poll_s = (max(0.05, min(heartbeat_timeout / 4.0, 5.0))
                  if hb else 0.1)
        armed = False
        while True:
            rc = child.poll()
            if rc is not None:
                return rc
            if not hb:
                time.sleep(poll_s)
                continue
            age = heartbeat_age_s(heartbeat_path)
            if not armed:
                # arm only once THIS child has written the heartbeat
                # (mtime after launch <=> age < runtime)
                if age is not None and age < time.time() - started:
                    armed = True
                else:
                    time.sleep(poll_s)
                    continue
            idle = age if age is not None else time.time() - started
            if idle > heartbeat_timeout:
                log(f"[supervise] heartbeat stale for {idle:.0f}s "
                    f"(> {heartbeat_timeout:.0f}s): killing child "
                    f"{child.pid} as hung")
                child.terminate()
                try:
                    child.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    child.kill()
                    child.wait()
                # deliberately EXIT_HANG even when the SIGTERM was
                # absorbed gracefully (the child checkpoints and exits
                # 0): that 0 means "clean final snapshot", NOT "job
                # finished" — a stalled-but-signal-responsive child must
                # be retried, not reported complete.  A healthy tail
                # phase is protected by Telemetry.alive() beats during
                # checkpoint/eval, and a spuriously killed near-done run
                # converges in one resumed relaunch.
                return EXIT_HANG
            time.sleep(poll_s)
    finally:
        for s, prev in restore.items():
            try:
                signal.signal(s, prev)
            except ValueError:
                pass


def supervise(cmd: Sequence[str], max_restarts: int,
              backoff: float = 1.0, backoff_cap: float = 60.0,
              env: Optional[dict] = None,
              log: Callable[[str], None] = None,
              heartbeat_path: Optional[str] = None,
              heartbeat_timeout: float = 0.0,
              postmortem_path: Optional[str] = None,
              ckpt_dir: Optional[str] = None,
              alerts_path: Optional[str] = None,
              jitter: float = 0.5,
              elastic: bool = False,
              min_devices: int = 0,
              probe: Optional[Callable[[], Optional[dict]]] = None,
              elastic_after: int = 2,
              events_path: Optional[str] = None,
              forward_preempt: bool = False,
              _sleep: Callable[[float], None] = time.sleep,
              _rand: Callable[[], float] = random.random) -> int:
    """Run ``cmd`` under the crash-restart policy; return the final exit
    code.

    ``max_restarts`` bounds RELAUNCHES (the initial launch is free).  Exit
    0, 44, 45 and 46 stop immediately (see the module exit-code
    contract); anything else — watchdog 42, peer-loss 43, crashes, signal
    deaths (negative returncodes) — is retried with exponential backoff
    ``backoff * 2^k`` capped at ``backoff_cap`` seconds and multiplied by
    a uniform jitter in ``[1-jitter, 1]`` — downward only, so
    ``backoff_cap`` stays a HARD upper bound an operator can size against
    a preemption-notice window, and the spread survives at the cap (an
    upward jitter clamped to the cap re-synchronizes every host at
    exactly ``backoff_cap`` once the doubling saturates): every host of a
    pod relaunches after the same failure, and pure deterministic
    doubling would hammer a recovering coordinator with a thundering herd
    at the exact same instants.  The relaunched command is identical;
    resume-from-newest-snapshot is the child's job (``cli`` appends
    ``--resume`` when a checkpoint dir is configured).

    ``elastic``: after ``elastic_after`` CONSECUTIVE peer-loss exits
    (43/42 — a world that keeps failing to re-form), run ``probe`` (a
    bounded topology discovery, e.g. ``parallel.mesh.probe_world``;
    defaults to the local :func:`default_probe`) and relaunch at the
    probed world: a degraded probe rewrites the child's world env
    (:func:`degrade_env`) so the child forms the SMALLER world and rides
    its elastic restore path.  A probe below ``min_devices`` parks and
    re-polls with the same backoff, consuming the restart budget;
    exhausting it returns :data:`EXIT_CAPACITY` (46, no-retry).  Only
    the supervisor of the original rank 0 ever degrades: two partition
    survivors independently relaunching as single-process leaders would
    split-brain the shared checkpoint dir, so non-zero ranks are fenced
    to same-world retries.

    ``heartbeat_path`` + ``heartbeat_timeout`` arm the external hang
    detector (see :func:`_run_child`).  ``postmortem_path``: when a child
    dies abnormally and the telemetry flight recorder dumped a postmortem
    during THIS child's lifetime, the relaunch log points at it.
    ``alerts_path`` (the child's metrics.jsonl): ``kind="alert"``
    records the child emitted during its lifetime — SLO burn-rate, EMA
    z-score anomalies — are summarized next to each exit, so the
    relaunch log shows what the telemetry plane SAW before the death.
    Observe-and-annotate only: alerts never change the retry decision
    (the exit-code contract owns that).
    ``ckpt_dir``: before each relaunch, log the newest VERIFIED snapshot
    (full manifest-checksum pass, utils.ckpt_manifest) the child's
    ``--resume`` will land on — so an operator tailing the supervisor sees
    immediately whether a crash mid-checkpoint cost a generation.
    ``events_path``: append machine-readable lifecycle records (launch /
    exit / relaunch, with wall-clock, run id, incarnation, rc) as JSONL —
    the supervisor half of the goodput join (``utils/goodput.py``).
    ``forward_preempt``: re-send :data:`PREEMPT_SIGNAL` (SIGUSR1) to the
    running child — a platform's advance notice lands on the top-level
    supervisor pid, and the child is the process that must answer with a
    final checkpoint + exit 47 (the :class:`GracefulShutdown` notice
    path, priced as ``drain`` instead of rollback+replay).
    """
    if log is None:
        log = lambda m: print(m, file=sys.stderr, flush=True)

    def next_delay(restarts_used: int) -> float:
        d = min(backoff * (2.0 ** restarts_used), backoff_cap)
        if jitter > 0:
            d *= 1.0 - jitter * _rand()
        return d

    attempt = 0
    peer_streak = 0
    child_env = dict(env) if env is not None else None
    # run identity for the trace channel: one run_id for the whole
    # supervised job (inherited when the operator set it — e.g. shared
    # across a multi-host world like COORDINATOR_ADDRESS — else
    # generated once here), plus the attempt number as the incarnation
    import os as _os

    _base = env if env is not None else _os.environ
    run_id = _base.get(RUN_ID_ENV) or (
        f"run-{int(time.time())}-{_os.getpid()}")
    # original world configuration, for grow-back: a degraded relaunch
    # rewrites child_env, and a LATER probe that finds the full world
    # healthy again must restore these keys — otherwise the child keeps
    # forming the small world while the log reports the full topology
    _world_keys = _COORD_ENV_KEYS + (_NUM_PROCESSES_ENV, _PROCESS_ID_ENV)
    orig_world = {k: (env if env is not None else _os.environ).get(k)
                  for k in _world_keys}
    while True:
        attempt += 1
        if child_env is None:
            child_env = dict(_os.environ)
        child_env[RUN_ID_ENV] = run_id
        child_env[INCARNATION_ENV] = str(attempt - 1)
        if not child_env.get(PREEMPT_NOTICE_ENV):
            # give the notice file somewhere to land: without a path the
            # signal still arrives but the grace window degrades to the
            # 2 s default — an in-child fault injection or an operator's
            # write_preempt_notice() must agree with the child on where
            import tempfile as _tempfile
            child_env[PREEMPT_NOTICE_ENV] = _os.path.join(
                _tempfile.gettempdir(),
                f"nnpt-preempt-{_os.getpid()}.json")
        log(f"[supervise] attempt {attempt}: {' '.join(cmd)}")
        launched = time.time()
        _append_event(events_path, {
            "kind": "supervisor", "event": "launch",
            "t": round(launched, 6), "run": run_id, "inc": attempt - 1})
        alert_pos = 0
        if alerts_path:
            try:
                alert_pos = _os.path.getsize(alerts_path)
            except OSError:
                alert_pos = 0
        rc = _run_child(cmd, child_env, heartbeat_path, heartbeat_timeout,
                        log, forward_signals=((PREEMPT_SIGNAL,)
                                              if forward_preempt else ()))
        _append_event(events_path, {
            "kind": "supervisor", "event": "exit",
            "t": round(time.time(), 6), "run": run_id,
            "inc": attempt - 1, "rc": rc})
        if alerts_path:
            alerts, _ = alerts_between(alerts_path, alert_pos)
            if alerts:
                by_name: dict = {}
                for a in alerts:
                    key = str(a.get("alert"))
                    by_name[key] = by_name.get(key, 0) + 1
                rendered = ", ".join(f"{k} x{v}"
                                     for k, v in sorted(by_name.items()))
                log(f"[supervise] {len(alerts)} telemetry alert(s) "
                    f"during this child: {rendered} (observe-only; the "
                    "exit code decides the relaunch)")
        # any ABNORMAL exit — including the no-retry anomaly abort (44),
        # whose dump is the flagship black-box case — gets the pointer
        if rc != EXIT_OK and postmortem_path:
            try:
                if _os.stat(postmortem_path).st_mtime >= launched - 1.0:
                    log(f"[supervise] child left a postmortem: "
                        f"{postmortem_path}")
            except OSError:
                pass
        if rc in _NO_RETRY:
            if rc == EXIT_ANOMALY:
                log("[supervise] child exited 44 (anomaly abort): "
                    "deterministic training failure — not retrying")
            elif rc == EXIT_SDC:
                log("[supervise] child exited 45 (SDC abort): "
                    "deterministic replica divergence or device strike "
                    "budget exhausted — not retrying")
            elif rc == EXIT_CAPACITY:
                log("[supervise] child exited 46 (capacity abort): the "
                    "healthy world is below --min_devices — not retrying "
                    "(a relaunch cannot create chips)")
            elif rc == EXIT_DECOMMISSION:
                log("[supervise] child exited 47 (decommission): drained "
                    "and retired on purpose — not retrying (no restart "
                    "budget burned)")
            else:
                log("[supervise] child completed (exit 0)")
            return rc
        peer_streak = peer_streak + 1 if rc in _PEER_LOSS_CODES else 0
        restarts_used = attempt - 1
        if restarts_used >= max_restarts:
            log(f"[supervise] giving up: {max_restarts} restarts exhausted "
                f"(last exit {rc})")
            return rc
        delay = next_delay(restarts_used)
        reason = {EXIT_HANG: "watchdog hang",
                  EXIT_PEER: "peer loss"}.get(rc, "crash")
        log(f"[supervise] child exit {rc} ({reason}); relaunching in "
            f"{delay:.1f}s ({restarts_used + 1}/{max_restarts})")
        _append_event(events_path, {
            "kind": "supervisor", "event": "relaunch",
            "t": round(time.time(), 6), "run": run_id,
            "inc": attempt, "delay_s": round(delay, 3), "reason": reason})
        if ckpt_dir:
            step, bad, path = _restore_target(ckpt_dir)
            if step is not None:
                cm = _ckpt_manifest()
                world = cm.world_line(cm.snapshot_meta(path))
                log(f"[supervise] relaunch resumes from verified snapshot "
                    f"step {step}"
                    + (f" [{world}]" if world else "")
                    + (f" ({bad} unverified generation(s) will be "
                       "quarantined on restore)" if bad else ""))
            else:
                cm = _ckpt_manifest()
                legacy = any(
                    (p / "meta.json").exists()
                    and not (p / cm.MANIFEST).exists()
                    for _, p in cm.snapshot_steps(ckpt_dir))
                if legacy:
                    # the child's restore REFUSES on pre-durability dirs
                    # rather than silently restarting from step 0 — say
                    # so instead of promising a from-scratch run
                    log("[supervise] no verified snapshot in "
                        f"{ckpt_dir} but pre-manifest snapshot(s) exist: "
                        "the relaunch will refuse to start — run "
                        "tools/ckpt_fsck.py --adopt to trust them")
                else:
                    log("[supervise] no verified snapshot in "
                        f"{ckpt_dir}: relaunch restarts from scratch"
                        + (f" ({bad} unverified generation(s) — "
                           "tools/ckpt_fsck.py)" if bad else ""))
        _sleep(delay)
        # ---- elastic probe-and-shrink (DESIGN.md §10) --------------------
        # only after REPEATED peer loss: one 43 can be a transient blip a
        # same-world retry absorbs; a streak means the old world cannot
        # re-form and looping the relaunch through world_setup forever is
        # the exact failure mode this policy exists to break.
        if not (elastic and peer_streak >= elastic_after):
            continue
        # split-brain fence: during a partition EVERY surviving host's
        # supervisor reaches this point, and each local probe reports a
        # degraded single-process world — if all of them relaunched as
        # process 0, two divergent leaders would interleave writes over
        # the same shared checkpoint dir.  Only the supervisor of the
        # ORIGINAL rank 0 may continue alone, and rank 0 must be
        # POSITIVELY identified: a multi-process world whose rank came
        # from some other channel (no NNPT_PROCESS_ID) fences too —
        # "every host assumes it is rank 0" is exactly the split brain.
        # The others retry at the current world until their budget runs
        # out (an operator, or the healed rank 0, owns the next move).
        orig_multi = (any(orig_world.get(k) for k in _COORD_ENV_KEYS)
                      or int(orig_world.get(_NUM_PROCESSES_ENV) or 1) > 1)
        pid_raw = orig_world.get(_PROCESS_ID_ENV)
        if orig_multi and (pid_raw is None or int(pid_raw) != 0):
            log("[supervise] elastic: original rank "
                f"{'unknown (no ' + _PROCESS_ID_ENV + ')' if pid_raw is None else pid_raw}"
                " is fenced from degraded relaunch (only a positively-"
                "identified rank 0 may continue as a shrunken world — "
                "two partition survivors must not both become single-"
                "process leaders over the same checkpoint dir); "
                "retrying at the current world")
            continue
        prober = probe if probe is not None else default_probe
        floor = max(1, int(min_devices))
        parked = False
        while True:
            res = prober()
            if res is None and not parked:
                # no topology answer and no evidence of a shortfall:
                # retrying at the current world is the conservative move
                # (the streak is kept, so the next loss re-probes)
                log("[supervise] elastic probe failed (no topology "
                    "answer); retrying at the current world")
                break
            n = int(res.get("n_devices", 0)) if res is not None else -1
            if res is not None and n >= floor:
                if res.get("degraded"):
                    try:
                        child_env = degrade_env(
                            dict(child_env if child_env is not None
                                 else _os.environ), res)
                    except ValueError as e:
                        # keep the streak (like the probe-failure path):
                        # the next peer loss re-probes immediately
                        log(f"[supervise] {e}; retrying at the current "
                            "world")
                        break
                    log(f"[supervise] topology probe: {n} healthy "
                        f"device(s) across "
                        f"{res.get('n_processes', '?')} process(es) — "
                        "relaunching at the DEGRADED world")
                else:
                    log(f"[supervise] topology probe: {n} healthy "
                        f"device(s) across "
                        f"{res.get('n_processes', '?')} process(es)")
                    if (child_env is not None
                            and DEGRADED_ENV in child_env):
                        # grow-back: the probe formed the FULL world
                        # again after a degraded relaunch — restore the
                        # original world configuration so the child
                        # actually rejoins it (the elastic restore path
                        # reshards 2->4 too)
                        for k, v in orig_world.items():
                            if v is None:
                                child_env.pop(k, None)
                            else:
                                child_env[k] = v
                        child_env.pop(DEGRADED_ENV, None)
                        log("[supervise] probe reports the full world "
                            "healthy: restoring the original topology "
                            "for the relaunch (grow-back)")
                peer_streak = 0
                break
            # capacity below the floor — or, once PARKED, a transient
            # probe failure (relaunching on it would let the child's own
            # floor check convert a known shortfall into a permanent
            # no-retry exit 46): park and re-poll with backoff,
            # consuming the restart budget so a floor that can never be
            # met terminates as a typed no-retry exit instead of an
            # infinite poll loop
            parked = True
            shown = (f"{n} healthy device(s)" if res is not None
                     else "no topology answer (probe failed)")
            attempt += 1
            restarts_used = attempt - 1
            if restarts_used >= max_restarts:
                log(f"[supervise] capacity shortfall: probe found "
                    f"{shown} < --min_devices {floor} and the "
                    f"restart budget is exhausted — exiting "
                    f"{EXIT_CAPACITY} (capacity abort)")
                return EXIT_CAPACITY
            delay = next_delay(restarts_used)
            log(f"[supervise] capacity shortfall: {shown} "
                f"< --min_devices {floor}; re-probing in {delay:.1f}s "
                f"({restarts_used + 1}/{max_restarts})")
            _sleep(delay)


# ---------------------------------------------------------------------------
# process-group supervision (DESIGN.md §11 "Serving fleet")
# ---------------------------------------------------------------------------

from dataclasses import dataclass, field as _field  # noqa: E402  (grouped
#   with the subsystem it serves; the module above predates dataclasses)


@dataclass
class ChildSpec:
    """One supervised child of a :class:`GroupSupervisor`: its command,
    role, and PER-CHILD contracts — heartbeat staleness bound, restart
    budget/backoff, and the exit codes that stop it for good.  ``spawn``
    overrides process creation (the fleet router passes a callable that
    wires stdio pipes and hands the Popen back); ``on_spawn`` fires
    after every (re)launch so the owner can re-attach to the fresh
    process."""
    name: str
    cmd: Sequence[str] = ()
    role: str = "worker"
    env: Optional[dict] = None
    heartbeat_path: Optional[str] = None
    heartbeat_timeout: float = 0.0
    max_restarts: int = 3
    backoff: float = 0.5
    backoff_cap: float = 30.0
    no_retry: Tuple[int, ...] = _NO_RETRY
    spawn: Optional[Callable] = None      # (spec, env) -> Popen-like
    on_spawn: Optional[Callable] = None   # (spec, proc, incarnation)


@dataclass
class _ChildState:
    spec: ChildSpec
    proc: Any = None
    incarnation: int = -1          # attempts - 1 (stamped into the env)
    restarts_used: int = 0
    launched_at: float = 0.0
    hb_armed: bool = False
    relaunch_at: Optional[float] = None   # pending backoff deadline
    final_rc: Optional[int] = None        # set once the child is done
    gave_up: bool = False
    retired: bool = False          # next exit is TERMINAL whatever its rc
    last_rc: Optional[int] = None  # most recent reaped rc (retire() uses
                                   # it to finalize a pending relaunch)
    events: List[dict] = _field(default_factory=list)


class GroupSupervisor:
    """Role-aware supervision of a PROCESS GROUP — the multi-child
    generalization of :func:`supervise`, which babysits exactly one
    child.  N children (serving replicas, a prefill tier, a router
    sidecar, ...) each carry their own :class:`ChildSpec` contract, and
    one failing child is relaunched with ITS backoff/budget without
    disturbing its siblings — the fleet property a serving tier needs
    (kill one replica: the others keep serving while it restarts).

    Deliberately NON-BLOCKING: :meth:`poll` reaps exits, kills
    stale-heartbeat children (reported as :data:`EXIT_HANG`, the same
    external-hang contract as :func:`_run_child`), executes due
    relaunches, and returns the events since the previous poll — so the
    owner (a fleet router pumping request traffic, a test) stays in
    control of the loop instead of parking inside a blocking
    ``supervise()`` call.  Exit-code handling is per child:
    ``spec.no_retry`` stops that child for good (``stopped`` event),
    anything else relaunches under ``backoff * 2^k`` (downward-jittered,
    capped — the :func:`supervise` policy) until ``max_restarts`` is
    spent (``gave_up``).  Every launch stamps the shared
    :data:`RUN_ID_ENV` plus the child's :data:`INCARNATION_ENV`, so
    trace/telemetry merging works exactly as under the single-child
    supervisor.  Stdlib-only, like everything else in this module."""

    def __init__(self, specs: Sequence[ChildSpec],
                 log: Optional[Callable[[str], None]] = None,
                 jitter: float = 0.5,
                 env: Optional[dict] = None,
                 events_path: Optional[str] = None,
                 _rand: Callable[[], float] = random.random,
                 now_fn: Callable[[], float] = time.time):
        import os as _os

        names = [s.name for s in specs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate child names: {names}")
        self._log = log or (lambda m: print(m, file=sys.stderr,
                                            flush=True))
        self._jitter = float(jitter)
        self._rand = _rand
        self._now = now_fn
        self._base_env = dict(env if env is not None else _os.environ)
        self.run_id = self._base_env.get(RUN_ID_ENV) or (
            f"run-{int(time.time())}-{_os.getpid()}")
        self._children = {s.name: _ChildState(spec=s) for s in specs}
        self._started = False
        # lifecycle JSONL for the goodput join (see supervise()'s
        # events_path); wall-clock stamped even under a virtual now_fn —
        # the ledger correlates against trace timestamps, which are real
        self._events_path = events_path

    def _emit_event(self, st: _ChildState, kind: str, **extra) -> None:
        spec = st.spec
        rec = {"kind": "supervisor", "event": kind,
               "t": round(time.time(), 6), "run": self.run_id,
               "child": spec.name, "role": spec.role,
               "inc": st.incarnation, **extra}
        pid = (spec.env or {}).get(_PROCESS_ID_ENV)
        if pid is not None:
            try:
                rec["p"] = int(pid)
            except (TypeError, ValueError):
                pass
        _append_event(self._events_path, rec)

    # ---- lifecycle -----------------------------------------------------
    def start(self) -> None:
        self._started = True
        for st in self._children.values():
            self._launch(st)

    def add_child(self, spec: ChildSpec) -> None:
        """Register (and, once :meth:`start` has run, immediately launch)
        a NEW child at runtime — the scale-out half of the autopilot
        contract.  Names stay unique for the supervisor's lifetime."""
        if spec.name in self._children:
            raise ValueError(f"duplicate child name: {spec.name!r}")
        st = _ChildState(spec=spec)
        self._children[spec.name] = st
        if self._started:
            self._launch(st)

    def retire(self, name: str) -> None:
        """Mark a child so its NEXT exit is terminal regardless of rc —
        no relaunch, no backoff burn.  The scale-in half of the autopilot
        contract: retire first, then ask the child to drain and exit
        (:data:`EXIT_DECOMMISSION`); if the drain stalls and the owner
        has to SIGKILL, the signal death still must not relaunch the
        replica the control plane just removed.  A retire that lands
        while a relaunch backoff is pending cancels it and finalizes the
        child at its last reaped rc."""
        st = self._children[name]
        st.retired = True
        if st.relaunch_at is not None:
            st.relaunch_at = None
            st.final_rc = st.last_rc
            self._log(f"[group] {st.spec.role}/{name}: retired while a "
                      "relaunch was pending — relaunch cancelled")
        else:
            self._log(f"[group] {st.spec.role}/{name}: retired (next "
                      "exit is terminal)")

    def notify_preempt(self, name: str, grace_s: float = 2.0) -> bool:
        """Propagate an advance preemption notice to a live child: write
        the notice file (when the child's env names one via
        :data:`PREEMPT_NOTICE_ENV`) and send :data:`PREEMPT_SIGNAL`.
        The child answers per its own contract — a trainer checkpoints
        and exits 47, a serving worker stops admitting, finishes
        in-flight work inside the grace window and exits 47 — and 47 is
        already in ``no_retry``, so the exit is terminal without an
        explicit :meth:`retire`.  Returns whether the notice was
        delivered (False: the child is already dead or unreachable —
        the crash path owns what happens next)."""
        st = self._children[name]
        if st.proc is None or st.proc.poll() is not None:
            return False
        env = dict(self._base_env)
        env.update(st.spec.env or {})
        path = env.get(PREEMPT_NOTICE_ENV)
        if path:
            write_preempt_notice(path, grace_s=grace_s)
        try:
            st.proc.send_signal(PREEMPT_SIGNAL)
        except OSError:
            return False
        self._log(f"[group] {st.spec.role}/{name}: preemption notice "
                  f"delivered (grace {float(grace_s):.1f}s)")
        self._emit_event(st, "preempt_notice",
                         grace_s=round(float(grace_s), 3))
        return True

    def remove_child(self, name: str) -> None:
        """Forget a TERMINAL child (stopped / gave up) so long-lived
        fleets don't accrue bookkeeping for every replica ever retired.
        Refuses to drop a child that could still run."""
        st = self._children[name]
        if st.final_rc is None and not st.gave_up:
            raise ValueError(f"child {name!r} is not terminal")
        del self._children[name]

    def _launch(self, st: _ChildState) -> None:
        spec = st.spec
        env = dict(self._base_env)
        if spec.env:
            env.update(spec.env)
        st.incarnation += 1
        env[RUN_ID_ENV] = self.run_id
        env[INCARNATION_ENV] = str(st.incarnation)
        if spec.spawn is not None:
            st.proc = spec.spawn(spec, env)
        else:
            st.proc = subprocess.Popen(list(spec.cmd), env=env)
        st.launched_at = self._now()
        st.hb_armed = False
        st.relaunch_at = None
        self._log(f"[group] {spec.role}/{spec.name} inc "
                  f"{st.incarnation}: pid {st.proc.pid}")
        self._emit_event(st, "launch", pid=getattr(st.proc, "pid", None))
        if spec.on_spawn is not None:
            spec.on_spawn(spec, st.proc, st.incarnation)

    def _next_delay(self, st: _ChildState) -> float:
        d = min(st.spec.backoff * (2.0 ** st.restarts_used),
                st.spec.backoff_cap)
        if self._jitter > 0:
            d *= 1.0 - self._jitter * self._rand()
        return d

    def _check_heartbeat(self, st: _ChildState) -> bool:
        """True when the child was just killed as hung (rc handled by
        the caller's reap on the next lines)."""
        spec = st.spec
        if not (spec.heartbeat_path and spec.heartbeat_timeout > 0):
            return False
        age = heartbeat_age_s(spec.heartbeat_path)
        now = self._now()
        if not st.hb_armed:
            # arm at THIS incarnation's first write (same discipline as
            # _run_child: first-compile must never be killed as a hang,
            # and a previous incarnation's file must not count)
            if age is not None and age < now - st.launched_at:
                st.hb_armed = True
            return False
        if age is not None and age > spec.heartbeat_timeout:
            self._log(f"[group] {spec.role}/{spec.name}: heartbeat "
                      f"stale for {age:.0f}s "
                      f"(> {spec.heartbeat_timeout:.0f}s): killing as "
                      "hung")
            st.proc.terminate()
            try:
                st.proc.wait(timeout=10)
            except Exception:
                st.proc.kill()
                st.proc.wait()
            return True
        return False

    def poll(self) -> List[dict]:
        """One non-blocking supervision pass; returns the events since
        the last poll: ``exit`` (rc, relaunch decision), ``hang_kill``,
        ``relaunch``, ``stopped`` (no-retry exit), ``gave_up`` (budget
        spent)."""
        events: List[dict] = []

        def ev(st: _ChildState, kind: str, **extra) -> None:
            e = {"event": kind, "child": st.spec.name,
                 "role": st.spec.role, "incarnation": st.incarnation,
                 **extra}
            st.events.append(e)
            events.append(e)
            self._emit_event(st, kind, **extra)

        now = self._now()
        for st in self._children.values():
            if st.final_rc is not None or st.gave_up:
                continue
            if st.proc is not None and st.proc.poll() is None:
                if self._check_heartbeat(st):
                    rc = st.proc.poll()
                    ev(st, "hang_kill", rc=rc)
                    # treat as EXIT_HANG for the retry contract, like
                    # _run_child: a graceful SIGTERM exit 0 here still
                    # means "stalled but signal-responsive", not done
                    self._after_exit(st, EXIT_HANG, ev)
                continue
            if st.proc is not None and st.relaunch_at is None:
                rc = st.proc.poll()
                ev(st, "exit", rc=rc)
                self._after_exit(st, rc, ev)
                continue
            if st.relaunch_at is not None and now >= st.relaunch_at:
                st.restarts_used += 1
                self._launch(st)
                ev(st, "relaunch", restarts_used=st.restarts_used,
                   max_restarts=st.spec.max_restarts)
        return events

    def _after_exit(self, st: _ChildState, rc: int, ev) -> None:
        spec = st.spec
        st.last_rc = rc
        if st.retired or rc in spec.no_retry:
            st.final_rc = rc
            ev(st, "stopped", rc=rc)
            why = ("retired" if st.retired and rc not in spec.no_retry
                   else "no-retry contract")
            self._log(f"[group] {spec.role}/{spec.name} exited {rc} "
                      f"({why}): stopped")
            return
        if st.restarts_used >= spec.max_restarts:
            st.gave_up = True
            st.final_rc = rc
            ev(st, "gave_up", rc=rc,
               max_restarts=spec.max_restarts)
            self._log(f"[group] {spec.role}/{spec.name}: "
                      f"{spec.max_restarts} restarts exhausted "
                      f"(last exit {rc}) — giving up on this child")
            return
        delay = self._next_delay(st)
        st.relaunch_at = self._now() + delay
        self._log(f"[group] {spec.role}/{spec.name} exit {rc}; "
                  f"relaunching in {delay:.1f}s "
                  f"({st.restarts_used + 1}/{spec.max_restarts}); "
                  "siblings undisturbed")

    # ---- introspection -------------------------------------------------
    def proc(self, name: str):
        return self._children[name].proc

    def incarnation(self, name: str) -> int:
        return self._children[name].incarnation

    def alive(self, name: str) -> bool:
        st = self._children[name]
        return (st.proc is not None and st.relaunch_at is None
                and st.final_rc is None and not st.gave_up
                and st.proc.poll() is None)

    def pending_relaunch(self, name: str) -> bool:
        return self._children[name].relaunch_at is not None

    def done(self, name: str) -> Optional[int]:
        """Final rc once the child will never run again, else None."""
        st = self._children[name]
        return st.final_rc if (st.final_rc is not None or st.gave_up) \
            else None

    def running(self) -> bool:
        """Any child not yet in a TERMINAL state (stopped/gave up)?  A
        child whose process has exited but whose exit has not been
        reaped by :meth:`poll` still counts — its retry decision is
        pending, so the owner must keep polling."""
        return any(st.final_rc is None and not st.gave_up
                   for st in self._children.values())

    def terminate_all(self, grace_s: float = 10.0) -> None:
        for st in self._children.values():
            st.relaunch_at = None
            if st.proc is not None and st.proc.poll() is None:
                st.proc.terminate()
        deadline = time.time() + grace_s
        for st in self._children.values():
            if st.proc is None:
                continue
            try:
                st.proc.wait(timeout=max(0.1, deadline - time.time()))
            except Exception:
                st.proc.kill()
                try:
                    st.proc.wait(timeout=5)
                except Exception:
                    pass
