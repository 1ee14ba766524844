"""Device-mesh construction and multi-host world formation.

Replaces the reference's world discovery
(``comm = MPI.COMM_WORLD; rank = comm.Get_rank(); nprocs = comm.Get_size()``,
dataParallelTraining_NN_MPI.py:61-63) and its external ``mpiexec`` launcher
(README.md:12).  On TPU:

* multi-host world formation = ``jax.distributed.initialize()`` over DCN,
* the "communicator" = a named ``jax.sharding.Mesh`` over all chips,
* "rank"/"size" = ``jax.process_index()`` / ``jax.process_count()`` at the
  host level and mesh axis coordinates at the device level.

The mesh axis order is chosen so the innermost (fastest-varying, best
ICI-locality) axes carry the most latency-sensitive collectives: tensor and
sequence parallelism innermost, data parallelism outermost (its allreduce is
bandwidth-bound and tolerant of the extra hop count).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from ..config import MeshConfig

# Env channel for explicit world configuration (the role mpiexec's rank
# arguments play for the reference).  COORDINATOR_ADDRESS /
# JAX_COORDINATOR_ADDRESS name the rendezvous; these two carry the world
# size and this process's rank when the platform does not provide them
# (e.g. the localhost gloo lane, or an elastic supervisor relaunching a
# shrunken world).  NNPT_WORLD_TIMEOUT_S overrides the formation timeout.
NUM_PROCESSES_ENV = "NNPT_NUM_PROCESSES"
PROCESS_ID_ENV = "NNPT_PROCESS_ID"
WORLD_TIMEOUT_ENV = "NNPT_WORLD_TIMEOUT_S"
PREFLIGHT_PORT_ENV = "NNPT_PREFLIGHT_PORT"    # default: coordinator port + 1
PREFLIGHT_DISABLE_ENV = "NNPT_NO_PREFLIGHT"   # any value disables


class WorldFormationError(RuntimeError):
    """World formation failed within its timeout (typed, so the
    supervisor's exit-code policy can distinguish the failure mode from a
    generic crash — the caller maps it to EXIT_PEER/43, a retryable
    peer-loss, never a silent hang)."""


class CoordinatorUnreachable(WorldFormationError):
    """A non-coordinator process could not reach the coordinator within
    the timeout: the coordinator host is down/unreachable (or the address
    is wrong).  Retrying against the same address is only useful if the
    coordinator is expected back."""


class PeerMissing(WorldFormationError):
    """The coordinator formed its endpoint but one or more peers never
    checked in within the timeout: a peer host is down.  The elastic
    supervisor reacts by probing the surviving topology and relaunching
    at the shrunken world (DESIGN.md §10)."""

# Canonical axis order, outermost first.  DCN-spanning axes must come first so
# that a multi-host mesh places the slow (DCN) hops on the outermost axis.
AXIS_ORDER: Tuple[str, ...] = ("data", "fsdp", "pipe", "expert", "seq", "tensor")


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Names of the mesh axes a strategy uses; import-friendly constants."""

    DATA: str = "data"
    FSDP: str = "fsdp"
    PIPE: str = "pipe"
    EXPERT: str = "expert"
    SEQ: str = "seq"
    TENSOR: str = "tensor"


def _world_env(coordinator_address: Optional[str],
               num_processes: Optional[int],
               process_id: Optional[int]) -> Tuple[Optional[str],
                                                   Optional[int],
                                                   Optional[int]]:
    """Resolve explicit world arguments against the env channel (explicit
    args win; the env is what a launcher — or the elastic supervisor's
    degraded relaunch — hands a child)."""
    if coordinator_address is None:
        coordinator_address = (os.environ.get("COORDINATOR_ADDRESS")
                               or os.environ.get("JAX_COORDINATOR_ADDRESS")
                               or None)
    if num_processes is None and os.environ.get(NUM_PROCESSES_ENV):
        num_processes = int(os.environ[NUM_PROCESSES_ENV])
    if process_id is None and os.environ.get(PROCESS_ID_ENV):
        process_id = int(os.environ[PROCESS_ID_ENV])
    return coordinator_address, num_processes, process_id


def _preflight_rendezvous(coordinator_address: str, num_processes: int,
                          process_id: int, timeout_s: float) -> None:
    """Bounded plain-socket rendezvous run BEFORE ``jax.distributed
    .initialize`` (DESIGN.md §10 probe protocol).

    On this jaxlib a failed initialization does not raise: XLA's
    distributed client ``LOG(FATAL)``s on its registration deadline and
    SIGABRTs the whole process — in BOTH roles — so the typed-error
    contract (and the elastic supervisor's exit-43 peer-loss streak that
    rides it) could never fire through exception mapping alone.  This
    rendezvous establishes, with an ordinary TCP socket on
    ``coordinator_port + 1`` (override: ``NNPT_PREFLIGHT_PORT``; disable:
    ``NNPT_NO_PREFLIGHT``), that every party is reachable *before* the
    fatal-on-failure native path runs:

    * the coordinator (process 0) listens and waits for every peer rank
      to check in — a rank that never arrives raises :class:`PeerMissing`
      naming the missing ranks;
    * a peer retry-connects until the deadline — no coordinator raises
      :class:`CoordinatorUnreachable`; connected-but-no-GO (some OTHER
      peer is missing, so the coordinator never released the barrier)
      raises :class:`PeerMissing`.

    A coordinator that cannot bind the preflight port retries until the
    deadline, then raises :class:`WorldFormationError` (typed, exit 43):
    silently skipping would be one-sided — the peers still require the
    rendezvous and would die :class:`CoordinatorUnreachable`, making a
    fully healthy world unformable whenever an unrelated process holds
    ``coordinator_port + 1``."""
    import socket
    import time

    host, _, port = coordinator_address.rpartition(":")
    pport = int(os.environ.get(PREFLIGHT_PORT_ENV) or int(port) + 1)
    deadline = time.monotonic() + timeout_s

    def remaining() -> float:
        return max(0.1, deadline - time.monotonic())

    if process_id == 0:
        # the bind must SUCCEED or the formation must fail TYPED: a
        # coordinator that silently skipped the rendezvous would proceed
        # while every peer keeps retry-connecting to this port and dies
        # CoordinatorUnreachable — a one-sided skip that makes a fully
        # healthy world unformable.  A busy port is usually a stale
        # listener (a previous run's probe/preflight mid-teardown), so
        # retry until the deadline before giving up.
        bind_err = None
        while True:
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                srv.bind(("", pport))
                srv.listen(num_processes + 4)
                break
            except OSError as e:
                srv.close()
                bind_err = e
                if time.monotonic() >= deadline:
                    raise WorldFormationError(
                        f"world preflight: coordinator could not bind "
                        f"the rendezvous port {pport} within "
                        f"{timeout_s:.0f}s ({bind_err}) — another "
                        "process holds it; free the port or set "
                        f"{PREFLIGHT_PORT_ENV}") from bind_err
                time.sleep(0.3)
        waiting = set(range(1, num_processes)) - {process_id}
        conns = []
        try:
            while waiting:
                srv.settimeout(remaining())
                try:
                    conn, _ = srv.accept()
                except socket.timeout:
                    raise PeerMissing(
                        f"world preflight timed out after {timeout_s:.0f}s:"
                        f" this process is the coordinator "
                        f"({coordinator_address}) and peer rank(s) "
                        f"{sorted(waiting)} of {num_processes} never "
                        "checked in — peer host down?") from None
                conns.append(conn)
                try:
                    # short per-connection budget: a real peer sends its
                    # rank immediately after connecting, so only a stray
                    # connection (port scanner, stalled client) hits this
                    # — giving it the full remaining() would starve the
                    # accept loop and convert healthy queued peers into a
                    # spurious PeerMissing
                    conn.settimeout(min(2.0, remaining()))
                    rank = int(conn.recv(64).split(b"\n")[0])
                    waiting.discard(rank)
                except (OSError, ValueError):
                    pass  # stray/garbled connection; keep waiting
            for conn in conns:
                try:
                    conn.sendall(b"GO\n")
                except OSError:
                    pass
        finally:
            for conn in conns:
                conn.close()
            srv.close()
        return
    # peer: retry-connect until the deadline, then await the GO barrier
    while True:
        try:
            conn = socket.create_connection((host or "127.0.0.1", pport),
                                            timeout=min(2.0, remaining()))
            break
        except OSError:
            if time.monotonic() >= deadline:
                raise CoordinatorUnreachable(
                    f"world preflight timed out after {timeout_s:.0f}s: "
                    f"could not reach the coordinator at "
                    f"{coordinator_address} as process {process_id} — "
                    "coordinator host down or address wrong?") from None
            time.sleep(0.3)
    try:
        conn.sendall(f"{process_id}\n".encode())
        conn.settimeout(remaining())
        try:
            go = conn.recv(8)
        except OSError:
            go = b""
        if not go.startswith(b"GO"):
            raise PeerMissing(
                f"world preflight: coordinator {coordinator_address} is "
                f"reachable but never released the barrier within "
                f"{timeout_s:.0f}s — another peer of the {num_processes}-"
                "process world is missing")
    finally:
        conn.close()


def world_setup(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    timeout_s: int = 300,
) -> Tuple[int, int]:
    """Form the multi-host world; returns (process_index, process_count).

    This is the TPU-native ``mpiexec`` + ``COMM_WORLD`` (reference :61-63):
    on Cloud TPU pods the coordinator/process info comes from the environment
    and ``jax.distributed.initialize()`` needs no arguments.  Fail-fast
    behavior (SURVEY.md §5.3): initialization that cannot form the world
    within ``timeout_s`` (env override: ``NNPT_WORLD_TIMEOUT_S``) raises a
    TYPED error instead of hanging the way a lost MPI rank hangs the
    reference's blocking collectives (:185) — :class:`PeerMissing` when
    this process is the coordinator (a peer never checked in),
    :class:`CoordinatorUnreachable` otherwise.  The CLI maps both to the
    retryable peer-loss exit (43), which is what lets the elastic
    supervisor count world-formation failures toward its probe-and-shrink
    policy (DESIGN.md §10).
    """
    if jax.distributed.is_initialized():
        return jax.process_index(), jax.process_count()
    coordinator_address, num_processes, process_id = _world_env(
        coordinator_address, num_processes, process_id)
    if os.environ.get(WORLD_TIMEOUT_ENV):
        timeout_s = int(float(os.environ[WORLD_TIMEOUT_ENV]))
    if coordinator_address:
        if (num_processes and num_processes > 1 and process_id is not None
                and not os.environ.get(PREFLIGHT_DISABLE_ENV)):
            _preflight_rendezvous(coordinator_address, num_processes,
                                  process_id, float(timeout_s))
        # a CPU multi-process world needs the gloo client for cross-host
        # collectives (device_put of a replicated sharding already runs
        # one); harmless on TPU — the option only governs the CPU backend.
        # Set only once the preflight says the world can form, and
        # reverted on failure: gloo without an initialized distributed
        # client poisons LOCAL backend init.
        old_cpu_collectives = jax.config.jax_cpu_collectives_implementation
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                initialization_timeout=timeout_s,
            )
        except WorldFormationError:
            raise
        except Exception as e:
            jax.config.update("jax_cpu_collectives_implementation",
                              old_cpu_collectives)
            # classify by role: the coordinator (process 0) waited for
            # peers that never arrived; everyone else failed to reach the
            # coordinator.  Unknown role reads as unreachable (the
            # conservative retry-against-coordinator interpretation).
            if process_id == 0:
                raise PeerMissing(
                    f"world formation timed out after {timeout_s}s: this "
                    f"process is the coordinator ({coordinator_address}) "
                    f"and one or more of the {num_processes or '?'} peers "
                    f"never checked in — peer host down? "
                    f"({type(e).__name__}: {e})") from e
            raise CoordinatorUnreachable(
                f"world formation timed out after {timeout_s}s: could not "
                f"reach the coordinator at {coordinator_address} as "
                f"process {process_id if process_id is not None else '?'} "
                f"— coordinator host down or address wrong? "
                f"({type(e).__name__}: {e})") from e
    return jax.process_index(), jax.process_count()


# Sentinel-prefixed so start-up banners on the probed image cannot corrupt
# the parse (only the PROBE_WORLD line is read).
_PROBE_WORLD_SRC = """
import json, os
import jax
addr = os.environ.get("_NNPT_PROBE_COORD") or None
n = os.environ.get("_NNPT_PROBE_NPROC") or None
pid = os.environ.get("_NNPT_PROBE_PID") or None
if addr:
    # ride world_setup, NOT a bare jax.distributed.initialize: the
    # surviving peers' relaunched children sit in the preflight
    # rendezvous on coordinator_port+1, and a probe that skips the
    # preflight can never meet them — the full world would look dead
    # (and grow-back unreachable) even with every host healthy
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh \\
        import world_setup
    world_setup(
        coordinator_address=addr,
        num_processes=int(n) if n else None,
        process_id=int(pid) if pid else None,
        timeout_s=int(float(
            os.environ.get("_NNPT_PROBE_TIMEOUT", "60"))))
print("PROBE_WORLD|" + json.dumps({
    "n_processes": jax.process_count(),
    "n_devices": jax.device_count(),
    "local_devices": jax.local_device_count()}))
"""


def probe_world(coordinator_address: Optional[str] = None,
                num_processes: Optional[int] = None,
                process_id: Optional[int] = None,
                timeout_s: float = 30.0,
                local_fallback: bool = True,
                log=None) -> Optional[dict]:
    """Discover the currently-HEALTHY topology with a bounded timeout.

    Runs world formation in a SUBPROCESS (``jax.distributed.initialize``
    is once-per-process; probing in-process would poison the caller) with
    a hard wall-clock kill, so a dead peer or coordinator can never hang
    the prober — the discovery primitive the elastic supervisor uses
    between relaunches (DESIGN.md §10).

    Returns ``{"n_processes", "n_devices", "local_devices",
    "degraded"}``:

    * full world formed -> the probed global topology, ``degraded=False``;
    * full world timed out and ``local_fallback`` -> THIS host's local
      topology alone (``n_processes=1``, ``degraded=True``) — the world
      the supervisor can relaunch at;
    * even the local probe failed -> ``None``.

    World arguments default from the same env channel ``world_setup``
    reads, so a supervisor probes exactly the world its child would form.
    """
    coordinator_address, num_processes, process_id = _world_env(
        coordinator_address, num_processes, process_id)
    if os.environ.get(WORLD_TIMEOUT_ENV):
        timeout_s = float(os.environ[WORLD_TIMEOUT_ENV])

    def attempt(with_world: bool) -> Optional[dict]:
        env = dict(os.environ)
        # the full-world probe imports THIS package (it rides
        # world_setup's preflight); the subprocess has no cwd guarantee
        pkg_root = str(Path(__file__).resolve().parents[2])
        env["PYTHONPATH"] = (pkg_root + os.pathsep + env["PYTHONPATH"]
                             if env.get("PYTHONPATH") else pkg_root)
        for k in ("_NNPT_PROBE_COORD", "_NNPT_PROBE_NPROC",
                  "_NNPT_PROBE_PID"):
            env.pop(k, None)
        if with_world and coordinator_address:
            env["_NNPT_PROBE_COORD"] = coordinator_address
            if num_processes is not None:
                env["_NNPT_PROBE_NPROC"] = str(num_processes)
            if process_id is not None:
                env["_NNPT_PROBE_PID"] = str(process_id)
            env["_NNPT_PROBE_TIMEOUT"] = str(int(timeout_s))
        try:
            # the wall timeout adds import/backend-init margin on top of
            # the formation budget, so formation gets its full budget.
            # A full-world probe runs TWO sequential bounded phases —
            # the preflight rendezvous, then jax.distributed.initialize,
            # each allowed timeout_s — so its wall is 2x: killing the
            # probe mid-initialize after a peer checked in late would
            # misread a healthy-but-slow world as dead and degrade it.
            wall = (2.0 * timeout_s if with_world and coordinator_address
                    else timeout_s) + 45.0
            out = subprocess.run([sys.executable, "-c", _PROBE_WORLD_SRC],
                                 capture_output=True, text=True, env=env,
                                 timeout=wall)
        except subprocess.TimeoutExpired:
            if log:
                log(f"[probe] world probe timed out after {timeout_s:.0f}s"
                    + (" (full world)" if with_world else " (local)"))
            return None
        for line in out.stdout.splitlines():
            if line.startswith("PROBE_WORLD|"):
                return json.loads(line.split("|", 1)[1])
        if log:
            tail = (out.stderr or out.stdout).strip().splitlines()[-1:] or [""]
            log(f"[probe] world probe rc={out.returncode}: {tail[0][:200]}")
        return None

    if coordinator_address:
        res = attempt(with_world=True)
        if res is not None:
            res["degraded"] = False
            return res
        if not local_fallback:
            return None
        if log:
            log("[probe] full world unreachable; probing local topology")
    res = attempt(with_world=False)
    if res is None:
        return None
    res["n_processes"] = 1
    res["n_devices"] = res["local_devices"]
    res["degraded"] = bool(coordinator_address)
    return res


def make_mesh(
    cfg: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_sizes: Optional[Dict[str, int]] = None,
) -> Mesh:
    """Build a named mesh over ``devices`` (default: all devices).

    Axes with size 1 are kept in the mesh (size-1 axes are free) so that
    sharding specs can always refer to every canonical axis name; this keeps
    pure-DP, DP+TP, etc. all expressible against one mesh type.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if axis_sizes is None:
        cfg = cfg or MeshConfig()
        axis_sizes = cfg.axis_sizes(n)
    shape = tuple(axis_sizes.get(name, 1) for name in AXIS_ORDER)
    total = int(np.prod(shape))
    if total != n:
        raise ValueError(f"mesh shape {dict(zip(AXIS_ORDER, shape))} needs {total} "
                         f"devices, have {n}")
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def local_mesh(n: int, platform: str = "cpu") -> Mesh:
    """A pure-DP mesh over the first ``n`` local devices — the moral
    equivalent of ``mpiexec -n N`` on a laptop (reference README.md:10-12).

    For CI, combine with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (SURVEY.md §4) so N fake CPU devices stand in for N chips.
    """
    devices = jax.devices(platform) if platform else jax.devices()
    if len(devices) < n:
        raise ValueError(f"need {n} {platform} devices, have {len(devices)}")
    return make_mesh(MeshConfig(data=n), devices=devices[:n])


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return int(mesh.shape[axis])


def describe(mesh: Mesh) -> str:
    return " ".join(f"{k}={v}" for k, v in mesh.shape.items() if v > 1) or "single-device"
