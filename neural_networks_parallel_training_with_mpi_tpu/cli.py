"""CLI entrypoint.

Usage (the TPU-native analogue of the reference's
``mpiexec -n numprocs python dataParallelTraining_NN_MPI.py --lr --momentum
--batch_size --nepochs``, README.md:12):

    python -m neural_networks_parallel_training_with_mpi_tpu \
        --lr 0.001 --momentum 0.9 --batch_size 4 --nepochs 3

No external launcher is needed on a single host: parallelism comes from the
device mesh, not from process replication.  On multi-host pods, run the same
command on every host (the TPU runtime provides world configuration).
"""

from __future__ import annotations

import sys

from .config import build_argparser, config_from_args
from .utils.logging import log
from .utils import platform as plat


def _select_platform(args) -> int:
    """Bind this process to its JAX platform (utils.platform.select): the
    backend comes up HERE — no helper child ever touches the device — the
    first log line names it, and a platform that was asked for and is not
    the one that came up exits 2.  Then the compile cache is placed, before
    the first jit.  Returns 0, or the exit code."""
    try:
        plat.select(args.platform, args.num_devices, log=log)
    except plat.PlatformUnavailable as e:
        log(f"ERROR: {e}")
        return 2
    plat.compile_cache()
    return 0


def _reinterpret_void_leaves(params, model):
    """npz stores extension dtypes (ml_dtypes bfloat16 — the
    --param_dtype bfloat16 training path) as raw void bytes; a
    template-less decode restore gets them back as ``|V2`` arrays.
    Reinterpret against the model's param dtype via the same helper the
    templated restore path uses (utils.checkpoint.reinterpret_void)."""
    import jax
    import numpy as np

    from .utils.checkpoint import reinterpret_void

    dt = np.dtype(getattr(getattr(model, "cfg", None), "param_dtype", None)
                  or np.float32)
    return jax.tree_util.tree_map(
        lambda x: reinterpret_void(x, dt), params)


def _dense_decode_params(params, model, meta):
    """Normalize a restored checkpoint into the dense per-layer layout the
    KV-cache decoder expects.  Checkpoints from the explicit-TP layouts
    (pipeline, seq x tensor) carry the head-aligned qkv column permutation
    (recorded as ``qkv_tp`` in meta.json — shape-preserving, hence
    undetectable from the pytree; same reconciliation the Trainer does on
    resume) and pipeline checkpoints carry stage-stacked blocks (the stack
    depth is inferable: a stacked qkv weight has 1 [(S, per)] or 2
    [(v, S, per) interleaved] extra leading dims vs the dense 2-D leaf)."""
    if not (isinstance(params, dict) and "blocks" in params):
        return params
    from .parallel.pipeline import dense_layer_blocks

    params = dict(params)
    params["blocks"] = dense_layer_blocks(
        params["blocks"], model.cfg,
        saved_tp=int((meta or {}).get("qkv_tp", 1)))
    return params


def _generate(args) -> int:
    """Decode from a trained LM checkpoint: the inference entrypoint
    (the reference has no inference path at all — its closest artifact is
    the dead test block at dataParallelTraining_NN_MPI.py:227-236).

    ``--generate "1,2,3"`` takes a comma-separated token-id prompt (this
    framework ships no tokenizer — datasets are synthetic/byte-level) and
    prints the continuation ids from models.generate's jitted KV-cache
    decode."""
    import jax
    import jax.numpy as jnp

    from .models.registry import build_model
    from .models.generate import generate
    from .train.state import TrainState
    from .ops import optim as optim_lib
    from .utils import checkpoint as ckpt, prng

    cfg = config_from_args(args)
    if cfg.model.arch != "transformer":
        log("ERROR: --generate needs a transformer model (--dataset lm "
            "or --arch transformer)")
        return 2
    # cheap input validation FIRST — before any model init or restore
    try:
        ids = [int(t) for t in args.generate.replace(" ", "").split(",") if t]
    except ValueError:
        log(f"ERROR: --generate expects comma-separated token ids, got "
            f"{args.generate!r}")
        return 2
    if not ids or any(t < 0 or t >= cfg.model.vocab_size for t in ids):
        log(f"ERROR: prompt ids must be in [0, {cfg.model.vocab_size}), "
            f"got {args.generate!r}")
        return 2
    if len(ids) + args.max_new_tokens > cfg.model.max_seq_len:
        log(f"ERROR: prompt ({len(ids)}) + max_new_tokens "
            f"({args.max_new_tokens}) exceeds max_seq_len "
            f"{cfg.model.max_seq_len} (raise --seq_len)")
        return 2
    if args.top_k > cfg.model.vocab_size:
        log(f"ERROR: --top_k {args.top_k} > vocab_size "
            f"{cfg.model.vocab_size}")
        return 2

    model = build_model(cfg.model)
    if cfg.checkpoint_dir:
        # only params matter for decoding; restore without a template so
        # the training-time optimizer flags need not be repeated (the npz
        # treedef is stored).  Orbax (multi-host sharded) snapshots DO need
        # a template for target shardings — build one on demand.
        try:
            restored = ckpt.restore(cfg.checkpoint_dir, template=None)
        except ValueError as e:
            if "template" not in str(e):
                log(f"ERROR: cannot restore {cfg.checkpoint_dir}: {e}")
                return 2
            opt = optim_lib.make(cfg.optimizer, cfg.lr, cfg.momentum,
                                 cfg.weight_decay)
            template = TrainState.create(model, opt, prng.init_key(cfg.seed))
            try:
                restored = ckpt.restore(cfg.checkpoint_dir, template)
            except ValueError as e2:
                log(f"ERROR: cannot restore {cfg.checkpoint_dir}: {e2} "
                    "(orbax restore needs the training-time --optimizer)")
                return 2
        if restored is None:
            log(f"ERROR: no checkpoint under {cfg.checkpoint_dir}")
            return 2
        # meta of the generation actually restored (the fallback chain can
        # land below an unquarantinable corrupt newest) — an unpinned read
        # could return a different generation's qkv_tp and silently
        # garble the decode weights
        params = _dense_decode_params(
            _reinterpret_void_leaves(restored.params, model), model,
            ckpt.read_meta(cfg.checkpoint_dir,
                           step=int(jax.device_get(restored.step))))
        log(f"restored step {int(jax.device_get(restored.step))} from "
            f"{cfg.checkpoint_dir}")
    else:
        log("note: no --checkpoint_dir; generating from a fresh init")
        params = model.init(prng.init_key(cfg.seed))
    if (getattr(args, "quantize", "none") == "int8"
            and cfg.model.matmul_dtype == "fp8"):
        # refuse loudly instead of silently falling through to the
        # dequant path: Linear's fp8 branch requires float kernels, so
        # over PTQ int8 weights the flag would do nothing (DESIGN §14)
        log("ERROR: --matmul_dtype fp8 cannot run over --quantize int8 "
            "PTQ kernels; use --matmul_dtype int8 (true int8 compute) "
            "or bf16 (dequant) with PTQ weights")
        return 2
    if getattr(args, "quantize", "none") == "int8":
        from .ops.quant import quantize_params, quantized_bytes

        skip = tuple(s for s in (args.quantize_skip or "").split(",") if s)
        full_b = quantized_bytes(params)
        params = quantize_params(params, skip=skip)
        log(f"int8 weights-only PTQ: param bytes {full_b/2**20:.1f} -> "
            f"{quantized_bytes(params)/2**20:.1f} MiB"
            + (f" (kept {','.join(skip)} full-precision)" if skip else ""))
        if cfg.model.matmul_dtype == "int8":
            # ops.qmm int8_serve_dot: the decode matmuls run int8 x int8
            # -> int32 with dynamic per-token activation scales instead
            # of dequantizing into the compute dtype (DESIGN.md §14)
            log("int8 COMPUTE decode: true int8 activation x weight dot "
                "(ops.qmm) over the PTQ kernels")
    prompt = jnp.asarray([ids], jnp.int32)
    out = generate(model, params, prompt, args.max_new_tokens,
                   temperature=args.temperature, top_k=args.top_k,
                   top_p=args.top_p,
                   key=jax.random.PRNGKey(cfg.seed),
                   kv_quant=getattr(args, "kv_quant", "none") == "int8",
                   prefill_chunk=getattr(args, "prefill_chunk", 0))
    toks = [int(t) for t in jax.device_get(out)[0]]
    print(",".join(str(t) for t in toks))
    return 0


def _supervise(args, argv) -> int:
    """--supervise N: run this same command under the crash-restart
    supervisor (train.resilience.supervise; exit-code contract in that
    module and DESIGN.md §6).  The child argv is this argv minus the
    supervisor flags, plus --resume when a checkpoint dir is configured so
    every relaunch continues from the newest snapshot.

    With --telemetry_dir the supervisor additionally (a) watches the
    child's OWN role-qualified heartbeat (heartbeat-<role>-p<P>.json,
    per the world env channel; leader-written, so only the rank-0
    supervisor's monitor ever arms) when --hang_timeout is set — an
    external hang detector that works even when the child process is
    frozen whole, armed at 4x the in-process timeout so the child's own
    watchdog fires first — (b) points the relaunch log at the child's
    postmortem.json flight-recorder dump after an abnormal exit, and
    (c) summarizes the kind="alert" records the child emitted during
    its lifetime next to each exit (observe-only).

    With --elastic the supervisor reacts to repeated peer-loss exits
    (43/42) by probing the surviving topology — the coordinator-aware
    ``parallel.mesh.probe_world``, driven by the same env channel the
    child's world_setup reads — and relaunching at the shrunken world;
    a probe below --min_devices parks/polls, then exits 46
    (DESIGN.md §10)."""
    import os

    from .train.resilience import strip_supervisor_flags, supervise

    child = strip_supervisor_flags(argv)
    if args.checkpoint_dir and "--resume" not in child:
        child.append("--resume")
    heartbeat = postmortem = alerts = events = None
    heartbeat_timeout = 0.0
    if getattr(args, "telemetry_dir", None):
        # watch exactly THIS child's heartbeat: the role-qualified file
        # its telemetry will write (workload decides the role; the
        # process id rides the world env channel) — never the freshest
        # sibling, which a co-resident process could keep beating while
        # our child hangs
        from .train.resilience import heartbeat_filename

        role = "rl" if getattr(args, "workload", "lm") == "rl" else "train"
        heartbeat = os.path.join(args.telemetry_dir,
                                 heartbeat_filename(role))
        postmortem = os.path.join(args.telemetry_dir, "postmortem.json")
        alerts = os.path.join(args.telemetry_dir, "metrics.jsonl")
        # supervisor lifecycle JSONL next to the trace files so one dir
        # holds the whole goodput join (utils/goodput.py prices the
        # relaunch gaps from these events); lands in the trace/ subdir
        # when tracing is on, else directly under the telemetry dir
        from .train import trace as _trace_lib

        events_dir = (_trace_lib.dir_from_config(args)
                      if (getattr(args, "trace", False)
                          or getattr(args, "trace_dir", None))
                      else args.telemetry_dir)
        os.makedirs(events_dir, exist_ok=True)
        events = os.path.join(events_dir, "supervisor-events.jsonl")
        if getattr(args, "hang_timeout", 0.0) > 0:
            heartbeat_timeout = max(4.0 * args.hang_timeout, 60.0)
    probe = None
    if getattr(args, "elastic", False):
        def probe():
            # imported lazily: pulls jax (module only — the probe itself
            # runs in a subprocess, so the supervisor process never
            # initializes a backend)
            from .parallel.mesh import probe_world

            return probe_world(log=lambda m: print(m, file=sys.stderr,
                                                   flush=True))
    pkg = __name__.rsplit(".", 1)[0]
    return supervise([sys.executable, "-m", pkg, *child],
                     max_restarts=args.supervise,
                     backoff=args.supervise_backoff,
                     backoff_cap=args.supervise_backoff_max,
                     heartbeat_path=heartbeat,
                     heartbeat_timeout=heartbeat_timeout,
                     postmortem_path=postmortem,
                     alerts_path=alerts,
                     ckpt_dir=args.checkpoint_dir,
                     elastic=getattr(args, "elastic", False),
                     min_devices=getattr(args, "min_devices", 0),
                     probe=probe,
                     events_path=events,
                     # a platform's advance notice (SIGUSR1) lands on
                     # this top-level pid; the child is the process that
                     # must checkpoint — forward it (train.resilience
                     # preemption-notice channel)
                     forward_preempt=True)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    args = build_argparser().parse_args(argv)
    if getattr(args, "supervise", 0) > 0:
        return _supervise(args, argv)  # before any backend init
    rc = _select_platform(args)
    if rc:
        return rc
    if getattr(args, "generate", None) is not None:
        return _generate(args)
    from .train.resilience import (EXIT_ANOMALY, EXIT_CAPACITY, EXIT_PEER,
                                   EXIT_SDC, AnomalyAbort, CapacityAbort,
                                   SDCAbort, is_peer_error)
    from .train.trainer import Trainer

    cfg = config_from_args(args)
    try:
        if cfg.workload == "rl":
            # Anakin actor-learner RL (rl/, DESIGN.md §13) — same
            # exception->exit-code contract, so the supervisor and the
            # elastic policy treat an RL child like any training child
            from .rl.runner import RLRunner

            trainer = RLRunner(cfg)
        else:
            trainer = Trainer(cfg)
        result = trainer.fit()
    except AnomalyAbort as e:
        # deterministic divergence: the last good checkpoint is preserved
        # (no final save) and the supervisor must NOT relaunch
        log(f"ERROR: anomaly abort: {e} (exit {EXIT_ANOMALY})")
        return EXIT_ANOMALY
    except SDCAbort as e:
        # silent data corruption the run must not survive: a replay-
        # reproducible (software) divergence, or a device past its strike
        # budget — no final save (it would snapshot corrupt state), and
        # the supervisor must NOT relaunch (it would replay the bug)
        log(f"ERROR: SDC abort: {e} (exit {EXIT_SDC})")
        return EXIT_SDC
    except CapacityAbort as e:
        # the healthy world is below --min_devices: no-retry exit 46 —
        # relaunching cannot create chips (DESIGN.md §10)
        log(f"ERROR: capacity abort: {e} (exit {EXIT_CAPACITY})")
        return EXIT_CAPACITY
    except Exception as e:
        # peer/transport loss (a collective raised, world formation timed
        # out): exit 43 so the supervisor retries — and, under --elastic,
        # counts the loss toward its probe-and-shrink streak.  Anything
        # else stays a crash (traceback, rc 1): also retried, but never
        # misread as a topology signal.
        if not is_peer_error(e):
            raise
        # full traceback first: the classifier is heuristic, and a
        # misread software crash must stay diagnosable from the log
        import traceback

        traceback.print_exc()
        log(f"ERROR: peer loss: {type(e).__name__}: {e} "
            f"(exit {EXIT_PEER})")
        # hard exit: after a lost peer the distributed client's background
        # threads LOG(FATAL) during interpreter teardown, overriding a
        # normal return with SIGABRT — which the supervisor would count as
        # an anonymous crash instead of the peer-loss streak the elastic
        # policy needs
        import os

        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(EXIT_PEER)
    unit = ("env frames/sec" if cfg.workload == "rl" else "samples/sec")
    log(f"done: final loss {result['final_loss']:.6f}, "
        f"{result['samples_per_sec']:.1f} {unit}")
    val = {k: v for k, v in result.items() if k.startswith("val_")}
    if val:
        log("validation: " + ", ".join(f"{k[4:]} {v:.6f}"
                                       for k, v in sorted(val.items())))
    if result.get("preempt_notice"):
        # advance-notice preemption (SIGUSR1): the final checkpoint is
        # on disk, but the node is going away — exit 47 (decommission)
        # so the supervisor stops WITHOUT calling the job finished, and
        # the goodput ledger prices the tail as drain, not rollback
        from .train.resilience import EXIT_DECOMMISSION

        return EXIT_DECOMMISSION
    return 0


if __name__ == "__main__":
    sys.exit(main())
