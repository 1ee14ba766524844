"""Standalone crash-restart supervisor.

Wraps ANY command with the framework's restart policy
(``neural_networks_parallel_training_with_mpi_tpu.train.resilience``):
relaunch on crash/hang with exponential backoff and bounded restarts,
honoring the exit-code contract —

* 0   run completed -> stop
* 42  watchdog hang -> retry
* 43  peer loss (a collective raised/timed out or world formation
      failed) -> retry; with --elastic, repeated 43/42 triggers the
      topology probe + shrunken-world relaunch
* 44  anomaly abort (rollback budget exhausted) -> stop, do NOT retry
* 45  SDC abort (deterministic replica divergence or a device past its
      strike budget) -> stop, do NOT retry
* 46  capacity abort (healthy devices stayed below --min-devices) ->
      stop, do NOT retry (a relaunch cannot create chips)
* any other nonzero / signal death -> retry

For training jobs the integrated form is usually what you want (it appends
``--resume`` so relaunches continue from the newest snapshot)::

    python -m neural_networks_parallel_training_with_mpi_tpu \
        --supervise 3 --checkpoint_dir /ckpt --checkpoint_every 50 ...

This script is the generic wrapper for everything else (a bench loop, a
watcher, a multi-host launcher that itself execs the trainer)::

    python tools/supervise.py --max-restarts 3 --backoff 2 -- \
        python -m neural_networks_parallel_training_with_mpi_tpu --resume ...

Exits with the wrapped command's final exit code.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from neural_networks_parallel_training_with_mpi_tpu.train.resilience import (  # noqa: E402
    default_probe,
    supervise,
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="relaunch a command on crash with exponential backoff "
                    "(exit 0, 44, 45 and 46 stop; see module docstring)")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="relaunches allowed after the initial run")
    p.add_argument("--backoff", type=float, default=1.0,
                   help="initial backoff seconds (doubles per restart, "
                        "jittered -50%% downward against thundering-herd "
                        "relaunches; --backoff-cap stays a hard bound)")
    p.add_argument("--backoff-cap", type=float, default=60.0)
    p.add_argument("--elastic", action="store_true",
                   help="after repeated peer-loss exits (43/42), probe "
                        "the surviving topology (a bounded subprocess "
                        "probe) and relaunch at the shrunken world: the "
                        "child env is rewritten so its world formation "
                        "targets the degraded topology; each relaunch "
                        "logs the probed device/process counts")
    p.add_argument("--min-devices", type=int, default=0, metavar="N",
                   help="with --elastic: park and re-poll while the "
                        "probe reports fewer than N healthy devices, "
                        "then exit 46 (capacity abort, no-retry) when "
                        "the restart budget runs out")
    p.add_argument("--probe-timeout", dest="world_probe_s", type=float,
                   default=60.0,
                   help="seconds the topology probe may spend before it "
                        "counts as failed")
    p.add_argument("--telemetry-dir", default=None,
                   help="the child's --telemetry_dir: watch its "
                        "heartbeat for staleness (with "
                        "--heartbeat-timeout; the freshest "
                        "heartbeat*.json in the dir — per-role "
                        "heartbeat-<role>-p<P>.json or the legacy "
                        "shared heartbeat.json), summarize kind=alert "
                        "records each child emitted next to its exit, "
                        "and point the relaunch log at postmortem.json "
                        "after abnormal exits")
    p.add_argument("--heartbeat-timeout", type=float, default=0.0,
                   help="kill the child as hung (exit-42 retry) when its "
                        "heartbeat goes stale for this many seconds "
                        "(0 = off; needs --telemetry-dir or --heartbeat)")
    p.add_argument("--heartbeat", default=None,
                   help="explicit heartbeat file (overrides the "
                        "--telemetry-dir derived path).  When several "
                        "programs share one telemetry dir, pass YOUR "
                        "child's heartbeat-<role>-p<P>.json here — the "
                        "derived legacy path falls back to the "
                        "freshest heartbeat in the dir, which another "
                        "program's beats could keep fresh while your "
                        "child hangs")
    p.add_argument("--checkpoint-dir", default=None,
                   help="the child's --checkpoint_dir: before each "
                        "relaunch, log the newest VERIFIED snapshot "
                        "(manifest checksums, utils.ckpt_manifest) the "
                        "child's --resume will land on")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="the command to run (prefix with -- to stop flag "
                        "parsing)")
    args = p.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd[:1] == ["--"] else args.cmd
    if not cmd:
        p.error("no command given (usage: supervise.py [flags] -- cmd ...)")
    import os

    heartbeat = args.heartbeat or (
        os.path.join(args.telemetry_dir, "heartbeat.json")
        if args.telemetry_dir else None)
    if args.heartbeat_timeout > 0 and not heartbeat:
        p.error("--heartbeat-timeout needs a heartbeat file to watch: "
                "pass --telemetry-dir (the child's --telemetry_dir) or "
                "--heartbeat")
    postmortem = (os.path.join(args.telemetry_dir, "postmortem.json")
                  if args.telemetry_dir else None)
    alerts = (os.path.join(args.telemetry_dir, "metrics.jsonl")
              if args.telemetry_dir else None)
    return supervise(cmd, max_restarts=args.max_restarts,
                     backoff=args.backoff, backoff_cap=args.backoff_cap,
                     heartbeat_path=heartbeat,
                     heartbeat_timeout=args.heartbeat_timeout,
                     postmortem_path=postmortem,
                     alerts_path=alerts,
                     ckpt_dir=args.checkpoint_dir,
                     elastic=args.elastic,
                     min_devices=args.min_devices,
                     probe=(lambda: default_probe(args.world_probe_s))
                     if args.elastic else None)


if __name__ == "__main__":
    sys.exit(main())
