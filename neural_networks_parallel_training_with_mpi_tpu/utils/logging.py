"""Structured metrics + process-0 logging.

The reference's observability is interleaved per-rank ``print`` under mpiexec
(dataParallelTraining_NN_MPI.py:152, :224; SURVEY.md §5.5).  Here: only
process 0 logs (each message carries global, already-allreduced values — so
one line *is* the whole job), optionally mirrored as JSONL for machines.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, Optional, TextIO

import jax
from jax._src import xla_bridge


def is_leader() -> bool:
    # jax.process_index() initialises the backend on first call, and a
    # process that only supervises (the --supervise parent, the fleet
    # router) must never take the accelerator just by logging: before any
    # backend exists this process is the leader.  Once training has
    # initialised a backend the real process index is used, so multi-host
    # leader-only logging is unaffected.
    if not xla_bridge.backends_are_initialized():
        return True
    return jax.process_index() == 0


def log(msg: str, *, every_process: bool = False,
        file: Optional[TextIO] = None) -> None:
    if every_process or is_leader():
        print(msg, file=file, flush=True)


class MetricsLogger:
    """Per-step structured metrics with samples/sec, from process 0 only."""

    def __init__(self, jsonl_path: Optional[str] = None):
        self.jsonl: Optional[TextIO] = None
        if jsonl_path and is_leader():
            self.jsonl = open(jsonl_path, "a")
        self._t0 = time.perf_counter()

    def write(self, record: Dict[str, Any]) -> None:
        if not is_leader():
            return
        record = {k: (float(v) if hasattr(v, "item") else v)
                  for k, v in record.items()}
        record["t"] = round(time.perf_counter() - self._t0, 6)
        if self.jsonl:
            self.jsonl.write(json.dumps(record) + "\n")
            self.jsonl.flush()

    def close(self) -> None:
        if self.jsonl:
            self.jsonl.close()


class Throughput:
    """Rolling samples/sec measurement (the BASELINE.md north-star metric).

    Steady-state accounting: the clock starts at the *first* ``add()`` —
    i.e. after the first train step has been dispatched, which is where jit
    tracing + XLA compilation happen — and that first batch's samples are
    excluded.  Short benchmark-style runs therefore report the pipelined
    steady-state rate rather than a compile-dominated average.  (The
    reference has no timing at all; its only observable is the per-epoch
    loss print, dataParallelTraining_NN_MPI.py:224.)
    """

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.samples = 0
        self.start: Optional[float] = None
        self._t0 = time.perf_counter()
        self._warmup_samples = 0

    def add(self, n: int) -> None:
        if self.start is None:  # first step = compile+warmup boundary
            self.start = time.perf_counter()
            self._warmup_samples = int(n)
            return
        self.samples += int(n)

    @property
    def samples_per_sec(self) -> float:
        if self.samples > 0 and self.start is not None:
            dt = time.perf_counter() - self.start
            return self.samples / dt if dt > 0 else 0.0
        # one-step runs have no steady window; fall back to the
        # compile-inclusive rate rather than reporting 0
        if self._warmup_samples:
            dt = time.perf_counter() - self._t0
            return self._warmup_samples / dt if dt > 0 else 0.0
        return 0.0
