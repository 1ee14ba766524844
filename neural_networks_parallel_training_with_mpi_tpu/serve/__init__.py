"""Production serving subsystem: paged KV cache + continuous batching.

``models/serve.py`` is the library-shaped slot server: every slot
reserves a dense ``max_len`` KV allocation and admission is "return None
when full".  This package is the service-shaped runtime above it:

* :mod:`serve.paged_kv` — a block-allocated KV pool with per-stream
  block tables, so heterogeneous stream lengths share device memory
  instead of each padding to max.  Attention is dispatched behind the
  ``attn_impl`` seam, default ``'auto'``: on a TPU, for the per-head K/V
  cache row at a lane-dense ``kv_heads * head_dim``, that is ``'fused'``
  (the Pallas paged-attention kernel, ``ops.pallas_kernels.paged_attention``,
  which reads K/V straight from the pool through the block tables, several
  pages a loop step, and stops at each stream's true length, in the decode
  and the prefill-chunk program alike); anywhere else (the CPU, the latent
  row, int8 KV) it is ``'gathered'`` (static-shape ``pool[table]``
  materialization, the parity reference).  Both stay as explicit values.
  With
  ``prefix_cache=True`` identical prompt prefixes share blocks across
  streams (refcounts + a host-side prefix index + copy-on-write forks),
  so a cached prefix admits without re-prefilling — near-zero TTFT for
  shared system prompts.
* :mod:`serve.scheduler` — a continuous-batching scheduler: bounded
  wait queue, per-tick admit/retire, chunked prefill interleaved with
  decode, admission control gated on free blocks + token budget, and
  SLO-aware eviction/requeue under block exhaustion.  Serving metrics
  ride the PR 2 telemetry records + heartbeat, so the PR 1 supervisor
  can babysit a serving fleet unchanged.
* :mod:`serve.loadgen` — a closed-loop load generator over a seeded
  request plan (tokens/s and TTFT/ITL percentiles for tests, examples
  and the fleet's tools; the benchmark's cells use the harness's own
  loop).
"""

from .paged_kv import (
    BlockAllocator,
    BlockExhausted,
    PagedDecodeServer,
    PrefixIndex,
    init_paged_kv,
)
from .paged_kv import ATTN_IMPLS
from .scheduler import Request, Scheduler, ServeConfig
from .loadgen import (
    MIXES,
    make_requests,
    prewarm,
    resolve_mix,
    run_closed_loop,
    run_fleet_closed_loop,
)
from .fleet import (
    Fleet,
    FleetRequest,
    FleetRouter,
    InprocReplica,
    LoadSignal,
    ProcReplica,
    TPGenerateReplica,
    launch_fleet,
    role_kind,
)
from .autopilot import (
    Autopilot,
    AutopilotConfig,
    load_weight_snapshot,
    save_weight_snapshot,
)

__all__ = [
    "ATTN_IMPLS", "BlockAllocator", "BlockExhausted", "PagedDecodeServer",
    "PrefixIndex", "init_paged_kv", "Request", "Scheduler", "ServeConfig",
    "MIXES", "make_requests", "prewarm", "resolve_mix",
    "run_closed_loop",
    "Fleet", "FleetRequest", "FleetRouter", "InprocReplica", "LoadSignal",
    "ProcReplica", "TPGenerateReplica", "launch_fleet", "role_kind",
    "run_fleet_closed_loop",
    "Autopilot", "AutopilotConfig", "load_weight_snapshot",
    "save_weight_snapshot",
]
