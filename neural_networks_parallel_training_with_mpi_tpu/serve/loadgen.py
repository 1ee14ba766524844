"""Closed-loop load generator + latency-percentile measurement.

Closed-loop means each simulated client holds at most ONE outstanding
request and submits its next the moment the previous completes — offered
load is the number of concurrent clients, and the system can never be
driven past saturation into a meaningless unbounded backlog (the
standard serving-bench discipline; open-loop arrival processes measure
queueing theory, closed-loop measures the server).

Per request we record TTFT (submit -> first output token, queue wait
included — that is what a client experiences) and mean ITL (decode span
/ (new_tokens - 1)); the sweep reports p50/p99 of each across requests,
plus aggregate generated tokens/s.  Tests, examples and the fleet's tools
drive it; the benchmark's serving cells use the harness's own closed loop
(``benchmark/harness/serve_closed_loop.py``), and no number from here is
a result.

**Shared-prefix traffic mixes** (``shared_prefix_len`` /
``shared_fraction``): real chat fleets share system prompts, so a
seeded fraction of requests prepend one fixed shared prefix to their
random suffix — the workload the prefix cache (``ServeConfig.
prefix_cache``) exists for.  The request stream is pre-generated
per seed (client-major, independent of queue dynamics), so a cache-off
and a cache-on arm serve BYTE-IDENTICAL requests and the row's
``tokens_sha256`` digest pins greedy output equality across the A/B
(tests/test_prefix_cache.py).  TTFT
percentiles split by class (shared-prefix vs unique) and per-tick
blocks-in-use peak/mean expose the two wins: cached-prefix TTFT and
pool residency.
"""

from __future__ import annotations

import hashlib
import time
from typing import Any, Dict, List, Optional

import numpy as np


def _pct(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64), q))


def prewarm(make_scheduler, *, prompt_lens=(4, 24)) -> None:
    """Pay every compile a load run can draw BEFORE any latency is
    measured: each power-of-two prefill bucket the prompt range can
    produce under the scheduler's ``prefill_chunk``, plus the batched
    decode program — which, under ``attn_impl='fused'``, is where the
    Pallas paged-attention kernel compiles.  Without this, the first
    request to hit a cold bucket (or the cold decode kernel) books XLA /
    Mosaic compile time as a fake TTFT outlier in the p99.

    The bucket set is derived THROUGH ``paged_kv.prefill_bucket`` — the
    same function ``prefill_step`` compiles against — over every chunk
    width the sweep can draw (``w <= min(prefill_chunk, prompt_len)``),
    so the warmed set cannot drift from the compiled set if the bucket
    rule ever changes.  Uses a throwaway scheduler from the same
    factory; the jitted programs are cached per (model, geometry,
    sampling, attn_impl), so the warmth carries to every load point."""
    from .paged_kv import prefill_bucket

    sched = make_scheduler()
    try:
        chunk = max(1, int(sched.cfg.prefill_chunk))
        hi = min(int(prompt_lens[1]), sched.server.max_len - 2)
        w_max = max(1, min(chunk, hi))
        targets = {prefill_bucket(w) for w in range(1, w_max + 1)}
        # a prompt of min(bucket, w_max) tokens prefills in one chunk
        # drawing exactly that bucket (the top bucket via the partial
        # width w_max)
        lens = sorted(min(b, w_max) for b in targets)
        rids = [sched.submit(list(range(1, p + 1)), 2) for p in lens]
        assert all(r is not None for r in rids), "prewarm rejected"
        sched.run_until_drained()
        for r in rids:
            sched.result(r)
    finally:
        sched.close()


# Named traffic presets: one word in a bench flag pins the whole shape
# (prompt/decode ranges + shared-prefix mix), so two arms saying
# ``mix="long_prefill"`` provably serve the same traffic.  Values are
# sized for the bench geometry (seq=128): the longest shared request is
# shared_prefix_len + prompt_lens[1] tokens, inside the seq-2 admission
# budget.
MIXES: Dict[str, Dict[str, Any]] = {
    # prefill-heavy: long prompts, with decodes just long enough that
    # per-stream cadence is a real measurement (a 4-token decode's ITL
    # is all admission noise) — the traffic where a unified pool lets
    # prefill bursts stall decode cadence, and the disaggregated
    # prefill/decode split (DESIGN.md §11) earns its keep.  Half the
    # requests share one 24-token prefix so the shared/unique split
    # prices the prefix cache under the same mix.  Longest request:
    # 24 + 72 prompt + 28 decode = 124 <= the seq-2 admission budget.
    "long_prefill": dict(prompt_lens=(32, 72), max_new=(16, 28),
                         shared_prefix_len=24, shared_fraction=0.5),
}


def resolve_mix(mix: Optional[str], prompt_lens, max_new,
                shared_prefix_len: int, shared_fraction: float):
    """Apply a :data:`MIXES` preset: when ``mix`` is set its values
    REPLACE the four traffic-shape arguments (a preset exists to pin
    the shape; silently merging caller overrides would unpin it)."""
    if mix is None:
        return prompt_lens, max_new, shared_prefix_len, shared_fraction
    if mix not in MIXES:
        raise ValueError(f"unknown mix {mix!r}; have {sorted(MIXES)}")
    m = MIXES[mix]
    return (m["prompt_lens"], m["max_new"], m["shared_prefix_len"],
            m["shared_fraction"])


def make_requests(clients: int, requests_per_client: int, *,
                  vocab_size: int, prompt_lens=(4, 24), max_new=(8, 32),
                  seed: int = 0, shared_prefix_len: int = 0,
                  shared_fraction: float = 0.0, stream: int = 0,
                  mix: Optional[str] = None
                  ) -> List[List[Dict[str, Any]]]:
    """Pre-generate every client's request list (client-major, one RNG
    pass) so the stream is a pure function of the arguments — queue
    dynamics (rejections, completion order) cannot perturb which
    requests get generated, which is what lets two scheduler arms serve
    byte-identical traffic for an A/B.  With ``shared_prefix_len`` > 0,
    a ``shared_fraction`` of requests prepend ONE fixed shared prefix
    (drawn first from the same seed) to their random suffix.

    ``stream`` partitions the request space per DRIVEN REPLICA: N
    loadgens driving N fleet replicas from one operator ``seed`` must
    not replay the identical request stream (colliding flow-trace ids
    on the merged timeline — see the scheduler's ``_flow_prefix`` — and
    N byte-identical ``tokens_sha256`` inputs that would vacuously
    "agree"); ``stream=k`` mixes ``k`` into the RNG seed sequence, while
    ``stream=0`` keeps the historical ``default_rng(seed)`` draws so
    every committed bench artifact's traffic is reproducible."""
    (prompt_lens, max_new, shared_prefix_len,
     shared_fraction) = resolve_mix(mix, prompt_lens, max_new,
                                    shared_prefix_len, shared_fraction)
    rng = (np.random.default_rng(seed) if not stream
           else np.random.default_rng((int(seed), int(stream))))
    shared = (rng.integers(0, vocab_size, (shared_prefix_len,)).tolist()
              if shared_prefix_len > 0 else [])
    out: List[List[Dict[str, Any]]] = []
    for _ in range(int(clients)):
        reqs = []
        for _ in range(int(requests_per_client)):
            p = int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
            n = int(rng.integers(max_new[0], max_new[1] + 1))
            is_shared = bool(shared
                             and rng.random() < float(shared_fraction))
            if not is_shared:
                p = max(1, p)     # a bare prompt needs >= 1 token; a
                #                   0-suffix SHARED request is legal (a
                #                   regenerated turn: the prompt IS the
                #                   shared prefix — the full-hit + CoW
                #                   path)
            suffix = rng.integers(0, vocab_size, (p,)).tolist()
            reqs.append({"prompt": shared + suffix if is_shared
                         else suffix,
                         "max_new": n, "shared": is_shared})
        out.append(reqs)
    return out


def run_closed_loop(scheduler, clients: int, requests_per_client: int,
                    *, vocab_size: int, prompt_lens=(4, 24),
                    max_new=(8, 32), seed: int = 0,
                    slo_ms: Optional[float] = None,
                    shared_prefix_len: int = 0,
                    shared_fraction: float = 0.0, stream: int = 0,
                    mix: Optional[str] = None,
                    max_ticks: int = 200_000) -> Dict[str, Any]:
    """Drive ``scheduler`` with ``clients`` closed-loop clients until
    each has completed ``requests_per_client`` requests; returns the
    measured row (tokens/s, TTFT/ITL percentiles — split by shared/
    unique class under a shared-prefix mix — per-tick blocks-in-use,
    counters, and a sha256 of every request's output tokens in
    submission order for cross-arm identity pins).

    The request stream comes from :func:`make_requests` — a pure
    function of the arguments — so a sweep's load points (and an A/B's
    arms) serve the same request mix."""
    (prompt_lens, max_new, shared_prefix_len,
     shared_fraction) = resolve_mix(mix, prompt_lens, max_new,
                                    shared_prefix_len, shared_fraction)
    plan = make_requests(clients, requests_per_client,
                         vocab_size=vocab_size, prompt_lens=prompt_lens,
                         max_new=max_new, seed=seed,
                         shared_prefix_len=shared_prefix_len,
                         shared_fraction=shared_fraction, stream=stream)
    next_idx = [0] * int(clients)
    outstanding: List[Optional[int]] = [None] * int(clients)
    finished: List[int] = []
    shared_rids: set = set()
    results: Dict[int, tuple] = {}    # rid -> (client, idx, tokens)
    submit_retries = 0
    blocks_peak = 0
    blocks_sum = 0
    n_ticks = 0
    t0 = time.perf_counter()
    for _ in range(max_ticks):
        for ci in range(clients):
            if outstanding[ci] is not None or \
                    next_idx[ci] >= requests_per_client:
                continue
            req = plan[ci][next_idx[ci]]
            rid = scheduler.submit(req["prompt"], req["max_new"],
                                   slo_ms=slo_ms)
            if rid is None:           # bounded queue full: retry next tick
                submit_retries += 1
                continue
            if req["shared"]:
                shared_rids.add(rid)
            results[rid] = (ci, next_idx[ci], None)
            outstanding[ci] = rid
            next_idx[ci] += 1
        for rid in scheduler.tick():
            ci = outstanding.index(rid)
            outstanding[ci] = None
            finished.append(rid)
            c, i, _ = results[rid]
            results[rid] = (c, i, scheduler.result(rid))
        used = scheduler.server.allocator.used_blocks
        blocks_peak = max(blocks_peak, used)
        blocks_sum += used
        n_ticks += 1
        if all(i >= requests_per_client for i in next_idx) and \
                all(o is None for o in outstanding):
            break
    else:
        raise RuntimeError(f"load run not drained in {max_ticks} ticks")
    wall = time.perf_counter() - t0
    stats = [scheduler.stats(rid) for rid in finished]
    ttft = [s.ttft_ms for s in stats if s.ttft_ms is not None]
    itl = [s.itl_ms for s in stats if s.itl_ms is not None]
    # output-identity digest: every request's tokens in SUBMISSION order
    # (client-major), so two arms serving the same plan hash equal iff
    # every generated token matches
    h = hashlib.sha256()
    if stream:
        # replica-partitioned streams carry their stream tag in the
        # digest preamble: two replicas' digests can then never collide
        # unless someone ALSO collapsed their request streams
        h.update(repr(("stream", int(stream))).encode())
    for ci, i, toks in sorted(results.values()):
        h.update(repr((ci, i, toks)).encode())
    row = {
        "clients": int(clients),
        "requests": len(finished),
        "wall_s": round(wall, 3),
        "tokens_out": scheduler.tokens_out,
        "tokens_per_sec": round(scheduler.tokens_out / wall, 1),
        "ttft_ms_p50": _pct(ttft, 50), "ttft_ms_p99": _pct(ttft, 99),
        "itl_ms_p50": _pct(itl, 50), "itl_ms_p99": _pct(itl, 99),
        "ticks": scheduler.tick_no,
        "admitted": scheduler.admitted,
        "rejected": scheduler.rejected,
        "evicted": scheduler.evicted,
        "submit_retries": submit_retries,
        "deadline_missed": sum(1 for s in stats if s.deadline_missed),
        "blocks_in_use_peak": blocks_peak,
        "blocks_in_use_mean": round(blocks_sum / max(1, n_ticks), 2),
        "tokens_sha256": h.hexdigest(),
    }
    if mix is not None:
        row["mix"] = mix
    if shared_prefix_len > 0:
        row["shared_prefix_len"] = int(shared_prefix_len)
        row["shared_fraction"] = float(shared_fraction)
        row["shared_requests"] = len(shared_rids)
        for cls, rids in (("shared", shared_rids),
                          ("unique", set(finished) - shared_rids)):
            vals = [scheduler.stats(r).ttft_ms for r in rids
                    if scheduler.stats(r).ttft_ms is not None]
            row[f"ttft_ms_p50_{cls}"] = _pct(vals, 50)
            row[f"ttft_ms_p99_{cls}"] = _pct(vals, 99)
            # decode cadence per class: a prefix hit shortens TTFT but
            # must NOT change steady-state ITL — the pair proves it
            ivals = [scheduler.stats(r).itl_ms for r in rids
                     if scheduler.stats(r).itl_ms is not None]
            row[f"itl_ms_p50_{cls}"] = _pct(ivals, 50)
            row[f"itl_ms_p99_{cls}"] = _pct(ivals, 99)
    if getattr(scheduler.cfg, "prefix_cache", False):
        row["prefix_cache"] = scheduler.server.prefix_stats()
    return row


def run_fleet_closed_loop(router, clients: int,
                          requests_per_client: int, *, vocab_size: int,
                          prompt_lens=(4, 24), max_new=(8, 32),
                          seed: int = 0,
                          classes: Optional[List[Dict[str, Any]]] = None,
                          stream: int = 0,
                          mix: Optional[str] = None,
                          max_wall_s: float = 600.0) -> Dict[str, Any]:
    """The MULTI-REPLICA closed-loop driver: ``clients`` one-outstanding
    clients against a ``serve.fleet.FleetRouter`` instead of one
    scheduler.  Same pre-generated request plan as
    :func:`run_closed_loop` (pure function of seed/stream — fleet arms
    at different replica counts serve byte-identical traffic), plus
    per-CLASS SLOs: ``classes`` is a list of ``{"name", "slo_ms"}``
    dicts assigned client-major (client ``ci`` runs class ``ci % K`` —
    an interactive client and a bulk client are different CLIENTS, not
    different requests of one), and the row reports TTFT percentiles
    per class — the split the router's deadline-aware placement is
    judged on.  Rejections at the ROUTER (fleet queue full / SLO
    infeasible) surface as ``router_rejections`` with clients retrying,
    the closed-loop discipline."""
    classes = classes or [{"name": "all", "slo_ms": None}]
    (prompt_lens, max_new, shared_prefix_len,
     shared_fraction) = resolve_mix(mix, prompt_lens, max_new, 0, 0.0)
    plan = make_requests(clients, requests_per_client,
                         vocab_size=vocab_size, prompt_lens=prompt_lens,
                         max_new=max_new, seed=seed, stream=stream,
                         shared_prefix_len=shared_prefix_len,
                         shared_fraction=shared_fraction)
    cls_of = [classes[ci % len(classes)] for ci in range(int(clients))]
    next_idx = [0] * int(clients)
    outstanding: List[Optional[int]] = [None] * int(clients)
    finished: List[int] = []
    owner: Dict[int, int] = {}          # fleet rid -> client
    tokens_of: Dict[int, tuple] = {}    # fleet rid -> (ci, idx, tokens)
    shared_rids: set = set()
    submit_retries = 0
    t0 = time.perf_counter()
    while True:
        progressed = False
        for ci in range(int(clients)):
            if outstanding[ci] is not None or \
                    next_idx[ci] >= requests_per_client:
                continue
            req = plan[ci][next_idx[ci]]
            # client idempotency key: a pure function of the request's
            # coordinates in the plan (seed/stream/client/index), so a
            # relaunched driver resubmitting after a control-plane
            # death names each request IDENTICALLY and the router's
            # journal dedupes instead of re-executing (serve/wal.py)
            idem = f"{int(seed)}.{int(stream)}.{ci}.{next_idx[ci]}"
            rid = router.submit(req["prompt"], req["max_new"],
                                slo_ms=cls_of[ci]["slo_ms"], idem=idem)
            if rid is None:
                submit_retries += 1
                continue
            owner[rid] = ci
            if req.get("shared"):
                shared_rids.add(rid)
            tokens_of[rid] = (ci, next_idx[ci], None)
            outstanding[ci] = rid
            next_idx[ci] += 1
            progressed = True
        for rid in router.pump():
            ci = owner.get(rid)
            if ci is None:
                # a journal-replayed request can complete before its
                # client re-attaches (recovered router, fresh driver);
                # the idempotency-key resubmit re-announces it
                continue
            outstanding[ci] = None
            finished.append(rid)
            c, i, _ = tokens_of[rid]
            tokens_of[rid] = (c, i, router.result(rid))
            progressed = True
        if all(i >= requests_per_client for i in next_idx) and \
                all(o is None for o in outstanding):
            break
        if time.perf_counter() - t0 > max_wall_s:
            raise RuntimeError(
                f"fleet load run not drained in {max_wall_s}s: "
                f"{len(finished)}/{clients * requests_per_client} done, "
                f"outstanding={[o for o in outstanding if o is not None]}")
        if not progressed:
            # subprocess replicas own the compute; a busy-spinning
            # driver would steal their core
            time.sleep(0.002)
    wall = time.perf_counter() - t0
    stats = [router.stats(rid) for rid in finished]
    h = hashlib.sha256()
    if stream:
        h.update(repr(("stream", int(stream))).encode())
    for ci, i, toks in sorted(tokens_of.values()):
        h.update(repr((ci, i, toks)).encode())
    tokens_out = sum(s.n_generated or 0 for s in stats)
    row: Dict[str, Any] = {
        "clients": int(clients),
        "requests": len(finished),
        "wall_s": round(wall, 3),
        "tokens_out": tokens_out,
        "tokens_per_sec": round(tokens_out / wall, 1),
        "submit_retries": submit_retries,
        "router_rejections": router.rejected,
        "requeued": router.requeued,
        "tokens_sha256": h.hexdigest(),
    }
    ttft_all = [s.ttft_ms for s in stats if s.ttft_ms is not None]
    row["ttft_ms_p50"] = _pct(ttft_all, 50)
    row["ttft_ms_p99"] = _pct(ttft_all, 99)
    # fleet-wide decode cadence: the signal a slow-but-alive replica
    # degrades first (utils/chaos.py's eviction-recovery A/B reads it)
    itl_all = [s.itl_ms for s in stats if s.itl_ms is not None]
    row["itl_ms_p50"] = _pct(itl_all, 50)
    row["itl_ms_p99"] = _pct(itl_all, 99)
    if mix is not None:
        row["mix"] = mix
    if shared_prefix_len > 0:
        # shared/unique split under a prefix mix, TTFT and ITL both:
        # the shared class's TTFT prices prefix reuse, its ITL pins
        # that reuse never taxes decode cadence
        row["shared_prefix_len"] = int(shared_prefix_len)
        row["shared_fraction"] = float(shared_fraction)
        row["shared_requests"] = len(shared_rids)
        for cls, rids in (("shared", shared_rids),
                          ("unique", set(finished) - shared_rids)):
            tv = [s.ttft_ms for rid, s in zip(finished, stats)
                  if rid in rids and s.ttft_ms is not None]
            iv = [s.itl_ms for rid, s in zip(finished, stats)
                  if rid in rids and s.itl_ms is not None]
            row[f"ttft_ms_p50_{cls}"] = _pct(tv, 50)
            row[f"ttft_ms_p99_{cls}"] = _pct(tv, 99)
            row[f"itl_ms_p50_{cls}"] = _pct(iv, 50)
            row[f"itl_ms_p99_{cls}"] = _pct(iv, 99)
    for k in classes:
        vals = [s.ttft_ms for rid, s in zip(finished, stats)
                if cls_of[owner[rid]]["name"] == k["name"]
                and s.ttft_ms is not None]
        row[f"ttft_ms_p50_{k['name']}"] = _pct(vals, 50)
        row[f"ttft_ms_p99_{k['name']}"] = _pct(vals, 99)
        row[f"requests_{k['name']}"] = len(vals)
        if k["slo_ms"] is not None:
            row[f"deadline_missed_{k['name']}"] = sum(
                1 for rid, s in zip(finished, stats)
                if cls_of[owner[rid]]["name"] == k["name"]
                and s.ttft_ms is not None and s.deadline_missed)
    row["per_replica_completed"] = router.per_replica_completed()
    return row
