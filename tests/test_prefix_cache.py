"""Prefix caching + copy-on-write block sharing (serve/paged_kv.py
``prefix_cache``).

The load-bearing contracts:

* **Token identity**: greedy decode with the prefix cache ON is
  bitwise-identical to cache OFF (and to the dense single-stream
  reference) — sharing changes WHERE K/V lives, never a number.  Pinned
  across GQA / int8 KV / scan_layers / rope and on both attention
  dispatches (``gathered`` and the fused Pallas kernel).
* **Refcount hygiene**: every block reference drains to zero at quiesce
  (``assert_drained``), a double release of a shared block is a hard
  error, and a stream never writes a block it merely borrows — the
  copy-on-write fork runs before the first write past the shared
  boundary (asserted inside the server on every prefill chunk and
  decode step, so the fuzz inherits it for free).
* **No recompiles**: cache-hit admission, CoW forks, and shared-block
  (LRU) eviction are host-side block bookkeeping riding traced
  src/dst/table values — after the programs' first compiles the ledger
  stays flat (the PR 10 table-churn invariant extended).
"""

import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.models.serve import (
    DecodeServer,
)
from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (
    BlockAllocator, PagedDecodeServer, Scheduler, ServeConfig,
    run_closed_loop,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

VOCAB = 64


def _model(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=64, n_layers=2, d_model=32,
                n_heads=4, d_ff=64)
    base.update(kw)
    return Transformer(TransformerConfig(**base))


def _dense_reference(model, params, prompt, n):
    srv = DecodeServer(model, params, slots=1)
    rid = srv.submit(list(prompt), max_new_tokens=n)
    while not srv.done(rid):
        srv.step()
    return srv.result(rid)


def _drain(srv, rid, prefill_width=16):
    while not srv.prefill_step(rid, prefill_width):
        pass
    while not srv.done(rid):
        srv.step()
    return srv.result(rid)


# ---------------------------------------------------------------------------
# allocator refcounts
# ---------------------------------------------------------------------------

def test_allocator_refcount_share_release():
    a = BlockAllocator(8)
    got = a.alloc(2)
    a.share(got[0])                      # refcount 2
    assert a.refcount(got[0]) == 2 and a.shared_extra == 1
    a.release([got[0]])                  # one reader gone, block lives
    assert a.refcount(got[0]) == 1 and a.used_blocks == 2
    a.release(got)                       # both to zero
    a.assert_drained()


def test_allocator_double_release_of_shared_block_raises():
    """The satellite hard error: once every reference is gone, another
    release (a stale caller freeing a shared block twice) must raise —
    all frees route through the one release path."""
    a = BlockAllocator(8)
    (b,) = a.alloc(1)
    a.share(b)
    a.release([b])
    a.release([b])
    with pytest.raises(ValueError):
        a.release([b])
    with pytest.raises(ValueError):
        a.free([b])                      # the legacy alias: same path
    a.assert_drained()


def test_allocator_cached_free_lru_eviction():
    """Cached-free blocks stay allocatable (counted in free_blocks) and
    are reclaimed LRU-first with the eviction callback firing."""
    evicted = []
    a = BlockAllocator(4, on_cache_evict=evicted.append)
    blocks = a.alloc(3)                  # whole pool
    for b in blocks:
        a.mark_cached(b)
    a.release([blocks[1]])               # LRU order: 2nd, 3rd, 1st
    a.release([blocks[2]])
    a.release([blocks[0]])
    assert a.free_blocks == 3 and a.cached_free_blocks == 3
    got = a.alloc(2)                     # reclaims the two oldest-parked
    assert evicted == [blocks[1], blocks[2]]
    assert got == [blocks[1], blocks[2]]
    a.reuse_cached(blocks[0])            # the survivor revives as a hit
    assert a.refcount(blocks[0]) == 1
    a.release(got + [blocks[0]])


def test_allocator_refused_alloc_evicts_nothing():
    evicted = []
    a = BlockAllocator(4, on_cache_evict=evicted.append)
    blocks = a.alloc(3)
    a.mark_cached(blocks[0])
    a.release([blocks[0]])
    assert a.alloc(4) is None            # over capacity: all-or-nothing
    assert evicted == [] and a.cached_free_blocks == 1
    a.release(blocks[1:])


# ---------------------------------------------------------------------------
# token-identity parity pins: cache on == cache off == dense reference
# ---------------------------------------------------------------------------

def _parity_roundtrip(model, params, *, attn_impl="gathered", **srv_kw):
    """Cold admit + warm (cache-hit) re-admit of a block-straddling
    prompt with the cache ON, against the same request with the cache
    OFF: all three token streams must be identical, refcounts drained,
    and the warm admission must have skipped the matched prefill."""
    prompt = list(range(1, 21))          # 20 tokens, bs 8: 2 full + 4
    n = 8
    on = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                           block_size=8, prefix_cache=True,
                           attn_impl=attn_impl, **srv_kw)
    cold = _drain(on, on.try_admit(prompt, n), prefill_width=4)
    warm_rid = on.try_admit(prompt, n)
    assert on.prefill_remaining(warm_rid) == 1      # only the last token
    assert on.prefix_hits == 1 and on.prefix_hit_tokens == 19
    warm = _drain(on, warm_rid, prefill_width=4)
    assert on.cow_forks == 1             # mid-block boundary forked
    off = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8, attn_impl=attn_impl, **srv_kw)
    base = _drain(off, off.try_admit(prompt, n), prefill_width=4)
    assert cold == warm == base
    on.allocator.assert_drained()
    off.allocator.assert_drained()
    return base


def test_prefix_cache_tokens_identical_and_skips_prefill():
    model = _model()
    params = model.init(prng.init_key(0))
    base = _parity_roundtrip(model, params)
    assert base == _dense_reference(model, params, list(range(1, 21)), 8)


def test_prefix_cache_concurrent_share_exact():
    """Two live streams sharing prefix blocks (one extending the other's
    prompt) decode concurrently; both match their single-stream
    references and the shared blocks survive the first stream's
    retirement for the second's reads."""
    model = _model()
    params = model.init(prng.init_key(0))
    prompt = list(range(1, 21))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8, prefix_cache=True)
    a = srv.try_admit(prompt, 10)
    while not srv.prefill_step(a, 16):
        pass
    srv.step(); srv.step()
    b = srv.try_admit(prompt + [33, 34], 6)     # shares 2 full + partial
    assert srv._streams[b].prefilled == 20      # partial share included
    assert srv.allocator.shared_extra >= 1
    while not srv.prefill_step(b, 16):
        pass
    while not (srv.done(a) and srv.done(b)):
        srv.step()
    assert srv.result(a) == _dense_reference(model, params, prompt, 10)
    assert srv.result(b) == _dense_reference(model, params,
                                             prompt + [33, 34], 6)
    assert srv.cow_forks == 1
    srv.allocator.assert_drained()


def test_evict_readmit_under_sharing_exact():
    """Eviction of a stream whose blocks are shared releases only ITS
    references; re-admission re-matches the cached blocks and the
    re-run reproduces the tokens exactly."""
    model = _model()
    params = model.init(prng.init_key(0))
    prompt = [4, 5, 6, 7, 8, 9, 10, 11, 12, 13]
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8, prefix_cache=True)
    a = srv.try_admit(prompt, 10)
    while not srv.prefill_step(a, 16):
        pass
    srv.step(); srv.step()
    b = srv.try_admit(prompt, 10)               # shares a's blocks
    p_back, n_back = srv.evict(a)               # owner evicted first
    assert (p_back, n_back) == (prompt, 10)
    tb = _drain(srv, b)                         # reader unaffected
    a2 = srv.try_admit(p_back, n_back)          # re-admit: cache hit
    assert srv.prefill_remaining(a2) == 1
    ta = _drain(srv, a2)
    assert ta == tb == _dense_reference(model, params, prompt, 10)
    srv.allocator.assert_drained()


def test_cache_pressure_evicts_lru_and_stays_exact():
    """Filling the pool with distinct prompts reclaims cached-free
    blocks LRU-first (counted), the index entries die with them, and a
    later re-admission of an evicted prefix simply re-prefills —
    tokens exact either way."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=9,
                            block_size=8, max_len=32, prefix_cache=True)
    first = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    t0 = _drain(srv, srv.try_admit(first, 4))
    for i in range(4):                          # churn the tiny pool
        _drain(srv, srv.try_admit([20 + i] * 9, 4))
    assert srv.cache_evictions > 0
    t1 = _drain(srv, srv.try_admit(first, 4))   # prefix may be gone: cold
    assert t0 == t1
    srv.allocator.assert_drained()


# ---------------------------------------------------------------------------
# model-variant parity (full lane: each variant is a fresh compile of the
# paged programs; the fused rows run the Pallas kernel in interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("attn_impl", [
    "gathered", pytest.param("fused", marks=pytest.mark.pallas)])
@pytest.mark.parametrize("variant", ["gqa", "int8", "scan", "rope"])
def test_variant_parity_cache_on_vs_off(variant, attn_impl):
    """The satellite pin: greedy decode with prefix cache on vs off is
    bitwise-identical across GQA / int8-KV / scan_layers / rope on BOTH
    attention dispatches — cold admit, cache-hit re-admit (CoW fork
    included) and the cache-off run all emit the same tokens."""
    kw = {"gqa": dict(n_kv_heads=2), "scan": dict(scan_layers=True),
          "rope": dict(pos_encoding="rope"), "int8": {}}[variant]
    srv_kw = {"kv_quant": True} if variant == "int8" else {}
    model = _model(**kw)
    params = model.init(prng.init_key(0))
    _parity_roundtrip(model, params, attn_impl=attn_impl, **srv_kw)


# ---------------------------------------------------------------------------
# scheduler-level: burst sharing, counters, fuzzed mixes
# ---------------------------------------------------------------------------

def test_scheduler_burst_shares_and_counts(tmp_path):
    """A burst of shared-system-prompt requests admitted in ONE tick
    still hits (the first-prefill rematch), tokens stay exact, the
    drain is faster than cache-off, and the kind="serve" telemetry
    carries the prefix counters."""
    import json
    import os

    model = _model()
    params = model.init(prng.init_key(0))
    sys_prompt = list(range(1, 25))
    reqs = [(sys_prompt + [30, 31], 8), (sys_prompt + [40], 6),
            (sys_prompt + [50, 51, 52], 10)]
    tdir = str(tmp_path / "t")
    on = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=40, block_size=8, prefill_chunk=8,
        prefix_cache=True, telemetry_dir=tdir, metrics_every=1))
    want = {on.submit(p, n): (p, n) for p, n in reqs}
    on.run_until_drained()
    for rid, (p, n) in want.items():
        assert on.result(rid) == _dense_reference(model, params, p, n)
    on.close()
    snap = on._snapshot()
    assert snap["prefix_hits"] == 2             # followers of the burst
    assert snap["prefix_hit_tokens"] == 48      # 3 aligned blocks each
    assert snap["prefix_hit_rate"] > 0.5
    assert snap["blocks_saved"] == 6
    on.server.allocator.assert_drained()
    off = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=40, block_size=8, prefill_chunk=8))
    for p, n in reqs:
        off.submit(p, n)
    off.run_until_drained()
    assert on.tick_no < off.tick_no             # skipped prefill ticks
    records = [json.loads(line) for line in
               open(os.path.join(tdir, "metrics.jsonl"))]
    finals = [r for r in records if r.get("kind") == "serve"
              and r.get("final")]
    assert finals[-1]["prefix_hits"] == 2
    assert finals[-1]["cow_forks"] == 0         # aligned prefix: no fork
    assert finals[-1]["prefix_hit_rate"] == snap["prefix_hit_rate"]


def test_loadgen_shared_mix_identity_and_residency():
    """The loadgen A/B the bench rides: identical pre-generated
    shared-prefix traffic through cache-off and cache-on schedulers —
    same tokens (sha256), fewer mean blocks in use, per-class TTFT
    fields present."""
    model = _model()
    params = model.init(prng.init_key(0))
    rows = {}
    for on in (False, True):
        sched = Scheduler(model, params, ServeConfig(
            slots=4, num_blocks=40, block_size=8, prefill_chunk=8,
            prefix_cache=on))
        rows[on] = run_closed_loop(
            sched, clients=3, requests_per_client=2, vocab_size=VOCAB,
            prompt_lens=(0, 6), max_new=(4, 8), seed=0,
            shared_prefix_len=20, shared_fraction=0.7)
        sched.server.allocator.assert_drained()
    assert rows[False]["tokens_sha256"] == rows[True]["tokens_sha256"]
    assert (rows[True]["blocks_in_use_mean"]
            < rows[False]["blocks_in_use_mean"])
    assert rows[True]["prefix_cache"]["prefix_hits"] > 0
    for row in rows.values():
        assert row["shared_requests"] > 0
        assert row["ttft_ms_p50_shared"] is not None


def _fuzz_prefix_round(seed, model, params, attn_impl="gathered"):
    """Admit/decode/CoW/evict/readmit fuzz with a shared-prefix mix:
    random arrivals draw from two shared system prompts (plus unique
    prompts and exact regenerations), the pool is tight enough to force
    stream eviction AND cached-block LRU reclaim, and after the drain
    every request must match its single-stream reference with all
    refcounts at zero.  The server's internal write-safety assertions
    (no write into a borrowed block) run on every chunk and step."""
    rng = np.random.default_rng(seed)
    block_size, max_len = 8, 64
    slots = int(rng.integers(2, 5))
    mbs = -(-max_len // block_size)
    num_blocks = int(rng.integers(mbs + 1, mbs + 2 * mbs))
    from tests.test_serve_sched import VClock

    clock = VClock()
    sched = Scheduler(model, params, ServeConfig(
        slots=slots, num_blocks=num_blocks, block_size=block_size,
        max_len=max_len, prefill_chunk=int(rng.choice([4, 8])),
        queue_depth=64, prefix_cache=True, attn_impl=attn_impl),
        now_fn=clock)
    prefixes = [rng.integers(0, VOCAB, (int(ln),)).tolist()
                for ln in (11, 20)]
    want = {}
    n_reqs = 12
    arrivals = sorted(int(t) for t in rng.integers(0, 30, n_reqs))
    submitted = 0
    tick = 0
    while submitted < n_reqs or sched.pending() or sched.in_flight():
        while submitted < n_reqs and arrivals[submitted] <= tick:
            kind = rng.random()
            if kind < 0.5:               # shared prefix + random suffix
                base = prefixes[int(rng.integers(0, len(prefixes)))]
                sfx = rng.integers(
                    0, VOCAB, (int(rng.integers(0, 6)),)).tolist()
                prompt = base + sfx
            elif kind < 0.7 and want:    # exact regeneration (full hit)
                prompt = list(next(iter(want.values()))[0])
            else:                        # unique
                prompt = rng.integers(
                    0, VOCAB, (int(rng.integers(1, 16)),)).tolist()
            n = int(rng.integers(1, min(max_len - len(prompt), 12) + 1))
            slo = (None if rng.random() < 0.3
                   else float(rng.integers(1, 1000)))
            rid = sched.submit(prompt, n, slo_ms=slo)
            assert rid is not None
            want[rid] = (prompt, n)
            submitted += 1
        clock.advance()
        sched.tick()
        tick += 1
        assert tick < 5000, "starvation: not drained"
    sched.server.allocator.assert_drained()     # refcounts all zero
    for rid, (prompt, n) in want.items():
        toks = sched.result(rid)
        assert len(toks) == len(prompt) + n
        assert toks == _dense_reference(model, params, prompt, n), (
            seed, rid, prompt, n)
    return sched


def test_prefix_cache_fuzz_property():
    """One seeded shared-prefix fuzz round in the core lane (tier-1):
    refcounts drain, no stream reads another's post-fork writes (token
    exactness + the in-server write-safety asserts), evict/readmit
    under sharing keeps tokens exact."""
    model = _model()
    params = model.init(prng.init_key(0))
    sched = _fuzz_prefix_round(0, model, params)
    assert sched.server.prefix_hits > 0         # the mix actually shared


@pytest.mark.serve
@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_prefix_cache_fuzz_more_seeds(seed):
    model = _model()
    params = model.init(prng.init_key(0))
    _fuzz_prefix_round(seed, model, params)


@pytest.mark.serve
@pytest.mark.slow
@pytest.mark.pallas
def test_prefix_cache_fuzz_fused():
    """The same sharing/CoW/evict fuzz with the Pallas paged-attention
    kernel active: shared tables and fork repointing flow through the
    kernel's scalar-prefetch plumbing unchanged."""
    model = _model()
    params = model.init(prng.init_key(0))
    _fuzz_prefix_round(4, model, params, attn_impl="fused")


# ---------------------------------------------------------------------------
# compile ledger: sharing/CoW/eviction churn never recompiles
# ---------------------------------------------------------------------------

def test_cache_hit_cow_and_eviction_add_no_compiles(tmp_path):
    """Extends the PR 10 table-churn invariant: once the prefill
    buckets, the decode step, and the CoW copy program have compiled,
    cache-hit admissions, further CoW forks, and shared/cached-block
    evictions add ZERO ledger events — sharing is host bookkeeping over
    traced values."""
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        compile_ledger as ledger_lib,
    )

    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=10, block_size=8, max_len=32,
        prefill_chunk=8, prefix_cache=True,
        trace_dir=str(tmp_path / "trace")))
    try:
        prompt = list(range(1, 12))             # 11 tokens: partial tail
        first = sched.submit(prompt, 4)
        sched.run_until_drained()
        sched.result(first)
        # warm pass: one cache-hit admission draws the CoW program's
        # single legitimate compile
        warm = sched.submit(prompt, 4)
        sched.run_until_drained()
        sched.result(warm)
        assert sched.server.cow_forks == 1
        ledger = ledger_lib.active()
        assert len(ledger.events_for("serve_cow")) == 1
        n_events = len(ledger.events)
        # churn: more hits + forks, block growth, and enough distinct
        # prompts (each parking 2 more cached-free blocks on release)
        # to exhaust the 9-usable-block pool's plain free list and force
        # LRU reclaim of cached blocks
        for i in range(6):
            sched.submit(prompt, 3)
            sched.submit([30 + i] * 9, 3)
            sched.tick()
        sched.run_until_drained()
        assert sched.server.cow_forks >= 2      # forks kept happening
        assert sched.server.cache_evictions > 0  # LRU reclaim happened
        assert len(ledger.events) == n_events, (
            "sharing/CoW/eviction churn recompiled: "
            f"{ledger.events[n_events:]}")
        sched.server.allocator.assert_drained()
    finally:
        sched.close()
    assert trace_lib.active() is None


def test_rows_landing_a_program_behind_share_prefixes_all_the_same(
        staggered_batch):
    """The prefix cache under the request boundary of ISSUE 36: a finished
    stream's blocks are released at the take of its row (owned prompt blocks
    park cached-free, shared ones drop a reference) while the row is still
    in flight, later arrivals hit them, and every request's tokens are those
    it gets alone; the refcounts drain."""
    model = _model()
    params = model.init(prng.init_key(0))
    rng = np.random.default_rng(5)
    shared = rng.integers(0, VOCAB, 18).tolist()
    requests = [(shared + rng.integers(0, VOCAB, int(k)).tolist(), int(n))
                for k, n in zip(rng.integers(0, 8, 8), rng.integers(2, 10, 8))]
    sched = staggered_batch(model, params, requests, 2, slots=2,
                            num_blocks=40, block_size=8, prefill_chunk=8,
                            prefix_cache=True)
    stats = sched.server.prefix_stats()
    assert stats["prefix_hits"] >= 6 and stats["prefix_hit_tokens"] >= 6 * 16
