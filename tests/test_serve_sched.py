"""Continuous-batching scheduler (serve/scheduler.py) + loadgen + the
serving telemetry channel.

The property the fuzz test pins (the subsystem's acceptance invariant):
under random arrivals, lengths, and pool geometries, the scheduler never
leaks a block (allocator balance returns to zero after drain), never
starves an accepted request (everything submitted completes), and never
violates a stream's max_len — while every greedy result stays
token-identical to the single-stream decode (referenced through the
dense ``DecodeServer``, which tests/test_serve.py pins == generate())."""

import json
import os

import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.models.serve import (
    DecodeServer,
)
from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (
    Scheduler, ServeConfig, run_closed_loop,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

VOCAB = 64


def _model(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=64, n_layers=2, d_model=32,
                n_heads=4, d_ff=64)
    base.update(kw)
    return Transformer(TransformerConfig(**base))


def _reference(model, params, prompt, n):
    """Single-stream greedy decode via the dense slot server (jitted
    programs lru-shared across calls; == generate() per test_serve.py)."""
    srv = DecodeServer(model, params, slots=1)
    rid = srv.submit(list(prompt), max_new_tokens=n)
    while not srv.done(rid):
        srv.step()
    return srv.result(rid)


class VClock:
    """Deterministic virtual clock: deadline policy without wall-time
    flakiness."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt=0.001):
        self.t += dt


def test_end_to_end_ragged_exact_tokens():
    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=40, block_size=8, prefill_chunk=8))
    want = {}
    for prompt, n in (([1, 2, 3], 10), ([5, 9, 11, 13, 2, 2, 2, 2, 2], 9),
                      ([7], 6)):
        rid = sched.submit(prompt, n)
        want[rid] = (prompt, n)
    sched.run_until_drained()
    for rid, (prompt, n) in want.items():
        assert sched.result(rid) == _reference(model, params, prompt, n)
        st = sched.stats(rid)
        assert st.ttft_ms is not None and st.itl_ms is not None
    sched.server.allocator.assert_drained()
    assert sched.completed == 3 and sched.tokens_out == 10 + 9 + 6


def test_single_token_request_completes_at_prefill():
    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=20, block_size=8))
    rid = sched.submit([4, 5, 6], 1)
    sched.run_until_drained()
    assert sched.result(rid) == _reference(model, params, [4, 5, 6], 1)


def test_chunked_prefill_interleaves_with_decode():
    """Admitting a LONG prompt must not stall an in-flight stream: the
    prompt prefills one chunk per tick while the running stream keeps
    producing a token per tick."""
    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=40, block_size=8, prefill_chunk=4))
    a = sched.submit([1, 2, 3], 24)
    for _ in range(4):
        sched.tick()                         # a is decoding
    srv = sched.server
    srv_a = sched._srv_rid[a]
    pos_before = int(srv._pos_host[srv._slot_of[srv_a]])
    b = sched.submit(list(range(1, 17)), 8)   # 16-token prompt, 4 chunks
    ticks_to_first = 0
    while sched.stats(b).t_first is None:
        sched.tick()
        ticks_to_first += 1
        assert ticks_to_first < 20
    assert ticks_to_first >= 4                # prefill really was chunked
    pos_after = int(srv._pos_host[srv._slot_of[srv_a]])
    # the in-flight stream advanced ~1 token per tick throughout
    assert pos_after - pos_before >= ticks_to_first - 1
    sched.run_until_drained()
    assert sched.result(a) == _reference(model, params, [1, 2, 3], 24)
    assert sched.result(b) == _reference(model, params,
                                         list(range(1, 17)), 8)


def test_bounded_queue_rejects_overload():
    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=1, num_blocks=20, block_size=8, queue_depth=2))
    rids = [sched.submit([1, 2], 4) for _ in range(5)]
    accepted = [r for r in rids if r is not None]
    assert len(accepted) == 2 and sched.rejected == 3
    sched.run_until_drained()
    for rid in accepted:
        assert len(sched.result(rid)) == 6
    sched.server.allocator.assert_drained()


def test_token_budget_gates_admission():
    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=40, block_size=8, token_budget=20))
    a = sched.submit([1, 2, 3], 10)          # 13 committed tokens
    b = sched.submit([4, 5, 6], 10)          # would commit 26 > 20
    sched.tick()
    assert sched.in_flight() == 1 and sched.pending() == 1
    sched.run_until_drained()                # b admits after a retires
    assert sched.result(a) == _reference(model, params, [1, 2, 3], 10)
    assert sched.result(b) == _reference(model, params, [4, 5, 6], 10)


def test_slo_eviction_prefers_latest_deadline():
    """Pool exhaustion must evict the LATEST-deadline stream, requeue it
    at the queue front, and still complete it exactly once capacity
    frees — and the tight-SLO stream must never be the victim."""
    model = _model()
    params = model.init(prng.init_key(0))
    clock = VClock()
    sched = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=6, block_size=8, max_len=32,
        prefill_chunk=16), now_fn=clock)
    a = sched.submit([1, 2, 3, 4], 28, slo_ms=100.0)    # tight: protected
    clock.advance()
    b = sched.submit([9, 8, 7, 6], 28, slo_ms=500.0)    # loose: victim
    while sched.pending() or sched.in_flight():
        clock.advance()
        sched.tick()
    assert sched.evicted >= 1
    assert sched.stats(a).evictions == 0
    assert sched.stats(b).evictions >= 1
    assert sched.result(a) == _reference(model, params, [1, 2, 3, 4], 28)
    assert sched.result(b) == _reference(model, params, [9, 8, 7, 6], 28)
    sched.server.allocator.assert_drained()


def _fuzz_once(seed: int, model, params, random_geometry: bool,
               attn_impl: str = "gathered", prefix_cache: bool = False):
    """One fuzz round: random arrivals, prompt/output lengths, SLOs and
    (in the serve lane) pool geometry; asserts the no-leak /
    no-starvation / max_len / exact-tokens invariants after drain.  The
    core-lane round pins the geometry the parity tests already compiled,
    so it adds steps to the budgeted lane, not programs.

    With ``prefix_cache=True`` half the prompts extend one of two shared
    system prefixes (and some are exact regenerations — the full-hit +
    CoW path), so admit/decode/CoW/evict/readmit sequences run with
    blocks genuinely shared: ``assert_drained`` then pins REFCOUNTS at
    zero, token exactness pins that no stream ever read a block another
    stream wrote after its fork, and evicted+readmitted shared requests
    stay token-exact (tests/test_prefix_cache.py carries the dedicated
    counter/LRU pins)."""
    rng = np.random.default_rng(seed)
    if random_geometry:
        block_size = int(rng.choice([4, 8, 16]))
        max_len = int(rng.choice([32, 48, 64]))
    else:
        block_size, max_len = 8, 64
    slots = int(rng.integers(2, 5))
    max_blocks_per_stream = -(-max_len // block_size)
    # pool between "one stream barely fits" and "plenty": forces the
    # whole admission/eviction surface
    lo = max_blocks_per_stream + 1
    num_blocks = int(rng.integers(lo, lo + 3 * max_blocks_per_stream))
    clock = VClock()
    sched = Scheduler(model, params, ServeConfig(
        slots=slots, num_blocks=num_blocks, block_size=block_size,
        max_len=max_len, prefill_chunk=int(rng.choice([4, 8, 32])),
        queue_depth=64, attn_impl=attn_impl,
        prefix_cache=prefix_cache), now_fn=clock)
    shared_prefixes = [rng.integers(0, VOCAB, (int(ln),)).tolist()
                       for ln in (9, 14)]
    want = {}
    n_reqs = 10
    arrivals = sorted(int(t) for t in rng.integers(0, 30, n_reqs))
    submitted = 0
    tick = 0
    while submitted < n_reqs or sched.pending() or sched.in_flight():
        while submitted < n_reqs and arrivals[submitted] <= tick:
            draw = rng.random()
            if prefix_cache and draw < 0.5:
                base = shared_prefixes[int(rng.integers(0, 2))]
                sfx = rng.integers(
                    0, VOCAB, (int(rng.integers(0, 6)),)).tolist()
                prompt = base + sfx
            elif prefix_cache and draw < 0.65 and want:
                prompt = list(next(iter(want.values()))[0])  # regen
            else:
                p = int(rng.integers(1, 20))
                prompt = rng.integers(0, VOCAB, (p,)).tolist()
            p = len(prompt)
            n = int(rng.integers(1, min(max_len - p, 24) + 1))
            slo = (None if rng.random() < 0.3
                   else float(rng.integers(1, 1000)))
            rid = sched.submit(prompt, n, slo_ms=slo)
            assert rid is not None            # queue_depth 64 >> n_reqs
            want[rid] = (prompt, n)
            submitted += 1
        clock.advance()
        sched.tick()
        tick += 1
        assert tick < 5000, "starvation: not drained"
    # no leak: every block reference returned (under prefix_cache this
    # is the refcount-drain invariant — shared blocks count per reader)
    sched.server.allocator.assert_drained()
    # no starvation: every accepted request completed, with max_len and
    # length contracts intact (greedy => token-exact against the
    # single-stream reference)
    for rid, (prompt, n) in want.items():
        toks = sched.result(rid)
        assert len(toks) == len(prompt) + n
        assert len(toks) <= max_len
        assert toks == _reference(model, params, prompt, n), (
            seed, rid, prompt, n)
    return sched.evicted


def test_scheduler_fuzz_property():
    """One seeded fuzz round in the core lane (more, with random pool
    geometry, in the serve lane): random arrivals/lengths -> zero leaked
    blocks, zero starved requests, exact tokens."""
    model = _model()
    params = model.init(prng.init_key(0))
    _fuzz_once(0, model, params, random_geometry=False)


@pytest.mark.serve
@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_scheduler_fuzz_property_more_seeds(seed):
    model = _model()
    params = model.init(prng.init_key(0))
    _fuzz_once(seed, model, params, random_geometry=True)


def test_scheduler_fuzz_prefix_cache_property():
    """The shared-prefix fuzz in the core lane: admit/decode/CoW/evict/
    readmit sequences with prefix_cache on — refcounts drain to zero at
    quiesce, no stream ever reads a block another stream wrote after
    its CoW fork (token exactness + the server's in-step write-safety
    asserts), and evict/readmit under sharing keeps tokens exact."""
    model = _model()
    params = model.init(prng.init_key(0))
    _fuzz_once(0, model, params, random_geometry=False,
               prefix_cache=True)


@pytest.mark.serve
@pytest.mark.slow
@pytest.mark.parametrize("seed", [8, 9, 10])
def test_scheduler_fuzz_prefix_cache_more_seeds(seed):
    model = _model()
    params = model.init(prng.init_key(0))
    _fuzz_once(seed, model, params, random_geometry=True,
               prefix_cache=True)


@pytest.mark.serve
@pytest.mark.slow
@pytest.mark.pallas
@pytest.mark.parametrize("seed", [5, 6, 7])
def test_scheduler_fuzz_fused_kernel(seed):
    """The same no-leak / no-starvation / exact-tokens invariants with
    the Pallas paged-attention kernel active (attn_impl='fused') under
    random pool geometry — eviction, re-admission and block growth all
    hitting the kernel's table/length plumbing."""
    model = _model()
    params = model.init(prng.init_key(0))
    _fuzz_once(seed, model, params, random_geometry=True,
               attn_impl="fused")


@pytest.mark.parametrize("attn_impl", ["fused", "gathered"])
def test_attended_keys_accounting_and_records(tmp_path, attn_impl):
    """The serving-telemetry satellite: kind="serve" records carry
    attended/padded/kernel key counters whose values match the
    scheduler's block accounting exactly (single deterministic stream:
    closed-form sums), the final snapshot carries the ratio and
    ``walked_keys_share`` — the kernel's whole pages over the padded
    width under ``fused``, 1.0 under ``gathered``, with ``padded_keys``
    meaning the same under both — and metrics_summary renders them."""
    model = _model()
    params = model.init(prng.init_key(0))
    tdir = str(tmp_path / "t")
    p, n, bs = 5, 6, 8
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=20, block_size=bs, max_len=64,
        telemetry_dir=tdir, metrics_every=1, attn_impl=attn_impl))
    rid = sched.submit(list(range(1, p + 1)), n)
    sched.run_until_drained()
    sched.result(rid)
    sched.close()
    t_cap = sched.server.t_cap
    # one stream, prefill emits token 1, then n-1 decode steps at
    # positions p .. p+n-2, each attending pos+1 keys
    want_attended = sum(range(p + 1, p + n))
    want_padded = (n - 1) * t_cap
    want_kernel = sum(-(-(k) // bs) * bs for k in range(p + 1, p + n))
    assert sched.attended_keys == want_attended
    assert sched.padded_keys == want_padded
    assert sched.kernel_keys == want_kernel
    records = [json.loads(line) for line in
               open(os.path.join(tdir, "metrics.jsonl"))]
    finals = [r for r in records if r.get("kind") == "serve"
              and r.get("final")]
    assert finals and finals[-1]["attended_keys"] == want_attended
    assert finals[-1]["padded_keys"] == want_padded
    assert finals[-1]["attended_ratio"] == round(
        want_attended / want_padded, 4)
    assert finals[-1]["walked_keys_share"] == (
        round(want_kernel / want_padded, 4) if attn_impl == "fused" else 1.0)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metrics_summary", os.path.join(
            os.path.dirname(__file__), "..", "tools", "metrics_summary.py"))
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)
    summary = ms.summarize(records)
    assert "attended_ratio" in summary["serving_ticks"]
    text = ms.render_text(summary, records, None, None, None)
    assert "attended keys" in text and "walked keys share" in text


def test_telemetry_serve_records_and_heartbeat(tmp_path):
    """Serving metrics ride the PR 2 channel: kind="serve" tick records
    + kind="serve_req" completions in metrics.jsonl, and the standard
    heartbeat.json the PR 1 supervisor's staleness monitor understands."""
    model = _model()
    params = model.init(prng.init_key(0))
    tdir = str(tmp_path / "t")
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=20, block_size=8, telemetry_dir=tdir,
        metrics_every=2))
    a = sched.submit([1, 2, 3], 8)
    b = sched.submit([4, 5], 5)
    sched.run_until_drained()
    sched.close()
    records = [json.loads(line) for line in
               open(os.path.join(tdir, "metrics.jsonl"))]
    serves = [r for r in records if r["kind"] == "serve"]
    reqs = [r for r in records if r["kind"] == "serve_req"]
    assert serves and len(reqs) == 2
    assert {r["rid"] for r in reqs} == {a, b}
    for r in reqs:
        assert r["ttft_ms"] >= 0 and r["itl_ms"] >= 0
    last = serves[-1]
    assert last["completed"] == 2 and last["tokens_out"] == 13
    assert last["block_utilization"] >= 0
    # per-role heartbeat file (fleet plane): a serving process owns
    # heartbeat-serve-p<P>.json; the legacy shared path still resolves
    # through the back-compat read
    hb = json.load(open(os.path.join(tdir, "heartbeat-serve-p0.json")))
    assert hb["final"] is True and hb["step"] == sched.tick_no
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        telemetry as telemetry_lib,
    )
    legacy = telemetry_lib.read_heartbeat(
        os.path.join(tdir, "heartbeat.json"))
    assert legacy == hb
    # the stdlib summary tool renders the serving section
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metrics_summary", os.path.join(
            os.path.dirname(__file__), "..", "tools", "metrics_summary.py"))
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)
    summary = ms.summarize(records)
    assert summary["serving"]["requests"] == 2
    assert summary["serving"]["ttft_ms"]["p50"] >= 0
    text = ms.render_text(summary, records, None, None, None)
    assert "serving" in text and "ttft" in text


def test_completed_history_bounded():
    """Per-request state must not grow without bound in a long-lived
    serving process: completed Requests (and never-consumed results)
    beyond ``completed_history`` are pruned; recent ones stay readable
    for stats()/result()."""
    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=20, block_size=8, completed_history=3))
    rids = []
    for i in range(6):
        rid = sched.submit([1 + i, 2, 3], 2)
        rids.append(rid)
        sched.run_until_drained()
    assert len(sched.reqs) == 3                 # only the newest 3 kept
    assert sched.stats(rids[-1]).t_done is not None
    with pytest.raises(KeyError):
        sched.stats(rids[0])                    # pruned
    assert len(sched.result(rids[-1])) == 5
    sched.server.allocator.assert_drained()


def test_loadgen_closed_loop_smoke():
    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=40, block_size=8))
    row = run_closed_loop(sched, clients=2, requests_per_client=2,
                          vocab_size=VOCAB, prompt_lens=(2, 6),
                          max_new=(4, 8), seed=0)
    assert row["requests"] == 4
    assert row["tokens_per_sec"] > 0
    assert row["ttft_ms_p50"] is not None and row["itl_ms_p99"] is not None
    assert row["evicted"] == 0
    sched.server.allocator.assert_drained()


# ---------------------------------------------------------------------------
# drain/requeue semantics (the fleet router's replica-death contract,
# pinned in ISOLATION: one scheduler, no router, no subprocesses)
# ---------------------------------------------------------------------------

def test_drain_returns_inflight_with_consumed_state():
    """drain() hands back every unfinished request in submission order
    with its consumed-token state (prefilled/generated), leaves the
    allocator fully drained, and keeps completed results readable."""
    model = _model()
    params = model.init(prng.init_key(0))
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=17, block_size=16, prefill_chunk=8,
        queue_depth=8))
    done_rid = sched.submit([1, 2, 3], 2)       # will complete pre-drain
    mid_rid = sched.submit(list(range(1, 21)), 8)   # long prompt: will
    #                                                 be mid-prefill
    for _ in range(40):
        sched.tick()
        if sched.done(done_rid):
            break
    assert sched.done(done_rid)
    queued_rid = sched.submit([7, 8, 9], 4)
    sched.tick()
    drained = sched.drain()
    sched.server.allocator.assert_drained()
    assert sched.in_flight() == 0 and sched.pending() == 0
    by_rid = {d["rid"]: d for d in drained}
    assert set(by_rid) == {mid_rid, queued_rid}
    assert [d["rid"] for d in drained] == [mid_rid, queued_rid]  # order
    # consumed-token state: the long prompt made progress; the one
    # still queued at drain time consumed nothing
    assert 0 < by_rid[mid_rid]["prefilled"] + by_rid[mid_rid]["generated"]
    assert by_rid[queued_rid]["prefilled"] == 0
    assert by_rid[queued_rid]["generated"] == 0
    assert by_rid[mid_rid]["prompt"] == list(range(1, 21))
    # the completed request survived the drain
    assert sched.result(done_rid)[:3] == [1, 2, 3]
    sched.close()


def test_drain_readmission_reproduces_identical_tokens():
    """Re-admitting a drained request on a FRESH scheduler reproduces
    byte-identical tokens (greedy determinism — the requeue-exactness
    argument the fleet router relies on), including requests drained
    mid-decode."""
    model = _model()
    params = model.init(prng.init_key(0))
    subs = [([3, 1, 4, 1, 5], 12), (list(range(2, 14)), 14),
            ([9, 2, 6], 10)]
    refs = [_reference(model, params, p, n) for p, n in subs]
    sched = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=33, block_size=16, prefill_chunk=8,
        queue_depth=8))
    rids = [sched.submit(p, n) for p, n in subs]
    assert all(r is not None for r in rids)
    for _ in range(6):   # far enough that some streams are DECODING
        sched.tick()
    assert any(sched.server.active)   # at least one mid-decode
    drained = sched.drain()
    sched.server.allocator.assert_drained()
    assert len(drained) == len(subs)
    fresh = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=33, block_size=16, prefill_chunk=8,
        queue_depth=8))
    rid2 = {d["rid"]: fresh.submit(d["prompt"], d["max_new"],
                                   slo_ms=d["slo_ms"])
            for d in drained}
    fresh.run_until_drained()
    for old_rid, ref in zip(rids, refs):
        assert fresh.result(rid2[old_rid]) == ref
    fresh.server.allocator.assert_drained()
    sched.close()
    fresh.close()


def test_drain_with_prefix_cache_refcounts_drain():
    """drain() under prefix sharing: shared/borrowed blocks release
    through the refcount path — assert_drained (all refcounts zero)
    holds even when streams were sharing prefix blocks at drain time."""
    model = _model()
    params = model.init(prng.init_key(0))
    shared = list(range(1, 33))     # two full shared blocks
    sched = Scheduler(model, params, ServeConfig(
        slots=4, num_blocks=33, block_size=16, prefill_chunk=32,
        queue_depth=8, prefix_cache=True))
    r1 = sched.submit(shared + [40, 41], 4)
    for _ in range(4):
        sched.tick()
    r2 = sched.submit(shared + [50, 51], 4)   # prefix-matches r1's blocks
    sched.tick()
    assert not sched.done(r1) or not sched.done(r2)
    drained = sched.drain()
    sched.server.allocator.assert_drained()   # refcounts all zero
    assert {d["rid"] for d in drained} <= {r1, r2}
    sched.close()


# ---- the request boundary: rows land one program behind (ISSUE 36) ---------

def _boundary_sched(**kw):
    model = _model()
    params = model.init(prng.init_key(0))
    cfg = dict(slots=4, num_blocks=40, block_size=8, prefill_chunk=8)
    cfg.update(kw)
    return model, params, Scheduler(model, params, ServeConfig(**cfg))


@pytest.fixture
def spans(tmp_path):
    """Every span closed while the test runs, ``(name, attrs)`` (a tracer is
    what makes the listeners hear them)."""
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )

    heard = []
    listener = lambda n, t, d, a: heard.append((n, dict(a or {})))  # noqa: E731
    tracer = trace_lib.start_run(str(tmp_path / "trace"))
    trace_lib.add_listener(listener)
    yield heard
    trace_lib.remove_listener(listener)
    trace_lib.stop_run(tracer)


def test_a_rid_is_reported_a_tick_after_its_last_step_or_in_it():
    """``a`` needs 4 tokens and ``b`` 9: ``a``'s last step is dispatched in
    tick 3 (its first token and its first step came in tick 1), its slot and
    blocks are free at once, and ``tick()`` returns it in tick 4, behind
    that tick's step; ``b`` is alone when its last step is dispatched and
    comes back in that same tick."""
    model, params, sched = _boundary_sched()
    a = sched.submit([1, 2, 3], 4)
    b = sched.submit([5, 6], 9)
    seen = {}
    while sched.pending() or sched.in_flight():
        free = sched.server.free_slots()
        for rid in sched.tick():
            seen[rid] = (sched.tick_no, free)
    # a: prefilled in tick 1, steps in ticks 1-3; b: prefilled in tick 2,
    # steps in ticks 2-9
    assert seen[a][0] == 4 and seen[b][0] == 9
    # a's slot was free before the tick that reported it began
    assert seen[a][1] == 3
    assert sched.result(a) == _reference(model, params, [1, 2, 3], 4)
    assert sched.result(b) == _reference(model, params, [5, 6], 9)
    assert (sched.server.rows_landed, sched.server.rows_landed_behind) \
        == (2, 1)
    assert sched.stats(a).t_done <= sched.stats(b).t_done


def test_rows_landed_behind_on_a_scripted_schedule(staggered_batch, spans):
    """Twelve requests over three callers: every row lands with a program
    queued behind it but those taken in a tick that left nothing running
    (the drain's last, and a moment when all three callers wait at once);
    the snapshot and the ``retire`` span carry both counters, and ``land``
    is a span of its own."""
    model = _model()
    params = model.init(prng.init_key(0))
    rng = np.random.default_rng(4)
    requests = [(rng.integers(0, VOCAB, int(rng.integers(1, 20))).tolist(),
                 int(rng.integers(2, 12))) for _ in range(12)]
    sched = staggered_batch(model, params, requests, 3, slots=3,
                            num_blocks=40, block_size=8, prefill_chunk=8)
    snap = sched._snapshot()
    assert snap["rows_landed"] == 12
    # (the fixture holds the count to the ticks it saw; here, its size)
    assert 9 <= snap["rows_landed_behind"] <= 11
    stamped = [a for n, a in spans if n == "retire" and "rows_landed" in a]
    # (the fixture's one-stream schedulers stamp theirs after this one's)
    stamped = stamped[:[a["rows_landed"] for a in stamped].index(12) + 1]
    assert stamped[-1]["rows_landed_behind"] == snap["rows_landed_behind"]
    assert [a["rows_landed"] for a in stamped] == sorted(
        a["rows_landed"] for a in stamped)
    # the staggered run's ``land`` spans (the alone runs land one each)
    assert sum(1 for n, _ in spans if n == "land") >= len(stamped)


def test_a_tick_that_only_lands_rows_opens_no_decode_span(spans):
    """``a`` finishes while ``b`` is still mid prefill: the next tick runs
    one chunk of ``b`` and no step, lands ``a`` behind the chunk, and opens
    ``retire`` but no ``decode``."""
    model, params, sched = _boundary_sched(prefill_chunk=4)
    a = sched.submit([1, 2, 3], 3)
    sched.tick()                    # a prefilled, first token, step 1 of 2
    b = sched.submit(list(range(1, 25)), 4)      # six chunks of prefill
    assert sched.tick() == []       # b's chunk 1, a's last step: row taken
    assert not sched.server.any_active()
    spans.clear()
    assert sched.tick() == [a]      # b's chunk 2, no step: a lands behind it
    names = [n for n, _ in spans]
    assert "decode" not in names and "land" in names and "retire" in names
    assert (sched.server.rows_landed, sched.server.rows_landed_behind) \
        == (1, 1)
    sched.run_until_drained()
    assert sched.result(a) == _reference(model, params, [1, 2, 3], 3)
    assert sched.result(b) == _reference(model, params,
                                         list(range(1, 25)), 4)


@pytest.mark.parametrize("how", ["run_until_drained", "drain", "quiesce",
                                 "close", "evict"])
def test_nothing_stays_in_flight_once_nothing_is_dispatched(how):
    """A row taken in the last tick before the caller stops ticking is
    landed by what the caller does next, and its request is complete, not
    handed back: ``drain`` / ``quiesce`` / ``close``; ``run_until_drained``
    ends with none in flight; and after an eviction of the last running
    stream the next tick lands it all the same."""
    model, params, sched = _boundary_sched()
    a = sched.submit([1, 2, 3], 3)
    b = sched.submit([7, 8, 9, 10], 12)
    for _ in range(2):
        assert sched.tick() == []
    # a's last step was dispatched in tick 2; b runs on
    assert [r.rid for r in sched.server._in_flight] == [sched._srv_rid[a]]
    assert not sched.done(a)
    if how == "run_until_drained":
        assert sched.run_until_drained() == [a, b]
    elif how in ("drain", "quiesce"):
        back = getattr(sched, how)()
        assert [d["rid"] for d in back] == [b]
    elif how == "close":
        sched.close()
    else:
        sched._evict(b)                 # back to the head of the queue
        assert sched.tick() == [a]      # behind b's new prefill chunk
    assert not sched.server._in_flight
    assert sched.done(a) and sched.stats(a).t_done is not None
    assert sched.result(a) == _reference(model, params, [1, 2, 3], 3)
    if how not in ("evict", "close"):   # b runs on under these two
        sched.server.assert_drained()


def test_a_second_scheduler_after_prewarm_records_no_compile(tmp_path):
    """``prewarm`` runs whole requests through a throwaway scheduler, so the
    boundary's three programs (``serve_admit``, ``serve_first_token``,
    ``serve_take``: one each, whatever the prefill buckets) compile there
    with the chunks and the step: the scheduler that serves records none."""
    from neural_networks_parallel_training_with_mpi_tpu.serve import prewarm
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        compile_ledger,
    )

    model = _model(d_ff=72)     # programs no other test of this file made
    params = model.init(prng.init_key(0))
    make = lambda: Scheduler(model, params, ServeConfig(     # noqa: E731
        slots=3, num_blocks=40, block_size=8, prefill_chunk=16))
    tracer = trace_lib.start_run(str(tmp_path))
    try:
        ledger = compile_ledger.active()
        prewarm(make, prompt_lens=(1, 30))
        warm = [e["name"] for e in ledger.events]
        sched = make()
        rng = np.random.default_rng(0)
        rids = [sched.submit(rng.integers(0, VOCAB, p).tolist(), n)
                for p, n in ((1, 1), (7, 5), (16, 2), (30, 9), (11, 3))]
        sched.run_until_drained()
        assert all(sched.done(r) for r in rids)
        sched.close()
        after = [e["name"] for e in ledger.events][len(warm):]
    finally:
        trace_lib.stop_run(tracer)
    assert after == []
    count = lambda stem: sum(n.startswith(stem) for n in warm)  # noqa: E731
    assert count("serve_admit[") == 1 and count("serve_take[") == 1
    assert count("serve_first_token[") == count("serve_decode[") == 1
    assert count("serve_prefill[") == 2
