"""Paged KV cache (serve/paged_kv.py): allocator accounting + the parity
pin.

The load-bearing property: greedy paged decode — blocks allocated on
demand, prompts straddling block boundaries, strangers sharing the
batched step — must emit exactly the tokens the dense ``DecodeServer``
and the single-stream ``generate()`` emit for the same request.  The
gathered attention reduces over the same values in the same order as the
dense cache, so this is a testable contract, not a tolerance band.

Core-lane budget note: one test pins paged == generate() DIRECTLY; the
rest pin paged == dense ``DecodeServer``, which tests/test_serve.py pins
against generate() per request — the transitive chain keeps the lane off
the expensive un-jitted generate() reference (several seconds per call)
without weakening the contract."""

import jax.numpy as jnp
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.models.generate import (
    generate,
)
from neural_networks_parallel_training_with_mpi_tpu.models.serve import (
    DecodeServer,
)
from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (
    BlockAllocator, PagedDecodeServer,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

VOCAB = 64


def _model(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=64, n_layers=2, d_model=32,
                n_heads=4, d_ff=64)
    base.update(kw)
    return Transformer(TransformerConfig(**base))


def _reference(model, params, prompt, n, **kw):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32), n, **kw)
    return [int(t) for t in np.asarray(out)[0]]


def _dense_reference(model, params, prompt, n):
    """Single-stream decode through the dense slot server (its jitted
    programs are lru-cached per model config, so repeat references cost
    steps, not compiles; test_serve.py pins this path == generate())."""
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
# allocator
# ---------------------------------------------------------------------------

def test_allocator_accounting():
    a = BlockAllocator(8)                     # 7 usable, block 0 = sink
    assert a.capacity == 7 and a.free_blocks == 7
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got     # the sink is never granted
    assert a.free_blocks == 4 and a.used_blocks == 3
    assert a.alloc(5) is None                 # all-or-nothing
    assert a.free_blocks == 4                 # refused alloc took nothing
    a.free(got)
    a.assert_drained()


def test_allocator_double_free_raises():
    a = BlockAllocator(4)
    got = a.alloc(2)
    a.free(got)
    with pytest.raises(ValueError):
        a.free([got[0]])
    with pytest.raises(ValueError):
        a.free([0])                           # the sink was never granted


def test_allocator_leak_detection():
    a = BlockAllocator(4)
    a.alloc(1)
    with pytest.raises(AssertionError):
        a.assert_drained()


def test_sink_pool_minimum():
    with pytest.raises(ValueError):
        BlockAllocator(1)                     # sink-only pool is unusable


# ---------------------------------------------------------------------------
# parity pin: paged == dense DecodeServer == generate (greedy)
# ---------------------------------------------------------------------------

def test_paged_matches_generate_directly():
    """The one direct generate() pin (the rest chain through the dense
    server): single request, blocks grown on demand across boundaries."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8)
    rid = srv.try_admit([1, 2, 3], 10)
    got = _drain(srv, rid)
    assert got == _reference(model, params, [1, 2, 3], 10)
    assert got == _dense_reference(model, params, [1, 2, 3], 10)
    srv.allocator.assert_drained()


def test_staggered_straddling_admissions_exact():
    """Requests joining mid-flight with ragged lengths — including an
    11-token prompt prefilled in width-4 chunks, straddling the 8-token
    block boundary mid-chunk — each token-identical to its single-stream
    decode, and every block back in the pool after the drain."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8)
    straddle = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]
    reqs = {}
    a = srv.try_admit(straddle, 12)
    while not srv.prefill_step(a, 4):         # chunks split mid-block
        pass
    reqs[a] = (straddle, 12)
    srv.step(); srv.step()
    b = srv.try_admit([7, 8], 6)
    while not srv.prefill_step(b, 16):
        pass
    reqs[b] = ([7, 8], 6)
    srv.step()
    c = srv.try_admit([5, 9, 11, 13], 9)
    while not srv.prefill_step(c, 16):
        pass
    reqs[c] = ([5, 9, 11, 13], 9)
    for _ in range(40):
        srv.step()
        if all(srv.done(r) for r in reqs):
            break
    for rid, (prompt, n) in reqs.items():
        assert srv.result(rid) == _dense_reference(model, params, prompt,
                                                   n), rid
    srv.allocator.assert_drained()


def test_evict_then_rerun_reproduces_tokens():
    """Eviction discards device state; a greedy re-run of the same
    request must reproduce the same tokens (the scheduler's requeue
    correctness hinges on this)."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=40,
                            block_size=8)
    rid = srv.try_admit([4, 5, 6], 10)
    while not srv.prefill_step(rid, 16):
        pass
    srv.step(); srv.step(); srv.step()        # mid-flight
    prompt, max_new = srv.evict(rid)
    srv.allocator.assert_drained()            # eviction freed everything
    rid2 = srv.try_admit(prompt, max_new)
    assert _drain(srv, rid2) == _dense_reference(model, params, [4, 5, 6],
                                                 10)


def test_unservable_request_raises():
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=3,
                            block_size=8, max_len=64)
    with pytest.raises(ValueError):           # needs 3 blocks, pool has 2
        srv.try_admit([1] * 8, 16)
    with pytest.raises(ValueError):
        srv.try_admit([1] * 60, 8)            # over max_len
    with pytest.raises(ValueError):
        srv.try_admit([], 4)


def test_capacity_beats_dense_at_equal_memory():
    """The tentpole claim at unit scale: the same cache positions, paged
    into blocks, admit MORE short concurrent streams than dense slots
    (counted by admitting until refusal)."""
    model = _model()
    params = model.init(prng.init_key(0))
    dense = DecodeServer(model, params, slots=2, max_len=64)
    dense_cap = 0
    while dense.submit([1, 2, 3, 4], 4) is not None:
        dense_cap += 1
    # equal cache positions: 2 slots x 64 = 128 = 16 blocks of 8 (+ sink)
    paged = PagedDecodeServer(model, params, slots=16, num_blocks=17,
                              block_size=8, max_len=64)
    paged_cap = 0
    while paged.try_admit([1, 2, 3, 4], 4) is not None:
        paged_cap += 1
    assert dense_cap == 2
    assert paged_cap > 2 * dense_cap, (dense_cap, paged_cap)


def test_dense_server_sync_flag_identical():
    """The host-sync satellite fix: completion from host-tracked
    positions must behave exactly like the legacy per-step device fetch
    (same tokens, same completion steps)."""
    model = _model()
    params = model.init(prng.init_key(0))
    outs = []
    for sync in (False, True):
        srv = DecodeServer(model, params, slots=2, sync_per_step=sync)
        a = srv.submit([1, 2, 3], max_new_tokens=7)
        srv.step(); srv.step()
        b = srv.submit([9, 4], max_new_tokens=5)
        steps = 0
        while not (srv.done(a) and srv.done(b)):
            srv.step()
            steps += 1
            assert steps < 30
        outs.append((srv.result(a), srv.result(b), steps))
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# model-variant parity (full lane: each is a fresh compile of the paged
# programs for a different config)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_gqa_paged_exact():
    model = _model(n_kv_heads=2)
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=20,
                            block_size=8)
    rid = srv.try_admit([1, 2, 3], 8)
    assert _drain(srv, rid) == _reference(model, params, [1, 2, 3], 8)


@pytest.mark.slow
def test_int8_kv_paged_exact():
    """kv_quant pools quantize per (position, head) — identical
    quantization points to the dense int8 cache, so tokens match the
    kv_quant single-stream decode exactly even with prefill chunks and
    block boundaries in different places."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=20,
                            block_size=8, kv_quant=True)
    assert srv.pools[0]["k"].dtype == jnp.int8
    rid = srv.try_admit([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 8)
    got = _drain(srv, rid, prefill_width=4)
    assert got == _reference(model, params, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10],
                             8, kv_quant=True)


@pytest.mark.slow
def test_scan_layers_paged_exact():
    model = _model(scan_layers=True)
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=20,
                            block_size=8)
    rid = srv.try_admit([9, 8, 7], 6)
    assert _drain(srv, rid) == _reference(model, params, [9, 8, 7], 6)


@pytest.mark.slow
def test_rope_paged_exact():
    """RoPE rotates at absolute positions; paging must not disturb them
    (chunked prefill at width 4 splits blocks and rotation windows)."""
    model = _model(pos_encoding="rope")
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=20,
                            block_size=8)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    rid = srv.try_admit(prompt, 8)
    assert _drain(srv, rid, prefill_width=4) == _reference(
        model, params, prompt, 8)


# ---------------------------------------------------------------------------
# two kinds of cache in one manager: window layers beside full ones
# ---------------------------------------------------------------------------

WINDOW = 8


def _window_model(**kw):
    """Two window layers and a full one (pattern ``LLG``), rotary."""
    return _model(n_layers=3, n_kv_heads=2, pos_encoding="rope",
                  max_seq_len=512, attention_pattern="LLG",
                  sliding_window=WINDOW, **kw)


def _pool_bytes(srv):
    return [sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in pool.values()) for pool in srv.pools]


def test_window_pools_do_not_grow_with_max_len():
    """A window layer's pool follows from slots, block size, window and the
    widest chunk; a full layer's from ``num_blocks``.  Neither reads
    ``max_len``, and the window pools are within the bound of slots x
    (window + prefill_chunk + 2 x block_size) positions."""
    from neural_networks_parallel_training_with_mpi_tpu.serve import paged_kv

    model = _window_model()
    params = model.init(prng.init_key(0))
    sizes = {}
    for max_len in (64, 256, 512):
        srv = PagedDecodeServer(model, params, slots=4, num_blocks=200,
                                block_size=4, max_len=max_len,
                                prefill_chunk=16)
        sizes[max_len] = _pool_bytes(srv)
        assert srv.window_tables.shape == srv.tables.shape
    assert sizes[64] == sizes[256] == sizes[512]
    window_layer, full_layer = sizes[64][0], sizes[64][2]
    assert sizes[64][1] == window_layer < full_layer
    blocks = paged_kv.window_pool_blocks(WINDOW, 4, 4, 16)
    assert blocks == 1 + 4 * 3 + 4            # sink, 3 pages a slot, a chunk
    assert (blocks - 1) * 4 <= 4 * (WINDOW + 16 + 2 * 4)
    row = 2 * 2 * 8 * 4                       # K and V, 2 heads of 8, f32
    assert window_layer == blocks * 4 * row
    assert full_layer == 200 * 4 * row
    # more slots or a wider chunk do move it; a model without window layers
    # has one kind of cache and no second allocator
    assert paged_kv.window_pool_blocks(WINDOW, 8, 4, 16) > blocks
    assert paged_kv.window_pool_blocks(WINDOW, 4, 4, 64) > blocks
    plain = PagedDecodeServer(_model(), _model().init(prng.init_key(0)),
                              slots=2, num_blocks=9, block_size=4)
    assert plain.window is None and plain.window_allocator is None


def test_a_stream_of_fifty_windows_holds_a_windows_blocks():
    """Prefill in chunks longer than the window, then decode to 50 windows:
    at no point does the stream hold more window-kind blocks than the bound
    (a window's pages between programs, a chunk's more while one is in
    flight), its full-kind blocks grow with its length, and the tokens are
    the gathered path's whatever the kernel does."""
    model = _window_model()
    params = model.init(prng.init_key(0))
    steady = -(-(WINDOW - 1) // 4) + 1                  # 3 pages
    outs = {}
    for impl in ("gathered", "fused"):
        srv = PagedDecodeServer(model, params, slots=2, num_blocks=120,
                                block_size=4, max_len=50 * WINDOW + 8,
                                prefill_chunk=16, attn_impl=impl)
        rid = srv.try_admit(list(range(1, 41)), 50 * WINDOW - 40)
        st = srv._streams[rid]
        held = []
        while not srv.prefill_step(rid, 16):
            held.append(len(st.window_pages))
        while not srv.done(rid):
            held.append(len(st.window_pages))
            assert srv.window_allocator.used_blocks == len(st.window_pages)
            srv.step()
        assert max(held) <= steady and min(held) >= 1
        assert srv.allocator.used_blocks == 0       # finished and released
        outs[impl] = srv.result(rid)
        assert len(outs[impl]) == 50 * WINDOW
        srv.assert_drained()
        # the counters: 1 full layer reads every key, 2 window layers 8
        ticks = 50 * WINDOW - 40 - 1
        lens = np.arange(41, 41 + ticks)
        assert srv.attention_counters["full_keys"] == lens.sum()
        assert srv.attention_counters["window_keys"] == 2 * WINDOW * ticks
        assert srv.attention_counters["window_blocks_held"] <= (
            2 * steady * ticks)
    assert outs["fused"] == outs["gathered"]


def test_both_kinds_drain_after_churn_eviction_and_readmission():
    """Admission, chunked prefill, growth across blocks, an eviction mid
    decode and mid prefill, re-admission: both allocators drain, an evicted
    stream's re-run gives the tokens of an undisturbed run, and the window
    kind never refuses (its pool covers every slot's window and one chunk
    whatever the full kind's pressure)."""
    model = _window_model()
    params = model.init(prng.init_key(0))
    prompts = [list(range(3, 40)), [7] * 21, list(range(50, 59))]

    def quiet(prompt, n):
        srv = PagedDecodeServer(model, params, slots=3, num_blocks=60,
                                block_size=4, max_len=96, prefill_chunk=16)
        return _drain(srv, srv.try_admit(prompt, n))

    want = [quiet(p, 12) for p in prompts]
    srv = PagedDecodeServer(model, params, slots=3, num_blocks=60,
                            block_size=4, max_len=96, prefill_chunk=16)
    a, b = (srv.try_admit(p, 12) for p in prompts[:2])
    while not srv.prefill_step(a, 16):
        pass
    srv.prefill_step(b, 16)                     # b is mid prefill
    for _ in range(5):
        srv.step()
    assert srv.evict(a) == (prompts[0], 12)     # mid decode
    assert srv.evict(b) == (prompts[1], 12)     # mid prefill
    srv.assert_drained()
    rids = [srv.try_admit(p, 12) for p in prompts]
    for rid in rids:
        while not srv.prefill_step(rid, 16):
            pass
        # the chunk is trimmed behind the window as soon as it is sent
        assert len(srv._streams[rid].window_pages) <= 3
    while not all(srv.done(r) for r in rids):
        srv.step()
    assert [srv.result(r) for r in rids] == want
    srv.assert_drained()
    assert srv.window_allocator.free_blocks \
        == srv.window_allocator.capacity
    # a chunk wider than the server was built for is cut to it, not refused
    rid = srv.try_admit(list(range(1, 60)), 2)
    srv.prefill_step(rid, 64)
    assert srv._streams[rid].prefilled == 16
    srv.evict(rid)
    srv.assert_drained()


def test_window_table_churn_never_recompiles():
    """Both kinds' tables are traced operands: admission, growth, the
    window's release behind itself, eviction and re-admission re-run one
    decode program and one prefill program a bucket."""
    model = _window_model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=3, num_blocks=60,
                            block_size=4, max_len=96, prefill_chunk=16)
    a = srv.try_admit([1] * 30, 20)
    while not srv.prefill_step(a, 16):
        pass
    for _ in range(4):
        srv.step()
    n_step, n_prefill = (srv._step_fn._cache_size(),
                         srv._prefill_fn._cache_size())
    b = srv.try_admit([9] * 27, 20)
    while not srv.prefill_step(b, 16):
        pass
    for _ in range(10):                 # several pages released behind
        srv.step()
    srv.evict(b)
    c = srv.try_admit([3] * 25, 6)
    while not srv.prefill_step(c, 16):
        pass
    while not (srv.done(a) and srv.done(c)):
        srv.step()
    srv.assert_drained()
    assert srv._step_fn._cache_size() == n_step
    assert srv._prefill_fn._cache_size() == n_prefill


def test_the_ledger_names_both_kinds_walks():
    """The compile ledger's event of a serving program of a model with
    window layers says what implements each kind's attention: the full walk,
    and beside it the walk bounded by the window with its own tiling."""
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        compile_ledger,
    )

    model = _window_model()
    params = model.init(prng.init_key(0))
    led = compile_ledger.Ledger(None)
    compile_ledger.install(led)
    try:
        # a geometry no other test of this file uses: the programs are new
        srv = PagedDecodeServer(model, params, slots=2, num_blocks=30,
                                block_size=4, max_len=56, prefill_chunk=16,
                                attn_impl="fused")
        _drain(srv, srv.try_admit([5] * 20, 6))
        gathered = PagedDecodeServer(model, params, slots=2, num_blocks=30,
                                     block_size=4, max_len=56,
                                     prefill_chunk=16, attn_impl="gathered")
        _drain(gathered, gathered.try_admit([5] * 20, 6))
    finally:
        compile_ledger.install(None)
    decode = led.events_for("serve_decode[bs4x14/fused]")
    assert len(decode) == 1 and decode[0]["attention"] == {
        "impl": "paged", "pages": 14, "tile_cols": 1, "block_size": 4,
        "window": {"impl": "paged", "window": WINDOW, "pages": 3,
                   "tile_cols": 1}}
    prefill = led.events_for("serve_prefill[bs4x14/fused]")
    assert prefill and all(e["attention"]["window"]["window"] == WINDOW
                           for e in prefill)
    assert led.events_for("serve_decode[bs4x14/gathered]")[0]["attention"] \
        == {"impl": "gathered", "keys": 56}
