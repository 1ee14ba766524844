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


# ---------------------------------------------------------------------------
# state that is not paged: a mixer beside the attention (models/ssm.py)
# ---------------------------------------------------------------------------

def _hybrid(dtype="float32"):
    """The toy of tests/test_ssm_hybrid_model.py with seeded weights from its
    family: (program model, program params, the family's model dict, the
    reference's tensors)."""
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark.families import ssm_attn_parallel as ref
    from benchmark.harness import weights

    model = {"vocab_size": 96, "d_model": 48, "n_layers": 3, "n_heads": 4,
             "n_kv_heads": 2, "head_dim": 16, "d_ff": 80, "d_ssm": 48,
             "ssm_heads": 4, "ssm_head_dim": 12, "ssm_state": 16,
             "ssm_groups": 2, "ssm_conv": 4, "ssm_chunk": 8,
             "max_seq_len": 128, "rms_eps": 1e-5, "rope_theta": 1e11,
             "embedding_multiplier": 5.657, "lm_head_multiplier": 0.0625,
             "attention_in_multiplier": 0.8,
             "attention_out_multiplier": 0.3, "key_multiplier": 0.11,
             "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.35,
             "ssm_multipliers": [0.354, 0.25, 0.177, 0.5, 0.354],
             "mlp_multipliers": [0.177, 0.4], "param_dtype": dtype,
             "compute_dtype": dtype, "family": ref, "config": "toy"}
    maker = weights.Maker(model, 5)
    outer, layers = maker.outer(), maker.layers()
    return (ref.program_model(model), ref.to_program(model, outer, layers),
            model, (outer, layers))


def _hybrid_server(net, params, **kw):
    base = dict(slots=3, num_blocks=40, block_size=4, max_len=64,
                prefill_chunk=8)
    base.update(kw)
    return PagedDecodeServer(net, params, **base)


def _record_prefill_logits(srv, into):
    """Keep every prefill chunk's logits: the ``(V,)`` row of its last true
    column where the chunk was its prompt's last, zeros otherwise (the
    program runs the head on the one column the server reads)."""
    inner = srv._prefill_fn

    def recording(*args):
        out = inner(*args)
        into.append(np.asarray(out[0][0], np.float32))
        return out

    srv._prefill_fn = recording


def _prefix_logits(srv, prompt, width, between=lambda: None):
    """The logits at every position of ``prompt``, through the server:
    position ``n - 1`` is the last true column of the last chunk of the
    prefix ``prompt[:n]``, so each prefix is prefilled as a request of its
    own (one token, gone at once); ``between`` runs between its chunks."""
    rows, out, inner = [], [], srv._prefill_fn
    _record_prefill_logits(srv, rows)
    for n in range(1, len(prompt) + 1):
        rid = srv.try_admit(prompt[:n], 1)
        while not srv.prefill_step(rid, width):
            assert not rows[-1].any()   # not the last chunk: no head ran
            between()
        out.append(rows[-1])
        srv.land(every=True)
    srv._prefill_fn = inner
    return np.stack(out)


def _state_rows(srv, slot):
    import jax

    return [{n: np.asarray(v[slot]) for n, v in layer.items()}
            for layer in jax.device_get(srv.state)]


# float32: program and reference differ in the order of float32 sums (the
# chunked recurrence against the step-by-step one, the gathered attention
# against the blocked one): 2e-5 of the logits' scale, observed 7e-7.
# bfloat16: every projection rounds its operands and its result to 8 bits of
# mantissa (2^-9 = 2e-3 each), some ten of them a layer over three layers,
# and the mixer's chunk products round ``xs``, ``B``, ``C`` and the masked
# decays once more: 3e-2 of the scale, observed 1.6e-2; a state that is stale
# moves the logits by more than 1e-1 of it (the planted fault of the next
# test).
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_chunked_prefill_then_decode_against_the_full_forward(dtype, tol):
    """A prompt of 21 in chunks of 8 (8, 8 and 5 true columns of a bucket
    of 8), then 9 decode ticks through ``PagedDecodeServer`` with a stranger
    beside it: the logits of every prompt position (each the last true
    column of a prefix's last chunk, the stranger decoding between chunks),
    and of the positions that predicted the served tokens, against the
    reference's full forward pass over prompt + served tokens."""
    import jax

    net, params, model, (outer, layers) = _hybrid(dtype)
    ref = model["family"]
    srv = _hybrid_server(net, params)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 96, size=21).tolist()
    other = srv.try_admit(rng.integers(0, 96, size=6).tolist(), 56)
    while not srv.prefill_step(other, 8):
        pass
    got = _prefix_logits(srv, prompt, 8, between=srv.step)
    chunks = []
    _record_prefill_logits(srv, chunks)
    rid = srv.try_admit(prompt, 10)
    while not srv.prefill_step(rid, 8):
        srv.step()                      # the stranger decodes between chunks
    assert [c.shape for c in chunks] == [(96,)] * 3
    assert not chunks[0].any() and not chunks[1].any()
    assert (chunks[2] == got[20]).all()
    while not srv.done(rid):
        srv.step()
    served = srv.result(rid)
    assert served[:21] == prompt and len(served) == 31
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jax.tree_util.tree_map(                 # noqa: E731
            lambda a: a.astype(jnp.float32), t)
        x = ref.embed(model, f32(outer), np.asarray([served]))
        for i, p in enumerate(layers):
            x = ref.block(model, f32(p), x, i)
        want = np.asarray(ref.head_logits(model, f32(outer), x))[0]
    scale = np.abs(want).max()
    assert np.abs(got - want[:21]).max() <= tol * scale
    # each served token is the reference's best at its position, or lies
    # within the tolerance of it (greedy; rounding may swap near ties)
    for t in range(21, 31):
        row = want[t - 1]
        assert row.max() - row[served[t]] <= 2 * tol * scale, t
    # three mixer layers: the stranger, the 21 prefixes, the prompt
    assert srv.ssm_counters["ssm_prefill_tokens"] == 3 * (
        6 + sum(range(1, 22)) + 21)


def test_a_slot_admitted_again_starts_from_zero_state(monkeypatch):
    """One slot, two streams one after the other: the second's first chunk
    leaves the state a fresh server's leaves, bit for bit, and its tokens
    are a fresh server's.  With the reset skipped (the planted fault) the
    state after that chunk is another one and the logits move by more than
    a tenth of their scale."""
    net, params, _model, _t = _hybrid()
    first, second = list(range(5, 30)), list(range(40, 52))

    def second_after_first(srv, logits):
        _drain(srv, srv.try_admit(first, 6), 8)
        srv.assert_drained()
        rid = srv.try_admit(second, 6)
        assert srv._slot_of[rid] == 0 and srv.state_rows == {0: rid}
        _record_prefill_logits(srv, logits)
        srv.prefill_step(rid, 8)
        rows = _state_rows(srv, 0)
        return rows, _drain(srv, rid, 8)

    fresh = _hybrid_server(net, params, slots=1)
    rid = fresh.try_admit(second, 6)
    want_logits = []
    _record_prefill_logits(fresh, want_logits)
    fresh.prefill_step(rid, 8)
    want_rows, want = _state_rows(fresh, 0), _drain(fresh, rid, 8)

    logits = []
    rows, got = second_after_first(_hybrid_server(net, params, slots=1),
                                   logits)
    assert got == want
    for mine, theirs in zip(rows, want_rows):
        for name in mine:
            assert (mine[name] == theirs[name]).all(), name
    # the first chunk (8 of 12) ran no head; the last one's row is read
    assert not logits[0].any() and want_logits[1].any()
    assert (logits[1] == want_logits[1]).all()

    def skipped(self, slot, rid):       # the fault: the row is not zeroed
        self.state_rows[slot] = rid

    monkeypatch.setattr(PagedDecodeServer, "_reset_state", skipped)
    logits = []
    rows, _ = second_after_first(_hybrid_server(net, params, slots=1), logits)
    assert not (rows[0]["ssm"] == want_rows[0]["ssm"]).all()
    moved = np.abs(logits[1] - want_logits[1]).max()
    assert moved > 0.1 * np.abs(want_logits[1]).max()


def test_idle_lanes_and_pad_columns_leave_state_and_tail_as_they_were():
    """A decode tick moves the decoding lane's rows alone: a lane that is
    mid prefill and a free lane with planted rows keep state and tail bit
    for bit; a prefill chunk moves its own slot's rows alone; and what a
    chunk's pad columns hold does not reach state, tail or pool."""
    import jax

    net, params, _model, _t = _hybrid()
    srv = _hybrid_server(net, params)
    a = srv.try_admit(list(range(1, 12)), 20)
    while not srv.prefill_step(a, 8):
        pass
    b = srv.try_admit(list(range(20, 45)), 5)
    srv.prefill_step(b, 8)                      # mid prefill: 8 of 25
    assert (srv._slot_of[a], srv._slot_of[b]) == (0, 1)
    srv.state = jax.tree_util.tree_map(         # the free lane, planted
        lambda s: s.at[2].set(0.25), srv.state)
    before = [_state_rows(srv, s) for s in range(3)]
    for _ in range(3):
        srv.step()
    after = [_state_rows(srv, s) for s in range(3)]
    for layer in range(3):
        for name in ("conv", "ssm"):
            assert not (after[0][layer][name]
                        == before[0][layer][name]).all()
            for idle in (1, 2):
                assert (after[idle][layer][name]
                        == before[idle][layer][name]).all(), (idle, name)
    srv.prefill_step(b, 8)                      # 16 of 25: b's rows alone
    later = [_state_rows(srv, s) for s in range(3)]
    for layer in range(3):
        assert not (later[1][layer]["ssm"] == after[1][layer]["ssm"]).all()
        for other in (0, 2):
            for name in ("conv", "ssm"):
                assert (later[other][layer][name]
                        == after[other][layer][name]).all()

    def one_chunk(pad_id):
        """A chunk of 5 true columns in a bucket of 8 whose pad columns
        hold ``pad_id``, through the prefill program itself."""
        one = _hybrid_server(net, params, slots=1)
        rid = one.try_admit([3, 1, 4, 1, 5], 2)
        chunk = np.full((1, 8), pad_id, np.int32)
        chunk[0, :5] = [3, 1, 4, 1, 5]
        _logits, one.pools, one.state, one.stats = one._prefill_fn(
            one.params, one.pools, one.state, one.stats,
            one._device_tables(slice(0, 1)), jnp.asarray(0, jnp.int32),
            jnp.asarray([0], jnp.int32), jnp.asarray(chunk),
            jnp.asarray(5, jnp.int32), jnp.asarray(True))
        blocks = one._streams[rid].blocks
        return _state_rows(one, 0), [
            np.asarray(pool["k"])[blocks] for pool in one.pools]

    (rows0, pools0), (rows9, pools9) = one_chunk(0), one_chunk(77)
    for r0, r9, p0, p9 in zip(rows0, rows9, pools0, pools9):
        assert (r0["ssm"] == r9["ssm"]).all()
        assert (r0["conv"] == r9["conv"]).all() and (p0 == p9).all()
    # the tail holds the last three TRUE inputs, not the bucket's last three
    assert np.abs(rows0[0]["conv"]).min() > 0


def test_evicted_and_readmitted_the_state_is_rebuilt_by_prefill():
    """A stream evicted mid decode and admitted again prefills its prompt
    anew from a zero state: the logits of its last chunk and its tokens are
    those of the undisturbed run, bit for bit; the state row went with the
    slot and came back with the admission."""
    net, params, _model, _t = _hybrid()
    prompt = list(range(7, 26))
    quiet = _hybrid_server(net, params)
    want_logits = []
    _record_prefill_logits(quiet, want_logits)
    want = _drain(quiet, quiet.try_admit(prompt, 12), 8)

    srv = _hybrid_server(net, params)
    logits = []
    _record_prefill_logits(srv, logits)
    other = srv.try_admit([9] * 5, 40)          # takes slot 0
    while not srv.prefill_step(other, 8):
        pass
    logits.clear()
    rid = srv.try_admit(prompt, 12)
    while not srv.prefill_step(rid, 8):
        pass
    for _ in range(4):
        srv.step()
    slot = srv._slot_of[rid]
    assert srv.evict(rid) == (prompt, 12) and slot not in srv.state_rows
    logits.clear()
    again = srv.try_admit(prompt, 12)
    assert srv.state_rows[srv._slot_of[again]] == again
    got = _drain(srv, again, 8)
    assert got == want
    assert len(logits) == len(want_logits) == 3
    # the last chunk's one row, which every earlier chunk's state reaches
    assert not logits[0].any() and not logits[1].any()
    assert want_logits[2].any() and (logits[2] == want_logits[2]).all()


def test_a_drained_server_holds_no_state_row(tmp_path):
    """``quiesce`` evicts everything and proves it: no block of any kind and
    no row of the state store is held; a row left behind is named.  The
    mixers' counters ride the ``retire`` spans and the serve records."""
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )

    net, params, _model, _t = _hybrid()
    spans = []
    listener = lambda n, t, d, a: spans.append((n, dict(a or {})))  # noqa: E731
    tracer = trace_lib.start_run(str(tmp_path))
    trace_lib.add_listener(listener)
    try:
        sched = Scheduler(net, params, ServeConfig(
            slots=3, block_size=4, num_blocks=40, max_len=64,
            prefill_chunk=8))
        rng = np.random.default_rng(1)
        for n in (13, 30, 7, 22, 9):
            sched.submit(rng.integers(0, 96, size=n).tolist(), 8)
        for _ in range(14):
            sched.tick()
        assert sched.server.state_rows
        sched.quiesce()
        sched.server.assert_drained()
        assert not sched.server.state_rows
    finally:
        trace_lib.remove_listener(listener)
        trace_lib.stop_run(tracer)
    stamped = [a for n, a in spans
               if n == "retire" and "ssm_state_updates" in a]
    assert stamped and set(stamped[-1]) >= {
        "ssm_state_updates", "ssm_prefill_tokens", "decode_ticks_counted",
        "prefill_chunks_counted"}
    snap = sched._snapshot()
    assert snap["ssm_state_updates"] == stamped[-1]["ssm_state_updates"] > 0
    # 3 mixer layers: a tick updates 3 rows a decoding stream
    assert snap["ssm_state_updates"] % 3 == 0
    sched.server.state_rows[1] = 99
    with pytest.raises(AssertionError, match="state leak.*1: 99"):
        sched.server.assert_drained()


# ---------------------------------------------------------------------------
# the request boundary (ISSUE 36): a finished row lands one program behind
# ---------------------------------------------------------------------------

def _two_streams(a_new=3, b_new=12):
    """``a`` and ``b`` prefilled side by side in slots 0 and 1."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=24,
                            block_size=8)
    a = srv.try_admit([1, 2, 3], a_new)
    b = srv.try_admit([9, 8, 7, 6, 5], b_new)
    for rid in (a, b):
        while not srv.prefill_step(rid, 16):
            pass
    return model, params, srv, a, b


def test_step_reports_a_row_one_step_later_while_others_run():
    """``a``'s last token is made by step 2: that call reports nothing, the
    slot and the blocks are free when it returns, ``done`` says no (and does
    not raise) while the row is in flight, and step 3 reports ``a``.  ``b``
    is alone at its last step and comes back from that very call."""
    model, params, srv, a, b = _two_streams()
    used = srv.allocator.used_blocks
    assert srv.step() == []
    assert srv.step() == [] and not srv.done(a) and not srv.holds(a)
    assert srv.free_slots() == 1 and srv.allocator.used_blocks == used - 1
    assert [r.rid for r in srv._in_flight] == [a]
    assert srv.step() == [a] and srv.done(a)
    assert srv.result(a) == _dense_reference(model, params, [1, 2, 3], 3)
    out = []
    while not out:
        out = srv.step()
    assert out == [b] and not srv._in_flight
    assert srv.result(b) == _dense_reference(model, params,
                                             [9, 8, 7, 6, 5], 12)
    assert (srv.rows_landed, srv.rows_landed_behind) == (2, 1)
    srv.assert_drained()
    with pytest.raises(KeyError):
        srv.done(a)


def test_a_slot_admitted_again_does_not_disturb_the_row_in_flight():
    """``a``'s slot is taken by ``c`` while ``a``'s row is in flight: the
    admission overwrites the slot's token row and position, ``c``'s chunk
    and first token are written, and ``a`` still lands with its own tokens
    (the take ran before them on the device, into a buffer of its own)."""
    model, params, srv, a, b = _two_streams()
    srv.step(); srv.step()                      # a's row taken, in flight
    slot = 0
    c = srv.try_admit([4] * 11, 6)
    assert srv._slot_of[c] == slot and not srv.done(a)
    while not srv.prefill_step(c, 16):
        pass
    assert srv.step() == [a]
    assert srv.result(a) == _dense_reference(model, params, [1, 2, 3], 3)
    while not (srv.done(b) and srv.done(c)):
        srv.step()
    assert srv.result(c) == _dense_reference(model, params, [4] * 11, 6)
    assert srv.result(b) == _dense_reference(model, params,
                                             [9, 8, 7, 6, 5], 12)
    srv.assert_drained()


def test_evicting_the_last_stream_leaves_nothing_to_wait_behind():
    """With ``b`` evicted nothing is active: ``step`` dispatches no program
    and lands ``a`` at once (no later program behind it: not counted as
    ``behind``); a step with nothing in flight and nothing active is a
    no-op."""
    model, params, srv, a, b = _two_streams()
    srv.step(); srv.step()
    srv.evict(b)
    assert srv.step() == [a]
    assert (srv.rows_landed, srv.rows_landed_behind) == (1, 0)
    assert srv.result(a) == _dense_reference(model, params, [1, 2, 3], 3)
    assert srv.step() == [] and srv.land() == [] and srv.land(every=True) == []
    srv.assert_drained()


def test_a_mid_prefill_stream_does_not_hold_a_row_in_flight_for_ever():
    """``b`` is admitted and never prefilled: ``a``'s row stays in flight
    after its last step (a chunk of ``b`` could still be queued behind it),
    and the next ``step``, which dispatches nothing, lands it."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=24,
                            block_size=8)
    a = srv.try_admit([1, 2, 3], 2)
    b = srv.try_admit([9, 8, 7, 6, 5], 4)
    while not srv.prefill_step(a, 16):
        pass
    assert srv.step() == [] and not srv.done(a)
    assert srv.step() == [a]
    assert (srv.rows_landed, srv.rows_landed_behind) == (1, 0)
    # a chunk dispatched behind the row counts
    c = srv.try_admit([3, 3, 3], 2)
    while not srv.prefill_step(c, 16):
        pass
    assert srv.step() == []
    srv.prefill_step(b, 4)
    assert srv.land() == [c]
    assert (srv.rows_landed, srv.rows_landed_behind) == (2, 1)


def test_a_single_token_request_is_taken_at_its_prefill():
    """``max_new`` 1: the prefill's first token is the whole answer, the row
    is taken behind the chunk and lands with the next call."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=24,
                            block_size=8)
    rid = srv.try_admit([4, 5, 6], 1)
    assert srv.prefill_step(rid, 16)
    assert not srv.done(rid) and srv.free_slots() == 2
    assert srv.step() == [rid]
    assert srv.result(rid) == _dense_reference(model, params, [4, 5, 6], 1)


def test_the_first_token_program_samples_as_the_eager_lines_did():
    """The program that ends a prefill (``serve_first_token``) against the
    lines it replaced, written out here: the same token from the same key,
    the same next key, the same row and position, at a temperature and a
    ``top_k`` that make the key matter."""
    import jax

    from neural_networks_parallel_training_with_mpi_tpu.models.generate import (
        _sample,
    )

    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=24,
                            block_size=8, temperature=0.9, top_k=12, seed=3)
    seen = []
    prefill = srv._prefill_fn
    srv._prefill_fn = lambda *a: seen.append(prefill(*a)) or seen[-1]
    prompt = [5, 9, 11, 13, 2, 2, 7]
    srv.try_admit([1, 2], 5)
    rid = srv.try_admit(prompt, 5)
    slot, p = srv._slot_of[rid], len(prompt)
    key = srv.key
    tokens = np.asarray(srv.tokens).copy()
    assert srv.prefill_step(rid, 16)
    logits = seen[-1][0]                # (1, V): the last true column's
    first, next_key = _sample(logits, 0.9, key, 12, 1.0)
    tokens[slot, p] = int(first[0])
    assert (np.asarray(srv.tokens) == tokens).all()
    assert (np.asarray(srv.pos) == [0, p]).all()
    assert (jax.random.key_data(srv.key)
            == jax.random.key_data(next_key)).all()
    assert int(first[0]) != int(np.asarray(logits)[0].argmax())


# ---------------------------------------------------------------------------
# the chunk's head (ISSUE 38): one column, and only a prompt's last chunk
# ---------------------------------------------------------------------------

FAMILIES = ["per_head", "latent", "window", "recurrent"]


def _family_toy(family):
    """One toy a kind of cache row: per-head K and V, the latent row (with
    routing without drops), window and full layers over two pools, and the
    recurrent state beside the pool; float32."""
    if family == "recurrent":
        return _hybrid()[:2]
    if family == "latent":
        import sys
        from pathlib import Path

        root = Path(__file__).resolve().parents[1]
        if str(root) not in sys.path:
            sys.path.insert(0, str(root))
        from benchmark.families import mla_moe

        rope = {"beta_fast": 4, "beta_slow": 1, "factor": 4,
                "llama_4_scaling_beta": 0.1, "mscale": 1,
                "mscale_all_dim": 1, "original_max_position_embeddings": 16}
        net = mla_moe.program_model({
            "vocab_size": 96, "d_model": 48, "n_layers": 2, "n_heads": 4,
            "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
            "qk_rope_head_dim": 8, "v_head_dim": 12, "expert_ff": 24,
            "shared_experts": 1, "experts_total": 8, "experts_first": 2,
            "experts_held": 4, "top_k": 2, "routed_scale": 1,
            "max_seq_len": 64, "rms_eps": 1e-6, "rope_theta": 10000,
            "rope": rope, "param_dtype": "float32",
            "compute_dtype": "float32", "family": mla_moe, "config": "toy"})
    elif family == "window":
        net = _window_model()
    else:
        net = _model(n_kv_heads=2, pos_encoding="rope")
    return net, net.init(prng.init_key(0))


def _family_server(net, params, **kw):
    return PagedDecodeServer(net, params, slots=3, num_blocks=40,
                             block_size=4, max_len=64, prefill_chunk=8, **kw)


def _device_state(srv):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        (srv.pools, srv.state, srv.stats))]


@pytest.mark.parametrize("family", FAMILIES)
def test_a_chunk_returns_its_last_true_columns_row_or_none(family):
    """A prompt of 21 in chunks of 8 (the last one 5 true columns of a
    bucket of 8): the last chunk's ``(1, V)`` row is the full forward's
    logits at the last prompt position, the two before it return zeros; the
    same chunks with every one told it is the last return the forward's
    rows at positions 7, 15 and 20, and after each chunk pools, state and
    counters are the same bit for bit whether the head ran or not."""
    net, params = _family_toy(family)
    vocab = net.cfg.vocab_size
    prompt = np.random.default_rng(2).integers(0, vocab, size=21).tolist()
    want = np.asarray(net.apply(params, jnp.asarray([prompt])), np.float32)[0]
    tol = 2e-5 * np.abs(want).max()

    served, forced = (_family_server(net, params) for _ in range(2))
    inner = forced._prefill_fn
    forced._prefill_fn = lambda *a: inner(*a[:-1], jnp.asarray(True))
    a, b = [], []
    _record_prefill_logits(served, a)
    _record_prefill_logits(forced, b)
    rids = [srv.try_admit(prompt, 4) for srv in (served, forced)]
    for chunk in range(3):
        done = [srv.prefill_step(rid, 8)
                for srv, rid in zip((served, forced), rids)]
        assert done == [chunk == 2] * 2
        for mine, theirs in zip(_device_state(served),
                                _device_state(forced)):
            assert (mine == theirs).all()
    assert [r.shape for r in a + b] == [(vocab,)] * 6
    assert not a[0].any() and not a[1].any()
    assert np.abs(a[2] - want[20]).max() <= tol
    for row, at in zip(b, (7, 15, 20)):
        assert np.abs(row - want[at]).max() <= tol, at
    assert (a[2] == b[2]).all()
    assert (served.prefill_chunks, served.prefill_heads) == (3, 1)


@pytest.mark.parametrize("family", FAMILIES)
def test_a_multi_chunk_prompt_beside_a_stranger_serves_greedy_tokens(family):
    """Three chunks with a stranger decoding between them, then decode: the
    served tokens are the full forward's greedy tokens (each the best of its
    row, or within rounding of it)."""
    net, params = _family_toy(family)
    vocab = net.cfg.vocab_size
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, vocab, size=21).tolist()
    srv = _family_server(net, params)
    other = srv.try_admit(rng.integers(0, vocab, size=6).tolist(), 30)
    while not srv.prefill_step(other, 8):
        pass
    rid = srv.try_admit(prompt, 6)
    while not srv.prefill_step(rid, 8):
        srv.step()
    while not srv.done(rid):
        srv.step()
    served = srv.result(rid)
    assert served[:21] == prompt and len(served) == 27
    want = np.asarray(net.apply(params, jnp.asarray([served])), np.float32)[0]
    for t in range(21, 27):
        row = want[t - 1]
        assert row.max() - row[served[t]] <= 4e-5 * np.abs(want).max(), t


def test_one_first_token_program_a_server_and_one_chunk_program_a_bucket():
    """Prompts that draw three buckets (8, 16, 32), one of them in two
    chunks (a chunk that is its prompt's last and one that is not, in the
    same bucket): the first-token program compiled once, the chunk program
    once a bucket, and nothing more on a second pass."""
    # a model and a geometry no other test uses: the caches start empty
    model = _model(max_seq_len=80)
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=2, num_blocks=40,
                            block_size=4, max_len=76)
    assert srv._first_fn._cache_size() == 0
    for _ in range(2):
        for n, width in ((5, 32), (11, 32), (27, 32), (60, 32)):
            _drain(srv, srv.try_admit(list(range(1, n + 1)), 3), width)
        assert srv._first_fn._cache_size() == 1
        assert srv._prefill_fn._cache_size() == 3
    assert (srv.prefill_chunks, srv.prefill_heads) == (10, 8)


def test_the_serve_records_count_chunks_and_heads(tmp_path):
    """A 3-chunk and a 1-chunk prompt: ``prefill_chunks`` 4 and
    ``prefill_heads`` 2 in the final ``kind="serve"`` record, ``bucket=``
    and ``head=`` on every ``prefill/submit`` span, and the summary tool's
    line."""
    import json
    import subprocess
    import sys
    from pathlib import Path

    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )

    model = _model()
    params = model.init(prng.init_key(0))
    spans = []
    listener = lambda n, t, d, a: spans.append((n, dict(a or {})))  # noqa: E731
    tracer = trace_lib.start_run(str(tmp_path / "trace"))
    trace_lib.add_listener(listener)
    sched = Scheduler(model, params, ServeConfig(
        slots=2, num_blocks=40, block_size=4, max_len=64, prefill_chunk=8,
        telemetry_dir=str(tmp_path), metrics_every=1))
    try:
        rids = [sched.submit(list(range(1, n + 1)), 4) for n in (20, 6)]
        sched.run_until_drained()
        assert all(len(sched.result(r)) for r in rids)
    finally:
        sched.close()
        trace_lib.remove_listener(listener)
        trace_lib.stop_run(tracer)
    final = [r for r in map(json.loads, open(tmp_path / "metrics.jsonl"))
             if r.get("kind") == "serve" and r.get("final")][-1]
    assert (final["prefill_chunks"], final["prefill_heads"]) == (4, 2)
    submits = sorted((a["bucket"], a["head"]) for n, a in spans
                     if n == "prefill/submit")
    assert submits == [(8, 0), (8, 0), (8, 1), (8, 1)]
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "metrics_summary.py"),
         str(tmp_path)], capture_output=True, text=True, check=True).stdout
    assert "prefill chunks: 4, the head ran in 2" in out
