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
