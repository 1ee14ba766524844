"""Fused paged attention: the Pallas kernel family
(``ops.pallas_kernels.paged_attention``) and its serving dispatch seam
(``serve/paged_kv.py``, ``attn_impl='fused'``).

Three layers of pins:

* **kernel vs. plain-numpy reference** — decode (width 1), chunked
  prefill (width > 1, per-row causal), GQA head folding, int8
  dequant-on-load, and the inactive-lane (``length 0``) zero-output
  convention, all in interpret mode on CPU (the ``_interpret_default``
  seam — CPU lanes never need a flag).
* **fused == gathered tokens** — the serving contract: swapping the
  attention dispatch must not move a single token.  The gathered path is
  pinned against dense ``DecodeServer``/``generate()`` by
  tests/test_serve_paged.py, so these pins chain the fused kernel to the
  eager reference without re-paying it.
* **the recompile invariant** — block tables and lengths are traced
  scalar-prefetch operands: admission, growth, eviction and re-admission
  re-run ONE compiled step program (``_cache_size`` pinned).

Core-lane budget note: one pinned-geometry parity scenario (plus the
cheap kernel-reference pins) runs in the budgeted core lane; per-variant
fresh compiles (GQA / int8 / scan_layers / rope) are in the slow lane,
and random-geometry scheduler fuzz under the fused path rides the
``serve`` lane in tests/test_serve_sched.py."""

import jax.numpy as jnp
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (
    paged_attention,
)
from neural_networks_parallel_training_with_mpi_tpu.serve import (
    PagedDecodeServer,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

pytestmark = pytest.mark.pallas

VOCAB = 64


def _model(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=64, n_layers=2, d_model=32,
                n_heads=4, d_ff=64)
    base.update(kw)
    return Transformer(TransformerConfig(**base))


def _drain(srv, rid, prefill_width=16):
    while not srv.prefill_step(rid, prefill_width):
        pass
    while not srv.done(rid):
        srv.step()
    return srv.result(rid)


# ---------------------------------------------------------------------------
# kernel vs. plain-numpy reference
# ---------------------------------------------------------------------------

def _np_reference(q, kp, vp, tables, lens, starts, ks=None, vs=None):
    """The paged-attention math in plain numpy: gather each stream's live
    blocks, truncate to its true length, per-row causal softmax."""
    s_n, w, n_heads, hd = q.shape
    _, bs, kv_heads, _ = kp.shape
    g = n_heads // kv_heads
    out = np.zeros((s_n, w, n_heads, hd), np.float32)
    for s in range(s_n):
        ln = int(lens[s])
        if ln == 0:
            continue
        nb = -(-ln // bs)
        gat = lambda pool: np.concatenate(                 # noqa: E731
            [np.asarray(pool, np.float32)[tables[s, j]] for j in range(nb)],
            axis=0)[:ln]
        k, v = gat(kp), gat(vp)
        if ks is not None:
            k = k * gat(ks)[..., None]
            v = v * gat(vs)[..., None]
        for col in range(w):
            q_pos = int(starts[s]) + col
            for h in range(n_heads):
                c = h // g
                sc = (np.asarray(q, np.float32)[s, col, h]
                      @ k[:, c].T) / np.sqrt(hd)
                sc = np.where(np.arange(ln) <= q_pos, sc, -1e30)
                p = np.exp(sc - sc.max())
                p /= p.sum()
                out[s, col, h] = p @ v[:, c]
    return out


def _pool_fixture(seed=0, nb=10, bs=4, kv=2, hd=8):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kv, hd)), jnp.float32)
    tables = np.zeros((3, 5), np.int32)
    tables[0, :3] = [1, 4, 7]
    tables[1, :2] = [2, 9]
    tables[2, :1] = [5]
    return rng, kp, vp, tables


def test_kernel_decode_matches_reference():
    """Width-1 (decode) against the numpy reference: ragged lengths, a
    block-straddling stream, and an INACTIVE length-0 lane that must
    contribute exactly nothing (output 0, zero blocks walked)."""
    rng, kp, vp, tables = _pool_fixture()
    lens = np.asarray([11, 6, 0], np.int32)
    starts = np.maximum(lens - 1, 0).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 8)), jnp.float32)
    got = paged_attention(q, kp, vp, jnp.asarray(tables),
                          jnp.asarray(lens), jnp.asarray(starts))
    want = _np_reference(q, kp, vp, tables, lens, starts)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)
    assert np.all(np.asarray(got)[2] == 0.0)      # inactive lane: nothing


def test_kernel_prefill_chunk_causal_gqa():
    """Width-4 chunk (the prefill variant) at nonzero start positions:
    per-row causal masking against absolute positions, with GQA folding
    (4 query heads over 2 kv heads)."""
    rng, kp, vp, tables = _pool_fixture(seed=1)
    lens = np.asarray([11, 6, 4], np.int32)
    starts = np.asarray([7, 2, 0], np.int32)
    q = jnp.asarray(rng.normal(size=(3, 4, 4, 8)), jnp.float32)
    got = paged_attention(q, kp, vp, jnp.asarray(tables),
                          jnp.asarray(lens), jnp.asarray(starts))
    want = _np_reference(q, kp, vp, tables, lens, starts)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


def test_kernel_int8_dequant_on_load():
    """int8 pools with per-(position, head) f32 scales dequantize inside
    the kernel — same numbers as dequantizing before the reference."""
    rng, _, _, tables = _pool_fixture(seed=2)
    kq = rng.integers(-127, 127, (10, 4, 2, 8)).astype(np.int8)
    vq = rng.integers(-127, 127, (10, 4, 2, 8)).astype(np.int8)
    ks = rng.uniform(0.01, 0.1, (10, 4, 2)).astype(np.float32)
    vs = rng.uniform(0.01, 0.1, (10, 4, 2)).astype(np.float32)
    lens = np.asarray([9, 3, 12], np.int32)
    tables[2, :3] = [3, 6, 8]
    starts = np.maximum(lens - 1, 0).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 8)), jnp.float32)
    got = paged_attention(q, jnp.asarray(kq), jnp.asarray(vq),
                          jnp.asarray(tables), jnp.asarray(lens),
                          jnp.asarray(starts), k_scale=jnp.asarray(ks),
                          v_scale=jnp.asarray(vs))
    want = _np_reference(q, kq.astype(np.float32), vq.astype(np.float32),
                         tables, lens, starts, ks, vs)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_kernel_validates_shapes():
    rng, kp, vp, tables = _pool_fixture()
    lens = jnp.zeros((3,), jnp.int32)
    q = jnp.zeros((3, 1, 3, 8), jnp.float32)      # 3 heads over 2 kv
    with pytest.raises(ValueError):
        paged_attention(q, kp, vp, jnp.asarray(tables), lens, lens)
    q = jnp.zeros((3, 1, 4, 8), jnp.float32)
    with pytest.raises(ValueError):               # one scale, not both
        paged_attention(q, kp, vp, jnp.asarray(tables), lens, lens,
                        k_scale=jnp.ones((10, 4, 2)))


# ---------------------------------------------------------------------------
# fused == gathered through the serving surface (the token contract)
# ---------------------------------------------------------------------------

def _staggered_scenario(srv):
    """Staggered ragged admissions incl. an 11-token prompt prefilled in
    width-4 chunks straddling the 8-position block boundary — the
    gathered parity suite's scenario, reused verbatim."""
    reqs = []
    a = srv.try_admit([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], 12)
    while not srv.prefill_step(a, 4):
        pass
    reqs.append(a)
    srv.step(); srv.step()
    b = srv.try_admit([7, 8], 6)
    while not srv.prefill_step(b, 16):
        pass
    reqs.append(b)
    srv.step()
    c = srv.try_admit([5, 9, 11, 13], 9)
    while not srv.prefill_step(c, 16):
        pass
    reqs.append(c)
    for _ in range(40):
        srv.step()
        if all(srv.done(r) for r in reqs):
            break
    out = [srv.result(r) for r in reqs]
    srv.allocator.assert_drained()                # no leak on the kernel path
    return out


def test_fused_matches_gathered_staggered_straddling():
    """The core-lane parity pin: same staggered block-straddling scenario
    through both attention impls — token-identical, allocator drained.
    (gathered == dense DecodeServer == generate() is pinned by
    tests/test_serve_paged.py, so this chains fused to the reference.)"""
    model = _model()
    params = model.init(prng.init_key(0))
    outs = {}
    for impl in ("gathered", "fused"):
        srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                                block_size=8, attn_impl=impl)
        outs[impl] = _staggered_scenario(srv)
    assert outs["fused"] == outs["gathered"]


def test_fused_evict_readmit_reproduces_tokens():
    """Mid-stream eviction discards device state; the fused path's greedy
    re-run after re-admission must land the same tokens the gathered
    path produces end to end (same geometry as the parity pin, so the
    core lane pays steps, not a fresh compile)."""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8, attn_impl="fused")
    rid = srv.try_admit([4, 5, 6], 10)
    while not srv.prefill_step(rid, 16):
        pass
    srv.step(); srv.step(); srv.step()            # mid-flight
    prompt, max_new = srv.evict(rid)
    srv.allocator.assert_drained()
    rid2 = srv.try_admit(prompt, max_new)
    got = _drain(srv, rid2)
    ref_srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                                block_size=8, attn_impl="gathered")
    ref = _drain(ref_srv, ref_srv.try_admit([4, 5, 6], 10))
    assert got == ref
    srv.allocator.assert_drained()


def test_block_table_churn_never_recompiles():
    """The recompile invariant (acceptance criterion): tables and lengths
    are traced scalar-prefetch operands, so admission, on-demand block
    growth, eviction and re-admission all re-run ONE compiled decode
    step; prefill compiles per pow2 bucket width, never per table.
    (The jitted programs are lru-shared across equal-geometry servers,
    so the pin is "no growth after churn", measured on this process's
    shared cache.)"""
    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=40,
                            block_size=8, attn_impl="fused")
    a = srv.try_admit([1] * 12, 12)               # bucket 16 + growth
    while not srv.prefill_step(a, 16):
        pass
    for _ in range(4):
        srv.step()
    # the jitted programs are lru-shared across servers, and OTHER
    # geometries (slots / pool size) legitimately add cache entries in a
    # shared pytest process — the invariant is zero growth from here on
    n_step = srv._step_fn._cache_size()
    n_prefill = srv._prefill_fn._cache_size()
    # churn: a second stream (new table rows, new lengths), growth across
    # a block boundary, an eviction (table zeroed to the sink), and a
    # re-admission — same bucket widths, so NOTHING may recompile
    b = srv.try_admit([9] * 11, 8)
    while not srv.prefill_step(b, 16):
        pass
    srv.step()
    srv.evict(b)
    c = srv.try_admit([3] * 9, 6)
    while not srv.prefill_step(c, 16):
        pass
    while not (srv.done(a) and srv.done(c)):
        srv.step()
    srv.result(a), srv.result(c)
    srv.allocator.assert_drained()
    assert srv._step_fn._cache_size() == n_step
    assert srv._prefill_fn._cache_size() == n_prefill


def test_donation_audit_fused_decode_program():
    """The donation audit extended to the fused serving decode program:
    it donates the KV pools, the token slab and the position vector
    (donate_argnums=(1, 2, 4)) — every donated leaf must alias in/out
    (an unaliased pool leaf would copy the whole block pool per decoded
    token)."""
    import jax

    from neural_networks_parallel_training_with_mpi_tpu.utils.profiling import (
        donation_report,
    )

    model = _model()
    params = model.init(prng.init_key(0))
    srv = PagedDecodeServer(model, params, slots=4, num_blocks=24,
                            block_size=8, attn_impl="fused")
    masked = np.where(srv.active[:, None], srv.tables, 0)
    comp = srv._step_fn.lower(
        srv.params, srv.pools, srv.stats, srv.tokens, jnp.asarray(masked),
        srv.pos,
        jnp.asarray(srv.active), srv.key).compile()
    rep = donation_report(comp)
    donated = len(jax.tree_util.tree_leaves(srv.pools)) + 2  # + tokens, pos
    assert rep["n_aliased"] == donated, rep
    assert rep["unaliased_donors"] == 0, rep


# ---------------------------------------------------------------------------
# model-variant parity (full lane: each variant is a fresh compile)
# ---------------------------------------------------------------------------

def _ab_tokens(model, params, prompt, n, prefill_width=16, **srv_kw):
    outs = []
    for impl in ("gathered", "fused"):
        srv = PagedDecodeServer(model, params, slots=2, num_blocks=20,
                                block_size=8, attn_impl=impl, **srv_kw)
        rid = srv.try_admit(prompt, n)
        outs.append(_drain(srv, rid, prefill_width))
        srv.allocator.assert_drained()
    return outs


@pytest.mark.slow
def test_gqa_fused_exact():
    model = _model(n_kv_heads=2)
    params = model.init(prng.init_key(0))
    g, f = _ab_tokens(model, params, [1, 2, 3], 8)
    assert f == g


@pytest.mark.slow
def test_int8_kv_fused_exact():
    """int8 pools: the kernel dequantizes on load from the same
    per-(position, head) scales the gathered path applies to its
    logits/probs — chunked prefill splitting blocks included."""
    model = _model()
    params = model.init(prng.init_key(0))
    g, f = _ab_tokens(model, params, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 8,
                      prefill_width=4, kv_quant=True)
    assert f == g


@pytest.mark.slow
def test_scan_layers_fused_exact():
    model = _model(scan_layers=True)
    params = model.init(prng.init_key(0))
    g, f = _ab_tokens(model, params, [9, 8, 7], 6)
    assert f == g


@pytest.mark.slow
def test_rope_fused_exact():
    """RoPE rotates at absolute positions; the kernel's q_pos/start
    plumbing must agree with the gathered path's rotation windows."""
    model = _model(pos_encoding="rope")
    params = model.init(prng.init_key(0))
    g, f = _ab_tokens(model, params, [1, 2, 3, 4, 5, 6, 7, 8, 9], 8,
                      prefill_width=4)
    assert f == g
