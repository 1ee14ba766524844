"""Pallas kernels (interpret mode on CPU) must match the plain-JAX
reference implementations, forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.pallas

from neural_networks_parallel_training_with_mpi_tpu.ops import (
    pallas_kernels as pk,
)
from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (
    flash_attention,
)
from neural_networks_parallel_training_with_mpi_tpu.parallel.sequence import (
    attention_reference,
)


def _qkv(b=2, t=64, h=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block", [16, 32, 64])
def test_flash_attention_matches_dense(causal, block):
    q, k, v = _qkv()
    expected = attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, block, block, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_grads_match_dense():
    q, k, v = _qkv(t=32)

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 16, 16, True) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_attention_in_transformer():
    """attention='flash' end to end through the model."""
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    t = 32
    mk = lambda att: Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=t, n_layers=2, d_model=32, n_heads=4,
        d_ff=64, attention=att))
    params = mk("dense").init(prng.init_key(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, t)),
                      jnp.int32)
    dense = mk("dense").apply(params, ids)
    flash = mk("flash").apply(params, ids)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_pallas_backward_matches_blocked_reference_vjp():
    """The two Mosaic backward kernels (dq; dk+dv) vs autodiff of
    _blocked_attention_reference — the same online-softmax math expressed in
    plain JAX.  This pins the hand-derived ds/dq/dk/dv algebra against an
    independently-differentiated implementation (not just the dense path)."""
    from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (
        _blocked_attention_reference,
    )

    q, k, v = _qkv(t=64)
    g = jnp.asarray(
        np.random.default_rng(7).standard_normal(q.shape), jnp.float32)

    out, vjp = jax.vjp(
        lambda q_, k_, v_: _blocked_attention_reference(q_, k_, v_, True, 16),
        q, k, v)
    want = vjp(g)

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, True, 16, 16, True)

    out_fa, vjp_fa = jax.vjp(flash, q, k, v)
    got = vjp_fa(g)

    np.testing.assert_allclose(np.asarray(out_fa), np.asarray(out),
                               rtol=2e-4, atol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


def test_flash_attention_with_lse_value_and_grads():
    """(out, lse) variant: both outputs and BOTH cotangent paths (the lse
    cotangent rides the Mosaic backward as a delta shift) must match a
    plain-JAX attention-with-lse reference."""
    import jax.scipy.special as jsp

    def ref_with_lse(q, k, v, causal):
        d = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * d**-0.5
        if causal:
            t = q.shape[1]
            mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
            s = jnp.where(mask[None, None], s, -1e30)
        lse = jsp.logsumexp(s, axis=-1)                     # (B, H, T)
        p = jnp.exp(s - lse[..., None])
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        b, t, h, _ = q.shape
        return out, lse.reshape(b * h, t)

    rng = np.random.default_rng(0)
    b, t, h, d = 2, 16, 2, 8
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    for causal in (True, False):
        o1, l1 = pk.flash_attention_with_lse(q, k, v, causal, 16, 16, True)
        o2, l2 = ref_with_lse(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-5)

        # nonlinear functions of BOTH outputs exercise g_out and g_lse
        def loss(fn):
            def f(q, k, v):
                o, l = fn(q, k, v)
                return (o ** 2).sum() + jnp.sin(l).sum()
            return f

        g1 = jax.grad(loss(lambda q, k, v: pk.flash_attention_with_lse(
            q, k, v, causal, 16, 16, True)), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(lambda q, k, v: ref_with_lse(q, k, v, causal)),
                      argnums=(0, 1, 2))(q, k, v)
        for a, bb in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=2e-5, atol=2e-5)


def test_flash_attention_rectangular_blocks():
    """block_q != block_k tilings (``FLASH_BLOCKS`` may hold such rows)
    must be numerically identical to the dense reference, fwd and bwd."""
    q, k, v = _qkv(t=64)
    expected = attention_reference(q, k, v, causal=True)
    for bq, bk in ((16, 32), (32, 16), (16, 64)):
        got = flash_attention(q, k, v, True, bq, bk, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"bq={bq} bk={bk}")

    def loss(bq, bk):
        return lambda q_, k_, v_: (
            flash_attention(q_, k_, v_, True, bq, bk, True) ** 2).sum()

    g_ref = jax.grad(loss(16, 16), argnums=(0, 1, 2))(q, k, v)
    g_rect = jax.grad(loss(16, 32), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_rect, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


def test_flash_block_config_reaches_kernel():
    """TransformerConfig.flash_block_q/flash_block_k thread through
    sequence_sharded_attention to the kernel: a non-default legal tiling
    gives the same forward as the default, and an illegal one (not
    dividing T) raises — proof the values actually arrive."""
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    t = 32
    mk = lambda **kw: Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=t, n_layers=1, d_model=32, n_heads=4,
        d_ff=64, attention="flash", **kw))
    params = mk().init(prng.init_key(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, t)),
                      jnp.int32)
    default = mk().apply(params, ids)
    tuned = mk(flash_block_q=16, flash_block_k=8).apply(params, ids)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(default),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="not divisible"):
        mk(flash_block_k=24).apply(params, ids)


# ---- bf16 operands (PR 27): the kernels take q/k/v as they arrive --------

def _dense_masked(q, k, v, mode):
    """``attention_reference``'s arithmetic (operands as they arrive, f32
    scores and softmax, probs cast for the values product) under any of the
    kernels' three masks; a row with no key gives 0, as the kernels do."""
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    pos = jnp.arange(t)
    keep = {"none": jnp.ones((t, t), bool),
            "causal": pos[None, :] <= pos[:, None],
            "causal_exclusive": pos[None, :] < pos[:, None]}[mode]
    s = jnp.where(keep[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1) * keep.any(-1)[None, None, :, None]
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@pytest.mark.parametrize("blocks", [(32, 32), (16, 32), (64, 16)])
@pytest.mark.parametrize("mode", ["causal", "none", "causal_exclusive"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_bf16_matches_dense_bf16(head_dim, mode, blocks):
    """Forward and dq/dk/dv on bf16 inputs against the dense path on the
    SAME bf16 inputs, at the tolerance two bf16 paths owe each other: both
    round their operands' products to f32 sums in another order and cast
    probabilities to bf16 (2^-8 relative) for the values product."""
    rng = np.random.default_rng(head_dim + len(mode))
    q, k, v, w = (jnp.asarray(rng.standard_normal((2, 64, 2, head_dim)),
                              jnp.bfloat16) for _ in range(4))
    bq, bk = blocks

    def flash(q_, k_, v_):
        return pk.flash_attention_with_lse(q_, k_, v_, True, bq, bk, True,
                                           mask_mode=mode)[0]

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                  * w.astype(jnp.float32))

    got = flash(q, k, v)
    want = _dense_masked(q, k, v, mode)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    g_got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(lambda *a: _dense_masked(*a, mode)),
                      argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_got, g_want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape and np.isfinite(a).all()
        # against the gradient's own size: single entries of a bf16
        # gradient carry the rounding of the whole row's sum
        assert np.abs(a - b).max() <= 3e-2 * np.abs(b).max(), name


def _kernel_dots(jaxpr, inside=False):
    """(lhs dtype, rhs dtype) of every ``dot_general`` inside a
    ``pallas_call`` of ``jaxpr``, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        kernel = inside or eqn.primitive.name == "pallas_call"
        if inside and eqn.primitive.name == "dot_general":
            found.append(tuple(v.aval.dtype for v in eqn.invars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_dots(sub, kernel)
    return found


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_kernels_feed_the_mxu_bf16(head_dim):
    """The guard against the upcast coming back: with bf16 inputs no
    product inside the three kernels has an f32 operand (head_dim 64 folds
    its power-of-two scale into the operand, 128 scales the f32 scores),
    and every product accumulates in f32."""
    x = jnp.zeros((1, 64, 2, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        out, lse = pk.flash_attention_with_lse(q, k, v, True, 32, 32, True)
        return out.astype(jnp.float32).sum() + lse.sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    dots = _kernel_dots(jaxpr.jaxpr)
    # fwd 2, dq 3, dkv 4 products, in two loops each (off and on the
    # diagonal), per head of the 128-lane column (two of width 64 share one)
    assert len(dots) == 2 * (2 + 3 + 4) * (128 // head_dim)
    assert set(dots) == {(jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.bfloat16))}
    # and f32 inputs stay f32 products (what the CPU tests feed)
    x32 = x.astype(jnp.float32)
    dots32 = _kernel_dots(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(x32, x32, x32).jaxpr)
    assert set(dots32) == {(jnp.dtype(jnp.float32), jnp.dtype(jnp.float32))}


@pytest.mark.parametrize("key,swept", sorted(pk.FLASH_BLOCKS.items()))
def test_derived_blocks_follow_the_swept_table(key, swept):
    """For each timed row: the derived tiling at the row's smallest T is
    the swept one and divides T; shorter T clamps; an explicit
    flash_block_q/k still wins; an untimed shape keeps 128 x 128."""
    from neural_networks_parallel_training_with_mpi_tpu.parallel.sequence import (
        AUTO_FLASH_MIN_SEQ,
    )

    head_dim, dtype = key
    t = AUTO_FLASH_MIN_SEQ[("tpu", head_dim, dtype)]
    bq, bk = pk.flash_blocks(t, head_dim, dtype)
    assert (bq, bk) == swept and t % bq == 0 and t % bk == 0
    assert pk.flash_blocks(4 * t, head_dim, dtype) == swept
    assert pk.flash_blocks(64, head_dim, dtype) == (64, 64)
    assert pk.flash_blocks(t + 128, head_dim, dtype) is None
    assert pk.flash_blocks(t, head_dim, dtype, 128, 256) == (128, 256)
    assert pk.flash_blocks(t, head_dim, dtype, None, 256) == (bq, 256)
    assert pk.flash_blocks(t, 96, dtype) == (128, 128)
    assert pk.flash_blocks(t, head_dim, "float32") == (128, 128)


def test_flash_default_blocks_are_none():
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        TransformerConfig,
    )

    c = TransformerConfig(vocab_size=8)
    assert c.flash_block_q is None and c.flash_block_k is None


@pytest.mark.parametrize("heads,head_dim,want", [
    (16, 64, (2, True)),        # gpt2-medium: two heads a 128-lane column
    (24, 128, (1, True)),       # starcoder2-3b: one head a column
    (4, 256, (1, True)),
    (8, 16, (8, True)),
    (2, 16, (1, False)),        # 8 heads a column, 2 do not fill one
    (3, 64, (1, False)),
    (4, 96, (1, False)),        # 96 neither divides 128 nor is a multiple
])
def test_fold_plan(heads, head_dim, want):
    assert pk._fold_plan(heads, head_dim) == want


@pytest.mark.parametrize("mode", ["causal", "none", "causal_exclusive"])
def test_heads_on_lanes_equal_heads_major(mode, monkeypatch):
    """Heads that share a 128-lane column (four of width 32 here), told
    apart by lane masks, give what one head a program over the heads-major
    layout gives: outputs, lse and all three gradients."""
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 64, 4, 32)), jnp.float32)
               for _ in range(3))
    assert pk._fold_plan(4, 32) == (4, True)

    def run():
        def loss(q_, k_, v_):
            o, l = pk.flash_attention_with_lse(q_, k_, v_, True, 32, 16,
                                               True, mask_mode=mode)
            live = l > -1e29
            return (o ** 2).sum() + jnp.where(live, jnp.sin(l), 0.0).sum()

        out = pk.flash_attention_with_lse(q, k, v, True, 32, 16, True,
                                          mask_mode=mode)
        return (*out, *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    folded = run()
    monkeypatch.setattr(pk, "_fold_plan", lambda h, d: (1, False))
    jax.clear_caches()          # the jitted calls keyed the folded plan
    try:
        major = run()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), folded, major):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
