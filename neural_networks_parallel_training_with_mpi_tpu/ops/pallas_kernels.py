"""Pallas TPU kernels for the hot ops.

The reference's compute path is torch's C++/ATen kernels (SURVEY.md §2.4 —
no in-repo native code); the TPU-native equivalent of "hand-tuned hot op"
is a Pallas kernel lowered through Mosaic onto the MXU/VPU.  This module
provides:

* **flash_attention** — blocked causal attention with online softmax.
  Never materializes the (T, T) score matrix: each q-block streams over
  k/v-blocks in VMEM, carrying running (max, denominator, accumulator) —
  the FlashAttention recurrence.  Causal blocks above the diagonal are
  skipped entirely (the fori_loop upper bound shrinks per q-block), saving
  ~2x FLOPs at long T.  O(T) memory per head instead of O(T^2).
* **fused_layernorm** — single-pass LayerNorm on the VPU; one read of x
  per row instead of XLA's separate mean/var/normalize passes when fusion
  declines.

Both run in interpreter mode on CPU (tests, SURVEY.md §4's fake-device
strategy) and compiled through Mosaic on TPU.  The backward pass of
flash_attention is also Pallas: the forward additionally emits the per-row
logsumexp, and two backward kernels (dq; dk+dv) recompute the probability
blocks from (q, k, lse) in VMEM — the standard FlashAttention-2 backward
split, no (T, T) buffer anywhere.  ``_blocked_attention_reference`` keeps
the same math in plain JAX as the cross-check for tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# Masking modes.  "causal" keeps k_pos <= q_pos (the standard triangle);
# "causal_exclusive" keeps k_pos < q_pos — the striped-ring case
# (parallel.sequence.striped_ring_flash_attention): with tokens laid out
# round-robin over the ring, the block pair (my_rank, src_rank) is EXACTLY
# the inclusive triangle when src <= my and the exclusive one when
# src > my, so every ring step does half work on every device.  Exclusive
# mode can leave a q-row with no attendable key (row 0 of the whole
# shard): such rows exit with output 0 and lse = NEG_INF, which the ring
# merge treats as "no contribution" — the same convention as its
# skip_block.
_MASK_MODES = ("none", "causal", "causal_exclusive")


def _resolve_mask(causal: bool, mask_mode: Optional[str]) -> str:
    mode = mask_mode if mask_mode is not None else (
        "causal" if causal else "none")
    if mode not in _MASK_MODES:
        raise ValueError(f"mask_mode must be one of {_MASK_MODES}, "
                         f"got {mode!r}")
    return mode


# ==========================================================================
# Flash attention
# ==========================================================================

def _k_block_hi(mask: str, qi, block_q: int, block_k: int,
                num_k_blocks: int):
    """Exclusive upper bound on the k-block loop for one q-block: blocks
    entirely above the (inclusive or exclusive) diagonal are never read."""
    if mask == "none":
        return num_k_blocks
    # highest attendable k index: last q row is (qi+1)*Bq - 1; inclusive
    # attends k <= that, exclusive k < that
    last_k = (qi + 1) * block_q - (1 if mask == "causal" else 2)
    return lax.min(num_k_blocks,
                   lax.max(0, lax.div(last_k + block_k, block_k)))


def _mask_scores(mask: str, s, q_pos, k_pos):
    if mask == "none":
        return s
    keep = (k_pos <= q_pos) if mask == "causal" else (k_pos < q_pos)
    return jnp.where(keep, s, NEG_INF)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                      block_k: int, seq_len: int, mask: str,
                      scale: float):
    """Grid: (batch*heads, T // block_q).  Refs (block-local):
    q (1, block_q, D), k/v (1, T, D), o (1, block_q, D), lse (1, 1, block_q).

    lse rides in a (BH, 1, T) layout: Mosaic requires the last two dims of
    every block shape to be (8, 128)-divisible or equal to the array dims,
    which a (1, block_q) block over (BH, T) violates (the leading 1 is a
    grid dim).  With the singleton axis the block's trailing dims are
    (1, block_q) against array dims (1, T) — legal."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (Bq, D)
    d = q.shape[-1]
    num_k_blocks = seq_len // block_k
    hi = _k_block_hi(mask, qi, block_q, block_k, num_k_blocks)

    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (Bq, Bk)
        k_pos = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = _mask_scores(mask, s, q_pos, k_pos)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + p.sum(axis=-1, keepdims=True)
        acc_new = corr * acc + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = lax.fori_loop(0, hi, body, (acc0, m0, l0))
    # exclusive mode can leave a row with NO attendable key (its m never
    # left NEG_INF — every seen score was the mask fill, or the loop never
    # ran): emit output 0 / lse NEG_INF, the ring merge's "no
    # contribution" convention.  Inclusive/none modes never hit this.
    empty = m < (NEG_INF * 0.5)
    l_safe = jnp.where(empty, 1.0, l)
    o_ref[0] = jnp.where(empty, 0.0, acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.where(empty, NEG_INF, m + jnp.log(l_safe))[:, 0]


def _heads_major(x: jax.Array) -> jax.Array:
    """(B, T, H, D) -> (B*H, T, D): contiguous per-head rows for kernels."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _heads_minor(x: jax.Array, b: int, h: int) -> jax.Array:
    """(B*H, T, D) -> (B, T, H, D)."""
    _, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _resolve_blocks(t: int, block_q: int, block_k: int):
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q or t % block_k:
        raise ValueError(f"seq_len {t} not divisible by blocks "
                         f"({block_q}, {block_k})")
    return block_q, block_k


def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                   block_q: int, block_k: int,
                   interpret: Optional[bool],
                   mask_mode: Optional[str] = None):
    """q/k/v: (B, T, H, D) -> out (B, T, H, D), lse (B*H, T) float32."""
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    block_q, block_k = _resolve_blocks(t, block_q, block_k)
    if interpret is None:
        interpret = _interpret_default()
    qh, kh, vh = _heads_major(q), _heads_major(k), _heads_major(v)

    kernel = functools.partial(_flash_fwd_kernel, block_q=block_q,
                               block_k=block_k, seq_len=t,
                               mask=_resolve_mask(causal, mask_mode),
                               scale=scale)
    mem = {"memory_space": pltpu.VMEM}
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0), **mem),
            pl.BlockSpec((1, t, d), lambda bh, i: (bh, 0, 0), **mem),
            pl.BlockSpec((1, t, d), lambda bh, i: (bh, 0, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0), **mem),
            pl.BlockSpec((1, 1, block_q), lambda bh, i: (bh, 0, i), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qh, kh, vh)
    return _heads_minor(out, b, h), lse.reshape(b * h, t)


def _blocked_attention_reference(q, k, v, causal: bool, block_k: int):
    """Same math as the kernel in plain JAX (for the VJP): q-rows attend to
    k/v in blocks via lax.scan — O(T * block_k) live memory, XLA-fusable."""
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    q_pos = jnp.arange(t)[:, None]

    num_blocks = t // block_k
    kb = kf.reshape(b, num_blocks, block_k, h, d)
    vb = vf.reshape(b, num_blocks, block_k, h, d)

    def step(carry, inp):
        acc, m, l = carry
        kj, vj, j = inp
        s = jnp.einsum("bthd,bshd->bhts", qf, kj)
        if causal:
            k_pos = j * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where((k_pos <= q_pos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + p.sum(-1, keepdims=True)
        acc_new = corr[..., 0][..., None] * acc + jnp.einsum(
            "bhts,bshd->bthd", p, vj).transpose(0, 2, 1, 3)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, h, t, d), jnp.float32)
    m0 = jnp.full((b, h, t, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t, 1), jnp.float32)
    (acc, m, l), _ = lax.scan(
        step, (acc0, m0, l0),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         jnp.arange(num_blocks)))
    out = acc / l[..., 0][..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# --------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 split: one kernel accumulates dq over
# k-blocks, one accumulates dk/dv over q-blocks; p is recomputed from
# (q, k, lse), delta = rowsum(do * o) is precomputed outside).
# --------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q: int, block_k: int, seq_len: int,
                         mask: str, scale: float):
    """Grid: (B*H, T // block_q).  q/do/dq blocks (1, block_q, D); k/v full
    rows (1, T, D); lse/delta blocks (1, 1, block_q) float32 (the singleton
    axis keeps the trailing block dims Mosaic-legal, see _flash_fwd_kernel)."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)[:, None]     # (Bq, 1)
    delta = delta_ref[0, 0].astype(jnp.float32)[:, None]
    d = q.shape[-1]
    num_k_blocks = seq_len // block_k
    hi = _k_block_hi(mask, qi, block_q, block_k, num_k_blocks)
    q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 0)
    # exclusive mode marks no-key rows with lse = NEG_INF; exp(s - lse)
    # would blow up there, and their true gradient is 0
    live = lse > (NEG_INF * 0.5)
    lse_safe = jnp.where(live, lse, 0.0)

    def body(j, dq_acc):
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = _mask_scores(mask, s, q_pos, k_pos)
        p = jnp.where(live, jnp.exp(s - lse_safe), 0.0)   # (Bq, Bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq_acc + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, block_k: int,
                          seq_len: int, mask: str, scale: float):
    """Grid: (B*H, T // block_k).  k/v/dk/dv blocks (1, block_k, D);
    q/do full rows (1, T, D); lse/delta full rows (1, 1, T) float32."""
    kj = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                      # (Bk, D)
    v = v_ref[0].astype(jnp.float32)
    d = k.shape[-1]
    num_q_blocks = seq_len // block_q
    # causal (either diagonal): k-block kj only feeds q rows >= kj*block_k
    # (exclusive needs strictly greater — the shared bound just admits one
    # nearly-masked extra block)
    lo = 0 if mask == "none" else lax.div(kj * block_k, block_q)
    k_pos = kj * block_k + lax.broadcasted_iota(jnp.int32,
                                                (block_q, block_k), 1)

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        # slice from the refs (Mosaic lowers pl.ds ref reads; value-level
        # lax.dynamic_slice has no TPU lowering rule)
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)].astype(
            jnp.float32)[:, None]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)].astype(
            jnp.float32)[:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = i * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        s = _mask_scores(mask, s, q_pos, k_pos)
        live = lse > (NEG_INF * 0.5)  # no-key rows: lse = NEG_INF, grad 0
        p = jnp.where(live, jnp.exp(s - jnp.where(live, lse, 0.0)), 0.0)
        dv_acc = dv_acc + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                     # (Bq, Bk)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    zeros = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = lax.fori_loop(lo, num_q_blocks, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal: bool, block_q: int,
                    block_k: int, interpret: Optional[bool],
                    g_lse: Optional[jax.Array] = None,
                    mask_mode: Optional[str] = None):
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    block_q, block_k = _resolve_blocks(t, block_q, block_k)
    if interpret is None:
        interpret = _interpret_default()
    qh, kh, vh = _heads_major(q), _heads_major(k), _heads_major(v)
    doh = _heads_major(g)
    # delta_i = sum_j p_ij * dp_ij = rowsum(do * o): one fused elementwise
    # reduce in XLA, shared by both kernels.  lse/delta travel as
    # (BH, 1, T) so every block shape's trailing dims stay Mosaic-legal.
    #
    # A cotangent on the lse OUTPUT (flash_attention_with_lse) folds into
    # the same kernels: d lse_i / d s_ij = p_ij, so
    # ds_ij = p_ij * (dp_ij - delta_i + g_lse_i) — i.e. shift delta by
    # -g_lse and nothing else changes (dv is lse-independent).
    delta = (doh.astype(jnp.float32)
             * _heads_major(out).astype(jnp.float32)).sum(-1)  # (BH, T)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    lse3 = lse.reshape(b * h, 1, t)
    delta3 = delta.reshape(b * h, 1, t)

    mem = {"memory_space": pltpu.VMEM}
    row = dict(block_q=block_q, block_k=block_k, seq_len=t,
               mask=_resolve_mask(causal, mask_mode), scale=scale)
    full = lambda spec_t: pl.BlockSpec((1, spec_t, d),
                                       lambda bh, i: (bh, 0, 0), **mem)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **row),
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0), **mem),
            full(t), full(t),
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0), **mem),
            pl.BlockSpec((1, 1, block_q), lambda bh, i: (bh, 0, i), **mem),
            pl.BlockSpec((1, 1, block_q), lambda bh, i: (bh, 0, i), **mem),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0),
                               **mem),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qh, kh, vh, doh, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **row),
        grid=(b * h, t // block_k),
        in_specs=[
            full(t),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0), **mem),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0), **mem),
            full(t),
            pl.BlockSpec((1, 1, t), lambda bh, j: (bh, 0, 0), **mem),
            pl.BlockSpec((1, 1, t), lambda bh, j: (bh, 0, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0), **mem),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qh, kh, vh, doh, lse3, delta3)
    return (_heads_minor(dq, b, h), _heads_minor(dk, b, h),
            _heads_minor(dv, b, h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Blocked attention, Pallas forward + Pallas backward.
    q/k/v: (B, T, H, D)."""
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, block_q, block_k,
                           interpret)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = True, block_q: int = 128,
                             block_k: int = 128,
                             interpret: Optional[bool] = None,
                             mask_mode: Optional[str] = None
                             ) -> Tuple[jax.Array, jax.Array]:
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ``lse`` (B*H, T) float32 — the building block for blockwise/ring
    composition (parallel.sequence.ring_flash_attention): partial outputs
    from different K/V blocks merge exactly via their lse weights.  Both
    outputs are differentiable; the lse cotangent rides the same Mosaic
    backward kernels as a ``delta`` shift (see _flash_backward).

    ``mask_mode`` overrides ``causal``: "none" / "causal" /
    "causal_exclusive" (strictly-below-diagonal — the striped-ring block
    case; rows with no attendable key return output 0 / lse NEG_INF)."""
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                          mask_mode)


def _fal_fwd(q, k, v, causal, block_q, block_k, interpret, mask_mode):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                              mask_mode)
    return (out, lse), (q, k, v, out, lse)


def _fal_bwd(causal, block_q, block_k, interpret, mask_mode, res, ct):
    q, k, v, out, lse = res
    g_out, g_lse = ct
    return _flash_backward(q, k, v, out, lse, g_out, causal, block_q,
                           block_k, interpret, g_lse=g_lse,
                           mask_mode=mask_mode)


flash_attention_with_lse.defvjp(_fal_fwd, _fal_bwd)


# ==========================================================================
# Paged attention (serving: decode + chunked prefill over a block pool)
# ==========================================================================

def _paged_attn_kernel(tables_ref, lens_ref, starts_ref, q_ref, k_hbm,
                       v_hbm, *rest, block_size: int, kv_heads: int,
                       groups: int, width: int, scale: float,
                       quant: bool):
    """Grid: (streams,).  Each program walks ITS stream's allocated
    block-table entries — ``ceil(len/block_size)`` of them, a dynamic
    ``fori_loop`` bound — double-buffering pool blocks HBM→VMEM with
    ``make_async_copy`` (block ``j+1``'s DMA is in flight while ``j``
    computes) and carrying the online-softmax (max, denom, acc) in the
    loop.  KV heads are unrolled in-program: one block fetch serves every
    head (a (stream, kv_head) grid would DMA each block ``kv_heads``
    times).

    Refs: ``tables (S, MB)`` / ``lens (S,)`` / ``starts (S,)`` ride
    scalar prefetch (SMEM) — runtime VALUES, not compile-time constants,
    so table churn and length growth re-run the same compiled kernel.
    ``q (1, KV, W·G, hd)`` in VMEM; ``k``/``v`` pools ``(NB, bs, KV·hd)``
    (and int8 scale pools ``(NB, 1, KV·bs)`` when ``quant``) stay
    UNBLOCKED in HBM — only the blocks a stream actually owns ever cross
    into VMEM, which is the bandwidth half of the win (the FLOPs half is
    the loop bound).  Every pool operand's last dim is lane-dense: Mosaic
    refuses to DMA-slice a block out of an array whose last dim is below
    the 128-lane tile, so heads are folded into it and head ``h`` is a
    static lane slice.  Scratch: 2-slot VMEM landing buffers per pool
    operand + a (2, n_operands) DMA semaphore array.

    Blocks past a stream's true length (and every block of an inactive
    ``len=0`` lane, whose loop never runs) contribute NOTHING.  Within
    the last live block the tail positions ``>= len`` are masked, so the
    sink block's frozen garbage is never attended.  int8 pools apply
    their per-(position, head) scales to the scores and probabilities —
    the gathered path's scheme.  A ``len=0`` lane exits with output 0,
    the flash kernels' "no contribution" convention."""
    if quant:
        (ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, sem) = rest
    else:
        o_ref, k_buf, v_buf, sem = rest
    s = pl.program_id(0)
    ln = lens_ref[s]
    nb = lax.div(ln + block_size - 1, block_size)
    rows = width * groups
    hd = q_ref.shape[-1]

    def _copies(j):
        slot = lax.rem(j, 2)
        blk = tables_ref[s, j]
        ops = [
            pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[slot],
                                  sem.at[slot, 0]),
            pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[slot],
                                  sem.at[slot, 1]),
        ]
        if quant:
            ops += [
                pltpu.make_async_copy(ks_hbm.at[blk], ks_buf.at[slot],
                                      sem.at[slot, 2]),
                pltpu.make_async_copy(vs_hbm.at[blk], vs_buf.at[slot],
                                      sem.at[slot, 3]),
            ]
        return ops

    # rows are (W, G) flattened: row r is query column r // groups
    k_off = lax.broadcasted_iota(jnp.int32, (rows, block_size), 1)
    q_pos = starts_ref[s] + lax.broadcasted_iota(
        jnp.int32, (rows, block_size), 0) // groups

    def body(j, carry):
        acc, m, l = carry

        @pl.when(j + 1 < nb)
        def _prefetch():
            for c in _copies(j + 1):
                c.start()

        for c in _copies(j):
            c.wait()
        slot = lax.rem(j, 2)
        k = k_buf[slot].astype(jnp.float32)          # (bs, KV*hd)
        v = v_buf[slot].astype(jnp.float32)

        def head_scale(buf, h):
            # (1, bs), sliced from the REF: a value slice past lane 128
            # of the (1, KV*bs) row does not lower
            return buf[slot, :, h * block_size:(h + 1) * block_size]

        k_pos = j * block_size + k_off
        keep = (k_pos < ln) & (k_pos <= q_pos)       # (rows, bs)
        acc, m, l = list(acc), list(m), list(l)
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale    # (rows, hd)
            sc = jax.lax.dot_general(
                q, k[:, h * hd:(h + 1) * hd], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # (rows, bs)
            if quant:
                sc = sc * head_scale(ks_buf, h)
            sc = jnp.where(keep, sc, NEG_INF)
            m_new = jnp.maximum(m[h], sc.max(axis=-1, keepdims=True))
            # a row with no attendable key in THIS block keeps its prior
            # max; every live row sees position 0 in block 0, so m is
            # finite before the running exp() can ever see exp(0) garbage
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m[h] - m_new)
            l[h] = corr * l[h] + p.sum(axis=-1, keepdims=True)
            if quant:
                p = p * head_scale(vs_buf, h)
            acc[h] = corr * acc[h] + jax.lax.dot_general(
                p, v[:, h * hd:(h + 1) * hd], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m[h] = m_new
        return tuple(acc), tuple(m), tuple(l)

    # per-head carries as tuples: the kv_heads loop is a Python unroll,
    # and a stacked (kv_heads, rows, ...) carry updated with .at[h].set
    # is a scatter, which Mosaic does not lower
    acc0 = (jnp.zeros((rows, hd), jnp.float32),) * kv_heads
    m0 = (jnp.full((rows, 1), NEG_INF, jnp.float32),) * kv_heads
    l0 = (jnp.zeros((rows, 1), jnp.float32),) * kv_heads

    @pl.when(nb == 0)
    def _inactive():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(nb > 0)
    def _walk():
        for c in _copies(0):
            c.start()
        acc, m, l = lax.fori_loop(0, nb, body, (acc0, m0, l0))
        for h in range(kv_heads):
            empty = m[h] < (NEG_INF * 0.5)
            l_safe = jnp.where(empty, 1.0, l[h])
            o_ref[0, h] = jnp.where(empty, 0.0,
                                    acc[h] / l_safe).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, lengths: jax.Array,
                    starts: jax.Array, *,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused paged attention: reads K/V straight from the serving block
    pool through per-stream block tables and reduces over each stream's
    TRUE length instead of the table capacity ``max_blocks·block_size``
    (serve/paged_kv.py's gathered path; ROADMAP 1(b)'s FLOPs win).

    One kernel covers the family: ``width == 1`` is the batched decode
    step (each stream's single query at position ``lengths-1``),
    ``width > 1`` is a chunked-prefill bucket (rows at absolute positions
    ``starts .. starts+width-1``, flash-style causal within the chunk).

    * ``q``: (streams, width, n_heads, head_dim) — GQA folds in-kernel
      (``n_heads`` must be a multiple of the pool's ``kv_heads``).
    * ``k_pool``/``v_pool``: (num_blocks, block_size, kv_heads, head_dim)
      — f32/bf16, or int8 with ``k_scale``/``v_scale``
      (num_blocks, block_size, kv_heads) f32, applied to the scores and
      probabilities.
    * ``tables``: (streams, max_blocks) int32 pool indices; unallocated
      entries point at the sink block and are NEVER walked (the block
      loop stops at ``ceil(length/block_size)``).
    * ``lengths``: (streams,) int32 attendable keys per stream (0 = an
      inactive lane: zero blocks walked, zero blocks fetched, output 0).
    * ``starts``: (streams,) int32 absolute position of each stream's
      first query row (decode passes ``lengths - 1``).

    Tables/lengths/starts are traced scalar-prefetch operands: block-table
    churn (admission, growth, eviction) re-runs the SAME compiled kernel
    — pinned by tests/test_paged_attn.py's compile-count test."""
    s_n, width, n_heads, hd = q.shape
    nb, bs, kv_heads, hd_k = k_pool.shape
    if hd_k != hd:
        raise ValueError(f"head_dim mismatch: q {hd} vs pool {hd_k}")
    if n_heads % kv_heads:
        raise ValueError(f"n_heads {n_heads} not a multiple of kv_heads "
                         f"{kv_heads}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("int8 pools need BOTH k_scale and v_scale")
    quant = k_scale is not None
    groups = n_heads // kv_heads
    scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = _interpret_default()

    # (S, W, H, hd) -> (S, KV, W·G, hd): per-kv-head query rows contiguous
    qk = q.reshape(s_n, width, kv_heads, groups, hd)
    qk = qk.transpose(0, 2, 1, 3, 4).reshape(s_n, kv_heads,
                                             width * groups, hd)

    row_map = lambda s, tbl, lns, sts: (s, 0, 0, 0)      # noqa: E731
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)      # never blocked
    in_specs = [
        pl.BlockSpec((1, kv_heads, width * groups, hd), row_map),
        hbm_spec, hbm_spec,
    ]
    # pool blocks cross into VMEM as lane-dense (bs, KV*hd) rows (see
    # the kernel's docstring); head h is the lane slice [h*hd, (h+1)*hd)
    operands = [qk, k_pool.reshape(nb, bs, kv_heads * hd),
                v_pool.reshape(nb, bs, kv_heads * hd)]
    n_dma = 2
    scratch = [
        pltpu.VMEM((2, bs, kv_heads * hd), k_pool.dtype),
        pltpu.VMEM((2, bs, kv_heads * hd), v_pool.dtype),
    ]
    if quant:
        # scales ride head-major, (1, KV*bs) per block, so head h's
        # per-position scales are a static lane slice that broadcasts
        # over the score rows
        in_specs += [hbm_spec, hbm_spec]
        operands += [
            sc.transpose(0, 2, 1).reshape(nb, 1, kv_heads * bs)
            for sc in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, 1, kv_heads * bs), k_scale.dtype),
                    pltpu.VMEM((2, 1, kv_heads * bs), v_scale.dtype)]
        n_dma = 4
    scratch.append(pltpu.SemaphoreType.DMA((2, n_dma)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv_heads, width * groups, hd), row_map),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, block_size=bs, kv_heads=kv_heads,
            groups=groups, width=width, scale=scale, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (s_n, kv_heads, width * groups, hd), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      starts.astype(jnp.int32), *operands)
    # (S, KV, W·G, hd) -> (S, W, H, hd)
    out = out.reshape(s_n, kv_heads, width, groups, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(s_n, width, n_heads, hd)


# ==========================================================================
# Fused LayerNorm
# ==========================================================================

def _ln_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    xc = x - mean
    var = (xc * xc).mean(-1, keepdims=True)
    y = xc * lax.rsqrt(var + eps)
    o_ref[:] = (y * scale_ref[:].astype(jnp.float32)
                + bias_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def fused_layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
                    eps: float = 1e-5, block_rows: int = 256,
                    interpret: Optional[bool] = None) -> jax.Array:
    """LayerNorm over the last dim; rows processed in VMEM blocks."""
    if interpret is None:
        interpret = _interpret_default()
    lead = x.shape[:-1]
    d = x.shape[-1]
    rows = 1
    for s in lead:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        block_rows = 1  # degenerate but correct fallback
    mem = {"memory_space": pltpu.VMEM}
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0), **mem),
            pl.BlockSpec((d,), lambda i: (0,), **mem),
            pl.BlockSpec((d,), lambda i: (0,), **mem),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, scale, bias)
    return out.reshape(*lead, d)
