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
* **delta_rule_step** — the gated delta rule's decode tick over a store of
  float32 state rows, each stream's state read once and written once, in
  place.

The kernels run in interpreter mode on CPU (tests, SURVEY.md §4's fake-device
strategy) and compiled through Mosaic on TPU.  The backward pass of
flash_attention is also Pallas: the forward additionally emits the per-row
logsumexp, and one backward kernel recomputes each causal tile pair's
probability block from (q, k, lse) in VMEM once and takes dq, dk and dv
from it — no (T, T) buffer anywhere.  ``_blocked_attention_reference`` keeps
the same math in plain JAX as the cross-check for tests.
"""

from __future__ import annotations

import functools
import math
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

# Measured tilings, ``(head_dim, operand dtype) -> (block_q, block_k)``: the
# fastest forward + backward of tools/flash_block_sweep.py on one TPU v5e
# (PR 27, PERF.md section 6; B 4, T 1024, H 16 x 64 and H 24 x 128, bf16).
# A row is there only if it was timed on the chip; every other shape keeps
# 128 x 128, the tiling the kernels were written at.
FLASH_BLOCKS = {
    (64, "bfloat16"): (512, 512),
    (128, "bfloat16"): (512, 512),
}
_UNTIMED_BLOCKS = (128, 128)


def flash_blocks(t: int, head_dim: Optional[int], dtype,
                 block_q: Optional[int] = None,
                 block_k: Optional[int] = None
                 ) -> Optional[Tuple[int, int]]:
    """The kernels' tiling for this input: an explicit size wins, ``None``
    is derived from (head_dim, dtype) out of the timed table, and both are
    cut to ``t``.  None where the tiling does not divide ``t`` (``auto``
    then answers dense; the kernels' own call raises)."""
    name = None if dtype is None else jnp.dtype(dtype).name
    bq, bk = FLASH_BLOCKS.get((head_dim, name), _UNTIMED_BLOCKS)
    bq = min(bq if block_q is None else block_q, t)
    bk = min(bk if block_k is None else block_k, t)
    return None if t % bq or t % bk else (bq, bk)


def _checked_blocks(q: jax.Array, block_q: Optional[int],
                    block_k: Optional[int]) -> Tuple[int, int]:
    _, t, _, d = q.shape
    blocks = flash_blocks(t, d, q.dtype, block_q, block_k)
    if blocks is None:
        raise ValueError(f"seq_len {t} not divisible by blocks "
                         f"({block_q or 'derived'}, {block_k or 'derived'})")
    return blocks


def _fold_scale(x, scale: float):
    """``(x', s_scale)`` with ``(x' . y) * s_scale == (x . y) * scale``.  A
    power-of-two scale (head_dim 64: 1/8) multiplies the ``(block, D)``
    operand tile exactly in any float type, and the scores need no pass of
    their own; any other scale stays an f32 multiply of the f32 scores.
    The operand is never upcast: the MXU takes it as it arrives."""
    if math.frexp(scale)[0] == 0.5:
        return x * jnp.asarray(scale, x.dtype), None
    return x, scale


def _dot(a, b, contract):
    """MXU product of two operands in the dtype they arrive in, f32 out."""
    return lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                           preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


def _row_bounds(mask: str, qi, block_q: int, block_k: int, num_k: int):
    """For q-block ``qi``: ``(full, hi)`` — k-blocks ``[0, full)`` lie
    wholly on the attendable side of the (inclusive or exclusive) diagonal
    and need no mask, ``[full, hi)`` straddle it, ``[hi, num_k)`` lie
    wholly above it and are never read."""
    if mask == "none":
        return num_k, num_k
    incl = 1 if mask == "causal" else 0
    # last q row is (qi+1)*Bq - 1: it attends k <= that (k < that)
    last_k = (qi + 1) * block_q - 2 + incl
    hi = lax.min(num_k, lax.max(0, lax.div(last_k + block_k, block_k)))
    # first q row is qi*Bq: block j is full when its last key
    # (j+1)*Bk - 1 is <= that (< that)
    full = lax.div(qi * block_q + incl, block_k)
    return lax.min(full, hi), hi


def _col_bounds(mask: str, kj, block_q: int, block_k: int, num_q: int):
    """For k-block ``kj``: ``(lo, full)`` — q-blocks ``[0, lo)`` lie wholly
    above the diagonal and are never read, ``[lo, full)`` straddle it,
    ``[full, num_q)`` need no mask.  (Exclusive shares the inclusive
    ``lo``: it just admits one nearly-masked block.)"""
    if mask == "none":
        return 0, 0
    incl = 1 if mask == "causal" else 0
    lo = lax.div(kj * block_k, block_q)
    # q-block i is full when its first row i*Bq is >= (>) the k-block's
    # last key (kj+1)*Bk - 1
    full = lax.div((kj + 1) * block_k - incl + block_q - 1, block_q)
    return lo, lax.max(lo, lax.min(full, num_q))


def _keep(mask: str, rel, q0, k0):
    """Where a key may be attended; ``rel`` is ``k_local - q_local`` of the
    tile (built once a program), ``q0``/``k0`` the tile's first positions."""
    return (rel <= q0 - k0) if mask == "causal" else (rel < q0 - k0)


def _two_loops(lo, mid, hi, first, second, carry):
    """``first`` over ``[lo, mid)``, then ``second`` over ``[mid, hi)``."""
    return lax.fori_loop(mid, hi, second,
                         lax.fori_loop(lo, mid, first, carry))


# --- heads on the lanes ----------------------------------------------------
# The kernels read q/k/v where the model keeps them: (B, T, H*D), heads side
# by side on the minor axis.  A program takes one 128-lane column of it —
# one head of width 128, or ``pack`` = 128 // D narrower heads — so nothing
# is transposed in HBM on the way in or out and every load and store fills
# its lanes.  Heads that share a column are told apart by lane masks: a
# product contracts q (or k, v, do) with the other heads' lanes zeroed, and
# a product's output is only read in its own head's lanes.  On a 128 x 128
# MXU that costs what a lone head of width 64 costs, which leaves half the
# array idle either way.  A head width that neither divides 128 nor is a
# multiple of it (or a head count ``pack`` does not divide) takes the
# heads-major layout (B*H, T, D) through two transposes, one head a program.

def _fold_plan(h: int, d: int) -> Tuple[int, bool]:
    """``(pack, folded)``: heads a program, and whether they ride the lanes
    of the model's own layout."""
    if d % 128 == 0:
        return 1, True
    if 128 % d == 0 and h % (128 // d) == 0:
        return 128 // d, True
    return 1, False


def _heads_major(x: jax.Array) -> jax.Array:
    """(B, T, H, D) -> (B*H, T, D): contiguous per-head rows."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _heads_minor(x: jax.Array, b: int, h: int) -> jax.Array:
    """(B*H, T, D) -> (B, T, H, D)."""
    _, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _to_kernel(x: jax.Array, folded: bool) -> jax.Array:
    """(B, T, H, D) -> the kernels' (G, T, L)."""
    b, t, h, d = x.shape
    return x.reshape(b, t, h * d) if folded else _heads_major(x)


def _from_kernel(x: jax.Array, b: int, h: int, folded: bool) -> jax.Array:
    return (x.reshape(b, x.shape[1], h, -1) if folded
            else _heads_minor(x, b, h))


def _head_lanes(pack: int, head_dim: int, rows: int):
    """Per head of the column, where its lanes are: a (rows, pack*D) bool
    mask each, or ``[None]`` for a lone head."""
    if pack == 1:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (rows, pack * head_dim), 1)
    return [(lane >= i * head_dim) & (lane < (i + 1) * head_dim)
            for i in range(pack)]


def _only(lanes, x):
    """``x`` with the other heads' lanes zeroed: a contraction operand."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _own_lanes(lanes, per_head):
    """One (rows, pack*D) value from each head's own lanes of its result."""
    out = per_head[0]
    for m, x in zip(lanes[1:], per_head[1:]):
        out = jnp.where(m, x, out)
    return out


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                      block_k: int, seq_len: int, mask: str, scale: float,
                      pack: int, head_dim: int):
    """Grid: (G, columns, T // block_q).  Refs (block-local): q (1, block_q,
    W), k/v (1, T, W), o (1, block_q, W), lse (1, 1, pack, block_q); W is
    the column's width, ``pack`` heads of ``head_dim``.

    Products take q/k/v in the dtype they arrive in and accumulate in f32;
    scores, the running max, the normaliser and the output accumulator are
    f32, and ``p`` is cast to v's dtype only as the operand of ``p @ v`` —
    the precision of ``parallel.sequence.attention_reference``.

    lse rides in a (G, columns, pack, T) layout: Mosaic requires the last
    two dims of every block shape to be (8, 128)-divisible or equal to the
    array dims, and a (pack, block_q) block against array dims (pack, T)
    is."""
    qi = pl.program_id(2)
    q, s_scale = _fold_scale(q_ref[0], scale)             # (Bq, W)
    w = q.shape[-1]
    lanes = _head_lanes(pack, head_dim, block_q)
    qs = [_only(m, q) for m in lanes]
    full, hi = _row_bounds(mask, qi, block_q, block_k, seq_len // block_k)
    rel = (lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
           - lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))

    def step(masked: bool):
        def body(j, carry):
            rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
            k = k_ref[0, rows, :]
            v = v_ref[0, rows, :]
            keep = (_keep(mask, rel, qi * block_q, j * block_k)
                    if masked else None)
            out = []
            for qh, (acc, m, l) in zip(qs, carry):
                s = _dot(qh, k, _NT)                      # (Bq, Bk) f32
                if s_scale is not None:
                    s = s * s_scale
                if masked:
                    s = jnp.where(keep, s, NEG_INF)
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m - m_new)
                l_new = corr * l + p.sum(axis=-1, keepdims=True)
                acc_new = corr * acc + _dot(p.astype(v.dtype), v, _NN)
                out.append((acc_new, m_new, l_new))
            return tuple(out)
        return body

    carry = ((jnp.zeros((block_q, w), jnp.float32),
              jnp.full((block_q, 1), NEG_INF, jnp.float32),
              jnp.zeros((block_q, 1), jnp.float32)),) * pack
    if mask == "none":
        carry = lax.fori_loop(0, hi, step(False), carry)
    else:
        carry = _two_loops(0, full, hi, step(False), step(True), carry)
    outs = []
    for i, (acc, m, l) in enumerate(carry):
        if mask == "causal_exclusive":
            # a row with NO attendable key (its m never left NEG_INF —
            # every seen score was the mask fill, or the loop never ran):
            # output 0 / lse NEG_INF, the ring merge's "no contribution"
            # convention.  Inclusive/none modes never hit this.
            empty = m < (NEG_INF * 0.5)
            l = jnp.where(empty, 1.0, l)
            outs.append(jnp.where(empty, 0.0, acc / l))
            lse_ref[0, 0, i] = jnp.where(empty, NEG_INF,
                                         m + jnp.log(l))[:, 0]
        else:
            outs.append(acc / l)
            lse_ref[0, 0, i] = (m + jnp.log(l))[:, 0]
    o_ref[0] = _own_lanes(lanes, outs).astype(o_ref.dtype)


def _kernel_geometry(b: int, t: int, h: int, d: int, block_q: int,
                     block_k: int, itemsize: int):
    """What the two ``pallas_call``s share: the layout plan, the block
    specs by role, and the VMEM they need besides the backward's scratch."""
    pack, folded = _fold_plan(h, d)
    g, cols, w = (b, h // pack, pack * d) if folded else (b * h, 1, d)
    mem = {"memory_space": pltpu.VMEM}
    specs = {
        # a block of rows of the column / the column's whole rows
        "q_blk": pl.BlockSpec((1, block_q, w), lambda g_, c, i: (g_, i, c),
                              **mem),
        "k_blk": pl.BlockSpec((1, block_k, w), lambda g_, c, i: (g_, i, c),
                              **mem),
        "rows": pl.BlockSpec((1, t, w), lambda g_, c, i: (g_, 0, c), **mem),
        # f32 row statistics (lse, delta), (G, columns, pack, T)
        "stat_blk": pl.BlockSpec((1, 1, pack, block_q),
                                 lambda g_, c, i: (g_, c, 0, i), **mem),
        "stat_rows": pl.BlockSpec((1, 1, pack, t),
                                  lambda g_, c, i: (g_, c, 0, 0), **mem),
    }
    # the resident rows (double-buffered, lane-padded) plus the f32
    # score-sized temporaries of one loop step, per head of the column
    resident = 8 * t * max(w, 128) * itemsize
    tiles = 8 * pack * block_q * block_k * 4
    return pack, folded, (g, cols, w), specs, resident + tiles


def _compiler_params(vmem: int, last: str):
    """The scoped-VMEM limit follows the tile; the first two grid axes are
    independent, the last is ``last`` ("parallel" or "arbitrary")."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", last),
        vmem_limit_bytes=int(min(100 << 20, max(32 << 20, vmem))))


def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                   block_q: Optional[int], block_k: Optional[int],
                   interpret: Optional[bool],
                   mask_mode: Optional[str] = None):
    """q/k/v: (B, T, H, D) -> out (B, T, H, D), lse (B*H, T) float32."""
    block_q, block_k = _checked_blocks(q, block_q, block_k)
    if interpret is None:
        interpret = _interpret_default()
    return _flash_forward_call(q, k, v, mask=_resolve_mask(causal, mask_mode),
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


# The calls are jitted so that a model lowers each kernel once, not once a
# layer: every layer's call is the same jaxpr, and JAX lowers one private
# function for it (Mosaic lowering is not what the compile cache saves).
@functools.partial(jax.jit, static_argnames=("mask", "block_q", "block_k",
                                             "interpret"))
def _flash_forward_call(q, k, v, *, mask: str, block_q: int, block_k: int,
                        interpret: bool):
    b, t, h, d = q.shape
    pack, folded, (g, cols, w), specs, vmem = _kernel_geometry(
        b, t, h, d, block_q, block_k, q.dtype.itemsize)
    kernel = functools.partial(_flash_fwd_kernel, block_q=block_q,
                               block_k=block_k, seq_len=t, mask=mask,
                               scale=1.0 / (d ** 0.5), pack=pack, head_dim=d)
    out, lse = pl.pallas_call(
        kernel,
        grid=(g, cols, t // block_q),
        in_specs=[specs["q_blk"], specs["rows"], specs["rows"]],
        out_specs=[specs["q_blk"], specs["stat_blk"]],
        out_shape=[
            jax.ShapeDtypeStruct((g, t, cols * w), q.dtype),
            jax.ShapeDtypeStruct((g, cols, pack, t), jnp.float32),
        ],
        compiler_params=_compiler_params(vmem, "parallel"),
        interpret=interpret,
        name="flash_fwd",
    )(*(_to_kernel(x, folded) for x in (q, k, v)))
    return _from_kernel(out, b, h, folded), lse.reshape(b * h, t)


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
# Backward kernel (one pass over the causal tile pairs: p and ds are
# recomputed once a pair from (q, k, lse) and feed dq, dk and dv alike;
# delta = rowsum(do * o) is precomputed outside).  As in the forward:
# operands in the dtype they arrive in, f32 scores, lse, delta and
# accumulators; p and ds are cast only as the operand of the next product,
# and the softmax scale lands on the accumulators, once.
# --------------------------------------------------------------------------

def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dqt_ref, *, block_q: int,
                      block_k: int, seq_len: int, mask: str, scale: float,
                      pack: int, head_dim: int):
    """Grid: (G, columns, T // block_k), the k-blocks in order.  k/v/dk/dv
    blocks (1, block_k, W); q/do/dq whole rows (1, T, W); lse/delta whole
    rows (1, 1, pack, T) float32; ``dqt_ref`` a (T // block_q, W, block_q)
    f32 scratch that holds dq^T of the column's rows across the k-blocks.

    Works on the TRANSPOSED tile, keys down and queries across: ``s^T = k
    q^T`` comes out of the MXU that way, ``p^T`` and ``ds^T`` are then the
    right or left operands of plain products, and lse/delta are read as
    the (1, block_q) lane rows they are stored as — no transposed-operand
    product and no row-to-column relayout in the loop.  dq is accumulated
    transposed, ``dq_h^T += k_h^T ds_h^T`` into head h's rows of the
    scratch (k^T made once a k-block), summed over the k-blocks in order,
    and written once, transposed back, after the last."""
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _():
        dqt_ref[...] = jnp.zeros_like(dqt_ref)

    v = v_ref[0]                                          # (Bk, W)
    k_in = k_ref[0]
    k, s_scale = _fold_scale(k_in, scale)
    w = k.shape[-1]
    lanes = _head_lanes(pack, head_dim, block_k)
    ks = [_only(m, k) for m in lanes]
    vs = [_only(m, v) for m in lanes]
    # k_h^T (D, Bk) of each head, once a k-block, as the product of the
    # identity's rows that pick the head's lanes with k^T: exact for bf16
    # operands, and no transpose op, which XLA's CPU backend (where the
    # kernel is interpreted) folds into the dq product as a bf16
    # transposed-operand product it cannot run
    pick = (lax.broadcasted_iota(jnp.int32, (head_dim, w), 1)
            - lax.broadcasted_iota(jnp.int32, (head_dim, w), 0))
    kts = [_dot((pick == n * head_dim).astype(k_in.dtype), k_in,
                _NT).astype(k_in.dtype) for n in range(pack)]
    num_q = seq_len // block_q
    lo, full = _col_bounds(mask, kj, block_q, block_k, num_q)
    # k_local - q_local on the transposed tile
    rel = (lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
           - lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1))

    def step(masked: bool):
        def body(i, carry):
            start = pl.multiple_of(i * block_q, block_q)
            q = q_ref[0, pl.ds(start, block_q), :]        # (Bq, W)
            do = do_ref[0, pl.ds(start, block_q), :]
            keep = (_keep(mask, rel, i * block_q, kj * block_k)
                    if masked else None)
            out = []
            for n, (kh, vh, kth, (dk_acc, dv_acc)) in enumerate(
                    zip(ks, vs, kts, carry)):
                # slice from the refs (Mosaic lowers pl.ds ref reads;
                # value-level lax.dynamic_slice has no TPU lowering rule)
                lse = lse_ref[0, 0, n:n + 1, pl.ds(start, block_q)]
                delta = delta_ref[0, 0, n:n + 1, pl.ds(start, block_q)]
                if mask == "causal_exclusive":
                    # no-key rows carry lse = NEG_INF; every score they see
                    # is the mask fill, so exp(fill - 0) is the 0 their
                    # gradient is, where exp(fill - NEG_INF) would be 1
                    lse = jnp.where(lse > (NEG_INF * 0.5), lse, 0.0)
                st = _dot(kh, q, _NT)                     # (Bk, Bq) f32
                if s_scale is not None:
                    st = st * s_scale
                if masked:
                    st = jnp.where(keep, st, NEG_INF)
                pt = jnp.exp(st - lse)                    # lse: (1, Bq)
                dv_acc = dv_acc + _dot(pt.astype(do.dtype), do, _NN)
                dst = (pt * (_dot(vh, do, _NT) - delta)).astype(q.dtype)
                dk_acc = dk_acc + _dot(dst, q, _NN)
                rows = slice(n * head_dim, (n + 1) * head_dim)
                dqt_ref[i, rows, :] += _dot(kth, dst, _NN)  # (D, Bq)
                out.append((dk_acc, dv_acc))
            return tuple(out)
        return body

    zeros = jnp.zeros((block_k, w), jnp.float32)
    carry = ((zeros, zeros),) * pack
    if mask == "none":
        carry = lax.fori_loop(0, num_q, step(False), carry)
    else:
        carry = _two_loops(lo, full, num_q, step(True), step(False), carry)
    dk_ref[0] = (_own_lanes(lanes, [dk for dk, _ in carry])
                 * scale).astype(dk_ref.dtype)
    dv_ref[0] = _own_lanes(lanes, [dv for _, dv in carry]).astype(
        dv_ref.dtype)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _():
        def write(i, c):
            rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            dq_ref[0, rows, :] = (dqt_ref[i].T * scale).astype(dq_ref.dtype)
            return c
        lax.fori_loop(0, num_q, write, 0)


def _flash_backward(q, k, v, out, lse, g, causal: bool,
                    block_q: Optional[int], block_k: Optional[int],
                    interpret: Optional[bool],
                    g_lse: Optional[jax.Array] = None,
                    mask_mode: Optional[str] = None):
    block_q, block_k = _checked_blocks(q, block_q, block_k)
    if interpret is None:
        interpret = _interpret_default()
    return _flash_backward_call(q, k, v, out, lse, g, g_lse,
                                mask=_resolve_mask(causal, mask_mode),
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("mask", "block_q", "block_k",
                                             "interpret"))
def _flash_backward_call(q, k, v, out, lse, g, g_lse, *, mask: str,
                         block_q: int, block_k: int, interpret: bool):
    b, t, h, d = q.shape
    pack, folded, (grp, cols, w), specs, vmem = _kernel_geometry(
        b, t, h, d, block_q, block_k, q.dtype.itemsize)
    # delta_i = sum_j p_ij * dp_ij = rowsum(do * o): one fused elementwise
    # reduce in XLA over the model's own layout.
    #
    # A cotangent on the lse OUTPUT (flash_attention_with_lse) folds into
    # the same kernel: d lse_i / d s_ij = p_ij, so
    # ds_ij = p_ij * (dp_ij - delta_i + g_lse_i) — i.e. shift delta by
    # -g_lse and nothing else changes (dv is lse-independent).
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = delta.transpose(0, 2, 1).reshape(b * h, t)         # (BH, T)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    stats = (lse.reshape(grp, cols, pack, t),
             delta.reshape(grp, cols, pack, t))
    like = lambda x: jax.ShapeDtypeStruct((grp, t, cols * w), x.dtype)
    # dq^T of the column's rows stays in VMEM across the k-blocks
    dqt = t * max(w, 128) * 4
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_q=block_q,
                          block_k=block_k, seq_len=t, mask=mask,
                          scale=1.0 / (d ** 0.5), pack=pack, head_dim=d),
        grid=(grp, cols, t // block_k),
        in_specs=[specs["rows"], specs["k_blk"], specs["k_blk"],
                  specs["rows"], specs["stat_rows"], specs["stat_rows"]],
        out_specs=[specs["rows"], specs["k_blk"], specs["k_blk"]],
        out_shape=[like(q), like(k), like(v)],
        scratch_shapes=[pltpu.VMEM((t // block_q, w, block_q), jnp.float32)],
        compiler_params=_compiler_params(vmem + dqt, "arbitrary"),
        interpret=interpret,
        name="flash_bwd",
    )(*(_to_kernel(x, folded) for x in (q, k, v, g)), *stats)
    return tuple(_from_kernel(x, b, h, folded) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Blocked attention, Pallas forward + Pallas backward.
    q/k/v: (B, T, H, D).  ``block_q``/``block_k`` None: derived from
    (T, head_dim, dtype) by :func:`flash_blocks`."""
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
                             causal: bool = True,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
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

# Measured tilings of the paged kernel, ``(block_size, lanes of a pool row,
# "decode" | "chunk") -> (pages, tile_cols)``: pool pages DMA'd and scored a
# loop step, and query columns a row tile (1 at decode).  Keyed by what the
# kernel sees of its operands (the lanes are ``kv_heads * head_dim`` of a
# per-head row, the stored width of a row that is its own value); a row is
# there only if it was timed on the chip (tools/paged_attn_timing.py on one
# TPU v5e, PERF.md section 6.  PR 30, the per-head row of 256 lanes: 64
# pages tie with 32 at the cell's lengths and win at the table's full
# width; a chunk is flat between 64 and 128 columns, and 64 compiles in a
# third of the time.  PR 32, the latent row stored 384 lanes wide, one copy
# a page: 64 pages tie with 128 at the cell's lengths and lose 3 % to them
# at the table's full width, at half the landing buffer; the untimed rule's
# 8 pages take 1.6 x as long.  PR 33, the per-head row of 1024 lanes, a
# 32 KB page a pool: decode, 64 pages beat 32 by 7 % and 128 by 14 %; under a
# window of 128 (``"decode_window"``, ``"chunk_window"``) the walk is flat
# from 5 pages a step up, a chunk's from 13 pages at 64 columns; row tiles of
# 512 rows over steps of 512 keys, and any tile of 1024 rows, do not fit
# VMEM with 8 KV heads unrolled; ``(32, 32)`` is 5 % faster than the chunk's
# ``(16, 64)`` at depth and as fast at start 0: a lead).  Every other shape walks ``_UNTIMED_KEYS`` key
# positions a step and tiles a chunk at about ``_UNTIMED_ROWS`` query rows
# a KV head, and at no more rows than ``_UNTIMED_TILE`` lanes of a pool row
# allow: 512 rows of 1024 lanes is the widest tile that compiled and was
# timed, and 256 rows of the 3840-lane row of 30 KV heads ask 137 MB of the
# 128 MB of VMEM (a described v5e's compile of a 512-column chunk).
PAGED_TILES = {
    (16, 256, "decode"): (64, 1),
    (16, 256, "chunk"): (32, 64),
    (16, 384, "decode"): (64, 1),
    (16, 1024, "decode"): (64, 1),
    (16, 1024, "chunk"): (16, 64),
    (16, 1024, "decode_window"): (5, 1),
    (16, 1024, "chunk_window"): (13, 64),
}
_UNTIMED_KEYS = 128
_UNTIMED_ROWS = 256
_UNTIMED_TILE = 512 * 1024


def paged_tiles(block_size: int, lanes: int, width: int, groups: int,
                max_blocks: int, pages: Optional[int] = None,
                tile_cols: Optional[int] = None,
                quant: bool = False,
                window: Optional[int] = None) -> Tuple[int, int]:
    """``(pages, tile_cols)`` of the paged kernel for this input: an
    explicit size wins, ``None`` comes from the timed table (else the
    untimed rule).  ``pages`` is cut to the table's width, and is 1 for
    int8 pools (``quant``: a 16-row int8 page is half a sublane tile, so
    pages do not stack in one landing buffer); ``tile_cols`` must divide
    ``width`` into tiles whose rows (``tile_cols * groups``) fill whole
    sublanes, else the chunk is one tile.  A walk bounded below by a
    ``window`` has rows of its own (``"decode_window"``, ``"chunk_window"``);
    untimed, it takes in one step the pages a tile's rows can see: the
    window's and the tile's own, and the page the bound cuts."""
    kind = ("decode" if width == 1 else "chunk") + (
        "" if window is None else "_window")
    rows = min(_UNTIMED_ROWS, _UNTIMED_TILE // lanes)
    t_pages, t_cols = PAGED_TILES.get((block_size, lanes, kind),
                                      (None, max(1, rows // groups)))
    cols = min(t_cols if tile_cols is None else tile_cols, width)
    fits = [c for c in range(cols, 0, -1)
            if width % c == 0 and (c * groups) % 8 == 0]
    cols = fits[0] if fits else width
    if t_pages is None:
        t_pages = (max(1, _UNTIMED_KEYS // block_size) if window is None
                   else -(-(window + cols - 1) // block_size) + 1)
    pages = 1 if quant else max(
        1, min(t_pages if pages is None else pages, max_blocks))
    return pages, cols


def _paged_attn_kernel(tables_ref, lens_ref, starts_ref, q_ref, k_hbm,
                       *rest, block_size: int, pages: int,
                       kv_heads: int, groups: int, tile_cols: int,
                       scale: float, quant: bool,
                       v_lanes: Optional[int] = None,
                       window: Optional[int] = None):
    """Grid: (streams, row tiles).  A program owns ``tile_cols`` query
    columns of one stream (all heads: ``tile_cols * groups`` rows a KV
    head) and walks the stream's block table ``pages`` entries a loop step,
    up to the last key ITS rows can see — ``min(len, first + tile_cols)``,
    a dynamic ``fori_loop`` bound.  All ``pages`` copies of step ``j+1``
    are in flight (``make_async_copy`` into the other half of a two-slot
    landing buffer) while step ``j`` computes lane-dense ``(rows, pages *
    block_size)`` scores, carrying the online-softmax (max, denom, acc) a
    head.  KV heads are unrolled in-program: one page fetch serves every
    head.  A step's copies are issued and awaited by a loop over its pages,
    not an unrolled list: unrolled they ran 15 % faster at decode and cost
    3.5 s of lowering a program (PERF.md section 6, PR 30).

    Refs: ``tables (S, MB)`` / ``lens (S,)`` / ``starts (S,)`` ride scalar
    prefetch (SMEM) — runtime VALUES, not compile-time constants, so table
    churn and length growth re-run the same compiled kernel.  ``q (1, KV,
    rows, hd)`` in VMEM; the pools ``(NB, bs, KV·hd)`` (and int8 scale
    pools ``(NB, 1, KV·bs)`` when ``quant``, one page a step) stay
    UNBLOCKED in HBM — only the pages a tile needs ever cross into VMEM.
    A pool's last dim is lane-dense (heads folded into it; head ``h`` is a
    static lane slice): Mosaic refuses to DMA-slice a page out of an array
    whose last dim is below the 128-lane tile.

    ``v_lanes`` is the **shared-row mode** (latent attention's absorbed
    decode: multi-query attention whose one KV head's value is the head of
    its key): there is no value pool, a page is fetched once into the one
    landing buffer, scores are ``q . page^T`` over the row's whole width and
    values ``p . page[:, :v_lanes]``, so the output is ``v_lanes`` wide.

    Operands reach the MXU in the pool's type (bf16 pools: bf16 products,
    f32 accumulation; f32 pools stay exact; int8 pools are cast to f32 and
    scaled as the gathered path scales them); scores, the softmax
    scale (``1/sqrt(hd)`` unless the caller hands one in) and the softmax
    state are f32.

    Steps wholly below the tile's first query position and the stream's
    length need no mask; the last one or two apply ``k < len`` and the
    per-row causal bound, and zero the value rows past the last fetched
    key (a page that is not needed is not fetched, so its landing rows
    hold whatever was there).  A ``len == 0`` lane, and a tile that starts
    at or past ``len`` (the pad columns of a bucketed chunk), walks nothing
    and exits with output 0, the flash kernels' "no contribution"
    convention; the sink block is never attended.

    ``window`` bounds the walk from below: a row at position ``p`` sees keys
    ``p - window + 1 .. p``, so the tile's walk STARTS at the page of its
    first row's oldest key (``(first - window + 1) // block_size``; table
    entries before it are never read, and may point at the sink) and every
    step is masked by position: the first page at the bound, the last at
    the causal edge.  A decode row at length ``n`` walks pages ``(n -
    window) // bs .. (n - 1) // bs``."""
    shared = v_lanes is not None
    if shared:
        o_ref, k_buf, sem = rest
    elif quant:
        (v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, sem) = rest
    else:
        v_hbm, o_ref, k_buf, v_buf, sem = rest
    s, t = pl.program_id(0), pl.program_id(1)
    ln = lens_ref[s]
    first = starts_ref[s] + t * tile_cols       # the tile's first query
    limit = lax.min(ln, first + tile_cols)      # keys its rows can see
    span = pages * block_size
    n_pages = lax.div(limit + block_size - 1, block_size)
    if window is None:
        n_steps = lax.div(limit + span - 1, span)
        n_full = lax.div(lax.min(first + 1, limit), span)
        page_of = lambda j, i: j * pages + i                # noqa: E731
        key0 = lambda j: j * span                           # noqa: E731
    else:
        # the walk's first page; pages, steps and key offsets count from it
        page0 = lax.div(lax.max(first - (window - 1), 0), block_size)
        n_pages = n_pages - page0
        n_steps = lax.div(n_pages + pages - 1, pages)
        n_full = 0
        page_of = lambda j, i: page0 + j * pages + i        # noqa: E731
        key0 = lambda j: page0 * block_size + j * span      # noqa: E731
    rows = tile_cols * groups
    hd = q_ref.shape[-1]
    vd = v_lanes if shared else hd              # lanes of a value row

    def page_copies(j, i):
        slot = lax.rem(j, 2)
        blk = tables_ref[s, page_of(j, i)]
        dst = pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
        ops = [pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[slot, dst],
                                     sem.at[slot, 0])]
        if not shared:
            ops.append(
                pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[slot, dst],
                                      sem.at[slot, 1]))
        if quant:
            ops += [
                pltpu.make_async_copy(ks_hbm.at[blk], ks_buf.at[slot],
                                      sem.at[slot, 2]),
                pltpu.make_async_copy(vs_hbm.at[blk], vs_buf.at[slot],
                                      sem.at[slot, 3]),
            ]
        return ops

    def each_page(j, act, guarded: bool):
        """``act`` on every copy of step ``j``; ``guarded`` stops at the
        tile's last page (a full step has them all)."""
        count = (lax.min(pages, n_pages - j * pages) if guarded else pages)

        def one(i, carry):
            for c in page_copies(j, i):
                act(c)
            return carry

        lax.fori_loop(0, count, one, 0)

    # rows are (column, group) flattened: row r is query column r // groups,
    # so key offset o is at or below it where o * groups <= r
    k_off = lax.broadcasted_iota(jnp.int32, (rows, span), 1)
    rel = k_off * groups - lax.broadcasted_iota(jnp.int32, (rows, span), 0)
    v_row = lax.broadcasted_iota(jnp.int32, (span, 1), 0)
    qs = [q_ref[0, h] for h in range(kv_heads)]             # (rows, hd)
    if quant:
        qs = [q.astype(jnp.float32) for q in qs]

    def step(masked: bool):
        def body(j, carry):
            @pl.when(j + 1 < n_steps)
            def _prefetch():
                each_page(j + 1, lambda c: c.start(), True)

            each_page(j, lambda c: c.wait(), masked)
            slot = lax.rem(j, 2)

            def head_scale(buf, h):
                # (1, bs), sliced from the REF: a value slice past lane 128
                # of the (1, KV*bs) row does not lower
                return buf[slot, :, h * block_size:(h + 1) * block_size]

            if masked:
                k0 = key0(j)
                keep = (k_off < ln - k0) & (rel <= (first - k0) * groups)
                if window is not None:
                    keep &= rel > (first - k0 - window) * groups
                fetched = v_row < limit - k0
            out = []
            for h, (acc, m, l) in enumerate(carry):
                lanes = slice(h * hd, (h + 1) * hd)
                k = k_buf[slot, :, lanes]                   # (span, hd)
                v = k[:, :vd] if shared else v_buf[slot, :, lanes]
                if quant:
                    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
                if masked:
                    v = jnp.where(fetched, v, jnp.zeros_like(v))
                sc = _dot(qs[h], k, _NT) * scale            # (rows, span)
                if quant:
                    sc = sc * head_scale(ks_buf, h)
                if masked:
                    sc = jnp.where(keep, sc, NEG_INF)
                m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
                # every walked row sees key 0 in step 0 (its position is
                # >= 0 and len > 0), so m is finite before the running
                # exp() can ever see exp(0) garbage (under a window a row
                # may see nothing before a later step: what it summed until
                # then is finite, and exp(m - m_new) = 0 wipes it)
                p = jnp.exp(sc - m_new)
                corr = jnp.exp(m - m_new)
                l_new = corr * l + p.sum(axis=-1, keepdims=True)
                if quant:
                    p = p * head_scale(vs_buf, h)
                acc_new = corr * acc + _dot(p.astype(v.dtype), v, _NN)
                out.append((acc_new, m_new, l_new))
            return tuple(out)
        return body

    # per-head carries as tuples: the kv_heads loop is a Python unroll,
    # and a stacked (kv_heads, rows, ...) carry updated with .at[h].set
    # is a scatter, which Mosaic does not lower
    carry0 = ((jnp.zeros((rows, vd), jnp.float32),
               jnp.full((rows, 1), NEG_INF, jnp.float32),
               jnp.zeros((rows, 1), jnp.float32)),) * kv_heads
    walk = (ln > 0) & (first < ln)

    @pl.when(jnp.logical_not(walk))
    def _inactive():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(walk)
    def _walk():
        each_page(0, lambda c: c.start(), True)
        carry = _two_loops(0, n_full, n_steps, step(False), step(True),
                           carry0)
        for h, (acc, m, l) in enumerate(carry):
            empty = m < (NEG_INF * 0.5)
            l_safe = jnp.where(empty, 1.0, l)
            o_ref[0, h] = jnp.where(empty, 0.0,
                                    acc / l_safe).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array,
                    v_pool: Optional[jax.Array],
                    tables: jax.Array, lengths: jax.Array,
                    starts: jax.Array, *,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None,
                    v_lanes: Optional[int] = None,
                    scale: Optional[float] = None,
                    pages: Optional[int] = None,
                    tile_cols: Optional[int] = None,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Fused paged attention: reads K/V straight from the serving block
    pool through per-stream block tables and reduces over each stream's
    TRUE length instead of the table capacity ``max_blocks·block_size``
    (serve/paged_kv.py's gathered path).

    One kernel covers the family: ``width == 1`` is the batched decode
    step (each stream's single query at position ``lengths-1``),
    ``width > 1`` is a chunked-prefill bucket (rows at absolute positions
    ``starts .. starts+width-1``, flash-style causal within the chunk),
    tiled over its query columns.

    * ``q``: (streams, width, n_heads, head_dim) — GQA folds in-kernel
      (``n_heads`` must be a multiple of the pool's ``kv_heads``).
    * ``k_pool``/``v_pool``: (num_blocks, block_size, kv_heads, head_dim),
      or with the heads already folded into the lanes, (num_blocks,
      block_size, kv_heads·head_dim) — the layout the kernel reads, so a
      pool stored that way reaches it without a copy — f32/bf16, or int8
      with ``k_scale``/``v_scale`` (num_blocks, block_size, kv_heads) f32,
      applied to the scores and probabilities.
    * ``v_pool=None`` with ``v_lanes``: the **shared-row mode** — one pool
      row is key and value (latent attention's absorbed decode: every query
      head scores against the same row, and the value is its first
      ``v_lanes`` lanes).  ``k_pool`` is (num_blocks, block_size, lanes) and
      ``q`` (streams, width, n_heads, lanes): a page is fetched once, and
      the result is (streams, width, n_heads, v_lanes).  Lanes of the row
      that are padding hold zeros in the pool or in the query.
    * ``tables``: (streams, max_blocks) int32 pool indices; unallocated
      entries point at the sink block and are NEVER walked (the page
      walk stops at ``ceil(length/block_size)``).
    * ``lengths``: (streams,) int32 attendable keys per stream (0 = an
      inactive lane: zero pages walked, zero pages fetched, output 0).
    * ``starts``: (streams,) int32 absolute position of each stream's
      first query row (decode passes ``lengths - 1``).
    * ``scale``: the softmax scale of the f32 scores; ``None`` is
      ``1/sqrt(head_dim)``.
    * ``pages`` / ``tile_cols``: pages a loop step and query columns a row
      tile; ``None`` asks :func:`paged_tiles` (int8 pools walk one page a
      step).
    * ``window``: a query at position ``p`` sees keys ``p - window + 1 ..
      p`` only, and the page walk starts at the page of the tile's oldest
      visible key; ``None`` walks from the stream's first page.

    Tables/lengths/starts are traced scalar-prefetch operands: block-table
    churn (admission, growth, eviction) re-runs the SAME compiled kernel
    — pinned by tests/test_paged_attn.py's compile-count test."""
    s_n, width, n_heads, hd = q.shape
    if v_pool is None:
        if v_lanes is None or k_scale is not None or v_scale is not None:
            raise ValueError("a pool row that is its own value (v_pool=None) "
                             "needs v_lanes, and has no int8 scheme")
        if k_pool.ndim != 3 or k_pool.shape[2] != hd or not 0 < v_lanes <= hd:
            raise ValueError(
                f"shared-row mode: the query's {hd} lanes must be the pool "
                f"row's (pool {k_pool.shape}), the value its first v_lanes "
                f"({v_lanes})")
        bs, kv_heads = k_pool.shape[1], 1
    elif v_lanes is not None:
        raise ValueError("v_lanes belongs to the shared-row mode "
                         "(v_pool=None)")
    elif k_pool.ndim == 3:
        nb, bs, lanes = k_pool.shape
        if lanes % hd:
            raise ValueError(f"folded pool row {lanes} is not a multiple of "
                             f"head_dim {hd}")
        kv_heads = lanes // hd
    else:
        nb, bs, kv_heads, hd_k = k_pool.shape
        if hd_k != hd:
            raise ValueError(f"head_dim mismatch: q {hd} vs pool {hd_k}")
    if n_heads % kv_heads:
        raise ValueError(f"n_heads {n_heads} not a multiple of kv_heads "
                         f"{kv_heads}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("int8 pools need BOTH k_scale and v_scale")
    if interpret is None:
        interpret = _interpret_default()
    pages, tile_cols = paged_tiles(
        bs, kv_heads * hd, width, n_heads // kv_heads, tables.shape[1],
        pages, tile_cols, quant=k_scale is not None, window=window)
    if window is None:      # the call's text (and cache key) as it was
        return _paged_attention_call(
            q, k_pool, v_pool, tables, lengths, starts, k_scale, v_scale,
            pages=pages, tile_cols=tile_cols, interpret=interpret,
            v_lanes=v_lanes, scale=scale)
    if window < 1:
        raise ValueError(f"window {window} < 1")
    return _paged_attention_call(
        q, k_pool, v_pool, tables, lengths, starts, k_scale, v_scale,
        pages=pages, tile_cols=tile_cols, interpret=interpret,
        v_lanes=v_lanes, scale=scale, window=window)


# jitted for the flash calls' reason: 30 unrolled layers lower one kernel
@functools.partial(jax.jit, static_argnames=("pages", "tile_cols",
                                             "interpret", "v_lanes", "scale",
                                             "window"))
def _paged_attention_call(q, k_pool, v_pool, tables, lengths, starts,
                          k_scale, v_scale, *, pages: int, tile_cols: int,
                          interpret: bool, v_lanes: Optional[int] = None,
                          scale: Optional[float] = None,
                          window: Optional[int] = None):
    s_n, width, n_heads, hd = q.shape
    nb, bs = k_pool.shape[:2]
    lanes = math.prod(k_pool.shape[2:])
    kv_heads = lanes // hd
    quant = k_scale is not None
    shared = v_pool is None
    groups = n_heads // kv_heads
    rows = tile_cols * groups
    span = pages * bs
    # the value's lanes inside the kernel: whole 128-lane tiles of the
    # shared row (a narrower slice of a landed page does not lower), cut to
    # ``v_lanes`` on the way out
    vd = min(-(-v_lanes // 128) * 128, hd) if shared else hd

    # (S, W, H, hd) -> (S, KV, W·G, hd): per-kv-head query rows contiguous
    qk = q.reshape(s_n, width, kv_heads, groups, hd)
    qk = qk.transpose(0, 2, 1, 3, 4).reshape(s_n, kv_heads,
                                             width * groups, hd)
    if not quant:
        qk = qk.astype(k_pool.dtype)

    row_map = lambda s, t, tbl, lns, sts: (s, 0, t, 0)      # noqa: E731
    hbm_spec = pl.BlockSpec(memory_space=pltpu.HBM)      # never blocked
    # pool pages cross into VMEM as lane-dense (bs, KV*hd) rows (see
    # the kernel's docstring); head h is the lane slice [h*hd, (h+1)*hd)
    pools = [k_pool] if shared else [k_pool, v_pool]
    in_specs = [pl.BlockSpec((1, kv_heads, rows, hd), row_map),
                *[hbm_spec] * len(pools)]
    operands = [qk, *[p.reshape(nb, bs, lanes) for p in pools]]
    n_dma = len(pools)
    scratch = [pltpu.VMEM((2, span, lanes), p.dtype) for p in pools]
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
        grid=(s_n, width // tile_cols),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv_heads, rows, vd), row_map),
        scratch_shapes=scratch,
    )
    # the landing buffers, the query and output tiles (double-buffered,
    # lane-padded) and the f32 score-sized temporaries of one loop step
    landing = 2 * len(pools) * span * lanes * k_pool.dtype.itemsize
    tiles = 4 * kv_heads * rows * max(hd, 128) * 4
    scores = 8 * max(rows, 8) * max(span, 128) * 4
    out = pl.pallas_call(
        functools.partial(
            _paged_attn_kernel, block_size=bs, pages=pages,
            kv_heads=kv_heads, groups=groups, tile_cols=tile_cols,
            scale=1.0 / (hd ** 0.5) if scale is None else scale,
            quant=quant, v_lanes=vd if shared else None, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (s_n, kv_heads, width * groups, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(min(100 << 20, max(
                32 << 20, landing + tiles + scores)))),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
      starts.astype(jnp.int32), *operands)
    # (S, KV, W·G, vd) -> (S, W, H, vd)
    out = out.reshape(s_n, kv_heads, width, groups, vd)
    out = out.transpose(0, 2, 1, 3, 4).reshape(s_n, width, n_heads, vd)
    return out[..., :v_lanes] if shared and vd != v_lanes else out


# ==========================================================================
# Delta rule: a decode tick's state update (models/deltanet.py, gdn_update)
# ==========================================================================

# State bytes a grid step of ``delta_rule_step`` holds at most: whole slots
# of every head, at least one.  A block is read once and written once, each
# double-buffered, so VMEM holds four of them.
DELTA_RULE_BLOCK_BYTES = 4 << 20


def delta_rule_block(slots: int, heads: int, rows: int, lanes: int) -> int:
    """Slots a grid step of :func:`delta_rule_step` updates: as many whole
    slots of all ``heads`` as ``DELTA_RULE_BLOCK_BYTES`` of float32 state
    rows ``(rows, lanes)`` hold, at least one and at most ``slots``."""
    per_slot = heads * rows * lanes * 4
    return max(1, min(slots, DELTA_RULE_BLOCK_BYTES // per_slot))


def _delta_rule_kernel(active_ref, gates_ref, s_ref, kq_ref, v_ref, s_out,
                       o_ref, *, slots: int, heads: int, fold: int, dv: int,
                       total: int):
    """One grid step: ``slots`` streams, every head, each head's state
    block ``(dk/fold, fold·dv)`` read once and written once.

    ``kq_ref`` (slots, dk/fold, 2·heads·fold) holds the keys then the
    queries with a stored row's ``fold`` entries in lanes side by side:
    lane ``h·fold + j`` of row ``r`` is ``k[h, fold·r + j]``.  A lane of it
    broadcast over the lanes of the stored row that hold ``S[fold·r + j]``
    (lane ``l`` holds row ``l // dv``) is ``k`` spread over the state;
    the state times it summed over the rows is each of the ``fold`` rows'
    part of ``S^T k``, and the ``fold`` parts summed by rolls of ``dv``
    lanes leave ``S^T k`` in every part: the ``u`` each part's update
    takes.  ``active_ref`` (streams,) and ``gates_ref`` (3·streams·heads:
    ``beta``, the decay, ``k . q``) are scalars in SMEM; ``v_ref`` and
    ``o_ref`` (slots, heads, fold·dv) are a head's value row tiled
    ``fold`` times and its output, every part the same."""
    rows, lanes = s_ref.shape[-2:]
    part = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // dv

    def spread(kq, col):
        out = jnp.broadcast_to(kq[:, col:col + 1], (rows, lanes))
        for j in range(1, fold):
            out = jnp.where(part == j, kq[:, col + j:col + j + 1], out)
        return out

    def gather(r):
        out = r
        for j in range(1, fold):
            out = out + pltpu.roll(r, j * dv, 1)
        return out

    for i in range(slots):
        # the last block's rows past the streams are read and not written
        at = jnp.minimum(pl.program_id(0) * slots + i, total - 1)
        live = active_ref[at] != 0
        kq = kq_ref[i]
        for h in range(heads):
            beta = gates_ref[at * heads + h]
            decay = gates_ref[(total + at) * heads + h]
            kdq = gates_ref[(2 * total + at) * heads + h]
            s = s_ref[i, h]
            ks = spread(kq, h * fold)
            sk = gather((s * ks).sum(0, keepdims=True))
            sq = gather((s * spread(kq, (heads + h) * fold)).sum(
                0, keepdims=True))
            u = beta * (v_ref[i, h:h + 1, :] - decay * sk)
            o_ref[i, h:h + 1, :] = decay * sq + kdq * u

            @pl.when(live)
            def _():
                s_out[i, h] = decay * s + ks * u

            @pl.when(jnp.logical_not(live))
            def _():
                s_out[i, h] = s


def delta_rule_step(state: jax.Array, k: jax.Array, q: jax.Array,
                    v: jax.Array, beta: jax.Array, decay: jax.Array,
                    active: jax.Array, *, block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """One decode tick of the gated delta rule over a store of state rows,
    in place: ``S' = decay S + k u^T`` with ``u = beta (v - decay S^T k)``,
    and ``o = S'^T q = decay S^T q + (k . q) u``, every product float32 on
    the VPU.  A stream's state is read once and written once: XLA's form
    (models/deltanet.py) reads it a second time, since the pass that writes
    ``S'`` needs ``u``, which needs the reduction ``S^T k`` of the same
    state.

    * ``state``: (streams, heads, dk/fold, fold·dv) float32, ``dk x dv`` a
      head with ``fold`` rows side by side in a stored row; it is aliased
      to the new state (donate it, and the update is in place).
    * ``k``, ``q``: (streams, heads, dk); ``v``: (streams, heads, dv);
      ``beta``, ``decay``: (streams, heads); all float32.
    * ``active``: (streams,) bool; a stream that is not active keeps its
      state bit for bit.
    * ``block``: streams a grid step, every head (None:
      :func:`delta_rule_block`); a last block that is cut short reads
      past the streams and writes nothing there.

    Returns (the new state, ``o`` (streams, heads, dv) float32)."""
    if interpret is None:
        interpret = _interpret_default()
    s_n, heads, rows, lanes = state.shape
    if block is None:
        block = delta_rule_block(s_n, heads, rows, lanes)
    return _delta_rule_call(state, k, q, v, beta, decay, active,
                            block=block, interpret=interpret)


# jitted for the flash calls' reason: 12 unrolled layers lower one kernel
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _delta_rule_call(state, k, q, v, beta, decay, active, *, block: int,
                     interpret: bool):
    s_n, heads, rows, lanes = state.shape
    dk, dv = k.shape[-1], v.shape[-1]
    fold = dk // rows

    def lanes_of(x):    # (S, H, dk) -> (S, dk/fold, H·fold)
        return x.reshape(s_n, heads, rows, fold).transpose(0, 2, 1, 3) \
            .reshape(s_n, rows, heads * fold)

    kq = jnp.concatenate([lanes_of(k), lanes_of(q)], axis=-1)
    gates = jnp.stack([beta, decay, (k * q).sum(-1)]).reshape(-1)
    blk = lambda *shape: pl.BlockSpec(                       # noqa: E731
        (block,) + shape, lambda g, *_: (g,) + (0,) * len(shape))
    # the state block in and out, double-buffered, and the small blocks
    # beside it (each lane- and sublane-padded)
    state_blk = block * heads * rows * lanes * 4
    small = 4 * block * (rows * max(2 * heads * fold, 128)
                         + 2 * -(-heads // 8) * 8 * lanes) * 4
    new, o = pl.pallas_call(
        functools.partial(_delta_rule_kernel, slots=block, heads=heads,
                          fold=fold, dv=dv, total=s_n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(s_n, block),),
            in_specs=[blk(heads, rows, lanes),
                      blk(rows, 2 * heads * fold), blk(heads, lanes)],
            out_specs=[blk(heads, rows, lanes), blk(heads, lanes)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((s_n, heads, lanes), jnp.float32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=int(min(100 << 20, max(
                32 << 20, 4 * state_blk + small + (8 << 20))))),
        interpret=interpret,
        name="delta_rule_step",
    )(active.astype(jnp.int32), gates, state, kq, jnp.tile(v, fold))
    return new, o[..., :dv]
