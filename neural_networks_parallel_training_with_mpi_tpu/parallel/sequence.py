"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence axis at all (inputs are (B, 2) feature vectors,
dataParallelTraining_NN_MPI.py:72; SURVEY.md §5.7), but long-context scaling
is first-class here: a sequence sharded over the mesh's 'seq' axis is attended
to without ever materializing the full (T, T) score matrix on one chip.

Two strategies, both pure functions meant to run inside ``shard_map`` with the
'seq' axis bound:

* ``ring_attention`` — K/V blocks rotate around the ring via ``ppermute``
  while each device keeps its Q shard, combining partial results with a
  numerically-stable online softmax (the blockwise/flash recurrence).  ICI
  traffic per step: one K/V block per hop, overlappable with the local
  block matmul.
* ``ulysses_attention`` — ``all_to_all`` re-shards from sequence-sharded to
  head-sharded, runs ordinary full-sequence attention per head group, then
  all-to-alls back.  Cheaper compute, two all-to-alls of activation size.

Shapes: q/k/v are the *local* shards (B, T_local, H, Dh); positions are
global (block i owns [i*T_local, (i+1)*T_local)), which is how causal masking
stays exact across the ring.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """(B, H, Tq, Tk) attention scores for one block pair, fp32 accumulate."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def _causal_mask(q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """(Tq, Tk) True where k may be attended (k_pos <= q_pos)."""
    return k_pos[None, :] <= q_pos[:, None]


def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Plain full-sequence attention (B, T, H, Dh) — the single-device
    semantics that ring/ulysses must reproduce; also the dense path of
    models.transformer.  ``window`` (causal only): a query sees itself and
    the ``window - 1`` keys before it."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    scores = _block_scores(q, k, scale)
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        mask = _causal_mask(jnp.arange(t_q), jnp.arange(t_k))
        if window is not None:
            mask &= (jnp.arange(t_k)[None, :]
                     > jnp.arange(t_q)[:, None] - window)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_dense_blockwise(q: jax.Array, k: jax.Array, v: jax.Array,
                              causal: bool = True,
                              scale: Optional[float] = None,
                              q_chunk: int = 256) -> jax.Array:
    """Exact dense attention computed one QUERY block at a time
    (VERDICT r4 item 5): the scores temp is (B, H, C, T) per scan tick,
    never the full (B, H, T, T) — the blockwise workaround for the
    remote-compile-helper HTTP 500 that the full dense big_lm variant
    trips (BIGLM_SWEEP.json ``b8_none_dense`` error; BASELINE.md calls
    the failure signature "suspected systematic for programs with the
    (B,H,T,T) dense-score temp").

    Math is IDENTICAL to :func:`attention_reference` — each query row
    still sees every key before its softmax (no streaming/rescaling), so
    this is dense attention with bounded temp memory, not flash.  XLA
    unrolls nothing: a ``lax.scan`` over T/q_chunk ticks keeps one
    block's scores live at a time (peak temp = B*H*q_chunk*T*4 bytes,
    8x under the b8 big_lm full tensor at the default chunk)."""
    b, t, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    if t % q_chunk:
        # keep the bounded-temp guarantee for any T: largest divisor of
        # t that fits the requested chunk (worst case 1 -> t ticks of
        # (B,H,1,T), still never the full (B,H,T,T) tensor this function
        # exists to avoid)
        q_chunk = next(c for c in range(min(q_chunk, t), 0, -1)
                       if t % c == 0)
    n_blocks = t // q_chunk
    t_k = k.shape[1]
    kt = jnp.swapaxes(k, 1, 2)                    # (B, H, Tk, D)
    vt = jnp.swapaxes(v, 1, 2)                    # (B, H, Tk, D)
    q_blocks = jnp.swapaxes(q, 1, 2).reshape(b, h, n_blocks, q_chunk, d)
    q_blocks = jnp.moveaxis(q_blocks, 2, 0)       # (N, B, H, C, D)

    def tick(i, q_blk):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q_blk.astype(jnp.float32),
                            kt.astype(jnp.float32)) * scale
        if causal:
            rows = i * q_chunk + jnp.arange(q_chunk)
            mask = _causal_mask(rows, jnp.arange(t_k))
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
        return i + 1, out

    _, out = lax.scan(tick, 0, q_blocks)          # (N, B, H, C, D)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, t, d)
    return jnp.swapaxes(out, 1, 2)                # (B, T, H, D)


def striped_permutation(t: int, s: int) -> "np.ndarray":
    """Permutation mapping a length-``t`` sequence to the STRIPED layout:
    after ``x[:, perm]`` and contiguous sharding into ``s`` shards, shard d
    holds the original positions d, d+s, d+2s, ... (round-robin).  Under
    this layout every causal ring block pair is exactly a triangle (half
    work on every device every tick — Striped Attention, Brandon et al.
    2023), instead of the contiguous layout's all-or-nothing blocks whose
    skipped FLOPs lockstep SPMD cannot convert into wall-clock.  Apply the
    same permutation to inputs AND targets; per-token losses are
    permutation-invariant, so training trajectories match the dense model
    exactly (tests/test_sequence_parallel.py)."""
    import numpy as np

    if t % s:
        raise ValueError(f"seq len {t} not divisible by {s} shards")
    return np.concatenate([np.arange(d, t, s) for d in range(s)])


def inverse_striped_permutation(t: int, s: int) -> "np.ndarray":
    import numpy as np

    return np.argsort(striped_permutation(t, s))


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis: str = "seq", causal: bool = True,
                   scale: Optional[float] = None,
                   striped: bool = False) -> jax.Array:
    """Ring attention over the named ``axis`` (must be bound by shard_map).

    Online-softmax state per Q row: running max ``m``, normalizer ``l``,
    accumulator ``o``.  Each of the S ring steps processes the K/V block that
    currently resides on this device, then rotates K/V one hop so every device
    sees every block after S steps.  Communication is S-1 ppermutes of one
    local K/V block (the final block's compute is hoisted out of the scan so
    no rotate-back hop is emitted) — no all-gather of the full sequence,
    which is what makes context length scale linearly in devices.

    ``striped``: the shards hold round-robin token stripes
    (:func:`striped_permutation`) instead of contiguous chunks; only the
    global-position vectors change (local index i on shard r is global
    position r + s*i), the ring/merge machinery is identical.
    """
    b, t_local, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    s = lax.axis_size(axis)
    my_idx = lax.axis_index(axis)
    q_pos = (my_idx + s * jnp.arange(t_local) if striped
             else my_idx * t_local + jnp.arange(t_local))

    m0 = jnp.full((b, h, t_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    o0 = jnp.zeros((b, t_local, h, d), jnp.float32)

    def merge(m, l, o, k_blk, v_blk, step_idx):
        # the block currently on this device originated at ring position:
        blk_idx = (my_idx + step_idx) % s
        k_pos = (blk_idx + s * jnp.arange(t_local) if striped
                 else blk_idx * t_local + jnp.arange(t_local))
        scores = _block_scores(q, k_blk, scale)  # (B,H,Tq,Tk) fp32
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        blk_max = scores.max(axis=-1)                      # (B,H,Tq)
        new_m = jnp.maximum(m, blk_max)
        # guard: rows with nothing attendable yet keep m=-inf; exp underflows to 0
        correction = jnp.exp(m - new_m)                    # (B,H,Tq)
        p = jnp.exp(scores - new_m[..., None])             # (B,H,Tq,Tk)
        new_l = l * correction + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        new_o = o * correction.transpose(0, 2, 1)[..., None] + pv
        return new_m, new_l, new_o

    def step(carry, step_idx):
        m, l, o, k_blk, v_blk = carry
        new_m, new_l, new_o = merge(m, l, o, k_blk, v_blk, step_idx)
        # rotate K/V to the next device (shift -1 so blk_idx advances by +1)
        perm = [(i, (i - 1) % s) for i in range(s)]
        k_next = lax.ppermute(k_blk, axis, perm)
        v_next = lax.ppermute(v_blk, axis, perm)
        return (new_m, new_l, new_o, k_next, v_next), None

    # scan the first s-1 blocks (compute + rotate); the last resident block
    # is merged outside the scan — its rotate-back hop would carry data no
    # step ever reads
    (m, l, o, k_last, v_last), _ = lax.scan(
        step, (m0, l0, o0, k, v), jnp.arange(s - 1))
    m, l, o = merge(m, l, o, k_last, v_last, s - 1)
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (none in causal LM) -> 0 output
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis: str = "seq", causal: bool = True,
                      scale: Optional[float] = None) -> jax.Array:
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all heads<->seq.

    Requires ``n_heads % axis_size == 0``.  Inside shard_map, local shards are
    (B, T/S, H, Dh); after the first all-to-all each device holds the *full*
    sequence for H/S heads; after attention, the second all-to-all restores
    sequence sharding.
    """
    s = lax.axis_size(axis)
    h = q.shape[2]
    if h % s != 0:
        raise ValueError(f"n_heads={h} not divisible by seq axis size {s}")
    # (B, T/S, H, D) -> gather seq, split heads -> (B, T, H/S, D)
    def to_heads(x):
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    out = attention_reference(to_heads(q), to_heads(k), to_heads(v),
                              causal=causal, scale=scale)
    return to_seq(out)


def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis: str = "seq", causal: bool = True,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Ring attention whose LOCAL block compute is the Pallas flash kernel
    (ops.pallas_kernels) — blockwise ring attention with the hot loop on
    the MXU instead of plain einsums.

    Each ring step classifies the resident K/V block against this device's
    Q shard (causal case): strictly-past blocks run the kernel unmasked,
    the diagonal block runs it causally, strictly-future blocks are
    skipped outright (zero output, -inf lse) — so unlike
    :func:`ring_attention`, future blocks cost no FLOPs at all.  Partial
    (out, lse) pairs merge exactly by logsumexp weighting; the merge is
    plain JAX, so autodiff drives the kernel's custom backward
    (flash_attention_with_lse) per block.

    The skip saves FLOPs, not ICI bandwidth: ``ppermute`` is collective
    and uniform, so in the causal case a block still rides the ring
    through ranks that will skip it (about half of all hops carry a
    block its host never uses; rank s-1 needs every block, so the ring
    cannot simply stop early).  The one universally dead hop — the final
    iteration's rotate-back — is elided by hoisting the last block's
    compute out of the scan.  Rerouting the causal dead hops would need a
    per-step partial permutation schedule (s compiled variants); at the
    ring sizes this framework targets the dead-hop cost is one K/V block
    per step on neighbor ICI links that the skipped compute leaves idle
    anyway, so the added compile complexity is not paid here.

    ``scale`` must be None/default: the kernel pins 1/sqrt(Dh).
    """
    b, t_local, h, d = q.shape
    if scale is not None and abs(scale - d ** -0.5) > 1e-12:
        raise ValueError("ring_flash_attention supports the default "
                         "1/sqrt(head_dim) scale only")
    from ..ops.pallas_kernels import flash_attention_with_lse

    s = lax.axis_size(axis)
    my_idx = lax.axis_index(axis)

    def full_block(k_blk, v_blk):
        return flash_attention_with_lse(q, k_blk, v_blk, False, block_q,
                                        block_k, interpret)

    def diag_block(k_blk, v_blk):
        return flash_attention_with_lse(q, k_blk, v_blk, True, block_q,
                                        block_k, interpret)

    def skip_block(k_blk, v_blk):
        return (jnp.zeros_like(q),
                jnp.full((b * h, t_local), NEG_INF, jnp.float32))

    def merge(o, lse, k_blk, v_blk, step_idx):
        blk_idx = (my_idx + step_idx) % s
        if causal:
            case = jnp.where(blk_idx == my_idx, 1,
                             jnp.where(blk_idx < my_idx, 0, 2))
            out_b, lse_b = lax.switch(case,
                                      (full_block, diag_block, skip_block),
                                      k_blk, v_blk)
        else:
            out_b, lse_b = full_block(k_blk, v_blk)
        new_lse = jnp.logaddexp(lse, lse_b)                 # (B*H, T)
        w_old = jnp.exp(lse - new_lse)
        w_new = jnp.exp(lse_b - new_lse)

        def rowscale(x, w):  # (B,T,H,D) * (B*H,T) -> row-weighted
            return x * w.reshape(b, h, t_local).transpose(0, 2, 1)[..., None]

        new_o = rowscale(o, w_old) + rowscale(out_b.astype(jnp.float32),
                                              w_new)
        return new_o, new_lse

    def step(carry, step_idx):
        o, lse, k_blk, v_blk = carry
        new_o, new_lse = merge(o, lse, k_blk, v_blk, step_idx)
        perm = [(i, (i - 1) % s) for i in range(s)]
        k_next = lax.ppermute(k_blk, axis, perm)
        v_next = lax.ppermute(v_blk, axis, perm)
        return (new_o, new_lse, k_next, v_next), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((b * h, t_local), NEG_INF, jnp.float32)
    # first s-1 blocks scan (compute + rotate); the final block merges
    # outside the scan, eliding its dead rotate-back hop (docstring)
    (o, lse, k_last, v_last), _ = lax.scan(
        step, (o0, lse0, k, v), jnp.arange(s - 1))
    o, _ = merge(o, lse, k_last, v_last, s - 1)
    return o.astype(q.dtype)


def striped_ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                 axis: str = "seq", causal: bool = True,
                                 scale: Optional[float] = None,
                                 block_q: Optional[int] = None,
                                 block_k: Optional[int] = None,
                                 interpret: Optional[bool] = None
                                 ) -> jax.Array:
    """Ring attention over ROUND-ROBIN token stripes with the Pallas flash
    kernel per block — the balanced-causal fix for lockstep SPMD.

    With contiguous chunks (:func:`ring_flash_attention`) the causal skip
    saves FLOPs but not wall-clock: at every ring step SOME device runs a
    full unmasked block, and every other device waits for it at the next
    collective.  Striped, the block pair (this_rank=r, src_rank=b) masks
    to EXACTLY a triangle — ``k_pos <= q_pos`` ⇔ ``b + s*j <= r + s*i`` ⇔
    ``j <= i`` when ``b <= r`` and ``j < i`` when ``b > r`` — so the
    kernel runs its inclusive ("causal") or exclusive ("causal_exclusive")
    diagonal mode, every device does half work on every tick, and causal
    ring attention approaches 2x the contiguous layout's throughput at
    scale (Striped Attention, Brandon et al. 2023).  Inputs must be laid
    out by :func:`striped_permutation`; merge math is the lse-weighted
    combination shared with :func:`ring_flash_attention`.

    ``scale`` must be None/default: the kernel pins 1/sqrt(Dh).
    """
    b, t_local, h, d = q.shape
    if scale is not None and abs(scale - d ** -0.5) > 1e-12:
        raise ValueError("striped_ring_flash_attention supports the "
                         "default 1/sqrt(head_dim) scale only")
    from ..ops.pallas_kernels import flash_attention_with_lse

    s = lax.axis_size(axis)
    my_idx = lax.axis_index(axis)

    def inclusive(k_blk, v_blk):
        return flash_attention_with_lse(q, k_blk, v_blk, True, block_q,
                                        block_k, interpret,
                                        mask_mode="causal")

    def exclusive(k_blk, v_blk):
        return flash_attention_with_lse(q, k_blk, v_blk, True, block_q,
                                        block_k, interpret,
                                        mask_mode="causal_exclusive")

    def full_block(k_blk, v_blk):
        return flash_attention_with_lse(q, k_blk, v_blk, False, block_q,
                                        block_k, interpret)

    def merge(o, lse, k_blk, v_blk, step_idx):
        blk_idx = (my_idx + step_idx) % s
        if causal:
            out_b, lse_b = lax.cond(blk_idx <= my_idx, inclusive, exclusive,
                                    k_blk, v_blk)
        else:
            out_b, lse_b = full_block(k_blk, v_blk)
        new_lse = jnp.logaddexp(lse, lse_b)                 # (B*H, T)
        w_old = jnp.exp(lse - new_lse)
        w_new = jnp.exp(lse_b - new_lse)

        def rowscale(x, w):  # (B,T,H,D) * (B*H,T) -> row-weighted
            return x * w.reshape(b, h, t_local).transpose(0, 2, 1)[..., None]

        new_o = rowscale(o, w_old) + rowscale(out_b.astype(jnp.float32),
                                              w_new)
        return new_o, new_lse

    def step(carry, step_idx):
        o, lse, k_blk, v_blk = carry
        new_o, new_lse = merge(o, lse, k_blk, v_blk, step_idx)
        perm = [(i, (i - 1) % s) for i in range(s)]
        k_next = lax.ppermute(k_blk, axis, perm)
        v_next = lax.ppermute(v_blk, axis, perm)
        return (new_o, new_lse, k_next, v_next), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full((b * h, t_local), NEG_INF, jnp.float32)
    (o, lse, k_last, v_last), _ = lax.scan(
        step, (o0, lse0, k, v), jnp.arange(s - 1))
    o, lse = merge(o, lse, k_last, v_last, s - 1)
    # No normalizer guard needed: the diagonal block (step 0) is inclusive,
    # so every query row attends >= 1 key and lse is finite; exclusive
    # blocks with empty rows are handled by the kernel's empty-row
    # convention (their partial lse is NEG_INF and merges as a no-op).
    return o.astype(q.dtype)


ATTENTION_IMPLS = {
    "dense": attention_reference,
    "dense_blockwise": attention_dense_blockwise,
    "ring": ring_attention,
    "ring_flash": ring_flash_attention,
    "striped": functools.partial(ring_attention, striped=True),
    "striped_flash": striped_ring_flash_attention,
    "ulysses": ulysses_attention,
}


SEQ_SHARDED_IMPLS = ("ring", "ring_flash", "striped", "striped_flash",
                     "ulysses")


# Shape-based dispatch for ``attention="auto"``: from which sequence length
# the Pallas flash kernels (ops.pallas_kernels) beat the XLA dense path
# (materialised (B, H, T, T) f32 scores, kept for the backward pass), as
# ``(backend, head_dim, operand dtype) -> smallest T``.  A row is there only
# if it was timed on the chip: PR 27, one TPU v5e, forward + backward at
# B 4, T 1024, H 16 x 64 and H 24 x 128, bf16, the kernels at their derived
# tilings against ``attention_reference`` (tools/flash_block_sweep.py; the
# numbers are in PERF.md section 6, "PR 27").
AUTO_FLASH_MIN_SEQ = {
    ("tpu", 64, "bfloat16"): 1024,
    ("tpu", 128, "bfloat16"): 1024,
}
# Every shape without a row keeps the rule it had before the table: dense
# below 2048, the kernels from there (f32 operands included, untimed).
# Backends absent here (cpu: the kernels run in interpret mode, orders of
# magnitude slow) never auto-select flash.
AUTO_FLASH_MIN_SEQ_UNTIMED = {"tpu": 2048}


def resolve_attention_impl(impl: str, seq_len: int,
                           backend: Optional[str] = None,
                           head_dim: Optional[int] = None,
                           dtype=None) -> str:
    """Resolve ``"auto"`` to a concrete impl for this (backend, T,
    head_dim, dtype) — THE single consult point
    (sequence_sharded_attention resolves through here, so every
    model/parallel path inherits the same table).  ``auto`` answers
    ``flash`` from the measured row's T up (the untimed rule where the
    shape has no row) and only where the kernels' derived tiling divides
    T; any other ``impl`` passes through unchanged."""
    if impl != "auto":
        return impl
    if backend is None:
        backend = jax.default_backend()
    name = None if dtype is None else jnp.dtype(dtype).name
    thresh = AUTO_FLASH_MIN_SEQ.get(
        (backend, head_dim, name), AUTO_FLASH_MIN_SEQ_UNTIMED.get(backend))
    if thresh is None or seq_len < thresh:
        return "dense"
    from ..ops.pallas_kernels import flash_blocks

    return "flash" if flash_blocks(seq_len, head_dim, dtype) else "dense"


def validate_ulysses_under_tp(n_heads: int, tp: int, sp: int,
                              seq_axis: str = "seq") -> None:
    """Ulysses redistributes this rank's LOCAL heads over the seq axis —
    under Megatron TP that is ``n_heads // tp`` heads over ``sp`` shards,
    which must divide evenly.  THE single consult point for the rule
    (spmd.make_sp_tp_train_step and expert._validate_moe_tp both route
    here so the two composed layouts cannot drift)."""
    if (n_heads // tp) % sp:
        raise ValueError(
            f"ulysses under TP redistributes the {n_heads // tp} "
            f"local heads over {seq_axis}={sp}: not divisible")


def global_positions(impl: str, axis: str, t: int) -> jax.Array:
    """Global token positions of this shard's ``t`` local indices under the
    impl's data layout — THE single source of truth consumed by every
    forward (models.transformer.apply, parallel.spmd._sp_tp_forward):
    striped layouts hold round-robin stripes (local i on rank r is global
    r + i*s, :func:`striped_permutation`), contiguous ring/ulysses layouts
    hold chunks (global r*t + i), dense/flash see the full sequence."""
    if impl in ("striped", "striped_flash"):
        return lax.axis_index(axis) + jnp.arange(t) * lax.axis_size(axis)
    if impl in ("ring", "ring_flash", "ulysses"):
        return lax.axis_index(axis) * t + jnp.arange(t)
    return jnp.arange(t)


def _note_resolved(impl: str, q, block_q, block_k) -> None:
    """Say on the compile ledger's event of the program being lowered what
    implements its attention, and at which tiling where that is a kernel's
    (one field a program; ``compile_ledger.note`` does nothing outside a
    ledger's compile)."""
    from ..ops.pallas_kernels import flash_blocks
    from ..utils import compile_ledger

    rec = {"impl": impl}
    if impl in ("flash", "ring_flash", "striped_flash"):
        blocks = flash_blocks(q.shape[1], q.shape[-1], q.dtype,
                              block_q, block_k)
        if blocks:      # else the kernel's own call raises, in full
            rec["block_q"], rec["block_k"] = blocks
    compile_ledger.note("attention", rec)


def sequence_sharded_attention(impl: str, q, k, v, *, axis: str = "seq",
                               causal: bool = True,
                               scale: Optional[float] = None,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None,
                               rope_theta: Optional[float] = None,
                               window: Optional[int] = None
                               ) -> jax.Array:
    """``block_q``/``block_k``: the flash kernels' tiling; None derives it
    from (T, head_dim, dtype) (ops.pallas_kernels.flash_blocks).
    ``window``: a causal sliding window, which only the dense
    implementation's mask has (``auto`` takes it; any other is refused)."""
    if window is not None:
        if impl not in ("auto", "dense") or not causal:
            raise ValueError(f"a sliding window runs the causal dense "
                             f"attention; attention={impl!r} has none yet")
        impl = "dense"
    impl = resolve_attention_impl(impl, q.shape[1], head_dim=q.shape[-1],
                                  dtype=q.dtype)
    _note_resolved(impl, q, block_q, block_k)
    if rope_theta is not None:
        # RoPE rotates q/k by their GLOBAL positions before any impl or
        # collective — global_positions already answers "what are this
        # shard's global token positions" for every layout (contiguous
        # ring shards, the striped permutation, unsharded dense/flash),
        # so the rotated K that travels the ring is correct by the same
        # argument the positional embedding relies on.
        from ..ops.rope import rope_rotate

        positions = global_positions(impl, axis, q.shape[1])
        q = rope_rotate(q, positions, rope_theta)
        k = rope_rotate(k, positions, rope_theta)
    # the inner scope names what implements the work, under the
    # caller's ``attention`` scope (the device trace is read by both)
    with jax.named_scope(f"attn_{impl}"):
        if impl == "dense":
            return attention_reference(q, k, v, causal=causal, scale=scale,
                                       window=window)
        if impl == "dense_blockwise":
            return attention_dense_blockwise(q, k, v, causal=causal,
                                             scale=scale)
        if impl == "flash":
            from ..ops.pallas_kernels import flash_attention

            return flash_attention(q, k, v, causal, block_q=block_q,
                                   block_k=block_k)
        if impl == "ring":
            return ring_attention(q, k, v, axis=axis, causal=causal,
                                  scale=scale)
        if impl == "ring_flash":
            return ring_flash_attention(q, k, v, axis=axis, causal=causal,
                                        scale=scale, block_q=block_q,
                                        block_k=block_k)
        if impl == "striped":
            return ring_attention(q, k, v, axis=axis, causal=causal,
                                  scale=scale, striped=True)
        if impl == "striped_flash":
            return striped_ring_flash_attention(q, k, v, axis=axis,
                                                causal=causal, scale=scale,
                                                block_q=block_q,
                                                block_k=block_k)
        if impl == "ulysses":
            return ulysses_attention(q, k, v, axis=axis, causal=causal,
                                     scale=scale)
    raise ValueError(f"unknown attention impl {impl!r}")
