"""Rotary position embeddings (RoPE — Su et al. 2021, RoFormer).

Instead of ADDING a learned position vector to the token embedding (the
reference-era convention this framework's default keeps), RoPE rotates
each query/key head pair-wise by an angle proportional to its absolute
position; the q·k contraction then depends only on the RELATIVE distance
m − n, which is what attention actually wants.  TPU-friendly by
construction: the rotation is a fused elementwise multiply-add on the
(…, head_dim) tile — no gather, no position table streamed from HBM, no
extra parameters (and so nothing for the optimizer/checkpoint to carry).

Applied OUTSIDE the attention kernels, on q/k right after the head
split: every impl (dense, Pallas flash, ring, striped, Ulysses) then
works unchanged, because a token's rotation depends only on its own
global position — under sequence parallelism each shard rotates its
local tokens by their global positions before any collective, and the
already-rotated K travels the ring.  Decode rotates the single new
position and caches the rotated key (standard practice), so cached keys
never need re-rotation.

Half-split convention: the head dim is split as [x1 | x2] and rotated as
(x1·cos − x2·sin, x1·sin + x2·cos) — self-consistent within this
framework (checkpoints trained here decode here; no external-weight
layout to match).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

DEFAULT_THETA = 10000.0


def rope_angles(positions: jax.Array, head_dim: int,
                theta: float = DEFAULT_THETA):
    """(cos, sin) tables for ``positions`` (any shape P) and an even
    ``head_dim`` -> each (*P, head_dim // 2) in f32."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def rope_rotate(x: jax.Array, positions: jax.Array,
                theta: float = DEFAULT_THETA) -> jax.Array:
    """Rotate q or k (..., T, H, D) by per-token ``positions``.

    ``positions`` is (T,) (one sequence of global positions — the
    training path, where sequence-parallel shards pass their own global
    slice) or (B, T) (per-row positions — the decode paths, where every
    batch row sits at its own depth).  Output dtype matches ``x``."""
    cos, sin = rope_angles(positions, x.shape[-1], theta)
    # broadcast over batch (T,) case and insert the heads axis
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]            # (1, T, half)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]  # (B|1, T, 1, half)
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos],
        axis=-1).astype(x.dtype)


# ---- long-context rotary: adjacent pairs, YaRN, a query scale by position ---

@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN (Peng et al. 2023) as the DeepSeek-family configs spell it,
    plus the position-dependent query scale (``llama_4_scaling_beta``).
    Hashable: it rides in the model's frozen config."""

    factor: float = 1.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    query_scale_beta: float = 0.0     # 0 = no scale by position


def yarn_mscale(scale: float, mscale: float) -> float:
    """``m(s) = 0.1 s ln(factor) + 1`` (1 at or below factor 1)."""
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_frequencies(dim: int, theta: float, sc: Optional[RopeScaling]):
    """(dim // 2,) float32 frequencies.  Plain ``theta^(-2i/dim)`` without
    scaling; under YaRN a dimension that turns at least ``beta_fast`` times
    within the original context keeps its frequency, one that turns at most
    ``beta_slow`` times has it divided by ``factor``, and the dimensions
    between are blended by a linear ramp (``find_correction_range`` /
    ``linear_ramp_mask``)."""
    half = dim // 2
    base = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if sc is None or sc.factor <= 1.0:
        return base
    orig = sc.original_max_position_embeddings

    def turns_to_dim(turns: float) -> float:
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_to_dim(sc.beta_fast)), 0)
    high = min(math.ceil(turns_to_dim(sc.beta_slow)), dim - 1)
    if low == high:
        high += 0.001                 # the reference's guard against 0 / 0
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                 # 1 where the dimension turns often
    return base / sc.factor * (1.0 - keep) + base * keep


def yarn_cos_sin_scale(sc: Optional[RopeScaling]) -> float:
    """What cos and sin are multiplied by: ``m(mscale) / m(mscale_all_dim)``."""
    if sc is None or sc.factor <= 1.0:
        return 1.0
    return (yarn_mscale(sc.factor, sc.mscale)
            / yarn_mscale(sc.factor, sc.mscale_all_dim))


def softmax_mscale(sc: Optional[RopeScaling]) -> float:
    """The factor ``m(mscale_all_dim)^2`` the attention's softmax scale
    carries under YaRN in the DeepSeek family (1 without)."""
    if sc is None or sc.factor <= 1.0 or not sc.mscale_all_dim:
        return 1.0
    return yarn_mscale(sc.factor, sc.mscale_all_dim) ** 2


def query_scale(positions: jax.Array, sc: Optional[RopeScaling]):
    """``1 + beta * ln(1 + floor(p / original_max))`` per position, float32:
    1 below the original context, growing with every further multiple."""
    if sc is None or not sc.query_scale_beta:
        return jnp.ones(positions.shape, jnp.float32)
    mult = jnp.floor(positions.astype(jnp.float32)
                     / sc.original_max_position_embeddings)
    return 1.0 + sc.query_scale_beta * jnp.log1p(mult)


def rope_rotate_pairs(x: jax.Array, positions: jax.Array, theta: float,
                      scaling: Optional[RopeScaling] = None) -> jax.Array:
    """Rotate ``x`` (..., T, H, D) by ``positions`` ((T,) or (B, T)) with
    ADJACENT pairs ``(x_2i, x_2i+1)`` (``rope_interleave``), where
    :func:`rope_rotate` pairs ``(x_i, x_i+D/2)``; frequencies from
    :func:`yarn_frequencies`.  The output keeps the interleaved layout:
    the scores are a dot product, which no permutation shared by queries
    and keys changes."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {d}")
    freqs = yarn_frequencies(d, theta, scaling)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    m = yarn_cos_sin_scale(scaling)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    if positions.ndim == 1:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]   # (B|1, T, 1, D/2)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
