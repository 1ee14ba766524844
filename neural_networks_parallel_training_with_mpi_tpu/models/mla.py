"""Latent attention (multi-head latent attention, DeepSeek-V2's MLA).

Keys and values of all heads are made from one small latent row a token:
``h W_kva -> [c | k_r]``, ``c_kv = norm(c)`` (``kv_lora_rank`` wide) and one
rotary key ``k_rope = R_p(k_r)`` shared by every head; ``c_kv W_kvb`` expands
to each head's ``[k_nope | v]``.  Queries go through a low-rank pair too:
``q = norm(h W_qa) W_qb`` -> heads x ``[q_nope | q_rope]``.

The piece owns its projections, its **cache row** (``[c_kv | k_rope]``: normed
and rotated before it is written, ``kv_lora_rank + qk_rope_head_dim`` numbers a
token and layer where per-head K and V would hold ``2 * heads * head_dim``),
and two forms of the same mathematics:

* **expanded** (:meth:`attend_expanded`: training forward, chunked prefill):
  the latent rows are expanded through ``W_kvb`` to per-head keys and values
  and attended as plain multi-head attention;
* **absorbed** (:meth:`attend_absorbed`: decode): ``W_kvb``'s key half is
  folded into the query (``q' = q_nope W_kvb^K^T``, ``kv_lora_rank`` wide) and
  its value half applied after the weighted sum of latent rows, so the cache is
  never expanded: scores and values are read straight off the latent rows.

Scores are float32 in both; the softmax scale is ``qk_head_dim^-0.5`` times
YaRN's ``m(mscale_all_dim)^2``; queries are scaled by position
(:func:`ops.rope.query_scale`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import rope
from .core import Linear, Module, Pytree, RMSNorm

NEG = -1e30


@dataclass(frozen=True)
class LatentAttention(Module):
    d_model: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    rope_scaling: Optional[rope.RopeScaling] = None
    norm_eps: float = 1e-6
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32

    # ---- shapes ---------------------------------------------------------
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def cache_row(self) -> Dict[str, Tuple[int, ...]]:
        """What one token holds in one layer's cache: pool name -> the
        row's trailing shape (the paged cache's seam, serve/paged_kv.py)."""
        return {"latent": (self.row_dim,)}

    @property
    def softmax_scale(self) -> float:
        return (self.qk_head_dim ** -0.5
                * rope.softmax_mscale(self.rope_scaling))

    def _mods(self):
        lin = lambda i, o: Linear(i, o, use_bias=False,       # noqa: E731
                                  param_dtype=self.param_dtype,
                                  compute_dtype=self.compute_dtype)
        norm = lambda d: RMSNorm(d, self.norm_eps, self.param_dtype)  # noqa: E731
        h = self.n_heads
        return {
            "q_a": lin(self.d_model, self.q_lora_rank),
            "q_norm": norm(self.q_lora_rank),
            "q_b": lin(self.q_lora_rank, h * self.qk_head_dim),
            "kv_a": lin(self.d_model, self.row_dim),
            "kv_norm": norm(self.kv_lora_rank),
            "kv_b": lin(self.kv_lora_rank,
                        h * (self.qk_nope_head_dim + self.v_head_dim)),
            "out": lin(h * self.v_head_dim, self.d_model),
        }

    def init(self, key: jax.Array) -> Pytree:
        mods = self._mods()
        keys = jax.random.split(key, len(mods))
        return {n: m.init(k) for (n, m), k in zip(mods.items(), keys)}

    # ---- projections ----------------------------------------------------
    def project(self, params: Pytree, h: jax.Array, positions: jax.Array):
        """``h`` (B, W, d) at ``positions`` (B, W) -> the queries
        ``q_nope`` (B, W, H, nope), ``q_rope`` (B, W, H, rope) (rotated, both
        scaled by position) and the new cache rows (B, W, row_dim)."""
        mods = self._mods()
        b, w, _ = h.shape
        cq = mods["q_norm"].apply(params["q_norm"],
                                  mods["q_a"].apply(params["q_a"], h))
        q = mods["q_b"].apply(params["q_b"], cq).reshape(
            b, w, self.n_heads, self.qk_head_dim)
        q_nope, q_rope = (q[..., :self.qk_nope_head_dim],
                          q[..., self.qk_nope_head_dim:])
        kva = mods["kv_a"].apply(params["kv_a"], h)
        c_kv = mods["kv_norm"].apply(params["kv_norm"],
                                     kva[..., :self.kv_lora_rank])
        k_r = kva[..., None, self.kv_lora_rank:]            # one head
        q_rope = rope.rope_rotate_pairs(q_rope, positions, self.rope_theta,
                                        self.rope_scaling)
        k_rope = rope.rope_rotate_pairs(k_r, positions, self.rope_theta,
                                        self.rope_scaling)[:, :, 0]
        qs = rope.query_scale(positions, self.rope_scaling)[..., None, None]
        q_nope = (q_nope.astype(jnp.float32) * qs).astype(q.dtype)
        q_rope = (q_rope.astype(jnp.float32) * qs).astype(q.dtype)
        return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)

    def _kv_b(self, params: Pytree):
        """``W_kvb`` as (rank, H, nope + v) in the compute type."""
        return params["kv_b"]["w"].astype(self.compute_dtype).reshape(
            self.kv_lora_rank, self.n_heads,
            self.qk_nope_head_dim + self.v_head_dim)

    def output(self, params: Pytree, o: jax.Array) -> jax.Array:
        b, w = o.shape[:2]
        return self._mods()["out"].apply(
            params["out"],
            o.reshape(b, w, self.n_heads * self.v_head_dim)
            .astype(self.compute_dtype))

    # ---- the two forms --------------------------------------------------
    def attend_expanded(self, params: Pytree, q_nope, q_rope, rows,
                        mask, n_keys=None, key_block: int = 512
                        ) -> jax.Array:
        """Expanded form.  ``rows`` (B, T, row_dim) are the latent rows to
        attend (the chunk's own among them), ``mask`` (B, W, T) says which
        key each query may see.  Returns (B, W, H, v) before ``W_o``.

        ``n_keys`` (a traced count) says that no query sees a key at or
        past it: the keys are then walked ``key_block`` at a time up to
        that count and no further, each block expanded through ``W_kvb``
        on its own and folded into a running softmax (the row maximum, the
        normaliser and the weighted values carried in float32), so a
        prefill chunk early in a long cache pays for the keys that exist
        and not for the cache's width.  Same scores, same weights; the
        normaliser's sum is taken block by block."""
        cdt = self.compute_dtype
        w_kvb = self._kv_b(params)
        qn, qr = q_nope.astype(cdt), q_rope.astype(cdt)

        def scores_and_values(rows, mask):
            c_kv = rows[..., :self.kv_lora_rank].astype(cdt)
            k_rope = rows[..., self.kv_lora_rank:].astype(cdt)
            kv = jnp.einsum("btr,rhe->bthe", c_kv, w_kvb)
            k_nope, v = (kv[..., :self.qk_nope_head_dim],
                         kv[..., self.qk_nope_head_dim:])
            s = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope,
                              preferred_element_type=jnp.float32))
            return jnp.where(mask[:, None], s * self.softmax_scale, NEG), v

        t = rows.shape[1]
        if n_keys is None or t % key_block or t <= key_block:
            s, v = scores_and_values(rows, mask)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(cdt), v,
                              preferred_element_type=jnp.float32)

        b, w, h = q_nope.shape[:3]

        def fold(i, carry):
            top, norm, acc = carry
            s, v = scores_and_values(
                jax.lax.dynamic_slice_in_dim(rows, i * key_block, key_block,
                                             axis=1),
                jax.lax.dynamic_slice_in_dim(mask, i * key_block, key_block,
                                             axis=2))
            new_top = jnp.maximum(top, s.max(-1))
            keep = jnp.exp(top - new_top)
            p = jnp.exp(s - new_top[..., None])
            acc = acc * keep.transpose(0, 2, 1)[..., None] + jnp.einsum(
                "bhqk,bkhd->bqhd", p.astype(cdt), v,
                preferred_element_type=jnp.float32)
            return new_top, norm * keep + p.sum(-1), acc

        blocks = jnp.minimum((n_keys + key_block - 1) // key_block,
                             t // key_block)
        _top, norm, acc = jax.lax.fori_loop(0, blocks, fold, (
            jnp.full((b, h, w), NEG, jnp.float32),
            jnp.zeros((b, h, w), jnp.float32),
            jnp.zeros((b, w, h, self.v_head_dim), jnp.float32)))
        # a query that sees its own key has a normaliser of at least 1; a
        # pad column past ``n_keys`` has seen none and reads 0, not 0 / 0
        return acc / jnp.maximum(norm, 1e-30).transpose(0, 2, 1)[..., None]

    def absorb_weights(self, params: Pytree):
        """``W_kvb`` split for the absorbed form: ``(W^K, W^V)``, (rank, H,
        nope) and (rank, H, v) in the compute type."""
        w_kvb = self._kv_b(params)
        return (w_kvb[..., :self.qk_nope_head_dim],
                w_kvb[..., self.qk_nope_head_dim:])

    def absorb_query(self, w_k, q_nope) -> jax.Array:
        """``q_nope W^K``: the query in the latent row's own lanes, (B, W, H,
        rank) float32 (scope ``mla_absorb``)."""
        with jax.named_scope("mla_absorb"):
            return jnp.einsum("bqhd,rhd->bqhr",
                              q_nope.astype(self.compute_dtype), w_k,
                              preferred_element_type=jnp.float32)

    def absorb_value(self, w_v, u) -> jax.Array:
        """``u W^V``: the weighted sum of latent rows (B, W, H, rank) to each
        head's value (B, W, H, v), float32 (scope ``mla_absorb``)."""
        with jax.named_scope("mla_absorb"):
            return jnp.einsum("bqhr,rhd->bqhd", u.astype(self.compute_dtype),
                              w_v, preferred_element_type=jnp.float32)

    def attend_absorbed(self, params: Pytree, q_nope, q_rope, rows,
                        mask) -> jax.Array:
        """Absorbed form: same arguments, same result; the latent rows are
        never expanded.  Scope ``mla_absorb`` holds the two products that
        take ``W_kvb``'s place around the cache (:meth:`absorb_query`,
        :meth:`absorb_value`); between them every head scores against the
        same row and weighs its first ``kv_lora_rank`` lanes, which is what
        the paged kernel's shared-row mode does over the pool in place
        (serve/paged_kv.py)."""
        cdt = self.compute_dtype
        c_kv = rows[..., :self.kv_lora_rank].astype(cdt)
        k_rope = rows[..., self.kv_lora_rank:].astype(cdt)
        w_k, w_v = self.absorb_weights(params)
        q_lat = self.absorb_query(w_k, q_nope)
        s = (jnp.einsum("bqhr,bkr->bhqk", q_lat.astype(cdt), c_kv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(cdt), k_rope,
                          preferred_element_type=jnp.float32))
        s = jnp.where(mask[:, None], s * self.softmax_scale, NEG)
        p = jax.nn.softmax(s, axis=-1)
        u = jnp.einsum("bhqk,bkr->bqhr", p.astype(cdt), c_kv,
                       preferred_element_type=jnp.float32)
        return self.absorb_value(w_v, u)

    def apply(self, params: Pytree, h: jax.Array, **kwargs) -> jax.Array:
        """The full causal forward over ``h`` (B, T, d), positions 0..T-1,
        expanded form: ``Attn(h) W_o`` (the norm before and the residual
        after are the block's).  The named scopes are the block's own
        (``Transformer._block``): the device trace is read by them."""
        b, t, _ = h.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        with jax.named_scope("attn_proj"):
            q_nope, q_rope, rows = self.project(params, h, positions)
        with jax.named_scope("attention"), jax.named_scope("attn_dense"):
            mask = jnp.arange(t)[None, None, :] <= positions[:, :, None]
            out = self.attend_expanded(params, q_nope, q_rope, rows, mask)
        with jax.named_scope("attn_proj"):
            return self.output(params, out)

    def fwd_flops_per_token(self, context: float) -> float:
        """Products of one token attending ``context`` keys, expanded."""
        h = self.n_heads
        proj = (self.d_model * self.q_lora_rank
                + self.q_lora_rank * h * self.qk_head_dim
                + self.d_model * self.row_dim
                + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + h * self.v_head_dim * self.d_model)
        return 2.0 * proj + 2.0 * h * (self.qk_head_dim
                                       + self.v_head_dim) * context
