"""Explicit (shard_map-style) Megatron tensor parallelism for transformer
blocks — the TP building block that composes with the pipeline's shard_map
(parallel.pipeline) where GSPMD annotations (parallel.gspmd) cannot reach.

The reference has no tensor parallelism (SURVEY.md §2.2: its model is a
fully-replicated 13-param MLP, dataParallelTraining_NN_MPI.py:41-45); this
module exists so pipeline x tensor meshes (DP x TP x PP) run as ONE SPMD
program with every collective explicit:

* **f / g operators** (Megatron's conjugate pair) as ``jax.custom_vjp`` so
  the backward communication is unambiguous: ``f`` is identity forward /
  psum backward (placed at a column-parallel layer's input — the partial
  input-gradients from each tensor rank must be summed), ``g`` is psum
  forward / identity backward (placed at a row-parallel layer's output).
* **qkv column permutation**: the fused qkv weight is ``(d, qkv_dim)``
  laid out ``[q | k | v]`` (``qkv_dim = 3d`` classic multi-head, or
  ``d + 2·kv_heads·head_dim`` under GQA); a contiguous tensor-axis slice
  of that would hand a rank fragments of q and k from unrelated heads.
  ``qkv_tp_permutation`` reorders columns to ``[q_r | k_r | v_r]`` per
  rank r (whole heads; under GQA rank r's ``n_heads/tp`` query heads and
  its ``kv_heads/tp`` K/V heads, contiguously, so every query-head group
  lands on its own rank's K/V heads), keeping the *sharded* layout
  head-aligned while checkpoints stay interchangeable with the dense
  model via the inverse permutation.
* **tp_block_apply**: one pre-LN block with column-parallel qkv/ff_in,
  local attention over ``n_heads / tp`` heads (GQA: ``kv_heads / tp``
  K/V heads repeated rank-locally to the query heads), and row-parallel
  attn_out/ff_out — numerically the dense ``Transformer._block``
  (models/transformer.py) up to split-matmul reassociation.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models.core import ACTIVATIONS, LayerNorm
from ..parallel.sequence import attention_reference

Pytree = Any
TENSOR_AXIS = "tensor"


def make_megatron_ops(axis: str = TENSOR_AXIS):
    """The (f, g) conjugate operator pair.  Explicit ``custom_vjp`` rather
    than relying on the transpose rule of ``lax.psum`` inside shard_map —
    the backward collective is the correctness-critical part."""

    @jax.custom_vjp
    def f(x):
        return x

    def f_fwd(x):
        return x, None

    def f_bwd(_, ct):
        return (lax.psum(ct, axis),)

    f.defvjp(f_fwd, f_bwd)

    @jax.custom_vjp
    def g(x):
        return lax.psum(x, axis)

    def g_fwd(x):
        return lax.psum(x, axis), None

    def g_bwd(_, ct):
        return (ct,)

    g.defvjp(g_fwd, g_bwd)
    return f, g


def qkv_tp_permutation(d_model: int, n_heads: int, tp: int,
                       kv_heads: int = 0) -> np.ndarray:
    """Column order mapping the fused ``[q | k | v]`` qkv weight to a layout
    whose tensor-axis slice r is ``[q_heads_r | k_heads_r | v_heads_r]``.

    Under GQA (``kv_heads < n_heads``) the k/v projections are
    ``kv_heads * head_dim`` wide: rank r takes ``n_heads/tp`` query heads
    and ``kv_heads/tp`` K/V heads, CONTIGUOUSLY — since the per-rank
    query-head count is a multiple of the group size G = n_heads/kv_heads,
    rank r's query heads group onto exactly rank r's K/V heads, so local
    attention needs no cross-rank head traffic.  ``kv_heads=0`` (or
    ``n_heads``) reduces to the classic equal-thirds layout."""
    kv = kv_heads or n_heads
    if n_heads % tp:
        raise ValueError(f"n_heads={n_heads} not divisible by tp={tp}")
    if kv % tp:
        raise ValueError(f"n_kv_heads={kv} not divisible by tp={tp}")
    head_dim = d_model // n_heads
    per_q = (n_heads // tp) * head_dim
    per_kv = (kv // tp) * head_dim
    kvw = kv * head_dim
    cols = []
    for r in range(tp):
        for base, per in ((0, per_q), (d_model, per_kv),
                          (d_model + kvw, per_kv)):   # q, k, v
            b0 = base + r * per
            cols.extend(range(b0, b0 + per))
    return np.asarray(cols, dtype=np.int64)


def permute_qkv(blocks: Pytree, d_model: int, n_heads: int, tp: int,
                inverse: bool = False, kv_heads: int = 0) -> Pytree:
    """Apply (or invert) the qkv column permutation on a blocks pytree —
    works on both per-layer lists and pipeline-stacked leaves, since the
    permuted dim is always the last."""
    perm = qkv_tp_permutation(d_model, n_heads, tp, kv_heads)
    if inverse:
        perm = np.argsort(perm)

    def fix(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        if "qkv" in names:
            return jnp.take(leaf, perm, axis=-1)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, blocks)


def validate_tp(cfg, tp: int) -> None:
    if hasattr(cfg, "require_plain_block"):
        cfg.require_plain_block("Megatron tensor parallelism")
    kv = getattr(cfg, "kv_heads", cfg.n_heads)
    if kv % tp:
        # same divisibility contract (and exception type) as the
        # d_model/n_heads/d_ff checks below and qkv_tp_permutation
        raise ValueError(
            f"GQA under Megatron TP shards the K/V heads over the tensor "
            f"axis, which needs n_kv_heads % tp == 0; got n_kv_heads={kv} "
            f"with tp={tp}.  Use a kv-head count divisible by tp, the "
            f"GSPMD TP path, or n_kv_heads=n_heads")
    for name, dim in (("d_model", cfg.d_model), ("n_heads", cfg.n_heads),
                      ("d_ff", cfg.d_ff)):
        if dim % tp:
            raise ValueError(f"{name}={dim} not divisible by tensor axis "
                             f"size {tp}")


def tp_block_apply(cfg, layer_params: Pytree, x: jax.Array, tp: int,
                   axis: str = TENSOR_AXIS, attention_fn=None,
                   ffn_fn=None):
    """One transformer block with the tensor dimension sharded over ``axis``
    (call inside shard_map; ``layer_params`` are the LOCAL shards — qkv and
    ff_in hold output-columns for this rank's heads/hidden units, attn_out
    and ff_out hold the matching input-rows).

    Mirrors ``Transformer._block`` (dense attention) exactly: pre-LN,
    residual adds in the input dtype, activations in ``cfg.compute_dtype``.

    ``attention_fn(q, k, v) -> out`` (all (B, T_local, H_local, Dh))
    overrides the attention impl — this is the TP x SP composition point:
    pass ``parallel.sequence.ring_attention`` bound to the 'seq' axis and
    the block runs Megatron-sharded matmuls with ring attention over the
    sequence shards (heads split over 'tensor', sequence over 'seq').
    Default: dense attention over the full local sequence.

    ``ffn_fn(layer_params, h) -> (ff, aux)`` replaces the dense
    column/row-parallel FFN — the TP x EP composition point: pass a
    tensor+expert-sharded ``models.moe.MoEFFN.apply`` closure and the block
    becomes a GShard expert layer with Megatron attention.  When set, the
    block returns ``(x, aux)`` instead of ``x`` (the FFN owns its own f/g
    placement; ``h`` is handed over tensor-replicated)."""
    f, g = make_megatron_ops(axis)
    cdt = cfg.compute_dtype
    heads_local = cfg.n_heads // tp
    ln = LayerNorm(cfg.d_model, param_dtype=cfg.param_dtype)
    if attention_fn is None:
        if getattr(cfg, "pos_encoding", "learned") == "rope":
            # dense attention runs the full (unsharded) local sequence,
            # so positions are arange(t); rotation is per-head-
            # independent, hence correct on this rank's local heads.
            # Seq-sharded impls arrive as attention_fn closures that
            # rotate INSIDE sequence_sharded_attention (global
            # positions) — rotating here too would double-rotate.
            from ..ops.rope import rope_rotate

            def attention_fn(q, k, v):
                pos = jnp.arange(q.shape[1])
                return attention_reference(
                    rope_rotate(q, pos, cfg.rope_theta),
                    rope_rotate(k, pos, cfg.rope_theta), v, causal=True)
        else:
            attention_fn = lambda q, k, v: attention_reference(
                q, k, v, causal=True)

    # --- attention: column-parallel qkv, local heads, row-parallel out ---
    h = ln.apply(layer_params["ln1"], x)
    h = f(h)  # identity fwd; backward psums the partial input-grads
    qkv = (h.astype(cdt) @ layer_params["qkv"]["w"].astype(cdt)
           + layer_params["qkv"]["b"].astype(cdt))
    b, t, _ = qkv.shape
    # local layout is [q_r | k_r | v_r] (qkv_tp_permutation); under GQA
    # the k/v spans are kv_local = kv_heads/tp heads wide and rank r's
    # query heads group onto exactly rank r's K/V heads (contiguous
    # assignment), so the repeat to local query heads stays rank-local
    kv_heads = getattr(cfg, "kv_heads", cfg.n_heads)
    kv_local = kv_heads // tp
    q_w = heads_local * cfg.head_dim
    kv_w = kv_local * cfg.head_dim
    q = qkv[..., :q_w].reshape(b, t, heads_local, cfg.head_dim)
    k = qkv[..., q_w:q_w + kv_w].reshape(b, t, kv_local, cfg.head_dim)
    v = qkv[..., q_w + kv_w:].reshape(b, t, kv_local, cfg.head_dim)
    if kv_local != heads_local:
        groups = heads_local // kv_local
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    out = attention_fn(q, k, v)
    out = out.reshape(b, t, heads_local * cfg.head_dim)
    partial = out @ layer_params["attn_out"]["w"].astype(cdt)
    attn = g(partial) + layer_params["attn_out"]["b"].astype(cdt)
    x = x + attn.astype(x.dtype)

    # --- FFN: column-parallel in, row-parallel out ---
    h = ln.apply(layer_params["ln2"], x)
    if ffn_fn is not None:
        ff, aux = ffn_fn(layer_params, h)
        return x + ff.astype(x.dtype), aux
    h = f(h)
    hh = tp_ffn_hidden(cfg, layer_params, h)
    ff = (g(hh @ layer_params["ff_out"]["w"].astype(cdt))
          + layer_params["ff_out"]["b"].astype(cdt))
    return x + ff.astype(x.dtype)


def tp_ffn_hidden(cfg, layer_params, h: jax.Array) -> jax.Array:
    """Column-parallel FFN hidden (the shard before the row-parallel
    ff_out): ``act(h W_in + b)``, or for SwiGLU ``silu(h W_gate + b_g) *
    (h W_in + b)``.  The gate is column-parallel with the SAME column
    partition as ff_in, so the elementwise gated product of the two
    local shards IS the local shard of the global product — no extra
    collective before ff_out.  One definition shared by the training
    block (``tp_block_apply``) and the KV-cache decode chunk
    (``models.generate_tp``), the same anti-drift rule as
    ``Transformer._ffn``."""
    cdt = cfg.compute_dtype
    hh = (h.astype(cdt) @ layer_params["ff_in"]["w"].astype(cdt)
          + layer_params["ff_in"]["b"].astype(cdt))
    if cfg.activation == "swiglu":
        gate = jax.nn.silu(
            h.astype(cdt) @ layer_params["ff_gate"]["w"].astype(cdt)
            + layer_params["ff_gate"]["b"].astype(cdt))
        return gate * hh
    return ACTIVATIONS[cfg.activation](hh)


# ---------------------------------------------------------------------------
# Vocab parallelism: embedding table + LM head sharded on the vocab dim
# ---------------------------------------------------------------------------

def vocab_parallel_embed(table_local: jax.Array, ids: jax.Array,
                         axis: str = TENSOR_AXIS) -> jax.Array:
    """Embedding lookup with the (V, D) table row-sharded over ``axis``
    (local shard (V/tp, D), contiguous blocks in rank order).  Each rank
    contributes rows it owns (zeros elsewhere); one psum assembles the
    full lookup.  The psum is the g operator (psum forward, identity
    backward) — as everywhere in this module, the backward collective is
    explicit rather than left to lax.psum's transpose under shard_map,
    which over-counts by the axis size with check_vma=False.  The
    identity-backward cotangent then scatters into the owning shard's
    rows — the Megatron vocab-parallel embedding."""
    _, g = make_megatron_ops(axis)
    v_local = table_local.shape[0]
    offset = lax.axis_index(axis) * v_local
    local = ids - offset
    in_shard = (local >= 0) & (local < v_local)
    rows = jnp.take(table_local, jnp.clip(local, 0, v_local - 1), axis=0)
    rows = jnp.where(in_shard[..., None], rows, 0.0)
    return g(rows)


def vocab_parallel_logits(x: jax.Array, head_w_local: jax.Array,
                          axis: str = TENSOR_AXIS,
                          compute_dtype=None) -> jax.Array:
    """(..., D) @ (D, V/tp) -> LOCAL logits shard (..., V/tp), f32.  The f
    operator makes the backward psum of x's partial cotangents explicit —
    the full (..., V) logits are never materialized on one device."""
    f, _ = make_megatron_ops(axis)
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        head_w_local = head_w_local.astype(compute_dtype)
    return (f(x) @ head_w_local).astype(jnp.float32)


def vocab_parallel_cross_entropy(logits_local: jax.Array, targets: jax.Array,
                                 mask: jax.Array = None,
                                 axis: str = TENSOR_AXIS):
    """Softmax cross-entropy over vocab-sharded logits WITHOUT gathering
    them: stable max via pmax (stop-gradient — softmax is shift-invariant),
    denominator and target-logit each one psum over ``axis``.  Same
    (loss_sum, count) contract as ops.losses.softmax_cross_entropy; the
    sum/count are tensor-replicated so downstream global-mean reductions
    need no 'tensor' axis, matching the Megatron invariant."""
    _, g = make_megatron_ops(axis)
    v_local = logits_local.shape[-1]
    offset = lax.axis_index(axis) * v_local
    m = lax.pmax(jax.lax.stop_gradient(logits_local).max(-1), axis)  # (...,)
    e = jnp.exp(logits_local - m[..., None])
    denom = g(e.sum(-1))
    local_t = targets - offset
    in_shard = (local_t >= 0) & (local_t < v_local)
    idx = jnp.clip(local_t, 0, v_local - 1)
    tgt_local = jnp.take_along_axis(logits_local, idx[..., None],
                                    axis=-1)[..., 0]
    tgt = g(jnp.where(in_shard, tgt_local, 0.0))
    nll = m + jnp.log(denom) - tgt                                   # (...,)
    from ..ops.losses import reduce_token_nll

    return reduce_token_nll(nll, mask)


def vocab_parallel_accuracy(logits_local: jax.Array, targets: jax.Array,
                            mask: jax.Array = None,
                            axis: str = TENSOR_AXIS):
    """argmax over the sharded vocab: global max via pmax, then the
    smallest global index attaining it via pmin (deterministic
    tie-breaking, matching jnp.argmax's first-occurrence rule).  Same
    EXAMPLE-level (correct_sum, count) contract as ops.losses.accuracy
    (per-example mean over token dims, count = examples).  A metric, not a
    loss: gradients are stopped at entry (pmax/pmin carry no
    differentiation rule, and argmax has no useful one)."""
    from ..ops.losses import reduce_example_hits

    logits_local = jax.lax.stop_gradient(logits_local)
    v_local = logits_local.shape[-1]
    offset = lax.axis_index(axis) * v_local
    local_max = logits_local.max(-1)
    global_max = lax.pmax(local_max, axis)
    local_arg = jnp.argmax(logits_local, axis=-1) + offset
    big = jnp.iinfo(jnp.int32).max
    cand = jnp.where(local_max >= global_max, local_arg.astype(jnp.int32),
                     big)
    pred = lax.pmin(cand, axis)
    hit = (pred == targets).astype(jnp.float32)
    return reduce_example_hits(hit, mask)


def path_names(path) -> Tuple[str, ...]:
    """Key path -> tuple of string names (dict keys / sequence indices)."""
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def is_tensor_sharded(names: Tuple[str, ...]) -> bool:
    """Whether a block leaf (by its key-path names) is SHARDED over the
    tensor axis.  THE single consult point for the TP layout — the pipeline
    and sp_tp spec builders and their grad-clip norm partitioning all call
    this, so a layout change cannot desynchronize them."""
    return any(sub in names and names[-1] == leaf
               for sub, leaf in tensor_sharded_block_paths())


def tensor_sharded_block_paths() -> Tuple[Tuple[str, str], ...]:
    """(submodule, leaf) pairs of block params that are SHARDED over the
    tensor axis (everything else in a block — ln1/ln2, attn_out.b,
    ff_out.b — is tensor-replicated with identical grads on every rank,
    which the f operator's backward psum guarantees)."""
    return (("qkv", "w"), ("qkv", "b"), ("ff_in", "w"), ("ff_in", "b"),
            ("ff_gate", "w"), ("ff_gate", "b"),   # SwiGLU: col like ff_in
            ("attn_out", "w"), ("ff_out", "w"))
