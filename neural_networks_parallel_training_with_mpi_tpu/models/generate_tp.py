"""Tensor-parallel autoregressive decoding — serving SP x TP / PP x TP
checkpoints in their NATIVE layout (VERDICT r2 item 4).

The reference has no inference path at all (its dead test-eval block,
dataParallelTraining_NN_MPI.py:227-236, is the closest thing); the dense
decode path is ``models.generate``.  This module removes the last host
gather from serving: a model trained on the seq x tensor layout
(``parallel.spmd``) or the pipe x tensor layout (``parallel.pipeline``)
decodes *without* ever assembling dense replicated params —

* **Megatron blocks, incremental.**  Each tensor rank holds its head-aligned
  qkv / ff_in column shards and attn_out / ff_out row shards (the training
  layout, ``parallel.megatron``); the per-chunk forward runs attention over
  ``n_heads / tp`` LOCAL query heads against a KV cache holding
  ``kv_heads / tp`` heads (== n_heads/tp for classic multi-head; under GQA
  the grouped heads — rank-local by the contiguous permutation — stack the
  cache shrink on top of the head sharding, with RoPE rotating the local
  heads at the chunk's absolute positions), one psum per row-parallel
  matmul (no backward here, so plain ``lax.psum`` replaces the f/g
  custom-vjp pair).
* **Vocab-parallel logits + sampling.**  With ``vocab_parallel=True`` the
  head matmul produces only the LOCAL ``(B, V/tp)`` logits shard
  (``megatron.vocab_parallel_logits``); greedy decoding argmaxes across the
  shards with the pmax/pmin trick (``megatron.vocab_parallel_accuracy``'s
  tie-breaking, exact vs dense argmax), and temperature sampling uses the
  **Gumbel-max trick**: each rank draws iid Gumbel noise for its own vocab
  slice (key folded with the rank index), and the global argmax of
  ``logits/T + g`` is *exactly* one categorical sample — the full logits
  row never exists on any device.
* **Batch rows over the data axes**, same contract as
  ``generate.generate_sharded``.

Pipeline checkpoints: :func:`pipeline_params_for_decode` unstacks the
(stage, layer) block stack back to the per-layer list with plain jnp ops on
the sharded arrays — XLA moves shards device-to-device; nothing bounces
through one host — after which the params ARE the SP x TP layout (the qkv
permutation convention is shared, ``parallel.pipeline.init_pipeline_params``)
and decode proceeds here.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import megatron
from .core import LayerNorm
from .generate import _filter_logits
from .transformer import Transformer

TENSOR_AXIS = "tensor"


def init_tp_kv_cache(model: Transformer, batch: int, max_len: int, tp: int):
    """Per-layer (k, v) buffers with LOCAL heads: (B, max_len, KV/tp, Dh)
    — under GQA the cache holds this rank's kv_heads/tp grouped heads
    (the same per-rank assignment as training, megatron.qkv_tp_permutation),
    stacking the GQA cache shrink on top of the head sharding."""
    model.cfg.require_plain_block("generate_tp's head-sharded KV cache")
    c = model.cfg
    shape = (batch, max_len, c.kv_heads // tp, c.head_dim)
    zeros = lambda: jnp.zeros(shape, c.compute_dtype)
    return [{"k": zeros(), "v": zeros()} for _ in range(c.n_layers)]


def _tp_block_chunk(cfg, lp, cache, x, pos, heads_local: int,
                    axis: str = TENSOR_AXIS, moe_ffn=None):
    """One Megatron block on a chunk (B, S, D) at position ``pos`` with the
    KV cache holding this rank's heads.  Mirrors ``generate._block_chunk``
    (dense) with ``megatron.tp_block_apply``'s sharding: column-parallel
    qkv (local layout [q_r | k_r | v_r]), local-head attention, psum after
    the row-parallel matmuls with the bias added once post-psum.

    ``moe_ffn`` (from ``parallel.expert.moe_ffn_fn`` with
    ``expert_axis=None, tensor_axis='tensor'``) replaces the dense FFN
    for MoE checkpoints: experts held whole per rank, each expert's
    hidden dim tensor-sharded — the same layout the SP x TP MoE train
    step uses, so trained expert shards decode in place."""
    cdt = cfg.compute_dtype
    ln = LayerNorm(cfg.d_model, param_dtype=cfg.param_dtype)
    h = ln.apply(lp["ln1"], x)
    qkv = (h.astype(cdt) @ lp["qkv"]["w"].astype(cdt)
           + lp["qkv"]["b"].astype(cdt))
    b, s, _ = qkv.shape
    # local layout is [q_r | k_r | v_r] (megatron.qkv_tp_permutation);
    # under GQA the k/v spans hold this rank's kv_heads/tp heads, whose
    # query-head groups are exactly this rank's (contiguous assignment)
    tp = cfg.n_heads // heads_local
    kv_local = cfg.kv_heads // tp
    q_w = heads_local * cfg.head_dim
    kv_w = kv_local * cfg.head_dim
    q = qkv[..., :q_w].reshape(b, s, heads_local, cfg.head_dim)
    k = qkv[..., q_w:q_w + kv_w].reshape(b, s, kv_local, cfg.head_dim)
    v = qkv[..., q_w + kv_w:].reshape(b, s, kv_local, cfg.head_dim)
    if cfg.pos_encoding == "rope":
        # rotation is per-head-independent, so this rank's local heads
        # rotate correctly; cached keys are stored rotated (standard)
        from ..ops.rope import rope_rotate

        chunk_pos = pos + jnp.arange(s)
        q = rope_rotate(q, chunk_pos, cfg.rope_theta)
        k = rope_rotate(k, chunk_pos, cfg.rope_theta)
    new_k = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                     (0, pos, 0, 0))
    new_v = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                     (0, pos, 0, 0))
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    T = cache["k"].shape[1]
    mask = (jnp.arange(T)[None, None, None, :]
            <= pos + jnp.arange(s)[None, None, :, None])
    if kv_local == heads_local:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            new_k.astype(jnp.float32)) * scale
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         new_v.astype(jnp.float32)).astype(x.dtype)
    else:
        # GQA: grouped-head attention on the local cache — the repeat
        # stays virtual (an einsum batch dim), mirroring the dense
        # decode's grouped branch (models.generate._block_chunk)
        g = heads_local // kv_local
        q5 = q.reshape(b, s, kv_local, g, cfg.head_dim)
        logits = jnp.einsum("bqcgd,bkcd->bcgqk", q5.astype(jnp.float32),
                            new_k.astype(jnp.float32)) * scale
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bcgqk,bkcd->bqcgd", probs,
                         new_v.astype(jnp.float32)).astype(x.dtype)
        out = out.reshape(b, s, heads_local, cfg.head_dim)
    out = out.reshape(b, s, heads_local * cfg.head_dim)
    partial = out.astype(cdt) @ lp["attn_out"]["w"].astype(cdt)
    attn = lax.psum(partial, axis) + lp["attn_out"]["b"].astype(cdt)
    x = x + attn.astype(x.dtype)
    h = ln.apply(lp["ln2"], x)
    if moe_ffn is not None:
        ff, _aux = moe_ffn(lp, h)  # load-balance aux is a training signal
        return x + ff.astype(x.dtype), {"k": new_k, "v": new_v}
    hh = megatron.tp_ffn_hidden(cfg, lp, h)
    ff = (lax.psum(hh @ lp["ff_out"]["w"].astype(cdt), axis)
          + lp["ff_out"]["b"].astype(cdt))
    return x + ff.astype(x.dtype), {"k": new_k, "v": new_v}


def _sharded_sample(logits_local, temperature: float, key,
                    axis: str = TENSOR_AXIS, top_k: int = 0) -> jax.Array:
    """One token per row from vocab-SHARDED logits (B, V/tp), exact:

    * greedy — global argmax via pmax, smallest-index tie-break via pmin
      (matches ``jnp.argmax`` on the dense row);
    * temperature — Gumbel-max: per-rank iid Gumbel noise on the local
      slice (key folded with the rank index so no two ranks share noise),
      then the same global argmax.  argmax_i(l_i/T + g_i) ~ Categorical
      (softmax(l/T)) exactly;
    * ``top_k > 0`` — the candidate set is restricted WITHOUT gathering
      the logits row: each rank takes its local top-k (at most k global
      winners can live on one shard), an all_gather of those tp*k scalars
      per row yields the global k-th value, and everything below it masks
      out before the Gumbel noise.  Matches ``generate._filter_logits``'s
      ``logits < kth -> -inf`` rule exactly (ties at the threshold kept).
    """
    v_local = logits_local.shape[-1]
    rank = lax.axis_index(axis)
    offset = rank * v_local
    scores = logits_local.astype(jnp.float32)
    if temperature > 0:
        scaled = scores / temperature
        if top_k > 0:
            k_eff = min(top_k, v_local)
            local_top = lax.top_k(scaled, k_eff)[0]          # (B, k)
            # (B, tp*k) of candidate maxima — tiny; never the logits row
            all_top = lax.all_gather(local_top, axis, axis=-1, tiled=True)
            kth = lax.top_k(all_top, top_k)[0][..., -1:]     # global k-th
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        g = jax.random.gumbel(jax.random.fold_in(key, rank),
                              scaled.shape, jnp.float32)
        scores = scaled + g
    local_max = scores.max(-1)
    global_max = lax.pmax(local_max, axis)
    local_arg = jnp.argmax(scores, axis=-1).astype(jnp.int32) + offset
    cand = jnp.where(local_max >= global_max, local_arg,
                     jnp.iinfo(jnp.int32).max)
    return lax.pmin(cand, axis)


def _full_sample(logits, temperature: float, key, top_k: int, top_p: float):
    """Sampling on full (replicated-head) logits inside the shard body:
    same math as ``generate._sample`` but with the key threaded by the
    caller (every tensor rank uses the SAME key -> identical draws, so the
    replicated token stays replicated)."""
    if temperature > 0:
        logits = _filter_logits(logits / temperature, top_k, top_p)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.lru_cache(maxsize=32)
def _tp_decode_program(model: Transformer, mesh, max_new_tokens: int,
                       temperature: float, top_k: int, top_p: float,
                       pad_id: int, vocab_parallel: bool, ragged: bool,
                       batch_axes: Tuple[str, ...]):
    """One jitted shard_map decode program per (model, mesh, knobs)."""
    c = model.cfg
    tp = int(mesh.shape[TENSOR_AXIS])
    megatron.validate_tp(c, tp)
    heads_local = c.n_heads // tp
    if vocab_parallel and c.vocab_size % tp:
        raise ValueError(f"vocab_size={c.vocab_size} not divisible by "
                         f"tp={tp}")
    if vocab_parallel and 0.0 < top_p < 1.0:
        raise NotImplementedError(
            "top_p needs a sorted cumulative view of the full logits row; "
            "with vocab_parallel the row is never materialized — use "
            "greedy, temperature, or top_k sampling here (top_k works "
            "shard-locally + a tp*k all_gather), or decode with "
            "vocab_parallel=False (replicated head)")
    if vocab_parallel and top_k > c.vocab_size:
        raise ValueError(f"top_k={top_k} > vocab_size={c.vocab_size}")

    def embed(params, ids, positions):
        if vocab_parallel:
            return model.add_pos(
                params,
                megatron.vocab_parallel_embed(params["embed"]["table"], ids),
                positions)
        return model.embed(params, ids, positions)

    def logits_last(params, x_last):
        """(B, S, D) -> sampling-ready logits of the LAST chunk position."""
        if vocab_parallel:
            return megatron.vocab_parallel_logits(
                model.final_norm(params, x_last), params["head"]["w"],
                compute_dtype=c.compute_dtype)
        return model.head_logits(params, x_last)

    def sample(logits_2d, key):
        if vocab_parallel:
            return _sharded_sample(logits_2d, temperature, key,
                                   top_k=top_k)
        return _full_sample(logits_2d, temperature, key, top_k, top_p)

    moe_ffn = None
    if c.moe_experts > 0:
        # experts whole per rank, hidden dim tensor-sharded — the SP x TP
        # MoE layout (parallel.expert.moe_ffn_fn is the single factory the
        # train steps use too, so decode cannot drift from training)
        from ..parallel.expert import moe_ffn_fn

        moe_ffn = moe_ffn_fn(c, expert_axis=None, tensor_axis=TENSOR_AXIS)

    def forward_chunk(params, caches, ids, pos):
        positions = pos + jnp.arange(ids.shape[1])
        x = embed(params, ids, positions)
        new_caches = []
        for lp, cache in zip(params["blocks"], caches):
            x, cache = _tp_block_chunk(c, lp, cache, x, pos, heads_local,
                                       moe_ffn=moe_ffn)
            new_caches.append(cache)
        return x, new_caches

    def shard_decode(params, prompt, lens, key):
        # Independent draws per DATA shard: the key arrives shard_map-
        # replicated (in_spec P()), so without this fold identical prompts
        # in different batch shards would decode identical continuations.
        # Only the batch axes fold here — the 'tensor' axis must NOT (the
        # sampled token must stay replicated across tensor ranks; the
        # per-rank fold for vocab-sharded Gumbel noise lives inside
        # _sharded_sample).
        for a in batch_axes:
            key = jax.random.fold_in(key, lax.axis_index(a))
        b, p = prompt.shape
        total = p + max_new_tokens
        caches = init_tp_kv_cache(model, b, total, tp)
        tokens = jnp.concatenate(
            [prompt.astype(jnp.int32),
             jnp.full((b, max_new_tokens), pad_id, jnp.int32)], axis=1)

        def step(carry, pos):
            tokens, caches, key = carry
            key, sub = jax.random.split(key)
            ids_1 = lax.dynamic_slice(tokens, (0, pos), (b, 1))
            x, caches = forward_chunk(params, caches, ids_1, pos)
            nxt = sample(logits_last(params, x)[:, 0], sub)
            if ragged:
                keep = (pos + 1) < lens
                cur = lax.dynamic_slice(tokens, (0, pos + 1), (b, 1))[:, 0]
                nxt = jnp.where(keep, cur, nxt)
            tokens = lax.dynamic_update_slice(tokens, nxt[:, None],
                                              (0, pos + 1))
            return (tokens, caches, key), None

        if ragged:
            start = 0
        else:  # prefill all P prompt positions in one parallel chunk
            x, caches = forward_chunk(params, caches, tokens[:, :p], 0)
            key, sub = jax.random.split(key)
            first = sample(logits_last(params, x[:, p - 1:p])[:, 0], sub)
            tokens = lax.dynamic_update_slice(tokens, first[:, None], (0, p))
            start = p
        if start < total - 1:
            (tokens, _, _), _ = lax.scan(step, (tokens, caches, key),
                                         jnp.arange(start, total - 1))
        return tokens

    dummy = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if c.scan_layers:
        # the caller unstacks scanned params to a per-layer list (the decode
        # walks layers with per-layer caches); mirror that here or the spec
        # tree cannot match the param tree
        dummy = dict(dummy)
        dummy["blocks"] = [
            jax.tree_util.tree_map(
                lambda x, i=i: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                dummy["blocks"])
            for i in range(c.n_layers)
        ]
    from ..parallel.spmd import sp_tp_param_specs

    pspecs = sp_tp_param_specs(dummy, vocab_parallel)
    rows = P(batch_axes)
    mapped = jax.shard_map(
        shard_decode, mesh=mesh,
        in_specs=(pspecs, rows, rows if ragged else P(), P()),
        out_specs=rows,
        check_vma=False,
    )
    return jax.jit(mapped), pspecs, rows


def generate_tp(model: Transformer, params, prompt, mesh,
                max_new_tokens: int, *, temperature: float = 0.0,
                top_k: int = 0, top_p: float = 1.0,
                key: Optional[jax.Array] = None,
                prompt_lens: Optional[jax.Array] = None,
                pad_id: int = 0, vocab_parallel: bool = False,
                batch_axes: Tuple[str, ...] = ("data",)) -> jax.Array:
    """Decode ``max_new_tokens`` after ``prompt`` (B, P) -> (B, P + N) with
    ``params`` in the NATIVE seq x tensor training layout (per-layer
    blocks, head-aligned qkv permutation, qkv/ff_in column- and
    attn_out/ff_out row-sharded over 'tensor'; MoE expert stacks whole
    per rank with their hidden dims tensor-sharded; embed/head
    vocab-sharded when ``vocab_parallel``).  No host gather, no dense
    param copy.

    Sampling knobs as in ``generate.generate``; with ``vocab_parallel``,
    greedy, temperature, and top_k are available (top_k restricts the
    candidate set via local top-k + a tp*k all_gather of scalars — the
    full logits row is still never materialized); top_p would need a
    sorted cumulative view of the whole row and is rejected.  ``prompt``
    rows shard over ``batch_axes`` (axes absent from the mesh are
    ignored).
    """
    c = model.cfg
    b, p = prompt.shape
    if p + max_new_tokens > c.max_seq_len:
        raise ValueError(f"prompt {p} + {max_new_tokens} new tokens exceeds "
                         f"max_seq_len {c.max_seq_len}")
    if temperature > 0 and key is None:
        raise ValueError("temperature sampling needs a PRNG key")
    if max_new_tokens == 0:
        return jnp.asarray(prompt, jnp.int32)
    if c.scan_layers:
        # per-layer caches need per-layer params; unstack the scanned
        # leaves (slices of the same buffers — no copy under jit)
        params = dict(params)
        stacked = params["blocks"]
        params["blocks"] = [
            jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
            for i in range(c.n_layers)
        ]
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    if b % n:
        raise ValueError(f"prompt batch {b} not divisible by the {axes} "
                         f"axes product {n}")
    ragged = prompt_lens is not None
    run, pspecs, rows = _tp_decode_program(
        model, mesh, max_new_tokens, temperature, top_k, top_p, pad_id,
        vocab_parallel, ragged, axes)
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params,
        pspecs)
    prompt = jax.device_put(jnp.asarray(prompt, jnp.int32),
                            NamedSharding(mesh, rows))
    if ragged:
        prompt_lens = jax.device_put(jnp.asarray(prompt_lens, jnp.int32),
                                     NamedSharding(mesh, rows))
    else:
        prompt_lens = jnp.zeros((), jnp.int32)  # unused placeholder
    if key is None:
        key = jax.random.PRNGKey(0)
    return run(params, prompt, prompt_lens, key)


def pipeline_params_for_decode(params, model: Transformer,
                               qkv_tp: Optional[int] = None,
                               decode_tp: Optional[int] = None):
    """(stage, layer)-stacked pipeline params (plain or interleaved — the
    stack depth is inferred from the leaf ndim) -> the per-layer list
    layout :func:`generate_tp` consumes.  Plain jnp ops on the sharded
    arrays: XLA reshards device-to-device (the pipe-sharded stack
    redistributes to the tensor/replicated decode placement inside
    ``generate_tp``'s device_put); no single-host gather
    (``Trainer._eval_params``) on the path.

    The qkv head-alignment convention is shared between the pipeline and
    sp_tp layouts, but the column *permutation* is tp-DEGREE-dependent:
    a checkpoint permuted for tp=2 decoded on a tensor=4 mesh would emit
    silently wrong tokens.  Pass ``qkv_tp`` (the checkpoint meta's value,
    as ``cli._dense_decode_params`` does) and ``decode_tp``
    (``mesh.shape['tensor']`` of the decode mesh): when they differ the
    blocks are re-permuted (inverse of the saved permutation, then the
    decode mesh's).  Omitting either keeps the historical same-degree
    assumption — only safe when caller guarantees the degrees match."""
    from ..parallel import megatron
    from ..parallel.pipeline import dense_layer_blocks

    out = dict(params)
    if (qkv_tp is not None and decode_tp is not None
            and int(qkv_tp) != int(decode_tp)):
        # undo the saved permutation via the one place that owns that rule
        # (dense_layer_blocks, parallel/pipeline.py), then re-permute for
        # the decode mesh's degree
        c = model.cfg
        out["blocks"] = dense_layer_blocks(params["blocks"], c,
                                           saved_tp=int(qkv_tp))
        if int(decode_tp) > 1:
            out["blocks"] = megatron.permute_qkv(
                out["blocks"], c.d_model, c.n_heads, int(decode_tp),
                kv_heads=c.kv_heads)
    else:
        # degrees match (or caller vouches): keep the head-aligned
        # permutation — generate_tp consumes the NATIVE tp layout; only
        # the stacking is flattened here
        out["blocks"] = dense_layer_blocks(params["blocks"])
    n_layers = model.cfg.n_layers
    if (not isinstance(out["blocks"], list)
            or len(out["blocks"]) != n_layers):
        raise ValueError(
            f"expected a stacked pipeline blocks pytree flattening to "
            f"{n_layers} layers; got "
            f"{type(params['blocks']).__name__} -> "
            f"{len(out['blocks']) if isinstance(out['blocks'], list) else 'non-list'}")
    return out
