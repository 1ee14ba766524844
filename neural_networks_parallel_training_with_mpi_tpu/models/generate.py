"""Autoregressive decoding for the Transformer LM (inference API).

The reference is a pure training demo — it has no inference path at all
(its dead test-evaluation block at dataParallelTraining_NN_MPI.py:227-236 is
the closest thing).  A complete framework needs one, so this module adds
jitted autoregressive decoding, TPU-shaped:

* **KV cache with static shapes**: the cache is a preallocated
  ``(B, max_len, heads, head_dim)`` buffer per layer, written with
  ``lax.dynamic_update_slice`` at the current position — no growing arrays,
  so the whole decode loop is one compiled program.
* **Prefill + scan**: uniform prompts are prefixed in ONE batched chunk
  (prompt positions run in parallel on the MXU, exactly like the training
  forward), then new tokens come from a ``lax.scan`` of single-position
  chunks.  Ragged prompts (``prompt_lens``) fall back to the fully
  sequential scan so short rows' generated tokens — not their pads — enter
  the cache.
* **Shared wiring with training**: embeddings/head come from
  ``Transformer.embed``/``head_logits`` and the block weights from
  ``Transformer._block_modules``, so inference cannot drift from training
  (pinned by tests/test_generate.py's replay check).

Works with the dense-attention configuration (flash/ring add nothing at
chunk size 1).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .core import ACTIVATIONS
from .transformer import Transformer, split_qkv


def init_kv_cache(model: Transformer, batch: int, max_len: int,
                  quant: bool = False):
    """Per-layer (k, v) buffers, (B, max_len, kv_heads, head_dim).

    Under GQA (cfg.n_kv_heads < n_heads) the cache stores the
    UN-repeated K/V heads — kv_heads/n_heads of the MHA bytes, which is
    the whole point: decode streams the cache every step, so grouped
    heads cut the long-context serving bandwidth (and HBM residency) by
    the group factor.

    ``quant=True`` stores K/V as int8 with one f32 scale per (batch,
    position, head) — the third serving-bandwidth lever (stacks with
    GQA and int8 weights).  Both scales commute through the attention
    contractions: the K scale multiplies each key position's logit
    column, and the V scale folds into the softmax weights before the
    value einsum, so dequantization never materializes an f32 cache."""
    model.cfg.require_plain_block("the dense KV cache (models.generate)")
    c = model.cfg
    shape = (batch, max_len, c.kv_heads, c.head_dim)
    if quant:
        zeros = lambda: jnp.zeros(shape, jnp.int8)
        ones = lambda: jnp.ones(shape[:-1], jnp.float32)
        return [{"k": zeros(), "v": zeros(),
                 "k_scale": ones(), "v_scale": ones()}
                for _ in range(c.n_layers)]
    zeros = lambda: jnp.zeros(shape, c.compute_dtype)
    return [{"k": zeros(), "v": zeros()} for _ in range(c.n_layers)]


def _quantize_kv(x: jax.Array):
    """(..., head_dim) -> int8 codes + f32 scale over the trailing dim
    (symmetric, +/-127; zero rows get scale 1 so 0/1 round-trips)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, s.astype(jnp.float32)


def _block_chunk(model: Transformer, params, cache, x, pos):
    """One block on a chunk ``x`` (B, S, D) starting at position ``pos``:
    writes the chunk's K/V into the cache and attends causally over
    positions 0..pos+S-1.  S = prompt length at prefill, 1 per decode step.
    Mirrors Transformer._block for the incremental case.

    ``pos`` may be a scalar (every row at the same depth — the
    single-stream generate() path) or a ``(B,)`` vector (each row at its
    OWN depth — continuous batching, models.serve): the cache write is a
    vmapped per-row dynamic_update_slice and the causal mask compares
    against each row's own position, so both cases share one
    implementation and the int8-KV branch."""
    c = model.cfg
    mods = model._block_modules()
    h = mods["ln1"].apply(params["ln1"], x)
    qkv = mods["qkv"].apply(params["qkv"], h)
    b, s, _ = qkv.shape
    q, k, v = split_qkv(c, qkv)      # q: (b,s,H,hd); k/v: (b,s,KV,hd)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if c.pos_encoding == "rope":
        # rotate q and THIS chunk's k at their absolute positions; the
        # cache then holds already-rotated keys (standard RoPE decode),
        # so earlier positions are never revisited
        from ..ops.rope import rope_rotate

        chunk_pos = pos_b[:, None] + jnp.arange(s)[None, :]   # (b, s)
        q = rope_rotate(q, chunk_pos, c.rope_theta)
        k = rope_rotate(k, chunk_pos, c.rope_theta)
    write = jax.vmap(lambda buf, row, p: lax.dynamic_update_slice(
        buf, row, (p,) + (0,) * (buf.ndim - 1)))
    quant = "k_scale" in cache       # int8 KV cache (init_kv_cache)
    if quant:
        k, ks = _quantize_kv(k)
        v, vs = _quantize_kv(v)
        new_ks = write(cache["k_scale"], ks, pos_b)
        new_vs = write(cache["v_scale"], vs, pos_b)
    new_k = write(cache["k"], k.astype(cache["k"].dtype), pos_b)
    new_v = write(cache["v"], v.astype(cache["v"].dtype), pos_b)
    scale = 1.0 / jnp.sqrt(jnp.asarray(c.head_dim, jnp.float32))
    T = cache["k"].shape[1]
    # causal within the chunk: key position <= row position + query
    # offset — (b, s, T), degenerating to the classic chunk mask when
    # pos is scalar
    mask = (jnp.arange(T)[None, None, :]
            <= pos_b[:, None, None] + jnp.arange(s)[None, :, None])
    if c.kv_heads == c.n_heads:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            new_k.astype(jnp.float32)) * scale
        if quant:
            # K scale: one multiplier per key position/head on the logit
            # column — dequantization without an f32 copy of the cache
            logits = logits * new_ks.transpose(0, 2, 1)[:, :, None, :]
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        if quant:
            # V scale folds into the softmax weights (out is linear in
            # each value row, so p_k * s_k reweights exactly)
            probs = probs * new_vs.transpose(0, 2, 1)[:, :, None, :]
        out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                         new_v.astype(jnp.float32)).astype(x.dtype)
    else:
        # GQA: attend with the cache's grouped heads directly — the
        # repeat stays virtual (an einsum batch dim), so each decode
        # step streams only kv_heads/n_heads of the MHA cache bytes
        g = c.n_heads // c.kv_heads
        q5 = q.reshape(b, s, c.kv_heads, g, c.head_dim)
        logits = jnp.einsum("bqcgd,bkcd->bcgqk", q5.astype(jnp.float32),
                            new_k.astype(jnp.float32)) * scale
        if quant:
            logits = logits * new_ks.transpose(0, 2, 1)[:, :, None, None, :]
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        if quant:
            probs = probs * new_vs.transpose(0, 2, 1)[:, :, None, None, :]
        out = jnp.einsum("bcgqk,bkcd->bqcgd", probs,
                         new_v.astype(jnp.float32)).astype(x.dtype)
        out = out.reshape(b, s, c.n_heads, c.head_dim)
    out = out.reshape(b, s, c.d_model)
    x = x + mods["attn_out"].apply(params["attn_out"], out)
    with jax.named_scope("ffn"):   # as Transformer._block names it
        h = mods["ln2"].apply(params["ln2"], x)
        if c.moe_experts > 0:
            ff, _ = mods["moe"].apply(params["moe"], h)
        else:
            ff = model._ffn(mods, params, h)
        x = x + ff.astype(x.dtype)
    new_cache = {"k": new_k, "v": new_v}
    if quant:
        new_cache.update(k_scale=new_ks, v_scale=new_vs)
    return x, new_cache


def _forward_token_batched(model: Transformer, params, caches, ids,
                           pos_vec: jax.Array):
    """Logits for one token per row at PER-ROW positions (continuous
    batching, models.serve): ids (B, 1), pos_vec (B,) -> ((B, 1, vocab)
    f32, updated caches).  Rides :func:`_block_chunk`'s vector-``pos``
    mode, so the int8-KV branch and any future attention fix are shared
    with the single-stream path by construction."""
    x = model.embed(params, ids, pos_vec[:, None])
    new_caches = []
    for layer_params, cache in zip(params["blocks"], caches):
        x, cache = _block_chunk(model, layer_params, cache, x, pos_vec)
        new_caches.append(cache)
    return model.head_logits(params, x), new_caches


def _forward_chunk(model: Transformer, params, caches, ids, pos):
    """Logits for a chunk: ids (B, S) at start position ``pos`` ->
    ((B, S, vocab) f32, updated caches)."""
    positions = pos + jnp.arange(ids.shape[1])
    x = model.embed(params, ids, positions)
    new_caches = []
    for layer_params, cache in zip(params["blocks"], caches):
        x, cache = _block_chunk(model, layer_params, cache, x, pos)
        new_caches.append(cache)
    return model.head_logits(params, x), new_caches


def _filter_logits(logits, top_k: int, top_p: float):
    """Mask logits outside the top-k / nucleus-p candidate sets to -inf.
    Static control flow only (both knobs are trace-time constants), so the
    decode step stays one compiled program."""
    neg = jnp.finfo(logits.dtype).min
    if top_k > 0:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest prefix with cumulative mass >= top_p; the shifted mask
        # always keeps the most-probable token
        keep_sorted = jnp.roll(cum < top_p, 1, axis=-1).at[..., 0].set(True)
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, -neg),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, neg, logits)
    return logits


def _sample(logits, temperature, key, top_k: int = 0, top_p: float = 1.0):
    if temperature > 0:
        key, sub = jax.random.split(key)
        # temperature FIRST, then the nucleus: top_p must measure the mass
        # of the distribution actually being sampled (top_k is monotone in
        # the logits, so its candidate set is temperature-invariant)
        logits = _filter_logits(logits / temperature, top_k, top_p)
        nxt = jax.random.categorical(sub, logits, axis=-1)
    else:
        nxt = jnp.argmax(logits, axis=-1)
    return nxt.astype(jnp.int32), key


def generate(model: Transformer, params, prompt: jax.Array,
             max_new_tokens: int, *, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 1.0,
             key: Optional[jax.Array] = None,
             prompt_lens: Optional[jax.Array] = None,
             pad_id: int = 0, kv_quant: bool = False,
             prefill_chunk: int = 0) -> jax.Array:
    """Decode ``max_new_tokens`` after ``prompt`` (B, P) -> (B, P + N).

    ``temperature=0`` is greedy argmax; otherwise softmax sampling at the
    given temperature (``key`` required), optionally restricted to the
    ``top_k`` most likely tokens and/or the smallest nucleus with
    cumulative probability ``top_p`` (both static; 0 / 1.0 disable).
    With ragged prompts, right-pad to a common P with ``pad_id`` and pass
    ``prompt_lens`` (B,); each row starts generating at its own length
    (sequential path — generated tokens, not pads, populate the cache for
    short rows).

    ``kv_quant=True`` stores the KV cache as int8 with per-(batch,
    position, head) f32 scales (see ``init_kv_cache``) — ~half the cache
    bytes re-streamed per step vs the bf16-compute cache (~4x vs f32),
    the long-context serving lever that stacks with GQA and int8
    weights.  Also accepted by :func:`generate_sharded`.

    ``prefill_chunk > 0`` prefills the prompt in chunks of that many
    positions instead of one (B, P) pass: peak prefill attention memory
    drops from O(P·T) scores to O(chunk·T) — the long-PROMPT lever;
    identical tokens (chunk boundaries only change which query rows
    share a pass).  Ignored on the ragged path (already sequential).

    Wrap in ``jax.jit`` (static: model, max_new_tokens, temperature,
    top_k, top_p, kv_quant) for repeated use; shapes are static so
    recompiles only on new (B, P, N).
    """
    c = model.cfg
    b, p = prompt.shape
    total = p + max_new_tokens
    if total > c.max_seq_len:
        raise ValueError(f"prompt {p} + {max_new_tokens} new tokens exceeds "
                         f"max_seq_len {c.max_seq_len}")
    if temperature > 0 and key is None:
        raise ValueError("temperature sampling needs a PRNG key")
    if max_new_tokens == 0:
        # nothing to generate; the prefill path below would sample one token
        # and clamp its write onto the last prompt column
        return prompt.astype(jnp.int32)
    key = key if key is not None else jax.random.PRNGKey(0)
    if c.scan_layers:
        # decode walks layers with per-layer caches; unstack the scanned
        # (n_layers, ...) block leaves back to a per-layer list (slices of
        # the same buffers — no copy under jit)
        params = dict(params)
        stacked = params["blocks"]
        params["blocks"] = [
            jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
            for i in range(c.n_layers)
        ]
    caches = init_kv_cache(model, b, total, quant=kv_quant)
    tokens = jnp.concatenate(
        [prompt.astype(jnp.int32),
         jnp.full((b, max_new_tokens), pad_id, jnp.int32)], axis=1)
    ragged = prompt_lens is not None

    def step(carry, pos):
        tokens, caches, key = carry
        ids_1 = lax.dynamic_slice(tokens, (0, pos), (b, 1))
        logits, caches = _forward_chunk(model, params, caches, ids_1, pos)
        nxt, key = _sample(logits[:, 0], temperature, key, top_k, top_p)
        if ragged:
            # rows whose prompt extends past pos+1 keep their prompt token
            keep = (pos + 1) < prompt_lens
            cur = lax.dynamic_slice(tokens, (0, pos + 1), (b, 1))[:, 0]
            nxt = jnp.where(keep, cur, nxt)
        tokens = lax.dynamic_update_slice(tokens, nxt[:, None], (0, pos + 1))
        return (tokens, caches, key), None

    if ragged:  # fully sequential: per-row start positions
        start = 0
    else:  # prefill: prompt positions in parallel chunks
        if 0 < prefill_chunk < p:
            # chunked prefill (long-context serving): attention scores
            # for a chunk are (B, H, C, T) instead of (B, H, P, T), so
            # peak prefill memory is bounded by the chunk size while the
            # cache still fills left to right (each chunk attends over
            # everything already written, mirroring _block_chunk's
            # causal mask at its start offset).  Chunk boundaries don't
            # change the math — only which query rows share a pass.
            logits = None
            for off in range(0, p, prefill_chunk):
                c_len = min(prefill_chunk, p - off)
                logits, caches = _forward_chunk(
                    model, params, caches, tokens[:, off:off + c_len],
                    off)
            last_logits = logits[:, -1]   # final chunk ends at p - 1
        else:
            logits, caches = _forward_chunk(model, params, caches,
                                            tokens[:, :p], 0)
            last_logits = logits[:, p - 1]
        first, key = _sample(last_logits, temperature, key, top_k, top_p)
        tokens = lax.dynamic_update_slice(tokens, first[:, None], (0, p))
        start = p
    if start < total - 1:
        (tokens, _, _), _ = lax.scan(step, (tokens, caches, key),
                                     jnp.arange(start, total - 1))
    return tokens


@functools.lru_cache(maxsize=32)
def _sharded_decode_program(model: Transformer, mesh, max_new_tokens: int,
                            temperature: float, top_k: int, top_p: float,
                            pad_id: int, batch_axes,
                            kv_quant: bool = False):
    """One jitted decode program per (model, mesh, decode knobs) — cached
    so a serving loop pays compilation once, not per call.  The PRNG key
    and prompt lengths are TRACED arguments (new keys don't recompile)."""
    from ..parallel.sharding import batch_sharding

    rows = batch_sharding(mesh, ndim=2, batch_axes=batch_axes)

    def run(params, prompt, lens, key):
        return generate(model, params, prompt, max_new_tokens,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        key=key, prompt_lens=lens, pad_id=pad_id,
                        kv_quant=kv_quant)

    # compile-ledger seam (utils/compile_ledger): decode-path compiles
    # land in compiles.jsonl whenever a ledger is installed
    from ..utils import compile_ledger as ledger_lib

    return ledger_lib.instrument(
        jax.jit(run, out_shardings=rows),
        f"generate_sharded[n={max_new_tokens}]"), rows


def generate_sharded(model: Transformer, params, prompt, mesh,
                     max_new_tokens: int, *, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 1.0,
                     key: Optional[jax.Array] = None,
                     prompt_lens: Optional[jax.Array] = None,
                     pad_id: int = 0,
                     batch_axes=("data", "fsdp"),
                     kv_quant: bool = False) -> jax.Array:
    """Batch-parallel decode over the mesh's data axes: params replicated,
    prompt rows sharded, one CACHED jitted program — GSPMD partitions the
    KV caches and the sampling with the batch, so serving throughput
    scales with devices the same way training does (the reference has no
    inference path at all; its closest artifact is the dead test-eval
    block, dataParallelTraining_NN_MPI.py:227-236).

    ``prompt`` (B, P) with B divisible by the product of the mesh's
    ``batch_axes`` sizes; axes absent from the mesh are ignored.  Same
    sampling knobs as :func:`generate`."""
    from ..parallel.sharding import batch_sharding, replicated_sharding

    if temperature > 0 and key is None:  # mirror generate()'s guard:
        # defaulting the key here would make every "sampled" request
        # silently deterministic
        raise ValueError("temperature sampling needs a PRNG key")
    axes = tuple(a for a in batch_axes if a in mesh.shape)
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    b = prompt.shape[0]
    if b % n:
        raise ValueError(f"prompt batch {b} not divisible by the "
                         f"{axes} axes product {n}")
    run, rows = _sharded_decode_program(model, mesh, max_new_tokens,
                                        temperature, top_k, top_p, pad_id,
                                        axes, kv_quant)
    params = jax.device_put(params, replicated_sharding(mesh))
    prompt = jax.device_put(jnp.asarray(prompt, jnp.int32), rows)
    if prompt_lens is not None:
        prompt_lens = jax.device_put(jnp.asarray(prompt_lens, jnp.int32),
                                     batch_sharding(mesh, ndim=1,
                                                    batch_axes=axes))
    if key is None:
        key = jax.random.PRNGKey(0)
    return run(params, prompt, prompt_lens, key)
