"""Continuous-batching decode server (in-flight batching).

The reference has no serving story at all (its closest artifact is the
dead test-eval block, dataParallelTraining_NN_MPI.py:227-236).  This is
the runtime layer above :mod:`models.generate`: a fixed pool of ``slots``
decodes as ONE batched jitted step per token, while requests join and
leave mid-flight — the scheduling model TPU serving wants, because the
chip's throughput comes from batching yet real traffic arrives ragged.

Design (slot server):

* Device state: per-layer KV caches ``(S, L, kv_heads, head_dim)``, a
  token ring ``(S, L)``, per-slot ``pos`` and ``target`` — all static
  shapes, so the decode step is ONE compiled program regardless of which
  subset of slots is live.
* ``submit()`` prefills the prompt with the existing chunk path
  (:func:`models.generate._forward_chunk`) on a batch-1 cache and
  inserts the resulting cache slab + first sampled token into a free
  slot (a vmapped ``dynamic_update_slice`` on the slot axis).  Admission
  cost is one prefill, never a pool-wide recompile.
* ``step()`` advances EVERY slot one token with
  :func:`models.generate._forward_token_batched` — each row attends at
  its own depth via a per-row causal mask and writes its K/V at its own
  position (vmapped update).  Finished or free slots still flow through
  the batch (their writes are idempotent re-writes of the same values
  and their samples are discarded); masking happens host-side in the
  pos/active bookkeeping, which is exactly the continuous-batching
  contract: dead lanes cost FLOPs, not recompiles, and are reclaimed at
  the next ``submit``.  Completion detection is host-side too: positions
  advance deterministically (+1 per active slot per step), so ``step()``
  performs ZERO per-token device syncs — the old per-step blocking
  ``device_get(self.pos)`` serialized the host against the device
  pipeline every token (not measured on a chip; ``sync_per_step=True``
  keeps the legacy fetch, held to the same tokens by
  tests/test_serve_paged.py).
* Greedy (temperature=0) decode matches :func:`models.generate.generate`
  token-for-token per request — pinned by tests/test_serve.py — because
  each row's attention reduces over exactly the same values in the same
  order as the single-stream path.

Host API::

    srv = DecodeServer(model, params, slots=4)
    rid = srv.submit([1, 2, 3], max_new_tokens=16)   # None if pool full
    while not srv.done(rid):
        srv.step()
    tokens = srv.result(rid)                          # prompt + decoded
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .generate import (
    _forward_chunk,
    _forward_token_batched,
    _sample,
    init_kv_cache,
)
from .transformer import Transformer

Pytree = Any


@functools.lru_cache(maxsize=8)
def _programs(model: Transformer, max_len: int, temperature: float,
              top_k: int, top_p: float, kv_quant: bool = False,
              prefill_chunk: int = 0):
    """The three jitted programs of a server instance, cached per (model,
    shape, sampling) so constructing several servers (or re-constructing
    in tests) compiles once."""

    def prefill(params, prompt):     # (1, P_bucket) -> logits + cache
        # prompts arrive padded to power-of-two buckets (submit), so the
        # number of compiled prefill programs is bounded by log2(max_len)
        # instead of one per distinct prompt length; all positions'
        # logits return and the caller indexes the true last position.
        # Pad positions' K/V land in the cache but are never attended:
        # decode masks keys <= pos and overwrites position p, p+1, ...
        # with generated tokens before each becomes visible.
        caches = init_kv_cache(model, 1, max_len, quant=kv_quant)
        pb = prompt.shape[1]
        if 0 < prefill_chunk < pb:
            # chunked prefill (generate()'s long-prompt lever): peak
            # attention memory O(chunk * T) instead of O(bucket * T);
            # all widths are static, so this is still ONE compiled
            # program per bucket
            outs = []
            for off in range(0, pb, prefill_chunk):
                w = min(prefill_chunk, pb - off)
                lg, caches = _forward_chunk(model, params, caches,
                                            prompt[:, off:off + w], off)
                outs.append(lg)
            return jnp.concatenate(outs, axis=1), caches
        logits, caches = _forward_chunk(model, params, caches, prompt, 0)
        return logits, caches

    def insert(pool, slab, slot):         # write batch-1 cache into slot
        return jax.tree_util.tree_map(
            lambda buf, one: lax.dynamic_update_slice(
                buf, one.astype(buf.dtype),
                (slot,) + (0,) * (buf.ndim - 1)),
            pool, slab)

    def step(params, caches, tokens, pos, active, key):
        b = tokens.shape[0]
        ids = jnp.take_along_axis(tokens, pos[:, None], axis=1)  # (S, 1)
        logits, caches = _forward_token_batched(model, params, caches,
                                                ids, pos)
        nxt, key = _sample(logits[:, 0], temperature, key, top_k, top_p)
        # only active slots append + advance; frozen slots re-write the
        # same K/V at the same pos (idempotent) and discard their sample
        nxt = jnp.where(active, nxt, jnp.take_along_axis(
            tokens, jnp.minimum(pos + 1, max_len - 1)[:, None],
            axis=1)[:, 0])
        write_at = jnp.minimum(pos + 1, max_len - 1)
        tokens = tokens.at[jnp.arange(b), write_at].set(nxt)
        pos = jnp.where(active, jnp.minimum(pos + 1, max_len - 1), pos)
        return caches, tokens, pos, key

    # compile-ledger seam (utils/compile_ledger): the dense server's
    # programs report their compiles like the paged server's
    from ..utils import compile_ledger as ledger_lib

    tag = f"T{max_len}" + ("/int8" if kv_quant else "")
    return (ledger_lib.instrument(jax.jit(prefill),
                                  f"dense_prefill[{tag}]"),
            ledger_lib.instrument(jax.jit(insert, donate_argnums=(0,)),
                                  f"dense_insert[{tag}]"),
            ledger_lib.instrument(jax.jit(step, donate_argnums=(1, 2, 3)),
                                  f"dense_decode[{tag}]"))


class DecodeServer:
    """Slot-based continuous batching on top of the KV-cache decoder."""

    def __init__(self, model: Transformer, params: Pytree, slots: int = 4,
                 max_len: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 kv_quant: bool = False, prefill_chunk: int = 0,
                 sync_per_step: bool = False):
        c = model.cfg
        self.model, self.params = model, params
        self.slots = int(slots)
        self.max_len = int(max_len or c.max_seq_len)
        if self.max_len > c.max_seq_len:
            raise ValueError(f"max_len {self.max_len} exceeds model "
                             f"max_seq_len {c.max_seq_len}")
        self._sampling = (float(temperature), int(top_k), float(top_p))
        self._prefill, self._insert, self._step = _programs(
            model, self.max_len, *self._sampling, bool(kv_quant),
            int(prefill_chunk))
        self.caches = init_kv_cache(model, self.slots, self.max_len,
                                    quant=kv_quant)
        self.tokens = jnp.zeros((self.slots, self.max_len), jnp.int32)
        self.pos = jnp.zeros((self.slots,), jnp.int32)
        self.active = np.zeros((self.slots,), bool)      # host-side
        # host shadow of ``pos``: positions advance deterministically
        # (one per active slot per step), so completion detection needs
        # NO device fetch — the per-token blocking device_get this loop
        # used to pay serialized every step against the device pipeline.
        # ``sync_per_step=True`` restores the old fetch, kept ONLY so
        # that the delta can be measured.
        self._pos_host = np.zeros((self.slots,), np.int64)
        self._sync_per_step = bool(sync_per_step)
        self.key = jax.random.PRNGKey(seed)
        # request bookkeeping (host): slot -> (request id, prompt_len,
        # target total length); results keyed by request id
        self._rid = 0
        self._slot_req: Dict[int, tuple] = {}
        self._results: Dict[int, List[int]] = {}
        if c.scan_layers:
            params = dict(params)
            stacked = params["blocks"]
            params["blocks"] = [
                jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
                for i in range(c.n_layers)]
            self.params = params

    # ---- admission ----------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int) -> Optional[int]:
        """Admit a request into a free slot; returns a request id, or
        None when the pool is full (caller queues and retries after
        step()s complete requests)."""
        free = [s for s in range(self.slots) if not self.active[s]
                and s not in self._slot_req]
        if not free:
            return None
        p = len(prompt_ids)
        if p == 0:
            raise ValueError("empty prompt: a request needs at least one "
                             "token (bucketed prefill would otherwise "
                             "sample from pad-position logits)")
        if p + max_new_tokens > self.max_len:
            raise ValueError(f"prompt {p} + {max_new_tokens} exceeds "
                             f"server max_len {self.max_len}")
        slot = free[0]
        bucket = 8
        while bucket < p:
            bucket *= 2
        bucket = min(bucket, self.max_len)
        padded = list(prompt_ids) + [0] * (bucket - p)
        prompt = jnp.asarray([padded], jnp.int32)
        logits, slab = self._prefill(self.params, prompt)
        t, tk, tp = self._sampling
        first_row, self.key = _sample(logits[:, p - 1], t, self.key, tk, tp)
        first = first_row[0]
        self.caches = [self._insert(pool, one, slot)
                       for pool, one in zip(self.caches, slab)]
        row = np.zeros((self.max_len,), np.int32)
        row[:p] = np.asarray(prompt_ids, np.int32)
        row[p] = int(first)
        self.tokens = self.tokens.at[slot].set(jnp.asarray(row))
        self.pos = self.pos.at[slot].set(p)      # last written position
        self._pos_host[slot] = p
        self.active[slot] = max_new_tokens > 1
        rid = self._rid
        self._rid += 1
        self._slot_req[slot] = (rid, p, p + max_new_tokens)
        if not self.active[slot]:                # single-token request
            self._finish(slot)
        return rid

    # ---- decode -------------------------------------------------------
    def step(self) -> None:
        """One batched decode step across all slots (no-op when nothing
        is active)."""
        if not self.active.any():
            return
        # a HOST-side copy: on the CPU backend asarray may alias the numpy
        # buffer (and jnp.array's own copy is an async device op), while
        # the host mutates self.active before the dispatched step has run
        active_dev = jnp.asarray(self.active.copy())
        self.caches, self.tokens, self.pos, self.key = self._step(
            self.params, self.caches, self.tokens, self.pos, active_dev,
            self.key)
        if self._sync_per_step:
            # measurement-only legacy path: block on the device every
            # step (the host sync the default path no longer pays)
            self._pos_host[:] = np.asarray(jax.device_get(self.pos))
        else:
            # positions advance deterministically: +1 per active slot.
            # The device array clamps at max_len-1 but an active slot
            # always finishes at target <= max_len first, so the shadow
            # never diverges while it matters.
            self._pos_host[self.active] += 1
        for slot, (rid, p, target) in list(self._slot_req.items()):
            if self.active[slot] and self._pos_host[slot] + 1 >= target:
                self._finish(slot)

    def _finish(self, slot: int) -> None:
        rid, p, target = self._slot_req.pop(slot)
        row = np.asarray(jax.device_get(self.tokens[slot]))
        self._results[rid] = [int(t) for t in row[:target]]
        self.active[slot] = False

    # ---- results ------------------------------------------------------
    def done(self, rid: int) -> bool:
        """True once ``rid`` finished; raises KeyError for an id this
        server never issued or whose result was already consumed — a
        'while not done(rid)' loop on a stale id must fail loudly, not
        spin forever on a pool with nothing active."""
        if rid in self._results:
            return True
        if any(r == rid for r, _, _ in self._slot_req.values()):
            return False
        raise KeyError(f"request {rid}: unknown or already consumed")

    def result(self, rid: int) -> List[int]:
        """Prompt + generated ids for a finished request (pops it)."""
        return self._results.pop(rid)

    def live(self) -> int:
        """Number of in-flight requests."""
        return len(self._slot_req)
