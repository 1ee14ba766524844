"""Paged KV cache: block-allocated pools + static-shape gathered attention.

The dense slot server (``models/serve.py``) reserves ``max_len`` cache
positions per slot the moment a stream is admitted, so device memory is
spent on the WORST-case length of every stream simultaneously — the
classic serving waste paged attention removes (vLLM, Kwon et al. 2023;
the TPU angle is that everything must stay static-shape so one compiled
step serves any mix of lengths).  Here the cache is a pool of fixed-size
blocks:

* **Pools**: per layer, one pool per entry of the attention's **cache
  row** (``Transformer.cache_row()``: pool name -> the trailing shape a
  token holds), each ``(num_blocks, block_size, *row)``.  Multi-head and
  grouped-query attention answer ``k``/``v`` of ``(kv_heads, head_dim)``
  (plus f32 scale pools under ``kv_quant`` — the same int8 scheme as
  :func:`models.generate.init_kv_cache`, quantized per (position, head)
  so block boundaries never change the numbers); latent attention
  answers one pool ``latent`` of ``(kv_lora_rank + qk_rope_head_dim,)``,
  normed and rotated before it is written.  Allocation, block tables,
  copy-on-write, export / import and the handoff geometry read the row
  and nothing else of the attention.
* **State that is not paged** (a model with a state-space mixer beside its
  attention, ``models/ssm.py``): per layer, one row a SLOT of the model's
  **state row** (``Transformer.state_row()``: the mixer's convolution tail
  and its float32 state), in a second store beside the pools
  (:func:`init_paged_state`).  A row is a function of its stream, not of
  positions: fixed in size, overwritten in place by every prefill chunk and
  decode tick, never shared; it is zeroed on the device at admission,
  held bit for bit by idle lanes and pad columns, and goes with the slot
  (DESIGN.md says why it is no kind of page).
* **Block tables**: per slot, ``(max_blocks,)`` int32 indices into the
  pool, host-owned (a tiny traced argument each step — never a
  recompile).  Unallocated entries point at the reserved **sink block
  0**, which is never handed to a stream: pad/frozen writes land there
  harmlessly and are never attended.
* **Attention dispatch** (``attn_impl``, default ``auto``, resolved once
  in :func:`resolve_attn_impl` from what the code can observe): on a TPU,
  with pools that are not int8 and a cache row that is per-head K/V at a
  lane-dense ``kv_heads * head_dim`` or latent attention's one row,
  ``auto`` is the **fused** path (``ops.pallas_kernels.paged_attention``):
  the Pallas kernel reads the cache straight from the pool through the
  tables, several pages a loop step, and stops at each stream's own
  length.  Its pools are stored in the layout the kernel DMAs
  (:func:`stored_rows`), so a pool reaches it without a copy: a per-head
  row with the heads folded into the lanes, ``(num_blocks, block_size,
  kv_heads * head_dim)`` (the same bytes a block row); the latent row
  zero-padded to whole 128-lane tiles, ``(num_blocks, block_size, 384)``
  for a row of 320 (what leaves the server stays 320 wide, so export /
  import and the handoff do not care).  With the per-head row the decode
  program and the prefill-chunk program alike materialise no
  ``pool[tables]`` and reduce over no ``T_cap`` keys.  With the latent row
  the decode program does not either: the kernel's shared-row mode (one
  row is key and value: multi-query attention over ``[c_kv | k_rope]``)
  sits between the absorbed form's two ``W_kvb`` products; its prefill
  chunk gathers its one stream's rows from the same pool and keeps the
  expanded form (the absorbed form a chunk through the kernel would need
  executes about three times the expanded form's FLOPs).  Anywhere else
  (the CPU, int8 KV, a head row that does not fill the lanes) ``auto`` is
  the **gathered** path, which gathers each row's blocks ``pool[table] ->
  (T_cap, kv_heads, head_dim)`` (``T_cap = max_blocks * block_size``) and
  attends under the causal mask ``t <= pos`` — the same reduction, over
  the same values in the same order, as the dense cache path, which is
  why greedy paged decode is token-identical to ``DecodeServer`` /
  ``models.generate.generate`` (pinned by tests/test_serve_paged.py); the
  fused path is token-identical to it (tests/test_paged_attn.py,
  tests/test_mla_moe_model.py).  ``"gathered"`` and ``"fused"`` stay as
  explicit values: the parity reference, and the kernel in interpret mode
  on the CPU.
* **Writes** are scatters at ``(table[pos // block_size], pos %
  block_size)`` — one position per row at decode, a chunk of positions
  at prefill (chunks may straddle block boundaries; each position
  resolves its own block).

Invariant the step relies on (mirrors the dense server's "dead lanes
cost FLOPs, not recompiles" contract): every slot flows through the
batched step every tick, but live blocks are written ONLY by prefill
chunks and ACTIVE decode lanes.  ``step()`` masks every non-active
slot's table row to the sink (free, finished, and mid-prefill slots
alike), so a dead lane's unconditional write lands in the sink and its
gathered read is discarded garbage — parity never rests on a frozen
lane recomputing bitwise-identical K/V, and a finished/evicted slot's
table is additionally zeroed BEFORE its blocks are freed so nothing can
touch a block someone else just allocated.

Completion is detected from HOST-tracked position counters (positions
advance deterministically, one per active slot per step), so the decode
loop performs zero per-token device syncs — the discipline the trainer's
monitor uses, taken to its limit (see the satellite fix in
``models/serve.py``).

**The contract of ``step()`` (and of ``Scheduler.tick()``), in four lines.**
A rid is reported when its row is on the host, and only then is its result
readable.  The row is waited for behind one queued program: the step that
makes a stream's last token takes the row (``serve_take``: a copy into a
buffer of its own, a copy to the host started behind it) and frees the slot
and the blocks at once; :meth:`PagedDecodeServer.land` waits for it after the
next chunk or step has been dispatched, so the wait never drains the device's
queue.  Nothing is in flight once nothing is dispatched: with no stream left
active or prefilling, or no program dispatched since the last landing, every
row lands at once (a single stream, a drained scheduler, ``drain`` /
``quiesce`` / ``close``).  ``t_done`` is the landing, never a dispatch.  The
rest of a request's boundary is one program each: an admission
(``serve_admit``: token row, position and, for a model with state, the
slot's zeroed state rows, in place) and a prefill's first token
(``serve_first_token``: sampled from the one row of logits the prompt's last
chunk returns, so one compile a server).  A prefill chunk runs the head on
the column that is read and on no other: the last true column of a prompt's
last chunk; a chunk that is not the last runs no head at all.

**Prefix caching + copy-on-write** (``prefix_cache=True``): real chat
traffic shares system prompts, and the block-table indirection above is
one refcount away from sharing the identical prefix K/V across streams
(vLLM's insight applied at admission; SGLang's RadixAttention shows the
hit rates a prefix-matched block store reaches on chat/agentic mixes).
A host-side :class:`PrefixIndex` maps hash-chained token chunks at block
granularity to resident blocks; ``try_admit`` longest-matches a new
prompt against it and points the matched table entries at the EXISTING
blocks instead of allocating and prefilling them — a fully cached prefix
admits with only the last prompt token left to prefill (its logits seed
the first sampled token), so TTFT collapses to the remaining-suffix
prefill.  :class:`BlockAllocator` grows per-block refcounts: a matched
in-use block is ``share()``d (refcount + 1), a matched cached-FREE block
(refcount 0, content intact, sitting in the allocator's LRU side of the
free list) is ``reuse_cached()``d, and fresh allocation under pressure
evicts cached-free blocks LRU-first (invalidating their index entries).
Sharing is read-only by construction: a stream may write ONLY blocks it
owns, and when its matched prefix ends mid-block the first write past
the shared boundary triggers **copy-on-write** — a fresh block (reserved
at admission, so the fork can never fail mid-prefill) receives the
shared block's contents via one on-device copy program (traced src/dst
scalars: forks never recompile), the table is repointed, and the share
is released.  This extends the block-0 sink invariant's discipline —
"nothing writes a block another stream can read" — to shared blocks,
asserted on every prefill chunk and decode step.  ``assert_drained``
extends to "all refcounts zero": after a drain every block is either
plain-free or cached-free, never referenced.
"""

from __future__ import annotations

import base64
import collections
import contextlib
import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import _quantize_kv, _sample
from ..models.transformer import Transformer, scaled
from ..ops.pallas_kernels import paged_attention, paged_tiles
from ..train import trace as trace_lib
from ..utils import compile_ledger as ledger_lib

Pytree = Any

# attention dispatch seam: 'gathered' materializes pool[table] and reduces
# over all max_blocks*block_size key positions per stream (the parity
# reference); 'fused' reads K/V straight from the block pool via the
# Pallas paged-attention kernel and stops at each stream's true length
# (ops.pallas_kernels.paged_attention — token-identical, pinned); 'auto'
# is 'fused' where a TPU runs a cache row the kernel takes (per-head K/V
# that fills the lanes, the latent row), else 'gathered' (resolve_attn_impl)
ATTN_IMPLS = ("auto", "gathered", "fused")

# cumulative expert-load counters of a model that routes without drops
# (models.moe.DroplessMoE), carried on the device beside the pools as one
# int32 vector and brought to the host with a finished stream's row (a copy
# taken by ``_finish``, folded by ``land``): choices that fell on held
# experts (all programs), the busiest held expert's count summed over
# programs and layers, held experts with at least one token summed over
# decode ticks and layers, decode ticks, and the first of these over prefill
# chunks alone with their count
EXPERT_COUNTERS = ("expert_assignments", "expert_tokens_max",
                   "experts_reached", "decode_ticks_counted",
                   "prefill_expert_assignments", "prefill_chunks_counted")

# cumulative attention counters of a model whose layers are of two kinds
# (window and full), carried like the expert counters: keys the decode
# ticks' attention had to read, by kind (sum over decoding streams of
# ``len`` x full layers, of ``min(len, window)`` x window layers), and the
# pool blocks those streams' visible keys sat in (table entries x layers)
ATTENTION_COUNTERS = ("full_keys", "window_keys", "full_blocks_held",
                      "window_blocks_held")

# cumulative counters of a model with recurrent state (a state-space mixer
# beside the attention, models/ssm.py), carried like the expert counters:
# state rows a decode tick updated (decoding streams x mixer layers), true
# prompt columns a prefill chunk ran through the chunked recurrence (columns
# x mixer layers), and the two counts of what was folded, under the names
# EXPERT_COUNTERS gives them (a program that carried both sets would count
# the same ticks and chunks in both)
SSM_COUNTERS = ("ssm_state_updates", "ssm_prefill_tokens",
                "decode_ticks_counted", "prefill_chunks_counted")

# block 0 is reserved: pad positions and frozen slots write (and gather)
# here, so a scatter never needs dynamic masking to be allocation-safe
SINK_BLOCK = 0


def prefill_bucket(width: int) -> int:
    """The pow2 bucket a prefill chunk of ``width`` tokens pads to
    (minimum 8) — the rule :meth:`PagedDecodeServer.prefill_step`
    compiles against, shared with ``serve.loadgen.prewarm`` so the
    warmed bucket set can never drift from the compiled set."""
    b = 8
    while b < width:
        b *= 2
    return b


class BlockExhausted(RuntimeError):
    """The pool cannot supply the next block for one or more streams;
    carries the starving request ids so a scheduler can pick a victim."""

    def __init__(self, rids: List[int]):
        super().__init__(f"KV block pool exhausted; streams needing a "
                         f"block: {rids}")
        self.rids = list(rids)


class BlockAllocator:
    """Refcounted free-list allocator over block ids ``1..num_blocks-1``
    (0 is the sink).  A block is in one of three states: **in use**
    (refcount >= 1 — several streams may share one block), **cached-free**
    (refcount 0 but still holding prefix-cache content: allocatable, kept
    in LRU order and evicted under pressure via ``on_cache_evict``), or
    **plain free**.  Leak-proof by construction: every id is in exactly
    one state, :meth:`release` of a block with no references raises (the
    double-free hard error — ALL frees route through this one path), and
    :meth:`assert_drained` pins every refcount at zero with the free
    balance equal to capacity after a drain (the fuzz invariant)."""

    def __init__(self, num_blocks: int,
                 on_cache_evict: Optional[Callable[[int], None]] = None):
        if num_blocks < 2:
            raise ValueError(f"num_blocks {num_blocks} < 2: block 0 is "
                             "the reserved sink, so a usable pool needs "
                             "at least one more")
        self.num_blocks = int(num_blocks)
        # pop from the tail -> ascending ids hand out first (stable tests)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        # cached-free: refcount 0, prefix content intact; insertion order
        # = release order, so popitem(last=False) is LRU eviction
        self._cached: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._cached_ids: set = set()   # blocks carrying a cache identity
        self._ref: Dict[int, int] = {}  # in-use refcounts (>= 1)
        self._on_cache_evict = on_cache_evict

    @property
    def capacity(self) -> int:
        """Usable blocks (the sink is not allocatable)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: plain free + cached-free (a cached block
        costs nothing to keep — it is reclaimed LRU-first on demand)."""
        return len(self._free) + len(self._cached)

    @property
    def used_blocks(self) -> int:
        return len(self._ref)

    @property
    def cached_free_blocks(self) -> int:
        return len(self._cached)

    @property
    def shared_extra(self) -> int:
        """Extra references across all shared blocks — the number of
        block allocations sharing is saving RIGHT NOW."""
        return sum(r - 1 for r in self._ref.values() if r > 1)

    def refcount(self, b: int) -> int:
        return self._ref.get(b, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh block ids at refcount 1, or None when the pool
        cannot satisfy the request (all-or-nothing: nothing is evicted
        or granted on refusal).  Plain-free blocks hand out first;
        beyond them, cached-free blocks are reclaimed LRU-first, their
        index entries invalidated via ``on_cache_evict``."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > self.free_blocks:
            return None
        out = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b, _ = self._cached.popitem(last=False)   # LRU victim
                self._cached_ids.discard(b)
                if self._on_cache_evict is not None:
                    self._on_cache_evict(b)
            self._ref[b] = 1
            out.append(b)
        return out

    def share(self, b: int) -> None:
        """One more reader of an in-use block (a cache-hit admission
        mapping its table onto an existing block)."""
        if b not in self._ref:
            raise ValueError(f"share of block {b} not in use")
        self._ref[b] += 1

    def reuse_cached(self, b: int) -> None:
        """Revive a specific cached-free block (refcount 0 -> 1) — a
        cache hit on content whose last reader already finished."""
        if b not in self._cached:
            raise ValueError(f"reuse_cached of block {b} not cached-free")
        del self._cached[b]
        self._ref[b] = 1

    def release(self, blocks: List[int]) -> None:
        """THE single release path: drop one reference per listed block.
        A block reaching refcount 0 returns to the free list — the
        cached-free LRU side when it carries prefix content, plain
        otherwise.  Releasing a block with no references is a hard error
        (double free of a shared block, foreign id, or the sink)."""
        for b in blocks:
            r = self._ref.get(b)
            if r is None:
                raise ValueError(f"release of block {b} not in use "
                                 "(double free or foreign id)")
            if r > 1:
                self._ref[b] = r - 1
            else:
                del self._ref[b]
                if b in self._cached_ids:
                    self._cached[b] = None      # MRU end of the LRU queue
                else:
                    self._free.append(b)

    def free(self, blocks: List[int]) -> None:
        """Alias of :meth:`release` kept for callers predating refcounts
        — every free routes through the one release path, so a double
        free of a shared block raises instead of silently re-pooling a
        block someone still reads."""
        self.release(blocks)

    def mark_cached(self, b: int) -> None:
        """Tag a block as carrying prefix-cache content: when its last
        reference drops it parks in the cached-free LRU instead of the
        plain free list."""
        self._cached_ids.add(b)

    def assert_drained(self) -> None:
        if self._ref:
            raise AssertionError(
                "block leak: refcounts not drained after quiesce: "
                f"{dict(sorted(self._ref.items()))}")
        if len(self._free) + len(self._cached) != self.capacity:
            raise AssertionError(
                f"free-list balance {len(self._free)} plain + "
                f"{len(self._cached)} cached != capacity {self.capacity}")


class PrefixIndex:
    """Host-side prefix-cache index: hash-chained token chunks at block
    granularity -> resident block id.  A key is ``(parent_key,
    tokens_tuple)`` — the EXACT token ids, so a hit can never be a hash
    collision, and nesting shares structure with the parent key (O(1)
    extra per entry).  Full prompt blocks chain with ``tokens_tuple`` of
    ``block_size`` ids; the final partial prompt block registers under
    the same scheme with a shorter tuple.  One identity per block, at
    most one block per key (first writer wins); entries are invalidated
    when the allocator reclaims their block."""

    def __init__(self):
        self._map: Dict[Tuple, int] = {}
        self._key_of: Dict[int, Tuple] = {}
        # bumped on every mutation: lookup results are pure functions of
        # (prompt, version), which is what lets the server memoize the
        # admission lookup (admit_need + try_admit + a blocked queue
        # head re-polling every tick would otherwise re-hash the whole
        # prompt each time)
        self.version = 0

    def __len__(self) -> int:
        return len(self._map)

    def get(self, key: Tuple) -> Optional[int]:
        return self._map.get(key)

    def insert(self, key: Tuple, block: int) -> bool:
        """Register ``block`` under ``key``; False when the key is
        already claimed (a concurrent identical prefill — first writer
        wins) or the block already carries another identity."""
        if key in self._map or block in self._key_of:
            return False
        self._map[key] = block
        self._key_of[block] = key
        self.version += 1
        return True

    def invalidate_block(self, block: int) -> None:
        key = self._key_of.pop(block, None)
        if key is not None and self._map.get(key) == block:
            del self._map[key]
            self.version += 1


def resolve_attn_impl(model: Transformer, attn_impl: str = "auto",
                      kv_quant: bool = False) -> str:
    """``'gathered'`` or ``'fused'`` for this model on this backend.  An
    explicit value is kept; ``auto`` takes the kernel where a TPU runs it
    on shapes it was made for: the pools are not int8 (that walk is one
    page a step and was never timed on the chip; the latent row has no int8
    scheme at all), and the cache row is either per-head K and V whose
    ``kv_heads * head_dim`` fills whole 128-lane tiles (a pool page is then
    one lane-dense DMA), or latent attention's one row, which is stored
    padded to whole lane tiles for the kernel (:func:`stored_rows`).  No
    width rule: the kernel beat ``gathered`` at every length timed (PERF.md
    section 6, PR 30 and PR 32)."""
    c = model.cfg
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"attn_impl must be one of {ATTN_IMPLS}, "
                         f"got {attn_impl!r}")
    if attn_impl != "auto":
        return attn_impl
    per_head = set(model.cache_row()) == {"k", "v"}
    kernel = (jax.default_backend() == "tpu" and not kv_quant
              and (not per_head or (c.kv_heads * c.head_dim) % 128 == 0))
    return "fused" if kernel else "gathered"


def stored_rows(row: Dict[str, Tuple[int, ...]],
                folded: bool) -> Dict[str, Tuple[int, ...]]:
    """Pool name -> the trailing shape a token's row is STORED in.  Not
    ``folded`` (the gathered path): the attention's own ``cache_row()``.
    ``folded`` (the fused kernel's layout) is flat and lane-dense, the
    shape a page is DMA'd in: per-head K and V with the heads folded into
    the lanes (the same bytes a block row), a row that is its own value
    (latent attention's) zero-padded up to whole 128-lane tiles, so that the
    default layout needs no padding the compiler would transpose the pool
    to avoid.  What leaves the server (export / import, the handoff
    geometry) is ``cache_row()`` wide whatever is stored."""
    if not folded:
        return dict(row)
    flat = {n: int(np.prod(r)) for n, r in row.items()}
    if set(row) == {"k", "v"}:
        return {n: (f,) for n, f in flat.items()}
    return {n: (-(-f // 128) * 128,) for n, f in flat.items()}


def layer_windows(model: Transformer) -> Tuple[Optional[int], ...]:
    """Each layer's window (None: a full layer), from the model's config."""
    c = model.cfg
    return tuple(c.layer_window(i) for i in range(c.n_layers))


def window_pool_blocks(window: int, slots: int, block_size: int,
                       prefill_chunk: int) -> int:
    """Blocks of a WINDOW layer's pool, the sink included.  A stream holds
    the pages its next query can still see (``window`` positions lie in at
    most ``ceil((window - 1) / bs) + 1`` pages) and, while one of its chunks
    is in flight, that chunk's pages; chunks are dispatched one at a time
    and trimmed behind the window as soon as they are.  Nothing here grows
    with ``max_len``."""
    steady = -(-(window - 1) // block_size) + 1
    return 1 + slots * steady + -(-prefill_chunk // block_size)


def init_paged_kv(model: Transformer, num_blocks: int, block_size: int,
                  quant: bool = False, folded: bool = False,
                  window_blocks: Optional[int] = None):
    """Per-layer paged pools, one per entry of the attention's cache row
    (``model.cache_row()``), each ``(num_blocks, block_size, *row)`` —
    :func:`models.generate.init_kv_cache` with the length axis split into
    (block, offset).  ``folded`` (the fused kernel's layout,
    :func:`stored_rows`) stores a per-head row with its heads folded into
    the lanes, ``(num_blocks, block_size, kv_heads * head_dim)``: the same
    bytes a block row, the shape the kernel DMAs; and the latent row padded
    with zero lanes to whole lane tiles, ``(num_blocks, block_size, 384)``
    for a row of 320.  A window layer's pool has ``window_blocks`` blocks
    (:func:`window_pool_blocks`) in place of ``num_blocks``.  ``quant=True``
    (per-head K and V only) stores int8
    codes plus one f32 scale per (block, offset, head), the identical
    scheme the dense cache uses (scales are per position, so paging cannot
    change the numbers)."""
    c = model.cfg
    row = model.cache_row()
    lead = (num_blocks, block_size)
    stored = stored_rows(row, folded)
    if quant:
        if set(row) != {"k", "v"}:
            raise ValueError(
                "kv_quant stores int8 codes of per-head K and V; the cache "
                f"row {sorted(row)} of this attention has no such scheme yet")
        return [{**{n: jnp.zeros(lead + r, jnp.int8)
                    for n, r in stored.items()},
                 **{f"{n}_scale": jnp.ones(lead + r[:-1], jnp.float32)
                    for n, r in row.items()}}
                for _ in range(c.n_layers)]
    windows = layer_windows(model)
    if any(windows) and window_blocks is None:
        raise ValueError("a model with window layers needs window_blocks "
                         "(window_pool_blocks)")
    return [{n: jnp.zeros(((window_blocks if w else num_blocks), block_size)
                          + r, c.compute_dtype)
             for n, r in stored.items()}
            for w in windows]


def init_paged_state(model: Transformer, slots: int):
    """The store that is NOT paged, beside the pools: per layer, one row a
    slot of every entry of the model's state row (``model.state_row()``:
    store name -> (shape, type); a mixer's convolution tail and its float32
    state), zeros.  ``[]`` for a model without recurrent state.  A row
    belongs to a slot for as long as a stream holds it: zeroed at admission,
    carried by prefill chunks and decode ticks, gone with the slot."""
    row = model.state_row()
    if not row:
        return []
    return [{n: jnp.zeros((slots,) + shape, dtype)
             for n, (shape, dtype) in row.items()}
            for _ in range(model.cfg.n_layers)]


def refuse_recurrent(model: Transformer, who: str, why: str) -> None:
    """What needs a snapshot of recurrent state that does not exist yet
    refuses a model that has such state, by name."""
    if model.cfg.has_mixer:
        raise ValueError(
            f"{who} cannot serve a model with recurrent state (a "
            f"state-space mixer beside the attention: ssm_heads) yet: "
            f"{why}")


@functools.lru_cache(maxsize=8)
def _boundary_programs(tag: str, temperature: float, top_k: int,
                       top_p: float, *server):
    """The three small programs of a request's boundary, beside the four of
    :func:`_paged_programs` and cached like them, a set a kind of server
    (``server``: the rest of that cache's key, so that a server's ledger
    events do not depend on which servers ran before it); none of them
    touches a pool.  ``slot`` is a traced scalar everywhere: one compile
    each.

    ``take``: a finished stream's row and the counters as of the program
    just dispatched, as buffers of their own: the next step donates
    ``tokens`` and ``stats``, and these are read on the host one program
    later (:meth:`PagedDecodeServer.land`)."""
    def take(tokens, stats, slot):
        return (jax.lax.dynamic_index_in_dim(tokens, slot, 0, keepdims=False),
                jax.tree_util.tree_map(jnp.copy, stats))

    def admit(tokens, pos, state, row, where):
        """An admission as one program: the slot's token row, its position
        ``at`` (0; an imported stream's prompt length), and under ``fresh``
        zeros in the slot's rows of the state store of a model that has one
        (``[]`` otherwise); ``where`` is ``[slot, at, fresh]``, one upload.
        All three arrays are donated and updated in place: no copy of the
        store."""
        slot, at, fresh = where[0], where[1], where[2] != 0
        tokens = jax.lax.dynamic_update_slice(tokens, row[None],
                                              (slot, jnp.zeros_like(slot)))
        pos = jax.lax.dynamic_update_slice(pos, at[None], (slot,))

        def reset(s):
            held = jax.lax.dynamic_slice_in_dim(s, slot, 1, 0)
            return jax.lax.dynamic_update_slice_in_dim(
                s, jnp.where(fresh, jnp.zeros_like(held), held), slot, 0)

        return tokens, pos, jax.tree_util.tree_map(reset, state)

    def first_token(logits, tokens, pos, key, where):
        """A prefill's last chunk hands over to decode as one program: the
        first token sampled from ``logits``, the ``(1, V)`` row the chunk
        program made at its last true column (whatever the chunk's bucket:
        one compile), written at ``(slot, at)``, the position set to ``at``;
        ``where`` is ``[slot, at]``."""
        slot, at = where[0], where[1]
        tok, key = _sample(logits, temperature, key, top_k, top_p)
        tokens = jax.lax.dynamic_update_slice(tokens, tok[:, None],
                                              (slot, at))
        pos = jax.lax.dynamic_update_slice(pos, at[None], (slot,))
        return tokens, pos, key

    return (ledger_lib.instrument(jax.jit(take), f"serve_take[{tag}]"),
            ledger_lib.instrument(jax.jit(admit, donate_argnums=(0, 1, 2)),
                                  f"serve_admit[{tag}]"),
            ledger_lib.instrument(
                jax.jit(first_token, donate_argnums=(1, 2)),
                f"serve_first_token[{tag}]"))


@functools.lru_cache(maxsize=8)
def _paged_programs(model: Transformer, block_size: int, max_blocks: int,
                    temperature: float, top_k: int, top_p: float,
                    kv_quant: bool = False, attn_impl: str = "gathered"):
    """The four jitted programs of a paged server: chunk prefill (one
    per power-of-two chunk bucket, via jit's shape cache), the batched
    decode step, the copy-on-write block copy (``serve_cow``), and the
    block-handoff import scatter (``serve_import`` — the CoW copy's
    sibling with the source row arriving from the host instead of
    another pool row).  Cached per (model, geometry, sampling,
    attn_impl) so several servers compile once.  ``attn_impl`` is
    resolved here, once (:func:`resolve_attn_impl`): ``'fused'`` swaps the
    gathered attention for the Pallas paged kernel over pools stored in the
    layout it reads (``init_paged_kv(folded=True)``): in both programs of a
    per-head row, in the decode program of the latent row (its prefill
    chunk gathers its one stream's rows from the same pools and keeps the
    expanded form); everything else (scatter coordinates, sampling,
    bookkeeping) is shared, which is what makes gathered-vs-fused an
    attention-only A/B."""
    bs, mb = int(block_size), int(max_blocks)
    t_cap = bs * mb
    c = model.cfg
    attn_impl = resolve_attn_impl(model, attn_impl, kv_quant)
    latent = c.attention_kind == "mla"
    if latent and kv_quant:
        raise ValueError(
            "kv_quant stores int8 codes of per-head K and V; latent "
            "attention's cache row has no such scheme yet")
    windows = layer_windows(model)
    win = next((w for w in windows if w), None)     # the model's one window
    two_kinds = win is not None     # window layers: a second table
    if two_kinds and kv_quant:
        raise ValueError("kv_quant cannot run a model with window layers "
                         "(attention_pattern 'L') yet: the int8 page walk "
                         "has no lower bound")
    recurrent = c.has_mixer     # a second store: one state row a slot
    if kv_quant:
        refuse_recurrent(model, "kv_quant", "int8 K and V beside a float32 "
                         "state was never compared with the reference")

    def kind_scope(window):
        """``attn_full`` / ``attn_window`` inside ``attn_core``, for a model
        whose layers are of several kinds (no scope of the other models'
        programs moves)."""
        if not c.has_layer_kinds:
            return contextlib.nullcontext()
        return jax.named_scope("attn_window" if window else "attn_full")

    def gathered_attention(q, kp, vp, tables, positions, ksp, vsp,
                           window=None):
        """Attention over each row's whole table width: ``pool[table]``
        materialised (scope ``paged_gather``), then a full-width masked
        scores-softmax-values reduction (scope ``attn_core``).  Same
        values, same order, as the dense cache's (B, T, kv, hd) slab.
        ``ksp``/``vsp`` are the int8 scale pools or None; ``window`` masks
        the keys behind it as well (their table entries may be the
        sink's)."""
        b, w = positions.shape
        with jax.named_scope("paged_gather"):
            # (B, MB, bs, kv, hd) -> (B, T_cap, kv, hd), positions in
            # ascending order
            gk = kp[tables].reshape(b, t_cap, c.kv_heads, c.head_dim)
            gv = vp[tables].reshape(b, t_cap, c.kv_heads, c.head_dim)
            if ksp is not None:
                gks = ksp[tables].reshape(b, t_cap, c.kv_heads)
                gvs = vsp[tables].reshape(b, t_cap, c.kv_heads)
        with jax.named_scope("attn_core"), kind_scope(window):
            scale = 1.0 / jnp.sqrt(jnp.asarray(c.head_dim, jnp.float32))
            mask = (jnp.arange(t_cap)[None, None, :]
                    <= positions[:, :, None])           # (B, W, T_cap)
            if window is not None:
                mask &= (jnp.arange(t_cap)[None, None, :]
                         > positions[:, :, None] - window)
            if c.kv_heads == c.n_heads:
                logits = jnp.einsum("bqhd,bkhd->bhqk",
                                    q.astype(jnp.float32),
                                    gk.astype(jnp.float32)) * scale
                if ksp is not None:
                    logits = logits * gks.transpose(0, 2, 1)[:, :, None, :]
                logits = jnp.where(mask[:, None], logits, -1e30)
                probs = jax.nn.softmax(logits, axis=-1)
                if ksp is not None:
                    probs = probs * gvs.transpose(0, 2, 1)[:, :, None, :]
                return jnp.einsum("bhqk,bkhd->bqhd", probs,
                                  gv.astype(jnp.float32))
            g = c.n_heads // c.kv_heads
            q5 = q.reshape(b, w, c.kv_heads, g, c.head_dim)
            logits = jnp.einsum("bqcgd,bkcd->bcgqk",
                                q5.astype(jnp.float32),
                                gk.astype(jnp.float32)) * scale
            if ksp is not None:
                logits = logits * gks.transpose(0, 2, 1)[:, :, None,
                                                         None, :]
            logits = jnp.where(mask[:, None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            if ksp is not None:
                probs = probs * gvs.transpose(0, 2, 1)[:, :, None,
                                                       None, :]
            out = jnp.einsum("bcgqk,bkcd->bqcgd", probs,
                             gv.astype(jnp.float32))
            return out.reshape(b, w, c.n_heads, c.head_dim)

    def scatter_coords(tables, positions, valid):
        """(block, offset) of every position of a chunk: each position
        resolves its own block via the row's table (chunks straddle block
        boundaries freely); pad columns land in the sink."""
        blk = jnp.take_along_axis(tables, positions // bs, axis=1)
        blk = jnp.where(valid[None, :], blk, SINK_BLOCK)
        off = jnp.where(valid[None, :], positions % bs, 0)
        return blk, off

    def dense_attention_half(mods, layer_params, pool, tables, starts, x,
                             valid, lengths, layer=0):
        """``x + Attn(LN(x))`` (and the normed input, for a mixer beside
        it) with per-head K and V rows: the fused qkv
        projection, K/V scattered into the pools, attention gathered or
        fused.  Mirrors ``models.generate._block_chunk`` (the pinned dense
        math) with the cache axis split into (block, offset).  ``layer``
        says which kind of layer this is where a model has several: whether
        q and k are rotated, and a window layer's lower bound (``tables``
        is then the window kind's)."""
        quant = "k_scale" in pool
        window = windows[layer]
        # named scopes as in ``Transformer._block`` (the device trace is
        # read by them): ``attention`` is the work, the inner scopes say
        # what implements it here
        with jax.named_scope("attn_proj"):
            h = mods["ln1"].apply(layer_params["ln1"], x)
            # q: (B,W,H,hd); k/v: (B,W,KV,hd)
            q, k, v = model.scaled_qkv(mods, layer_params, h)
            b, w = q.shape[:2]
            if c.qk_norm:
                q, k = model.qk_normed(mods, layer_params, q, k)
        with jax.named_scope("attention"):
            positions = starts[:, None] + jnp.arange(w)[None, :]  # (B, W)
            if c.layer_rotary(layer):
                from ..ops.rope import rope_rotate

                q = rope_rotate(q, positions, c.rope_theta)
                k = rope_rotate(k, positions, c.rope_theta)
            with jax.named_scope("paged_scatter"):
                blk, off = scatter_coords(tables, positions, valid)
                if quant:
                    k, ks = _quantize_kv(k)
                    v, vs = _quantize_kv(v)
                    new_ksp = pool["k_scale"].at[blk, off].set(ks)
                    new_vsp = pool["v_scale"].at[blk, off].set(vs)
                # a token's row in the pool's own trailing shape: (KV, hd),
                # or KV*hd under the fused kernel's folded layout
                row = pool["k"].shape[2:]
                new_kp = pool["k"].at[blk, off].set(
                    k.astype(pool["k"].dtype).reshape(b, w, *row))
                new_vp = pool["v"].at[blk, off].set(
                    v.astype(pool["v"].dtype).reshape(b, w, *row))
            if attn_impl == "fused":
                # the Pallas kernel reads K/V straight from the pool
                # through the tables and reduces over each row's TRUE
                # length — no pool[table] materialization, no
                # max_blocks*bs reduction.  int8 scale pools ride in and
                # dequantize on load.
                pages, cols = paged_tiles(
                    bs, c.kv_heads * c.head_dim, w, c.n_heads // c.kv_heads,
                    mb, quant=quant, window=window)
                ledger_lib.note("attention", {
                    "impl": "paged", "pages": pages, "tile_cols": cols,
                    "block_size": bs})
                with jax.named_scope("attn_core"), kind_scope(window), \
                        jax.named_scope("paged_attention_fused"):
                    out = paged_attention(
                        q, new_kp, new_vp, tables, lengths, starts,
                        k_scale=new_ksp if quant else None,
                        v_scale=new_vsp if quant else None,
                        pages=pages, tile_cols=cols,
                        window=window).astype(x.dtype)
            else:
                ledger_lib.note("attention", {"impl": "gathered",
                                              "keys": t_cap})
                out = gathered_attention(
                    q, new_kp, new_vp, tables, positions,
                    new_ksp if quant else None,
                    new_vsp if quant else None, window).astype(x.dtype)
        with jax.named_scope("attn_proj"):
            out = out.reshape(b, w, c.q_dim)
            x = x + scaled(
                mods["attn_out"].apply(layer_params["attn_out"], out),
                c.attention_out_multiplier)
        new_pool = {"k": new_kp, "v": new_vp}
        if quant:
            new_pool.update(k_scale=new_ksp, v_scale=new_vsp)
        return x, new_pool, h

    def latent_attention_half(mods, layer_params, pool, tables, starts, x,
                              valid, lengths, decode):
        """``x + LatentAttn(norm(x))`` with the latent row: the chunk's rows
        ``[c_kv | k_rope]`` (normed, rotated) scattered into the one pool and
        attended in the expanded form (prefill: the stream's rows gathered
        through its table and expanded through ``W_kvb``; ``lengths`` bounds
        the keys a chunk walks) or the absorbed form (decode: the cache is
        never expanded).  Under ``fused`` the decode reads the pool in place:
        the paged kernel's shared-row mode walks each stream's pages up to
        its length between the two ``mla_absorb`` products, over a pool whose
        rows are stored padded to whole lane tiles (zero lanes, which a
        zero-padded query adds nothing for); nothing of width ``T_cap`` is
        gathered or reduced over."""
        attn, ap = mods["attn"], layer_params["attn"]
        b, w, _ = x.shape
        positions = starts[:, None] + jnp.arange(w)[None, :]      # (B, W)
        lanes = pool["latent"].shape[-1]        # as stored
        extra = lanes - attn.row_dim            # zero lanes of a stored row

        def widen(a):
            """``a``'s last axis from the row's width to the stored one."""
            return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, extra)]) \
                if extra else a

        with jax.named_scope("attn_proj"):
            h = mods["ln1"].apply(layer_params["ln1"], x)
            q_nope, q_rope, rows = attn.project(ap, h, positions)
        with jax.named_scope("attention"):
            with jax.named_scope("paged_scatter"):
                blk, off = scatter_coords(tables, positions, valid)
                new_lp = pool["latent"].at[blk, off].set(
                    widen(rows.astype(pool["latent"].dtype)))
            if decode and attn_impl == "fused":
                pages, cols = paged_tiles(bs, lanes, w, c.n_heads, mb)
                ledger_lib.note("attention", {
                    "impl": "paged", "pages": pages, "tile_cols": cols,
                    "block_size": bs})
                cdt = attn.compute_dtype
                w_k, w_v = attn.absorb_weights(ap)
                q_lat = attn.absorb_query(w_k, q_nope)
                with jax.named_scope("attn_core"), \
                        jax.named_scope("paged_attention_fused"):
                    q_row = widen(jnp.concatenate(
                        [q_lat.astype(cdt), q_rope.astype(cdt)], axis=-1))
                    u = paged_attention(
                        q_row, new_lp, None, tables, lengths, starts,
                        v_lanes=attn.kv_lora_rank, scale=attn.softmax_scale,
                        pages=pages, tile_cols=cols)
                out = attn.absorb_value(w_v, u)
            else:
                ledger_lib.note("attention", {"impl": "gathered",
                                              "keys": t_cap})
                with jax.named_scope("paged_gather"):
                    got = new_lp[tables].reshape(b, t_cap, lanes)
                    if extra:
                        got = got[..., :attn.row_dim]
                with jax.named_scope("attn_core"):
                    mask = (jnp.arange(t_cap)[None, None, :]
                            <= positions[:, :, None])       # (B, W, T_cap)
                    if decode:
                        out = attn.attend_absorbed(ap, q_nope, q_rope, got,
                                                   mask)
                    else:
                        # a chunk sees no key past its own last position:
                        # the expanded form walks the keys that exist
                        out = attn.attend_expanded(ap, q_nope, q_rope, got,
                                                   mask,
                                                   n_keys=lengths.max())
        with jax.named_scope("attn_proj"):
            x = x + attn.output(ap, out).astype(x.dtype)
        return x, {"latent": new_lp}

    def block_fwd(layer_params, pool, tables, starts, x, valid, lengths,
                  decode, layer=0, state=None):
        """Transformer block ``layer`` over a chunk ``x`` (B, W, D) whose rows
        sit at per-row start positions: the attention half of the model's
        kind writes the chunk's cache rows into the paged pool and reads
        the streams' rows back through the block tables, then the
        feed-forward half.  ``valid`` (W,) masks pad columns of a bucketed
        prefill chunk: their writes divert to the sink block.  ``lengths``
        (B,) is each row's attendable-key count (0 = inactive lane), traced
        like the tables so length churn never recompiles.  ``decode``
        (static) picks latent attention's absorbed form.  Returns (x, the
        new pool, the held experts' load (count,) int32 under routing
        without drops, else None, the new state rows).  Where the model
        has window layers ``tables`` is the pair (full kind's, window
        kind's), and the layer takes its own.  Where it has a mixer beside
        the attention, ``state`` holds this layer's state rows of the
        chunk's B streams: the mixer reads the attention's normed input,
        its output is added to the same residual, and it writes to the
        second store what the attention writes to its pages: a chunk carries
        the state over its true columns, a tick over its decoding lanes
        (``lengths > 0``), everything else holds."""
        mods = model._block_modules(layer)
        if two_kinds:
            tables = tables[1 if windows[layer] else 0]
        if latent:
            x, new_pool = latent_attention_half(
                mods, layer_params, pool, tables, starts, x, valid, lengths,
                decode)
        else:
            x, new_pool, h = dense_attention_half(
                mods, layer_params, pool, tables, starts, x, valid, lengths,
                layer)
        if recurrent:
            if decode:
                run = lambda mixer, p, u: mixer.apply_step(     # noqa: E731
                    p, u, state, lengths > 0)
            else:
                run = lambda mixer, p, u: mixer.apply_chunk(    # noqa: E731
                    p, u, state, valid)
            mix, state = model.mixer_half(mods, layer_params, h, run)
            x = x + mix
        load = None
        with jax.named_scope("ffn"):
            h = mods["ln2"].apply(layer_params["ln2"], x)
            if "moe" not in mods:
                ff = model._ffn(mods, layer_params, h)
            elif c.moe_dropless:
                # pad columns and idle lanes reach no expert (and read
                # none): the load counts what the traffic asked for
                live = valid[None, :] & (lengths > 0)[:, None]
                ff, _, load = mods["moe"].apply(
                    layer_params["moe"], h, mask=live, return_load=True)
            else:
                ff, _ = mods["moe"].apply(layer_params["moe"], h)
            x = x + ff.astype(x.dtype)
        return x, new_pool, load, state

    def forward(params, pools, tables, starts, ids, valid, lengths,
                decode, state=None):
        # clamp pad columns' embedding positions into range (their
        # outputs are discarded; learned positional tables have no row
        # past max_seq_len)
        w = ids.shape[1]
        emb_pos = jnp.minimum(starts[:, None] + jnp.arange(w)[None, :],
                              c.max_seq_len - 1)
        x = model.embed(params, ids, emb_pos)
        if two_kinds and attn_impl == "fused":
            # the first note of a program wins: name both kinds' walks
            lanes, groups = c.kv_heads * c.head_dim, c.n_heads // c.kv_heads
            fp, fc = paged_tiles(bs, lanes, w, groups, mb)
            wp, wc = paged_tiles(bs, lanes, w, groups, mb, window=win)
            ledger_lib.note("attention", {
                "impl": "paged", "pages": fp, "tile_cols": fc,
                "block_size": bs,
                "window": {"impl": "paged", "window": win, "pages": wp,
                           "tile_cols": wc}})
        new_pools, loads, new_state = [], [], []
        for i, (layer_params, pool) in enumerate(zip(params["blocks"],
                                                     pools)):
            x, pool, load, rows = block_fwd(
                layer_params, pool, tables, starts, x, valid, lengths,
                decode, i, state[i] if recurrent else None)
            new_pools.append(pool)
            loads.append(load)
            new_state.append(rows)
        # the last block's hidden state: each program applies the head to
        # the columns it reads
        return x, new_pools, loads, new_state

    def count_load(stats, loads, decode):
        """Fold one program's expert loads (a (count,) int32 per layer)
        into the cumulative counters the server carries on the device
        (``EXPERT_COUNTERS``); ``stats`` is ``{}`` for a model without
        routing without drops, and stays so."""
        if "experts" not in stats:
            return stats
        # (L, count); a leading dense layer of such a model has no load
        load = jnp.stack([ld for ld in loads if ld is not None])
        assigned, busiest = load.sum(), load.max(axis=1).sum()
        zero, one = jnp.zeros((), jnp.int32), jnp.ones((), jnp.int32)
        if decode:
            step = [assigned, busiest, (load > 0).sum(), one, zero, zero]
        else:
            step = [assigned, busiest, zero, zero, assigned, one]
        return {**stats, "experts": stats["experts"]
                + jnp.stack(step).astype(jnp.int32)}

    def count_keys(stats, lengths):
        """Fold one decode tick's attention into ``ATTENTION_COUNTERS``
        (a model with window layers; nothing otherwise): ``lengths`` (S,)
        are the decoding streams' key counts, 0 for a lane that idles."""
        if "attention" not in stats:
            return stats
        n_win = sum(1 for w_ in windows if w_)
        n_full = len(windows) - n_win
        seen = jnp.minimum(lengths, win)
        last = jnp.maximum(lengths - 1, 0) // bs
        first = (lengths - seen) // bs      # page of the oldest visible key
        step = [n_full * lengths.sum(), n_win * seen.sum(),
                n_full * jnp.where(lengths > 0, last + 1, 0).sum(),
                n_win * jnp.where(lengths > 0, last - first + 1, 0).sum()]
        return {**stats, "attention": stats["attention"]
                + jnp.stack(step).astype(jnp.int32)}

    def count_state(stats, work, decode):
        """Fold one program's work at the mixers into ``SSM_COUNTERS`` (a
        model with recurrent state; nothing otherwise): ``work`` is the
        tick's decoding streams or the chunk's true columns."""
        if "ssm" not in stats:
            return stats
        work = work.astype(jnp.int32) * c.n_layers
        zero, one = jnp.zeros((), jnp.int32), jnp.ones((), jnp.int32)
        step = [work, zero, one, zero] if decode else [zero, work, zero, one]
        return {**stats, "ssm": stats["ssm"] + jnp.stack(step)}

    # ``state`` and ``slot``: the second store of a model with recurrent
    # state and the chunk's stream's place in it (None otherwise)
    def prefill(params, pools, state, stats, table, slot, start, chunk,
                true_w, last):
        # chunk (1, W_bucket) int32; the (1, V) float32 logits of the last
        # true column return, and only under ``last`` (the chunk is its
        # prompt's last: the one column a first token is sampled from);
        # any other chunk runs no head and returns zeros.  ``last`` is a
        # traced scalar: one program a bucket.  attendable keys after
        # this chunk's writes: everything up to start + true_w (pad
        # columns wrote to the sink, which is past every length)
        valid = jnp.arange(chunk.shape[1]) < true_w
        # the stream's rows of the second store, carried over the chunk
        rows = jax.tree_util.tree_map(
            lambda s: jax.lax.dynamic_slice_in_dim(s, slot, 1, 0),
            state) if recurrent else None
        x, new_pools, loads, rows = forward(
            params, pools, table, start, chunk, valid, start + true_w, False,
            rows)
        logits = jax.lax.cond(
            last,
            lambda h: model.head_logits(params, h)[:, 0],
            lambda h: jnp.zeros((1, c.vocab_size), jnp.float32),
            jax.lax.dynamic_slice_in_dim(x, true_w - 1, 1, 1))
        if recurrent:
            state = jax.tree_util.tree_map(
                lambda s, r: jax.lax.dynamic_update_slice_in_dim(
                    s, r, slot, 0), state, rows)
        return logits, new_pools, state, count_state(
            count_load(stats, loads, False), true_w, False)

    def step(params, pools, state, stats, tokens, tables, pos, active, key):
        s = tokens.shape[0]
        cap = tokens.shape[1] - 1
        ids = jnp.take_along_axis(tokens, pos[:, None], axis=1)  # (S, 1)
        # a decode row attends its own fresh write too: pos + 1 keys;
        # inactive lanes carry length 0, so the fused kernel walks ZERO
        # of their blocks (the gathered path computes-and-discards them)
        lengths = jnp.where(active, pos + 1, 0)
        x, new_pools, loads, state = forward(
            params, pools, tables, pos, ids, jnp.ones((1,), bool), lengths,
            True, state)
        logits = model.head_logits(params, x)
        with jax.named_scope("sample"):
            nxt, key = _sample(logits[:, 0], temperature, key, top_k,
                               top_p)
        # frozen slots re-write the token already there (idempotent) and
        # hold position — the dense server's exact bookkeeping
        nxt = jnp.where(active, nxt, jnp.take_along_axis(
            tokens, jnp.minimum(pos + 1, cap)[:, None], axis=1)[:, 0])
        write_at = jnp.minimum(pos + 1, cap)
        tokens = tokens.at[jnp.arange(s), write_at].set(nxt)
        pos = jnp.where(active, jnp.minimum(pos + 1, cap), pos)
        # the counters come last: the dense programs' results keep their
        # places (and their compile-cache keys)
        stats = count_state(
            count_keys(count_load(stats, loads, True), lengths),
            active.sum(), True)
        return new_pools, state, tokens, pos, key, stats

    # a model with recurrent state hands its second store through both
    # programs (donated: the rows are updated in place); every other model's
    # programs take and return what they did, and lower to the text they had
    donate_prefill, donate_step = (1, 2, 3), (1, 2, 3, 4, 6)
    if not recurrent:
        with_state = (prefill, step)

        def prefill(params, pools, stats, table, start, chunk, true_w,
                    last):
            logits, new_pools, _, stats = with_state[0](
                params, pools, None, stats, table, None, start, chunk,
                true_w, last)
            return logits, new_pools, stats

        def step(params, pools, stats, tokens, tables, pos, active, key):
            new_pools, _, tokens, pos, key, stats = with_state[1](
                params, pools, None, stats, tokens, tables, pos, active, key)
            return new_pools, tokens, pos, key, stats

        donate_prefill, donate_step = (1, 2), (1, 2, 3, 5)

    def cow(pools, src, dst):
        """Copy-on-write fork: duplicate block row ``src`` into the
        stream-owned ``dst`` across every layer's pool tensors (K, V and
        the int8 scale pools alike).  ``src``/``dst`` are TRACED scalars,
        so fork churn reuses one compiled program — the same discipline
        that keeps table churn recompile-free.  The whole block row
        copies (positions past the shared prefix are overwritten by the
        forking stream's own writes before they are ever attended)."""
        return jax.tree_util.tree_map(
            lambda p: p.at[dst].set(p[src]), pools)

    def imp(pools, rows, dst):
        """Block-handoff import: scatter one block row of host-supplied
        K/V content (``rows`` — a pytree matching one pool block row per
        layer, int8 scale pools included) into pool row ``dst``.  Like
        ``cow``, ``dst`` is a TRACED scalar, so importing N blocks
        reuses one compiled program no matter which pool rows the
        allocator handed out."""
        return jax.tree_util.tree_map(
            lambda p, r: p.at[dst].set(r.astype(p.dtype)), pools, rows)

    # compile-ledger seam (utils/compile_ledger): while a ledger is
    # installed every distinct compile of the serve programs is recorded
    # — which is how the "block-table churn never recompiles" invariant
    # becomes a production assertion instead of a test-only cache count
    # (tables/lengths are traced args; only a NEW prefill bucket width
    # may legitimately add an entry).  Cache-hit admissions, CoW forks
    # and shared-block evictions ride the same contract: src/dst/table
    # values are runtime data, so the ledger stays flat.
    tag = (f"bs{bs}x{mb}" + ("/int8" if kv_quant else "")
           + f"/{attn_impl}")
    return (ledger_lib.instrument(
                jax.jit(prefill, donate_argnums=donate_prefill),
                f"serve_prefill[{tag}]"),
            ledger_lib.instrument(jax.jit(step, donate_argnums=donate_step),
                                  f"serve_decode[{tag}]"),
            ledger_lib.instrument(jax.jit(cow, donate_argnums=(0,)),
                                  f"serve_cow[{tag}]"),
            ledger_lib.instrument(jax.jit(imp, donate_argnums=(0,)),
                                  f"serve_import[{tag}]"))


@dataclass
class _Stream:
    """Host bookkeeping for one in-flight request."""
    rid: int
    prompt: List[int]
    max_new: int
    target: int                       # prompt_len + max_new
    blocks: List[int] = field(default_factory=list)
    prefilled: int = 0                # prompt tokens written so far
    # prefix-cache state: the leading n_shared table entries are BORROWED
    # (read-only — owned by the index/another stream); fork_pending is
    # the block reserved at admission for the copy-on-write fork of a
    # borrowed PARTIAL tail (None when the match ended on a block
    # boundary); chain_key/registered_tokens track how far this stream's
    # own prompt blocks have been registered into the prefix index
    n_shared: int = 0
    fork_pending: Optional[int] = None
    chain_key: Any = None
    registered_tokens: int = 0
    shared_at_admit: int = 0          # matched prefix tokens (stats)
    # a model with window layers: the window kind's blocks, by the page of
    # the stream they hold (only pages the next query can still see, and
    # the chunk in flight)
    window_pages: Dict[int, int] = field(default_factory=dict)


@dataclass
class _Row:
    """A finished stream's row on its way to the host (taken, not landed)."""
    rid: int
    target: int
    row: Any                          # device (t_cap,) int32, its own buffer
    stats: Any                        # the counters' copy taken with it
    after: int                        # model programs dispatched by then


class PagedDecodeServer:
    """Slot server over a paged KV pool: same host contract as the dense
    ``DecodeServer`` (submit/step/done/result), plus the paged-runtime
    surface a scheduler drives — partial (chunked) prefill, on-demand
    block growth, eviction, and free-block/slot introspection."""

    def __init__(self, model: Transformer, params: Pytree, *,
                 slots: int = 8, num_blocks: int = 64,
                 block_size: int = 16, max_len: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0,
                 kv_quant: bool = False, attn_impl: str = "auto",
                 prefix_cache: bool = False, prefill_chunk: int = 32):
        c = model.cfg
        self.model, self.params = model, params
        self.slots = int(slots)
        self.block_size = int(block_size)
        self.max_len = int(max_len or c.max_seq_len)
        if self.max_len > c.max_seq_len:
            raise ValueError(f"max_len {self.max_len} exceeds model "
                             f"max_seq_len {c.max_seq_len}")
        self.max_blocks = -(-self.max_len // self.block_size)   # ceil
        self.t_cap = self.max_blocks * self.block_size
        self.num_blocks = int(num_blocks)
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache:
            refuse_recurrent(model, "prefix_cache", "a shared prefix's "
                             "blocks hold its keys and values, and no "
                             "snapshot of the state at its end")
        self.prefix = PrefixIndex()
        self.allocator = BlockAllocator(
            self.num_blocks,
            on_cache_evict=self._on_cache_evict if self.prefix_cache
            else None)
        # prefix-cache counters (host arithmetic; the scheduler folds
        # them into kind="serve" telemetry records)
        self.prefix_hits = 0          # admissions with matched_len > 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0    # prompt tokens served from cache
        self.prompt_tokens_admitted = 0
        self.cow_forks = 0            # copy-on-write block forks
        self.cache_evictions = 0      # cached-free blocks reclaimed (LRU)
        self.blocks_shared_total = 0  # cumulative matched blocks at admit
        # disaggregated-handoff counters (export happens on the prefill
        # role, import on the decode role)
        self.handoffs_exported = 0
        self.handoffs_imported = 0
        self._lookup_memo = None      # (prompt, index-version) -> walk
        self._sampling = (float(temperature), int(top_k), float(top_p))
        self.kv_quant = bool(kv_quant)
        # what runs, never 'auto': the programs and the pools' layout
        # follow it
        self.attn_impl = resolve_attn_impl(model, attn_impl, self.kv_quant)
        (self._prefill_fn, self._step_fn, self._cow_fn,
         self._import_fn) = _paged_programs(
            model, self.block_size, self.max_blocks, *self._sampling,
            self.kv_quant, self.attn_impl)
        self._take_fn, self._admit_fn, self._first_fn = _boundary_programs(
            f"bs{self.block_size}x{self.max_blocks}", *self._sampling, model,
            self.block_size, self.max_blocks, self.kv_quant, self.attn_impl)
        # two kinds of cache in one manager: a full layer's pool is what it
        # was (``num_blocks``, one table a stream, grown on demand); a
        # window layer's holds, for each stream, the pages its next query
        # can still see and the chunk in flight, through an allocator and
        # a table of its own.  Its size follows from the slots, the block
        # size, the window and the widest chunk (``prefill_chunk``:
        # ``prefill_step`` takes no wider one), never from ``max_len``, and
        # by that count it can never be what refuses or evicts a stream.
        self.window = next((w for w in layer_windows(model) if w), None)
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.window_allocator: Optional[BlockAllocator] = None
        window_blocks = None
        if self.window is not None:
            if self.prefix_cache:
                raise ValueError(
                    "prefix_cache cannot serve a model with window layers "
                    "(attention_pattern 'L') yet: a window layer keeps no "
                    "block of a prefix to share")
            window_blocks = window_pool_blocks(
                self.window, self.slots, self.block_size,
                self.prefill_chunk)
            self.window_allocator = BlockAllocator(window_blocks)
            self.window_tables = np.zeros((self.slots, self.max_blocks),
                                          np.int32)
        self.pools = init_paged_kv(model, self.num_blocks,
                                   self.block_size, quant=self.kv_quant,
                                   folded=self.attn_impl == "fused",
                                   window_blocks=window_blocks)
        # state that is not paged, in the same manager: a model with a
        # mixer beside its attention keeps, per layer, one row a SLOT (the
        # convolution's tail and the float32 state), zeroed on the device
        # when a stream is admitted to the slot, carried by its prefill
        # chunks and decode ticks, and gone with the slot.  [] otherwise.
        self.state = init_paged_state(model, self.slots)
        self.state_rows: Dict[int, int] = {}    # slot -> the rid it holds
        # expert-load counters (EXPERT_COUNTERS): cumulative on the device
        # modulo 2**32, folded into host integers at every fetch; {} for a
        # model that does not route without drops
        self.stats = ({"experts": jnp.zeros((len(EXPERT_COUNTERS),),
                                            jnp.int32)}
                      if c.moe_dropless else {})
        self.expert_counters: Dict[str, int] = (
            dict.fromkeys(EXPERT_COUNTERS, 0) if c.moe_dropless else {})
        # attention counters by kind of layer (ATTENTION_COUNTERS), carried
        # and folded the same way; {} for a model without window layers
        self.attention_counters: Dict[str, int] = {}
        if self.window is not None:
            self.stats["attention"] = jnp.zeros(
                (len(ATTENTION_COUNTERS),), jnp.int32)
            self.attention_counters = dict.fromkeys(ATTENTION_COUNTERS, 0)
        # the mixers' counters (SSM_COUNTERS), the same way
        self.ssm_counters: Dict[str, int] = {}
        if self.state:
            self.stats["ssm"] = jnp.zeros((len(SSM_COUNTERS),), jnp.int32)
            self.ssm_counters = dict.fromkeys(SSM_COUNTERS, 0)
        self._stats_seen = {k: np.zeros(v.shape, np.int64)
                            for k, v in self.stats.items()}
        self.tokens = jnp.zeros((self.slots, self.t_cap), jnp.int32)
        self.pos = jnp.zeros((self.slots,), jnp.int32)
        self.tables = np.zeros((self.slots, self.max_blocks), np.int32)
        self.active = np.zeros((self.slots,), bool)     # decoding slots
        self._pos_host = np.zeros((self.slots,), np.int64)
        self.key = jax.random.PRNGKey(seed)
        self._rid = 0
        self._streams: Dict[int, _Stream] = {}
        self._slot_of: Dict[int, int] = {}
        self._results: Dict[int, List[int]] = {}
        # finished streams' rows between their take and their landing
        # (:meth:`land`), and the count of model programs dispatched (chunks
        # and steps) that says whether one is queued behind a row
        self._in_flight: List[_Row] = []
        self._programs = 0
        self._programs_at_land = 0
        self.rows_landed = 0          # rows brought to the host
        self.rows_landed_behind = 0   # ... with a later program queued
        self.prefill_chunks = 0       # chunk programs dispatched
        self.prefill_heads = 0        # ... whose head ran (a prompt's last)
        if c.scan_layers:
            params = dict(params)
            stacked = params["blocks"]
            params["blocks"] = [
                jax.tree_util.tree_map(lambda x, i=i: x[i], stacked)
                for i in range(c.n_layers)]
            self.params = params

    # ---- geometry ------------------------------------------------------
    def blocks_for(self, length: int) -> int:
        """Blocks needed to hold ``length`` cache positions."""
        return -(-int(length) // self.block_size)

    def free_slots(self) -> int:
        return self.slots - len(self._slot_of)

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    def block_utilization(self) -> float:
        cap = self.allocator.capacity
        return self.allocator.used_blocks / cap if cap else 0.0

    def assert_drained(self) -> None:
        """Every block of every kind back in its allocator, and no state
        row held by a stream."""
        self.allocator.assert_drained()
        if self.window_allocator is not None:
            self.window_allocator.assert_drained()
        if self.state_rows:
            raise AssertionError(
                "state leak: rows of the recurrent state still held after "
                f"quiesce (slot -> rid): {dict(sorted(self.state_rows.items()))}")

    # ---- the window kind's blocks ---------------------------------------
    def _window_span(self, st: _Stream, slot: int, first: int,
                     last: int) -> None:
        """Make the stream's window-kind table hold exactly the pages of
        positions ``first - window + 1 .. last``: what queries at ``first
        .. last`` can see and will write.  Pages behind are released (their
        table entries back to the sink) BEFORE the new ones are taken, so a
        decoding stream never holds more than the window's pages, and the
        pool's size (:func:`window_pool_blocks`) always covers the ask."""
        lo = max(first - self.window + 1, 0) // self.block_size
        hi = last // self.block_size
        held = st.window_pages          # always a run of neighbouring pages
        if len(held) == hi - lo + 1 and lo in held and hi in held:
            return                      # most decode ticks: nothing moves
        behind = [pg for pg in st.window_pages if pg < lo]
        if behind:
            self.window_allocator.release(
                [st.window_pages.pop(pg) for pg in behind])
            self.window_tables[slot, behind] = SINK_BLOCK
        fresh = [pg for pg in range(lo, hi + 1)
                 if pg not in st.window_pages]
        if fresh:
            got = self.window_allocator.alloc(len(fresh))
            assert got is not None, (
                "the window pool is sized for every slot's window and one "
                "chunk in flight; a chunk wider than prefill_chunk?")
            st.window_pages.update(zip(fresh, got))
            self.window_tables[slot, fresh] = got

    def _device_tables(self, rows, keep=None):
        """The block tables of ``rows`` (a slice or all slots) as the
        programs take them: one array, or the pair (full kind's, window
        kind's) for a model with window layers.  ``keep`` (slots,) bool
        masks the other lanes' rows to the sink.  HOST-side copies: see
        :meth:`prefill_step`."""
        def one(t):
            t = t[rows]
            return jnp.asarray(t.copy() if keep is None else
                               np.where(keep[:, None], t, SINK_BLOCK))
        if self.window is None:
            return one(self.tables)
        return one(self.tables), one(self.window_tables)

    def keys_accounting(self) -> Dict[str, int]:
        """Key-position accounting for the NEXT decode step, from host
        state (no device traffic): ``attended_keys`` is what the math
        needs (sum of pos+1 over active lanes), ``kernel_keys`` is what
        the fused kernel touches (whole blocks: ceil((pos+1)/bs)·bs per
        lane), ``padded_keys`` is what the gathered path reduces over
        (t_cap per active lane; ``serve_tokens_per_s`` is counted from it
        whatever implements attention), ``walked_keys`` is whichever of
        the two this server's implementation reads.  attended/padded is
        the measurable skipped-work ratio the telemetry reports;
        walked/padded says the mechanism engaged."""
        att = kern = n_active = 0
        for rid, slot in self._slot_of.items():
            if not self.active[slot]:
                continue
            ln = int(self._pos_host[slot]) + 1
            att += ln
            kern += -(-ln // self.block_size) * self.block_size
            n_active += 1
        padded = n_active * self.t_cap
        return {"attended_keys": att,
                "kernel_keys": kern,
                "padded_keys": padded,
                # what THIS server's attention reads: whole pages up to
                # each length under the kernel, the table's width gathered
                "walked_keys": kern if self.attn_impl == "fused" else padded,
                "active_streams": n_active}

    # ---- prefix cache --------------------------------------------------
    def _on_cache_evict(self, block: int) -> None:
        """Allocator callback: a cached-free block is being reclaimed
        for fresh use — its prefix identity must die with it."""
        self.prefix.invalidate_block(block)
        self.cache_evictions += 1

    def _prefix_lookup(self, prompt_ids: List[int]
                       ) -> Tuple[List[Tuple[int, int]], Any, int]:
        """Longest prefix match of ``prompt_ids`` against the index:
        returns ``(entries, chain_key, matched_len)`` where ``entries``
        is ``[(block, used_tokens), ...]`` (all full ``block_size``
        chunks except possibly a final partial), ``chain_key`` is the
        index key after the FULL matches (the new stream's registration
        resumes there), and ``matched_len <= len(prompt) - 1`` — the
        last prompt token is always left to prefill so its logits can
        seed the first sampled token (the vLLM full-hit rule).

        Memoized on ``(prompt, index version)``: the scheduler's
        ``admit_need`` pre-check, the ``try_admit`` that follows it in
        the same tick, and a queue head re-polled across ticks while
        blocked all reuse one walk instead of re-hashing the prompt.
        Refcount churn cannot stale the cache — it changes how a matched
        block is PINNED (share vs reuse), which both callers read live,
        never which blocks match."""
        key = (tuple(prompt_ids), self.prefix.version)
        if self._lookup_memo is not None and self._lookup_memo[0] == key:
            return self._lookup_memo[1]
        out = self._prefix_walk(prompt_ids)
        self._lookup_memo = (key, out)
        return out

    def _prefix_walk(self, prompt_ids: List[int]
                     ) -> Tuple[List[Tuple[int, int]], Any, int]:
        p = len(prompt_ids)
        cap = p - 1             # never match the final prompt token
        bs = self.block_size
        entries: List[Tuple[int, int]] = []
        chain: Any = None
        off = 0
        while off + bs <= cap:
            key = (chain, tuple(prompt_ids[off:off + bs]))
            b = self.prefix.get(key)
            if b is None:
                break
            entries.append((b, bs))
            chain = key
            off += bs
        # partial tail: the longest registered chunk that prefixes the
        # remaining prompt (a FULL block's entry also serves here when
        # the cap truncates it — the overhang is recomputed after the
        # CoW fork); usable tokens stop at the cap
        for length in range(min(bs, p - off), 0, -1):
            b = self.prefix.get((chain, tuple(prompt_ids[off:off + length])))
            if b is not None:
                usable = min(length, cap - off)
                if usable > 0:
                    entries.append((b, usable))
                    off += usable
                break
        return entries, chain, off

    def admit_need(self, prompt_ids, max_new_tokens: int,
                   full_residency: bool = False) -> int:
        """Free-list consumption :meth:`try_admit` would require right
        now: the raw block count for prompt+1 (or the stream's FULL
        residency when ``full_residency`` — the scheduler's anti-thrash
        gate for previously evicted requests) minus the matched prefix
        blocks that are currently IN USE (shared references consume no
        free block; matched cached-FREE blocks still occupy a free-list
        slot), plus the one reserved CoW fork block when the match ends
        mid-block."""
        prompt_ids = [int(t) for t in prompt_ids]
        p = len(prompt_ids)
        base = self.blocks_for(p + max_new_tokens if full_residency
                               else p + 1)
        if not self.prefix_cache:
            return base
        entries, _, matched_len = self._prefix_lookup(prompt_ids)
        n_in_use = sum(1 for b, _ in entries
                       if self.allocator.refcount(b) > 0)
        fork = 1 if matched_len % self.block_size else 0
        return max(0, base - n_in_use + fork)

    def prefix_stats(self) -> Dict[str, int]:
        """Prefix-cache accounting (host arithmetic, no device traffic):
        cumulative hit/fork/eviction counters plus the instantaneous
        sharing state — ``shared_blocks`` is the number of allocations
        sharing is saving right now (sum of refcount-1 over shared
        blocks), ``cached_free_blocks`` the reusable content parked in
        the allocator's LRU."""
        return {
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens_admitted": self.prompt_tokens_admitted,
            "cow_forks": self.cow_forks,
            "cache_evictions": self.cache_evictions,
            "blocks_saved": self.blocks_shared_total,
            "shared_blocks": self.allocator.shared_extra,
            "cached_free_blocks": self.allocator.cached_free_blocks,
        }

    def shared_token_discount(self) -> int:
        """Upper-bound estimate of committed tokens double-counted by
        refcount sharing (each extra reference of a shared block holds
        at most ``block_size`` token positions once, not once per
        stream) — the scheduler subtracts this from its token-budget
        accounting so shared residency is not double-charged."""
        return self.allocator.shared_extra * self.block_size

    # ---- admission -----------------------------------------------------
    def try_admit(self, prompt_ids, max_new_tokens: int) -> Optional[int]:
        """Reserve a slot + the blocks covering the prompt and the first
        generated token; no model compute happens here (the scheduler
        interleaves the prefill chunks).  Under ``prefix_cache``, the
        longest indexed prefix of the prompt maps onto EXISTING blocks —
        in-use blocks gain a reference, cached-free blocks revive — and
        only the unmatched remainder allocates fresh (plus one reserved
        fork block when the match ends mid-block, so the copy-on-write
        fork can never fail mid-prefill); ``prefilled`` starts at the
        matched length, so the scheduler skips those prefill chunks
        entirely.  Returns a request id, or None when a slot or the
        blocks are unavailable.  Raises for a request this server could
        NEVER hold (over max_len, or more total blocks than the pool
        owns) — returning None there would make a retry loop spin
        forever."""
        prompt_ids = [int(t) for t in prompt_ids]
        p = len(prompt_ids)
        if p == 0:
            raise ValueError("empty prompt: a request needs at least one "
                             "token")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens {max_new_tokens} < 1")
        if p + max_new_tokens > self.max_len:
            raise ValueError(f"prompt {p} + {max_new_tokens} exceeds "
                             f"server max_len {self.max_len}")
        total_need = self.blocks_for(p + max_new_tokens)
        if total_need > self.allocator.capacity:
            raise ValueError(
                f"request needs {total_need} blocks but the pool only "
                f"has {self.allocator.capacity}: unservable at any load")
        if not self.free_slots():
            return None
        entries: List[Tuple[int, int]] = []
        chain: Any = None
        matched_len = 0
        if self.prefix_cache:
            entries, chain, matched_len = self._prefix_lookup(prompt_ids)
        partial = matched_len % self.block_size != 0
        # fresh blocks: the prompt+1 span not covered by the match, plus
        # the reserved CoW fork target for a mid-block match boundary
        need_fresh = (self.blocks_for(p + 1) - len(entries)
                      + (1 if partial else 0))
        n_reuse = sum(1 for b, _ in entries
                      if self.allocator.refcount(b) == 0)
        if need_fresh + n_reuse > self.allocator.free_blocks:
            return None
        # pin the matched blocks FIRST so the fresh allocation's LRU
        # eviction can never reclaim one of them
        for b, _ in entries:
            if self.allocator.refcount(b) > 0:
                self.allocator.share(b)
            else:
                self.allocator.reuse_cached(b)
        fresh = self.allocator.alloc(need_fresh) if need_fresh else []
        assert fresh is not None    # capacity checked above
        fork_reserve = fresh.pop() if partial else None
        if matched_len:
            self.prefix_hits += 1
            self.prefix_hit_tokens += matched_len
            self.blocks_shared_total += len(entries)
        elif self.prefix_cache:
            self.prefix_misses += 1
        self.prompt_tokens_admitted += p
        blocks = [b for b, _ in entries] + fresh
        n_full = len(entries) - (1 if partial else 0)
        slot = next(s for s in range(self.slots)
                    if s not in self._slot_of.values())
        rid = self._rid
        self._rid += 1
        st = _Stream(rid=rid, prompt=prompt_ids,
                     max_new=int(max_new_tokens),
                     target=p + int(max_new_tokens), blocks=blocks,
                     prefilled=matched_len, n_shared=len(entries),
                     fork_pending=fork_reserve, chain_key=chain,
                     registered_tokens=n_full * self.block_size,
                     shared_at_admit=matched_len)
        self._streams[rid] = st
        self._slot_of[rid] = slot
        # reset the slot BEFORE any prefill chunk: the batched step's
        # frozen-lane write for this slot is then the position-0 write
        # prefill itself performs (idempotent — see module docstring)
        self.tables[slot, :] = SINK_BLOCK
        self.tables[slot, :len(blocks)] = blocks
        if self.window is not None:     # taken chunk by chunk, at prefill
            self.window_tables[slot, :] = SINK_BLOCK
        row = np.zeros((self.t_cap,), np.int32)
        row[:p] = prompt_ids
        self._place(slot, row, 0,
                    fresh=bool(self.state and self._reset_state(slot, rid)))
        self.active[slot] = False
        return rid

    def _reset_state(self, slot: int, rid: int) -> bool:
        """A stream admitted to ``slot`` starts from a zero state and an
        empty convolution tail, whatever the slot's last stream left: books
        the row and says so to the admission's program (:meth:`_place`),
        which zeroes the rows on the device in the call that writes the
        token row (no fetch, no program of its own), ordered before the
        stream's first chunk by the store it donates."""
        self.state_rows[slot] = rid
        return True

    def _place(self, slot: int, row: np.ndarray, at: int,
               fresh: bool = False) -> None:
        """The device's side of an admission, one program (``serve_admit``):
        ``row`` into the slot's token row, the position to ``at``, and under
        ``fresh`` zeros into the slot's state rows."""
        self.tokens, self.pos, self.state = self._admit_fn(
            self.tokens, self.pos, self.state, row,
            np.asarray([slot, at, fresh], np.int32))
        self._pos_host[slot] = at

    def prefill_remaining(self, rid: int) -> int:
        """Prompt tokens not yet prefilled (0 = stream is decoding)."""
        st = self._streams[rid]
        return len(st.prompt) - st.prefilled

    def prefill_step(self, rid: int, width: int) -> bool:
        """Advance ``rid``'s prefill by up to ``width`` prompt tokens
        (one chunk, padded to a power-of-two bucket so compiled prefill
        programs stay O(log max_len)).  On the final chunk, samples the
        first output token and activates the stream.  Returns True when
        prefill is complete."""
        st = self._streams[rid]
        slot = self._slot_of[rid]
        p = len(st.prompt)
        # the three child spans split the scheduler's ``prefill`` span
        # where the device can fall idle (train/trace.py vocabulary)
        with trace_lib.span("prefill/prepare"):
            # late match: a stream that found nothing at ADMISSION retries
            # the index once at its first prefill chunk — under burst
            # arrivals several shared-prompt requests admit in one tick
            # before any of them has registered a block, but streams
            # prefill FIFO, so by the time this one runs its
            # predecessors' blocks are indexed (the admission-time match
            # alone would miss the whole burst)
            if (self.prefix_cache and st.prefilled == 0
                    and st.n_shared == 0):
                self._rematch_prefix(st, slot)
            remaining = p - st.prefilled
            if remaining <= 0:
                return True
            w = min(int(width), remaining)
            if w < 1:
                raise ValueError(f"prefill width {width} < 1")
            if self.window is not None:
                # the window pool holds one chunk of prefill_chunk at most
                w = min(w, self.prefill_chunk)
                self._window_span(st, slot, st.prefilled,
                                  st.prefilled + w - 1)
            # copy-on-write: the FIRST write past the shared boundary
            # lands here when the matched prefix ended mid-block — fork
            # the borrowed partial block (reserved target, one on-device
            # copy, repoint, release the share) BEFORE the chunk writes
            # into it
            if (st.fork_pending is not None
                    and st.prefilled // self.block_size < st.n_shared):
                self._cow_fork(st, slot)
            # sink-invariant extension: every block this chunk writes
            # must be OWNED by the stream — a shared block is read-only
            assert st.prefilled // self.block_size >= st.n_shared, (
                f"prefill would write shared block of rid={rid}: "
                f"pos {st.prefilled} inside the first {st.n_shared} "
                "borrowed table entries")
            # the chunk as one fresh numpy row (a list of a thousand Python
            # ints costs 2 ms to hand over, with the device idle behind it
            # whenever a finished stream's fetch has just drained the queue)
            bucket = prefill_bucket(w)
            chunk = np.zeros((1, bucket), np.int32)
            chunk[0, :w] = st.prompt[st.prefilled:st.prefilled + w]
            # the prompt's last chunk is the one whose head runs
            last = st.prefilled + w >= p
            # the device gets a HOST-side copy: on the CPU backend asarray
            # may alias the numpy buffer (and jnp.array's own copy is an
            # async device op), while the host mutates self.tables /
            # self.active in place before the dispatched program has run
            table = self._device_tables(slice(slot, slot + 1))
            args = (jnp.asarray([st.prefilled], jnp.int32),
                    jnp.asarray(chunk),
                    jnp.asarray(w, jnp.int32),
                    jnp.asarray(last, jnp.bool_))
        with trace_lib.span("prefill/submit", bucket=bucket, head=int(last)):
            if self.state:
                (logits, self.pools, self.state,
                 self.stats) = self._prefill_fn(
                    self.params, self.pools, self.state, self.stats, table,
                    jnp.asarray(slot, jnp.int32), *args)
            else:
                logits, self.pools, self.stats = self._prefill_fn(
                    self.params, self.pools, self.stats, table, *args)
            self._programs += 1
            self.prefill_chunks += 1
            self.prefill_heads += int(last)
        st.prefilled += w
        if self.window is not None:
            # behind the window at once: the pool holds ONE chunk beside
            # the slots' windows, and the next op may be another stream's
            self._window_span(st, slot, st.prefilled, st.prefilled - 1)
        self._register_prefix(st, final=last)
        if not last:
            return False
        with trace_lib.span("prefill/first_token"):
            self.tokens, self.pos, self.key = self._first_fn(
                logits, self.tokens, self.pos, self.key,
                np.asarray([slot, p], np.int32))
            self._pos_host[slot] = p
            self.active[slot] = st.max_new > 1
            if st.max_new <= 1:
                self._finish(rid)
        return True

    def _cow_fork(self, st: _Stream, slot: int) -> None:
        """Fork the stream's borrowed partial tail block: copy the
        shared block's contents into the reserved fresh block on-device
        (traced src/dst — no recompile), repoint the table entry, drop
        the share.  After this the stream owns every block it will ever
        write; positions past the shared prefix inside the copy are
        overwritten by the stream's own prefill/decode writes before
        they are attended."""
        idx = st.n_shared - 1
        src, dst = st.blocks[idx], st.fork_pending
        self.pools = self._cow_fn(self.pools,
                                  jnp.asarray(src, jnp.int32),
                                  jnp.asarray(dst, jnp.int32))
        st.blocks[idx] = dst
        # repoint BEFORE releasing the share: once the table stops
        # naming src, this stream can never touch it again
        self.tables[slot, idx] = dst
        st.fork_pending = None
        st.n_shared = idx
        self.allocator.release([src])
        self.cow_forks += 1

    def _rematch_prefix(self, st: _Stream, slot: int) -> None:
        """Retry the prefix lookup for a stream that matched nothing at
        admission (see :meth:`prefill_step`): point its leading table
        entries at the now-indexed blocks, release the fresh blocks they
        displace (keeping one as the CoW fork reserve when the match
        ends mid-block), and reclassify the admission as a hit."""
        entries, chain, matched_len = self._prefix_lookup(st.prompt)
        if not matched_len:
            return
        partial = matched_len % self.block_size != 0
        n = len(entries)
        # pin the matched blocks before releasing the displaced ones so
        # the release cannot hand a matched cached-free block back out
        for b, _ in entries:
            if self.allocator.refcount(b) > 0:
                self.allocator.share(b)
            else:
                self.allocator.reuse_cached(b)
        displaced = st.blocks[:n]
        st.blocks[:n] = [b for b, _ in entries]
        st.fork_pending = displaced.pop() if partial else None
        self.allocator.release(displaced)
        self.tables[slot, :len(st.blocks)] = st.blocks
        st.n_shared = n
        st.chain_key = chain
        st.prefilled = matched_len
        st.registered_tokens = (n - (1 if partial else 0)) * self.block_size
        st.shared_at_admit = matched_len
        self.prefix_misses -= 1
        self.prefix_hits += 1
        self.prefix_hit_tokens += matched_len
        self.blocks_shared_total += n

    def _register_prefix(self, st: _Stream, final: bool) -> None:
        """Publish this stream's OWNED, fully-written prompt blocks into
        the prefix index (borrowed blocks are already there): every full
        ``block_size`` chunk covered by ``prefilled``, plus — once the
        prompt is complete — the partial tail.  The tail entry claims
        only the prompt positions; decode writes land past them, so the
        entry stays valid while the stream keeps generating."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        p = len(st.prompt)
        while st.registered_tokens + bs <= st.prefilled:
            off = st.registered_tokens
            key = (st.chain_key, tuple(st.prompt[off:off + bs]))
            if off // bs >= st.n_shared:
                b = st.blocks[off // bs]
                if self.prefix.insert(key, b):
                    self.allocator.mark_cached(b)
            st.chain_key = key
            st.registered_tokens = off + bs
        if final and st.registered_tokens < p:
            off = st.registered_tokens
            key = (st.chain_key, tuple(st.prompt[off:p]))
            if off // bs >= st.n_shared:
                b = st.blocks[off // bs]
                if self.prefix.insert(key, b):
                    self.allocator.mark_cached(b)

    # ---- block growth / eviction --------------------------------------
    def needs_block(self) -> List[int]:
        """Rids of active streams whose NEXT decode write crosses into an
        unallocated block."""
        out = []
        for rid, slot in self._slot_of.items():
            if not self.active[slot]:
                continue
            nxt = int(self._pos_host[slot]) + 1
            if nxt < self.t_cap and \
                    nxt // self.block_size >= len(self._streams[rid].blocks):
                out.append(rid)
        return out

    def ensure_blocks(self) -> List[int]:
        """Grow every stream that needs its next block; returns the rids
        the pool could NOT satisfy (the scheduler's eviction trigger)."""
        short = []
        for rid in self.needs_block():
            got = self.allocator.alloc(1)
            if got is None:
                short.append(rid)
                continue
            st = self._streams[rid]
            slot = self._slot_of[rid]
            self.tables[slot, len(st.blocks)] = got[0]
            st.blocks.extend(got)
        return short

    def _release_stream(self, st: _Stream, slot: int) -> None:
        """THE single stream-release path (_finish and evict both end
        here): zero the table to the sink FIRST — the next step's
        frozen-lane write must go to the sink, never into a block
        someone else holds — then drop one reference per block through
        :meth:`BlockAllocator.release`, including the unused CoW fork
        reserve.  A shared block survives at refcount >= 1 for its other
        readers; an owned cached block parks in the cached-free LRU; a
        double release is a hard error by the allocator's contract."""
        self.tables[slot, :] = SINK_BLOCK
        rel = list(st.blocks)
        if st.fork_pending is not None:
            rel.append(st.fork_pending)
            st.fork_pending = None
        st.blocks = []
        self.allocator.release(rel)
        if self.window is not None:
            self.window_tables[slot, :] = SINK_BLOCK
            self.window_allocator.release(list(st.window_pages.values()))
            st.window_pages = {}
        # the state row goes with the slot (its next stream zeroes it)
        self.state_rows.pop(slot, None)
        self.active[slot] = False

    def evict(self, rid: int):
        """Preempt ``rid``: release its block references (table zeroed
        to the sink first, so the frozen lane cannot touch live blocks)
        and forget the stream.  Returns ``(prompt_ids,
        max_new_tokens)`` for the caller to requeue; generated tokens
        are discarded and recomputed on re-admission (greedy re-runs
        reproduce them exactly — and under ``prefix_cache`` the re-run
        usually re-matches the very blocks this eviction parked in the
        cached-free LRU)."""
        st = self._streams.pop(rid)
        slot = self._slot_of.pop(rid)
        self._release_stream(st, slot)
        return list(st.prompt), st.max_new

    # ---- block handoff (disaggregated prefill/decode) -----------------
    def _refuse_window(self, who: str) -> None:
        """A handoff's payload is one kind of block: a model with window
        layers, or with recurrent state beside its blocks, is refused."""
        if self.window is not None:
            raise ValueError(
                f"{who} cannot hand off a model with window layers "
                "(attention_pattern 'L') yet: the payload carries one kind "
                "of block")
        refuse_recurrent(self.model, who, "the payload carries blocks of "
                         "keys and values, and no snapshot of the state")

    def _handoff_geometry(self) -> Dict[str, Any]:
        """The pool facts both sides of a handoff must agree on byte-for-
        byte.  Everything here is static server config, so a mismatch is
        a deployment error (raise), never a transient to retry."""
        return {
            "block_size": self.block_size,
            "n_layers": len(self.pools),
            # the attention's cache row: pool name -> what a token holds
            "row": {n: [int(d) for d in r]
                    for n, r in self.model.cache_row().items()},
            "kv_quant": self.kv_quant,
            "dtype": str(np.dtype(next(iter(
                self.pools[0][n] for n in self.model.cache_row())).dtype)),
        }

    def export_stream(self, rid: int) -> Dict[str, Any]:
        """Serialize a prefill-complete stream for handoff to a decode
        server: the block CONTENTS covering the written prompt positions
        (per layer, K/V and int8 scale pools alike, base64 of the raw
        device bytes — ``tobytes``/``frombuffer`` round-trips every
        dtype exactly, bf16 included), the prompt, and the first sampled
        token.  Read-only: the stream keeps running here until the
        caller explicitly releases it (``evict``), so a failed handoff
        costs nothing.  Only positions ``0..p-1`` have K/V (the first
        sampled token's K/V is written by its decode step, which runs on
        the importing side) — so exactly ``blocks_for(p)`` block rows
        travel.  Raises for a stream whose prefill is not complete."""
        self._refuse_window("export_stream")
        st = self._streams[rid]
        slot = self._slot_of[rid]
        p = len(st.prompt)
        if st.prefilled < p:
            raise ValueError(
                f"export of rid={rid} with prefill incomplete "
                f"({st.prefilled}/{p}): handoff happens at the "
                "prefill->decode boundary only")
        n_copy = self.blocks_for(p)
        idx = jnp.asarray(np.asarray(st.blocks[:n_copy], np.int64))
        wide = {n: int(np.prod(r))
                for n, r in self.model.cache_row().items()}
        layers = []
        for pool in self.pools:
            rec = {}
            for name, arr in pool.items():
                # a row stored padded to whole lane tiles travels as the
                # attention's own row (a folded per-head row is the same
                # bytes as it stands)
                rows = arr[idx][..., :wide.get(name)]
                rows = np.ascontiguousarray(np.asarray(jax.device_get(rows)))
                rec[name] = base64.b64encode(rows.tobytes()).decode("ascii")
            layers.append(rec)
        first_token = int(jax.device_get(self.tokens[slot, p]))
        self.handoffs_exported += 1
        return {
            "v": 1,
            "prompt": list(st.prompt),
            "max_new": int(st.max_new),
            "first_token": first_token,
            "n_blocks": n_copy,
            "geom": self._handoff_geometry(),
            "layers": layers,
        }

    def import_stream(self, payload: Dict[str, Any]) -> Optional[int]:
        """Admit a handed-off stream directly in the DECODING state:
        allocate fresh blocks, scatter the exported block contents into
        them on-device (one traced-dst program — block-id churn never
        recompiles), rebuild the token row (prompt + first sampled
        token), and register the prompt blocks into the local prefix
        index so later arrivals sharing the prompt hit the cache here
        too.  Returns a request id, or None when a slot or the blocks
        are unavailable (nothing consumed — the router retries or falls
        back).  Raises on geometry mismatch or a request this server
        could never hold, mirroring :meth:`try_admit`'s contract."""
        self._refuse_window("import_stream")
        geom = dict(payload["geom"])
        mine = self._handoff_geometry()
        if geom != mine:
            raise ValueError(f"handoff geometry mismatch: exporter "
                             f"{geom} vs importer {mine}")
        prompt_ids = [int(t) for t in payload["prompt"]]
        max_new = int(payload["max_new"])
        p = len(prompt_ids)
        if p == 0:
            raise ValueError("empty prompt in handoff payload")
        if max_new < 1:
            raise ValueError(f"max_new_tokens {max_new} < 1")
        if p + max_new > self.max_len:
            raise ValueError(f"prompt {p} + {max_new} exceeds server "
                             f"max_len {self.max_len}")
        total_need = self.blocks_for(p + max_new)
        if total_need > self.allocator.capacity:
            raise ValueError(
                f"request needs {total_need} blocks but the pool only "
                f"has {self.allocator.capacity}: unservable at any load")
        n_copy = int(payload["n_blocks"])
        if n_copy != self.blocks_for(p):
            raise ValueError(f"handoff carries {n_copy} blocks, prompt "
                             f"of {p} needs {self.blocks_for(p)}")
        if not self.free_slots():
            return None
        need = self.blocks_for(p + 1)
        blocks = self.allocator.alloc(need)
        if blocks is None:
            return None
        # decode the per-layer block rows; shapes are fixed by geometry,
        # so a short buffer is a hard error, not a retry
        decoded = []
        for li, rec in enumerate(payload["layers"]):
            pool = self.pools[li]
            out = {}
            for name, b64 in rec.items():
                # a block row of this pool, whatever the attention's row is
                # (a scale pool's is the row without its last axis); a pool
                # stored wider than the row that travels (padded to whole
                # lane tiles) gets its zero lanes back
                stored = pool[name].shape[1:]
                got = np.frombuffer(
                    base64.b64decode(b64),
                    dtype=np.dtype(pool[name].dtype)).reshape(
                        n_copy, self.block_size, -1)
                short = int(np.prod(stored[1:])) - got.shape[-1]
                if short:
                    got = np.pad(got, [(0, 0), (0, 0), (0, short)])
                out[name] = got.reshape((n_copy,) + stored)
            decoded.append(out)
        for i in range(n_copy):
            rows = [{name: jnp.asarray(lay[name][i])
                     for name in lay} for lay in decoded]
            self.pools = self._import_fn(
                self.pools, rows, jnp.asarray(blocks[i], jnp.int32))
        rid = self._rid
        self._rid += 1
        st = _Stream(rid=rid, prompt=prompt_ids, max_new=max_new,
                     target=p + max_new, blocks=blocks, prefilled=p)
        slot = next(s for s in range(self.slots)
                    if s not in self._slot_of.values())
        self._streams[rid] = st
        self._slot_of[rid] = slot
        self.tables[slot, :] = SINK_BLOCK
        self.tables[slot, :len(blocks)] = blocks
        row = np.zeros((self.t_cap,), np.int32)
        row[:p] = prompt_ids
        row[p] = int(payload["first_token"])
        self._place(slot, row, p)
        self.active[slot] = max_new > 1
        self.prompt_tokens_admitted += p
        self.handoffs_imported += 1
        self._register_prefix(st, final=True)
        if max_new <= 1:
            # degenerate single-token request: already complete (the
            # prefill side normally finishes these without a handoff)
            self._finish(rid)
        return rid

    # ---- decode --------------------------------------------------------
    def step(self) -> List[int]:
        """:meth:`dispatch` one decode step, then :meth:`land`: returns the
        rids whose rows reached the host in this call.  A stream whose last
        token this step makes is reported by the NEXT call while other
        streams run, and by this one when none does."""
        self.dispatch()
        return self.land()

    def dispatch(self) -> None:
        """One batched decode step across all slots, dispatched and not
        waited for.  Completion comes from host-side position counters — no
        device fetch: a stream whose last token this step makes has its row
        taken (:meth:`_finish`) and its slot and blocks released at once;
        :meth:`land` reports it.  Raises :class:`BlockExhausted` when a
        stream's next write has no block (call :meth:`ensure_blocks` / evict
        first)."""
        if not self.active.any():
            return
        # the three child spans split the scheduler's ``decode`` span
        # where the device can fall idle (train/trace.py vocabulary)
        with trace_lib.span("decode/prepare"):
            short = self.ensure_blocks()
            if short:
                raise BlockExhausted(short)
            # sink-invariant extension for sharing: an active lane's
            # decode write position must sit in a block the stream OWNS
            # (decode positions start past the prompt, and the CoW fork
            # ran during the suffix prefill — so this can only fire on a
            # bookkeeping bug, which must not silently corrupt a shared
            # block)
            for rid, slot in self._slot_of.items():
                if self.active[slot]:
                    st = self._streams[rid]
                    assert (int(self._pos_host[slot]) // self.block_size
                            >= st.n_shared), (
                        f"decode would write shared block of rid={rid}")
                    if self.window is not None:
                        at = int(self._pos_host[slot])
                        self._window_span(st, slot, at, at)
            # non-active lanes (free, finished, MID-PREFILL) see an
            # all-sink table: their writes land in the sink and their
            # reads gather garbage that is discarded — so live blocks are
            # written ONLY by prefill chunks and active decode lanes, and
            # parity never rests on a frozen lane recomputing
            # bitwise-identical K/V under a different batch shape
            tables = self._device_tables(slice(None), keep=self.active)
            active = jnp.asarray(self.active.copy())   # see prefill_step
        with trace_lib.span("decode/submit"):
            if self.state:
                (self.pools, self.state, self.tokens, self.pos, self.key,
                 self.stats) = self._step_fn(
                    self.params, self.pools, self.state, self.stats,
                    self.tokens, tables, self.pos, active, self.key)
            else:
                (self.pools, self.tokens, self.pos, self.key,
                 self.stats) = self._step_fn(
                    self.params, self.pools, self.stats, self.tokens, tables,
                    self.pos, active, self.key)
            self._programs += 1
        with trace_lib.span("decode/finish"):
            for rid, slot in list(self._slot_of.items()):
                if not self.active[slot]:
                    continue
                self._pos_host[slot] += 1
                if self._pos_host[slot] + 1 >= self._streams[rid].target:
                    self._finish(rid)

    def _finish(self, rid: int) -> None:
        """The program just dispatched makes ``rid``'s last token.  Take the
        row: one small program that copies ``tokens[slot]`` and the counters
        into buffers of their own (the next step donates both arrays) and a
        copy to the host started behind it; nothing waits.  The slot and the
        blocks go back at once: the device runs its programs in the order
        they were dispatched, so whoever gets them next writes after this
        read, which is what lets an admission overwrite a slot at all.  The
        rid is reported when the row is on the host (:meth:`land`)."""
        st = self._streams.pop(rid)
        slot = self._slot_of.pop(rid)
        row, stats = self._take_fn(self.tokens, self.stats, np.int32(slot))
        for leaf in jax.tree_util.tree_leaves((row, stats)):
            leaf.copy_to_host_async()
        self._in_flight.append(_Row(rid, st.target, row, stats,
                                    self._programs))
        self._release_stream(st, slot)

    def land(self, every: bool = False) -> List[int]:
        """Wait for the rows that have a later program queued behind them,
        fold the counters that came along, and return their rids (results
        readable from here on).  The lag is one program: a row taken behind
        the step just dispatched stays in flight until a chunk or a step has
        been dispatched behind it, so the host's wait never drains the
        device's queue.  Where nothing will be queued behind (no stream left
        active or prefilling, or no program dispatched since the last call),
        and under ``every`` (a caller about to stop ticking), everything in
        flight lands now: nothing is in flight once nothing is dispatched."""
        every = (every or not self._streams
                 or self._programs == self._programs_at_land)
        self._programs_at_land = self._programs
        behind = [r for r in self._in_flight if r.after < self._programs]
        due = self._in_flight if every else behind
        if not due:
            return []
        self._in_flight = [] if every else [
            r for r in self._in_flight if r.after >= self._programs]
        # the wait: with a program queued behind, the healthy state
        with trace_lib.span("land"):
            got = jax.device_get([(r.row, r.stats) for r in due])
        for r, (row, stats) in zip(due, got):
            self._results[r.rid] = [int(t) for t in row[:r.target]]
            self._fold_stats(stats)
        self.rows_landed += len(due)
        self.rows_landed_behind += len(behind)
        return [r.rid for r in due]

    def _fold_stats(self, stats) -> None:
        """The device's cumulative counters (modulo 2**32) into the host's
        integers; the fetch of a finished stream's row brought them."""
        for key, names, into in (
                ("experts", EXPERT_COUNTERS, self.expert_counters),
                ("attention", ATTENTION_COUNTERS, self.attention_counters),
                ("ssm", SSM_COUNTERS, self.ssm_counters)):
            if key in stats:
                raw = np.asarray(stats[key]).astype(np.int64) % (1 << 32)
                for name, d in zip(names,
                                   (raw - self._stats_seen[key]) % (1 << 32)):
                    into[name] += int(d)
                self._stats_seen[key] = raw

    # ---- results -------------------------------------------------------
    def done(self, rid: int) -> bool:
        """True once ``rid``'s row is on the host (not while it is in
        flight: :meth:`land`)."""
        if rid in self._results:
            return True
        if self.holds(rid) or any(r.rid == rid for r in self._in_flight):
            return False
        raise KeyError(f"request {rid}: unknown or already consumed")

    def holds(self, rid: int) -> bool:
        """``rid`` still runs here: prefilling or decoding, its slot and
        blocks not yet released."""
        return rid in self._streams

    def result(self, rid: int) -> List[int]:
        """Prompt + generated ids for a finished request (pops it)."""
        return self._results.pop(rid)

    def live(self) -> int:
        return len(self._streams)

    def any_active(self) -> bool:
        return bool(self.active.any())
