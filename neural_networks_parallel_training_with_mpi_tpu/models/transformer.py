"""Tiny decoder-only Transformer LM (BASELINE.json config #5).

The reference has no attention or sequence axis (SURVEY.md §5.7); this model
is the flagship for the TPU-native capabilities the framework adds on top of
reference parity: bfloat16 matmuls on the MXU, optional rematerialization,
and pluggable attention (dense / ring / ulysses — parallel.sequence) so the
sequence dimension can be sharded over the mesh's 'seq' axis.

Pre-LN architecture: x + Attn(LN(x)), x + MLP(LN(x)); learned positional
embeddings; weight-tied output head kept separate (simpler sharding).
"""

from __future__ import annotations

import dataclasses
from dataclasses import InitVar, dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.rope import RopeScaling, Rotary
from ..parallel.sequence import sequence_sharded_attention
from .core import (ACTIVATIONS, Embedding, LayerNorm, Linear, Module,
                   RMSNorm)


def split_qkv(c: "TransformerConfig", qkv: jax.Array):
    """Split a fused qkv projection (B, T, qkv_dim) into per-head
    q (B, T, n_heads, hd) and k/v (B, T, kv_heads, hd) — the single
    definition shared by the training block and the KV-cache decode path
    so the GQA column layout [q | k | v] cannot drift between them."""
    b, t, _ = qkv.shape
    kvw = c.kv_heads * c.head_dim
    q = qkv[..., :c.q_dim].reshape(b, t, c.n_heads, c.head_dim)
    k = qkv[..., c.q_dim:c.q_dim + kvw].reshape(b, t, c.kv_heads,
                                                c.head_dim)
    v = qkv[..., c.q_dim + kvw:].reshape(b, t, c.kv_heads, c.head_dim)
    return q, k, v


def repeat_kv(c: "TransformerConfig", kv: jax.Array) -> jax.Array:
    """Broadcast grouped K/V heads (B, T, kv_heads, hd) to full query
    heads (B, T, n_heads, hd); identity for classic multi-head."""
    groups = c.n_heads // c.kv_heads
    if groups == 1:
        return kv
    return jnp.repeat(kv, groups, axis=2)


def scaled(x: jax.Array, multiplier: float) -> jax.Array:
    """``x`` times one of the model's fixed multipliers, in ``x``'s type; a
    multiplier of 1 adds no operation (the programs of a model without
    multipliers are what they were)."""
    return x if multiplier == 1.0 else x * jnp.asarray(multiplier, x.dtype)


def _whole(module, params, u: jax.Array):
    """``module`` over a whole sequence from position 0: its ``apply``."""
    return module.apply(params, u)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    max_seq_len: int = 512
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    d_ff: int = 512
    activation: str = "gelu"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32   # set bfloat16 for TPU throughput
    # "auto" dispatches dense-vs-flash by (backend, T, head_dim, dtype)
    # from the measured table (parallel.sequence.resolve_attention_impl)
    attention: str = "auto"            # auto | dense | flash | ring | ...
    seq_axis: str = "seq"
    # Position encoding: "learned" adds a trained position-embedding table
    # (the default, matching the original treedef); "rope" rotates q/k by
    # their global positions instead (ops.rope — no position parameters at
    # all, relative-distance attention, fused elementwise on TPU).  The
    # rotation happens inside sequence_sharded_attention, so every
    # attention impl (dense/flash/ring/striped/ulysses) and every
    # seq-parallel layout inherits it; the KV-cache decode paths
    # (dense AND the native-TP generate_tp) rotate the new position and
    # cache rotated keys; Megatron-TP dense attention rotates inside
    # tp_block_apply on its local heads.
    pos_encoding: str = "learned"      # learned | rope
    rope_theta: float = 10000.0
    # Grouped-query attention (GQA, Ainslie et al. 2023): n_kv_heads < n_heads
    # shares each K/V head across n_heads/n_kv_heads query heads.  None =
    # classic multi-head (n_kv_heads == n_heads), keeping the default
    # param treedef byte-identical to pre-GQA checkpoints.  The win is
    # the KV cache: decode streams (and stores) n_kv_heads/n_heads of
    # the MHA cache bytes — the long-context serving bottleneck — while
    # training repeats K/V to full heads before the attention impls
    # (same math, unchanged kernels).  Under Megatron TP the K/V heads
    # shard over the tensor axis too (needs n_kv_heads % tp == 0; the
    # contiguous head-aligned permutation keeps each rank's query-head
    # groups on exactly its own K/V heads — qkv_tp_permutation), and the
    # native-TP decode (generate_tp) serves the kv_heads/tp-sharded
    # cache with grouped local attention.
    n_kv_heads: Optional[int] = None
    # Pallas flash-kernel tile sizes (flash / ring_flash / striped_flash
    # only; dense and the non-flash ring ignore them).  None: the kernels
    # derive them from (T, head_dim, dtype) out of the tilings timed on
    # the chip (ops.pallas_kernels.FLASH_BLOCKS; 128 x 128 where a shape
    # was not timed).  A number is an explicit override.
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    remat: bool = False                # jax.checkpoint each block (HBM <-> FLOPs)
    remat_policy: str = "full"         # full | dots | dots_no_batch (models.core.make_remat)
    # lax.scan over a stacked block pytree (leaves (n_layers, ...)) instead
    # of a Python loop: XLA traces/compiles ONE block body regardless of
    # depth, so compile time and program size stop growing with n_layers —
    # the TPU-idiomatic layout for deep models.  Changes the param treedef
    # (stacked vs per-layer list); composes with remat (checkpoint the
    # scan body) and with the seq x tensor path (parallel.spmd scans the
    # Megatron block), but not with the pipeline/GSPMD/expert layouts,
    # which own their own stacking/sharding.
    scan_layers: bool = False
    # MoE FFN (models.moe): 0 experts = dense FFN.  With ``moe_expert_axis``
    # set, apply() must run inside a shard_map binding that mesh axis and
    # expert params sharded over it (parallel.expert wires the train step).
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_capacity: Optional[int] = None
    moe_expert_axis: Optional[str] = None
    moe_top_k: int = 1  # 1 = Switch; 2 = GShard-style top-2 routing
    # Quantized-matmul seam (ops.qmm, DESIGN.md §14): run every dense
    # projection (qkv/attn_out/ffn/head) in this format.  'bf16' = the
    # plain compute_dtype matmul (byte-identical to the pre-seam model);
    # 'int8' = dynamic symmetric int8 x int8 -> int32 (training via
    # custom_vjp, serving against ops.quant PTQ weights); 'fp8' = e4m3
    # fwd / e5m2 bwd with delayed-scaling activation amax histories
    # carried in TrainState.qstate and threaded through apply(qscales=).
    # Attention's score/value einsums stay in compute_dtype.
    matmul_dtype: str = "bf16"
    # Roles excluded from the quantized-compute seam (kept on the plain
    # compute_dtype matmul): mirrors ops.quant's `skip` — a layer the
    # user kept full-precision in STORAGE (--quantize_skip head) must
    # not be dynamically quantized in COMPUTE either, and high-precision
    # first/last layers are the standard low-precision-training recipe.
    matmul_skip: Tuple[str, ...] = ()
    # Fused chunked cross-entropy (>0 enables): the LM head + CE are
    # evaluated over sequence blocks of this many tokens under
    # jax.checkpoint, so the full (B, T, vocab) f32 logits tensor — the
    # dominant HBM temp for large vocabularies, bigger than the entire
    # rest of the activation stack for the flagship 32k-vocab config —
    # is never materialized.  Peak head memory drops from O(B*T*V) to
    # O(B*ce_chunk*V) in both passes (backward recomputes each chunk's
    # logits).  Identical math to head_logits + ops.losses
    # softmax_cross_entropy up to f32 summation order.  T must be a
    # multiple of ce_chunk.  Training-loss path only (the decode path
    # wants actual logits); picked up via fused_loss_sum by
    # parallel.data_parallel.make_loss_fn.
    ce_chunk: int = 0
    # The norm: "layernorm" (mean and bias) or "rmsnorm" (neither), with
    # this eps; ``use_bias=False`` takes the biases off every projection
    # and off the feed-forward (``activation="swiglu"`` is the gated SiLU
    # feed-forward).
    norm: str = "layernorm"            # layernorm | rmsnorm
    norm_eps: float = 1e-5
    use_bias: bool = True
    # The attention: "mha" is the fused-qkv multi-head / grouped-query
    # attention above; "mla" is latent attention (models/mla.py), which
    # owns its projections and its cache row.  The five sizes and the
    # long-context rotary (ops.rope.RopeScaling: YaRN and the query scale
    # by position) are the model's own config fields; positions are rotary
    # with adjacent pairs.
    attention_kind: str = "mha"        # mha | mla
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[RopeScaling] = None
    # Routing without drops (models.moe.DroplessMoE) in place of the
    # capacity layer: ``moe_experts`` is the router's width, ``moe_top_k``
    # the choices a token, ``d_ff`` an expert's width, and
    # ``moe_experts_held = (first, count)`` the contiguous range of the
    # layer's experts that live HERE (None: all): one chip's share of an
    # expert-parallel deployment, computed without its exchange.
    # ``moe_shared_ff`` is the shared expert's width (0: none).
    moe_dropless: bool = False
    moe_experts_held: Optional[Tuple[int, int]] = None
    moe_shared_ff: int = 0
    # The router's score under routing without drops: "softmax" over all
    # experts, or "sigmoid" with a stored correction bias that takes part
    # in the choice and not in the weight; either way the chosen scores are
    # divided by their sum, then multiplied by ``moe_routed_scale``.
    moe_score: str = "softmax"         # softmax | sigmoid
    moe_routed_scale: float = 1.0
    # A head width of its own (None: ``d_model // n_heads``): the query
    # projection is then ``n_heads * head_width`` wide, not ``d_model``.
    head_width: Optional[int] = None
    # The model's norm over each head of q and k, one scale vector of
    # ``head_dim`` for all heads, before any rotation; under
    # ``qk_norm_whole`` one norm over the whole q projection and one over the
    # whole k projection, a scale a lane (Olmo 2's).
    qk_norm: bool = False
    qk_norm_whole: bool = False
    # Where the block's two norms sit: before each half (``x + F(norm(x))``,
    # pre-norm) or, under ``post_norm``, on each half's output (``x +
    # norm(F(x))``, Olmo 2's order).
    post_norm: bool = False
    # Layers that are not all alike, given as a model's config gives them.
    # ``attention_pattern`` is repeated over the layers: layer i is
    # ``pattern[i % len(pattern)]``; "G" attends every position before it,
    # "L" itself and the ``sliding_window - 1`` before it, and "D" has no
    # attention: a Gated DeltaNet (models/deltanet.py) mixes its tokens in
    # its place, of ``gdn_heads`` heads with keys of ``gdn_key_dim`` and
    # values of ``gdn_value_dim``, a convolution of ``gdn_conv`` taps, beta
    # in (0, 2) under ``gdn_neg_eigval`` (else (0, 1)).  The first
    # ``moe_first_dense`` layers of a model with experts have a dense
    # feed-forward of width ``dense_ff`` in place of the expert layer.
    attention_pattern: Optional[str] = None
    sliding_window: int = 0
    moe_first_dense: int = 0
    dense_ff: int = 0
    gdn_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 4
    gdn_neg_eigval: bool = False
    # The rotation of each kind of layer under rotary positions, as the
    # model's config gives it: (the "L" layers', the "G" layers'), each an
    # ``ops.rope.Rotary`` (plain at its theta, or YaRN at it) or None (the
    # layer carries no positions at all).  None: every layer plain at
    # ``rope_theta``.  ``layer_rotary(i)`` reads it.
    rope_kinds: Optional[Tuple[Optional[Rotary], Optional[Rotary]]] = None
    # A state-space mixer beside the attention in every block (models/ssm.py,
    # Falcon-H1's hybrid block): both read the same normed input and their
    # outputs are added, ``x + a_out Attn(a_in h) + s_out Mixer(s_in h)``.
    # ``ssm_heads`` 0 is no mixer; the other five are its shapes (heads of
    # ``ssm_head_dim``, ``ssm_groups`` groups of B and C, a state of
    # ``ssm_state`` a channel, ``ssm_conv`` taps) and ``ssm_chunk`` the tile
    # of its chunked recurrence (an implementation's, not mathematics).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # The model's fixed multipliers (muP): on the embedded tokens and on the
    # logits; on the attention's input, on its key projection (before the
    # rotation) and on its output; on the mixer's input and output, and on
    # the five segments [z | xs | B | C | dt] of its projection; on the gated
    # feed-forward's gate and on its output.  All 1: today's models, whose
    # programs hold no operation for them.
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    # The older spelling of ``rope_kinds`` for a model whose "G" layers
    # carry no positions: False is ``(Rotary(rope_theta), None)``.  Read
    # once, here; the config keeps ``rope_kinds`` alone.
    rope_global: InitVar[Optional[bool]] = None

    def __post_init__(self, rope_global=None):
        if rope_global is False:
            plain = (Rotary(self.rope_theta), None)
            if self.rope_kinds not in (None, plain):
                raise ValueError(f"rope_global=False and rope_kinds "
                                 f"{self.rope_kinds} say different things")
            object.__setattr__(self, "rope_kinds", plain)
        if self.rope_kinds is not None:
            if len(self.rope_kinds) != 2 or any(
                    r is not None and not isinstance(r, Rotary)
                    for r in self.rope_kinds):
                raise ValueError(f"rope_kinds is (window kind's, full "
                                 f"kind's), each a Rotary or None; got "
                                 f"{self.rope_kinds!r}")
            if self.pos_encoding != "rope" or self.attention_kind != "mha":
                raise ValueError("rope_kinds rotates the fused-qkv "
                                 "attention's q and k: pos_encoding must be "
                                 "'rope' and attention_kind 'mha'")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be layernorm or rmsnorm, got "
                             f"{self.norm!r}")
        if self.attention_kind not in ("mha", "mla"):
            raise ValueError(f"attention_kind must be mha or mla, got "
                             f"{self.attention_kind!r}")
        if self.attention_kind == "mla":
            if self.pos_encoding != "rope":
                raise ValueError("latent attention carries its positions in "
                                 "the rotary key: pos_encoding must be 'rope'")
            if self.attention not in ("auto", "dense"):
                raise ValueError(
                    f"latent attention has a dense expanded form only; "
                    f"attention={self.attention!r} cannot run it yet")
            sizes = (self.q_lora_rank, self.kv_lora_rank,
                     self.qk_nope_head_dim, self.qk_rope_head_dim,
                     self.v_head_dim)
            if self.matmul_dtype != "bf16":
                raise ValueError("the quantized-matmul seam does not reach "
                                 "latent attention's projections: "
                                 "matmul_dtype must be 'bf16'")
            if min(sizes) < 1 or self.qk_rope_head_dim % 2:
                raise ValueError(f"latent attention needs its five sizes "
                                 f"(an even rotary one), got {sizes}")
        if self.moe_dropless and self.moe_experts < 1:
            raise ValueError("moe_dropless needs moe_experts >= 1")
        if self.moe_dropless and self.moe_expert_axis is not None:
            raise ValueError(
                "routing without drops runs one chip's share without its "
                "exchange; moe_expert_axis (parallel/expert.py's all-to-all) "
                "belongs to the capacity layer")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score must be softmax or sigmoid, got "
                             f"{self.moe_score!r}")
        if ((self.moe_score != "softmax" or self.moe_routed_scale != 1.0)
                and not self.moe_dropless):
            raise ValueError("moe_score and moe_routed_scale belong to "
                             "routing without drops (moe_dropless)")
        own = [n for n, on in (("head_width", self.head_width is not None),
                               ("qk_norm", self.qk_norm),
                               ("post_norm", self.post_norm),
                               ("attention_pattern", self.has_layer_kinds))
               if on]
        if own and self.attention_kind != "mha":
            raise ValueError(f"{', '.join(own)}: latent attention owns its "
                             "projections and has one kind of layer")
        if self.qk_norm_whole and not self.qk_norm:
            raise ValueError("qk_norm_whole says how wide the norm of q and "
                             "k is: it needs qk_norm")
        if self.head_width is not None and self.head_width < 1:
            raise ValueError(f"head_width {self.head_width} < 1")
        pat = self.attention_pattern
        if pat is not None:
            if not pat or set(pat) - set("LGD"):
                raise ValueError(f"attention_pattern is a string of 'L', 'G' "
                                 f"and 'D', got {pat!r}")
            if "L" in pat and self.sliding_window < 1:
                raise ValueError("an 'L' layer needs sliding_window >= 1")
        if self.has_delta_layers != (self.gdn_heads > 0):
            raise ValueError(
                "a 'D' layer of attention_pattern is a Gated DeltaNet: it "
                "needs gdn_heads >= 1, and gdn_heads needs a 'D' layer")
        if self.has_delta_layers:
            if min(self.gdn_key_dim, self.gdn_value_dim, self.gdn_conv) < 1:
                raise ValueError(
                    "'D' layers need gdn_key_dim, gdn_value_dim and gdn_conv "
                    "of at least 1")
            if self.has_mixer or self.matmul_dtype != "bf16":
                raise ValueError(
                    "'D' layers hold recurrent state of their own and sit "
                    "outside the quantized-matmul seam: ssm_heads must be 0 "
                    "and matmul_dtype 'bf16'")
        if self.moe_first_dense:
            if not 0 < self.moe_first_dense <= self.n_layers \
                    or self.moe_experts < 1 or self.dense_ff < 1:
                raise ValueError(
                    f"moe_first_dense {self.moe_first_dense} needs experts "
                    f"(moe_experts {self.moe_experts}), a width for the "
                    f"dense layers (dense_ff {self.dense_ff}) and at most "
                    f"n_layers {self.n_layers}")
        if self.has_layer_kinds:
            if self.scan_layers:
                raise ValueError("scan_layers stacks layers that are all "
                                 "alike; this model's are not")
            if self.attention not in ("auto", "dense"):
                raise ValueError(
                    f"layers of several kinds run the dense attention "
                    f"(the window is in its mask); attention="
                    f"{self.attention!r} has no window yet")

        if self.has_mixer:
            if min(self.ssm_head_dim, self.ssm_state, self.ssm_groups,
                   self.ssm_conv, self.ssm_chunk) < 1:
                raise ValueError(
                    "a mixer (ssm_heads) needs ssm_head_dim, ssm_state, "
                    "ssm_groups, ssm_conv and ssm_chunk of at least 1")
            if self.attention_kind != "mha" or self.matmul_dtype != "bf16":
                raise ValueError(
                    "the mixer sits beside the fused-qkv attention and "
                    "outside the quantized-matmul seam: attention_kind "
                    "must be 'mha' and matmul_dtype 'bf16'")
            if self.attention not in ("auto", "dense", "flash"):
                raise ValueError(
                    f"the mixer's recurrence runs over a whole sequence; "
                    f"attention={self.attention!r} shards it")
        if (tuple(self.mlp_multipliers) != (1.0, 1.0)
                and self.activation != "swiglu"):
            raise ValueError("mlp_multipliers scale the gate and the output "
                             "of the gated feed-forward (activation="
                             "'swiglu')")

    # ---- a mixer beside the attention, and the multipliers ---------------
    @property
    def has_mixer(self) -> bool:
        return self.ssm_heads > 0

    @property
    def has_multipliers(self) -> bool:
        return any(m != 1.0 for m in (
            self.embedding_multiplier, self.lm_head_multiplier,
            self.attention_in_multiplier, self.attention_out_multiplier,
            self.key_multiplier, self.ssm_in_multiplier,
            self.ssm_out_multiplier, *self.ssm_multipliers,
            *self.mlp_multipliers))

    # ---- layers that are not all alike ---------------------------------
    @property
    def has_layer_kinds(self) -> bool:
        return self.attention_pattern is not None or self.moe_first_dense > 0

    def _kind(self, i: int) -> str:
        pat = self.attention_pattern
        return pat[i % len(pat)] if pat else "G"

    def layer_window(self, i: int) -> Optional[int]:
        """Keys layer ``i``'s query sees, itself included (None: all, or no
        attention at all)."""
        return self.sliding_window if self._kind(i) == "L" else None

    @property
    def has_delta_layers(self) -> bool:
        return "D" in (self.attention_pattern or "")

    def layer_is_delta(self, i: int) -> bool:
        """Layer ``i`` mixes its tokens by a Gated DeltaNet, not attention."""
        return self._kind(i) == "D"

    def layer_has_cache(self, i: int) -> bool:
        """Layer ``i`` attends, so it holds keys and values (a page pool)."""
        return not self.layer_is_delta(i)

    @property
    def has_recurrent_state(self) -> bool:
        """Some layer holds a state a stream carries between calls."""
        return self.has_mixer or self.has_delta_layers

    def layer_is_recurrent(self, i: int) -> bool:
        """Layer ``i`` holds such a state (a state row a slot)."""
        return self.has_mixer or self.layer_is_delta(i)

    def recurrent_kind(self) -> str:
        """What holds the recurrent state, as the refusals name it."""
        if self.has_delta_layers:
            return ("delta-rule layers in place of attention "
                    "(attention_pattern 'D', gdn_heads)")
        return "a state-space mixer beside the attention (ssm_heads)"

    def layer_rotary(self, i: int) -> Optional[Rotary]:
        """Layer ``i``'s rotation of q and k (None: no positions)."""
        if self.pos_encoding != "rope":
            return None
        if self.rope_kinds is None:
            return Rotary(self.rope_theta)
        window, full = self.rope_kinds
        return window if self.layer_window(i) is not None else full

    def layer_is_moe(self, i: int) -> bool:
        return self.moe_experts > 0 and i >= self.moe_first_dense

    def require_plain_block(self, who: str) -> None:
        """Paths that know the fused-qkv block and the capacity layer only
        refuse the other kinds by name, where they are built."""
        kinds = [k for k, on in (
            ("latent attention (attention_kind='mla')",
             self.attention_kind != "mha"),
            ("routing without drops (moe_dropless)", self.moe_dropless),
            ("a head width of its own (head_width)",
             self.head_width is not None),
            ("the per-head norm of q and k (qk_norm)", self.qk_norm),
            ("layers of several kinds (attention_pattern / "
             "moe_first_dense)", self.has_layer_kinds),
            ("a rotation by kind of layer (rope_kinds: YaRN or none)",
             self.rope_kinds is not None),
            (f"recurrent state: {self.recurrent_kind()}",
             self.has_recurrent_state),
            ("a norm on each half's output (post_norm)", self.post_norm),
            ("the model's multipliers (embedding_multiplier, "
             "key_multiplier, ...)", self.has_multipliers))
            if on]
        if kinds:
            raise ValueError(f"{who} cannot run a block with "
                             f"{' and '.join(kinds)} yet; the paged server "
                             "(serve.PagedDecodeServer / Scheduler) and "
                             "Transformer.apply can")

    @property
    def head_dim(self) -> int:
        if self.head_width is not None:
            return self.head_width
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        """Width of the query projection and of the attention's output:
        ``d_model`` unless the heads have a width of their own."""
        return self.n_heads * self.head_dim

    @property
    def kv_heads(self) -> int:
        """Effective K/V head count (== n_heads unless GQA)."""
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        assert self.n_heads % kv == 0, (
            f"n_heads={self.n_heads} not divisible by n_kv_heads={kv}")
        return kv

    @property
    def qkv_dim(self) -> int:
        """Fused qkv projection width: q_dim (q) + 2 * kv_heads * head_dim
        (k, v) — reduces to 3 * d_model for classic multi-head."""
        return self.q_dim + 2 * self.kv_heads * self.head_dim


@dataclass(frozen=True)
class Transformer(Module):
    cfg: TransformerConfig = dataclasses.field(default_factory=TransformerConfig)

    # ---- submodule builders (stateless; params live in the pytree) ----
    def _mm(self, role: str) -> str:
        """Effective matmul format for one projection site: the config
        format, unless the role is in ``matmul_skip`` (kept full
        precision — the compute analogue of ops.quant's ``skip``)."""
        c = self.cfg
        return "bf16" if role in c.matmul_skip else c.matmul_dtype

    def _norm(self, dim: Optional[int] = None):
        """The model's norm over ``d_model`` (block norms and the final
        one), or over ``dim`` (a head of q or k)."""
        c = self.cfg
        dim = c.d_model if dim is None else dim
        if c.norm == "rmsnorm":
            return RMSNorm(dim, c.norm_eps, c.param_dtype)
        return LayerNorm(dim, c.norm_eps, c.param_dtype)

    def cache_row(self):
        """What one token holds in one layer of a serving cache: pool name
        -> the row's trailing shape.  The paged cache (serve/paged_kv.py)
        asks this and assumes nothing else about the attention: per-head K
        and V here, one latent row under latent attention."""
        c = self.cfg
        if c.attention_kind == "mla":
            return self._block_modules()["attn"].cache_row()
        return {"k": (c.kv_heads, c.head_dim), "v": (c.kv_heads, c.head_dim)}

    def state_row(self, layer: Optional[int] = None):
        """What one STREAM holds in layer ``layer`` (None: the first layer
        that holds any) beside its cache rows: store name -> (shape, type);
        empty for a layer without recurrent state.  The paged server keeps
        it in a second store, one row a slot (models/ssm.py,
        models/deltanet.py)."""
        c = self.cfg
        if layer is None:
            layer = next((i for i in range(c.n_layers)
                          if c.layer_is_recurrent(i)), 0)
        if not c.layer_is_recurrent(layer):
            return {}
        mods = self._block_modules(layer)
        return mods["gdn" if "gdn" in mods else "ssm"].state_row()

    def _block_modules(self, layer: int = 0):
        """The modules of layer ``layer``: the same for every layer unless
        the config gives the layers kinds (``layer_is_moe``; the attention's
        kinds differ in what it does, not in what it holds; a 'D' layer has
        a Gated DeltaNet in place of the attention's projections)."""
        c = self.cfg
        if c.layer_is_delta(layer):
            from .deltanet import GatedDeltaNet

            mods = {"ln1": self._norm(),
                    "gdn": GatedDeltaNet(
                        c.d_model, c.gdn_heads, c.gdn_key_dim,
                        c.gdn_value_dim, d_conv=c.gdn_conv,
                        allow_neg_eigval=c.gdn_neg_eigval,
                        norm_eps=c.norm_eps, param_dtype=c.param_dtype,
                        compute_dtype=c.compute_dtype),
                    "ln2": self._norm()}
        elif c.attention_kind == "mla":
            from .mla import LatentAttention

            mods = {"ln1": self._norm(),
                    "attn": LatentAttention(
                        c.d_model, c.n_heads, c.q_lora_rank, c.kv_lora_rank,
                        c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                        rope_theta=c.rope_theta, rope_scaling=c.rope_scaling,
                        norm_eps=c.norm_eps, param_dtype=c.param_dtype,
                        compute_dtype=c.compute_dtype),
                    "ln2": self._norm()}
        else:
            mods = {
                "ln1": self._norm(),
                "qkv": Linear(c.d_model, c.qkv_dim, use_bias=c.use_bias,
                              param_dtype=c.param_dtype,
                              compute_dtype=c.compute_dtype,
                              matmul_dtype=self._mm("qkv"), q_role="qkv"),
                "attn_out": Linear(c.q_dim, c.d_model,
                                   use_bias=c.use_bias,
                                   param_dtype=c.param_dtype,
                                   compute_dtype=c.compute_dtype,
                                   matmul_dtype=self._mm("attn_out"),
                                   q_role="attn_out"),
            }
            if c.qk_norm_whole:
                mods["q_norm"] = self._norm(c.q_dim)
                mods["k_norm"] = self._norm(c.kv_heads * c.head_dim)
            elif c.qk_norm:
                mods["q_norm"] = self._norm(c.head_dim)
                mods["k_norm"] = self._norm(c.head_dim)
            if c.has_mixer:
                from .ssm import Mamba2Mixer

                mods["ssm"] = Mamba2Mixer(
                    c.d_model, c.ssm_heads, c.ssm_head_dim, c.ssm_state,
                    n_groups=c.ssm_groups, d_conv=c.ssm_conv,
                    chunk=c.ssm_chunk,
                    multipliers=tuple(c.ssm_multipliers),
                    norm_eps=c.norm_eps, param_dtype=c.param_dtype,
                    compute_dtype=c.compute_dtype)
            mods["ln2"] = self._norm()
        if not c.layer_is_moe(layer):
            self._dense_ffn_modules(mods)
        elif c.moe_dropless:
            from .moe import DroplessMoE

            mods["moe"] = DroplessMoE(
                c.d_model, c.d_ff, c.moe_experts, top_k=c.moe_top_k,
                held=c.moe_experts_held, shared_ff=c.moe_shared_ff,
                score=c.moe_score, routed_scale=c.moe_routed_scale,
                param_dtype=c.param_dtype,
                compute_dtype=c.compute_dtype)
        else:
            from .moe import MoEFFN

            mods["moe"] = MoEFFN(
                c.d_model, c.d_ff, c.moe_experts,
                capacity_factor=c.moe_capacity_factor,
                capacity=c.moe_capacity, activation=c.activation,
                expert_axis=c.moe_expert_axis,
                router_top_k=c.moe_top_k,
                param_dtype=c.param_dtype, compute_dtype=c.compute_dtype)
        return mods

    def _dense_ffn_modules(self, mods) -> None:
        """The two-matrix or gated feed-forward's Linears into ``mods``: of
        width ``d_ff``, or ``dense_ff`` for a leading dense layer of a model
        with experts (whose ``d_ff`` is an expert's width)."""
        c = self.cfg
        ff = c.dense_ff if c.moe_experts > 0 else c.d_ff
        mods["ff_in"] = Linear(c.d_model, ff, use_bias=c.use_bias,
                               param_dtype=c.param_dtype,
                               compute_dtype=c.compute_dtype,
                               matmul_dtype=self._mm("ff_in"),
                               q_role="ff_in")
        if c.activation == "swiglu":
            # gated FFN (Shazeer 2020): silu(x W_gate) * (x W_in),
            # then W_out — the modern-LM FFN.  A third (d, ff)
            # projection; pick d_ff ~2/3 of the ungated width for
            # iso-parameter comparisons.
            mods["ff_gate"] = Linear(c.d_model, ff,
                                     use_bias=c.use_bias,
                                     param_dtype=c.param_dtype,
                                     compute_dtype=c.compute_dtype,
                                     matmul_dtype=self._mm("ff_gate"),
                                     q_role="ff_gate")
        mods["ff_out"] = Linear(ff, c.d_model, use_bias=c.use_bias,
                                param_dtype=c.param_dtype,
                                compute_dtype=c.compute_dtype,
                                matmul_dtype=self._mm("ff_out"),
                                q_role="ff_out")

    def _qk_normed(self, mods, params, q: jax.Array, k: jax.Array):
        """The norm of q and k (``qk_norm``: each head, or under
        ``qk_norm_whole`` the whole projection), before any rotation."""
        with jax.named_scope("qk_norm"):
            if not self.cfg.qk_norm_whole:
                return (mods["q_norm"].apply(params["q_norm"], q),
                        mods["k_norm"].apply(params["k_norm"], k))
            whole = lambda n, x: mods[n].apply(                 # noqa: E731
                params[n], x.reshape(*x.shape[:-2], -1)).reshape(x.shape)
            return whole("q_norm", q), whole("k_norm", k)

    def _norm_in(self, mods, params, name: str, x: jax.Array) -> jax.Array:
        """A half's input: ``norm(x)`` (pre-norm), or ``x`` itself under
        ``post_norm``."""
        if self.cfg.post_norm:
            return x
        return mods[name].apply(params[name], x)

    def _norm_out(self, mods, params, name: str, y: jax.Array) -> jax.Array:
        """A half's output before the residual add: ``y`` itself
        (pre-norm), or ``norm(y)`` under ``post_norm``."""
        if not self.cfg.post_norm:
            return y
        return mods[name].apply(params[name], y)

    def quant_roles(self):
        """fp8 delayed-scaling roles (ops.qmm): one activation amax
        history per logical matmul site, shared across layers (under
        scan_layers the layers share one traced block anyway; for the
        python-loop stack the cross-layer max is a conservative
        per-tensor bound).  Skipped roles carry no history — their
        Linears run the plain matmul.  MoE blocks apply no ffn Linears
        (the expert einsums live outside the seam; the Trainer refuses
        the combination, but a directly-built step must not seed
        histories no forward will ever observe)."""
        c = self.cfg
        roles = ["qkv", "attn_out", "head"]
        if c.moe_experts <= 0:
            ffn = ["ff_in", "ff_out"]
            if c.activation == "swiglu":
                ffn.insert(1, "ff_gate")
            roles[2:2] = ffn
        return tuple(r for r in roles if r not in c.matmul_skip)

    def _ffn(self, mods, params, h: jax.Array, **qkw) -> jax.Array:
        """Dense-FFN tail shared by the training block and the KV-cache
        decode chunk (anti-drift): classic act(W_in h) W_out, or SwiGLU
        when activation == 'swiglu'.  ``qkw`` threads the fp8
        delayed-scaling context (qscales/qobserved) to the Linears."""
        c = self.cfg
        if c.activation == "swiglu":
            m_gate, m_out = c.mlp_multipliers
            gate = jax.nn.silu(scaled(
                mods["ff_gate"].apply(params["ff_gate"], h, **qkw), m_gate))
            return scaled(mods["ff_out"].apply(
                params["ff_out"],
                gate * mods["ff_in"].apply(params["ff_in"], h, **qkw),
                **qkw), m_out)
        h = mods["ff_in"].apply(params["ff_in"], h, **qkw)
        h = ACTIVATIONS[c.activation](h)
        return mods["ff_out"].apply(params["ff_out"], h, **qkw)

    def init(self, key: jax.Array):
        c = self.cfg
        keys = jax.random.split(key, c.n_layers + 3)
        embed = Embedding(c.vocab_size, c.d_model, c.param_dtype)
        pos = Embedding(c.max_seq_len, c.d_model, c.param_dtype)
        head = Linear(c.d_model, c.vocab_size, use_bias=False,
                      param_dtype=c.param_dtype, compute_dtype=c.compute_dtype)
        blocks = []
        for i in range(c.n_layers):
            mods = self._block_modules(i)
            bkeys = jax.random.split(keys[i], len(mods))
            blocks.append({name: m.init(k) for (name, m), k in zip(mods.items(), bkeys)})
        if c.scan_layers:  # stacked layout: leaves (n_layers, ...)
            blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                            *blocks)
        out = {
            "embed": embed.init(keys[-3]),
            "blocks": blocks,
            "ln_f": self._norm().init(keys[-1]),
            "head": head.init(keys[-1]),
        }
        if c.pos_encoding != "rope":   # RoPE has no position parameters
            out["pos"] = pos.init(keys[-2])
        return out

    def _block(self, params, x: jax.Array, qscales=None, collect=False,
               layer: int = 0, *, attend=None, attend_latent=_whole,
               run=_whole, live=None):
        """Layer ``layer``'s block: (params, x) -> (x, aux, qobs); aux is
        the MoE load-balance loss for this block (0.0 for a dense FFN), qobs
        the fp8 calibration observations ({role: amax} when ``collect``, {}
        otherwise — ops.qmm delayed scaling; ``qscales`` is the delayed
        amax each Linear reads).

        The one statement of a block's halves, for a whole sequence and for
        the paged server's prefill chunks and decode ticks alike.  A caller
        passes only what reads or writes a cache; the defaults are a whole
        causal sequence from position 0:

        * ``attend(q, k, v, layer)``: the per-head attention core, from the
          normed, projected queries (B, W, H, hd) and grouped keys and
          values (B, W, KV, hd) to the attended values (B, W, H, hd)
          (default :meth:`_attend`);
        * ``attend_latent(attn, params, h)``: latent attention from its
          normed input through its output projection (default
          ``attn.apply``);
        * ``run(mixer, params, u)``: the form a mixer beside the attention
          or a 'D' layer's Gated DeltaNet runs in (default ``apply``, from
          a zero state);
        * ``live()``: under routing without drops, the (B, W) mask of the
          tokens that are routed, taken inside the ``ffn`` scope; aux is
          then the held experts' load (count,) int32.

        A cache's core writes the cache itself: the block sees
        activations only."""
        c = self.cfg
        mods = self._block_modules(layer)
        qobs = {} if collect else None
        qkw = ({"qscales": qscales, "qobserved": qobs}
               if c.matmul_dtype == "fp8" else {})
        # the named scopes are what the device trace is read by
        # (benchmark/reducers/scopes.py); each norm and residual add sits
        # in the scope of the matrix product it feeds or follows
        if c.layer_is_delta(layer):
            # ``x + norm(GDN(x))`` (or ``x + GDN(norm(x))`` pre-norm)
            with jax.named_scope("gdn"):
                y = run(mods["gdn"], params["gdn"],
                        self._norm_in(mods, params, "ln1", x))
                x = x + self._norm_out(mods, params, "ln1",
                                       y).astype(x.dtype)
            return self._ffn_half(mods, params, x, qkw, qobs, live)
        if c.attention_kind == "mla":
            x = self._latent_attention(mods, params, x, attend_latent)
            return self._ffn_half(mods, params, x, qkw, qobs, live)
        with jax.named_scope("attn_proj"):
            h = self._norm_in(mods, params, "ln1", x)
            # the keys times ``key_multiplier`` before any norm or rotation
            q, k, v = split_qkv(c, mods["qkv"].apply(
                params["qkv"], scaled(h, c.attention_in_multiplier), **qkw))
            k = scaled(k, c.key_multiplier)
            if c.qk_norm:
                q, k = self._qk_normed(mods, params, q, k)
        out = (attend or self._attend)(q, k, v, layer)
        with jax.named_scope("attn_proj"):
            out = out.reshape(*out.shape[:2], c.q_dim)
            x = x + self._norm_out(mods, params, "ln1", scaled(
                mods["attn_out"].apply(params["attn_out"], out, **qkw),
                c.attention_out_multiplier))
        if c.has_mixer:
            # beside the attention, from the same normed input, added
            with jax.named_scope("ssm"):
                y = scaled(run(mods["ssm"], params["ssm"],
                               scaled(h, c.ssm_in_multiplier)),
                           c.ssm_out_multiplier)
            x = x + y
        return self._ffn_half(mods, params, x, qkw, qobs, live)

    def _attend(self, q, k, v, layer: int) -> jax.Array:
        """The attention core over a whole causal sequence, rotated and
        windowed as layer ``layer`` is.  K/V are repeated to full query
        heads so that every attention impl (dense/flash/ring/...) sees plain
        MHA — same math as grouped attention; the bandwidth win is
        decode-side, where the caches hold the un-repeated kv_heads."""
        c = self.cfg
        with jax.named_scope("attn_proj"):
            k, v = repeat_kv(c, k), repeat_kv(c, v)
        rot = c.layer_rotary(layer)
        with jax.named_scope("attention"):
            return sequence_sharded_attention(
                c.attention, q, k, v,
                axis=c.seq_axis, causal=True, block_q=c.flash_block_q,
                block_k=c.flash_block_k,
                rope_theta=None if rot is None else rot.theta,
                rope_scaling=None if rot is None else rot.scaling,
                window=c.layer_window(layer))

    def _ffn_half(self, mods, params, x: jax.Array, qkw, qobs, live=None):
        """``x + FFN(norm(x))``, the block's second half, whatever the
        first was: the two-matrix or gated feed-forward, the capacity
        layer, or routing without drops (``live``: see :meth:`_block`)."""
        with jax.named_scope("ffn"):
            h = self._norm_in(mods, params, "ln2", x)
            if "moe" not in mods:
                ff = self._ffn(mods, params, h, **qkw)
                aux = jnp.zeros((), jnp.float32)
            elif live is not None:
                # pad columns and idle lanes reach no expert (and read
                # none): the load counts what the traffic asked for
                ff, _, aux = mods["moe"].apply(
                    params["moe"], h, mask=live(), return_load=True)
            else:
                ff, aux = mods["moe"].apply(params["moe"], h)
            return (x + self._norm_out(mods, params, "ln2",
                                       ff).astype(x.dtype),
                    aux, (qobs or {}))

    def _latent_attention(self, mods, params, x: jax.Array,
                          attend=_whole) -> jax.Array:
        """``x + LatentAttn(norm(x))``; ``attend`` as ``attend_latent`` of
        :meth:`_block` (by default the whole causal sequence from position
        0, in the expanded form)."""
        with jax.named_scope("attn_proj"):
            h = mods["ln1"].apply(params["ln1"], x)
        out = attend(mods["attn"], params["attn"], h)
        with jax.named_scope("attn_proj"):
            return x + out.astype(x.dtype)

    def add_pos(self, params, x_tokens: jax.Array,
                positions: jax.Array) -> jax.Array:
        """Positional embedding + compute-dtype cast on an already-looked-up
        token embedding — the non-vocab half of :meth:`embed`, shared with
        the vocab-parallel path (parallel.spmd) where the token lookup is
        table-sharded but THIS part must stay identical to the dense
        model."""
        c = self.cfg
        if c.pos_encoding == "rope":
            # position enters through the q/k rotation inside attention
            # (sequence_sharded_attention / the decode chunk), not here
            return x_tokens.astype(c.compute_dtype)
        x = x_tokens + Embedding(c.max_seq_len, c.d_model,
                                 c.param_dtype).apply(params["pos"],
                                                      positions)
        return x.astype(c.compute_dtype)

    def embed(self, params, ids: jax.Array, positions: jax.Array) -> jax.Array:
        """Token + positional embedding -> (B, T, D) in compute dtype.
        Single definition shared by the training forward and the KV-cache
        decode path (models.generate), so they cannot drift."""
        c = self.cfg
        with jax.named_scope("embed"):
            x = Embedding(c.vocab_size, c.d_model, c.param_dtype).apply(
                params["embed"], ids)
            return self.add_pos(params, scaled(x, c.embedding_multiplier),
                                positions)

    def final_norm(self, params, x: jax.Array) -> jax.Array:
        """The pre-head LayerNorm — the non-vocab half of
        :meth:`head_logits`, shared with the vocab-parallel head (same
        drift argument as :meth:`add_pos`)."""
        c = self.cfg
        with jax.named_scope("lm_head"):
            return self._norm().apply(params["ln_f"], x)

    def head_logits(self, params, x: jax.Array, qscales=None) -> jax.Array:
        """Final LayerNorm + untied head -> f32 logits (shared with
        models.generate, same drift argument as :meth:`embed`)."""
        c = self.cfg
        x = self.final_norm(params, x)
        with jax.named_scope("lm_head"):
            logits = Linear(c.d_model, c.vocab_size, use_bias=False,
                            param_dtype=c.param_dtype,
                            compute_dtype=c.compute_dtype,
                            matmul_dtype=self._mm("head"),
                            q_role="head").apply(params["head"], x,
                                                 qscales=qscales)
            return scaled(logits.astype(jnp.float32),
                           c.lm_head_multiplier)

    def fwd_flops(self, x_shape):
        """(B, T) token batch.  qkv/out/ffn/attention matmuls + LM head;
        with MoE, each token runs ``moe_top_k`` expert FFNs plus the
        router matmul.  Layers of several kinds are counted one by one (a
        window layer's scores and values over ``min(T, window)`` keys)."""
        c = self.cfg
        b, t = x_shape
        d, v = c.d_model, c.vocab_size
        total = 0.0
        for i in (range(c.n_layers) if c.has_layer_kinds else (0,)):
            if c.attention_kind == "mla":
                # the low-rank projections and the expanded scores + values
                per_layer = b * t * self._block_modules()[
                    "attn"].fwd_flops_per_token(t)
            elif c.layer_is_delta(i):
                # the Gated DeltaNet in place of the attention
                per_layer = b * t * self._block_modules(i)[
                    "gdn"].fwd_flops_per_token()
            else:
                keys = min(t, c.layer_window(i) or t)
                per_layer = 2.0 * b * t * d * c.qkv_dim  # qkv (GQA-aware)
                per_layer += 2.0 * b * t * c.q_dim * d  # attention out
                per_layer += 2.0 * (2.0 * b * t * keys * c.q_dim)  # scores + values
                if c.has_mixer:
                    per_layer += b * t * self._block_modules()[
                        "ssm"].fwd_flops_per_token()
            moe = c.layer_is_moe(i)
            ff = c.d_ff if moe or not c.moe_experts else c.dense_ff
            # FFN in + out per expert; SwiGLU adds the (d, ff) gate matmul
            gated = c.activation == "swiglu" or (moe and c.moe_dropless)
            ffn = 2.0 * ((3.0 if gated else 2.0) * b * t * d * ff)
            if moe and c.moe_dropless:
                # of a token's k choices the share that is held here, plus
                # the shared expert
                count = (c.moe_experts_held or (0, c.moe_experts))[1]
                ffn *= c.moe_top_k * count / c.moe_experts
                ffn += 2.0 * 3.0 * b * t * d * c.moe_shared_ff
                per_layer += 2.0 * b * t * d * c.moe_experts  # router
            elif moe:
                ffn *= c.moe_top_k
                per_layer += 2.0 * b * t * d * c.moe_experts  # router
            total += per_layer + ffn
        if not c.has_layer_kinds:
            total *= c.n_layers
        return float(total + 2.0 * b * t * d * v)

    def backbone(self, params, ids: jax.Array, qscales=None,
                 collect=False):
        """Embedding + all blocks -> ((B, T_local, d_model) pre-head
        hidden states, MoE aux sum, fp8 amax observations).  The shared
        trunk of :meth:`apply` and the fused chunked-CE loss path (same
        drift argument as :meth:`embed` / :meth:`head_logits`).

        ``qscales``/``collect`` are the fp8 delayed-scaling context
        (ops.qmm): blocks read the per-role delayed amax and, under
        ``collect``, report this step's observed amax — max-merged across
        layers, riding the scan carry under ``scan_layers`` so the
        observations escape the scan trace."""
        c = self.cfg
        from ..parallel.sequence import global_positions

        positions = global_positions(c.attention, c.seq_axis, ids.shape[1])
        x = self.embed(params, ids, positions)
        collect = collect and c.matmul_dtype == "fp8"
        # qscales/collect are CLOSED OVER (not block_fn args): collect is
        # a static python bool — as a positional arg, jax.checkpoint
        # would trace it — and qscales is calibration state, constant
        # w.r.t. the differentiated params
        _qs, _collect = qscales, collect

        def block_at(i):
            """Layer ``i``'s block (the index is read only where the
            config gives the layers kinds)."""
            def block_fn(layer_params, h):
                return self._block(layer_params, h, _qs, _collect, layer=i)

            if c.remat:
                from .core import make_remat

                return make_remat(c.remat_policy)(block_fn)
            return block_fn

        block_fn = block_at(0)
        aux_total = jnp.zeros((), jnp.float32)
        # block-level roles only (head observes in apply/qloss callers)
        block_roles = [r for r in (self.quant_roles() if collect else ())
                       if r != "head"]
        qobs_total = {r: jnp.zeros((), jnp.float32) for r in block_roles}
        if c.scan_layers:
            def body(carry, layer_params):
                h, aux_sum, obs_acc = carry
                h, aux, obs = block_fn(layer_params, h)
                obs_acc = {r: jnp.maximum(obs_acc[r], obs[r])
                           for r in obs_acc}
                return (h, aux_sum + aux, obs_acc), None

            (x, aux_total, qobs_total), _ = jax.lax.scan(
                body, (x, aux_total, qobs_total), params["blocks"])
        else:
            for i, layer_params in enumerate(params["blocks"]):
                # one function for layers that are all alike (traced once)
                fn = block_at(i) if c.has_layer_kinds else block_fn
                x, aux, obs = fn(layer_params, x)
                aux_total = aux_total + aux
                qobs_total = {r: jnp.maximum(qobs_total[r], obs[r])
                              for r in qobs_total}
        return x, aux_total, qobs_total

    def apply(self, params, ids: jax.Array, return_aux: bool = False,
              qscales=None, return_qobs: bool = False, **kwargs):
        """ids: (B, T_local) int32 -> logits (B, T_local, vocab), or
        (logits, aux) with ``return_aux`` (aux = summed MoE load-balance
        loss over blocks; 0.0 for dense FFNs), or (logits, qobs) with
        ``return_qobs`` (the fp8 delayed-scaling observations,
        {role: amax} — the training step's calibration input).

        ``qscales`` is the per-role delayed amax read from
        TrainState.qstate (ops.qmm.delayed_amax); None = current scaling
        (eval/decode, no calibration state to thread).

        Under sequence parallelism T_local = T / seq_axis_size and
        ``pos_offset`` (the shard's global starting position) is derived from
        the bound axis index; dense attention uses offset 0.
        """
        x, aux_total, qobs = self.backbone(params, ids, qscales=qscales,
                                           collect=return_qobs)
        if (return_qobs and self.cfg.matmul_dtype == "fp8"
                and "head" in self.quant_roles()):
            from ..ops import qmm

            qobs = dict(qobs)
            qobs["head"] = qmm.tensor_amax(self.final_norm(params, x))
        logits = self.head_logits(params, x, qscales=qscales)
        if return_qobs:
            return (logits, aux_total, qobs) if return_aux else (logits,
                                                                 qobs)
        return (logits, aux_total) if return_aux else logits

    # ---- fused chunked cross-entropy (cfg.ce_chunk > 0) ----

    def _chunked_ce_sum(self, params, x: jax.Array, labels: jax.Array,
                        mask: Optional[jax.Array],
                        label_smoothing: float
                        ) -> Tuple[jax.Array, jax.Array]:
        """(loss_sum, token_count) of head-projection + softmax CE computed
        ``ce_chunk`` tokens at a time under ``jax.checkpoint``.  ``x`` is
        the post-final-norm hidden state (B, T, d_model); the (B, T, V)
        logits tensor never exists — each scan tick materializes only a
        (B, ce_chunk, V) slice, and backward recomputes it.  Matches
        head_logits + ops.losses.softmax_cross_entropy exactly up to f32
        summation order (chunk sums are accumulated sequentially)."""
        c = self.cfg
        B, T, _ = x.shape
        k = c.ce_chunk
        if T % k != 0:
            raise ValueError(
                f"ce_chunk={k} must divide the local sequence length {T}")
        n = T // k
        head = Linear(c.d_model, c.vocab_size, use_bias=False,
                      param_dtype=c.param_dtype,
                      compute_dtype=c.compute_dtype,
                      matmul_dtype=self._mm("head"), q_role="head")

        from ..ops import losses as losses_lib

        def chunk_sum(head_params, xc, yc):
            # ops.losses.softmax_cross_entropy is the single definition of
            # the nll/mask/count semantics (same anti-drift argument as
            # embed/head_logits: the fused path must stay byte-equivalent
            # in math to the materializing path it replaces); per chunk it
            # returns (sum over B x k masked tokens, mask.sum() * k), and
            # the scan total reproduces reduce_token_nll's (sum,
            # mask.sum() * T) exactly
            logits = head.apply(head_params, xc).astype(jnp.float32)
            return losses_lib.softmax_cross_entropy(
                logits, yc, mask, label_smoothing=label_smoothing)

        chunk_sum = jax.checkpoint(chunk_sum)

        def body(acc, inp):
            xc, yc = inp
            s, cnt = chunk_sum(params["head"], xc, yc)
            return (acc[0] + s, acc[1] + cnt), None

        with jax.named_scope("chunked_ce"):
            xs = x.reshape(B, n, k, x.shape[-1]).swapaxes(0, 1)  # (n,B,k,d)
            ys = labels.reshape(B, n, k).swapaxes(0, 1)          # (n, B, k)
            (s, cnt), _ = jax.lax.scan(
                body,
                (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                (xs, ys))
        return s, cnt

    def fused_loss_sum(self, loss_name: str):
        """(params, batch) -> (loss_sum, count) closure fusing the LM head
        into a chunked cross-entropy, or None when not applicable (chunking
        disabled, or a loss the fusion doesn't cover).  Hook consumed by
        parallel.data_parallel.make_loss_fn; batch/mask semantics are
        those of ops.losses.softmax_cross_entropy + reduce_token_nll."""
        if self.cfg.ce_chunk <= 0:
            return None
        base, _, smooth = loss_name.partition("@")
        if base != "cross_entropy":
            return None
        label_smoothing = float(smooth) if smooth else 0.0

        def loss_fn(params, batch):
            x, _aux, _qobs = self.backbone(params, batch["x"])
            x = self.final_norm(params, x)
            return self._chunked_ce_sum(params, x, batch["y"],
                                        batch.get("mask"), label_smoothing)

        return loss_fn
