"""Model construction from config (the 'model zoo' front door)."""

from __future__ import annotations

import jax.numpy as jnp

from ..config import SCALAR_MULTIPLIERS, ModelConfig
from .convnet import ConvNet
from .mlp import MLP
from .core import Module
from .transformer import Transformer, TransformerConfig

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def build_model(cfg: ModelConfig) -> Module:
    pdt = _DTYPES[cfg.dtype]
    cdt = _DTYPES[cfg.compute_dtype]
    if cfg.arch == "mlp":
        return MLP(in_features=cfg.in_features, hidden=tuple(cfg.hidden),
                   out_features=cfg.out_features, activation=cfg.activation,
                   param_dtype=pdt, compute_dtype=cdt)
    if cfg.arch == "convnet":
        return ConvNet(in_channels=cfg.in_channels, channels=tuple(cfg.channels),
                       image_hw=tuple(cfg.image_hw), n_classes=cfg.out_features,
                       activation=cfg.activation, param_dtype=pdt,
                       compute_dtype=cdt)
    if cfg.arch == "transformer":
        from ..ops.rope import RopeScaling

        scaling = None
        if cfg.rope_scaling:
            f, orig, fast, slow, ms, ms_all, beta = cfg.rope_scaling
            scaling = RopeScaling(f, int(orig), fast, slow, ms, ms_all, beta)
        tc = TransformerConfig(
            vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
            n_layers=cfg.n_layers, d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads or None,
            pos_encoding=cfg.pos_encoding,
            activation=cfg.ffn_activation,
            d_ff=cfg.d_ff, attention=cfg.attention, param_dtype=pdt,
            compute_dtype=cdt, remat=cfg.remat,
            remat_policy=cfg.remat_policy,
            moe_experts=cfg.moe_experts,
            moe_expert_axis=cfg.moe_expert_axis,
            moe_capacity_factor=cfg.moe_capacity_factor,
            moe_top_k=cfg.moe_top_k,
            norm=cfg.norm, norm_eps=cfg.norm_eps, use_bias=cfg.use_bias,
            rope_theta=cfg.rope_theta, attention_kind=cfg.attention_kind,
            q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, rope_scaling=scaling,
            moe_dropless=cfg.moe_dropless,
            moe_experts_held=tuple(cfg.moe_experts_held) or None,
            moe_shared_ff=cfg.moe_shared_ff,
            moe_score=cfg.moe_score,
            moe_routed_scale=cfg.moe_routed_scale,
            head_width=cfg.head_width or None, qk_norm=cfg.qk_norm,
            attention_pattern=cfg.attention_pattern or None,
            sliding_window=cfg.sliding_window,
            rope_global=cfg.rope_global,
            moe_first_dense=cfg.moe_first_dense, dense_ff=cfg.dense_ff,
            ssm_heads=cfg.ssm_heads, ssm_head_dim=cfg.ssm_head_dim,
            ssm_state=cfg.ssm_state, ssm_groups=cfg.ssm_groups,
            ssm_conv=cfg.ssm_conv, ssm_chunk=cfg.ssm_chunk,
            **{name: getattr(cfg, name) for name in SCALAR_MULTIPLIERS},
            ssm_multipliers=tuple(cfg.ssm_multipliers),
            mlp_multipliers=tuple(cfg.mlp_multipliers),
            ce_chunk=cfg.ce_chunk,
            matmul_dtype=cfg.matmul_dtype,
            matmul_skip=tuple(cfg.matmul_skip),
            scan_layers=cfg.scan_layers)
        return Transformer(tc)
    raise ValueError(f"unknown arch {cfg.arch!r}")
