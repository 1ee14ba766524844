"""Minimal pure-functional module system.

Models are ``(init, apply)`` pairs over plain pytrees (nested dicts/lists of
``jax.Array``), the closest TPU-native analogue of the reference's 13-param
``nn.Module`` (dataParallelTraining_NN_MPI.py:35-51) without dragging in a
framework: parameters are first-class pytrees, so sharding annotations,
``jax.grad``, ``shard_map`` and optimizers compose with no extraction step
(the reference must pull ``param.grad`` tensors out into a list to
communicate them, :179-182 — here the pytree *is* the interface).

Weight init follows torch's ``nn.Linear``/``nn.Conv2d`` resets (Kaiming
uniform with a=sqrt(5), i.e. U(+-1/sqrt(fan_in)) for both weight and bias) so
models are distributionally faithful to the reference; init is deterministic
from a ``jax.random`` key (fixing the reference's misleading seeding, bug B5:
``torch.manual_seed(rank)`` runs only on rank 0, :66-69).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


class Module:
    """Protocol: ``init(key) -> params`` and ``apply(params, x, **kw) -> y``.

    Subclasses are frozen dataclasses (hashable, safe as jit static args).
    """

    def init(self, key: jax.Array) -> Pytree:
        raise NotImplementedError

    def apply(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        raise NotImplementedError

    def __call__(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        return self.apply(params, x, **kwargs)

    def n_params(self, key: Optional[jax.Array] = None) -> int:
        params = self.init(key if key is not None else jax.random.PRNGKey(0))
        return sum(p.size for p in jax.tree_util.tree_leaves(params))

    def fwd_flops(self, x_shape: Tuple[int, ...]) -> Optional[float]:
        """Matmul/conv FLOPs of one forward pass on a batch of shape
        ``x_shape`` (2 x MACs; elementwise ops excluded — they are noise
        next to the matmuls on the MXU).  None = unaccounted architecture.
        One optimizer step is conventionally ``3 x fwd_flops`` (forward +
        ~2x for the backward).  Single source for the telemetry's MFU and
        the Trainer's achieved-FLOPs metric."""
        return None


def _uniform(key: jax.Array, shape: Tuple[int, ...], bound: float,
             dtype: jnp.dtype) -> jax.Array:
    return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)


ACTIVATIONS: Dict[str, Callable[[jax.Array], jax.Array]] = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "silu": jax.nn.silu,
    "identity": lambda x: x,
}


@dataclass(frozen=True)
class Activation(Module):
    """Parameter-free activation (reference's ``nn.ReLU()``, :43)."""

    name: str = "relu"

    def init(self, key: jax.Array) -> Pytree:
        return {}

    def apply(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        return ACTIVATIONS[self.name](x)


@dataclass(frozen=True)
class Linear(Module):
    """Dense layer ``y = x @ W + b`` (reference's ``nn.Linear``, :42/:44).

    Stored as ``W: (in, out)`` — the natural layout for ``x @ W`` on the MXU
    (torch stores the transpose).  ``compute_dtype`` casts inputs/params for
    the matmul (bfloat16 on TPU) while params stay in ``param_dtype``.
    """

    in_features: int
    out_features: int
    use_bias: bool = True
    param_dtype: Any = jnp.float32
    compute_dtype: Optional[Any] = None
    # quantized-matmul seam (ops.qmm): 'bf16' = the plain path below,
    # byte-identical to the pre-seam layer; 'int8'/'fp8' run the
    # contraction in the quantized domain (training: custom_vjp qdot;
    # serving: a true int8 activation dot against ops.quant PTQ weights).
    # q_role names this layer's fp8 amax-history slot (delayed scaling).
    matmul_dtype: str = "bf16"
    q_role: str = ""

    def init(self, key: jax.Array) -> Pytree:
        wkey, bkey = jax.random.split(key)
        bound = 1.0 / math.sqrt(self.in_features)
        params = {"w": _uniform(wkey, (self.in_features, self.out_features),
                                bound, self.param_dtype)}
        if self.use_bias:
            params["b"] = _uniform(bkey, (self.out_features,), bound,
                                   self.param_dtype)
        return params

    def apply(self, params: Pytree, x: jax.Array,
              qscales=None, qobserved=None, **kwargs) -> jax.Array:
        cdt = self.compute_dtype or x.dtype
        fmt = self.matmul_dtype
        if fmt == "int8" and "w_scale" in params:
            # serving: ops.quant PTQ weights + the quantized-compute seam
            # — dynamic per-token activation scales, int8 x int8 -> int32
            # on the MXU, both scales folded on the output tile (the
            # dequant-then-bf16-dot below was the bandwidth half only)
            from ..ops import qmm

            y = qmm.int8_serve_dot(x.astype(cdt), params["w"],
                                   params["w_scale"]).astype(cdt)
        elif fmt == "fp8" and "w_scale" in params:
            # refuse at the dispatch site, not only in the CLI: fp8 qdot
            # needs float kernels, and silently falling through to the
            # dequant matmul would mislabel every non-CLI caller's run
            raise ValueError(
                "matmul_dtype='fp8' cannot run over int8 PTQ kernels "
                "(params carry w_scale); use matmul_dtype='int8' for "
                "true int8 compute or 'bf16' for the dequant path")
        elif fmt in ("int8", "fp8"):
            from ..ops import qmm

            a_amax = None
            if fmt == "fp8" and qscales is not None and self.q_role:
                a_amax = qscales.get(self.q_role)
            if fmt == "fp8" and qobserved is not None and self.q_role:
                # calibration observation (stop-gradient amax); max-merged
                # across layers sharing this role
                prev = qobserved.get(self.q_role)
                obs = qmm.tensor_amax(x)
                qobserved[self.q_role] = (obs if prev is None
                                          else jnp.maximum(prev, obs))
            y = qmm.qdot(x.astype(cdt), params["w"],
                         fmt=fmt, scales=a_amax).astype(cdt)
        else:
            y = jnp.matmul(x.astype(cdt), params["w"].astype(cdt))
            if "w_scale" in params:
                # weights-only int8 (ops.quant.quantize_params): w is int8,
                # cast in-register for a bf16 MXU matmul, and the per-output-
                # channel scale commutes through the contraction — one fused
                # multiply on the output tile, half the HBM bytes per token
                # on the bandwidth-bound decode path
                y = y * params["w_scale"].astype(cdt)
        if self.use_bias:
            y = y + params["b"].astype(cdt)
        return y


@dataclass(frozen=True)
class Sequential(Module):
    """Chain of modules (reference's ``nn.Sequential``, :41-45).  Params are
    a list aligned with the layer tuple."""

    layers: Tuple[Module, ...]

    def init(self, key: jax.Array) -> Pytree:
        keys = jax.random.split(key, max(len(self.layers), 1))
        return [layer.init(k) for layer, k in zip(self.layers, keys)]

    def apply(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        for layer, p in zip(self.layers, params):
            x = layer.apply(p, x, **kwargs)
        return x


@dataclass(frozen=True)
class Conv2D(Module):
    """NHWC conv for the CIFAR ConvNet (BASELINE.json config #4).  NHWC +
    HWIO is XLA's preferred TPU layout."""

    in_channels: int
    out_channels: int
    kernel: int = 3
    stride: int = 1
    padding: str = "SAME"
    use_bias: bool = True
    param_dtype: Any = jnp.float32

    def init(self, key: jax.Array) -> Pytree:
        wkey, bkey = jax.random.split(key)
        fan_in = self.in_channels * self.kernel * self.kernel
        bound = 1.0 / math.sqrt(fan_in)
        params = {"w": _uniform(
            wkey, (self.kernel, self.kernel, self.in_channels, self.out_channels),
            bound, self.param_dtype)}
        if self.use_bias:
            params["b"] = _uniform(bkey, (self.out_channels,), bound,
                                   self.param_dtype)
        return params

    def apply(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        y = jax.lax.conv_general_dilated(
            x, params["w"].astype(x.dtype),
            window_strides=(self.stride, self.stride),
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + params["b"].astype(y.dtype)
        return y


@dataclass(frozen=True)
class LayerNorm(Module):
    dim: int
    eps: float = 1e-5
    param_dtype: Any = jnp.float32

    def init(self, key: jax.Array) -> Pytree:
        return {"scale": jnp.ones((self.dim,), self.param_dtype),
                "bias": jnp.zeros((self.dim,), self.param_dtype)}

    def apply(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        x32 = x.astype(jnp.float32)
        mean = x32.mean(-1, keepdims=True)
        var = x32.var(-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return y.astype(x.dtype)


@dataclass(frozen=True)
class RMSNorm(Module):
    """``x / sqrt(mean(x^2) + eps) * scale``: no mean taken off, no bias;
    statistics in float32 whatever the input's type."""

    dim: int
    eps: float = 1e-6
    param_dtype: Any = jnp.float32

    def init(self, key: jax.Array) -> Pytree:
        return {"scale": jnp.ones((self.dim,), self.param_dtype)}

    def apply(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                                + self.eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


@dataclass(frozen=True)
class Embedding(Module):
    vocab_size: int
    dim: int
    param_dtype: Any = jnp.float32

    def init(self, key: jax.Array) -> Pytree:
        return {"table": jax.random.normal(key, (self.vocab_size, self.dim),
                                           self.param_dtype)}

    def apply(self, params: Pytree, ids: jax.Array, **kwargs) -> jax.Array:
        return jnp.take(params["table"], ids, axis=0)


@dataclass(frozen=True)
class Flatten(Module):
    def init(self, key: jax.Array) -> Pytree:
        return {}

    def apply(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        return x.reshape(x.shape[0], -1)


@dataclass(frozen=True)
class AvgPool2D(Module):
    window: int = 2
    stride: Optional[int] = None

    def init(self, key: jax.Array) -> Pytree:
        return {}

    def apply(self, params: Pytree, x: jax.Array, **kwargs) -> jax.Array:
        s = self.stride or self.window
        return jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, self.window, self.window, 1),
            (1, s, s, 1), "VALID") / float(self.window * self.window)

_REMAT_POLICIES = {
    # what jax.checkpoint may SAVE between forward and backward:
    "full": None,  # nothing — recompute the whole block (max HBM saving)
    "dots": "dots_saveable",  # keep matmul outputs (skip re-running the MXU)
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
}


def make_remat(policy: str = "full"):
    """``jax.checkpoint`` bound to a named save policy (config
    ``--remat_policy``) — the HBM <-> recompute-FLOPs dial every
    block-remat site shares, so the policy vocabulary cannot drift
    between the DP/SP, SP x TP, EP x TP and pipeline paths."""
    try:
        name = _REMAT_POLICIES[policy]
    except KeyError:
        raise ValueError(f"unknown remat policy {policy!r}; have "
                         f"{sorted(_REMAT_POLICIES)}") from None
    if name is None:
        return jax.checkpoint
    pol = getattr(jax.checkpoint_policies, name)
    return lambda fn: jax.checkpoint(fn, policy=pol)
