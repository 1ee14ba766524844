"""MLP family.

``reference_mlp()`` is the parity model: the reference's
``nn.Sequential(nn.Linear(2,3), nn.ReLU(), nn.Linear(3,1))``
(dataParallelTraining_NN_MPI.py:41-45) — 13 scalar params in 4 tensors
(SURVEY.md §3.2).  ``MLP`` generalizes it for the wide-MLP and MNIST
BASELINE.json configs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax.numpy as jnp

from .core import Activation, Linear, Module, Sequential


def _build_layers(in_features: int, hidden: Tuple[int, ...], out_features: int,
                  activation: str, param_dtype, compute_dtype) -> Tuple[Module, ...]:
    layers = []
    prev = in_features
    for h in hidden:
        layers.append(Linear(prev, h, param_dtype=param_dtype,
                             compute_dtype=compute_dtype))
        layers.append(Activation(activation))
        prev = h
    layers.append(Linear(prev, out_features, param_dtype=param_dtype,
                         compute_dtype=compute_dtype))
    return tuple(layers)


@dataclass(frozen=True)
class MLP(Module):
    in_features: int = 2
    hidden: Tuple[int, ...] = (3,)
    out_features: int = 1
    activation: str = "relu"
    param_dtype: Any = jnp.float32
    compute_dtype: Optional[Any] = None

    @property
    def net(self) -> Sequential:
        return Sequential(_build_layers(self.in_features, tuple(self.hidden),
                                        self.out_features, self.activation,
                                        self.param_dtype, self.compute_dtype))

    def init(self, key):
        return self.net.init(key)

    def apply(self, params, x, **kwargs):
        return self.net.apply(params, x, **kwargs)

    def fwd_flops(self, x_shape):
        dims = (self.in_features,) + tuple(self.hidden) + (self.out_features,)
        batch = 1
        for s in x_shape[:-1]:
            batch *= s
        return float(2 * batch * sum(a * b for a, b in zip(dims, dims[1:])))


def reference_mlp(param_dtype=jnp.float32) -> MLP:
    """The reference's exact architecture: 2 -> 3 (ReLU) -> 1."""
    return MLP(in_features=2, hidden=(3,), out_features=1, activation="relu",
               param_dtype=param_dtype)


def wide_mlp(in_features: int = 2, width: int = 512, depth: int = 4,
             out_features: int = 1, param_dtype=jnp.float32,
             compute_dtype=None) -> MLP:
    """BASELINE.json config #2: 4x512 regression MLP to stress the gradient
    allreduce."""
    return MLP(in_features=in_features, hidden=(width,) * depth,
               out_features=out_features, param_dtype=param_dtype,
               compute_dtype=compute_dtype)

