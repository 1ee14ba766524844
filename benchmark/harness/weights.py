"""Seeded weights, made by the benchmark and by nothing else.

The program under test and the plain reference both get their weights from
here, so neither takes anything the other has made.  A ``Maker`` builds one
layer (or the outer part: embeddings, final norm and head) per jitted call on
the device, in the type the configuration stores them in: one call a layer,
which is also how the reference walks a model that does not fit the chip in
float32.

Which tensors a model has, their shapes and how each is initialised is its
family's to say (``benchmark/families/<family>.py``: ``outer_shapes``,
``layer_shapes``, ``init_tensor``); the key each tensor is drawn with follows
its place in those lists.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def n_params(model: dict) -> int:
    fam = model["family"]
    groups = [fam.outer_shapes(model)] + [
        fam.layer_shapes(model, i) for i in range(model["n_layers"])]
    return sum(math.prod(s) for g in groups for s in g.values()
               if s is not None)


def layer_kind(model: dict, i: int) -> tuple:
    """Layer ``i``'s tensors, names and shapes in order, as a hashable: layers
    of one kind are made by one program and walked by one ``scan``."""
    return tuple(model["family"].layer_shapes(model, i).items())


def layer_runs(model: dict) -> list:
    """[(first, count)]: the layers as runs of neighbours of one kind (one run
    where every layer is alike)."""
    runs, last = [], None
    for i in range(model["n_layers"]):
        kind = layer_kind(model, i)
        if kind == last:
            runs[-1][1] += 1
        else:
            runs.append([i, 1])
        last = kind
    return [tuple(r) for r in runs]


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds pass
    2**31, which a 32-bit key constructor would fold away)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _group(model: dict, key, shapes: dict) -> dict:
    """One tensor a name, each from the key folded with its place in
    ``shapes``; a name with shape None keeps its place and makes nothing."""
    dtype = jnp.dtype(model["param_dtype"])
    init = model["family"].init_tensor
    return {n: init(model, jax.random.fold_in(key, i), n, s, dtype)
            for i, (n, s) in enumerate(shapes.items()) if s is not None}


class Maker:
    """The weights of one model from one seed.  The key and the layer's index
    are traced arguments, so the small programs (one for the outer part, one
    for each kind of layer the family has) compile once for a model and are
    found in the compile cache whatever the seed."""

    def __init__(self, model: dict, seed: int, sharding=None):
        self.model, self.key, self.sharding = model, seed_key(seed), sharding
        outer = model["family"].outer_shapes(model)
        self._outer = jax.jit(
            lambda key: _group(model, jax.random.fold_in(key, 1 << 20), outer),
            out_shardings=sharding)
        self._layers = {}       # a layer's kind -> the program that makes it

    def outer(self) -> dict:
        return self._outer(self.key)

    def layer(self, i: int) -> dict:
        model, kind = self.model, layer_kind(self.model, i)
        if kind not in self._layers:
            self._layers[kind] = jax.jit(
                lambda key, i: _group(model, jax.random.fold_in(key, i),
                                      dict(kind)),
                out_shardings=self.sharding)
        return self._layers[kind](self.key, i)

    def layers(self) -> list:
        return [self.layer(i) for i in range(self.model["n_layers"])]
