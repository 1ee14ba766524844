"""The reference's logits for served sequences, layer by layer.

One full forward pass in float32 over each prompt with its served tokens (no
cache, causal, so right-padding is harmless).  A 3-billion-parameter model does
not fit the chip in float32, so the weights of one layer at a time are made from
the seed, used for every sequence and dropped.  The walk is here; ``embed``,
``block`` and ``head_logits`` are the model's family's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import weights

F32 = jnp.float32


def generated_logits(model: dict, seed: int, seqs, prompt_lens, *,
                     quant=None, pad_to: int = 256):
    """Reference logits at every position that predicted a served token.

    ``seqs[i]`` is prompt + served tokens, ``prompt_lens[i]`` its prompt's
    length.  Returns (logits (N, V) float32 on the device, tokens (N,) numpy):
    row j is the distribution from which served token ``tokens[j]`` was drawn,
    sequences concatenated in order."""
    t_pad = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    ids = np.zeros((len(seqs), t_pad), np.int32)
    rows, cols, toks = [], [], []
    for i, (s, p) in enumerate(zip(seqs, prompt_lens)):
        ids[i, :len(s)] = s
        for t in range(p, len(s)):          # token t is predicted at t - 1
            rows.append(i), cols.append(t - 1), toks.append(s[t])
    fam = model["family"]
    with jax.default_matmul_precision("highest"):
        maker = weights.Maker(model, seed)
        outer = maker.outer()
        embed = jax.jit(functools.partial(fam.embed, model))
        xs = [embed(outer, ids[i:i + 1]) for i in range(len(seqs))]
        to_f32 = jax.jit(lambda p: {k: v.astype(F32) for k, v in p.items()})
        # the layer's index is traced: one program for every layer of a shape
        layer = jax.jit(lambda p, x, i: fam.block(model, p, x, i, quant),
                        donate_argnums=(1,))
        for i in range(model["n_layers"]):
            p = to_f32(maker.layer(i))
            xs = [layer(p, x, i) for x in xs]   # one sequence at a time
        picked = jnp.stack([xs[r][0, c] for r, c in zip(rows, cols)])
        logits = jax.jit(lambda o, h: fam.head_logits(model, o, h, quant))(
            outer, picked)
    return logits, np.asarray(toks, np.int32)
