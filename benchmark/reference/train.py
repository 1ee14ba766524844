"""The reference's first three optimizer steps, in float32.

Next-token cross-entropy, mean over every token of the batch, its gradient by
``jax.grad``, and AdamW as published (Loshchilov & Hutter: decoupled decay, bias
correction).  The batch is walked in blocks of rows and each layer is
recomputed in the backward pass, so that the whole thing fits one chip beside
nothing else; neither changes the arithmetic beyond the order of float32 sums.
The walk, the optimizer and the norms are here; ``embed``, ``block``,
``head_logits`` and the names of the leaves are the model's family's.

``fault`` plants what a broken data-parallel step would do, for the readings
that set the limits in the cells' files (never used in a benchmark run):
``half_batch`` takes the mean over the first half of the rows only;
``no_exchange`` takes it over the first ``1/shards`` of the rows, which is what
one chip's state becomes when the gradient exchange is left out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..harness import weights

F32 = jnp.float32


def init_params(model: dict, seed: int):
    """{'outer': {...}, 'layers': [{name: (count, ...)} a run]} in float32."""
    maker = weights.Maker(model, seed)
    per = maker.layers()
    runs = weights.layer_runs(model)
    stack = jax.jit(lambda outer, per: {
        "outer": {k: v.astype(F32) for k, v in outer.items()},
        "layers": [{n: jnp.stack([p[n] for p in per[a:a + c]]).astype(F32)
                    for n in per[a]} for a, c in runs]})
    return stack(maker.outer(), per)


def nll_sum(model, params, ids, labels):
    fam = model["family"]
    x = fam.embed(model, params["outer"], ids)
    body = jax.checkpoint(
        lambda x, pi: (fam.block(model, pi[0], x, pi[1]), None))
    for (first, count), stacked in zip(weights.layer_runs(model),
                                       params["layers"]):
        x, _ = jax.lax.scan(body, x,
                            (stacked, first + jnp.arange(count)))
    logits = fam.head_logits(model, params["outer"], x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).sum()


def adamw(params, grads, m, v, t, opt):
    b1, b2 = opt["b1"], opt["b2"]

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + opt["eps"])
        return p - opt["lr"] * (upd + opt["weight_decay"] * p), m, v

    out = jax.tree_util.tree_map(one, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(                    # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(model, tree) -> dict:
    """Flat {name: norm} over the leaves the model's family names (it may
    split a fused tensor into the published model's own); a stacked layer
    tensor gives one norm per layer, ``L<i>.<leaf>``."""
    fam = model["family"]
    out = {n: jnp.sqrt((x.astype(F32) ** 2).sum())
           for n, x in fam.leaves(model, tree["outer"]).items()}
    for (first, count), stacked in zip(weights.layer_runs(model),
                                       tree["layers"]):
        for name, y in fam.leaves(model, stacked).items():
            per = jnp.sqrt((y ** 2).reshape(count, -1).sum(-1))
            out.update({f"L{first + i}.{name}": per[i]
                        for i in range(count)})
    return out


def three_steps(model: dict, opt: dict, seed: int, batches, *,
                row_block: int = 2, fault: str | None = None,
                shards: int = 1, log=lambda msg: None) -> dict:
    """Follow the first ``len(batches)`` steps.  ``batches`` is a list of
    (ids, labels) int arrays of shape (rows, T).  Returns the loss of each
    step, the per-leaf norm of the first gradient and of the parameters'
    change over all the steps."""
    with jax.default_matmul_precision("highest"):
        return _three_steps(model, opt, seed, batches, row_block, fault,
                            shards, log)


def _three_steps(model, opt, seed, batches, row_block, fault, shards, log):
    params0 = init_params(model, seed)
    jax.block_until_ready(params0)
    log("reference: weights made")
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params0)  # noqa: E731
    grad_fn = jax.value_and_grad(functools.partial(nll_sum, model))

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def accumulate(params, acc, total, ids, labels):
        s, g = grad_fn(params, ids, labels)
        return jax.tree_util.tree_map(jnp.add, acc, g), total + s

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3))
    def update(params, acc, m, v, count, t):
        grads = jax.tree_util.tree_map(lambda g: g / count, acc)
        new, m, v = adamw(params, grads, m, v, t, opt)
        return new, m, v, leaf_norms(model, grads)

    params, m, v = params0, zeros(), zeros()
    losses, grad_norms = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        rows = len(ids)
        if fault == "half_batch":
            rows //= 2
        elif fault == "no_exchange":
            rows //= shards
        acc, total = zeros(), jnp.zeros((), F32)
        for r in range(0, rows, row_block):
            sl = slice(r, min(r + row_block, rows))
            acc, total = accumulate(params, acc, total,
                                    jnp.asarray(ids[sl]),
                                    jnp.asarray(labels[sl]))
        count = float(rows * ids.shape[1])
        new, m, v, gn = update(params, acc, m, v, count, float(t))
        if params is not params0:
            jax.tree_util.tree_map(lambda x: x.delete(), params)
        params = new
        losses.append(float(total) / count)
        log(f"reference: step {t} done")
        if grad_norms is None:
            grad_norms = gn
    delta = jax.jit(lambda a, b: leaf_norms(
        model, jax.tree_util.tree_map(jnp.subtract, a, b)))(params, params0)
    to_np = lambda d: {k: float(x) for k, x in                  # noqa: E731
                       jax.device_get(d).items()}
    return {"losses": losses, "grad_norm": to_np(grad_norms),
            "change_norm": to_np(delta)}
