"""The comparison that decides ``correct``: the program's numbers against the
plain reference's, each held to a limit of its own from the cell's file."""

from __future__ import annotations

import statistics

# a leaf whose first gradient is nought to rounding in the reference (a key's
# bias under softmax) moves under Adam by round-off alone: left out of the
# comparison of changes by this rule on the reference's gradient
DEAD_LEAF = 1e-3


def worst_leaf(prog: dict, ref: dict, names=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger.  Returns (gap, leaf)."""
    names = list(ref if names is None else names)
    median = statistics.median(ref[n] for n in names)
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], median) for n in names}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def entry(name, value, limit, note=""):
    ok = value == value and value <= limit        # NaN fails
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok),
            **({"note": note} if note else {})}


def train_checks(prog: dict, ref: dict, limits: dict) -> list:
    """``prog`` and ``ref`` hold ``losses`` (one per followed step),
    ``grad_norm`` and ``change_norm`` ({leaf: norm})."""
    out = []
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out.append(entry(f"loss_step{i}", abs(lp - lr) / abs(lr),
                          limits[f"loss_step{i}"]))
    missing = set(ref["grad_norm"]) ^ set(prog["grad_norm"])
    if missing:
        raise KeyError(f"leaves differ between program and reference: "
                       f"{sorted(missing)[:5]}")
    gap, leaf = worst_leaf(prog["grad_norm"], ref["grad_norm"])
    out.append(entry("grad_norm", gap, limits["grad_norm"], leaf))
    median = statistics.median(ref["grad_norm"].values())
    live = [n for n, g in ref["grad_norm"].items() if g >= DEAD_LEAF * median]
    gap, leaf = worst_leaf(prog["change_norm"], ref["change_norm"], live)
    out.append(entry("change_norm", gap, limits["change_norm"], leaf))
    return out


def served_gap(logits, tokens):
    """How far each served token's reference logit lies below the reference's
    best at its position, in standard deviations of that position's logits.
    ``logits`` (N, V) on the device, ``tokens`` (N,).  Returns numpy (N,)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(logits, tokens):
        tok = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
        return (logits.max(-1) - tok) / logits.std(-1)

    return jax.device_get(gaps(logits, jnp.asarray(tokens)))


def serve_checks(n_malformed: int, gaps, limits: dict) -> list:
    """The mean gap over the sampled served tokens is what is held to a
    limit: a token from a broken cache sits about 4 sigma down and moves the
    mean of some hundreds of tokens tenfold, and computing in a lower
    precision moves it tenfold too, while the widest single gap swings from
    seed to seed by its nature (readings in PERF.md)."""
    mean = float(sum(gaps) / len(gaps)) if len(gaps) else float("nan")
    return [entry("malformed_answers", n_malformed, 0),
            entry("served_gap_mean_sigma", mean,
                  limits["served_gap_mean_sigma"],
                  f"{len(gaps)} served tokens, widest "
                  f"{float(max(gaps)) if len(gaps) else float('nan'):.4f}")]
