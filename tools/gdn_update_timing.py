#!/usr/bin/env python3
"""Time what can implement the delta rule's decode tick (scope ``gdn_update``
of ``models/deltanet.py``) over a store of state rows on one chip.

    python tools/gdn_update_timing.py             # on one TPU chip
    python tools/gdn_update_timing.py --slots 64 --heads 30 --dk 96 \\
        --dv 192 --blocks 1,2,4

The defaults are ``olmo-hybrid-7b-pp2``'s layer: 64 streams, 30 heads, keys
of 96 and values of 192, the state float32 and stored ``dk/fold x fold dv``
a head.  Timed, ``--reps`` ticks in one program over a store it carries:
XLA's form (``GatedDeltaNet._update``, two passes over the state) and the
Pallas kernel (``ops.pallas_kernels.delta_rule_step``) at each of
``--blocks`` streams a grid step, and at the block ``delta_rule_block``
picks (``auto``).  Each line gives the time, the floor (the state read once
and written once at 819 GB/s), their ratio, and the kernel's largest
distance from XLA's form in the new state and in ``o`` (over the largest
value) and whether the streams that are not active kept their state bit for
bit.  One JSON line per timing; with ``--out FILE`` the list written to that
file too.  PERF.md quotes the numbers; ``DELTA_RULE_BLOCK_BYTES`` follows
them.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from neural_networks_parallel_training_with_mpi_tpu.models.deltanet import (  # noqa: E402
    GatedDeltaNet,
)
from neural_networks_parallel_training_with_mpi_tpu.ops import (  # noqa: E402
    pallas_kernels as pk,
)

HBM_BYTES_PER_S = 819e9     # one TPU v5e (benchmark/reducers/peaks.json)


def draw(mixer: GatedDeltaNet, slots: int, seed: int):
    """A store and one tick's inputs as the mixer makes them: unit keys,
    queries over sqrt(dk), ``beta`` in (0, 2), log decays down to
    -e^0.5, one stream in eight idle."""
    h, dk, dv, f = (mixer.n_heads, mixer.key_dim, mixer.value_dim,
                    mixer.fold)
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    state = jax.random.normal(ks[0], (slots, h, dk // f, f * dv))
    k = unit(jax.random.normal(ks[1], (slots, h, dk)))
    q = unit(jax.random.normal(ks[2], (slots, h, dk))) * dk ** -0.5
    v = jax.random.normal(ks[3], (slots, h, dv))
    beta = jax.random.uniform(ks[4], (slots, h), minval=0.0, maxval=2.0)
    g = -jnp.exp(jax.random.uniform(ks[5], (slots, h), minval=-3.0,
                                    maxval=0.5))
    active = jnp.arange(slots) % 8 != 7
    return state, (k, q, v, beta, g, active)


def timed(step, state, rest, reps):
    """Device ms of one tick: ``reps`` ticks in one program, each on the
    store the last returned and every ``o`` summed (so nothing is hoisted
    out of the loop or left unread), over ``reps``; the median of five such
    programs, so dispatch is spread over ``reps`` ticks."""
    def body(_, carry):
        s, acc = carry
        s, o = step(s, *rest)
        return s, acc + o

    loop = jax.jit(lambda s: jax.lax.fori_loop(
        0, reps, body, (s, jnp.zeros(rest[2].shape, jnp.float32))),
        donate_argnums=0)
    state, _ = jax.block_until_ready(loop(state))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        state, _ = jax.block_until_ready(loop(state))
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) / reps * 1e3


def blocks_list(text: str):
    return tuple(int(x) for x in text.split(","))


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--slots", type=int, default=64, help="streams")
    p.add_argument("--heads", type=int, default=30)
    p.add_argument("--dk", type=int, default=96, help="key width a head")
    p.add_argument("--dv", type=int, default=192, help="value width a head")
    p.add_argument("--blocks", type=blocks_list, default=(1, 2, 4),
                   help="streams a grid step to time, comma-separated")
    p.add_argument("--reps", type=int, default=50)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", type=Path, default=None,
                   help="also write the timings to this JSON file")
    return p.parse_args(argv)


def main(argv=None) -> int:
    a = parse(argv)
    if jax.default_backend() != "tpu":
        print("gdn_update_timing: needs a TPU", file=sys.stderr)
        return 3
    mixer = GatedDeltaNet(a.heads * a.dk, a.heads, a.dk, a.dv)
    state, (k, q, v, beta, g, active) = draw(mixer, a.slots, a.seed)
    floor_ms = 2 * state.size * 4 / HBM_BYTES_PER_S * 1e3
    want_s, want_o = jax.jit(mixer._update)(state, k, q, v, beta, g, active)
    want_s, want_o = np.asarray(want_s), np.asarray(want_o)
    idle = ~np.asarray(active)
    impls = {"xla": mixer._update}
    auto = pk.delta_rule_block(*state.shape)
    for block in sorted(set(a.blocks) | {auto}):
        impls[f"pallas {block}" + (" auto" if block == auto else "")] = (
            lambda s, k, q, v, b, g, act, block=block: pk.delta_rule_step(
                s, k, q, v, b, jnp.exp(g), act, block=block))
    rows = []
    for name, fn in impls.items():
        rest = (k, q, v, beta, g, active)
        try:
            got_s, got_o = jax.jit(fn)(state, *rest)
            got_s, got_o = np.asarray(got_s), np.asarray(got_o)
            ms = timed(fn, state + 0.0, rest, a.reps)
        except Exception as e:              # a block the compiler refuses
            rec = {"impl": name, "error": f"{type(e).__name__}: "
                                          f"{str(e)[:200]}"}
            print(json.dumps(rec), flush=True)
            rows.append(rec)
            continue
        rec = {"impl": name, "slots": a.slots, "heads": a.heads,
               "dk": a.dk, "dv": a.dv, "ms": round(ms, 4),
               "floor_ms": round(floor_ms, 4),
               "share_of_floor_pct": round(100 * floor_ms / ms, 2),
               "state_rel_diff": float(np.abs(got_s - want_s).max()
                                       / np.abs(want_s).max()),
               "o_rel_diff": float(np.abs(got_o - want_o).max()
                                   / np.abs(want_o).max()),
               "idle_kept": bool((got_s[idle]
                                  == np.asarray(state)[idle]).all())}
        print(json.dumps(rec), flush=True)
        rows.append(rec)
    if a.out is not None:
        a.out.parent.mkdir(parents=True, exist_ok=True)
        a.out.write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
