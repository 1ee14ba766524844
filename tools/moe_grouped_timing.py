#!/usr/bin/env python3
"""Time what can implement the grouped product of the layer without drops
(``models/moe.py`` ``grouped_matmul``) at the serving cell's two shapes.

    chiprun --chips 1 -- python tools/moe_grouped_timing.py

A decode tick (32 tokens) and a prefill chunk (1024 tokens), top-4 of 128
experts of which 32 are held, widths 4096 -> 2048 -> 4096, bfloat16: the whole
gated expert (three grouped products and the gate) through
``jax.lax.ragged_dot`` and through the Pallas grouped matmul at several
tilings.  One JSON line per timing, the table last; written to
``chiprun_out/moe_grouped_timing.json`` too.  PERF.md quotes the numbers;
``gmm_tiling`` and ``grouped_matmul``'s "auto" follow them.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from neural_networks_parallel_training_with_mpi_tpu.models import moe  # noqa: E402

D, F, TOTAL, HELD, K = 4096, 2048, 128, 32, 4
BF16 = jnp.bfloat16


def dispatch(tokens: int, seed: int):
    """Sorted rows and group sizes as ``DroplessMoE.route`` makes them, from
    uniform random choices (numpy: only the products are timed)."""
    rng = np.random.default_rng(seed)
    choice = np.stack([rng.choice(TOTAL, K, replace=False)
                       for _ in range(tokens)])
    gid = np.where(choice < HELD, choice, HELD).reshape(-1)
    sizes = np.bincount(gid, minlength=HELD + 1)[:HELD].astype(np.int32)
    return tokens * K, sizes


def expert(xs, sizes, wg, wu, wd, product):
    gate, up = product(xs, wg, sizes), product(xs, wu, sizes)
    h = (jax.nn.silu(gate) * up).astype(BF16)
    return product(h, wd, sizes)


def timed(fn, args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3, out


def main() -> int:
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    if jax.default_backend() != "tpu":
        print("moe_grouped_timing: needs a TPU", file=sys.stderr)
        return 3
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    wg = jax.random.normal(keys[0], (HELD, D, F), BF16) * 0.02
    wu = jax.random.normal(keys[1], (HELD, D, F), BF16) * 0.02
    wd = jax.random.normal(keys[2], (HELD, F, D), BF16) * 0.02
    rows_out = []
    for tokens in (32, 1024):
        m, sizes = dispatch(tokens, tokens)
        xs = jax.random.normal(keys[3], (m, D), BF16)
        sz = jnp.asarray(sizes)
        reached, rows = int((sizes > 0).sum()), int(sizes.sum())
        floor_ms = max(reached * 3 * D * F * 2 / 819e9,
                       rows * 6 * D * F / 197e12) * 1e3
        impls = {
            "ragged": lambda x, w, s: jax.lax.ragged_dot(
                x, w, s, preferred_element_type=jnp.float32),
            "auto": lambda x, w, s: moe.grouped_matmul(x, w, s, "auto"),
        }
        tms = (16, 32, 128) if m < 512 else (128, 256, 512)
        for tm in tms:
            for tk, tn in ((512, 512), (1024, 1024), (2048, 1024),
                           (2048, 2048)):
                impls[f"gmm {tm}x{tk}x{tn}"] = (
                    lambda x, w, s, t=(tm, tk, tn): gmm(
                        x, w, s, preferred_element_type=jnp.float32,
                        tiling=(t[0], min(t[1], w.shape[1]),
                                min(t[2], w.shape[2]))))
        ref = None
        for name, product in impls.items():
            fn = jax.jit(lambda xs, sz, wg, wu, wd, p=product: expert(
                xs, sz, wg, wu, wd, p))
            try:
                ms, out = timed(fn, (xs, sz, wg, wu, wd))
            except Exception as e:          # a tiling the compiler refuses
                rec = {"tokens": tokens, "impl": name,
                       "error": f"{type(e).__name__}: {str(e)[:200]}"}
                print(json.dumps(rec), flush=True)
                rows_out.append(rec)
                continue
            out = np.asarray(out[:rows], np.float32)
            if ref is None:
                ref = out
            rec = {"tokens": tokens, "rows": m, "rows_held": rows,
                   "experts_reached": reached, "impl": name,
                   "ms": round(ms, 4), "floor_ms": round(floor_ms, 4),
                   "share_of_floor_pct": round(100 * floor_ms / ms, 2),
                   "max_abs_diff_vs_first": float(np.abs(out - ref).max())}
            print(json.dumps(rec), flush=True)
            rows_out.append(rec)
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / "moe_grouped_timing.json").write_text(
        json.dumps(rows_out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
