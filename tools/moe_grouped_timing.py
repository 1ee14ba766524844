#!/usr/bin/env python3
"""Time what can implement the grouped product of the layer without drops
(``models/moe.py`` ``grouped_matmul``) at a configuration's expert shape.

    python tools/moe_grouped_timing.py            # on one TPU chip
    python tools/moe_grouped_timing.py --d 2304 --f 896 --experts 64 \\
        --held 64 --top-k 8 --slots 64

A decode tick (``--slots`` tokens) and a prefill chunk (``--chunk`` tokens),
top-``k`` of ``--experts`` experts of which the first ``--held`` are held,
widths d -> f -> d, bfloat16 (the defaults are ``mistral-small-4-119b-ep4``'s:
top-4 of 128, 32 held, 4096 -> 2048 -> 4096, 32 slots, 1024 columns): the
whole gated expert (three grouped products and the gate) through
``jax.lax.ragged_dot``, through ``grouped_matmul``'s "auto" (whose tiles
``gmm_tiling`` picks; the line names them) and through the Pallas grouped
matmul at each row tile of ``--tick-rows`` / ``--chunk-rows`` crossed with
each of ``--weight-tiles`` (``whole`` is one expert's whole matrix).  One
JSON line per timing, the table last; written to
``chiprun_out/moe_grouped_timing.json`` too (``--out`` names another file
there).  PERF.md quotes the numbers; ``gmm_tiling`` and ``grouped_matmul``'s
"auto" follow them.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from neural_networks_parallel_training_with_mpi_tpu.models import moe  # noqa: E402

BF16 = jnp.bfloat16
WEIGHT_TILES = ("512x512", "1024x1024", "2048x1024", "2048x2048", "whole")


def dispatch(tokens: int, seed: int, total: int, held: int, k: int):
    """Row count and group sizes as ``DroplessMoE.route`` makes them, from
    uniform random choices (numpy: only the products are timed)."""
    rng = np.random.default_rng(seed)
    choice = np.stack([rng.choice(total, k, replace=False)
                       for _ in range(tokens)])
    gid = np.where(choice < held, choice, held).reshape(-1)
    sizes = np.bincount(gid, minlength=held + 1)[:held].astype(np.int32)
    return tokens * k, sizes


def weight_tile(name: str, k: int, n: int):
    if name == "whole":
        return k, n
    tk, tn = (int(x) for x in name.split("x"))
    return min(tk, k), min(tn, n)


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


def rows_list(text: str):
    return tuple(int(x) for x in text.split(","))


def tiles_list(text: str):
    tiles = tuple(text.split(","))
    for t in tiles:
        if t != "whole" and not re.fullmatch(r"\d+x\d+", t):
            raise argparse.ArgumentTypeError(f"not a weight tile: {t!r}")
    return tiles


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--d", type=int, default=4096, help="model width")
    p.add_argument("--f", type=int, default=2048, help="expert width")
    p.add_argument("--experts", type=int, default=128,
                   help="experts the router chooses among")
    p.add_argument("--held", type=int, default=32,
                   help="experts this chip holds (the first ones)")
    p.add_argument("--top-k", type=int, default=4)
    p.add_argument("--slots", type=int, default=32,
                   help="tokens of a decode tick")
    p.add_argument("--chunk", type=int, default=1024,
                   help="tokens of a prefill chunk")
    p.add_argument("--tick-rows", type=rows_list, default=(16, 32, 128),
                   help="row tiles to time in the tick, comma-separated")
    p.add_argument("--chunk-rows", type=rows_list, default=(128, 256, 512),
                   help="row tiles to time in the chunk, comma-separated")
    p.add_argument("--weight-tiles", type=tiles_list, default=WEIGHT_TILES,
                   help="weight tiles to time, comma-separated: KxN or "
                        "whole (one expert's whole matrix)")
    p.add_argument("--seed", type=int, default=0,
                   help="added to the token count to seed the choices")
    p.add_argument("--out", default="moe_grouped_timing.json",
                   help="file name under chiprun_out/")
    return p.parse_args(argv)


def main(argv=None) -> int:
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    a = parse(argv)
    if jax.default_backend() != "tpu":
        print("moe_grouped_timing: needs a TPU", file=sys.stderr)
        return 3
    d, f, held = a.d, a.f, a.held
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    wg = jax.random.normal(keys[0], (held, d, f), BF16) * 0.02
    wu = jax.random.normal(keys[1], (held, d, f), BF16) * 0.02
    wd = jax.random.normal(keys[2], (held, f, d), BF16) * 0.02
    rows_out = []
    for tokens, tms in ((a.slots, a.tick_rows), (a.chunk, a.chunk_rows)):
        m, sizes = dispatch(tokens, a.seed + tokens, a.experts, held,
                            a.top_k)
        xs = jax.random.normal(keys[3], (m, d), BF16)
        sz = jnp.asarray(sizes)
        reached, rows = int((sizes > 0).sum()), int(sizes.sum())
        floor_ms = max(reached * 3 * d * f * 2 / 819e9,
                       rows * 6 * d * f / 197e12) * 1e3
        auto_tiles = {"in": moe.gmm_tiling(m, d, f),
                      "out": moe.gmm_tiling(m, f, d)}
        impls = {
            "ragged": lambda x, w, s: jax.lax.ragged_dot(
                x, w, s, preferred_element_type=jnp.float32),
            "auto": lambda x, w, s: moe.grouped_matmul(x, w, s, "auto"),
        }
        for tm in tms:
            for tile in a.weight_tiles:
                impls[f"gmm {tm} {tile}"] = (
                    lambda x, w, s, tm=tm, tile=tile: gmm(
                        x, w, s, preferred_element_type=jnp.float32,
                        tiling=(tm, *weight_tile(tile, *w.shape[1:]))))
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
            if name == "auto":
                rec["tiling"] = auto_tiles
            print(json.dumps(rec), flush=True)
            rows_out.append(rec)
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / a.out).write_text(json.dumps(rows_out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
