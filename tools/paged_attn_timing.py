#!/usr/bin/env python3
"""Times the paged-attention kernel's tilings on the chip.

    chiprun --chips 1 -- python tools/paged_attn_timing.py [--quick]
        [--rows per_head,latent,wide]

Three rows (``--rows``, default all).  ``per_head``: at ``sc2-3b-serve-code``'s shapes (24 query heads over 2 KV heads of 128,
bf16 pools of 2305 blocks of 16 with the heads folded into the lanes, 256
blocks a table): the decode step over 16 streams whose lengths are drawn
like the cell's (mean about 800), the same at the table's full width (the
kernel's worst case), and a 512-token prefill chunk at three depths of its
prompt and with 200 real columns.  Each row is one layer's attention as the
serving programs call it (the query's and the output's transposes around
the kernel included), run ``--reps`` times inside one program (each call's
output is the next call's query, so nothing overlaps) and divided.
``ops/pallas_kernels.py`` ``PAGED_TILES`` holds the row chosen of each kind;
PERF.md section 6 (PR 30) prints the table.  What the kernel replaces is
not timed here: alone in a loop, XLA lifts ``pool[tables]`` out of it (the
pool does not change), so a gathered row would leave the gather out; its
cost is read from the parent's trace, per layer inside the programs
(``tools/scope_ops.py``).  Also timed: the scatter of a chunk's 512 rows
into either pool layout.  ``latent``: at ``ms4-119b-ep4-serve-docqa``'s
shapes (32 query heads against ONE row that is key and value: 256 lanes of
``c_kv``, 64 of ``k_rope``, stored 384 wide in bf16 pools of 17409 blocks of
16; 544 blocks a table), the kernel's shared-row mode in the decode step
over 32 streams whose lengths are drawn like that cell's (prompts lognormal
around 2048, mean about 2.7 k), at the table's full width of 8704, and the
scatter of a 1024-token chunk's rows into the stored layout (PERF.md section
6, PR 32).  ``wide``: at ``kexaone-236b-ep8-serve-longmix``'s shapes (64 query
heads over 8 KV heads of 128: a 1024-lane row, a 32 KB page a pool; 48
streams whose lengths are drawn like that cell's, mean about 5 k; 1088
blocks a table), the decode step and a 1024-token chunk, each as the FULL
walk and as the walk bounded below by a window of 128 (PERF.md section 6,
PR 33).  Results go to ``chiprun_out/paged_attn_timing.json``
and, one JSON line a row, to standard output.  There is no CPU path.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (  # noqa: E402
    paged_attention,
)

H, KV, HD, BS, NB, MB = 24, 2, 128, 16, 2305, 256
T_CAP = BS * MB
# the latent row: heads, lanes stored / of the value / of the row, geometry
LAT = dict(heads=32, lanes=384, v_lanes=256, row=320, nb=17409, mb=544,
           streams=32, scale=0.0884)


def timed(fn, args, reps: int) -> float:
    """ms a call of ``fn(q, *rest)``, ``reps`` chained calls a program."""
    def many(q, *rest):
        return jax.lax.fori_loop(0, reps, lambda _i, x: fn(x, *rest), q)

    run = jax.jit(many)
    jax.block_until_ready(run(*args))              # compile + warm
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t)
    return 1e3 * best / reps


def time_per_head(args, rng, emit, tables_nb):
    """The per-head K/V row at ``sc2-3b-serve-code``'s shapes."""
    kp4 = jnp.asarray(rng.normal(size=(NB, BS, KV, HD)), jnp.bfloat16)
    vp4 = jnp.asarray(rng.normal(size=(NB, BS, KV, HD)), jnp.bfloat16)
    kp3, vp3 = kp4.reshape(NB, BS, KV * HD), vp4.reshape(NB, BS, KV * HD)
    tables_for = lambda lens: tables_nb(lens, NB, MB)        # noqa: E731
    # ---- decode: 16 streams, the cell's lengths (prompt + answer so far)
    prompts = np.clip(rng.lognormal(np.log(768), 0.6, 16), 128, 2048)
    lens = (prompts + rng.uniform(0, 96, 16)).astype(np.int32)
    tables = tables_for(lens)
    lens_j = jnp.asarray(lens)
    starts = lens_j - 1
    q = jnp.asarray(rng.normal(size=(16, 1, H, HD)), jnp.bfloat16)
    live = int(lens.sum())
    for pages in ((16, 32, 64) if args.quick else (2, 4, 8, 16, 32, 64, 128)):
        ms = timed(lambda x, t, ln, st, pages=pages: paged_attention(
            x, kp3, vp3, t, ln, st, pages=pages), (q, tables, lens_j, starts),
            args.reps)
        emit(kind="decode", pages=pages, ms=ms, mean_len=float(lens.mean()),
             live_gb_s=live * 2 * KV * HD * 2 / ms / 1e6)
    # every stream at the table's full width: the kernel's worst case
    full = jnp.full((16,), T_CAP, jnp.int32)
    t_full = tables_for([T_CAP // 8] * 16)
    t_full = jnp.tile(t_full[:, :MB // 8], (1, 8))
    for pages in (16, 32, 64, 128):
        emit(kind="decode_full_width", pages=pages,
             ms=timed(lambda x, t, ln, st, pages=pages: paged_attention(
                 x, kp3, vp3, t, ln, st, pages=pages),
                 (q, t_full, full, full - 1), args.reps))

    # ---- a 512-token chunk at three depths of its prompt, and a short one
    q = jnp.asarray(rng.normal(size=(1, 512, H, HD)), jnp.bfloat16)
    for start, true_w in ((0, 512), (512, 512), (1536, 512), (512, 200)):
        ln = jnp.asarray([start + true_w], jnp.int32)
        st = jnp.asarray([start], jnp.int32)
        tbl = tables_for([start + true_w])
        reps = max(4, args.reps // 4)
        grid = (((16, 64), (32, 64), (32, 128)) if args.quick else
                ((8, 32), (8, 64), (16, 32), (16, 64), (16, 128), (32, 32),
                 (32, 64), (32, 128), (64, 64), (64, 128)))
        for pages, cols in grid:
            ms = timed(lambda x, t, l_, s_, pages=pages, cols=cols:
                       paged_attention(x, kp3, vp3, t, l_, s_, pages=pages,
                                       tile_cols=cols),
                       (q, tbl, ln, st), reps)
            emit(kind="chunk", start=start, true_w=true_w,
                 pages=pages, tile_cols=cols, ms=ms)

    # ---- the scatter of a chunk's rows into either pool layout
    blk = jnp.asarray(rng.integers(1, NB, (1, 512)), jnp.int32)
    off = jnp.asarray(rng.integers(0, BS, (1, 512)), jnp.int32)
    new = jnp.asarray(rng.normal(size=(1, 512, KV, HD)), jnp.bfloat16)
    for name, pool, rows_ in (("(NB,bs,KV,hd)", kp4, new),
                              ("(NB,bs,KV*hd)", kp3,
                               new.reshape(1, 512, KV * HD))):
        fn = jax.jit(lambda p, r: p.at[blk, off].set(r), donate_argnums=0)
        pool = fn(pool + 0, rows_)
        jax.block_until_ready(pool)
        t = time.perf_counter()
        for _ in range(50):
            pool = fn(pool, rows_)
        jax.block_until_ready(pool)
        emit(kind="scatter512", layout=name,
             ms=1e3 * (time.perf_counter() - t) / 50)



def time_latent(args, rng, emit, tables_for):
    """The latent row at ``ms4-119b-ep4-serve-docqa``'s shapes: the decode
    step's shared-row walk, and the chunk's scatter into the stored row."""
    heads, lanes, nb, mb, s_n = (LAT[k] for k in ("heads", "lanes", "nb",
                                                  "mb", "streams"))
    pool = jnp.asarray(rng.normal(size=(nb, BS, lanes)), jnp.bfloat16)
    pool = pool.at[..., LAT["row"]:].set(0)
    prompts = np.clip(rng.lognormal(np.log(2048), 0.7, s_n), 512, 8192)
    lens = (prompts + rng.uniform(0, 256, s_n)).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(s_n, 1, heads, lanes)), jnp.bfloat16)
    q = q.at[..., LAT["row"]:].set(0)

    def walk(x, t, ln, st, pages):
        # the kernel's result is v_lanes wide: pad it back to a query so
        # that calls chain (the pad is what the step's own concat costs)
        u = paged_attention(x, pool, None, t, ln, st,
                            v_lanes=LAT["v_lanes"], scale=LAT["scale"],
                            pages=pages)
        return jnp.pad(u, [(0, 0)] * 3 + [(0, lanes - LAT["v_lanes"])])

    full = np.full((s_n,), BS * mb, np.int32)
    for kind, ls in (("latent_decode", lens),
                     ("latent_decode_full_width", full)):
        # the full width: 32 x 544 pages are more than the draw below may
        # hand out once, so every stream walks the same 544 scattered pages
        tables = (tables_for(ls, nb, mb) if kind == "latent_decode" else
                  jnp.tile(tables_for(ls[:1], nb, mb), (s_n, 1)))
        ls_j = jnp.asarray(ls)
        for pages in ((16, 32, 64) if args.quick else (8, 16, 32, 64, 128)):
            ms = timed(lambda x, t, ln, st, pages=pages: walk(
                x, t, ln, st, pages), (q, tables, ls_j, ls_j - 1), args.reps)
            emit(kind=kind, pages=pages, ms=ms, mean_len=float(ls.mean()),
                 live_gb_s=int(ls.sum()) * LAT["row"] * 2 / ms / 1e6)

    # ---- the scatter of a 1024-token chunk's rows into the stored row
    blk = jnp.asarray(rng.integers(1, nb, (1, 1024)), jnp.int32)
    off = jnp.asarray(rng.integers(0, BS, (1, 1024)), jnp.int32)
    new = jnp.asarray(rng.normal(size=(1, 1024, lanes)), jnp.bfloat16)
    fn = jax.jit(lambda p, r: p.at[blk, off].set(r), donate_argnums=0)
    pool = fn(pool, new)
    jax.block_until_ready(pool)
    t = time.perf_counter()
    for _ in range(50):
        pool = fn(pool, new)
    jax.block_until_ready(pool)
    emit(kind="latent_scatter1024", layout=f"(NB,bs,{lanes})",
         ms=1e3 * (time.perf_counter() - t) / 50)


def time_wide(args, rng, emit, tables_for):
    """The per-head row of 1024 lanes at ``kexaone-236b-ep8-serve-longmix``'s
    shapes: the full walk and the walk under a window of 128, decode and a
    1024-token chunk."""
    heads, kv, hd, nb, mb, s_n, win = 64, 8, 128, 52225, 1088, 48, 128
    lanes = kv * hd
    # 1.7 GB a pool: made on the device and handed to the programs as
    # arguments (a closure would bake each into every program as a constant)
    kp, vp = (jax.random.normal(jax.random.PRNGKey(i), (nb, BS, lanes),
                                jnp.bfloat16) for i in (1, 2))
    prompts = np.clip(rng.lognormal(np.log(4096), 0.7, s_n), 1024, 16384)
    lens = (prompts + rng.uniform(0, 512, s_n)).astype(np.int32)
    tables, lens_j = tables_for(lens, nb, mb), jnp.asarray(lens)
    q = jnp.asarray(rng.normal(size=(s_n, 1, heads, hd)), jnp.bfloat16)
    row = 2 * lanes * 2                     # K and V bytes of one key

    def walk(x, k_, v_, t, ln, st, **kw):
        return paged_attention(x, k_, v_, t, ln, st, **kw)

    def timed_or_refused(fn, a, reps):
        """ms, or None where the chip's compiler refuses the tiling (a row
        tile of 1024 rows does not fit VMEM beside its landing buffers)."""
        try:
            return timed(fn, a, reps)
        except Exception as e:                              # noqa: BLE001
            print(f"refused: {str(e)[:160]}", file=sys.stderr)
            return None

    for pages in ((32, 64) if args.quick else (16, 32, 64, 128)):
        ms = timed(lambda x, *a, pages=pages: walk(x, *a, pages=pages),
                   (q, kp, vp, tables, lens_j, lens_j - 1), args.reps)
        emit(kind="wide_decode", pages=pages, ms=ms,
             mean_len=float(lens.mean()),
             live_gb_s=int(lens.sum()) * row / ms / 1e6)
    for pages in ((9, 16) if args.quick else (3, 5, 9, 12, 16)):
        ms = timed(lambda x, *a, pages=pages: walk(
            x, *a, pages=pages, window=win),
            (q, kp, vp, tables, lens_j, lens_j - 1), args.reps)
        emit(kind="wide_decode_window", pages=pages, ms=ms, window=win,
             live_gb_s=int(np.minimum(lens, win).sum()) * row / ms / 1e6)
    q = jnp.asarray(rng.normal(size=(1, 1024, heads, hd)), jnp.bfloat16)
    reps = max(4, args.reps // 4)
    for start in ((4096,) if args.quick else (0, 4096, 12288)):
        ln = jnp.asarray([start + 1024], jnp.int32)
        st = jnp.asarray([start], jnp.int32)
        tbl = tables_for([start + 1024], nb, mb)
        for pages, cols in (((32, 64), (64, 64)) if args.quick else
                            ((16, 64), (32, 32), (32, 64), (64, 32),
                             (64, 64), (32, 128))):
            ms = timed_or_refused(lambda x, *a, pages=pages, cols=cols: walk(
                x, *a, pages=pages, tile_cols=cols),
                (q, kp, vp, tbl, ln, st), reps)
            emit(kind="wide_chunk", start=start, pages=pages,
                 tile_cols=cols, ms=ms)
        for pages, cols in (((13, 64), (17, 128)) if args.quick else
                            ((6, 32), (11, 32), (7, 64), (13, 64),
                             (16, 64), (17, 128))):
            ms = timed_or_refused(lambda x, *a, pages=pages, cols=cols: walk(
                x, *a, pages=pages, tile_cols=cols, window=win),
                (q, kp, vp, tbl, ln, st), reps)
            emit(kind="wide_chunk_window", start=start, pages=pages,
                 tile_cols=cols, window=win, ms=ms)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--quick", action="store_true",
                    help="the table's rows and their neighbours only")
    ap.add_argument("--seed", type=int, default=30)
    ap.add_argument("--rows", default="per_head,latent,wide",
                    help="which cache rows to time")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("paged_attn_timing: no TPU; a CPU timing says nothing",
              file=sys.stderr)
        return 3
    rng = np.random.default_rng(args.seed)
    rows = []

    def emit(**row):
        row["device"] = dev.device_kind
        rows.append(row)
        print(json.dumps(row), flush=True)

    def tables_for(lens, nb, mb):
        """Each stream's live pages scattered over the pool, the rest at
        the sink."""
        tables = np.zeros((len(lens), mb), np.int32)
        free = rng.permutation(np.arange(1, nb))
        at = 0
        for i, ln in enumerate(lens):
            n = -(-int(ln) // BS)
            tables[i, :n] = free[at:at + n]
            at += n
        return jnp.asarray(tables)

    if "per_head" in args.rows:
        time_per_head(args, rng, emit, tables_for)
    if "latent" in args.rows:
        time_latent(args, rng, emit, tables_for)
    if "wide" in args.rows:
        time_wide(args, rng, emit, tables_for)

    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "paged_attn_timing.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
