"""On-chip big_lm MFU sweep (VERDICT r3 item 2 follow-through).

The 0.4 MFU bar needs <= ~131 ms/step.  This tool sweeps the two
HBM<->speed dials — batch size and remat policy — in ONE process (one
chip, one process; shared compile cache) and records every variant to
``BIGLM_SWEEP.json``.  OOM variants are caught and recorded, not fatal:
v5e RESOURCE_EXHAUSTED raises cleanly.

Usage:  python tools/big_lm_sweep.py     # needs a TPU; exits 2 without one
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import bench  # noqa: E402  (importable by design; main() is guarded)

# (label, batch, remat?, policy, attention, ce_chunk, scan_layers)
#
# ce_chunk > 0 = fused chunked cross-entropy (TransformerConfig.ce_chunk):
# the (B, T, 32k) f32 logits tensor is never materialized.  Measured XLA
# temp bytes (CPU buffer-assignment proxy, BENCH_PREFLIGHT.json
# ce_chunk_variants; BASELINE.md documents the early/late-pin accounting
# caveat): b8 6.9 -> 4.5 GB, b16 fits at 9.0 GB, b32 18.1 GB — over the
# CPU proxy's budget but in-budget under the test env's accounting, so
# it stays as an OOM-tolerant stretch bet (run_variant records OOM and
# continues; b32_full_ce256 is the fallback).  The main 0.298 -> 0.4 MFU
# lever is the 2-4x batch headroom at unchanged matmul FLOPs.
# Dense-attention variants probe the other known deficit: the compiled
# flash kernel only crosses over dense at T=2048 (seed-era capture) but
# big_lm runs at T=1024.
# Round-1 of this sweep (chip-captured 2026-07-31T01:04Z) answered the
# batch/remat question: b16/b32 with any remat policy all land at MFU
# 0.283-0.288 vs b8_dots 0.295 — per-token step time is flat, so batch
# headroom buys nothing — while **no remat at b8 FIT the real chip and
# hit MFU 0.320** (163.4 ms; the 17 GB CPU-proxy temp estimate was
# pessimistic).  Round-2 variants therefore start from no-remat and
# attack step time directly: fused chunked CE (kills ~2.7 GB of logits
# HBM traffic per step) and dense attention at big_lm's exact shapes
# (the compiled kernel-only bench reads ~parity at T>=2048 and the
# small-model full-step reads flash 1.046x at T=1024 — big_lm's
# d_model/heads may tip either way).
# Round-2 (chip 01:21Z): b8_none_ce256 0.3145 (chunking is perf-neutral
# at this batch — its win is capacity, not speed), b12_none_ce256 0.297
# (batch >8 *degrades* per-token time), b8_none re-anchored at 0.3195;
# dense variants + b16 died on a remote-compile-helper HTTP 500
# (INTERNAL, not OOM — retried below).  Round-3 variants probe the next
# suspect: lax.scan over layers serializes XLA's scheduler at every
# layer boundary, so unrolled (scan_layers=False) may overlap better.
# Round-4 variants attack the head geometry: n_heads only changes the
# head RESHAPE of the same (d, 3d)/(d, d) projections — zero parameter
# or FLOP delta — but head_dim 64 (h16) leaves half of every (8, 128)
# vector lane empty in the flash kernel's q/k/v tiles and runs the MXU
# score/value matmuls at K=64; head_dim 128 (h8) is exactly one lane
# tile, head_dim 256 (h4) two.  The last tuple slot overrides bench._BIG
# keys for the variant (recorded in the row's `config`, so the sweep's
# `best` gate keeps shape-mismatched rows from waiving the committed
# config's preflight until bench._BIG itself is flipped to the winner).
# Round-4b stacks the head-geometry lever on the measured round-4a
# winner (no remat, UNROLLED layers, fused ce_chunk=256 — MFU 0.3778 at
# h16): every variant below keeps that base.  The dense retry gets a
# fresh label because the two prior 500s were at scan=True shapes.
VARIANTS = [
    ("b8_unroll_ce256_h8", 8, False, "dots", "flash", 256, False,
     {"n_heads": 8}),
    ("b8_unroll_ce256_h4", 8, False, "dots", "flash", 256, False,
     {"n_heads": 4}),
    ("b8_unroll_ce256_h8_bk256", 8, False, "dots", "flash", 256, False,
     {"n_heads": 8, "flash_block_k": 256}),
    ("b8_unroll_ce256_bk512", 8, False, "dots", "flash", 256, False,
     {"flash_block_k": 512}),
    ("b8_unroll_ce256_h8_dense", 8, False, "dots", "dense", 256, False,
     {"n_heads": 8}),
    # round 5 (VERDICT r4 item 5): blockwise dense — identical math to
    # dense with a (B,H,256,T) scores temp per scan tick, so the remote
    # compile helper never sees the (B,H,T,T) tensor its suspected-
    # systematic HTTP 500 keys on.  Answers "is flash the right choice
    # at big_lm shape" even if full dense keeps 500ing.
    ("b8_unroll_ce256_h8_dense_blockwise", 8, False, "dots",
     "dense_blockwise", 256, False, {"n_heads": 8}),
    ("b8_unroll_ce256_dense_blockwise", 8, False, "dots",
     "dense_blockwise", 256, False, {}),
]


def run_variant(label, batch, remat, policy, attention, ce_chunk=0,
                scan_layers=True, overrides=None):
    import jax
    import jax.numpy as jnp

    from neural_networks_parallel_training_with_mpi_tpu.config import MeshConfig
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.ops import optim
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        data_parallel as dp,
        mesh as mesh_lib,
        sharding as shd,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.state import (
        TrainState,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    c = {**bench._BIG, **(overrides or {})}
    # override keys that are not bench._BIG shape knobs pass straight
    # through as TransformerConfig kwargs (e.g. flash_block_q/block_k)
    extra = {k: v for k, v in (overrides or {}).items()
             if k not in bench._BIG}
    devices = jax.devices()
    on_tpu = devices[0].platform not in ("cpu",)
    model = Transformer(TransformerConfig(
        vocab_size=c["vocab"], max_seq_len=c["seq"], n_layers=c["n_layers"],
        d_model=c["d_model"], n_heads=c["n_heads"], d_ff=c["d_ff"],
        compute_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        attention=attention, scan_layers=scan_layers, remat=remat,
        remat_policy=policy, ce_chunk=ce_chunk, **extra))
    mesh = mesh_lib.make_mesh(MeshConfig(data=len(devices)),
                              devices=devices)
    opt = optim.sgd(lr=1e-4, momentum=0.9)
    state = dp.replicate_state(TrainState.create(model, opt,
                                                 prng.init_key(0)), mesh)
    step = dp.make_train_step(model, opt, mesh, "cross_entropy",
                              "global_mean")
    rng = np.random.default_rng(0)
    raw = {"x": rng.integers(0, c["vocab"], (batch, c["seq"])).astype(np.int32),
           "y": rng.integers(0, c["vocab"], (batch, c["seq"])).astype(np.int32),
           "mask": np.ones((batch,), np.float32)}
    placed = shd.shard_batch(mesh, raw)
    t0 = time.perf_counter()
    _, state, _ = bench.timed_chain(step, state, placed, 2)
    compile_s = time.perf_counter() - t0
    n1, n2 = 10, 30
    t1, state, _ = bench.timed_chain(step, state, placed, n1)
    t2, state, loss = bench.timed_chain(step, state, placed, n2)
    step_ms = max(t2 - t1, 1e-9) / (n2 - n1) * 1e3
    fwd = model.fwd_flops(raw["x"].shape)
    peak = bench.peak_flops(devices[0].device_kind) if on_tpu else None
    mfu = (3.0 * fwd / (step_ms / 1e3) / (peak * len(devices))
           if peak and fwd else None)
    return {
        "label": label, "batch": batch, "remat": remat, "policy": policy,
        "attention": attention, "ce_chunk": ce_chunk,
        "scan_layers": scan_layers,
        # the model shapes this row was measured at (SHAPE keys only:
        # non-shape overrides — kernel tile knobs — ride in tf_overrides)
        "config": {k: c[k] for k in bench._BIG},
        "tf_overrides": extra,
        "step_ms": round(step_ms, 2),
        "samples_per_sec": round(batch / step_ms * 1e3, 1),
        "mfu": None if mfu is None else round(mfu, 4),
        "loss": float(loss), "compile_s": round(compile_s, 1),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
    }


def main() -> int:
    # this process is the one that touches the chip: the backend comes up
    # here, and anything but a TPU is an error (utils.platform.select)
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        platform as plat,
    )

    try:
        plat.select("tpu", log=lambda m: print(m, file=sys.stderr))
    except plat.PlatformUnavailable as e:
        print(f"ERROR: {e}", file=sys.stderr)
        return 2
    plat.compile_cache()
    rows = []
    for variant in VARIANTS:
        label = variant[0]
        try:
            row = run_variant(*variant)
        except Exception as e:  # OOM or lowering failure: record, continue
            row = {"label": label, "error": f"{type(e).__name__}: {e}"[:400]}
        print(f"[big_lm_sweep] {json.dumps(row)}", flush=True)
        rows.append(row)
    best = max((r for r in rows if r.get("mfu")),
               key=lambda r: r["mfu"], default=None)
    doc = {"results": rows, "best": best,
           "captured_unix": round(time.time(), 1),
           "captured_iso": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime())}
    with open(os.path.join(REPO, "BIGLM_SWEEP.json"), "w") as f:
        json.dump(doc, f, indent=2)
    print(json.dumps({"sweep_artifact": "BIGLM_SWEEP.json",
                      "best": best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
