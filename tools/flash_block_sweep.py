"""Flash-attention tiling sweep on the chip: where the table rows of
``parallel.sequence.AUTO_FLASH_MIN_SEQ`` and ``ops.pallas_kernels.FLASH_BLOCKS``
come from.

    chiprun --chips 1 -- python tools/flash_block_sweep.py

For each shape it times forward + backward (grad of a sum over q, k, v;
causal; bf16) of: the XLA dense path (``attention_reference``, what ``auto``
picked before), this repo's kernels at every ``block_q x block_k`` of
``BLOCKS``, and, as a yardstick that is not shipped, JAX's own
``jax.experimental.pallas.ops.tpu.flash_attention`` at its default tiling and
at 512s.  Ours is timed from and to the model's ``(B, T, H, D)`` layout; the
library kernel is handed its own ``(B, H, T, D)`` layout.  ``flash_hm_*`` is
ours forced to the heads-major layout, one head a program over (B*H, T, D)
and a transpose around every operand (what reading the model's own layout is
worth; in a model those transposes cost more than they do here, PERF.md).
The shapes: the training cells' (B 4, T 1024, H 16 x 64), ``starcoder2-3b``'s head
(H 24 x 128), head_dim 128 at the FLOPs of the first (H 8 x 128: what
half-filled lanes cost), and T 512 / 2048 / 4096 at head_dim 64 (either side
of the table's row, and whether the derived tiling holds at longer T).

Artifact: ``FLASH_BLOCK_SWEEP.json`` (also under ``chiprun_out/``).  The
platform is whatever JAX brings up and every row names it: on a CPU the
kernels run in interpret mode, so the sweep records a skip note and one tiny
mechanism row instead of meaningless emulation timings.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from neural_networks_parallel_training_with_mpi_tpu.utils import (  # noqa: E402
    platform as plat,
)

# (label, batch, seq, heads, head_dim)
SHAPES = [
    ("b4_t1024_h16_d64", 4, 1024, 16, 64),
    ("b4_t1024_h24_d128", 4, 1024, 24, 128),
    ("b4_t1024_h8_d128", 4, 1024, 8, 128),
    ("b8_t512_h16_d64", 8, 512, 16, 64),
    ("b2_t2048_h16_d64", 2, 2048, 16, 64),
    ("b1_t4096_h16_d64", 1, 4096, 16, 64),
]
_SIZES = (128, 256, 512, 1024)
BLOCKS = [(bq, bk) for bq in _SIZES for bk in _SIZES]
HEADS_MAJOR_BLOCKS = [(256, 256), (256, 512), (512, 512), (512, 1024),
                      (1024, 1024)]


def time_grad(fn, args, reps):
    import jax

    g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
    jax.block_until_ready(g(*args))           # compile
    jax.block_until_ready(g(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        outs = g(*args)
    jax.block_until_ready(outs)
    return round((time.perf_counter() - t0) / reps * 1e3, 4)


def library_rows(qkv, reps):
    """JAX's own TPU flash kernel, forward + backward, (B, H, T, D) in."""
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import flash_attention as lib

    q, k, v = (x.transpose(0, 2, 1, 3) for x in qkv)
    t, d = q.shape[2], q.shape[3]
    out = {}
    for label, bs in (("default", None), ("512", min(512, t))):
        sizes = None if bs is None else lib.BlockSizes(
            block_q=bs, block_k_major=bs, block_k=bs, block_b=1,
            block_q_major_dkv=bs, block_k_major_dkv=bs, block_k_dkv=bs,
            block_q_dkv=bs, block_k_major_dq=bs, block_k_dq=bs,
            block_q_dq=bs)

        def loss(q_, k_, v_, _s=sizes):
            return jnp.sum(lib.flash_attention(
                q_, k_, v_, causal=True, sm_scale=d ** -0.5,
                block_sizes=_s).astype(jnp.float32))

        try:
            out[f"library_{label}_ms"] = time_grad(loss, (q, k, v), reps)
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            out[f"library_{label}_error"] = str(e)[:200]
    return out


def heads_major_rows(qkv, reps):
    """Ours with every head a program of its own over (B*H, T, D)."""
    import jax
    import jax.numpy as jnp

    from neural_networks_parallel_training_with_mpi_tpu.ops import (
        pallas_kernels as pk,
    )

    plan, out = pk._fold_plan, {}
    pk._fold_plan = lambda h, d: (1, False)
    jax.clear_caches()          # the jitted calls keyed the folded plan
    try:
        for bq, bk in HEADS_MAJOR_BLOCKS:
            if bq > qkv[0].shape[1] or bk > qkv[0].shape[1]:
                continue

            def loss(q, k, v, _bq=bq, _bk=bk):
                return jnp.sum(pk.flash_attention(
                    q, k, v, True, block_q=_bq, block_k=_bk)
                    .astype(jnp.float32))

            try:
                out[f"flash_hm_{bq}x{bk}_ms"] = time_grad(loss, qkv, reps)
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                out[f"flash_hm_{bq}x{bk}_error"] = str(e)[:200]
    finally:
        pk._fold_plan = plan
        jax.clear_caches()
    return out


def main() -> int:
    plat.select("auto", log=lambda m: print(m, file=sys.stderr))
    plat.compile_cache()

    import jax
    import jax.numpy as jnp

    from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (
        flash_blocks, flash_attention,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel.sequence import (
        attention_reference,
    )

    platform = jax.devices()[0].platform
    doc = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "jax": jax.__version__,
        "captured_unix": round(time.time(), 1),
        "captured_iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "note": "fwd+bwd ms, causal, bf16: dense, ours at block_q x "
                "block_k (model layout in and out), JAX's library kernel",
        "rows": [],
    }
    rng = np.random.default_rng(0)
    on_chip = platform != "cpu"
    cd = jnp.bfloat16 if on_chip else jnp.float32
    shapes = SHAPES if on_chip else [("t128_h2_d32_cpu_mech", 1, 128, 2, 32)]
    blocks = BLOCKS if on_chip else [(64, 64), (128, 128)]
    if not on_chip:
        doc["skipped"] = ("cpu: pallas interpret-mode timings "
                          "say nothing about MXU tiling; mechanism row "
                          "only")
    reps = 30 if on_chip else 2
    outs = [os.path.join(REPO, "FLASH_BLOCK_SWEEP.json")]
    if on_chip:
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        outs.append(os.path.join(REPO, "chiprun_out",
                                 "FLASH_BLOCK_SWEEP.json"))

    for label, b, seq, h, dh in shapes:
        qkv = [jnp.asarray(rng.standard_normal((b, seq, h, dh)), cd)
               for _ in range(3)]

        def dense_loss(q, k, v):
            return jnp.sum(attention_reference(q, k, v,
                                               causal=True)
                           .astype(jnp.float32))

        row = {"shape": label, "batch": b, "seq": seq, "heads": h,
               "head_dim": dh, "dtype": jnp.dtype(cd).name,
               "derived_block": flash_blocks(seq, dh, cd),
               "dense_ms": time_grad(dense_loss, qkv, reps)}
        if on_chip:
            row.update(library_rows(qkv, reps))
        best = (None, None)
        for bq, bk in blocks:
            if bq > seq or bk > seq:
                continue

            def flash_loss(q, k, v, _bq=bq, _bk=bk):
                return jnp.sum(flash_attention(q, k, v, True,
                                               block_q=_bq, block_k=_bk)
                               .astype(jnp.float32))

            try:
                ms = time_grad(flash_loss, qkv, reps)
            except Exception as e:  # noqa: BLE001 — record, keep sweeping
                row[f"flash_{bq}x{bk}_error"] = str(e)[:200]
                continue
            row[f"flash_{bq}x{bk}_ms"] = ms
            if best[1] is None or ms < best[1]:
                best = ((bq, bk), ms)
        if on_chip:
            row.update(heads_major_rows(qkv, reps))
        if best[1] is not None:
            row["best_block"] = f"{best[0][0]}x{best[0][1]}"
            row["best_flash_ms"] = best[1]
            row["best_flash_vs_dense"] = round(row["dense_ms"] / best[1],
                                               3)
        print(f"[flash_sweep] {json.dumps(row)}", flush=True)
        doc["rows"].append(row)
        for path in outs:           # flush per shape: a mid-run failure
            with open(path, "w") as f:      # keeps completed rows
                json.dump(doc, f, indent=2)

    print(json.dumps({"metric": "flash_block_sweep_rows",
                      "value": len(doc["rows"]), "unit": "rows",
                      "platform": platform,
                      "sweep_artifact": "FLASH_BLOCK_SWEEP.json"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
