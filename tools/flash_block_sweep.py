"""Flash-attention block-size sweep for the 1k-2k regime (VERDICT r4
item 4).

The seed-era attention capture (compiled, TPU v5 lite) shows the Pallas
kernel LOSING kernel-only below the 4k crossover — 0.91x at T=1024, 0.98x
at T=2048 (head_dim 64) — which says the default 128x128 tiles are wrong
for short sequences, not that flash is.  This sweeps block_q x block_k
over the exact deficit shapes, plus the head_dim-128 geometry queued by
the round-4b head sweep (n_heads 8->4 at constant H*D is a pure reshape
that fills the (8,128) lane tiles), and records dense alongside so the
"kernel-only >= 1.0x at T=2048" bar is answered by a number.

Artifact: ``FLASH_BLOCK_SWEEP.json``.  Timings are fwd+bwd (grad of
sum), matching the bench's kernel-only rows.  The platform is whatever
JAX brings up (utils.platform.select("auto")) and every row names it: on
a CPU the kernel runs in interpret mode, so the sweep records a skip note
and one tiny mechanism row instead of 21 meaningless emulation timings.
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

# (label, batch, seq, heads, head_dim) — the two measured-deficit shapes
# at head_dim 64, and the head_dim-128 geometry from the h8->h4 reshape
SHAPES = [
    ("t1024_h8_d64", 8, 1024, 8, 64),
    ("t2048_h8_d64", 4, 2048, 8, 64),
    ("t2048_h4_d128", 4, 2048, 4, 128),
]
BLOCKS = [(128, 128), (128, 256), (256, 128), (256, 256),
          (128, 512), (512, 128), (512, 512)]


def time_grad(fn, args, reps):
    import jax

    g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
    jax.block_until_ready(g(*args))           # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        outs = g(*args)
    jax.block_until_ready(outs)
    return round((time.perf_counter() - t0) / reps * 1e3, 3)


def main() -> int:
    plat.select("auto", log=lambda m: print(m, file=sys.stderr))
    plat.compile_cache()

    import jax
    import jax.numpy as jnp

    from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels import (
        flash_attention,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel.sequence import (
        attention_reference,
    )

    platform = jax.devices()[0].platform
    doc = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "captured_unix": round(time.time(), 1),
        "captured_iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "note": "fwd+bwd kernel-only block_q x block_k sweep at the "
                "sub-4k deficit shapes; dense column is the >=1.0x bar",
        "rows": [],
    }
    rng = np.random.default_rng(0)
    cd = jnp.bfloat16 if platform != "cpu" else jnp.float32
    shapes = SHAPES if platform != "cpu" else [("t128_h2_d32_cpu_mech",
                                                1, 128, 2, 32)]
    blocks = BLOCKS if platform != "cpu" else [(64, 64), (128, 128)]
    if platform == "cpu":
        doc["skipped"] = ("cpu: pallas interpret-mode timings "
                          "say nothing about MXU tiling; mechanism row "
                          "only")
    reps = 20 if platform != "cpu" else 2

    for label, b, seq, h, dh in shapes:
        qkv = [jnp.asarray(rng.standard_normal((b, seq, h, dh)), cd)
               for _ in range(3)]

        def dense_loss(q, k, v):
            return jnp.sum(attention_reference(q, k, v,
                                               causal=True)
                           .astype(jnp.float32))

        row = {"shape": label, "batch": b, "seq": seq, "heads": h,
               "head_dim": dh,
               "dense_ms": time_grad(dense_loss, qkv, reps)}
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
        if best[1] is not None:
            row["best_block"] = f"{best[0][0]}x{best[0][1]}"
            row["best_flash_ms"] = best[1]
            row["best_flash_vs_dense"] = round(row["dense_ms"] / best[1],
                                               3)
        print(f"[flash_sweep] {json.dumps(row)}", flush=True)
        doc["rows"].append(row)
        with open(os.path.join(REPO, "FLASH_BLOCK_SWEEP.json"), "w") as f:
            json.dump(doc, f, indent=2)   # flush per shape: a mid-run
            # failure keeps completed rows

    print(json.dumps({"metric": "flash_block_sweep_rows",
                      "value": len(doc["rows"]), "unit": "rows",
                      "platform": platform,
                      "sweep_artifact": "FLASH_BLOCK_SWEEP.json"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
