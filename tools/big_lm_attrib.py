"""On-chip step-time attribution for the flagship config (round 4).

The seed-era sweep pinned big_lm at MFU 0.320 (163.6 ms/step, b8, no
remat) and refuted the batch lever; closing the remaining 1.25x to the
0.4 bar (130.8 ms) needs to know WHERE the 163 ms goes.  This attributes
by differencing — every variant is the full jitted train step with one
dial moved (ROADMAP S2 prefers the split from a device trace):

* ``layers6``  — n_layers 12 -> 6, same head/embed.  per-layer cost =
  (T12 - T6) / 6; head + embed + optimizer + dispatch = T12 - 12 x that.
* ``fwd_only`` — jit of the loss (no grad, no update): fwd vs bwd split.
* ``no_update`` — value_and_grad but SGD update replaced by a no-op
  (params returned unchanged): isolates the optimizer+donation cost.
* ``d_ff_half`` — d_ff 4096 -> 2048: FFN share by differencing (the FFN
  is 57% of matmul FLOPs; if time drops by less, the FFN runs at higher
  efficiency than the rest — or vice versa).

Writes ``BIGLM_ATTRIB.json``.  Usage: ``python tools/big_lm_attrib.py``
(needs a TPU; exits 2 without one).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import bench  # noqa: E402

ARTIFACT = os.path.join(REPO, "BIGLM_ATTRIB.json")


def build(n_layers=None, d_ff=None):
    import jax.numpy as jnp

    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )

    # mirrors the COMMITTED flagship (bench.py big_lm make_model): no
    # remat, unrolled layers, fused ce_chunk=256 — the round-4 sweep
    # winner the attribution must explain (b8_none_unroll_*)
    c = bench._BIG
    return Transformer(TransformerConfig(
        vocab_size=c["vocab"], max_seq_len=c["seq"],
        n_layers=n_layers or c["n_layers"], d_model=c["d_model"],
        n_heads=c["n_heads"], d_ff=d_ff or c["d_ff"],
        compute_dtype=jnp.bfloat16, attention="flash", scan_layers=False,
        remat=False, remat_policy="dots", ce_chunk=256))


def timed(fn, *args, n1=10, n2=30):
    t1, *_ = bench.timed_chain(fn, *args, n1)
    t2, _, out = bench.timed_chain(fn, *args, n2)
    return max(t2 - t1, 1e-9) / (n2 - n1) * 1e3, out


def main() -> int:
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        platform as plat,
    )

    try:
        plat.select("tpu", log=lambda m: print(m, file=sys.stderr))
    except plat.PlatformUnavailable as e:
        print(f"ERROR: {e}", file=sys.stderr)
        return 2
    plat.compile_cache()

    import jax

    from neural_networks_parallel_training_with_mpi_tpu.config import (
        MeshConfig,
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

    c = bench._BIG
    batch = 8
    mesh = mesh_lib.make_mesh(MeshConfig(data=1),
                              devices=jax.devices()[:1])
    opt = optim.sgd(lr=1e-4, momentum=0.9)
    rng = np.random.default_rng(0)
    raw = {"x": rng.integers(0, c["vocab"], (batch, c["seq"])).astype(np.int32),
           "y": rng.integers(0, c["vocab"], (batch, c["seq"])).astype(np.int32),
           "mask": np.ones((batch,), np.float32)}
    placed = shd.shard_batch(mesh, raw)

    rows = []

    def record(label, fn):
        t0 = time.perf_counter()
        try:
            row = fn()
            row["label"] = label
        except Exception as e:  # noqa: BLE001 — record, continue
            row = {"label": label,
                   "error": f"{type(e).__name__}: {e}"[:400]}
        row["elapsed_s"] = round(time.perf_counter() - t0, 1)
        if "error" not in row:
            row["platform"] = info.get("platform")
            row["device_kind"] = info.get("device_kind")
        print(f"[big_lm_attrib] {json.dumps(row)}", flush=True)
        rows.append(row)
        # flush after EVERY variant: the first run of this tool lost all
        # five measurements to a watchdog timeout because it wrote only at
        # the end — each chip-minute is too scarce for that
        flush(rows)

    def full_step(model):
        state = dp.replicate_state(TrainState.create(model, opt,
                                                     prng.init_key(0)), mesh)
        step = dp.make_train_step(model, opt, mesh, "cross_entropy",
                                  "global_mean", donate=False)
        bench.timed_chain(step, state, placed, 2)  # compile
        ms, _ = timed(step, state, placed)
        return ms

    def var_full():
        return {"step_ms": round(full_step(build()), 2)}

    def var_layers6():
        return {"step_ms": round(full_step(build(n_layers=6)), 2)}

    def var_dff_half():
        return {"step_ms": round(full_step(build(d_ff=2048)), 2)}

    # timed_chain's only sync is device_get of the FINAL value, which is
    # valid ONLY when every iteration depends on the previous one (its
    # docstring).  The fwd-only/grad-only chains below therefore thread the
    # previous scalar INTO each program (prev * 1e-30 added to the loss —
    # numerically invisible, but a real data dependence XLA cannot fold
    # away, unlike `0.0 * prev` which fast-math may) so the final value
    # transitively forces the whole chain.
    def var_fwd_only():
        model = build()
        state = dp.replicate_state(TrainState.create(model, opt,
                                                     prng.init_key(0)), mesh)
        loss_fn = dp.make_loss_fn(model, "cross_entropy")

        @jax.jit
        def fwd(prev, b):
            s, cnt = loss_fn(state.params, b)
            return s / cnt + prev * 1e-30

        def chainable(carry, b):  # timed_chain wants (state-like, batch)
            out = fwd(carry, b)
            return out, out

        import jax.numpy as jnp

        zero = jnp.zeros((), jnp.float32)
        bench.timed_chain(chainable, zero, placed, 2)
        ms, _ = timed(chainable, zero, placed)
        return {"fwd_ms": round(ms, 2)}

    def var_no_update():
        model = build()
        state = dp.replicate_state(TrainState.create(model, opt,
                                                     prng.init_key(0)), mesh)
        loss_fn = dp.make_loss_fn(model, "cross_entropy")

        @jax.jit
        def grad_only(prev, b):
            def scalar(p):
                s, cnt = loss_fn(p, b)
                return s / cnt

            l, g = jax.value_and_grad(scalar)(state.params)
            # reduce the grads to a scalar so the timed chain depends on
            # the whole backward without materializing an update
            return (l + prev * 1e-30
                    + sum(jax.tree_util.tree_map(
                        lambda x: x.sum().astype(l.dtype),
                        jax.tree_util.tree_leaves(g))))

        def chainable(carry, b):
            out = grad_only(carry, b)
            return out, out

        import jax.numpy as jnp

        zero = jnp.zeros((), jnp.float32)
        bench.timed_chain(chainable, zero, placed, 2)
        ms, _ = timed(chainable, zero, placed)
        return {"fwd_bwd_ms": round(ms, 2)}

    record("full", var_full)
    record("layers6", var_layers6)
    record("fwd_only", var_fwd_only)
    record("no_update", var_no_update)
    record("dff_half", var_dff_half)

    derived = flush(rows)
    print(json.dumps({"attrib_artifact": "BIGLM_ATTRIB.json",
                      "derived": derived}))
    return 0


def flush(rows) -> dict:
    """Derive the attribution from ``rows`` and write the artifact.
    Called after every variant so a timeout costs at most the in-flight
    measurement."""
    import time as _t

    merged = list(rows)
    by = {r["label"]: r for r in merged}
    derived = {}
    if "step_ms" in by.get("full", {}) and "step_ms" in by.get("layers6", {}):
        per_layer = (by["full"]["step_ms"] - by["layers6"]["step_ms"]) / 6.0
        derived["per_layer_ms"] = round(per_layer, 2)
        derived["layers_total_ms"] = round(12 * per_layer, 2)
        derived["head_embed_opt_dispatch_ms"] = round(
            by["full"]["step_ms"] - 12 * per_layer, 2)
    if "fwd_ms" in by.get("fwd_only", {}) and "step_ms" in by.get("full", {}):
        derived["bwd_plus_update_ms"] = round(
            by["full"]["step_ms"] - by["fwd_only"]["fwd_ms"], 2)
    if ("fwd_bwd_ms" in by.get("no_update", {})
            and "step_ms" in by.get("full", {})):
        derived["update_ms"] = round(
            by["full"]["step_ms"] - by["no_update"]["fwd_bwd_ms"], 2)
    if "step_ms" in by.get("full", {}) and "step_ms" in by.get("dff_half", {}):
        derived["dff_half_delta_ms"] = round(
            by["full"]["step_ms"] - by["dff_half"]["step_ms"], 2)
    doc = {"results": merged, "derived": derived,
           "captured_unix": round(_t.time(), 1),
           "captured_iso": _t.strftime("%Y-%m-%dT%H:%M:%SZ", _t.gmtime())}
    with open(ARTIFACT, "w") as f:
        json.dump(doc, f, indent=2)
    return derived


if __name__ == "__main__":
    sys.exit(main())
