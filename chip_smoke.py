#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, run from the root of a checkout on a machine with a TPU:

    python chip_smoke.py             # one chip: device, train, serve, serve_fused
    python chip_smoke.py --chips 4   # four chips: data-parallel training only

It drives the main path through the entry points a user calls — the trainer
through ``cli.main``, the paged server through ``Scheduler`` — at the full
width of ``big_lm`` (``BIG_LM`` below: 12 layers, d_model 1024, 16 heads x
head_dim 64, d_ff 4096, vocab 32768, T 1024, bf16), with seeded random
weights, and checks what comes out by the repo's own means.

There is no CPU path and no option that gives one: phase ``device`` fails
unless JAX finds a TPU.  The phases are plain functions that take their
sizes as arguments, so tests/test_chip_smoke.py rehearses them on the CPU at
a tiny size by calling them — the steering lives in the test.

Output: one JSON line before each phase (``"status": "start"``) and one after
it (``"status": "ok"``, the seconds it took, what it checked).  A failing
phase prints its name and the full traceback to stderr and the process exits
1 at once.  The last line of stdout, and nothing more on it, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Everything it writes goes under ``<checkout>/chip_smoke_out`` (git-ignored);
the compile cache is placed by ``utils.platform.compile_cache``.  It starts
no child process and no thread that outlives its phase.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chip_smoke_out"

# big_lm — the invented shape this smoke has always run
BIG_LM = dict(vocab_size=32768, seq_len=1024, n_layers=12, d_model=1024,
              n_heads=16, d_ff=4096)
# serving geometry at that width, and six ragged prompts
SERVE = dict(slots=8, num_blocks=513, block_size=16, prefill_chunk=128,
             prompt_lens=(5, 40, 130, 300, 450, 700), max_new=(16, 32))
# Teacher-forced tolerance.  Seeded random weights give near-uniform logits,
# so batched-vs-single bf16 reductions can flip greedy near-ties for no
# fault of the program.  At every generated position the served token's
# reference logit must lie within this many standard deviations (of that
# position's reference logits over the vocabulary) of the reference maximum;
# a token drawn from a broken cache sits ~4 sigma below it at vocab 32768.
MARGIN_SIGMA = 0.5


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def run_phase(name: str, fn, *args, **kwargs) -> dict:
    """Announce, run and time one phase; on any exception name the phase,
    print the traceback and exit non-zero at once."""
    emit({"phase": name, "status": "start"})
    t0 = time.perf_counter()
    try:
        checked = fn(*args, **kwargs)
    except BaseException:
        sys.stdout.flush()
        print(f"chip_smoke: phase {name!r} FAILED", file=sys.stderr)
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    emit({"phase": name, "status": "ok",
          "seconds": round(time.perf_counter() - t0, 3), **checked})
    return checked


def _peak_bytes() -> list:
    """``peak_bytes_in_use`` per local device (empty where the backend
    reports no memory stats, as the CPU's does not)."""
    from neural_networks_parallel_training_with_mpi_tpu.utils.profiling \
        import device_memory_stats

    return [s.get("peak_bytes_in_use")
            for s in device_memory_stats().values()]


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _compile_seconds(trace_dir: Path) -> dict:
    """Compile seconds by program name, from the compile ledger's files."""
    out: dict = {}
    for path in sorted(trace_dir.glob("compiles-*.jsonl")):
        for line in path.read_text().splitlines():
            rec = json.loads(line)
            if rec.get("kind") == "compile" and "compile_ms" in rec:
                out[rec["name"]] = round(
                    out.get(rec["name"], 0.0) + rec["compile_ms"] / 1e3, 3)
    return out


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def device(min_count: int = 1) -> dict:
    """Bring the backend up in THIS process, require a TPU, and place the
    compile cache before the first jit."""
    import jax

    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        platform as plat,
    )

    info = plat.select("tpu")     # raises unless a TPU came up
    if info["n_devices"] < min_count:
        raise RuntimeError(f"--chips {min_count} needs {min_count} TPU "
                           f"devices, JAX found {info['n_devices']}")
    cache_dir = plat.compile_cache()
    return {"platform": info["platform"], "kind": info["device_kind"],
            "count": info["n_devices"], "jax": jax.__version__,
            "cache_dir": cache_dir,
            "cache_entries": _cache_entries(cache_dir)}


# ---------------------------------------------------------------------------
# phase: train (and the --chips 4 data-parallel comparison)
# ---------------------------------------------------------------------------

def _train_flags(out_dir: Path, tag: str, *, vocab_size, seq_len, n_layers,
                 d_model, n_heads, d_ff, batch_size, steps,
                 compute_dtype="bfloat16") -> list:
    """The CLI flags that spell the model (big_lm by default) with the
    optimizer: SGD-momentum, lr 1e-4."""
    return [
        "--dataset", "lm", "--arch", "transformer",
        "--vocab_size", str(vocab_size), "--seq_len", str(seq_len),
        "--n_layers", str(n_layers), "--d_model", str(d_model),
        "--n_heads", str(n_heads), "--d_ff", str(d_ff),
        "--compute_dtype", compute_dtype, "--attention", "flash",
        "--ce_chunk", str(min(256, seq_len)),
        "--no-full-batch", "--batch_size", str(batch_size),
        "--optimizer", "sgd", "--lr", "1e-4", "--momentum", "0.9",
        "--nepochs", "1", "--n_samples", str(batch_size * steps),
        "--seed", "0",
        "--metrics_jsonl", str(out_dir / f"{tag}_metrics.jsonl"),
        "--trace_dir", str(out_dir / f"{tag}_trace"),
    ]


def _fresh(out_dir: Path, tag: str) -> None:
    """Create ``out_dir`` and clear what an earlier run left under ``tag``
    (the metrics and ledger files are appended to)."""
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{tag}_metrics.jsonl").unlink(missing_ok=True)
    shutil.rmtree(out_dir / f"{tag}_trace", ignore_errors=True)


def _check_losses(out_dir: Path, tag: str, steps: int, vocab_size: int
                  ) -> list:
    recs = [json.loads(line) for line in
            (out_dir / f"{tag}_metrics.jsonl").read_text().splitlines()]
    losses = [r["loss"] for r in recs if "loss" in r]
    if [r["step"] for r in recs if "loss" in r] != list(range(1, steps + 1)):
        raise AssertionError(f"expected steps 1..{steps} in the metrics "
                             f"file, got {recs}")
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    ln_v = math.log(vocab_size)
    if abs(losses[0] - ln_v) > 1.0:
        raise AssertionError(f"first loss {losses[0]:.4f} is not within 1.0 "
                             f"of ln(vocab) = {ln_v:.4f}")
    return losses


def _flash_lowering(batch, seq_len, n_heads, head_dim, dtype) -> str:
    """How ``flash_attention`` lowers on the live backend at the phase's
    shapes with its default ``interpret``: a Mosaic ``tpu_custom_call`` on
    a TPU, the Pallas interpreter anywhere else."""
    import jax

    from neural_networks_parallel_training_with_mpi_tpu.ops.pallas_kernels \
        import flash_attention

    qkv = jax.ShapeDtypeStruct((batch, seq_len, n_heads, head_dim), dtype)
    text = jax.jit(flash_attention).lower(qkv, qkv, qkv).as_text()
    return "tpu_custom_call" if "tpu_custom_call" in text else "interpret"


def train(out_dir: Path = OUT_DIR, *, batch_size: int = 8, steps: int = 5,
          platform: str = "tpu", **sizes) -> dict:
    """A few optimizer steps through ``cli.main``, in this process."""
    import jax
    import jax.numpy as jnp

    from neural_networks_parallel_training_with_mpi_tpu import cli
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        platform as plat,
    )

    sizes = {**BIG_LM, **sizes}
    _fresh(out_dir, "train")
    cache_dir = plat.compile_cache()
    entries = _cache_entries(cache_dir)
    flags = _train_flags(out_dir, "train", batch_size=batch_size,
                         steps=steps, **sizes)
    rc = cli.main(flags + ["--platform", platform])
    if rc != 0:
        raise AssertionError(f"cli.main returned {rc}")
    losses = _check_losses(out_dir, "train", steps, sizes["vocab_size"])
    on_tpu = jax.devices()[0].platform == "tpu"
    lowering = _flash_lowering(
        batch_size, sizes["seq_len"], sizes["n_heads"],
        sizes["d_model"] // sizes["n_heads"], jnp.bfloat16)
    if (lowering == "tpu_custom_call") != on_tpu:
        raise AssertionError(
            f"flash_attention lowers as {lowering!r} on platform "
            f"{jax.devices()[0].platform!r}")
    return {"steps": steps, "losses": losses,
            "ln_vocab": round(math.log(sizes["vocab_size"]), 4),
            "flash_lowering": lowering,
            "compile_s": _compile_seconds(out_dir / "train_trace"),
            "cache_entries": [entries, _cache_entries(cache_dir)],
            "peak_bytes_in_use": _peak_bytes()}


def train_dp(out_dir: Path = OUT_DIR, *, n_devices: int = 4,
             batch_size: int = 8, steps: int = 5, **sizes) -> dict:
    """The same seed, global batch and steps on an ``n_devices``-wide
    ``data`` mesh and on a one-device mesh, in this process: per-step
    losses must agree, and the wide run must really be spread out."""
    import jax

    from neural_networks_parallel_training_with_mpi_tpu.config import (
        MeshConfig, build_argparser, config_from_args,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
        make_mesh,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
        Trainer,
    )

    sizes = {**BIG_LM, **sizes}
    devices = jax.devices()[:n_devices]
    if len(devices) < n_devices:
        raise AssertionError(f"need {n_devices} devices, have {len(devices)}")
    losses, placement = {}, {}
    for n in (n_devices, 1):
        tag = f"dp{n}"
        _fresh(out_dir, tag)
        flags = _train_flags(out_dir, tag, batch_size=batch_size,
                             steps=steps, **sizes)
        cfg = config_from_args(build_argparser().parse_args(flags))
        trainer = Trainer(cfg, mesh=make_mesh(MeshConfig(data=n),
                                              devices=devices[:n]))
        trainer.fit()
        losses[n] = _check_losses(out_dir, tag, steps, sizes["vocab_size"])
        if n == 1:
            continue
        # code that has never seen more than one real chip may put
        # everything on the first: look at where the arrays actually sit
        param_devs = [len({s.device for s in leaf.addressable_shards})
                      for leaf in jax.tree_util.tree_leaves(
                          trainer.state.params)]
        batch = next(iter(trainer.loader.epoch(0)))
        batch_devs = {k: len({s.device for s in v.addressable_shards})
                      for k, v in batch.items()}
        if set(param_devs) != {n}:
            raise AssertionError(f"param leaves not addressable on all {n} "
                                 f"devices: {sorted(set(param_devs))}")
        if set(batch_devs.values()) != {n}:
            raise AssertionError(f"batch shards not on {n} distinct "
                                 f"devices: {batch_devs}")
        placement = {"param_leaves": len(param_devs),
                     "devices_per_param_leaf": n,
                     "devices_per_batch_leaf": batch_devs}
    rel = [abs(a - b) / abs(b) for a, b in zip(losses[n_devices], losses[1])]
    if max(rel) > 1e-2:
        raise AssertionError(f"{n_devices}-device and 1-device losses "
                             f"disagree: {losses}")
    peaks = _peak_bytes()[:n_devices]
    if devices[0].platform == "tpu":
        # each device must have held at least the replicated f32 params
        floor = 4 * trainer.model.n_params()
        if not all(p and p > floor for p in peaks):
            raise AssertionError(f"peak_bytes_in_use {peaks} not above the "
                                 f"params' {floor} bytes on every device")
    return {"n_devices": n_devices, "steps": steps,
            "loss_pairs": [[a, b] for a, b in
                           zip(losses[n_devices], losses[1])],
            "max_rel_diff": max(rel), **placement,
            "compile_s": {**_compile_seconds(out_dir / f"dp{n_devices}_trace"),
                          "one_device": _compile_seconds(
                              out_dir / "dp1_trace")},
            "peak_bytes_in_use": peaks}


# ---------------------------------------------------------------------------
# phase: serve / serve_fused
# ---------------------------------------------------------------------------

def serve(out_dir: Path = OUT_DIR, attn_impl: str | None = None, *,
          compute_dtype: str = "bfloat16", seed: int = 0, **sizes) -> dict:
    """Six ragged requests through ``Scheduler`` over the paged KV cache,
    greedy, then a teacher-forced comparison with the dense model.
    ``attn_impl=None`` is the scheduler's default."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig, prewarm,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        compile_ledger, platform as plat, prng,
    )

    sizes = {**BIG_LM, **SERVE, **sizes}
    prompt_lens, (new_lo, new_hi) = sizes["prompt_lens"], sizes["max_new"]
    vocab, seq_len = sizes["vocab_size"], sizes["seq_len"]
    tag = f"serve_{attn_impl or 'default'}"
    _fresh(out_dir, tag)
    cache_dir = plat.compile_cache()
    entries = _cache_entries(cache_dir)

    model = Transformer(TransformerConfig(
        vocab_size=vocab, max_seq_len=seq_len, n_layers=sizes["n_layers"],
        d_model=sizes["d_model"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], compute_dtype=jnp.dtype(compute_dtype),
        attention="dense"))
    params = model.init(prng.init_key(seed))
    serve_cfg = dict(slots=sizes["slots"], num_blocks=sizes["num_blocks"],
                     block_size=sizes["block_size"],
                     prefill_chunk=sizes["prefill_chunk"], seed=seed)
    if attn_impl is not None:
        serve_cfg["attn_impl"] = attn_impl
    make_scheduler = lambda: Scheduler(            # noqa: E731
        model, params, ServeConfig(**serve_cfg))

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, vocab, size=p).tolist() for p in prompt_lens]
    max_new = [int(rng.integers(new_lo, new_hi + 1)) for _ in prompt_lens]

    # one compile ledger across warm-up and the six requests
    tracer = trace_lib.start_run(str(out_dir / f"{tag}_trace"))
    try:
        ledger = compile_ledger.active()
        # every prefill bucket the prompts can draw, and the decode step
        prewarm(make_scheduler, prompt_lens=(min(prompt_lens),
                                             max(prompt_lens)))
        warm_compiles = len(ledger.events)
        sched = make_scheduler()
        try:
            rids = [sched.submit(p, n) for p, n in zip(prompts, max_new)]
            if any(r is None for r in rids):
                raise AssertionError(f"a request was rejected: {rids}")
            done = sched.run_until_drained()
            served = [sched.result(r) for r in rids]
            sched.server.allocator.assert_drained()
            attn = sched.server.attn_impl
            ticks = sched.tick_no
        finally:
            sched.close()
        compiles = [e["name"] for e in ledger.events]
    finally:
        trace_lib.stop_run(tracer)
    if sorted(done) != sorted(rids):
        raise AssertionError(f"completed {done}, submitted {rids}")
    if compiles[warm_compiles:]:
        raise AssertionError("compiled after the warm-up requests: "
                             f"{compiles[warm_compiles:]}")
    for prompt, n, seq in zip(prompts, max_new, served):
        if seq[:len(prompt)] != prompt or len(seq) != len(prompt) + n:
            raise AssertionError(f"asked for {len(prompt)}+{n} tokens, got "
                                 f"{len(seq)}")
        if not all(0 <= t < vocab for t in seq):
            raise AssertionError("token id out of range")

    # teacher-forced, tolerant: one dense forward over prompt + served
    # tokens (right-padded: causal attention never looks ahead)
    ids = np.zeros((len(served), seq_len), np.int32)
    for i, seq in enumerate(served):
        ids[i, :len(seq)] = seq

    @jax.jit
    def reference(params, ids):
        logits = model.apply(params, ids).astype(jnp.float32)
        nxt = jnp.roll(ids, -1, axis=1)          # position t predicts t+1
        tok = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        return (logits.max(-1) - tok, logits.std(-1),
                logits.argmax(-1) == nxt)

    gap, sigma, exact = jax.device_get(reference(params, jnp.asarray(ids)))
    worst, n_exact, n_gen = 0.0, 0, 0
    for i, (prompt, seq) in enumerate(zip(prompts, served)):
        gen = slice(len(prompt) - 1, len(seq) - 1)
        if not np.all(np.isfinite(gap[i, gen])):
            raise AssertionError(f"request {i}: non-finite reference logits")
        worst = max(worst, float((gap[i, gen] / sigma[i, gen]).max()))
        n_exact += int(exact[i, gen].sum())
        n_gen += gen.stop - gen.start
    if worst > MARGIN_SIGMA:
        raise AssertionError(
            f"a served token's reference logit is {worst:.3f} sigma below "
            f"the reference maximum (margin {MARGIN_SIGMA})")
    return {"attn_impl": attn, "requests": len(rids),
            "prompt_lens": list(prompt_lens), "max_new": max_new,
            "ticks": ticks, "allocator_drained": True,
            "compiles_warmup": warm_compiles, "compiles_after_warmup": 0,
            "generated_tokens": n_gen, "argmax_matches": n_exact,
            "worst_gap_sigma": round(worst, 4),
            "margin_sigma": MARGIN_SIGMA,
            "compile_s": _compile_seconds(out_dir / f"{tag}_trace"),
            "cache_entries": [entries, _cache_entries(cache_dir)],
            "peak_bytes_in_use": _peak_bytes()}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run ONLY data-parallel training on a 4-device "
                         "data mesh against the same run on one device "
                         "(needs four chips; no serving phase)")
    args = ap.parse_args(argv)
    dev = run_phase("device", device, args.chips)
    if args.chips == 4:
        run_phase("train_dp", train_dp, n_devices=4)
    else:
        run_phase("train", train)
        run_phase("serve", serve)
        run_phase("serve_fused", serve, attn_impl="fused")
    emit({"ok": True, "device": {"platform": dev["platform"],
                                 "kind": dev["kind"],
                                 "count": dev["count"]}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
