"""--attention auto: shape-based dense-vs-flash dispatch from a measured
``(backend, head_dim, dtype) -> smallest T`` table (PR 27: the flash
kernels fed bf16 operands win from T 1024 at head_dim 64 and 128 on a TPU
v5e; every untimed shape keeps dense below 2048)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.config import (
    TrainConfig, build_argparser, config_from_args,
)
from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.parallel.sequence import (
    AUTO_FLASH_MIN_SEQ, AUTO_FLASH_MIN_SEQ_UNTIMED, resolve_attention_impl,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng


BF16, F32 = jnp.bfloat16, jnp.float32


def test_dispatch_table_holds_the_timed_rows():
    """A change to the table is a deliberate re-measurement, not an
    accident: the rows are the ones PR 27 timed on the chip."""
    assert AUTO_FLASH_MIN_SEQ == {("tpu", 64, "bfloat16"): 1024,
                                  ("tpu", 128, "bfloat16"): 1024}
    assert AUTO_FLASH_MIN_SEQ_UNTIMED == {"tpu": 2048}


@pytest.mark.parametrize("backend,t,head_dim,dtype,want", [
    # the timed rows: flash from T 1024, dense below
    ("tpu", 1024, 64, BF16, "flash"),
    ("tpu", 512, 64, BF16, "dense"),
    ("tpu", 1023, 64, BF16, "dense"),
    ("tpu", 4096, 64, BF16, "flash"),
    ("tpu", 1024, 128, BF16, "flash"),
    ("tpu", 512, 128, BF16, "dense"),
    # an untimed head_dim or dtype keeps the 2048 rule
    ("tpu", 1024, 96, BF16, "dense"),
    ("tpu", 2047, 96, BF16, "dense"),
    ("tpu", 2048, 96, BF16, "flash"),
    ("tpu", 1024, 64, F32, "dense"),
    ("tpu", 2048, 64, F32, "flash"),
    ("tpu", 2047, None, None, "dense"),     # callers that know only T
    ("tpu", 8192, None, None, "flash"),
    # T the derived blocks do not divide: dense, where the kernel's own
    # call would raise
    ("tpu", 1024 + 128, 64, BF16, "dense"),
    ("tpu", 2048 + 64, 96, BF16, "dense"),
    # cpu (and any unmeasured backend): never the pallas kernel — it
    # runs in interpret mode there
    ("cpu", 128, 64, BF16, "dense"),
    ("cpu", 1024, 64, BF16, "dense"),
    ("cpu", 65536, 64, BF16, "dense"),
    ("gpu", 4096, 64, BF16, "dense"),
])
def test_dispatch_table_pinned(backend, t, head_dim, dtype, want):
    assert resolve_attention_impl("auto", t, backend, head_dim=head_dim,
                                  dtype=dtype) == want


@pytest.mark.parametrize("impl", ["dense", "dense_blockwise", "flash", "ring",
                                  "ring_flash", "striped", "striped_flash",
                                  "ulysses"])
def test_explicit_impls_pass_through(impl):
    assert resolve_attention_impl(impl, 8192, "tpu", head_dim=64,
                                  dtype=BF16) == impl
    assert resolve_attention_impl(impl, 100, "cpu") == impl


def test_auto_is_the_default():
    """TransformerConfig, ModelConfig, and the CLI all default to auto."""
    assert TransformerConfig(vocab_size=8).attention == "auto"
    assert TrainConfig().model.attention == "auto"
    args = build_argparser().parse_args(["--dataset", "text"])
    assert config_from_args(args).model.attention == "auto"


def test_dense_blockwise_exact_vs_dense():
    """attention_dense_blockwise (VERDICT r4 item 5): same math as dense
    with a (B,H,C,T) scores temp — outputs AND grads must match the
    reference to float32 tolerance at chunking, non-chunking (T % chunk
    != 0 falls back to one block), causal and bidirectional shapes."""
    from neural_networks_parallel_training_with_mpi_tpu.parallel.sequence import (
        attention_dense_blockwise, attention_reference,
    )

    rng = np.random.default_rng(0)
    for (b, t, h, d), chunk, causal in [
        ((2, 512, 4, 16), 128, True),
        ((2, 512, 4, 16), 128, False),
        # 96 % 64 != 0 -> falls back to the LARGEST DIVISOR of t that
        # fits the requested chunk: 48 here (two blocks), not one
        # whole-seq block
        ((1, 96, 2, 8), 64, True),
        # prime T: the divisor fallback's worst case, q_chunk=1 -> t
        # scan ticks of (B, H, 1, T) — still never the full (B, H, T, T)
        ((1, 29, 2, 8), 16, True),
        ((2, 256, 2, 32), 256, True),  # chunk == T
    ]:
        q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)),
                               jnp.float32) for _ in range(3))

        def loss(fn, q, k, v, _c=causal):
            return jnp.sum(fn(q, k, v, causal=_c).astype(jnp.float32) ** 2)

        ref = attention_reference(q, k, v, causal=causal)
        blk = attention_dense_blockwise(q, k, v, causal=causal,
                                        q_chunk=chunk)
        np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        g_ref = jax.grad(lambda *a: loss(attention_reference, *a))(q, k, v)
        g_blk = jax.grad(
            lambda *a: loss(attention_dense_blockwise, *a))(q, k, v)
        np.testing.assert_allclose(np.asarray(g_blk), np.asarray(g_ref),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("seq,compute", [(16, jnp.float32),
                                         (16, jnp.bfloat16),
                                         (32, jnp.bfloat16)])
def test_auto_equals_dense_below_crossover(seq, compute):
    """On this backend (cpu) auto resolves to dense at every T, head_dim
    and dtype, so the forward is bitwise identical — the resolution
    changes dispatch, never math."""
    kw = dict(vocab_size=64, max_seq_len=32, n_layers=2, d_model=32,
              n_heads=4, d_ff=64, compute_dtype=compute)
    cfg_auto = TransformerConfig(attention="auto", **kw)
    cfg_dense = TransformerConfig(attention="dense", **kw)
    model_a, model_d = Transformer(cfg_auto), Transformer(cfg_dense)
    params = model_a.init(prng.init_key(0))
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, seq)), jnp.int32)
    out_a = jax.jit(model_a.apply)(params, ids)
    out_d = jax.jit(model_d.apply)(params, ids)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_d))


# ---- the counter that says it engaged (PR 27) -----------------------------

def test_train_step_compile_event_names_the_attention(tmp_path, mesh8):
    """The resolved implementation and the kernels' tiling are on the
    compile ledger's event of the train step, once a program."""
    import glob
    import json
    import os

    from neural_networks_parallel_training_with_mpi_tpu.config import (
        DataConfig, ModelConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train import (
        trace as trace_lib,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.trainer import (
        Trainer,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        compile_ledger,
    )

    def events(attention, sub):
        cfg = TrainConfig(
            lr=1e-3, nepochs=1, full_batch=False, batch_size=16, seed=5,
            log_every=0, optimizer="adam", loss="cross_entropy",
            telemetry_dir=str(tmp_path / sub), trace=True,
            data=DataConfig(dataset="lm", n_samples=16, seq_len=32,
                            vocab_size=64),
            model=ModelConfig(arch="transformer", n_layers=2, d_model=32,
                              n_heads=4, d_ff=64, vocab_size=64,
                              max_seq_len=32, attention=attention))
        try:
            Trainer(cfg, mesh=mesh8).fit()
        finally:
            trace_lib.stop_run()
            compile_ledger.install(None)
        recs = []
        for path in glob.glob(os.path.join(cfg.telemetry_dir, "trace",
                                           "compiles-*.jsonl")):
            recs += [json.loads(line) for line in open(path)]
        return [r for r in recs if r["name"].startswith("train_step")]

    flash = events("flash", "flash")
    assert flash and all(
        r["attention"] == {"impl": "flash", "block_q": 32, "block_k": 32}
        for r in flash)
    auto = events("auto", "auto")       # the cpu never gets the kernel
    assert auto and all(r["attention"] == {"impl": "dense"} for r in auto)
    # outside a ledger's compile the note goes nowhere
    compile_ledger.note("attention", {"impl": "x"})


def test_scope_report_lists_attn_flash_and_its_kernels(tmp_path):
    """``tools/scope_report.py`` on a toy trace: the by-scope table holds
    ``attention`` for both passes, and under ``attn_flash`` the two
    kernels' self time beside what XLA does around them."""
    import importlib.util
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root / "benchmark" / "tests"))
    try:
        import xspace_writer
    finally:
        sys.path.pop(0)
    spec = importlib.util.spec_from_file_location(
        "scope_report", root / "tools" / "scope_report.py")
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    step = "jit(shard_step)/loss_and_grad/"
    fwd = step + "jvp(attention)/attn_flash/jit(_flash_forward_call)/"
    bwd = (step + "transpose(jvp(attention))/attn_flash/"
           "jit(_flash_backward_call)/")
    ops = [(fwd + "transpose", 0, 2), (fwd + "flash_fwd/pallas_call", 2, 10),
           (bwd + "flash_bwd/pallas_call", 20, 28),
           (bwd + "reduce_sum", 48, 1),
           (step + "jvp(attn_proj)/dot_general", 49, 1),
           (step + "jvp(ffn)/dot_general", 50, 30)]
    path = tmp_path / "xplane/plugins/profile/1/t.xplane.pb"
    xspace_writer.write(path, [xspace_writer.plane("/device:TPU:0", {
        "XLA Ops": [(f"%op.{i} = f32[8] fusion(%p)", s, d, {"tf_op": p})
                    for i, (p, s, d) in enumerate(ops)]})])
    obs = {"profiler": report._Trace(path)}
    ns = 1e-9                   # the writer's times are nanoseconds
    got = report.attention_by_impl(obs)
    assert set(got) == {"attn_flash"}
    want = {"total": 41 * ns, "flash_fwd": 10 * ns, "flash_bwd": 28 * ns,
            "around": 3 * ns}
    assert got["attn_flash"] == pytest.approx(want)
    from benchmark.reducers import scopes

    by_scope = scopes.coverage(obs)["by_scope_s"]
    assert by_scope["attention"] == pytest.approx(12 * ns)
    assert by_scope["attention:bwd"] == pytest.approx(29 * ns)
