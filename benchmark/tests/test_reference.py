"""The plain reference against the program at a tiny size, before it judges a
chip run: learned positions + MHA through the training loss and its gradient,
RoPE + GQA with 2 KV heads through chunked prefill then paged decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, common, program, weights
from benchmark.reference import serve as ref_serve
from benchmark.reference import train as ref_train


def f32(model):
    return {**model, "param_dtype": "float32", "compute_dtype": "float32"}


def test_loss_and_gradient_match_the_program(bench_dir):
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer,
    )
    from neural_networks_parallel_training_with_mpi_tpu.ops import losses

    model = f32(common.load_cell("tiny-train", bench_dir)["model"])
    net = Transformer(program.transformer_config(model))
    maker = weights.Maker(model, 5)
    tree = program.to_program(maker.outer(), maker.layers())
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, size=(3, 65)).astype(np.int32)
    ids, labels = toks[:, :-1], toks[:, 1:]

    def loss(p):
        s, c = losses.softmax_cross_entropy(net.apply(p, ids), labels)
        return s / c

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(loss)(tree)
        params = ref_train.init_params(model, 5)
        lr, gr = jax.value_and_grad(
            lambda p: ref_train.nll_sum(model, p, ids, labels) / ids.size)(
                params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    prog = {n: float(jnp.sqrt((x ** 2).sum()))
            for n, x in program.flat_names(model, gp).items()}
    ref = {n: float(x) for n, x in ref_train.leaf_norms(model, gr).items()}
    assert set(prog) == set(ref)
    gap, leaf = check.worst_leaf(prog, ref)
    assert gap < 1e-4, leaf


def test_prefill_then_decode_matches_the_reference(bench_dir):
    """Greedy tokens from the paged server (chunked prefill, block tables,
    GQA, RoPE at absolute positions, batched decode) lie on the float32
    reference's best; a token altered on the way is far below it."""
    from neural_networks_parallel_training_with_mpi_tpu.models import (
        Transformer,
    )
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig,
    )

    cell = common.load_cell("tiny-serve", bench_dir)
    model = f32(cell["model"])
    net = Transformer(program.transformer_config(model))
    params = program.to_program(weights.Maker(model, 9).outer(), weights.Maker(model, 9).layers())
    sched = Scheduler(net, params, ServeConfig(**cell["job"]["serve_config"]))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (9, 37, 20)]
    rids = [sched.submit(p, 10) for p in prompts]
    sched.run_until_drained()
    seqs = [sched.result(r) for r in rids]
    sched.close()
    logits, toks = ref_serve.generated_logits(
        model, 9, seqs, [len(p) for p in prompts], pad_to=16)
    gaps = check.served_gap(logits, toks)
    assert len(gaps) == 30 and gaps.max() < 1e-3
    seqs[1][-3] = (seqs[1][-3] + 1) % 256
    logits, toks = ref_serve.generated_logits(
        model, 9, seqs, [len(p) for p in prompts], pad_to=16)
    assert check.served_gap(logits, toks).max() > 0.5
