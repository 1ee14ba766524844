"""The plain reference against the program at a tiny size, before it judges a
chip run: learned positions + MHA through the training loss and its gradient,
RoPE + GQA with 2 KV heads through chunked prefill then paged decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, common, train, weights
from benchmark.reference import serve as ref_serve
from benchmark.reference import train as ref_train


def f32(model):
    return {**model, "param_dtype": "float32", "compute_dtype": "float32"}


def test_loss_and_gradient_match_the_program(bench_dir):
    from neural_networks_parallel_training_with_mpi_tpu.ops import losses

    model = f32(common.load_cell("tiny-train", bench_dir)["model"])
    fam = model["family"]
    net = fam.program_model(model)
    maker = weights.Maker(model, 5)
    tree = fam.to_program(model, maker.outer(), maker.layers())
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
            for n, x in train.flat_names(model, gp).items()}
    ref = {n: float(x) for n, x in ref_train.leaf_norms(model, gr).items()}
    assert set(prog) == set(ref)
    gap, leaf = check.worst_leaf(prog, ref)
    assert gap < 1e-4, leaf


def test_prefill_then_decode_matches_the_reference(bench_dir):
    """Greedy tokens from the paged server (chunked prefill, block tables,
    GQA, RoPE at absolute positions, batched decode) lie on the float32
    reference's best; a token altered on the way is far below it."""
    from neural_networks_parallel_training_with_mpi_tpu.serve import (
        Scheduler, ServeConfig,
    )

    cell = common.load_cell("tiny-serve", bench_dir)
    model = f32(cell["model"])
    fam = model["family"]
    net = fam.program_model(model)
    maker = weights.Maker(model, 9)
    params = fam.to_program(model, maker.outer(), maker.layers())
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


def test_layers_of_two_kinds_walk_like_layers_of_one(bench_dir):
    """A family says a layer's tensors as a function of its index.  Here the
    second layer has one tensor more, which its block never reads: the maker
    makes it with a second program, the reference walks two runs of one layer
    where it walked one run of two, and every number it gives is the dense
    family's."""
    import types

    model = f32(common.load_cell("tiny-train", bench_dir)["model"])
    fam = model["family"]

    def layer_shapes(m, i):
        return {**fam.layer_shapes(m, i), **({"spare.bias": (3,)} if i else {})}

    two = {**model, "family": types.SimpleNamespace(
        **{**vars(fam), "layer_shapes": layer_shapes})}
    assert weights.layer_runs(model) == [(0, 2)]
    assert weights.layer_runs(two) == [(0, 1), (1, 1)]
    assert set(weights.Maker(two, 5).layer(1)) \
        == set(weights.Maker(two, 5).layer(0)) | {"spare.bias"}
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, size=(2, 65)).astype(np.int32)
    batches = [(toks[:, :-1], toks[:, 1:])] * 2
    opt = common.load_cell("tiny-train", bench_dir)["job"]["optimizer"]
    a = ref_train.three_steps(model, opt, 5, batches)
    b = ref_train.three_steps(two, opt, 5, batches)
    assert b["grad_norm"].pop("L1.spare.bias") == 0.0
    del b["change_norm"]["L1.spare.bias"]
    assert a["losses"] == pytest.approx(b["losses"], rel=1e-6)
    for key in ("grad_norm", "change_norm"):
        assert set(a[key]) == set(b[key])
        assert check.worst_leaf(b[key], a[key])[0] < 1e-5
