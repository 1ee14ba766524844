"""Pipeline parallelism must be a pure re-scheduling: a DP x PP pipelined
train step produces the same loss and the same updated weights as a
single-device dense step over the identical global batch and params."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neural_networks_parallel_training_with_mpi_tpu.config import MeshConfig
from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.ops import losses, optim
from neural_networks_parallel_training_with_mpi_tpu.parallel import pipeline as pp
from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import make_mesh
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

# integration-heavy: full lane only (core lane: -m 'not slow')
pytestmark = pytest.mark.slow

VOCAB, T = 64, 16


def tiny_model(n_layers=4, attention="dense"):
    return Transformer(TransformerConfig(
        vocab_size=VOCAB, max_seq_len=T, n_layers=n_layers, d_model=32,
        n_heads=4, d_ff=64, attention=attention))


def lm_batch(rows, seed=0):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, VOCAB, (rows, T + 1))
    return {"x": tok[:, :-1].astype(np.int32),
            "y": tok[:, 1:].astype(np.int32),
            "mask": np.ones((rows,), np.float32)}


def reference_step(model, opt, params, batch):
    """Single-device global-mean CE step on the unpipelined model."""
    def scalar(p):
        logits = model.apply(p, jnp.asarray(batch["x"]))
        s, c = losses.softmax_cross_entropy(logits, jnp.asarray(batch["y"]),
                                            jnp.asarray(batch["mask"]))
        return s / c, (s, c)

    (loss, _), grads = jax.value_and_grad(scalar, has_aux=True)(params)
    opt_state = opt.init(params)
    new_params, _ = opt.update(grads, opt_state, params)
    return loss, new_params


def test_stack_unstack_roundtrip():
    model = tiny_model(4)
    params = model.init(prng.init_key(0))
    stacked = pp.stack_blocks(params["blocks"], 2)
    back = pp.unstack_blocks(stacked)
    assert len(back) == 4
    for orig, rt in zip(params["blocks"], back):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            orig, rt)


@pytest.mark.parametrize("pipe,data,n_mb", [(4, 2, 4), (2, 1, 6)])
def test_pipeline_matches_single_device(pipe, data, n_mb):
    devs = jax.devices("cpu")[: pipe * data]
    mesh = make_mesh(MeshConfig(data=data, pipe=pipe), devices=devs)
    model = tiny_model(4)
    opt = optim.sgd(lr=0.1, momentum=0.9)
    batch = lm_batch(rows=data * n_mb * 2)

    state, loss = pp.run_one_step(model, opt, mesh, batch, prng.init_key(0),
                                  n_microbatches=n_mb)

    params = model.init(prng.init_key(0))
    ref_loss, ref_params = reference_step(model, opt, params, batch)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)

    got_blocks = pp.unstack_blocks(jax.device_get(state.params["blocks"]))
    ref_blocks = jax.device_get(ref_params["blocks"])
    for got, ref in zip(got_blocks, ref_blocks):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            got, ref)
    for name in ("embed", "pos", "ln_f", "head"):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            jax.device_get(state.params[name]), jax.device_get(ref_params[name]))


def test_pipeline_multiple_steps_decrease_loss():
    devs = jax.devices("cpu")[:4]
    mesh = make_mesh(MeshConfig(data=1, pipe=4), devices=devs)
    model = tiny_model(4)
    opt = optim.adam(lr=1e-2)
    batch = lm_batch(rows=8)

    state = pp.init_pipeline_state(model, opt, prng.init_key(0), 4)
    state = pp.shard_pipeline_state(state, mesh, opt)
    from jax.sharding import NamedSharding, PartitionSpec as P
    placed = {k: jax.device_put(jnp.asarray(v),
                                NamedSharding(mesh, P(("data", "fsdp"))))
              for k, v in batch.items()}
    step = pp.make_pipeline_train_step(model, opt, mesh, n_microbatches=4,
                                       donate=False)
    state, first = step(state, placed)
    for _ in range(10):
        state, loss = step(state, placed)
    assert float(loss) < float(first)
    assert int(state.step) == 11


def test_bubble_fraction_accounting():
    """More microbatches -> smaller bubble; accounting matches the scan
    length the step actually runs (n_mb + n_stages - 1 ticks)."""
    assert pp.schedule_ticks(4, 4) == 7
    assert pp.bubble_fraction(4, 4) == pytest.approx(3 / 7)
    # accum_steps folding (Trainer: n_mb = n_stages * accum) shrinks it
    assert pp.bubble_fraction(4, 16) == pytest.approx(3 / 19)
    assert pp.bubble_fraction(4, 16) < pp.bubble_fraction(4, 4)
    assert pp.bubble_fraction(2, 64) < 0.02


@pytest.mark.parametrize("pipe,data,v,n_mb", [(2, 2, 2, 2), (2, 1, 2, 4),
                                              (4, 1, 2, 4)])
def test_interleaved_matches_single_device(pipe, data, v, n_mb):
    """Virtual-stage interleaving is a pure re-scheduling: loss and updated
    weights match the single-device dense step exactly (same bar as the
    plain GPipe ring)."""
    devs = jax.devices("cpu")[: pipe * data]
    mesh = make_mesh(MeshConfig(data=data, pipe=pipe), devices=devs)
    model = tiny_model(pipe * v)  # one layer per virtual stage
    opt = optim.sgd(lr=0.1, momentum=0.9)
    batch = lm_batch(rows=data * n_mb * 2)

    state, loss = pp.run_one_step(model, opt, mesh, batch, prng.init_key(0),
                                  n_microbatches=n_mb, interleave=v)

    params = model.init(prng.init_key(0))
    ref_loss, ref_params = reference_step(model, opt, params, batch)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    got_blocks = pp.unstack_blocks(jax.device_get(state.params["blocks"]),
                                   stack_ndims=3)
    ref_blocks = jax.device_get(ref_params["blocks"])
    assert len(got_blocks) == len(ref_blocks)
    for got, ref in zip(got_blocks, ref_blocks):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            got, ref)


def test_interleaved_with_tensor_matches_single_device():
    """Interleave composes with the pipeline's Megatron tensor axis
    (DP x TP x PP with virtual stages): still a pure re-scheduling."""
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        megatron,
    )

    pipe, tp, v, n_mb = 2, 2, 2, 2
    devs = jax.devices("cpu")[: pipe * tp * 2]
    mesh = make_mesh(MeshConfig(data=2, pipe=pipe, tensor=tp), devices=devs)
    model = tiny_model(pipe * v)
    opt = optim.sgd(lr=0.1, momentum=0.9)
    batch = lm_batch(rows=2 * n_mb * 2)

    state, loss = pp.run_one_step(model, opt, mesh, batch, prng.init_key(0),
                                  n_microbatches=n_mb, interleave=v)

    params = model.init(prng.init_key(0))
    ref_loss, ref_params = reference_step(model, opt, params, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)

    got_stack = megatron.permute_qkv(
        jax.device_get(state.params["blocks"]), model.cfg.d_model,
        model.cfg.n_heads, tp, inverse=True)
    got_blocks = pp.unstack_blocks(got_stack, stack_ndims=3)
    ref_blocks = jax.device_get(ref_params["blocks"])
    for got, ref in zip(got_blocks, ref_blocks):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            got, ref)


def test_interleaved_matches_gpipe_trajectory():
    """interleave=2 and the plain ring compute the SAME math (GPipe
    semantics) — multi-step trajectories agree to float tolerance."""
    devs = jax.devices("cpu")[:2]
    mesh = make_mesh(MeshConfig(data=1, pipe=2), devices=devs)
    model = tiny_model(4)
    opt = optim.adam(lr=1e-2)
    batch = lm_batch(rows=8)
    from jax.sharding import NamedSharding, PartitionSpec as P

    placed = {k: jax.device_put(jnp.asarray(v),
                                NamedSharding(mesh, P(("data", "fsdp"))))
              for k, v in batch.items()}
    losses_by_v = {}
    for v in (1, 2):
        state = pp.init_pipeline_state(model, opt, prng.init_key(0), 2,
                                       interleave=v)
        state = pp.shard_pipeline_state(state, mesh, opt, interleave=v)
        step = pp.make_pipeline_train_step(model, opt, mesh,
                                           n_microbatches=4, donate=False,
                                           interleave=v)
        traj = []
        for _ in range(4):
            state, loss = step(state, placed)
            traj.append(float(loss))
        losses_by_v[v] = traj
    np.testing.assert_allclose(losses_by_v[1], losses_by_v[2], rtol=1e-5)


def test_interleaved_eval_matches_dense():
    devs = jax.devices("cpu")[:2]
    mesh = make_mesh(MeshConfig(data=1, pipe=2), devices=devs)
    model = tiny_model(4)
    opt = optim.sgd(lr=0.1)
    batch = lm_batch(rows=8, seed=3)
    state = pp.init_pipeline_state(model, opt, prng.init_key(1), 2,
                                   interleave=2)
    state = pp.shard_pipeline_state(state, mesh, opt, interleave=2)
    from jax.sharding import NamedSharding, PartitionSpec as P

    placed = {k: jax.device_put(jnp.asarray(v),
                                NamedSharding(mesh, P(("data", "fsdp"))))
              for k, v in batch.items()}
    ev = pp.make_pipeline_eval_step(model, mesh, with_accuracy=True,
                                    n_microbatches=2, interleave=2)
    got = ev(state.params, placed)

    params = model.init(prng.init_key(1))
    logits = model.apply(params, jnp.asarray(batch["x"]))
    s, c = losses.softmax_cross_entropy(logits, jnp.asarray(batch["y"]),
                                        jnp.asarray(batch["mask"]))
    np.testing.assert_allclose(float(got["loss"]), float(s / c), rtol=1e-5)
    assert float(got["count"]) == float(c)


def test_interleaved_bubble_shrinks_at_constant_microbatches():
    """The r2 item 5 claim: v virtual stages divide the warmup/drain bubble
    at CONSTANT microbatch count — (S-1)/(vM+S-1) — refuting the earlier
    'only more microbatches can' note; ticks match the scan length."""
    assert pp.schedule_ticks(4, 8, interleave=2) == 19
    assert pp.bubble_fraction(4, 8, interleave=2) == pytest.approx(3 / 19)
    assert (pp.bubble_fraction(4, 8, interleave=2)
            < pp.bubble_fraction(4, 8))
    assert (pp.bubble_fraction(4, 8, interleave=4)
            < pp.bubble_fraction(4, 8, interleave=2))
    # v=1 reduces to the plain accounting
    assert pp.bubble_fraction(4, 8, interleave=1) == pp.bubble_fraction(4, 8)


def test_interleaved_rejects_ragged_groups():
    devs = jax.devices("cpu")[:2]
    mesh = make_mesh(MeshConfig(data=1, pipe=2), devices=devs)
    model = tiny_model(4)
    opt = optim.sgd(lr=0.1)
    with pytest.raises(ValueError, match="groups of n_stages"):
        pp.make_pipeline_train_step(model, opt, mesh, n_microbatches=3,
                                    interleave=2)


def test_pipeline_eval_matches_dense_eval():
    """The forward-only ring schedule on pipe-sharded params must produce
    the same loss/accuracy as the dense model on gathered params."""
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        data_parallel as dp,
    )

    model = tiny_model(4)
    mesh = make_mesh(MeshConfig(data=2, pipe=2),
                     devices=jax.devices("cpu")[:4])
    opt = optim.sgd(lr=1e-2)
    state = pp.init_pipeline_state(model, opt, prng.init_key(0), 2)
    state = pp.shard_pipeline_state(state, mesh, opt)
    batch = lm_batch(8)
    from jax.sharding import NamedSharding, PartitionSpec as P

    placed = {k: jax.device_put(jnp.asarray(v),
                                NamedSharding(mesh, P(("data", "fsdp"))))
              for k, v in batch.items()}
    eval_step = pp.make_pipeline_eval_step(model, mesh, "cross_entropy",
                                           with_accuracy=True)
    got = jax.device_get(eval_step(state.params, placed))

    dense_params = dict(jax.device_get(state.params))
    dense_params["blocks"] = pp.unstack_blocks(dense_params["blocks"])
    dense_eval = dp.make_eval_step(model, mesh, "cross_entropy",
                                   with_accuracy=True)
    rep = jax.device_put(dense_params, NamedSharding(mesh, P()))
    want = jax.device_get(dense_eval(rep, placed))

    assert float(got["count"]) == float(want["count"])
    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(got["accuracy"]),
                               float(want["accuracy"]), rtol=1e-5)


def test_pipeline_remat_matches_no_remat():
    """cfg.remat re-materializes stage activations in the backward; the
    trajectory must be identical to the stored-activation path."""
    import dataclasses as dc

    mesh = make_mesh(MeshConfig(data=2, pipe=2),
                     devices=jax.devices("cpu")[:4])
    batch = lm_batch(8)
    results = []
    for remat in (False, True):
        model = Transformer(dc.replace(tiny_model(4).cfg, remat=remat))
        opt = optim.sgd(lr=1e-2)
        state, loss = pp.run_one_step(model, opt, mesh, batch,
                                      prng.init_key(0))
        results.append((float(jax.device_get(loss)),
                        jax.device_get(state.params)))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a, np.float32),
                                                np.asarray(b, np.float32),
                                                rtol=1e-6, atol=1e-7),
        results[0][1], results[1][1])


def test_pipeline_eval_pads_non_divisible_batch():
    """A validation batch whose per-shard rows don't divide into the
    schedule's microbatches is padded with mask-0 rows — same metrics as the
    dense eval on the unpadded batch (the small-val-set case that must not
    crash: VERDICT r1 review)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        data_parallel as dp,
    )

    model = tiny_model(4)
    mesh = make_mesh(MeshConfig(data=2, pipe=2),
                     devices=jax.devices("cpu")[:4])
    opt = optim.sgd(lr=1e-2)
    state = pp.init_pipeline_state(model, opt, prng.init_key(0), 2)
    state = pp.shard_pipeline_state(state, mesh, opt)
    batch = lm_batch(6)  # per data-shard: 3 rows, n_mb=2 -> pad 1
    placed = {k: jax.device_put(jnp.asarray(v),
                                NamedSharding(mesh, P(("data", "fsdp"))))
              for k, v in batch.items()}
    eval_step = pp.make_pipeline_eval_step(model, mesh, "cross_entropy",
                                           with_accuracy=True)
    got = jax.device_get(eval_step(state.params, placed))

    dense_params = dict(jax.device_get(state.params))
    dense_params["blocks"] = pp.unstack_blocks(dense_params["blocks"])
    rep = jax.device_put(dense_params, NamedSharding(mesh, P()))
    dense_eval = dp.make_eval_step(model, mesh, "cross_entropy",
                                   with_accuracy=True)
    want = jax.device_get(dense_eval(rep, placed))

    assert float(got["count"]) == float(want["count"])  # pads not counted
    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(got["accuracy"]),
                               float(want["accuracy"]), rtol=1e-5)

def test_pipeline_tensor_flash_matches_single_device():
    """PP x TP with flash attention (VERDICT r3 item 4): the Pallas flash
    kernel runs over each tensor rank's LOCAL heads inside the Megatron
    stage body — the composed step must still be a pure re-scheduling of
    the single-device flash model (loss + updated blocks match)."""
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        megatron,
    )

    pipe, tp, v, n_mb = 2, 2, 2, 2
    devs = jax.devices("cpu")[: pipe * tp * 2]
    mesh = make_mesh(MeshConfig(data=2, pipe=pipe, tensor=tp), devices=devs)
    model = tiny_model(pipe * v, attention="flash")
    opt = optim.sgd(lr=0.1, momentum=0.9)
    batch = lm_batch(rows=2 * n_mb * 2)

    state, loss = pp.run_one_step(model, opt, mesh, batch, prng.init_key(0),
                                  n_microbatches=n_mb, interleave=v)

    params = model.init(prng.init_key(0))
    ref_loss, ref_params = reference_step(model, opt, params, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)

    got_stack = megatron.permute_qkv(
        jax.device_get(state.params["blocks"]), model.cfg.d_model,
        model.cfg.n_heads, tp, inverse=True)
    got_blocks = pp.unstack_blocks(got_stack, stack_ndims=3)
    ref_blocks = jax.device_get(ref_params["blocks"])
    for got, ref in zip(got_blocks, ref_blocks):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            got, ref)


def test_pipeline_rejects_seq_sharded_attention():
    """ring/striped/ulysses need a 'seq' mesh axis the pipe mesh does not
    bind; the guard must fire for tp=1 too (previously only tp>1 was
    checked and tp=1 failed at trace time with an unbound-axis error)."""
    devs = jax.devices("cpu")[:2]
    mesh = make_mesh(MeshConfig(data=1, pipe=2), devices=devs)
    model = tiny_model(4, attention="ring")
    with pytest.raises(NotImplementedError, match="seq-sharded"):
        pp.make_pipeline_train_step(model, optim.sgd(0.1), mesh)

def test_pipeline_seq_matches_single_device():
    """PP x SP (round 4): ring attention over 'seq' inside pipeline stages
    — activations rotate over 'pipe' while each stage's attention rings
    over the sequence shards.  Ring attention is exact, so the composed
    step must match the single-device dense model on the same weights."""
    pipe, sp, n_mb = 2, 2, 2
    devs = jax.devices("cpu")[: pipe * sp * 2]
    mesh = make_mesh(MeshConfig(data=2, pipe=pipe, seq=sp), devices=devs)
    model = tiny_model(4, attention="ring")
    opt = optim.sgd(lr=0.1, momentum=0.9)
    batch = lm_batch(rows=2 * n_mb * 2)

    state, loss = pp.run_one_step(model, opt, mesh, batch, prng.init_key(0),
                                  n_microbatches=n_mb)

    # oracle: the DENSE model with the same params (ring == dense math;
    # init is attention-independent)
    dense = tiny_model(4, attention="dense")
    params = dense.init(prng.init_key(0))
    ref_loss, ref_params = reference_step(dense, opt, params, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    got_blocks = pp.unstack_blocks(jax.device_get(state.params["blocks"]))
    ref_blocks = jax.device_get(ref_params["blocks"])
    for got, ref in zip(got_blocks, ref_blocks):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            got, ref)
    for name in ("embed", "pos", "ln_f", "head"):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            jax.device_get(state.params[name]),
            jax.device_get(ref_params[name]))


def test_pipeline_seq_requires_seq_axis_match():
    """Seq-sharded attention without a 'seq' mesh axis, and a seq axis
    with dense attention, both get specific errors."""
    devs = jax.devices("cpu")[:2]
    mesh = make_mesh(MeshConfig(data=1, pipe=2), devices=devs)
    with pytest.raises(NotImplementedError, match="'seq' mesh axis"):
        pp.make_pipeline_train_step(tiny_model(4, attention="ring"),
                                    optim.sgd(0.1), mesh)
    mesh_sp = make_mesh(MeshConfig(pipe=2, seq=2),
                        devices=jax.devices("cpu")[:4])
    with pytest.raises(ValueError, match="not seq-sharded"):
        pp.make_pipeline_train_step(tiny_model(4, attention="dense"),
                                    optim.sgd(0.1), mesh_sp)


def test_pipeline_seq_tensor_matches_single_device():
    """PP x SP x TP (round 4): ring attention over 'seq' inside
    Megatron-sharded pipeline stages (heads over 'tensor') while
    activations rotate over 'pipe' — three model axes in one program.
    Ring attention is exact, so the composed step must match the
    single-device dense model on the same weights."""
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        megatron,
    )

    pipe, sp, tp, n_mb = 2, 2, 2, 2
    devs = jax.devices("cpu")[: pipe * sp * tp]
    mesh = make_mesh(MeshConfig(data=1, pipe=pipe, seq=sp, tensor=tp),
                     devices=devs)
    model = tiny_model(4, attention="ring")
    opt = optim.sgd(lr=0.1, momentum=0.9)
    batch = lm_batch(rows=2 * n_mb)

    state, loss = pp.run_one_step(model, opt, mesh, batch, prng.init_key(0),
                                  n_microbatches=n_mb)

    dense = tiny_model(4, attention="dense")
    params = dense.init(prng.init_key(0))
    ref_loss, ref_params = reference_step(dense, opt, params, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    got_stack = megatron.permute_qkv(
        jax.device_get(state.params["blocks"]), model.cfg.d_model,
        model.cfg.n_heads, tp, inverse=True)
    got_blocks = pp.unstack_blocks(got_stack)
    ref_blocks = jax.device_get(ref_params["blocks"])
    for got, ref in zip(got_blocks, ref_blocks):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            got, ref)
    for name in ("embed", "pos", "ln_f", "head"):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            jax.device_get(state.params[name]),
            jax.device_get(ref_params[name]))


def test_pipeline_seq_expert_matches_dense():
    """PP x SP x EP — GPipe ring x ring attention x all_to_all experts in
    one shard_map program (8 devices = 2x2x2).  Generous capacity keeps
    routing drop-free, so one step matches the single-device dense-MoE
    model (aux_weight=0 — per-shard aux means differ from the global
    mean by design, as in every MoE layout-parity pin)."""
    pipe, sp, ep_, n_mb = 2, 2, 2, 2
    rows = 4 * ep_
    capacity = rows * T
    devs = jax.devices("cpu")[:8]
    mesh = make_mesh(MeshConfig(data=1, pipe=pipe, seq=sp, expert=ep_),
                     devices=devs)
    model = Transformer(TransformerConfig(
        vocab_size=VOCAB, max_seq_len=T, n_layers=4, d_model=32,
        n_heads=4, d_ff=64, attention="ring", moe_experts=4,
        moe_capacity=capacity, moe_expert_axis="expert"))
    opt = optim.sgd(lr=0.1, momentum=0.9)
    batch = lm_batch(rows=rows)

    state = pp.init_pipeline_state(model, opt, prng.init_key(0), pipe)
    state = pp.shard_pipeline_state(state, mesh, opt)
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows_spec = ("data", "fsdp", "expert")
    placed = {k: jax.device_put(
        jnp.asarray(v), NamedSharding(
            mesh, P(rows_spec, "seq") if k != "mask" else P(rows_spec)))
        for k, v in batch.items()}
    step = pp.make_pipeline_train_step(model, opt, mesh,
                                       n_microbatches=n_mb, donate=False,
                                       aux_weight=0.0)
    state, loss = step(state, placed)

    dense = Transformer(TransformerConfig(
        vocab_size=VOCAB, max_seq_len=T, n_layers=4, d_model=32,
        n_heads=4, d_ff=64, attention="dense", moe_experts=4,
        moe_capacity=capacity))
    params = dense.init(prng.init_key(0))
    ref_loss, ref_params = reference_step(dense, opt, params, batch)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    got_blocks = pp.unstack_blocks(jax.device_get(state.params["blocks"]))
    ref_blocks = jax.device_get(ref_params["blocks"])
    for got, ref in zip(got_blocks, ref_blocks):
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            got, ref)


def test_pipeline_four_axis_pp_sp_ep_tp_subprocess():
    """The FULL four-model-axis composition — pipe x seq x expert x tensor
    in one shard_map program — needs 16 devices, so it runs in a
    subprocess with its own virtual-device count (same pattern as the
    multi-process tests).  One step must match the single-device
    dense-MoE model (ring attention is exact; ample capacity keeps
    routing drop-free)."""
    import json
    import os
    import subprocess
    import sys

    script = r"""
import json
import numpy as np
import jax
import jax.numpy as jnp

from neural_networks_parallel_training_with_mpi_tpu.config import MeshConfig
from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
    Transformer, TransformerConfig,
)
from neural_networks_parallel_training_with_mpi_tpu.ops import losses, optim
from neural_networks_parallel_training_with_mpi_tpu.parallel import (
    megatron, pipeline as pp,
)
from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
    make_mesh,
)
from neural_networks_parallel_training_with_mpi_tpu.utils import prng

V, T = 64, 8
rows = 8
capacity = rows * T
mesh = make_mesh(MeshConfig(data=1, pipe=2, seq=2, expert=2, tensor=2),
                 devices=jax.devices("cpu")[:16])
model = Transformer(TransformerConfig(
    vocab_size=V, max_seq_len=T, n_layers=2, d_model=32, n_heads=4,
    d_ff=64, attention="ring", moe_experts=4, moe_capacity=capacity,
    moe_expert_axis="expert"))
opt = optim.sgd(lr=0.1, momentum=0.9)
rng = np.random.default_rng(0)
tok = rng.integers(0, V, (rows, T + 1))
batch = {"x": tok[:, :-1].astype(np.int32),
         "y": tok[:, 1:].astype(np.int32),
         "mask": np.ones((rows,), np.float32)}

state, loss = pp.run_one_step(model, opt, mesh, batch, prng.init_key(0),
                              n_microbatches=2)

dense = Transformer(TransformerConfig(
    vocab_size=V, max_seq_len=T, n_layers=2, d_model=32, n_heads=4,
    d_ff=64, attention="dense", moe_experts=4, moe_capacity=capacity))
params = dense.init(prng.init_key(0))

def scalar(p):
    logits = dense.apply(p, jnp.asarray(batch["x"]))
    s, c = losses.softmax_cross_entropy(
        logits, jnp.asarray(batch["y"]), jnp.asarray(batch["mask"]))
    return s / c

ref_loss_val = scalar(params)
grads = jax.grad(scalar)(params)
ref_params, _ = opt.update(grads, opt.init(params), params)

np.testing.assert_allclose(float(loss), float(ref_loss_val),
                           rtol=1e-5, atol=1e-6)
got_stack = megatron.permute_qkv(
    jax.device_get(state.params["blocks"]), 32, 4, 2, inverse=True)
got_blocks = pp.unstack_blocks(got_stack)
ref_blocks = jax.device_get(ref_params["blocks"])
# four stacked collective reductions (pipe + expert + seq psums, ring
# online-softmax) reassociate more f32 sums than any pairwise layout;
# tolerances match the MoE layout-parity pins (tests/test_moe.py)
for got, ref in zip(got_blocks, ref_blocks):
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4),
        got, ref)
print(json.dumps({"ok": True, "loss": float(loss)}))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900,
                         env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, (out.stderr or "")[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and np.isfinite(rec["loss"])
