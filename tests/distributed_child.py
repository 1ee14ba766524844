"""Child process for the 2-process jax.distributed integration test.

Each child is one "host" of a 2-process CPU world (2 virtual devices per
process -> a 4-device global mesh), formed exactly the way a TPU pod slice
forms its world: ``jax.distributed.initialize`` via ``world_setup``.  This
is the role one ``mpiexec`` rank plays for the reference
(dataParallelTraining_NN_MPI.py:61-63) — but exercised for real, across OS
processes, unlike the single-process degrade mode the rest of the suite
uses.

Covers: world formation, barrier, broadcast_host_array, per-host data
loading into a global mesh, a jitted DP train step over the 2-host mesh,
replica-consistency assertion, the SDC sweep (detect -> localize -> heal
on an injected bitflip: both the local-shard and the cross-host digest
verdicts, DESIGN.md §9), an orbax shard-parallel checkpoint
save + restore round trip, and cross-host SP (ring-attention ppermute),
TP (partitioner all-reduces), and EP (MoE all_to_all) steps whose
collectives span the process boundary.

Usage: distributed_child.py <process_id> <num_processes> <port> <tmpdir>
Prints one JSON line with per-phase results.
"""

import json
import os
import sys


def main() -> int:
    pid, n, port, tmp = (int(sys.argv[1]), int(sys.argv[2]), sys.argv[3],
                         sys.argv[4])
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import numpy as np

    from neural_networks_parallel_training_with_mpi_tpu.config import (
        MeshConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.models.mlp import MLP
    from neural_networks_parallel_training_with_mpi_tpu.ops import optim
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        data_parallel as dp,
        distributed,
        sharding as shd,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
        make_mesh, world_setup,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.state import (
        TrainState,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng

    report = {"pid": pid}

    # ---- world formation (reference :61-63 / mpiexec) --------------------
    idx, cnt = world_setup(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=n, process_id=pid, timeout_s=60)
    report["process_index"] = idx
    report["process_count"] = cnt
    assert idx == pid and cnt == n, (idx, cnt)
    assert distributed.is_multi_host()

    # ---- barrier + host-array broadcast (reference :87/:97 bcast) --------
    distributed.barrier("smoke")
    src = np.arange(8, dtype=np.float64) * 3.5
    got = distributed.broadcast_host_array(
        src if idx == 0 else np.zeros_like(src))
    assert np.array_equal(np.asarray(got), src), got
    report["broadcast_ok"] = True

    # ---- global mesh over both hosts' devices ----------------------------
    devices = jax.devices()
    assert len(devices) == 2 * n, devices
    mesh = make_mesh(MeshConfig(data=2 * n), devices=devices)

    # ---- per-host data loading: each host materializes only its rows -----
    # (unlike the reference, which materializes everything on rank 0, :72)
    rng = np.random.default_rng(0)  # same seed -> same global dataset
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = (x @ np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
         + 0.1).astype(np.float32)
    batch = shd.shard_batch(mesh, {
        "x": x, "y": y, "mask": np.ones((32,), np.float32)})

    # ---- jitted SPMD train step over the 2-host mesh ---------------------
    model = MLP(4, (8,), 1)
    opt = optim.sgd(lr=1e-2, momentum=0.9)
    state = TrainState.create(model, opt, prng.init_key(0))
    state = dp.replicate_state(state, mesh)
    step = dp.make_train_step(model, opt, mesh, "mse", "global_mean")
    losses = []
    for _ in range(5):
        state, loss = step(state, batch)
        losses.append(float(jax.device_get(loss)))
    report["losses"] = [round(v, 8) for v in losses]
    assert losses[-1] < losses[0], losses  # actually training

    # ---- replica consistency across hosts --------------------------------
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        consistency,
    )

    consistency.assert_replicated(state, what="2-host state")
    report["replicas_ok"] = True

    # ---- SDC sweep: detect -> localize on an injected bitflip ------------
    # (DESIGN.md §9) — not just the healthy-path assert_replicated.  Both
    # the fingerprint gather and the leaf-digest sweep are collectives, so
    # every phase below runs on BOTH processes with the corruption
    # injected on process 1 only.
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        faults,
    )

    fpr = consistency.Fingerprinter(state, mesh)
    assert fpr.n_leaves > 0
    target = state.params
    flat, _ = jax.tree_util.tree_flatten_with_path(target)
    leaf_name = jax.tree_util.keystr(flat[0][0])

    def with_flip(leaf_fn):
        new_flat = [leaf_fn(leaf) if jax.tree_util.keystr(p) == leaf_name
                    else leaf for p, leaf in flat]
        treedef = jax.tree_util.tree_flatten(target)[1]
        return state._replace(
            params=jax.tree_util.tree_unflatten(treedef, new_flat))

    # phase A: flip one bit in process 1's LOCAL shard 1 -> process 1's
    # devices disagree internally; the gathered digest matrix convicts
    # process 1 ("local"), and process 1's divergence_report names the
    # shard while process 0's stays clean
    bad = (with_flip(lambda l: faults.flip_bit_in_shard(l, 1, 9))
           if idx == 1 else state)
    digests, _folds = consistency.Fingerprinter.fetch(fpr.compute(bad))
    mat = np.asarray(distributed.allgather_host_array(digests))
    verdict = consistency.digest_report(mat)
    assert verdict.get("local") == [1] and verdict.get("cross") == [], (
        verdict)
    local_rep = consistency.divergence_report(bad)
    if idx == 1:
        assert list(local_rep) and local_rep[next(iter(local_rep))][
            "shards"] == [1], local_rep
        healed, _ = consistency.heal_replication(bad, local_rep)
        assert consistency.check_replicas(healed) == {}
    else:
        assert local_rep == {}, local_rep
    report["sdc_local_ok"] = True

    # phase B: flip the SAME bit in BOTH of process 1's shards -> each
    # host internally consistent but the hosts disagree: the digest
    # matrix says "cross", and the leaf-digest sweep names the leaf and
    # the diverging process on EVERY host (the symmetric report the
    # trainer's rollback-heal path branches on)
    bad2 = (with_flip(lambda l: faults.flip_bit_in_shard(
        faults.flip_bit_in_shard(l, 0, 9), 1, 9)) if idx == 1 else state)
    digests2, _ = consistency.Fingerprinter.fetch(fpr.compute(bad2))
    mat2 = np.asarray(distributed.allgather_host_array(digests2))
    verdict2 = consistency.digest_report(mat2)
    assert verdict2.get("cross") == [1] and verdict2.get("local") == [], (
        verdict2)
    assert consistency.divergence_report(bad2) == {}  # locally lockstep
    sweep = distributed.cross_host_report(consistency.leaf_digests(bad2))
    assert sweep, "cross-host sweep missed the diverged leaf"
    assert any(leaf_name in k for k in sweep), (leaf_name, sweep)
    assert all(v["processes"] == [1] for v in sweep.values()), sweep
    report["sdc_cross_ok"] = True

    # ---- checkpoint round trip (orbax shard-parallel for multi-host) -----
    from neural_networks_parallel_training_with_mpi_tpu.utils import (
        checkpoint as ckpt,
    )

    ckpt_dir = os.path.join(tmp, "ckpt")
    ckpt.save(ckpt_dir, state)
    distributed.barrier("after-save")
    restored = ckpt.restore(ckpt_dir, state)
    assert restored is not None
    p0 = jax.device_get(jax.tree_util.tree_leaves(state.params)[0])
    r0 = jax.device_get(jax.tree_util.tree_leaves(restored.params)[0])
    assert np.array_equal(np.asarray(p0), np.asarray(r0))
    report["checkpoint_ok"] = True

    # ---- cross-host sequence parallelism: ring attention whose ppermute
    # hops cross the process boundary (the 'seq' axis pairs device k of
    # host 0 with device k of host 1 via an interleaved device order) ----
    from neural_networks_parallel_training_with_mpi_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel import spmd

    inter = np.asarray(devices).reshape(n, 2).T.reshape(-1)  # seq spans hosts
    mesh_sp = make_mesh(MeshConfig(data=2, seq=n), devices=inter)
    seq_len = 16 * n
    model_sp = Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=seq_len, n_layers=2, d_model=32,
        n_heads=4, d_ff=64, attention="ring"))
    tok = np.random.default_rng(1).integers(0, 64, (4, seq_len + 1))
    sp_batch = {"x": tok[:, :-1].astype(np.int32),
                "y": tok[:, 1:].astype(np.int32),
                "mask": np.ones((4,), np.float32)}
    state_sp = TrainState.create(model_sp, opt, prng.init_key(0))
    _, loss_sp = spmd.run_one_step(model_sp, opt, mesh_sp, state_sp,
                                   sp_batch, loss_name="cross_entropy")
    report["sp_loss"] = round(float(jax.device_get(loss_sp)), 8)
    assert np.isfinite(report["sp_loss"]), report["sp_loss"]
    report["sp_ok"] = True

    # ---- cross-host tensor parallelism: GSPMD Megatron sharding with the
    # 'tensor' axis spanning the hosts — the partitioner's all-reduces run
    # over the distributed backend ------------------------------------------
    from neural_networks_parallel_training_with_mpi_tpu.parallel import gspmd

    mesh_tp = make_mesh(MeshConfig(data=2, tensor=n), devices=inter)
    model_tp = Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=16, n_layers=2, d_model=32, n_heads=4,
        d_ff=64, attention="dense"))
    opt_tp = optim.adam(lr=1e-3)
    state_tp = TrainState.create(model_tp, opt_tp, prng.init_key(0))
    state_tp = gspmd.shard_state(model_tp, state_tp, opt_tp, mesh_tp)
    tok2 = np.random.default_rng(2).integers(0, 64, (4, 17))
    batch_tp = gspmd.shard_batch(mesh_tp, {
        "x": tok2[:, :-1].astype(np.int32),
        "y": tok2[:, 1:].astype(np.int32),
        "mask": np.ones((4,), np.float32)})
    step_tp = gspmd.make_gspmd_train_step(model_tp, opt_tp, mesh_tp,
                                          "cross_entropy",
                                          example_batch=batch_tp,
                                          donate=False)
    _, loss_tp = step_tp(state_tp, batch_tp)
    report["tp_loss"] = round(float(jax.device_get(loss_tp)), 8)
    assert np.isfinite(report["tp_loss"]), report["tp_loss"]
    report["tp_ok"] = True

    # ---- cross-host expert parallelism: the MoE all_to_all slot exchange
    # crosses the process boundary (the 'expert' axis pairs device k of
    # host 0 with device k of host 1, same interleaved order as seq/tp) --
    from neural_networks_parallel_training_with_mpi_tpu.parallel import (
        expert as ep_lib,
    )

    mesh_ep = make_mesh(MeshConfig(data=2, expert=n), devices=inter)
    model_ep = Transformer(TransformerConfig(
        vocab_size=64, max_seq_len=16, n_layers=2, d_model=32, n_heads=4,
        d_ff=64, attention="dense", moe_experts=2 * n,
        moe_expert_axis="expert"))
    tok3 = np.random.default_rng(3).integers(0, 64, (4 * n, 17))
    ep_batch = {"x": tok3[:, :-1].astype(np.int32),
                "y": tok3[:, 1:].astype(np.int32),
                "mask": np.ones((4 * n,), np.float32)}
    _, metrics_ep = ep_lib.run_one_step(model_ep, optim.adam(lr=1e-3),
                                        mesh_ep, ep_batch,
                                        prng.init_key(0))
    report["ep_loss"] = round(float(jax.device_get(metrics_ep["loss"])), 8)
    assert np.isfinite(report["ep_loss"]), report["ep_loss"]
    report["ep_ok"] = True

    distributed.barrier("done")
    report["ok"] = True
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
