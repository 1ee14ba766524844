"""Child for the distributed fault-injection test (SURVEY.md §5.3).

Two processes form a jax.distributed world and train in lockstep.  The
VICTIM (process 1) dies abruptly mid-training (os._exit, no cleanup — the
moral equivalent of a crashed MPI rank).  The SURVIVOR (process 0) must
FAIL FAST: either its next collective raises (exit 43) or, if the runtime
blocks instead, the step-hang watchdog fires (exit 42).  What must NOT
happen is the reference's behavior — hanging forever in a collective
(dataParallelTraining_NN_MPI.py:185's gather is a barrier with no timeout;
README.md:10 notes the cluster path was never even run).

Usage: faulty_child.py <process_id> <port>
"""

import json
import os
import sys


def main() -> int:
    pid, port = int(sys.argv[1]), sys.argv[2]
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
        sharding as shd,
    )
    from neural_networks_parallel_training_with_mpi_tpu.parallel.mesh import (
        make_mesh, world_setup,
    )
    from neural_networks_parallel_training_with_mpi_tpu.train.state import (
        TrainState,
    )
    from neural_networks_parallel_training_with_mpi_tpu.utils import prng
    from neural_networks_parallel_training_with_mpi_tpu.utils.watchdog import (
        HangWatchdog,
    )

    world_setup(coordinator_address=f"127.0.0.1:{port}", num_processes=2,
                process_id=pid, timeout_s=60)
    mesh = make_mesh(MeshConfig(data=4), devices=jax.devices())

    rng = np.random.default_rng(0)
    batch = shd.shard_batch(mesh, {
        "x": rng.standard_normal((32, 4)).astype(np.float32),
        "y": rng.standard_normal((32, 1)).astype(np.float32),
        "mask": np.ones((32,), np.float32)})
    model = MLP(4, (8,), 1)
    opt = optim.sgd(lr=1e-2)
    state = dp.replicate_state(
        TrainState.create(model, opt, prng.init_key(0)), mesh)
    step = dp.make_train_step(model, opt, mesh, "mse", "global_mean")

    victim = pid == 1
    watchdog = HangWatchdog(8.0)
    with watchdog:
        for i in range(10_000):
            if victim and i == 20:
                # die like a crashed MPI rank: no shutdown, no goodbye
                os._exit(1)
            try:
                state, loss = step(state, batch)
                # the blocking readback is what stalls when the peer dies
                float(jax.device_get(loss))
            except Exception as e:  # noqa: BLE001 — fail-fast path A
                print(json.dumps({"pid": pid, "error_step": i,
                                  "error": f"{type(e).__name__}"}),
                      flush=True)
                os._exit(43)
            watchdog.pat()
    return 0


if __name__ == "__main__":
    sys.exit(main())
