"""TPU-native synchronous data-parallel training framework.

A brand-new JAX/XLA framework with the capabilities of the reference
``btourn/Neural-Networks-parallel-training-with-MPI``
(/root/reference/dataParallelTraining_NN_MPI.py): a replicated model is
trained on disjoint shards of a dataset with per-shard gradients averaged
across workers every step.  Where the reference hand-rolls this over mpi4py
(state-dict ``bcast`` at :87, ``Scatter``/``Scatterv`` data distribution at
:108/:138, gather-average-at-root gradient sync at :185-208), this framework
expresses it TPU-first:

* world formation   -> ``jax.distributed`` + ``jax.sharding.Mesh``  (parallel.mesh)
* data distribution -> batch-axis ``NamedSharding`` / host sharding (parallel.sharding, data.loader)
* gradient sync     -> one fused ``lax.pmean``/``psum`` over ICI    (parallel.data_parallel)
* model/optimizer   -> pure-pytree modules + optimizers             (models, ops.optim)

Public API is re-exported here for convenience.
"""

from .config import TrainConfig, MeshConfig, DataConfig, ModelConfig
from .parallel.mesh import make_mesh, world_setup, local_mesh
from .parallel.sharding import (
    shard_sizes,
    pad_to_multiple,
    batch_sharding,
    replicated_sharding,
    shard_batch,
)
from .ops import optim, losses
from .train.trainer import Trainer, TrainState

__version__ = "0.1.0"

__all__ = [
    "TrainConfig",
    "MeshConfig",
    "DataConfig",
    "ModelConfig",
    "make_mesh",
    "world_setup",
    "local_mesh",
    "shard_sizes",
    "pad_to_multiple",
    "batch_sharding",
    "replicated_sharding",
    "shard_batch",
    "optim",
    "losses",
    "Trainer",
    "TrainState",
]
