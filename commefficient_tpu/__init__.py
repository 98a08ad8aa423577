"""CommEfficient-TPU: a TPU-native communication-efficient federated learning framework.

A from-scratch JAX/XLA re-design of the capabilities of Tzq2doc/CommEfficient
(reference layout documented in SURVEY.md). The reference simulates federated
clients with a parameter-server process, per-GPU worker processes, shared
memory and NCCL (reference: fed_aggregator.py, fed_worker.py). Here the whole
federated round is ONE functional SPMD program: clients are a sharded batch
axis on a `jax.sharding.Mesh`, aggregation is `psum`/`reduce_scatter` over
ICI, and all state lives in a `FedState` pytree that stays on device.

Subpackages
-----------
- ``ops``:      compression kernels (top-k, CountSketch), pytree flattening, clipping
- ``core``:     client step, server update rules, the jitted federated round
- ``parallel``: mesh construction, sharded round step, ring attention
- ``models``:   Flax models (ResNet family, Fixup variants, GPT-2 DoubleHeads)
- ``data``:     federated datasets / client samplers (static-shape, TPU-friendly)
- ``utils``:    schedules, loggers, timers
"""

__version__ = "0.1.0"

from commefficient_tpu.config import FedConfig  # noqa: F401
