"""distributed_pytorch_example_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA rebuild of the capabilities of
``northflank-examples/distributed-pytorch-example`` (reference mounted at
``/root/reference``): multi-host data-parallel training with compiled gradient
all-reduce, deterministic sharded data loading with per-epoch reshuffle,
cross-replica mean metrics, host-0 best/latest checkpointing with resume,
hostname-derived rendezvous, and containerized launch — extended TPU-first with
device meshes (data / fsdp / tensor / sequence axes), tensor & sequence
parallelism, ring attention, and Pallas kernels.

Architecture (reference layer map is in SURVEY.md §1):

- ``runtime/``  — process bootstrap (`jax.distributed`), mesh construction,
  process-tagged logging. TPU-native replacement for the reference's
  torchrun + gloo process-group layer (reference train.py:70-98).
- ``data/``     — deterministic global-permutation sharded sampling
  (reference's ``DistributedSampler`` contract, train.py:101-116), synthetic +
  real dataset pipelines, host→device sharded batch assembly with prefetch.
- ``models/``   — flax model zoo for the BASELINE.json configs: SimpleNet MLP
  (train.py:32-50 parity), ResNet-18/50, ViT-B/16, BERT-base MLM, GPT-2 124M.
- ``ops/``      — attention ops: fused/flash (Pallas) and ring attention
  (sequence-parallel shard_map) with a pure-XLA reference path.
- ``parallel/`` — partition rules (DP/FSDP/TP/SP), sharding application,
  collective helpers. The TPU-native replacement for DDP (train.py:233).
- ``train/``    — jit-compiled train/eval steps, the epoch loop, metrics, and
  best/latest checkpointing with epoch-granularity resume (train.py:178-318).
- ``launch/``   — per-host entrypoint + container image (entrypoint.sh,
  Dockerfile parity).

Typical use::

    import distributed_pytorch_example_tpu as dpx

    dpx.runtime.initialize()             # multi-host rendezvous (no-op 1-proc)
    mesh = dpx.runtime.make_mesh()       # all devices on the 'data' axis
    ...
"""

import time as _time

# the package's import (JAX's among it) is the first span of the record
_IMPORT_NS, _IMPORT_CPU_S = _time.perf_counter_ns(), _time.process_time()

__version__ = "0.1.0"

from distributed_pytorch_example_tpu import runtime  # noqa: F401,E402
from distributed_pytorch_example_tpu import data  # noqa: F401,E402
from distributed_pytorch_example_tpu import models  # noqa: F401,E402
from distributed_pytorch_example_tpu import ops  # noqa: F401,E402
from distributed_pytorch_example_tpu import parallel  # noqa: F401,E402
from distributed_pytorch_example_tpu import robustness  # noqa: F401,E402
from distributed_pytorch_example_tpu import train  # noqa: F401,E402
from distributed_pytorch_example_tpu import utils  # noqa: F401,E402
from distributed_pytorch_example_tpu.telemetry import trace as _trace  # noqa: E402

_trace.add(
    "import", _IMPORT_NS, _time.perf_counter_ns(),
    _trace.cpu_args(_IMPORT_CPU_S),
)
