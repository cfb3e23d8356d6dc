"""Host→device batch pipeline.

TPU-native replacement for the reference's ``DataLoader(num_workers=2,
pin_memory=...)`` + ``DistributedSampler`` pair (reference train.py:101-116).
The shape of the problem differs from torch's (SURVEY.md §7 "Per-host batch
semantics"): torchrun gives one process per *device*, each loading its own
shard; JAX gives one process per *host* feeding all local devices. So:

- the dataset is sharded **by process** with :class:`ShardedSampler`
  (identical determinism contract to ``DistributedSampler``);
- each step, the host assembles its local slice of the global batch and the
  loader forms a single global ``jax.Array`` sharded over the mesh's data
  axes (``jax.make_array_from_process_local_data``), so the jitted train step
  sees one logical batch regardless of topology;
- a SUPERVISED background worker pre-assembles and pre-transfers the next
  batches (replaces ``num_workers=2`` + ``pin_memory`` H2D overlap,
  train.py:112-113): graft-intake's :class:`~.intake.PrefetchWorker` —
  bounded queue with timeouts on every wait, heartbeats, bounded retry on
  transient shard-read ``OSError``, and crash ⇒ deterministic restart
  that re-produces exactly the batch the consumer expects next (batch
  assembly is a pure function of the batch index).

Static shapes: the final partial batch is padded by wrapping (same spirit as
``DistributedSampler``'s wrap-padding) so every step has identical shape and
XLA never recompiles; ``drop_last=True`` drops it instead.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator, Optional, Sequence

import numpy as np

from distributed_pytorch_example_tpu.data import intake
from distributed_pytorch_example_tpu.data.sampler import ShardedSampler
from distributed_pytorch_example_tpu.runtime import mesh as mesh_lib


def _get_batch(dataset, indices: np.ndarray) -> Dict[str, np.ndarray]:
    if hasattr(dataset, "get_batch"):
        return dataset.get_batch(indices)
    elems = [dataset[int(i)] for i in indices]
    first = elems[0]
    if isinstance(first, dict):
        return {k: np.stack([e[k] for e in elems]) for k in first}
    # tuple convention (x, y) — the reference's __getitem__ shape (train.py:66-67)
    return {
        "x": np.stack([e[0] for e in elems]),
        "y": np.stack([e[1] for e in elems]),
    }


class DeviceLoader:
    """Iterates sharded device batches for one process of a multi-host job."""

    def __init__(
        self,
        dataset,
        global_batch_size: int,
        mesh=None,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = False,
        prefetch: int = 2,
        num_shards: Optional[int] = None,
        shard_id: Optional[int] = None,
    ):
        import jax

        self.dataset = dataset
        self.mesh = mesh
        if num_shards is None:
            num_shards = jax.process_count()
        if shard_id is None:
            shard_id = jax.process_index()
        if global_batch_size % num_shards != 0:
            raise ValueError(
                f"global_batch_size {global_batch_size} not divisible by "
                f"{num_shards} processes"
            )
        self.global_batch_size = global_batch_size
        self.local_batch_size = global_batch_size // num_shards
        self.sampler = ShardedSampler(
            len(dataset),
            num_shards=num_shards,
            shard_id=shard_id,
            shuffle=shuffle,
            seed=seed,
            drop_last=drop_last,
        )
        self.drop_last = drop_last
        self.prefetch = prefetch
        # graft-scope hook: Trainer.fit attaches its Telemetry scope here so
        # batch assembly and host->device transfers emit "assemble" and
        # "h2d" spans (the prefetch thread's track in the trace) and
        # consumer-side queue waits land in the per-boundary data_stall_ms
        # counter; None = no tracing
        self.telemetry = None
        # graft-intake counters, accumulated across iterations (read by
        # the bench input-plane probe and operators): consumer stalls,
        # worker restarts, retried shard reads
        self.data_stall_ms = 0.0
        self.batches_served = 0
        self.stalled_batches = 0
        self.worker_restarts = 0
        self.io_retries = 0
        if drop_last:
            self.steps_per_epoch = len(self.sampler) // self.local_batch_size
        else:
            self.steps_per_epoch = -(-len(self.sampler) // self.local_batch_size)
        if self.steps_per_epoch == 0:
            raise ValueError("Dataset shard smaller than one batch with drop_last")
        self._sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            axes = mesh_lib.data_axes(mesh)
            self._sharding = NamedSharding(mesh, PartitionSpec(axes))

    def set_epoch(self, epoch: int) -> None:
        """Reseed the global shuffle (reference train.py:267 contract)."""
        self.sampler.set_epoch(epoch)

    def __len__(self) -> int:
        return self.steps_per_epoch

    def _epoch_indices(self) -> np.ndarray:
        """This epoch's padded shard-local index order (pure fn of epoch)."""
        indices = self.sampler.shard_indices()
        n = self.steps_per_epoch * self.local_batch_size
        if n > len(indices):  # wrap-pad the final partial batch
            indices = np.concatenate([indices, indices[: n - len(indices)]])
        return indices

    def _span(self, name: str):
        scope = self.telemetry
        if scope is None:
            return contextlib.nullcontext()
        return scope.span(name)

    def _assemble(self, step: int, indices: np.ndarray) -> Dict[str, np.ndarray]:
        """Host batch for one step — a pure function of (epoch, step), the
        property that makes supervised-worker restart exact."""
        lo = step * self.local_batch_size
        with self._span("assemble"):
            return _get_batch(
                self.dataset, indices[lo : lo + self.local_batch_size]
            )

    def _host_batches(
        self, start_step: int = 0
    ) -> Iterator[Dict[str, np.ndarray]]:
        indices = self._epoch_indices()
        for step in range(start_step, self.steps_per_epoch):
            yield self._assemble(step, indices)

    def _to_device(self, host_batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        import jax

        with self._span("h2d"):
            if self._sharding is not None:
                return {
                    k: jax.make_array_from_process_local_data(
                        self._sharding, v
                    )
                    for k, v in host_batch.items()
                }
            return {k: jax.device_put(v) for k, v in host_batch.items()}

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return self.iter_from(0)

    def iter_from(self, start_step: int) -> Iterator[Dict[str, Any]]:
        """Iterate this epoch's batches from ``start_step`` onward.

        Step-level resume support: the sampler's permutation is a pure
        function of (seed, epoch), so skipping the first ``start_step``
        batches reproduces EXACTLY the batches an uninterrupted run would
        have seen — skipped batches are never assembled or transferred.

        The prefetch path runs under graft-intake supervision
        (:class:`~.intake.PrefetchWorker`): worker crashes restart at the
        consumer cursor re-producing the exact batch, transient shard-read
        ``OSError`` is retried in place, and abandoning this generator
        mid-epoch (``GeneratorExit`` — e.g. a ``BadStepBudgetExceeded``
        rollback unwinding the epoch) stops, drains, and JOINS the worker
        instead of leaking a thread blocked on a full queue.
        """
        if not 0 <= start_step <= self.steps_per_epoch:
            raise ValueError(
                f"start_step {start_step} outside [0, {self.steps_per_epoch}]"
            )
        if self.prefetch <= 0:
            for hb in self._host_batches(start_step):
                yield self._to_device(hb)
            return

        indices = self._epoch_indices()
        worker = intake.PrefetchWorker(
            make_batch=lambda i: self._to_device(
                self._assemble(i, indices)
            ),
            start=start_step,
            stop=self.steps_per_epoch,
            maxsize=self.prefetch,
            name=f"loader-shard{self.sampler.shard_id}",
            telemetry=self.telemetry,
        )
        try:
            while True:
                item = worker.next_batch()
                if item is None:
                    break
                self.batches_served += 1
                yield item
        finally:
            worker.close()
            self.data_stall_ms += worker.stall_ms
            self.stalled_batches += worker.empty_gets
            self.worker_restarts += worker.restarts
            self.io_retries += worker.io_retries
