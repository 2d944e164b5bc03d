"""DataIterator — the per-worker view of a dataset shard.

Reference analogue: `python/ray/data/iterator.py` (``DataIterator`` with
``iter_batches`` / ``iter_torch_batches``).  Train workers receive one of
these from ``session.get_dataset_shard`` and pull host batches from it; the
TPU-first addition is ``iter_jax_batches``, which stages each numpy batch
onto device (optionally sharded over a mesh axis by the caller).
"""

from __future__ import annotations

from typing import Any, Iterator, Optional

from ray_tpu.util import tracing


class DataIterator:
    def __init__(self, dataset):
        self._dataset = dataset

    def iter_batches(self, *, batch_size: int = 256,
                     batch_format: str = "numpy", drop_last: bool = False,
                     local_shuffle_buffer_size: Optional[int] = None,
                     local_shuffle_seed: Optional[int] = None) -> Iterator[Any]:
        return self._dataset.iter_batches(
            batch_size=batch_size, batch_format=batch_format,
            drop_last=drop_last,
            local_shuffle_buffer_size=local_shuffle_buffer_size,
            local_shuffle_seed=local_shuffle_seed)

    def iter_rows(self) -> Iterator[Any]:
        return self._dataset.iter_rows()

    def iter_jax_batches(self, *, batch_size: int = 256,
                         drop_last: bool = True, dtype=None,
                         device=None) -> Iterator[Any]:
        """Numpy batches staged to a JAX device (host→HBM transfer)."""
        import jax
        import jax.numpy as jnp

        for batch in self.iter_batches(batch_size=batch_size,
                                       drop_last=drop_last):
            with tracing.timeline_span("data.to_device"):
                if isinstance(batch, dict):
                    out = {k: jnp.asarray(v, dtype=dtype)
                           if v.dtype.kind in "fiub" else v
                           for k, v in batch.items()}
                else:
                    out = jnp.asarray(batch, dtype=dtype)
                if device is not None:
                    out = jax.device_put(out, device)
            tracing.count("data.batches")
            yield out

    def materialize(self):
        return self._dataset.materialize()

    def count(self) -> int:
        return self._dataset.count()

    def __iter__(self):
        return self.iter_rows()

    def __repr__(self):
        return f"DataIterator({self._dataset!r})"


class StreamSplitDataIterator(DataIterator):
    """One shard of ``Dataset.streaming_split(n)`` (reference:
    `_internal/iterator/stream_split_iterator.py`).

    Blocks are claimed from the split coordinator on demand and executed
    through the dataset's lazy op chain with a small prefetch pipeline —
    nothing materializes up front, and whatever this consumer doesn't
    claim goes to its siblings."""

    def __init__(self, dataset, coordinator, index: int, world: int):
        super().__init__(dataset)
        self._coord = coordinator
        self.index = index
        self.world = world
        self._epoch = 0

    def _claimed_blocks(self):
        """Generator of local blocks for this epoch (prefetch depth 2)."""
        import ray_tpu

        epoch = self._epoch
        self._epoch += 1

        def claim():
            return ray_tpu.get(self._coord.claim.remote(epoch), timeout=120)

        from ray_tpu.data.dataset import _get_block

        pending = []
        for _ in range(2):
            i = claim()
            if i is None:
                break
            pending.append((i, self._dataset._execute_block(i)))
        while pending:
            index, ref = pending.pop(0)
            i = claim()
            if i is not None:
                pending.append((i, self._dataset._execute_block(i)))
            yield _get_block(ref, index)

    def iter_batches(self, *, batch_size: int = 256,
                     batch_format: str = "numpy", drop_last: bool = False,
                     local_shuffle_buffer_size: Optional[int] = None,
                     local_shuffle_seed: Optional[int] = None) -> Iterator[Any]:
        from ray_tpu.data.dataset import _batches_from_block_iter

        return _batches_from_block_iter(
            self._claimed_blocks(), batch_size=batch_size,
            batch_format=batch_format, drop_last=drop_last,
            local_shuffle_buffer_size=local_shuffle_buffer_size,
            local_shuffle_seed=local_shuffle_seed)

    def iter_rows(self) -> Iterator[Any]:
        from ray_tpu.data.block import BlockAccessor

        for block in self._claimed_blocks():
            yield from BlockAccessor.for_block(block).iter_rows()

    def count(self) -> int:
        raise TypeError("a streaming-split shard has no static count — "
                        "its share of blocks is decided by the pull loop")

    def materialize(self):
        raise TypeError("streaming-split shards are consume-once streams")

    def __repr__(self):
        return (f"StreamSplitDataIterator({self.index}/{self.world}, "
                f"{self._dataset!r})")
