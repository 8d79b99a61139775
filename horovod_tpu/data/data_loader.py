"""Data loading helpers.

Reference: ``horovod/data/data_loader_base.py`` (``BaseDataLoader`` and
``AsyncDataLoaderMixin`` — a background-thread prefetch queue, :23-151).
TPU additions: :class:`ShardedDataset` for per-worker sharding (the
reference leaves sharding to torch's DistributedSampler) and device
prefetch hooks (host→HBM transfer overlapped with compute).
"""

from __future__ import annotations

import collections
import queue
import threading
from typing import Any, Iterator, Optional, Sequence

import jax

from horovod_tpu.profiling import annotate, scopes


class BaseDataLoader:
    """Iteration contract (reference: ``BaseDataLoader:23-60``)."""

    def __len__(self) -> int:
        raise NotImplementedError

    def _iterate(self) -> Iterator[Any]:
        raise NotImplementedError

    def __iter__(self) -> Iterator[Any]:
        return self._iterate()


class AsyncDataLoaderMixin:
    """Background-thread prefetch (reference: ``AsyncDataLoaderMixin:63-151``).

    Mix in FIRST: ``class MyAsyncLoader(AsyncDataLoaderMixin, MyLoader)``.
    ``async_loader_queue_size=0`` disables prefetch (synchronous).
    """

    def __init__(self, *args: Any, async_loader_queue_size: int = 64,
                 **kwargs: Any) -> None:
        self._queue_size = async_loader_queue_size
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._closing = False
        super().__init__(*args, **kwargs)

    def close_async_loader(self) -> None:
        """Reference: ``close_async_loader`` — drain and join."""
        self._closing = True
        if self._q is not None:
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    class _End:
        def __init__(self, error=None):
            self.error = error

    def _put(self, item) -> bool:
        """Bounded put that aborts when the loader is closing (so the
        producer can never wedge in a full queue)."""
        while not self._closing:
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _producer(self) -> None:
        error = None
        try:
            for item in super()._iterate():
                if not self._put(item):
                    return
        except BaseException as e:  # surface loader errors to the consumer
            error = e
        self._put(self._End(error))

    def __iter__(self) -> Iterator[Any]:
        if self._queue_size <= 0:
            yield from super()._iterate()
            return
        self._closing = False
        self._q = queue.Queue(self._queue_size)
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()
        while True:
            item = self._q.get()
            if isinstance(item, AsyncDataLoaderMixin._End):
                if item.error is not None:
                    raise item.error
                break
            yield item


def device_prefetch(iterator, sharding=None, buffer_size: int = 2):
    """Keep ``buffer_size`` batches RESIDENT ON DEVICE ahead of the
    consumer (double-buffered by default): ``jax.device_put`` is
    asynchronous, so the host→HBM transfer of the next batches overlaps
    the compute consuming the current one and H2D drops off the step's
    critical path (the reference's analog is the CUDA-stream prefetch
    users pair with its AsyncDataLoaderMixin).

    ``sharding`` places each leaf (e.g. ``hvd.batch_sharding(mesh)`` for
    dp-sharded batches); ``None`` uses the default device. When the
    sharding spans devices of OTHER processes too (a multi-host mesh),
    each process's batch is treated as its process-local shard and the
    global array is assembled with
    ``jax.make_array_from_process_local_data`` — so the documented
    ShardedDataset-per-rank + ``batch_sharding(mesh)`` stack is correct
    on pods as well. Works on any iterator of pytrees — stack with
    :class:`AsyncDataLoaderMixin` so the HOST side (decode/augment) is
    also off the critical path: background thread feeds
    ``device_prefetch`` feeds the step.

    If the source iterator raises mid-stream, batches already
    transferred are yielded first; the error surfaces at its true
    position in the stream."""
    if buffer_size < 1:
        # eager: a generator would defer this to the first next() deep
        # inside the training loop, far from the misconfigured call
        raise ValueError(f"buffer_size={buffer_size} must be >= 1")
    return _device_prefetch_gen(iter(iterator), sharding, buffer_size)


def _device_prefetch_gen(it, sharding, buffer_size: int):
    q: "collections.deque" = collections.deque()
    pending_error = None

    if sharding is not None and not getattr(
            sharding, "is_fully_addressable", True):
        # multi-host mesh: this process holds only ITS shard of the
        # global batch
        def place(batch):
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(
                    sharding, x), batch)
    else:
        def place(batch):
            # device_put takes the whole pytree: one dispatch per batch
            return jax.device_put(batch, sharding)

    def put_next() -> bool:
        nonlocal pending_error
        if pending_error is not None:
            return False
        try:
            with annotate(scopes.INPUT_SOURCE):
                batch = next(it)
        except StopIteration:
            return False
        except BaseException as e:
            # drain the already-transferred batches before surfacing it
            pending_error = e
            return False
        with annotate(scopes.INPUT_PLACE):
            q.append(place(batch))
        return True

    for _ in range(buffer_size):
        if not put_next():
            break
    while q:
        out = q.popleft()
        put_next()  # enqueue the NEXT transfer before handing this one out
        yield out
    if pending_error is not None:
        raise pending_error


class ShardedDataset(BaseDataLoader):
    """Deterministic per-worker shard of an indexable dataset: worker r of n
    sees items ``r, r+n, r+2n, ...`` after an epoch-seeded shuffle — the
    sharding contract of torch's DistributedSampler that reference users
    pair with hvd (``torch/elastic/sampler.py`` is its elastic variant)."""

    def __init__(self, data: Sequence[Any], rank: int, size: int,
                 shuffle: bool = True, seed: int = 0) -> None:
        self._data = data
        self._rank = rank
        self._size = max(size, 1)
        self._shuffle = shuffle
        self._seed = seed
        self._epoch = 0
        self._cursor = 0  # items this worker yielded in the current epoch
        self._resume_skip = 0

    def set_epoch(self, epoch: int) -> None:
        """Reshuffle per epoch (reference: ``ElasticSampler.set_epoch``).
        Re-announcing the CURRENT epoch keeps the restored cursor — the
        standard resume loop (``load_state_dict`` then ``set_epoch``
        inside the epoch loop) must not replay committed items."""
        if epoch != self._epoch:
            self._cursor = 0
            self._resume_skip = 0
        self._epoch = epoch

    def state_dict(self) -> dict:
        """Checkpointable data position: ``{"epoch", "cursor"}`` —
        ``cursor`` counts the items THIS worker has yielded in the
        current epoch, so data position rides the same commit as model
        state (reference analog: ``ElasticSampler.state_dict``).  With a
        prefetching wrapper the cursor counts items handed to the
        prefetcher, which can run a few batches ahead of the consumer —
        commit ordering, not a correctness issue."""
        return {"epoch": self._epoch, "cursor": self._cursor}

    def load_state_dict(self, state: dict) -> None:
        """Resume mid-epoch: the next iteration replays the epoch's
        deterministic order and skips the first ``cursor`` items.  The
        cursor is per-worker: after an elastic world-size change start
        from the next epoch boundary instead (the shard stride changed,
        so mid-epoch positions don't map)."""
        self._epoch = int(state["epoch"])
        self._cursor = int(state.get("cursor", 0))
        self._resume_skip = self._cursor

    def __len__(self) -> int:
        return len(self._data) // self._size

    def _iterate(self) -> Iterator[Any]:
        import numpy as np
        idx = np.arange(len(self._data))
        if self._shuffle:
            rng = np.random.RandomState(self._seed + self._epoch)
            rng.shuffle(idx)
        n = len(self) * self._size  # drop remainder so all workers agree
        skip, self._resume_skip = self._resume_skip, 0
        self._cursor = skip
        for pos, i in enumerate(idx[self._rank:n:self._size]):
            if pos < skip:
                continue
            self._cursor = pos + 1
            yield self._data[int(i)]
        # a completed epoch resets the position (an abandoned iterator —
        # e.g. a mid-epoch checkpoint + crash — keeps its cursor)
        self._cursor = 0
