"""paddle.io — Dataset / Sampler / DataLoader.

Reference: python/paddle/io/ + python/paddle/fluid/dataloader/. The DataLoader
prefetch pipeline is backed by the native C++ worker core (csrc/) when built;
falls back to a Python thread pool. Host-side batching feeds device transfers
once per step (minimizing host↔HBM traffic).
"""
from __future__ import annotations

import itertools
import math
import queue
import threading
import time
from collections import deque

import numpy as np

from ..core import rng as rng_mod
from ..core.tensor import Tensor
from ..observability import gc_tracker as _gc_tracker
from ..observability import log as _obs_log
from ..observability import tracing as _tracing

_logger = _obs_log.get_logger(__name__)


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = [t.numpy() if isinstance(t, Tensor) else np.asarray(t)
                        for t in tensors]

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return len(self.tensors[0])


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __getitem__(self, idx):
        out = []
        for ds in self.datasets:
            item = ds[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)

    def __len__(self):
        return min(len(d) for d in self.datasets)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __iter__(self):
        for ds in self.datasets:
            yield from ds


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self.cum[-1])

    def __getitem__(self, idx):
        ds_idx = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if ds_idx == 0 else int(self.cum[ds_idx - 1])
        return self.datasets[ds_idx][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset, self.indices = dataset, list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    idx = np.random.permutation(len(dataset))
    out, off = [], 0
    for n in lengths:
        out.append(Subset(dataset, idx[off:off + n].tolist()))
        off += n
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self.num_samples = num_samples or len(data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        return iter(np.random.choice(len(p), self.num_samples,
                                     replace=self.replacement, p=p).tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards indices across data-parallel ranks (ref:
    python/paddle/io/__init__.py DistributedBatchSampler)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from ..distributed import get_rank, get_world_size
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            g = np.random.RandomState(self.epoch)
            indices = g.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[:self.total_size - len(indices)]
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, (tuple, list)):
        return tuple(default_collate_fn([b[i] for b in batch])
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if isinstance(sample, Tensor):
        return Tensor(np.stack([b.numpy() for b in batch]))
    arr = np.stack([np.asarray(b) for b in batch])
    return Tensor(arr)


_loader_fallback_seen = set()


def _warn_loader_fallback(what, e):
    """A silent perf-path downgrade hid the dead flash backward for three
    rounds (r4 finding) — loader fallbacks warn once per (path, error)."""
    key = (what, type(e).__name__)
    if key not in _loader_fallback_seen:
        _loader_fallback_seen.add(key)
        import warnings
        warnings.warn(f"DataLoader fell back from {what}: "
                      f"{type(e).__name__}: {str(e)[:160]}", RuntimeWarning,
                      stacklevel=3)


#: waits `loader_stats()` keeps of an iterator: the newest LOADER_RING
LOADER_RING = 16384
#: a wait this long is logged at WARNING (not an iterator's first, which
#: is its workers starting)
LOADER_SLOW_WAIT_S = 1.0


class _LoaderWaits:
    """What the consumer of ONE iterator of a DataLoader waited for its
    batches: `loader_stats()`. Kept apart from the loader, so that the
    record outlives it."""

    def __init__(self):
        self._lock = threading.Lock()
        self.batches = 0
        self.wait_s = deque(maxlen=LOADER_RING)
        self.gap_s = deque(maxlen=LOADER_RING)
        self.wait_max_s = 0.0
        self.gap_max_s = 0.0

    def note(self, wait_s, gap_s):
        with self._lock:
            self.batches += 1
            self.wait_s.append(wait_s)
            self.gap_s.append(gap_s)
            self.wait_max_s = max(self.wait_max_s, wait_s)
            self.gap_max_s = max(self.gap_max_s, gap_s)

    def snapshot(self):
        with self._lock:
            return {"batches": self.batches, "wait_s": list(self.wait_s),
                    "gap_s": list(self.gap_s),
                    "wait_max_s": self.wait_max_s,
                    "gap_max_s": self.gap_max_s}


_newest_waits = _LoaderWaits()   # of the newest iterator of any loader


def loader_stats():
    """What the newest DataLoader iterator's consumer waited, always on:
    `batches` handed out; for the newest LOADER_RING of them `wait_s`,
    the seconds each `next()` took, and `gap_s`, the seconds between the
    batch before being handed out and this `next()` (the consumer's
    step, as the loader sees it; 0 for the first); `wait_max_s` and
    `gap_max_s` over all of them. Stays readable after the iterator and
    its loader are gone. It is the record of ONE iterator, the newest of
    the process: a program that iterates a second loader (an evaluation
    set) or the same one again reads that one's here, and each loader's
    own through `DataLoader.wait_stats()`."""
    return _newest_waits.snapshot()


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = persistent_workers and num_workers > 0
        self._pool = None  # live PersistentLoaderPool when enabled
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
        self._waits = _LoaderWaits()   # of this loader's newest iterator
        _gc_tracker.install()   # a wait reads the collector's seconds

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        return len(self.batch_sampler)

    def close(self):
        """Release the persistent worker pool (no-op otherwise)."""
        if self._pool is not None:
            self._pool.close()
            self._pool = None

    def __del__(self):  # pragma: no cover - gc path
        try:
            self.close()
        except Exception:
            pass

    def _iter_batches(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        else:
            for idx_batch in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in idx_batch])

    def __iter__(self):
        global _newest_waits
        _newest_waits = self._waits = waits = _LoaderWaits()
        return self._hand_out(
            self._iter_batches() if self.num_workers <= 0
            else self._multiprocess_iter(), waits)

    def wait_stats(self):
        """`loader_stats()` of this loader's newest iterator, whatever
        other loaders the process iterates."""
        return self._waits.snapshot()

    @staticmethod
    def _hand_out(batches, waits):
        """Every path's batches go to the consumer through here: each
        `next()` is timed (`pt:loader_wait`, `loader_stats()`), and one
        that stood still is logged with the step before it and the
        seconds the collector ran inside it."""
        t_out = None
        try:
            while True:
                t0 = time.perf_counter()
                gc0 = _gc_tracker.seconds()
                with _tracing.span("loader_wait"):
                    try:
                        batch = next(batches)
                    except StopIteration:
                        return
                t1 = time.perf_counter()
                wait, gap = t1 - t0, 0.0 if t_out is None else t0 - t_out
                waits.note(wait, gap)
                if wait > LOADER_SLOW_WAIT_S and t_out is not None:
                    _logger.warning(
                        "[slow loader] waited %.3f s for batch %d (the "
                        "step before it took %.3f s; gc %.3f s inside "
                        "the wait)", wait, waits.batches - 1, gap,
                        _gc_tracker.seconds() - gc0)
                t_out = t1
                yield batch
        finally:
            batches.close()

    def _multiprocess_iter(self):
        """Worker processes do __getitem__ + collate (ref:
        fluid/dataloader/dataloader_iter.py); batches travel through shared
        memory into the C++ byte-queue. Falls back to the single-process
        thread prefetcher if process spawn fails (e.g. sandboxed)."""
        from .worker import MultiprocessLoaderIter
        if self.persistent_workers:
            try:
                if self._pool is None or self._pool._shutdown:
                    self._pool = MultiprocessLoaderIter(
                        self.dataset, self.collate_fn, None,
                        self.num_workers, self.prefetch_factor,
                        self.timeout, self.worker_init_fn,
                        self.use_shared_memory,
                        iterable_batch_size=(self.batch_size
                                             if self._iterable_mode
                                             else None),
                        iterable_drop_last=(self.drop_last
                                            if self._iterable_mode
                                            else False),
                        persistent=True)
            except Exception as e:
                _warn_loader_fallback("persistent worker pool", e)
                yield from self._prefetch_iter()
                return
            yield from self._pool.epoch(
                None if self._iterable_mode else list(self.batch_sampler))
            return
        try:
            if self._iterable_mode:
                it = MultiprocessLoaderIter(
                    self.dataset, self.collate_fn, None, self.num_workers,
                    self.prefetch_factor, self.timeout, self.worker_init_fn,
                    self.use_shared_memory,
                    iterable_batch_size=self.batch_size,
                    iterable_drop_last=self.drop_last)
        except Exception as e:  # construction only: a mid-stream failure
            # must NOT restart iteration (duplicate batches); and a silent
            # perf downgrade hid a dead kernel path for rounds — warn.
            _warn_loader_fallback("worker processes", e)
            yield from self._prefetch_iter()
            return
        try:
            if not self._iterable_mode:
                it = MultiprocessLoaderIter(
                    self.dataset, self.collate_fn,
                    list(self.batch_sampler), self.num_workers,
                    self.prefetch_factor, self.timeout, self.worker_init_fn,
                    self.use_shared_memory)
        except Exception as e:
            _warn_loader_fallback("worker processes", e)
            yield from self._prefetch_iter()
            return
        yield from it

    def _prefetch_iter(self):
        """Single-process background prefetch: native C++ ring buffer when
        available, otherwise a Python thread."""
        prefetcher = None
        try:
            from .native_loader import NativePrefetcher
            prefetcher = NativePrefetcher(self._iter_batches(),
                                          depth=self.num_workers *
                                          self.prefetch_factor)
        except Exception as e:  # construction only — see worker fallback
            _warn_loader_fallback("native C++ prefetcher", e)
        if prefetcher is not None:
            yield from prefetcher
            return
        q: queue.Queue = queue.Queue(maxsize=self.num_workers * self.prefetch_factor)
        sentinel = object()

        def worker():
            try:
                for b in self._iter_batches():
                    q.put(b)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item


def get_worker_info():
    from .worker import get_worker_info as _gwi
    return _gwi()


class Transform:
    """Base dataset transform callable (ref: the reference io namespace
    re-export; vision transforms subclass the same contract)."""

    def __call__(self, data):
        return data
