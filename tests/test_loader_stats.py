"""`io.DataLoader` records each wait (ISSUE 34): every path hands its
batches out through one wrapper that times each `next()` and the
consumer's step before it, `paddle_tpu.io.loader_stats()` reads the newest
iterator's record and outlives the loader, a wait is a `pt:loader_wait`
span, and one that stood still is logged.  Dataset classes live at module
top level so the spawn start method works too."""
import gc
import glob
import re
import time

import numpy as np
import pytest

from paddle_tpu import io
from paddle_tpu.io import DataLoader, Dataset, IterableDataset
from paddle_tpu.observability import gc_tracker

KEYS = {"batches", "wait_s", "gap_s", "wait_max_s", "gap_max_s"}


class OneSlowItem(Dataset):
    """Item `slow` takes `delay` seconds to load; the rest nothing."""

    def __init__(self, n=16, slow=9, delay=0.25):
        self.n, self.slow, self.delay = n, slow, delay

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.slow:
            time.sleep(self.delay)
        return np.full((3,), i, np.float32)


class Counting(IterableDataset):
    def __iter__(self):
        for i in range(10):
            yield np.full((2,), i, np.float32)


def values(batch):
    return np.asarray(batch[0] if isinstance(batch, (list, tuple))
                      else batch)


@pytest.mark.parametrize("num_workers", [0, 2])
def test_every_batch_is_timed_and_a_slow_item_shows(num_workers):
    loader = DataLoader(OneSlowItem(), batch_size=2,
                        num_workers=num_workers)
    seen = []
    for batch in loader:
        seen.append(values(batch)[:, 0].tolist())
        if num_workers == 0:
            time.sleep(0.01)               # the consumer's step
    assert seen == [[float(i), float(i + 1)] for i in range(0, 16, 2)]
    st = io.loader_stats()
    assert set(st) == KEYS
    assert st["batches"] == len(st["wait_s"]) == len(st["gap_s"]) == 8
    assert all(w >= 0 for w in st["wait_s"])
    # item 9 is in batch 4. Workers load it while the batches before it
    # are consumed, so a consumer that takes its time hides the wait
    # (what prefetching is for): this one asks at once, and waits
    assert st["wait_max_s"] == max(st["wait_s"]) >= 0.25 * 0.5
    # the step before each batch, as the loader saw it: none before the
    # first
    assert st["gap_s"][0] == 0.0 and min(st["gap_s"]) >= 0.0
    assert st["gap_max_s"] == max(st["gap_s"])
    if num_workers == 0:      # one thread: the wait is the item's own
        assert st["wait_s"].index(st["wait_max_s"]) == 4
        assert st["wait_max_s"] >= 0.25
        assert all(g >= 0.01 for g in st["gap_s"][1:])   # the sleep


def test_the_record_is_the_newest_iterators_and_outlives_the_loader():
    loader = DataLoader(OneSlowItem(n=6, slow=99), batch_size=2)
    it = iter(loader)
    assert io.loader_stats()["batches"] == 0   # a new iterator: a new record
    next(it)
    next(it)
    assert io.loader_stats()["batches"] == 2
    it.close()                                 # as the train cell shuts it
    del it, loader
    gc.collect()
    st = io.loader_stats()
    assert st["batches"] == 2 and len(st["wait_s"]) == 2
    # another loader's iterator takes over; an exhausted one keeps its count
    other = DataLoader(Counting(), batch_size=4)
    assert [values(b).shape[0] for b in other] == [4, 4, 2]
    assert io.loader_stats()["batches"] == 3


def test_each_loader_keeps_the_record_of_its_own_newest_iterator():
    train = DataLoader(OneSlowItem(n=8, slow=99), batch_size=2)
    assert train.wait_stats()["batches"] == 0      # never iterated
    assert len(list(train)) == 4
    held_out = DataLoader(Counting(), batch_size=4)
    assert len(list(held_out)) == 3
    # the process's newest iterator is the second loader's; the first
    # loader's own record is still its four batches
    assert io.loader_stats()["batches"] == 3
    assert train.wait_stats()["batches"] == 4
    assert held_out.wait_stats() == io.loader_stats()
    next(iter(train))                              # a second epoch begins
    assert train.wait_stats()["batches"] == 1


def test_closing_the_iterator_closes_what_is_underneath():
    closed = []

    class Watched(IterableDataset):
        def __iter__(self):
            try:
                for i in range(100):
                    yield np.zeros((1,), np.float32)
            finally:
                closed.append(True)

    it = iter(DataLoader(Watched(), batch_size=1))
    next(it)
    it.close()
    assert closed == [True]


def test_the_ring_keeps_the_newest_and_the_maxima_all(monkeypatch):
    monkeypatch.setattr(io, "LOADER_RING", 4)
    waits = io._LoaderWaits()
    for i in range(10):
        waits.note(float(10 - i), float(i))
    st = waits.snapshot()
    assert st["batches"] == 10
    assert st["wait_s"] == [4.0, 3.0, 2.0, 1.0]
    assert st["gap_s"] == [6.0, 7.0, 8.0, 9.0]
    assert st["wait_max_s"] == 10.0 and st["gap_max_s"] == 9.0


def test_a_wait_that_stood_still_is_logged(library_log, monkeypatch):
    monkeypatch.setattr(io, "LOADER_SLOW_WAIT_S", 0.1)
    # the first wait of an iterator is its start-up: not logged
    for _ in DataLoader(OneSlowItem(n=4, slow=0, delay=0.2), batch_size=2):
        pass
    assert not [r for r in library_log if "[slow loader]" in r.getMessage()]
    for _ in DataLoader(OneSlowItem(n=8, slow=5, delay=0.2), batch_size=2):
        time.sleep(0.02)
    lines = [r for r in library_log if "[slow loader]" in r.getMessage()]
    assert len(lines) == 1 and lines[0].levelname == "WARNING"
    text = lines[0].getMessage()
    assert "for batch 2" in text and "the step before it took 0.0" in text
    assert re.search(r"gc \d\.\d{3} s inside the wait", text)


def test_a_wait_is_a_span_and_the_collector_is_hooked(tmp_path):
    import jax
    from jax.profiler import ProfileData

    loader = DataLoader(OneSlowItem(n=8, slow=99), batch_size=2)
    assert gc_tracker.stats()["installed"]   # by the loader's constructor
    before = gc_tracker.stats()["collections"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in loader:
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    assert gc_tracker.stats()["collections"] >= before + 4
    files = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = [ev.name for plane in ProfileData.from_file(files[-1]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith("pt:")]
    # 4 batches and the `next()` that found the end; a collection each
    assert names.count("pt:loader_wait") == 5
    assert names.count("pt:gc") >= 4
