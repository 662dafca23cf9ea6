"""Native C++ runtime tests (queue, arena, prefetching DataLoader)."""
import os
import shutil

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.io import native_loader as native

# With a compiler on the host a failing build or load is an error of the
# build step, not a reason to skip.
pytestmark = pytest.mark.skipif(
    shutil.which(os.environ.get("CXX", "g++")) is None,
    reason="no C++ compiler on this host")


class TestByteQueue:
    def test_roundtrip_order(self):
        import ctypes
        lib = native.get_lib()
        q = lib.ptq_create(4, 1 << 20)
        for i in range(10):
            data = bytes([i]) * (i + 1)
            if i >= 4:
                break
            buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
            assert lib.ptq_push(q, buf, len(data)) == 0
        assert lib.ptq_size(q) == 4
        for i in range(4):
            n = lib.ptq_peek_size(q)
            out = (ctypes.c_uint8 * n)()
            assert lib.ptq_pop(q, out, n) == n == i + 1
            assert bytes(out) == bytes([i]) * (i + 1)
        lib.ptq_close(q)
        assert lib.ptq_peek_size(q) == -1
        lib.ptq_destroy(q)

    def test_blocking_producer_consumer(self):
        import threading
        items = list(range(50))
        out = []

        def gen():
            for i in items:
                yield np.full((16,), i, np.float32)

        pf = native.NativePrefetcher(gen(), depth=3)
        for arr in pf:
            out.append(int(arr[0]))
        assert out == items


class TestArena:
    def test_alloc_free_reuse(self):
        a = native.HostArena(limit_bytes=1 << 24)
        p1 = a.alloc(1000)
        a.free(p1)
        p2 = a.alloc(900)  # same bucket (1024) -> reused block
        assert p2 == p1
        r = a.reserved_bytes
        assert r >= 1024

    def test_buffer_view(self):
        a = native.HostArena()
        view, ptr = a.buffer(4096)
        view[:] = 7
        assert view.sum() == 7 * 4096
        a.free(ptr)


class TestLoaderIntegration:
    def test_dataloader_native_path(self):
        from paddle_tpu.io import DataLoader, TensorDataset
        xs = np.arange(40, dtype=np.float32).reshape(40, 1)
        ds = TensorDataset([xs])
        loader = DataLoader(ds, batch_size=8, num_workers=2)
        seen = []
        for (x,) in loader:
            seen.extend(x.numpy().reshape(-1).tolist())
        assert sorted(seen) == list(range(40))


class TestNativeMultiSlotParser:
    """r4: the C++ MultiSlot parser (ms_scan/ms_fill) — the reference
    parses this format in C++ (data_feed.cc) too; the Python line parser
    is the fallback contract."""

    def _meta(self):
        return [("x", np.float32, None), ("y", np.int64, 1)]

    def test_correctness_and_padding(self):
        from paddle_tpu.io.native_loader import parse_multislot
        out = parse_multislot(
            b"4 0.5 1.5 2.5 3.5 1 1\n2 9.0 8.0 1 0\n", self._meta())
        np.testing.assert_allclose(
            out["x"], [[0.5, 1.5, 2.5, 3.5], [9.0, 8.0, 0, 0]])
        np.testing.assert_array_equal(out["y"], [[1], [0]])

    def test_malformed_raises(self):
        from paddle_tpu.io.native_loader import parse_multislot
        with pytest.raises(ValueError):
            parse_multislot(b"3 1 2\n", [("a", np.int64, None)])
        with pytest.raises(ValueError):  # trailing junk = slot mismatch
            parse_multislot(b"1 5 junk extra\n",
                            [("a", np.int64, None)])
        with pytest.raises(ValueError):  # code-review r4: a short line
            # must NOT silently merge with the next one (strtoll skips \n)
            parse_multislot(b"1 7\n1 8\n",
                            [("a", np.int64, 1), ("b", np.int64, 1)])

    def test_dataset_native_path_matches_python(self, tmp_path):
        from paddle_tpu import fluid
        rs = np.random.RandomState(1)
        lines = ["4 " + " ".join(f"{v:.4f}" for v in rs.rand(4))
                 + f" 1 {rs.randint(2)}" for _ in range(200)]
        p = tmp_path / "part"
        p.write_text("\n".join(lines))

        class V:
            def __init__(self, name, dtype, shape):
                self.name, self.dtype, self.shape = name, dtype, shape

        def mk():
            ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
            ds.set_use_var([V("x", "float32", [None, 4]),
                            V("y", "int64", [None, 1])])
            ds.set_batch_size(64)
            ds.set_filelist([str(p)])
            return ds

        ds_native = mk()
        ds_native.load_into_memory()
        assert ds_native._native is not None  # fast path actually taken
        assert ds_native.get_memory_data_size() == 200
        ds_py = mk()
        ds_py._load_native = lambda: False
        ds_py.load_into_memory()
        for bn, bp in zip(ds_native, ds_py):
            np.testing.assert_allclose(bn["x"], bp["x"], rtol=1e-6)
            np.testing.assert_array_equal(bn["y"], bp["y"])
        # shuffle permutes rows, keeps the multiset of labels
        ds_native.local_shuffle()
        ys = np.concatenate([b["y"].ravel() for b in ds_native])
        np.testing.assert_array_equal(
            np.sort(ys), np.sort(np.concatenate(
                [b["y"].ravel() for b in ds_py])))

    def test_type_mismatch_cannot_desync(self):
        """code-review r4: a float token under an int64 slot once desynced
        ms_fill from ms_scan's framing and wrote past the output arrays
        (heap corruption). Must raise ValueError instead."""
        from paddle_tpu.io.native_loader import parse_multislot
        with pytest.raises(ValueError):
            parse_multislot(b"1 2.0\n2 7 8\n", [("a", np.int64, 2)])
        # and a float slot still accepts decimals
        out = parse_multislot(b"2 0.5 1.5\n", [("x", np.float32, 2)])
        np.testing.assert_allclose(out["x"], [[0.5, 1.5]])
