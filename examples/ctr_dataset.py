"""Reference-style CTR pipeline end to end, 1.x idioms throughout:

  MultiSlotDataGenerator --part files--> InMemoryDataset --batches-->
  Executor.train_from_dataset (static Program: sparse embedding + dense
  tower) --> infer_from_dataset eval (weights untouched)

This is the fluid workflow a reference CTR user brings over verbatim
(data_generator writes the same slot text the reference's C++
MultiSlotDataFeed parses); the execution underneath is one jitted XLA
computation per batch shape.

Run: JAX_PLATFORMS=cpu python examples/ctr_dataset.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def write_parts(tmpdir, n_parts=2, rows=128):
    from paddle_tpu.incubate.data_generator import MultiSlotDataGenerator

    class CTRGen(MultiSlotDataGenerator):
        def __init__(self, seed):
            super().__init__()
            self.rs = np.random.RandomState(seed)

        def generate_sample(self, line):
            def reader():
                for _ in range(rows):
                    slot_ids = self.rs.randint(0, 1000, 4)
                    dense = self.rs.rand(8)
                    click = [int(slot_ids.sum() % 2)]
                    yield [("sparse_ids", [int(i) for i in slot_ids]),
                           ("dense_x", [float(v) for v in dense]),
                           ("click", click)]
            return reader

    paths = []
    for part in range(n_parts):
        g = CTRGen(seed=part)
        p = os.path.join(tmpdir, f"part-{part:03d}")
        with open(p, "w") as f:
            for sample in g.generate_sample(None)():
                f.write(g._gen_str(sample))
        paths.append(p)
    return paths


def main():
    import jax
    # a CPU example: pin the platform before any backend initialises, so
    # it never takes the chip from the process that should hold it
    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu import fluid

    paddle.enable_static()
    paddle.seed(0)

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        ids = fluid.data(name="sparse_ids", shape=[None, 4], dtype="int64")
        dense = fluid.data(name="dense_x", shape=[None, 8],
                           dtype="float32")
        label = fluid.data(name="click", shape=[None, 1], dtype="int64")
        emb = fluid.embedding(ids, size=[1000, 8])          # [B, 4, 8]
        emb_sum = fluid.layers.reduce_sum(emb, dim=1)       # [B, 8]
        feat = fluid.layers.concat([emb_sum, dense], axis=1)
        fc1 = fluid.layers.fc(feat, size=32, act="relu")
        logits = fluid.layers.fc(fc1, size=2)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)

    with tempfile.TemporaryDirectory() as td:
        parts = write_parts(td)
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_use_var([ids, dense, label])
        ds.set_batch_size(32)
        ds.set_filelist(parts)
        ds.load_into_memory()
        ds.local_shuffle()
        print(f"loaded {ds.get_memory_data_size()} samples "
              f"from {len(parts)} part files")

        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        first = float(exe.run(main_prog, feed=next(iter(ds)),
                              fetch_list=[loss])[0])
        for epoch in range(4):
            exe.train_from_dataset(main_prog, ds, fetch_list=[loss])
        last = float(exe.run(main_prog, feed=next(iter(ds)),
                             fetch_list=[loss])[0])
        print(f"loss {first:.4f} -> {last:.4f}")
        assert last < first, (first, last)

        # eval pass: same program, optimizers suspended
        w_name = main_prog.all_parameters()[0].name
        before = np.asarray(fluid.global_scope().find_var(w_name)).copy()
        exe.infer_from_dataset(main_prog, ds, fetch_list=[loss])
        after = np.asarray(fluid.global_scope().find_var(w_name))
        assert np.array_equal(before, after), "eval must not train"
        print("OK: dataset pipeline trained; infer pass left weights "
              "untouched")


if __name__ == "__main__":
    main()
