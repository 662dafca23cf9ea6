"""trace_reduce.py on the small recorded trace beside it: the last 12 ms
of one GPT-2-medium train step on the v5e, the gap, and the first 12 ms of
the next (1,688 events; data/ beside this file).  The written values were
read off the trace once by the functions under test and checked by the
slow ways below."""
import os

import pytest

import bench_paths  # noqa: F401 — sys.path for the next import
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "train_step_slice.json.gz")
WINDOW = (223523612.0, 253937042.0)  # the slice as it was cut, in ns


@pytest.fixture(scope="module")
def trace():
    return tr.load_json(DATA)


def test_planes_and_lines(trace):
    assert [p["name"] for p in tr.device_planes(trace)] == ["/device:TPU:0"]
    plane = tr.device_planes(trace)[0]
    assert len(tr.op_events(plane)) == 1682
    assert [e[0] for e in tr.module_events(plane)] == \
        ["jit_step(16435987673963576356)"] * 2


def test_busy_union_against_a_nanosecond_sweep(trace):
    ops = tr.op_events(tr.device_planes(trace)[0])
    busy = tr.busy_ns(ops)
    assert busy == 23902199.0
    # the slow way: sweep the sorted end points and count coverage
    points = sorted([(e[1], 1) for e in ops] + [(e[1] + e[2], -1)
                                                for e in ops])
    depth, last, covered = 0, None, 0.0
    for t, d in points:
        if depth > 0:
            covered += t - last
        depth, last = depth + d, t
    assert covered == busy


def test_busy_window_and_idle_share(trace):
    busy_s, window_s = tr.busy_and_window_s(trace, WINDOW)
    assert round(busy_s, 6) == 0.023902 and round(window_s, 6) == 0.030413
    assert round(tr.idle_share(trace, WINDOW), 4) == 0.2141
    # without a window the first and last op bound it
    assert tr.window_of(trace) == (223555074.0, 253901201.0)
    assert round(tr.idle_share(trace), 4) == 0.2123


def test_union_merges_overlaps_and_touching():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 15, 5], ["d", 30, 1]]
    assert tr.union_intervals(ev) == [[0, 20], [30, 31]]
    assert tr.busy_ns(ev) == 21


def test_names():
    n = '%jvp__.24 = custom-call(...), custom_call_target="tpu_custom_call"'
    assert tr.short_name(n) == "jvp__.24" and tr.op_family(n) == "jvp__"
    assert tr.is_kernel(n)
    m = '%custom-call.230 = custom-call(...), ' \
        'custom_call_target="ConcatBitcast"'
    assert not tr.is_kernel(m) and tr.op_family(m) == "custom-call"
    assert tr.op_family("%copy = copy(...)") == "copy"


def test_kernel_time_and_share(trace):
    assert round(tr.kernel_time_s(trace) * 1e3, 4) == 6.2297
    assert round(tr.kernel_share(trace), 4) == 0.2606
    by = tr.time_by(trace, only=tr.is_kernel)
    assert {k: round(v * 1e3, 3) for k, v in by.items()} == \
        {"jvp__": 3.904, "transpose_jvp___": 2.326}


def test_top_ops(trace):
    top = tr.top_ops(trace, k=3)
    assert [n for n, _ in top] == ["multiply_subtract_fusion",
                                   "pallas:jvp__", "copy"]
    assert round(top[0][1] * 1e3, 3) == 5.21


def test_gap_attribution(trace):
    gaps = tr.idle_gaps(trace, WINDOW)
    assert round(sum(b - a for a, b in gaps)) == round(
        (WINDOW[1] - WINDOW[0]) - 23902199.0)
    attributed = tr.attribute_gaps(trace, WINDOW)
    assert [n for n, _ in attributed] == ["next(loader)", "loss read",
                                          "step dispatch", "(no span)"]
    assert [round(s * 1e3, 3) for _, s in attributed] == \
        [2.815, 2.706, 0.978, 0.012]
    # every idle nanosecond is attributed exactly once
    assert round(sum(s for _, s in attributed) * 1e9) == \
        round(sum(b - a for a, b in gaps))
    # the label of an uncovered gap is the caller's
    other = tr.attribute_gaps(trace, WINDOW, no_span="engine (no span)")
    assert other[3][0] == "engine (no span)"


def test_inner_span_wins_and_slice_marker_explains_nothing():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%a = fusion(...)", 0, 10], ["%b = fusion(...)", 110, 10]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench:slice", 0, 120], ["bench:outer", 5, 100],
            ["bench:inner", 40, 20]]}]}]}
    got = dict(tr.attribute_gaps(trace, (0, 120)))
    assert {k: round(v * 1e9) for k, v in got.items()} == \
        {"outer": 75, "inner": 20, "(no span)": 5}


def test_clip_keeps_whole_events_only(trace):
    cut = tr.clip(trace, 240e6, 250e6)
    for p in cut["planes"]:
        for ln in p["lines"]:
            assert all(e[1] >= 240e6 and e[1] + e[2] <= 250e6
                       for e in ln["events"])
    assert 0 < len(tr.op_events(tr.device_planes(cut)[0])) < 1682
