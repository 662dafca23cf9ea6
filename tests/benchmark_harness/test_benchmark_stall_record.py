"""The three per-layer metrics of PR 34 against hand-made `obs`: what each
computes and logs, that a program without the counters (the parent commit)
makes each read nothing and not raise, and that BENCHMARK.json lists them
for the cells that report the end-to-end metric each moves."""
import json
import os
import sys

import pytest

from bench_paths import ROOT
from run import load_plugin

SERVE_CELLS = ["gpt2_medium.serve_closed32",
               "kimi_linear_48b_ep2.serve_decode128",
               "zaya1_8b_l16.serve_reason128", "brumby_14b_l8.serve_decode16",
               "kanana2_30b_l6.serve_longdoc64"]
TRAIN_CELLS = ["gpt2_medium.train", "bert_large.train"]
PHASES = ("admit", "idle_wait", "plan", "dispatch", "read_back", "emit",
          "other")


def seconds(**kw):
    return {k: float(kw.get(k, 0.0)) for k in PHASES}


def slow_round(ms, kind, at_s, **phases_ms):
    return {"ms": ms, "at_s": at_s, "kind": kind, "round": int(at_s * 100),
            "phases_ms": seconds(**phases_ms), "gc_ms": 0.0, "compiles": 0}


def stats_of(probed=1000, found=(40, 210, 0)):
    """A window of 800 rounds of which 250 starved the device."""
    slowest = [slow_round(640.0, "decode", 18.1, read_back=600.0, emit=35.0),
               slow_round(127.4, "prefill+decode", 31.5, read_back=110.2,
                          emit=9.1)]
    slowest[1]["gc_ms"], slowest[1]["compiles"] = 95.5, 1
    decode, prefill, verify = found
    return {
        "dispatch_ahead": {
            "decode_dispatches": 700, "issued_ahead": 700,
            "ahead_share": 1.0, "drains": {}, "dropped_rows": 0,
            "probed": probed,
            "probed_by_kind": {"decode": probed - 300, "prefill": 300,
                               "verify": 0},
            "found_idle": {"decode": decode, "prefill": prefill,
                           "verify": verify},
            "found_idle_share": (decode + prefill + verify) / (probed or 1)},
        "round_phases": {
            "seconds": seconds(plan=0.8, dispatch=1.6, read_back=2.4),
            "dispatches": 1000,
            "starved_seconds": seconds(plan=0.5, dispatch=0.75),
            "starved_rounds": 250,
            "longest_round": slowest[0],
            "round_ms": {
                "count": 800, "p50_ms": 5.9, "p99_ms": 14.25,
                "by_kind": {
                    "decode": {"count": 560, "p50_ms": 5.6, "p99_ms": 9.0},
                    "prefill+decode": {"count": 240, "p50_ms": 9.8,
                                       "p99_ms": 15.1}},
                "slowest": slowest}}}


def obs_of(stats, lines=None, peaks=True):
    return {"stats": stats, "peaks": {"flops": 197e12} if peaks else None,
            "log": (lines.append if lines is not None
                    else (lambda _line: None))}


def test_device_found_idle_pct():
    read = load_plugin("metrics", "device_found_idle_pct.serve").read
    lines = []
    assert read(obs_of(stats_of(), lines)) == pytest.approx(25.0)
    (line,) = lines
    assert "250 of 1000" in line
    assert "decode 40 of 700" in line
    assert "prefill 210 of 300" in line
    assert "verify" not in line                 # never probed: left out
    # ms a round in the starved rounds beside all rounds: 0.5 s over 250
    # against 0.8 s over 800
    starved, every = line.split("in all 800:")
    assert "plan 2.00" in starved and "dispatch 3.00" in starved
    assert "plan 1.00" in every and "read_back 3.00" in every
    # nothing probed (a drafter reads every dispatch at once): no reading
    assert read(obs_of(stats_of(probed=0, found=(0, 0, 0)))) is None
    # off the chip a program queue that ran dry is no device's reading
    assert read(obs_of(stats_of(), peaks=False)) is None
    assert len(lines) == 1


def test_round_p99_ms_logs_the_kinds_and_the_rounds_that_stood_still():
    read = load_plugin("metrics", "round_p99_ms.serve").read
    lines = []
    assert read(obs_of(stats_of(), lines)) == pytest.approx(14.25)
    assert len(lines) == 3
    assert "800 rounds" in lines[0] and "5.9 / 14.2" in lines[0]
    assert "decode 560: 5.6 / 9.0" in lines[0]
    assert "prefill+decode 240: 9.8 / 15.1" in lines[0]
    assert "640.0 ms (decode)" in lines[1] and "at 18.10s" in lines[1]
    assert lines[1].index("read_back 600.0") < lines[1].index("emit 35.0")
    assert "plan" not in lines[1]               # an empty phase is left out
    assert "gc 95.5 ms, compiles 1" in lines[2]
    empty = stats_of()
    empty["round_phases"]["round_ms"] = {
        "count": 0, "p50_ms": 0.0, "p99_ms": 0.0, "by_kind": {},
        "slowest": []}
    assert read(obs_of(empty)) is None


@pytest.fixture
def fake_loader(monkeypatch):
    """`paddle_tpu.io.loader_stats` as the reader finds it, without
    importing the program: a stand-in module under that name."""
    import types

    def install(stats):
        pkg = types.ModuleType("paddle_tpu")
        pkg.__path__ = []
        mod = types.ModuleType("paddle_tpu.io")
        if stats is not None:
            mod.loader_stats = lambda: stats
        monkeypatch.setitem(sys.modules, "paddle_tpu", pkg)
        monkeypatch.setitem(sys.modules, "paddle_tpu.io", mod)

    return install


def test_loader_wait_max_ms_reads_the_windows_waits(fake_loader):
    read = load_plugin("metrics", "loader_wait_max_ms.train").read
    # two warm-up batches (the workers' start-up: 4.2 s) and a window of 5
    fake_loader({"batches": 7,
                 "wait_s": [4.2, 0.3, 0.0004, 0.0003, 0.0021, 0.0005, 0.0004],
                 "gap_s": [0.0, 20.0, 0.169, 0.168, 0.170, 2.45, 0.169],
                 "wait_max_s": 4.2, "gap_max_s": 20.0})
    lines = []
    obs = {"data_wait_s": [0.0004] * 5, "log": lines.append}
    assert read(obs) == pytest.approx(2.1)
    (line,) = lines
    assert "7 batches" in line and "last 5" in line
    assert "2450.0 ms (step 3)" in line         # the step that stood still
    # the newest iterator handed out fewer batches than the window has
    # steps: another loader's (an evaluation set's), not the train loop's
    fake_loader({"batches": 3, "wait_s": [0.5, 0.4, 0.3],
                 "gap_s": [0.0, 0.1, 0.1], "wait_max_s": 0.5,
                 "gap_max_s": 0.1})
    assert read(obs) is None and len(lines) == 1
    # an iterator that handed nothing out
    fake_loader({"batches": 0, "wait_s": [], "gap_s": [],
                 "wait_max_s": 0.0, "gap_max_s": 0.0})
    assert read(obs) is None


def test_a_program_without_the_counters_reads_nothing(fake_loader):
    none = {"stats": {"decode_steps": 500}, "peaks": {"flops": 197e12},
            "log": lambda _line: None}
    for name in ("device_found_idle_pct.serve", "round_p99_ms.serve"):
        assert load_plugin("metrics", name).read(none) is None
    # PR 24's counters without PR 34's keys: the parent commit
    parent = stats_of()
    for key in ("probed", "probed_by_kind", "found_idle", "found_idle_share"):
        del parent["dispatch_ahead"][key]
    for key in ("starved_seconds", "starved_rounds", "round_ms"):
        del parent["round_phases"][key]
    for name in ("device_found_idle_pct.serve", "round_p99_ms.serve"):
        assert load_plugin("metrics", name).read(obs_of(parent)) is None
    fake_loader(None)                           # io without loader_stats
    assert load_plugin("metrics", "loader_wait_max_ms.train").read(
        {"data_wait_s": [0.0004], "log": lambda _line: None}) is None


DECLARED = [
    ("device_found_idle_pct.serve", SERVE_CELLS, "serving engine",
     "serve_tokens_per_s", "%"),
    ("round_p99_ms.serve", SERVE_CELLS, "serving engine", "itl_p95_ms",
     "ms"),
    ("loader_wait_max_ms.train", TRAIN_CELLS, "input pipeline",
     "train_tokens_per_s", "ms")]


def check_declared(bench, name, cells, layer, moves, unit):
    """What the entry must hold, wherever it stands in the list: its
    fields, every cell of `cells`, and no cell that does not report the
    end-to-end metric it moves.  A later PR appends entries after it and
    its own cells to its `workloads`."""
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_counter", "layer": layer, "moves": moves}
    reporting = next(e for e in bench["end_to_end"]
                     if e["name"] == moves)["workloads"]
    assert set(cells) <= set(m["workloads"]) <= set(reporting)


@pytest.mark.parametrize("name, cells, layer, moves, unit", DECLARED)
def test_the_cells_declare_them(name, cells, layer, moves, unit):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check_declared(bench, name, cells, layer, moves, unit)
