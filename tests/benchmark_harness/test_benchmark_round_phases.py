"""The three metrics of the serving engine's round phases against a
hand-made `obs["stats"]`: what each computes, that each is declared for the
serve cells, and that a program without the counters (the parent commit of
the PR that added them) makes them read nothing, not raise."""
import json
import os

import pytest

from bench_paths import ROOT
from run import load_plugin

# name: (unit, better, the end-to-end metric it moves)
DECLARED = {"host_ms_per_dispatch.serve": ("ms", "lower",
                                           "serve_tokens_per_s"),
            "device_wait_share_pct.serve": ("%", "higher",
                                            "serve_tokens_per_s"),
            "longest_round_ms.serve": ("ms", "lower", "itl_p95_ms")}
NAMES = tuple(DECLARED)
# the cells whose chip runs give the three readers a number
SERVE_CELLS = ("gpt2_medium.serve_closed32",
               "kimi_linear_48b_ep2.serve_decode128",
               "zaya1_8b_l16.serve_reason128", "brumby_14b_l8.serve_decode16",
               "kanana2_30b_l6.serve_longdoc64")
SECONDS = {"admit": 0.5, "idle_wait": 2.0, "plan": 1.0, "dispatch": 1.5,
           "read_back": 40.0, "emit": 0.75, "other": 0.25}


def obs_of(round_phases, log=None):
    stats = {"decode_steps": 500, "prefill_dispatches": 200}
    if round_phases is not None:
        stats["round_phases"] = round_phases
    return {"stats": stats, "log": log or (lambda _line: None)}


def phases(dispatches=800, kind="prefill+decode", ms=6600.0):
    return {"seconds": dict(SECONDS), "dispatches": dispatches,
            "longest_round": {
                "ms": ms, "at_s": 31.5, "kind": kind, "round": 412,
                "phases_ms": {"admit": 0.2, "idle_wait": 0.0, "plan": 1.1,
                              "dispatch": 2.0, "read_back": 6595.0,
                              "emit": 1.5, "other": 0.2}}}


def test_host_ms_per_dispatch():
    read = load_plugin("metrics", "host_ms_per_dispatch.serve").read
    # admit + plan + dispatch + emit + other = 4.0 s over 800 dispatches:
    # neither the wait for the device nor the wait for work is the host's
    assert read(obs_of(phases())) == pytest.approx(5.0)
    assert read(obs_of(phases(dispatches=0))) is None


def test_device_wait_share_pct():
    read = load_plugin("metrics", "device_wait_share_pct.serve").read
    # read_back 40 s of the 44 s the thread was not waiting for work
    assert read(obs_of(phases())) == pytest.approx(100 * 40.0 / 44.0)
    idle = phases()
    idle["seconds"] = dict.fromkeys(SECONDS, 0.0)
    idle["seconds"]["idle_wait"] = 45.0
    assert read(obs_of(idle)) is None


def test_longest_round_ms_says_which_phase():
    lines = []
    read = load_plugin("metrics", "longest_round_ms.serve").read
    assert read(obs_of(phases(), lines.append)) == pytest.approx(6600.0)
    assert len(lines) == 1
    line = lines[0]
    assert "prefill+decode" in line and "31.5s" in line
    # the phases in order of their share: the one that took the seconds
    # comes first, the empty one is left out
    assert line.index("read_back 6595.0") < line.index("dispatch 2.0")
    assert "idle_wait" not in line
    # a window without a round has no longest one
    assert read(obs_of(phases(kind="", ms=0.0), lines.append)) is None
    assert len(lines) == 1


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_counters_reads_nothing(name):
    assert load_plugin("metrics", name).read(obs_of(None)) is None


def check_declared(bench):
    """What each entry must hold, wherever it stands in the list: its
    fields, every serve cell of SERVE_CELLS, and only cells that report
    the end-to-end metric it moves, so no train cell.  A later PR appends
    entries after them and its own cells to their `workloads`."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    train = set(e2e["train_tokens_per_s"]["workloads"])
    for name, (unit, better, moves) in DECLARED.items():
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert (m["unit"], m["better"], m["moves"]) == (unit, better, moves)
        assert m["source"] == "program_counter"
        assert m["layer"] == "serving engine"
        assert set(SERVE_CELLS) <= set(m["workloads"])
        assert set(m["workloads"]) <= set(e2e[moves]["workloads"])
        assert not set(m["workloads"]) & train


def test_the_serve_cell_declares_them():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check_declared(bench)
