"""`decode_blocks_per_step.serve` against hand-made `obs["stats"]`: what it
computes and logs, that a program or an engine without the counter reads
nothing and does not raise, and that BENCHMARK.json declares it for the
cells whose kinds run a decode attention kernel."""
import json
import os

import pytest

from bench_paths import ROOT
from run import load_plugin

NAME = "decode_blocks_per_step.serve"
# the cells whose chip runs give the reader a number: GPT-2's and ZAYA's
# paged decode kernel, Kimi's and Kanana's mla_decode
DECODE_CELLS = ("gpt2_medium.serve_closed32",
                "kimi_linear_48b_ep2.serve_decode128",
                "zaya1_8b_l16.serve_reason128",
                "kanana2_30b_l6.serve_longdoc64")
# traffic kinds whose engines run no decode attention kernel: power
# retention keeps no pool (`power_decode` steps a state), training decodes
NO_DECODE_KERNEL = ("serve_recurrent", "train")


def obs_of(decode_kernel, lines):
    stats = {"decode_steps": 500}
    if decode_kernel is not None:
        stats["decode_kernel"] = decode_kernel
    return {"stats": stats, "log": lines.append}


def counter(kernel="mla_decode", fan=8, launches=600, live_blocks=44700,
            steps=6000):
    return {"kernel": kernel, "fan": fan, "launches": launches,
            "live_blocks": live_blocks, "steps": steps}


def test_live_blocks_over_steps():
    lines = []
    read = load_plugin("metrics", NAME).read
    assert read(obs_of(counter(), lines)) == pytest.approx(7.45)
    assert read(obs_of(counter("paged_attn_decode", 1, 48, 9000, 9000),
                       lines)) == 1.0


def test_the_log_line():
    lines = []
    load_plugin("metrics", NAME).read(obs_of(counter(), lines))
    assert lines == ["[decode kernel] mla_decode fan 8: 44700 live blocks "
                     "in 6000 steps, 600 launches"]


@pytest.mark.parametrize("decode_kernel", [
    None,                                       # a program without it
    counter(kernel=None, fan=0, launches=0, live_blocks=0, steps=0),
    counter(launches=0, live_blocks=0, steps=0)],   # no decode step
    ids=["absent", "no_kernel", "no_steps"])
def test_nothing_to_read(decode_kernel):
    lines = []
    assert load_plugin("metrics", NAME).read(
        obs_of(decode_kernel, lines)) is None
    assert not lines


def check_declared(bench, root):
    """What the entry must hold, wherever it stands in the list: its
    fields, every cell of DECODE_CELLS, only cells that report
    `serve_tokens_per_s`, and no cell of a kind that runs no decode
    kernel (on today's cells: exactly DECODE_CELLS).  A later PR appends
    its own cells to its `workloads`; a cell of a kind not named here is
    judged by its chip run, not by this check."""
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "blocks/step", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "serve_tokens_per_s"}
    serving = next(e for e in bench["end_to_end"]
                   if e["name"] == "serve_tokens_per_s")["workloads"]
    assert set(DECODE_CELLS) <= set(m["workloads"]) <= set(serving)
    for w in bench["workloads"]:
        with open(os.path.join(root, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        if kind in NO_DECODE_KERNEL:
            assert w["name"] not in m["workloads"], w["name"]


def test_the_cells_whose_kinds_run_a_decode_kernel_declare_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check_declared(bench, ROOT)
