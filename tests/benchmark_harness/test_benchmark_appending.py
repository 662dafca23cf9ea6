"""What a `model_config` PR appends to BENCHMARK.json passes every
declaration check of the harness's tests with no edit to them: a
configuration, a cell, a per-layer entry at the END of the list, and the
cell in the `workloads` of the end-to-end metrics it reports.  An existing
entry whose fields change still fails them."""
import json
import os
import shutil

import pytest

from bench_paths import BENCH, ROOT
import test_benchmark_decode_blocks as decode_blocks
import test_benchmark_harness as harness
import test_benchmark_round_phases as round_phases
import test_benchmark_stall_record as stall_record

CONFIG = "appended_model"
CELL = "appended_model.serve_longdoc64"
METRIC = "appended_roofline_pct.serve"
GUARDED = ([name for name, *_ in stall_record.DECLARED]
           + list(round_phases.DECLARED) + [decode_blocks.NAME])


def declaration_checks(bench, root):
    harness.check_keys_and_limits(bench)
    harness.check_names_units_and_texts(bench)
    harness.check_every_cell_reports_what_it_must(bench)
    harness.check_files(bench, root)
    for args in stall_record.DECLARED:
        stall_record.check_declared(bench, *args)
    round_phases.check_declared(bench)
    decode_blocks.check_declared(bench, root)


@pytest.fixture
def appended(tmp_path):
    """(BENCHMARK.json's entries, a checkout) with what a later PR adds:
    a configuration of an existing family under its own file, a cell of it
    on existing traffic, and a per-layer entry whose reader is a copy of
    an existing one."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    model = next(c for c in bench["configs"] if c["name"] == "kanana2_30b_l6")
    file = f"benchmark/configs/{CONFIG}.json"
    shutil.copy(tmp_path / model["file"], tmp_path / file)
    with open(os.path.join(BENCH, "workloads",
                           "kanana2_30b_l6.serve_longdoc64.json")) as f:
        told = json.load(f)
    with open(tmp_path / "benchmark" / "workloads" / f"{CELL}.json",
              "w") as f:
        json.dump(dict(told, config=CONFIG), f)
    shutil.copy(os.path.join(BENCH, "metrics", "mla_roofline_pct.serve.py"),
                tmp_path / "benchmark" / "metrics" / f"{METRIC}.py")

    bench["configs"].append(dict(model, name=CONFIG, file=file))
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "longdoc_closed64",
        "chips": 1, "why": "a cell that a later PR adds"})
    for m in bench["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "itl_p95_ms"):
            m["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels", "moves": "itl_p95_ms",
        "workloads": [CELL]})
    return bench, str(tmp_path)


def test_the_checks_take_what_a_later_pr_appends(appended):
    bench, root = appended
    declaration_checks(bench, root)
    assert bench["per_layer"][-1]["name"] == METRIC


@pytest.mark.parametrize("name", GUARDED)
def test_a_changed_unit_still_fails(appended, name):
    bench, root = appended
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    m["unit"] = "s"
    with pytest.raises(AssertionError):
        declaration_checks(bench, root)
