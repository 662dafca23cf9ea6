"""The harness end to end on the CPU, at the tiny presets of the data
files: what a rehearsal prints, that a CPU can never give a result, that
BENCHMARK.json keeps to its contract's letters, and that a cell, a
configuration and a per-layer metric are added as new files alone."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "n_embd", "n_inner", "expansion", "experts_per")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def run_cell(root, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def rehearsal_line(proc):
    assert proc.returncode == 3, proc.stderr[-2000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("[rehearsal]") and "correct True" in last, last
    for line in proc.stdout.splitlines():   # never a result line
        assert not line.startswith("{"), line
    return last


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal(cell, trace):
    proc = run_cell(ROOT, "--workload", cell, "--seed", str(2 ** 31 + 11),
                    "--seconds", "2", "--trace", trace, "--rehearse")
    last = rehearsal_line(proc)
    assert "failed 0" in last
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    kind = "serve" if "serve" in entry["name"] else "train"
    if trace == "0":
        assert "'setup_s'" in last and f"'{kind}_tokens_per_s'" in last
    else:   # the counters are there; the device's metrics read nothing
        assert f"'compiles_in_window.{kind}'" in last
        assert "roofline" not in last and "idle" not in last
    assert "0 compiles in the window" in proc.stdout


def test_a_cpu_never_gives_a_result():
    proc = run_cell(ROOT, "--workload", CELLS[0], "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert proc.returncode not in (0, 3)
    assert "no accelerator" in proc.stderr
    assert "{" not in proc.stdout and "tokens_per_s" not in proc.stdout


def test_unknown_cell_fails():
    proc = run_cell(ROOT, "--workload", "no_such.cell", "--rehearse")
    assert proc.returncode == 2 and "no workload" in proc.stderr


def test_only_the_benchmark_is_not_enough(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(str(tmp_path), "--workload", CELLS[0], "--rehearse")
    assert proc.returncode not in (0, 3) and "{" not in proc.stdout


# ---- BENCHMARK.json to the letter of its contract -------------------------

# Each check is a function of the benchmark's dict (and, for the files, of
# the checkout that holds them), so that test_benchmark_appending.py runs
# the same checks on a copy that holds what a later PR appends.

def check_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    assert len(json.dumps(bench)) < 64 * 1024
    assert bench["command"] == ["python3", "benchmark/run.py"]
    for p in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and ".." not in p
    # full check: 2 + 14 runs a cell, at the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_keys_and_limits():
    check_keys_and_limits(BENCHMARK)


def check_names_units_and_texts(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for group in ("configs", "workloads"):
        got = [x["name"] for x in bench[group]]
        assert len(set(got)) == len(got)
        for x in bench[group]:
            assert NAME.match(x["name"])
            assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"] \
                and "\t" not in x["why"]


def test_names_units_and_texts():
    check_names_units_and_texts(BENCHMARK)


def check_every_cell_reports_what_it_must(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "workloads" not in e2e["setup_s"] and e2e["setup_s"]["bound"] <= 0.1
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(pairs) // 4)
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        mine = [m for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2
        layer = [m for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer
        for m in layer:   # what a layer metric moves is reported there
            assert m["moves"] in {x["name"] for x in mine}, m["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        for cell in m.get("workloads", []):
            assert cell in cells


def test_every_cell_reports_what_it_must():
    check_every_cell_reports_what_it_must(BENCHMARK)


def check_files(bench, root):
    """The files that the entries name, in the checkout at `root`."""
    bench_dir = os.path.join(root, "benchmark")
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:   # never a width
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank", "_size"))
            assert not any(w in key for w in WIDTH_WORDS), key
        assert os.path.exists(os.path.join(
            bench_dir, "families", cfg["family"] + ".py"))
        assert os.path.exists(os.path.join(
            bench_dir, "reference", cfg["family"] + ".py"))
        assert cfg["deployment"]["chips"] == 1 and "assumed" in cfg
    for w in bench["workloads"]:
        with open(os.path.join(bench_dir, "workloads",
                               w["name"] + ".json")) as f:
            told = json.load(f)
        assert {k: told[k] for k in ("config", "traffic", "chips")} == \
            {k: w[k] for k in ("config", "traffic", "chips")}
        assert told["why"] and told["who"] and told["sizing"]
        with open(os.path.join(bench_dir, "traffic",
                               w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(bench_dir, "kinds", kind + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(bench_dir, "metrics",
                                           m["name"] + ".py")), m["name"]
    for dirpath, _dirs, names in os.walk(bench_dir):
        if "__pycache__" in dirpath:
            continue
        for n in names:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", n), n


def test_files_are_where_the_names_say():
    check_files(BENCHMARK, ROOT)


def test_published_sizes():
    """The widths of the two configurations as their sources publish them."""
    with open(os.path.join(BENCH, "configs", "gpt2_medium.json")) as f:
        g = json.load(f)
    assert (g["n_embd"], g["n_layer"], g["n_head"], g["n_positions"],
            g["vocab_size"], g["n_inner"]) == (1024, 24, 16, 1024, 50257, None)
    with open(os.path.join(BENCH, "configs", "bert_large.json")) as f:
        b = json.load(f)
    assert (b["hidden_size"], b["num_hidden_layers"],
            b["num_attention_heads"], b["intermediate_size"],
            b["vocab_size"], b["max_position_embeddings"]) == \
        (1024, 24, 16, 4096, 30522, 512)


# ---- adding by files alone -------------------------------------------------

def digest(root):
    out = {}
    for dirpath, _dirs, names in os.walk(root):
        if "__pycache__" in dirpath or ".bench_cache" in dirpath:
            continue
        for n in names:
            p = os.path.join(dirpath, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha1(
                    f.read()).hexdigest()
    return out


def test_a_cell_a_configuration_and_a_metric_are_new_files(tmp_path):
    """In a copy of the benchmark: a new configuration (same family), a new
    traffic mix, a new cell and a new per-layer metric are four new files
    and three new entries of BENCHMARK.json.  No file that was there
    changes, and the new cell runs."""
    root = str(tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"), tmp_path / "paddle_tpu")
    before = digest(tmp_path / "benchmark")

    with open(tmp_path / "benchmark/configs/gpt2_medium.json") as f:
        cfg = json.load(f)
    cfg["rehearse"].update(n_embd=64, n_head=2, n_layer=1)
    with open(tmp_path / "benchmark/configs/gpt2_other.json", "w") as f:
        json.dump(cfg, f)
    with open(tmp_path / "benchmark/traffic/lm_other.json", "w") as f:
        json.dump({"kind": "train", "batch": 8, "seq": 256,
                   "steps_per_s_cap": 12,
                   "rehearse": {"batch": 2, "seq": 32,
                                "steps_per_s_cap": 300}}, f)
    with open(tmp_path / "benchmark/workloads/gpt2_other.train.json",
              "w") as f:
        json.dump({"config": "gpt2_other", "traffic": "lm_other",
                   "chips": 1, "why": "w", "who": "w", "sizing": "s"}, f)
    with open(tmp_path / "benchmark/metrics/steps_counted.train.py",
              "w") as f:
        f.write("def read(obs):\n    return len(obs['step_s'])\n")

    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({
        "name": "gpt2_other", "source": cfg["source"],
        "file": "benchmark/configs/gpt2_other.json",
        "reduced": cfg["reduced"], "why": "w"})
    bench["workloads"].append({
        "name": "gpt2_other.train", "config": "gpt2_other",
        "traffic": "lm_other", "chips": 1, "why": "w"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt2_medium.train" in m.get("workloads", []):
            m["workloads"].append("gpt2_other.train")
    bench["per_layer"].append({
        "name": "steps_counted.train", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s", "workloads": ["gpt2_other.train"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    proc = run_cell(root, "--workload", "gpt2_other.train", "--seed", "3",
                    "--seconds", "1", "--trace", "1", "--rehearse")
    last = rehearsal_line(proc)
    assert "'steps_counted.train'" in last and "'step_ms.train'" in last
    assert "hidden 64 x 1 layers" in proc.stdout and "batch 2 x 32" \
        in proc.stdout
    after = digest(tmp_path / "benchmark")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/gpt2_other.json", "metrics/steps_counted.train.py",
        "traffic/lm_other.json", "workloads/gpt2_other.train.json"]
