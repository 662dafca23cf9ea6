"""scripts/compare_bench.py (ISSUE 14 satellite): direction-aware
axis-by-axis bench diffing, capture-shape extraction, and the --tiny
self-check wired tier-1."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SCRIPT = os.path.join(os.path.dirname(HERE), "scripts",
                      "compare_bench.py")


def _load():
    spec = importlib.util.spec_from_file_location("compare_bench",
                                                  SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tiny_self_check_subprocess():
    out = subprocess.run([sys.executable, SCRIPT, "--tiny"],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "self-check passed" in out.stdout


def test_direction_inference():
    m = _load()
    assert m.lower_is_better("gpt2s_served_ttft_p99_ms")
    assert m.lower_is_better("x_itl_p50_ms")
    assert m.lower_is_better("telemetry_overhead_pct")
    assert m.lower_is_better("anything", "ms")
    assert not m.lower_is_better("gpt2s_served_tokens_per_sec",
                                 "tokens/s")
    assert not m.lower_is_better("goodput_ratio")


def test_compare_flags_only_true_regressions():
    m = _load()
    old = [{"metric": "a_tokens_per_sec", "value": 100.0,
            "unit": "tokens/s"},
           {"metric": "b_ttft_p99_ms", "value": 10.0, "unit": "ms"}]
    new = [{"metric": "a_tokens_per_sec", "value": 95.0,
            "unit": "tokens/s"},          # -5%: within 10%
           {"metric": "b_ttft_p99_ms", "value": 30.0, "unit": "ms"}]
    rep = m.compare(old, new, threshold=0.10)
    assert [e["metric"] for e in rep["regressions"]] \
        == ["b_ttft_p99_ms"]
    assert [e["metric"] for e in rep["unchanged"]] \
        == ["a_tokens_per_sec"]
    # tighter threshold flags the tok/s drop too
    rep = m.compare(old, new, threshold=0.02)
    assert {e["metric"] for e in rep["regressions"]} \
        == {"a_tokens_per_sec", "b_ttft_p99_ms"}


def test_extract_records_all_capture_shapes():
    m = _load()
    recs = [{"metric": "x", "value": 1.0}, {"metric": "y", "value": 2}]
    assert {r["metric"] for r in m.extract_records(recs)} == {"x", "y"}
    assert {r["metric"] for r in m.extract_records(
        {"parsed": {"metric": "x", "value": 1.0,
                    "parsed_all": recs}})} == {"x", "y"}
    tail = "\n".join(["noise", json.dumps(recs[0]),
                      json.dumps({**recs[1], "parsed_all": recs})])
    assert {r["metric"] for r in m.extract_records(
        {"tail": tail})} == {"x", "y"}
    assert m.extract_records({"tail": "no json here"}) == []


def test_find_latest_pair_and_main(tmp_path):
    m = _load()
    old = [{"metric": "a_tokens_per_sec", "value": 100.0,
            "unit": "tokens/s"}]
    new_ok = [{"metric": "a_tokens_per_sec", "value": 99.0,
               "unit": "tokens/s"}]
    new_bad = [{"metric": "a_tokens_per_sec", "value": 50.0,
                "unit": "tokens/s"}]
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(old))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(new_ok))
    a, b = m.find_latest_pair(str(tmp_path))
    assert a.endswith("r01.json") and b.endswith("r02.json")
    assert m.main([str(tmp_path)]) == 0
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(new_bad))
    a, b = m.find_latest_pair(str(tmp_path))
    assert a.endswith("r02.json") and b.endswith("r03.json")
    assert m.main([str(tmp_path)]) == 1  # 49% tok/s drop flags
    assert m.main(["--threshold=0.6", str(tmp_path)]) == 0


@pytest.mark.slow
def test_regression_gate_over_newest_full_records():
    """Slow regression gate (quantized-collectives round satellite):
    `compare_bench.py --threshold` over the two newest FULL bench
    captures checked into the repo — a chip/bench round that tanks a
    headline axis past 50% fails here instead of being discovered
    rounds later. The generous threshold reflects that successive
    captures come from different (often CPU-degraded, shared) boxes;
    the gate is for collapses, not noise."""
    repo = os.path.dirname(HERE)
    import re as _re

    names = [n for n in os.listdir(repo)
             if _re.fullmatch(r"BENCH_r\d+\.json", n)]
    if len(names) < 2:
        pytest.skip("fewer than 2 BENCH_*.json captures in the repo")
    out = subprocess.run(
        [sys.executable, SCRIPT, "--threshold=0.5", repo],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, \
        f"bench regression past threshold:\n{out.stdout}{out.stderr}"


def test_topology_guard_skips_cross_transport_pairs():
    """r19 bench hygiene: records measured over different transports
    or pool topologies are different EXPERIMENTS — the comparator
    must refuse to diff them (loud `topology_skipped` entry) instead
    of reading the wire hop or the pool split as a regression."""
    m = _load()
    old = [{"metric": "f_fleet_tokens_per_sec", "value": 200.0,
            "unit": "tokens/s", "transport": "inproc",
            "pool_topology": "pooled"},
           {"metric": "g_fleet_ttft_p99_ms", "value": 10.0,
            "unit": "ms", "transport": "http",
            "pool_topology": "pooled"}]
    new = [{"metric": "f_fleet_tokens_per_sec", "value": 120.0,
            "unit": "tokens/s", "transport": "http",
            "pool_topology": "pooled"},          # 40% wire "drop"
           {"metric": "g_fleet_ttft_p99_ms", "value": 10.5,
            "unit": "ms", "transport": "http",
            "pool_topology": "pooled"}]          # same topology: diffed
    rep = m.compare(old, new, threshold=0.10)
    assert [e["metric"] for e in rep["topology_skipped"]] \
        == ["f_fleet_tokens_per_sec"], rep
    assert rep["topology_skipped"][0]["fields"] == ["transport"]
    assert rep["regressions"] == [], rep
    assert [e["metric"] for e in rep["unchanged"]] \
        == ["g_fleet_ttft_p99_ms"], rep
    # the skip is LOUD in the human report
    txt = m.format_report(rep)
    assert "TOPOLOGY-SKIPPED f_fleet_tokens_per_sec" in txt, txt
    assert "topology-skipped" in txt.splitlines()[-1], txt
    # pool split changes guard too, and gaining provenance counts
    assert m.topology_mismatch(
        {"transport": "http", "pool_topology": "pooled"},
        {"transport": "http", "pool_topology": "disagg:1p+1d"}) \
        == ["pool_topology"]
    assert m.topology_mismatch({}, {"pool_topology": "pooled"}) \
        == ["pool_topology"]
    # provenance-free records (every non-fleet axis) are untouched
    assert m.topology_mismatch({"metric": "a"}, {"metric": "a"}) == []


@pytest.mark.slow
def test_threshold_smoke_over_real_served_records():
    """r19 satellite: `compare_bench.py --threshold` smoke over REAL
    `bench.py served --tiny` records — bench-record schema drift (a
    renamed metric, a value field that stops parsing, a fleet record
    that loses its topology provenance) breaks HERE instead of on the
    next chip round. One tiny bench run plays both captures; a
    synthetic 60% collapse on the paged axis proves the gate fires."""
    import tempfile

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    repo = os.path.dirname(HERE)
    r = subprocess.run([sys.executable, "bench.py", "served",
                        "--tiny"], env=env, capture_output=True,
                       text=True, timeout=900, cwd=repo)
    assert r.returncode == 0, r.stderr[-3000:]
    recs = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    assert recs, r.stdout
    # every fleet record carries its topology provenance (satellite:
    # compare_bench must never diff across topologies silently)
    fleet = [rec for rec in recs if "fleet" in rec["metric"]]
    assert fleet and all(
        rec.get("transport") in ("inproc", "http")
        and rec.get("pool_topology") for rec in fleet), fleet
    with tempfile.TemporaryDirectory() as td:
        with open(os.path.join(td, "BENCH_r01.json"), "w") as f:
            json.dump(recs, f)
        with open(os.path.join(td, "BENCH_r02.json"), "w") as f:
            json.dump(recs, f)
        out = subprocess.run(
            [sys.executable, SCRIPT, "--threshold=0.10", td],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "0 new axis(es)" in out.stdout, out.stdout
        # engineered collapse: the same records with the paged tok/s
        # down 60% must flip the exit code through the same CLI path
        bad = [dict(rec) for rec in recs]
        for rec in bad:
            if "paged" in rec["metric"] and "fleet" not in \
                    rec["metric"]:
                rec["value"] = rec["value"] * 0.4
        with open(os.path.join(td, "BENCH_r03.json"), "w") as f:
            json.dump(bad, f)
        out = subprocess.run(
            [sys.executable, SCRIPT, "--threshold=0.10", td],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 1, out.stdout + out.stderr
        assert "REGRESSION" in out.stdout, out.stdout
