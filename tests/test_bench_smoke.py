"""bench.py must always produce its one JSON line — a bitrotted bench is a
silent zero. Runs the CPU-degraded path (an explicit JAX_PLATFORMS=cpu; with
no platform named and no TPU, bench.py fails instead)."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_cpu_smoke_emits_json_line():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "bench.py"], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, r.stdout
    rec = json.loads(lines[0])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(rec)
    assert rec["value"] > 0
    assert rec["degraded"] is True  # CPU path must self-mark


def test_bench_single_axis_modes_cpu():
    """Every named axis (r5: one parsed record per BASELINE config) must
    run standalone — a bitrotted secondary axis would silently vanish
    from the multi-axis default."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    for axis in ("bert_base", "decode"):
        r = subprocess.run([sys.executable, "bench.py", axis], env=env,
                           capture_output=True, text=True, timeout=600,
                           cwd=REPO)
        assert r.returncode == 0, (axis, r.stderr[-3000:])
        lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
        assert lines, (axis, r.stdout)
        rec = json.loads(lines[0])
        assert rec["value"] > 0
        assert rec.get("degraded") is True


def test_bench_exits_nonzero_when_an_axis_raises(monkeypatch, capsys):
    """An axis that throws must not stop the others, but the run must not
    exit 0 either: a green exit over a missing axis hid failures before."""
    import pytest

    monkeypatch.syspath_prepend(REPO)
    import bench

    def train(name, on_tpu):
        if name == "bert_large":
            raise RuntimeError("boom")
        return {"metric": f"{name}_train_tokens_per_sec_per_chip",
                "value": 1.0}

    monkeypatch.setattr(bench, "_bench_train", train)
    monkeypatch.setattr(bench, "_bench_decode", lambda on_tpu: [])
    monkeypatch.setattr(bench, "_bench_served", lambda *a, **k: [])
    monkeypatch.setattr(bench, "_remaining", lambda: 1e9)
    with pytest.raises(SystemExit) as exc:
        bench._run_all_axes(True)
    assert exc.value.code not in (0, None)
    assert "bert_large" in str(exc.value.code)
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    landed = {r["metric"] for r in last["parsed_all"]}
    assert "gpt2m_train_tokens_per_sec_per_chip" in landed
    assert not any(m.startswith("bert_large") for m in landed)
