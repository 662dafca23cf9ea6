#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name, nothing is registered here:

    BENCHMARK.json                    the cell's entry (config, traffic,
                                      chips) and the metric lists
    benchmark/workloads/<cell>.json   why, who, sizing (for the reader)
    benchmark/configs/<config>.json   published sizes, family, deployment
    benchmark/traffic/<traffic>.json  kind and the generator's parameters
    benchmark/families/<family>.py    program entry points + reference
    benchmark/kinds/<kind>.py         the traffic driver: run(ctx) -> result
    benchmark/metrics/<metric>.py     one per-layer metric: read(obs)

The run sets up (imports, weights, compile or cache read, warm-up of the
cell's own shapes: `setup_s`), measures for --seconds, checks the outputs
against the plain reference after the window, and prints one JSON object
as the last line of standard output; its last key, `checks`, holds the
numbers `correct` compared beside their limits, which are also the last
lines of standard error.  --trace 0 reports the cell's end-to-end metrics,
--trace 1 its per-layer metrics (a few seconds of the window are traced).
It needs the chips the cell asks for: on anything else it exits non-zero
and prints no result.  `--rehearse` runs the same code at the tiny presets
of the data files on whatever backend JAX has; it prints counts, never a
result line, and exits with code 3.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = ".bench_cache"      # under the checkout; one directory per cell
EXIT_REHEARSAL = 3


class BenchFailure(Exception):
    """The run cannot give a result; it exits non-zero and prints none."""


CHECKS = []  # the kind's "[check]" lines: each number compared beside its limit


def log(msg):
    print(msg, flush=True)
    if msg.startswith("[check] "):
        CHECKS.append(msg[len("[check] "):])


def read_json(*parts):
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchFailure(f"missing {os.path.relpath(path, ROOT)}") from None


def load_plugin(folder, name):
    """benchmark/<folder>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.exists(path):
        raise BenchFailure(f"missing benchmark/{folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def overlay(base, over):
    """`base` with the keys of `over` laid over it, dicts merged deeply."""
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(name, rehearse):
    """The cell's entry of BENCHMARK.json with its configuration and
    traffic files read in (and, rehearsing, their tiny presets laid over)."""
    bench = read_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = read_json(ROOT, entry["file"])
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearse:
        cfg = overlay(cfg, cfg.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    return bench, cell, cfg, traffic


def metrics_of(bench, group, cell_name):
    return [m for m in bench[group]
            if cell_name in m.get("workloads", [cell_name])]


def configure_jax(cell_name, rehearse):
    """This process's JAX: the compile cache in one fixed directory per
    cell under the checkout, every program persisted (no minimum compile
    time, no size limit — the machine's environment sets a 192 MiB limit
    that evicts what the next run needs), and a count of the cache's
    requests and hits.  A rehearsal keeps no cache: its CPU programs are
    no use to a chip run and its tests run side by side."""
    import jax

    cache = os.path.join(ROOT, CACHE_DIR, cell_name)
    os.makedirs(cache, exist_ok=True)
    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_compilation_cache_max_size", -1)
    counts = {"compile_requests_use_cache": 0, "cache_hits": 0}
    heard = []   # (perf_counter, stage, seconds) of everything compiled

    def on_event(event, **_):
        key = event.rsplit("/", 1)[-1]
        if key in counts:
            counts[key] += 1

    def on_duration(event, seconds, **_):
        # a compile ends in a backend compile or, from the persistent
        # cache, in a retrieval; tracing and lowering come before either
        if "/compile/" in event or event.endswith("cache_retrieval_time_sec"):
            heard.append((time.perf_counter(), event.rsplit("/", 1)[-1],
                          seconds))

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return cache, counts, heard


def device_report(jax, chips, rehearse):
    devices = jax.devices()
    dev = devices[0]
    if not rehearse:
        if dev.platform != "tpu":
            raise BenchFailure(f"no accelerator: JAX found {dev.platform} "
                               f"({dev.device_kind})")
        if len(devices) < chips:
            raise BenchFailure(f"the cell needs {chips} chip(s), JAX found "
                               f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes(jax, chips):
    """The peak on the fullest chip.  The TPU runtime keeps live arrays
    (`peak_bytes_in_use`) and the region it reserves for the running
    programs' temporaries (`peak_bytes_reserved`) apart — on this chip
    `bytes_limit - bytes_in_use - bytes_reserved` is the reported largest
    free block to within 3 MB (chip run, PR 23) — so the peak is their
    sum.  A backend that reports neither gives 0."""
    peak = 0
    for d in jax.devices()[:chips]:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved", 0)))
    return peak


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise BenchFailure("--seed must be a non-negative whole number")
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        raise BenchFailure("benchmark/run.py runs from a paddle_tpu checkout")
    sys.path.insert(0, ROOT)

    bench, cell, cfg, traffic = load_cell(args.workload, args.rehearse)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    cache_dir, cache_counts, heard = configure_jax(cell["name"],
                                                  args.rehearse)
    import jax

    device = device_report(jax, cell["chips"], args.rehearse)
    on_tpu = device["platform"] == "tpu"
    peaks = None
    if on_tpu:
        import flops

        peaks = flops.device_peaks(device["kind"])
    log(f"[device] {json.dumps(device)}; jax {jax.__version__}; compile "
        f"cache {os.path.relpath(cache_dir, ROOT)} "
        f"({len(os.listdir(cache_dir))} entries at start)")

    ctx = {
        "cell": cell, "cfg": cfg, "traffic": traffic,
        "family": load_plugin("families", cfg["family"]),
        "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
        "on_tpu": on_tpu, "peaks": peaks,
        "t_process_start": T_PROCESS_START, "root": ROOT, "log": log,
        "fail": BenchFailure,
        "memory_peak": lambda: memory_peak_bytes(jax, cell["chips"]),
        # what jax.monitoring heard of compiling between two instants:
        # (programs compiled or read from the cache, every timed stage)
        "compiles_heard": lambda t0, t1: (
            sum(1 for t, e, _s in heard if t0 <= t < t1 and e in (
                "backend_compile_duration", "cache_retrieval_time_sec")),
            [(e, round(s, 3)) for t, e, s in heard if t0 <= t < t1]),
    }
    result = load_plugin("kinds", traffic["kind"]).run(ctx)

    log(f"[cache] persistent compile cache: {cache_counts['cache_hits']} "
        f"hits in {cache_counts['compile_requests_use_cache']} compile "
        f"requests, now {len(os.listdir(cache_dir))} entries")
    for line in result["notes"]:
        log(f"[note] {line}")
    end_to_end = {m["name"]: m for m in
                  metrics_of(bench, "end_to_end", cell["name"])}
    values = {}
    if args.trace:
        obs = result["obs"]
        for m in metrics_of(bench, "per_layer", cell["name"]):
            got = load_plugin("metrics", m["name"]).read(obs)
            if got is not None:
                values[m["name"]] = {"value": float(got), "unit": m["unit"]}
    else:
        for name, value in result["end_to_end"].items():
            if name in end_to_end:
                values[name] = {"value": float(value),
                                "unit": end_to_end[name]["unit"]}
    # read by the kind after the window, before the reference's own arrays
    device["memory_peak_bytes"] = int(result["memory_peak_bytes"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": values,
            "device": device}
    if args.trace and result["obs"]["busy_s"] is not None:
        device["busy_s"] = result["obs"]["busy_s"]
        device["window_s"] = result["obs"]["trace_window_s"]
        line["breakdown"] = result["obs"]["breakdown"]
    for why in result["wrong"]:
        log(f"[wrong] {why}")
    if args.rehearse:
        log(f"[rehearsal] not a chip run, no result line. counts: "
            f"attempted {line['attempted']}, failed {line['failed']}, "
            f"correct {line['correct']}, metrics {sorted(values)}")
        return EXIT_REHEARSAL if result["correct"] else 1
    # what `correct` compared, last: under the line's last key, and as the
    # last lines of standard error
    line["checks"] = CHECKS
    print(json.dumps(line), flush=True)
    for check in CHECKS:
        print(f"[check] {check}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BenchFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr, flush=True)
        code = 2
    sys.exit(code)
