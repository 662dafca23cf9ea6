"""Serving engine: the 99th percentile of the lengths of the window's
rounds, from stats()["round_phases"]["round_ms"] (PR 34; a round is one
iteration of the engine's loop that dispatched, and every token of a row
waits for the round it rides in).  The count, median and 99th percentile by
the kinds of dispatch a round held (`decode`, `prefill+decode`, ...) and the
8 longest rounds with their phases, the collector's milliseconds and the
programs compiled inside them go to the log.  In a traced run the round near
seconds / 3 + trace_seconds of the window is the one in which the profiler
stops.  A program without the ring gives nothing."""


def read(obs):
    phases = obs["stats"].get("round_phases")
    rounds = phases.get("round_ms") if phases else None
    if not rounds or not rounds["count"]:
        return None
    by_kind = "; ".join(
        f"{kind} {v['count']}: {v['p50_ms']:.1f} / {v['p99_ms']:.1f}"
        for kind, v in rounds["by_kind"].items())
    obs["log"](f"[rounds] {rounds['count']} rounds, ms p50 / p99 "
               f"{rounds['p50_ms']:.1f} / {rounds['p99_ms']:.1f}; by kind "
               f"(count: p50 / p99): {by_kind}")
    for r in rounds["slowest"]:
        top = ", ".join(f"{k} {v:.1f}" for k, v in sorted(
            r["phases_ms"].items(), key=lambda kv: -kv[1]) if v >= 0.05)
        obs["log"](f"[rounds] slow: {r['ms']:.1f} ms ({r['kind']}) round "
                   f"{r['round']} at {r['at_s']:.2f}s: {top}; gc "
                   f"{r['gc_ms']:.1f} ms, compiles {r['compiles']}")
    return rounds["p99_ms"]
