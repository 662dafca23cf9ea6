"""Serving engine: the share of the window's dispatches, of those that
landed while another was still unread, that found that one finished: the
device had run dry and stood idle until the new dispatch landed.  From
stats()["dispatch_ahead"] (PR 34): `found_idle_share`.  Unlike
`device_idle_pct.serve` it needs no profiler, so the engine's `stop()` line
gives the same counter on a `--trace 0` run.  The engine asks once a
dispatch, when its jitted call has returned: the device runs dry while the
host uploads and calls, and the flag turns true some tenths of a millisecond
after the device's last op ends, so the share is a floor of the dispatches
that met an idle device.  The split by the kind issued and the host's phases
in the rounds that starved the device beside all rounds
(stats()["round_phases"]: `starved_seconds` a starved round, `seconds` a
round) go to the log: the phase that is longer in the starved rounds is the
one to cut.  A program without the counters, or one that reads every
dispatch where it issues it (nothing probed), gives nothing; nor does a run
off the chip (no `peaks`): how often a CPU's program queue ran dry is no
reading of a device."""


def read(obs):
    ahead = obs["stats"].get("dispatch_ahead")
    if not ahead or not ahead.get("probed") or obs["peaks"] is None:
        return None
    by_kind = ", ".join(
        f"{k} {n} of {ahead['probed_by_kind'][k]}"
        for k, n in ahead["found_idle"].items() if ahead["probed_by_kind"][k])
    line = (f"[found idle] found the device idle when they landed: "
            f"{sum(ahead['found_idle'].values())} of {ahead['probed']} "
            f"dispatches issued with one in flight ({by_kind})")
    phases = obs["stats"].get("round_phases") or {}
    rounds = phases.get("round_ms", {}).get("count")
    if phases.get("starved_rounds") and rounds:

        def a_round(seconds, n):
            return ", ".join(f"{k} {1e3 * v / n:.2f}"
                             for k, v in seconds.items() if v)

        starved = phases["starved_rounds"]
        line += (f"; ms a round in the {starved} rounds that starved the "
                 f"device: {a_round(phases['starved_seconds'], starved)}; "
                 f"in all {rounds}: {a_round(phases['seconds'], rounds)}")
    obs["log"](line)
    return 100 * ahead["found_idle_share"]
