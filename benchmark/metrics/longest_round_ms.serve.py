"""Serving engine: the longest round of the window, from
stats()["round_phases"]["longest_round"].  Its kind, when it began and its
own phases go to the log: in a run that stood still they say which phase
took the seconds.  A program without the counters gives nothing."""


def read(obs):
    phases = obs["stats"].get("round_phases")
    longest = phases["longest_round"] if phases else None
    if not longest or not longest["kind"]:   # no round in the window
        return None
    by_phase = ", ".join(f"{k} {v:.1f}" for k, v in sorted(
        longest["phases_ms"].items(), key=lambda kv: -kv[1]) if v >= 0.05)
    obs["log"](f"[rounds] longest round {longest['ms']:.1f} ms "
               f"({longest['kind']}) at {longest['at_s']:.1f}s of the "
               f"window; ms by phase: {by_phase}")
    return longest["ms"]
