"""Serving engine: how unevenly a dispatch's picks fall on the experts
held here.  From stats()["experts"]["dispatches"], over the whole window:
per dispatch the most picks on one expert of one layer over the mean picks
of a held expert (held picks / (expert layers x experts held)); the median
over the dispatches.  A program without the counters gives nothing."""
from statistics import median


def read(obs):
    log = (obs["stats"].get("experts") or {}).get("dispatches")
    held = obs["shape"].get("held_experts")
    if not log or not held:
        return None
    # an entry: [clock, tokens, held picks, experts touched, max load,
    # expert layers]
    return median(e[4] * e[5] * held / e[2] for e in log if e[2])
