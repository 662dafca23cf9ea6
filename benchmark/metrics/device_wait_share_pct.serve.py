"""Serving engine: the share of the engine thread's working time spent
blocked on the device.  From stats()["round_phases"], over the whole
window: `read_back` over every phase but `idle_wait`.  100 minus it is the
device's idle share as the host sees it (`device_idle_pct.serve` is the
same quantity from a 3 s slice of the device's own trace).  A program
without the counters gives nothing."""


def read(obs):
    phases = obs["stats"].get("round_phases")
    if not phases:
        return None
    seconds = phases["seconds"]
    working_s = sum(seconds.values()) - seconds["idle_wait"]
    if working_s <= 0:
        return None
    return 100 * seconds["read_back"] / working_s
