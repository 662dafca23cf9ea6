"""Serving engine: what the host costs per dispatch.  From
stats()["round_phases"], over the whole window: the engine thread's
seconds in `admit`, `plan`, `dispatch`, `emit` and `other` (everything
but waiting for the device, `read_back`, and waiting for work,
`idle_wait`) over the dispatches it issued.  A program without the
counters gives nothing."""

HOST_PHASES = ("admit", "plan", "dispatch", "emit", "other")


def read(obs):
    phases = obs["stats"].get("round_phases")
    if not phases or not phases["dispatches"]:
        return None
    host_s = sum(phases["seconds"][k] for k in HOST_PHASES)
    return 1e3 * host_s / phases["dispatches"]
