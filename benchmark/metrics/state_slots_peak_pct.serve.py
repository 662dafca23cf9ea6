"""KV pool: the most slots of the recurrent-state store held at once in
the window, over the slots it has (stats()["state"]).  A program without
a store gives nothing."""


def read(obs):
    state = obs["stats"].get("state")
    if not state or not state["slots"]:
        return None
    return 100 * state["peak_used_slots"] / state["slots"]
