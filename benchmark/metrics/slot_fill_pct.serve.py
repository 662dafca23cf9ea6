"""Serving engine: stats()["slot_fill"], the time-averaged share of the
engine's slots that held a request in the window."""


def read(obs):
    return 100 * obs["stats"]["slot_fill"]
