"""Trainer: compile requests heard by a jax.monitoring listener inside the
window, as chip_smoke.py counts them.  0 expected."""


def read(obs):
    return obs["compiles_in_window"]
