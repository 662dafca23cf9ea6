"""Decode programs: compile_tracker.count_since(mark at the window's
start).  0 expected: every bucket was warmed in set-up."""


def read(obs):
    return obs["compiles_in_window"]
