"""Trainer: host clock around one turn of the step loop once its steps in
flight are full (dispatch a step, read the loss of the oldest): the
device's pace, median over the window's turns."""
import statistics


def read(obs):
    return statistics.median(obs["step_s"]) * 1e3
