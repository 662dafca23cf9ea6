"""Input pipeline: how long the step loop waited in next(loader), median
per step, from the benchmark's own span around it (host clock)."""
import statistics


def read(obs):
    return statistics.median(obs["data_wait_s"]) * 1e3
