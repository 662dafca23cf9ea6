"""Device: peak_bytes_in_use + peak_bytes_reserved after the window
(run.py's memory_peak_bytes says why the sum), in GB."""


def read(obs):
    return obs["memory_peak_bytes"] / 1e9 or None
