"""Device: 1 - union of device-op intervals over the traced slice."""


def read(obs):
    if obs["trace"] is None:
        return None
    return 100 * (1 - obs["busy_s"] / obs["trace_window_s"])
