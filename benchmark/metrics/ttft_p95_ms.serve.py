"""Serving engine: the client's time to first token, from the moment a
request was due (its client's previous completion) to its first on_token,
nearest-rank 95th percentile over the requests due in the window.  Per
layer and unbounded in a closed loop: requests become due at the ends of
engine rounds, so the value is quantized in rounds and flips between two
modes from run to run (PERF.md section 6).  It becomes an end-to-end
metric with the open-loop cells, whose arrivals are not phase-locked."""


def read(obs):
    return obs["ttft_p95_ms"]
