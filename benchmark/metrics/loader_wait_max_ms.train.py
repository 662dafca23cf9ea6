"""Input pipeline: the longest wait of the window in the loader's own
`next()`, from paddle_tpu.io.loader_stats() (PR 34): the program's counter
of every batch its newest DataLoader iterator handed out, which outlives the
loader (the train kind deletes it before metrics are read).  The window's
waits are the last len(obs["data_wait_s"]) of them, one a step dispatched in
the window; `data_wait_ms.train` is the median of the benchmark's span
around the same call, which one starved step does not move.  The consumer's longest step as the loader saw it (`gap_s`)
goes to the log.  The counter is of ONE iterator, the newest of the process:
the train kind iterates one loader once, and a newest iterator that handed
out fewer batches than the window has steps is not that one, so nothing is
read from it.  A program without the counter gives nothing."""


def read(obs):
    try:
        from paddle_tpu.io import loader_stats
    except ImportError:
        return None
    stats = loader_stats()
    steps = len(obs["data_wait_s"])
    if not steps or stats["batches"] < steps:
        return None
    waits, gaps = stats["wait_s"][-steps:], stats["gap_s"][-steps:]
    at = max(range(len(gaps)), key=gaps.__getitem__)
    obs["log"](f"[loader] {stats['batches']} batches handed out, the "
               f"window's last {len(waits)}: longest wait "
               f"{max(waits) * 1e3:.3f} ms; the consumer's longest step as "
               f"the loader saw it {gaps[at] * 1e3:.1f} ms (step {at})")
    return max(waits) * 1e3
