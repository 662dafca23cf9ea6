"""KV pool: 1 - the fewest blocks an allocation could obtain
(cache.available_block_count, sampled by the benchmark every 100 ms) over
the usable blocks of the pool."""


def read(obs):
    return 100 * (1 - obs["free_blocks_min"] / obs["num_blocks"])
