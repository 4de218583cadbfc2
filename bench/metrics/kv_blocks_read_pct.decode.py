"""Share of the KV cache's blocks the decode attention kernel read over the
window's batched steps: each slot's blocks up to its position, over the
blocks the cache holds (the program's serving.kv_blocks_read and
serving.kv_blocks_cached counters), %."""
from bench.spans import counter_delta


def read(run):
    blocks = counter_delta(run, "serving.kv_blocks_read")
    cached = counter_delta(run, "serving.kv_blocks_cached")
    if blocks is None or not cached:
        return None                       # a program without these counters
    return 100.0 * blocks / cached
