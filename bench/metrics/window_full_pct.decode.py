"""Share of the window's batched decode steps whose leader stopped waiting
because every active session had arrived, not because the window ran out
(the program's serving.window_full and serving.window_expired counters), %."""
from bench.spans import counter_delta


def read(run):
    full = counter_delta(run, "serving.window_full")
    expired = counter_delta(run, "serving.window_expired")
    if full is None and expired is None:
        return None                       # a program without these counters
    full, expired = full or 0, expired or 0
    return 100.0 * full / (full + expired) if full + expired > 0 else None
