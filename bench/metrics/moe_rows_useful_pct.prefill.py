"""Share of the expert rows the prefill steps computed that carried a
(token, choice) pair routed to a held expert: the program's
serving.moe_assign_held.prefill over serving.moe_rows.prefill (rows include the
padding of the dropless dispatch), %."""
from bench.spans import counter_delta


def read(run):
    held = counter_delta(run, "serving.moe_assign_held.prefill")
    rows = counter_delta(run, "serving.moe_rows.prefill")
    if held is None or not rows:
        return None                       # a program without these counters
    return 100.0 * held / rows
