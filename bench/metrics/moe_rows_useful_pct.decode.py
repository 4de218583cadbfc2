"""Share of the expert rows the decode steps computed that carried a
(token, choice) pair of a served session routed to a held expert: the
program's serving.moe_assign_held.decode over serving.moe_rows.decode (rows
include the idle slots' and the padding of the dropless dispatch), %."""
from bench.spans import counter_delta


def read(run):
    held = counter_delta(run, "serving.moe_assign_held.decode")
    rows = counter_delta(run, "serving.moe_rows.decode")
    if held is None or not rows:
        return None                       # a program without these counters
    return 100.0 * held / rows
