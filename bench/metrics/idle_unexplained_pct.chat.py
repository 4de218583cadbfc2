"""Share of the traced slice's device idle time in which the program had no
span open on any thread: idle that its spans cannot name, %."""
from bench.spans import idle_unexplained_pct


def read(run):
    return idle_unexplained_pct(run)
