"""Share of the traced slice in which no operation ran on the device, %."""
from bench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run)
