"""The device's own record of each executed step program."""

from statistics import median


def median_ms(trace, ctx):
    """Median event of each device's `Steps` line, whole steps only."""
    return [median(d for _, d in dev.steps) / 1000.0
            for dev in trace.devices]
