"""How much of the traced window the device ran nothing."""

from benchmark import trace_reduce as tr


def idle_pct(trace, ctx):
    """1 - union of the operations' intervals / span of the whole steps."""
    return [100.0 * tr.idle_share(dev) for dev in trace.devices]
