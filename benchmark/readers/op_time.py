"""Device time of the operations a metric's file selects."""

from benchmark import trace_reduce as tr


def per_step_ms(trace, ctx, **patterns):
    """Per device, the summed duration per whole step of the operations
    that match `patterns` (see `trace_reduce.select`). Nothing where no
    device ran such an operation."""
    picked = [tr.select(dev, **patterns) for dev in trace.devices]
    if not any(picked):
        return None
    return [tr.ms_per_step(dev, ops)
            for dev, ops in zip(trace.devices, picked)]
