"""Host spans the benchmark's own loop writes into the profiler trace."""

from statistics import median


def median_ms(trace, ctx, span):
    """Median duration of the named span; one value (the host is one)."""
    items = trace.spans.get(span)
    if not items:
        return None
    t0 = min(d.window[0] for d in trace.devices)
    t1 = max(d.window[1] for d in trace.devices)
    inside = [d for s, d in items if t0 <= s < t1]
    return [median(inside) / 1000.0] if inside else None
