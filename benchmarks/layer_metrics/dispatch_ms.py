"""Median host time of the step call (dispatch, not the wait for the
result), inside the benchmark's own span around it."""

import statistics


def read(ctx):
    return statistics.median(ctx["dispatch_ms"]) if ctx["dispatch_ms"] else None
