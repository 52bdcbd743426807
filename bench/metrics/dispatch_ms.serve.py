"""Median duration of the program's ``serve.dispatch`` spans in the run's
window: one closed batch taken through padding, the compiled assignment
and its one device-to-host copy."""

import statistics


def read(ctx):
    spans = ctx["counters"].get("dispatch_spans_s") or []
    return 1e3 * statistics.median(spans) if spans else None
