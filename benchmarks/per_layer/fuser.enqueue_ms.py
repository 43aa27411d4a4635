"""Median host time of the gate calls of one application, before its
completion read: the benchmark's own span around them."""

import statistics


def read(ctx):
    spans = ctx["host_spans"].get("gate_calls")
    return statistics.median(spans) * 1e3 if spans else None
