"""Median host milliseconds of the program's `serve.step.emit` span: prefix
insert, token distribution with the `on_token` callbacks, trims, gauges."""
from benchmark.metrics import _program_spans


def read(ctx):
    got = _program_spans.collect(ctx)
    return None if got is None else _program_spans.phase_ms(got, "emit")
