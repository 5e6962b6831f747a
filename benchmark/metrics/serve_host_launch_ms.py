"""Median host milliseconds of the program's `serve.step.launch` span: key
split, seven host-to-device transfers and the dispatch of the step."""
from benchmark.metrics import _program_spans


def read(ctx):
    got = _program_spans.collect(ctx)
    return None if got is None else _program_spans.phase_ms(got, "launch")
