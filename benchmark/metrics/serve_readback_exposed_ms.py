"""Median milliseconds from the end of a traced step's program on the chip
(its `jit_step` run in the device trace) to the end of the program's
`serve.step.wait` span, mapped onto the trace's clock: the transfer of the
tokens back and the wake-up of the host, which no call from outside sees."""
from benchmark.metrics import _program_spans


def read(ctx):
    got = _program_spans.collect(ctx)
    return None if got is None else _program_spans.readback_exposed_ms(got)
