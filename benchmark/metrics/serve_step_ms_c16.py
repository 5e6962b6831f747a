"""Median device time of the prefill-chunk-wide program in the trace."""
from benchmark.metrics._common import serve_step_ms


def read(ctx):
    widths = [s["width"] for s in ctx["window"].get("steps", [])
              if s["width"] > 1]
    return serve_step_ms(ctx, widths[0]) if widths else None
