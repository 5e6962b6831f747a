"""1 - union of device-op intervals / traced window, on the chip with the
largest idle share."""
from benchmark.metrics._common import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx)
