"""Median round time over the window's rounds: what a user waits per round
record (first operator span's start to the next round's)."""

from benchmark.window import percentile

UNIT = "s"
SOURCE = "host_clock"


def read(ctx):
    return percentile([r.seconds for r in ctx.window.rounds], 50)
