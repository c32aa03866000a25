"""Runner: the share of a round the runner spends NOT waiting for the
device. Per round, 1 - (the ``round.<op>.host_transfer`` spans + the
``round.<op>.eval`` spans) / round time — those two phases are the only
places the runner blocks on a device result — and the median of that over
the window's rounds."""

from benchmark.window import percentile

LAYER = "Runner"
UNIT = "%"
SOURCE = "program_span"
MOVES = "round_s.p50"
WAITS = ("host_transfer", "eval")


def read(ctx):
    shares = []
    for r in ctx.window.rounds:
        waiting = sum(s for (_, phase), s in r.phases.items() if phase in WAITS)
        shares.append(100.0 * (1.0 - waiting / r.seconds))
    return percentile(shares, 50)
