"""Process start to window open: imports, session, submit, scheduling,
dataset generation and placement, compilation or cache load, warm-up
rounds."""

UNIT = "s"
SOURCE = "host_clock"


def read(ctx):
    return ctx.setup_s
