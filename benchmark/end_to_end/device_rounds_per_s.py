"""Simulated device-rounds whose update entered the server model, per second
of the window: the sum of ``clients_trained`` over the window's rounds over
the window's length (host clock, the runner's span clock)."""

UNIT = "device-rounds/s"
SOURCE = "host_clock"


def read(ctx):
    return ctx.device_rounds / ctx.window.seconds
