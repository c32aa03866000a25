"""Device: 1 - (union of the device-op intervals) / traced stretch, from the
profiler trace; the worst chip where there are four."""

LAYER = "Device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.worst_idle_share
