"""Device: ``memory_stats()`` peak of live buffers + peak reservation (program
scratch) after the window and
before the check, the fullest chip, in GB (10^9 bytes)."""

LAYER = "Device"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def read(ctx):
    return ctx.memory_peak_bytes / 1e9
