"""Device: what the server state holds of the chip's memory (parameters,
the server optimizer's moments, the round counter and key):
``device_bytes_in_use`` when ``bridge.init_state`` closed less the same when
``bridge.place`` closed, the fullest chip, in GB (10^9 bytes)."""

from benchmark import setup_memory_spans

LAYER = "Device"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def read(ctx):
    return setup_memory_spans.memory_part_gb(ctx, 2)
