"""Device: what the task's resident data hold of the chip's memory:
``device_bytes_in_use`` when ``bridge.place`` closed less
``device_bytes_in_use_before`` when ``bridge.build`` opened, the fullest
chip, in GB (10^9 bytes)."""

from benchmark import setup_memory_spans

LAYER = "Device"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def read(ctx):
    return setup_memory_spans.memory_part_gb(ctx, 1)
