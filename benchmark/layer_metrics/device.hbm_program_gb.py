"""Device: what the task's programs add to the chip's memory while they run
(scratch, outputs beside the donated state, evaluation batches): the
allocator's peak (``device_peak_bytes``: live buffers + reservation) on the
last ``host_transfer`` or ``eval`` phase span of the window's last round less
``device_bytes_in_use`` when ``bridge.init_state`` closed, the fullest chip,
in GB (10^9 bytes). With ``device.hbm_data_gb``, ``device.hbm_state_gb`` and
what the device held before the task, it sums to ``device.hbm_peak_gb``."""

from benchmark import setup_memory_spans

LAYER = "Device"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def read(ctx):
    return setup_memory_spans.memory_part_gb(ctx, 3)
