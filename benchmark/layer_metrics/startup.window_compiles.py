"""Start-up: backend compilations (``jax.monitoring`` event
``/jax/core/compile/backend_compile_duration``, persistent-cache hits do
not fire it) between window open and close. Should read 0: warm-up is the
task's own first rounds."""

LAYER = "Start-up"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def read(ctx):
    return ctx.window_compiles
