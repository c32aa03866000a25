"""Round program: the share of the traced stretch's operation time spent
computing again in the backward pass what the forward pass had computed
(``trace_reduce.RECOMPUTED``: operations whose ``op_name`` holds
``rematted_computation``) — what the program pays in time
for the memory ``jax.checkpoint`` / ``nn.remat`` save. Over the plain sum of
the operations' times, as ``round_program.scoped_share`` is."""

from benchmark import trace_reduce

LAYER = "Round program"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "round_s.p50"


def read(ctx):
    if ctx.trace is None or ctx.trace.op_seconds <= 0:
        return None
    return 100.0 * (ctx.trace.scope_seconds(which=trace_reduce.RECOMPUTED)
                    / ctx.trace.op_seconds)
