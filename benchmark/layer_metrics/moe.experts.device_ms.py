"""Expert layer: device time a round in the held experts' grouped products,
in training and evaluation — the operations under ``moe.experts`` (the
gate or the squared ReLU, the weights' casts to the matmul dtype and their
gradients' way back, the recomputed hidden products' elementwise part) plus
the grouped matmuls themselves, which the TPU compiler renames
``ragged-dot-none`` and strips of the scope they were written under (with
the local steps unrolled their ``op_name`` ends ``client_train/closed_call/
ragged-dot-none``; with the steps as a loop, and in the evaluation, it is
the bare word), so they are found by that name wherever they are: the
models have no other grouped product.

``train_seconds`` is training's part of that, what
``moe.experts_roofline`` divides by: the same two kinds of operation that
ran **inside a ``round_step`` program**, the program being the one whose
execution on the device holds the operation
(``trace_reduce``: the ``XLA Modules`` line), not a component of its
``op_name``. Taken so, and not as every such kernel less the evaluation's,
because the work it is held against is counted by ``round_step`` alone: a
third program with grouped products of its own would then add to neither
side. The scope path cannot say it: until PR 46 the rule was "under
``client_train``", which a kernel made inside a ``while`` is not, and a
loop of local steps read twice the share of the same steps unrolled
(PERF.md section 6, PR 46)."""

from benchmark import scope_metrics

LAYER = "Expert layer"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"
SCOPE = "moe.experts"
GROUPED_MATMUL = "ragged-dot-none"
TRAIN_PROGRAM = "jit_round_step"


def train_seconds(ctx):
    """Seconds of the traced stretch in the training rounds' grouped
    products: what the roofline share divides by."""
    return (ctx.trace.scope_seconds(SCOPE, program=TRAIN_PROGRAM)
            + ctx.trace.scope_seconds(GROUPED_MATMUL, program=TRAIN_PROGRAM))


def read(ctx):
    return scope_metrics.round_ms(ctx, SCOPE, GROUPED_MATMUL)
