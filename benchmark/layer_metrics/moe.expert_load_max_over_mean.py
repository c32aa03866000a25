"""Expert layer: how unevenly the routers load the experts this chip holds:
the largest number of assignments one held expert (of one layer) got over a
round, over the mean of all of them, median over the window's train rounds;
from ``moe_expert_load_max`` / ``moe_expert_load_mean`` on
``round.<operator>.host_transfer``. 1 is an even load; the grouped products
take the time of their sum, the most loaded expert's chip in a deployment
takes the time of its own. Nothing to read: the metric is left out."""

from benchmark import manifest, program_spans

LAYER = "Expert layer"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def read(ctx):
    counts = manifest.find_module(
        "layer_metrics", "moe.dropped_assignments",
        ctx.cell.files_root).window_counts(ctx)
    if counts is None:
        return None
    ratios = [a["moe_expert_load_max"] / a["moe_expert_load_mean"]
              for a in counts if a["moe_expert_load_mean"] > 0]
    return program_spans.median(ratios) if ratios else None
