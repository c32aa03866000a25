"""Kernels: the grouped expert products' share of their roofline in
training — the least time the chip could take for the products the traced
rounds' local steps needed, over the training rounds' time in the grouped
products (``moe.experts.device_ms`` ``train_seconds``: the ``moe.experts``
scope and the compiler's ``ragged-dot-none`` kernels inside a
``round_step`` program, whatever their ``op_name`` kept of the scopes they
were written under).

The work (``benchmark/roofline.py``) is that of the form the cell's
configuration file states: ``grouped_swiglu``, three products a row, or,
where its ``model`` has ``mlp_hidden_act`` ``relu2``, ``grouped_relu2``,
two; forward, weight-gradient and input-gradient passes at the published
``hidden_size`` x ``moe_intermediate_size``; every held expert's weights
read once a pass in bfloat16 and their float32 gradients written once a
call, a call being one expert layer in one local step of one client. The
expert layers are ``num_layers - num_dense_layers`` of the file's ``model``
or, where it has no ``num_dense_layers``, the ``E`` letters of its
``layer_pattern``. The rows are the assignments the router sent to held
experts, ``moe_assignments_local`` on ``round.<operator>.host_transfer``:
what the mathematics needs. ``moe_assignments_computed``, what the grouped
products were told to cover, is the same number in a dropless layer (the
two are counted from one sort; ``moe.dropped_assignments`` is their
difference and reads 0 in every cell) and would part from the need in a
program that drops or pads. The recomputed hidden products, the weights'
casts, a width padded with zeros, a last group lengthened over zero rows
and the per-assignment arrays around the rows in use are in the time and
not in the work.

Which bound holds follows the rows an expert sees in a step: the bytes
(its weights' traffic) under some 510 to 610 rows at the three cells'
widths, where every cell so far stands (512, 128 and 192 rows an expert,
the cells' ``why``), the FLOPs above. Nothing counted (no held experts in
the file, no assignment routed to them, or no trace): the metric is left
out."""

from benchmark import manifest, roofline, scope_metrics

LAYER = "Kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"
ROWS = "moe_assignments_local"


def expert_layers(model):
    """How many of the configuration's layers hold routed experts."""
    if "num_dense_layers" in model:
        return int(model["num_layers"]) - int(model["num_dense_layers"])
    return model["layer_pattern"].count("E")


def needed(ctx):
    """The traced train rounds' work, or None where nothing was counted."""
    model = ctx.cell.config["model"]
    counts = [a for a in scope_metrics.traced_round_counts(ctx) or ()
              if ROWS in a]
    if not counts or not model.get("held_experts"):
        return None
    form = (roofline.grouped_relu2 if model.get("mlp_hidden_act") == "relu2"
            else roofline.grouped_swiglu)
    layers = expert_layers(model)
    return form(
        rows=sum(a[ROWS] for a in counts),
        calls=sum(layers * a["local_steps"] * a["clients_resident"]
                  for a in counts),
        experts=len(model["held_experts"]), hidden=int(model["hidden_size"]),
        intermediate=int(model["moe_intermediate_size"]))


def read(ctx):
    if ctx.trace is None:
        return None
    work = needed(ctx)
    if work is None:
        return None
    seconds = manifest.find_module(
        "layer_metrics", "moe.experts.device_ms").train_seconds(ctx)
    return roofline.share_percent(work, seconds, ctx.peaks,
                                  ctx.device["count"])
