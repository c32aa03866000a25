"""Kernels: model-FLOP utilization of the round program — the FLOPs the
algorithm needs per round, counted from the shapes by ``benchmark/flops.py``
with the model's layer list from its reference, over the device-busy time
per round (``round_program.device_ms``'s rule) and the chips' bfloat16
peak from ``benchmark/peaks.json``. Not a kernel's roofline share: per-kernel
shares wait for named scopes in the program."""

from benchmark import flops, manifest

LAYER = "Kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def needed_flops(ctx):
    cfg = ctx.cell.config
    layers = manifest.find_module(
        "reference", cfg["reference"], ctx.cell.files_root
    ).layers(cfg["model"])
    evaluates = len(ctx.task["operatorflow"]["operators"]) > len(
        manifest.train_operator_names(ctx.task))
    return flops.cell_round_flops(
        layers, ctx.params, int(ctx.cell.traffic["clients"]), evaluates)


def read(ctx):
    device_ms = manifest.find_module("layer_metrics",
                                     "round_program.device_ms")
    seconds = device_ms.per_round_seconds(ctx)
    if seconds is None:
        return None
    peak = ctx.device["count"] * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * needed_flops(ctx)["total"] / seconds / peak
