"""Expert layer: trips of the layer's loop over row windows a call, a call
being one expert layer in one local step of one client, over the window's
train rounds; from ``moe_window_trips`` on
``round.<operator>.host_transfer``, which the round program sums on the
device beside the other ``moe_*`` counts (``models/moe.py``
``DroplessMoE``: a trip covers the next C sorted rows of the assignments
routed to held experts, C a static function of the layer's sizes; PERF.md
section 6, PR 47), over the expert layers x local steps x resident clients
of the same spans. 1.0 says every call's held rows fitted one window: the
round's time then does not follow the load. More says some layer's held
experts drew over C rows in some step and took another trip (exact, and
slower); less, that some drew none. Nothing to read (a program that counts
no trips, as the parent's; a model without such a layer): the metric is
left out."""

from benchmark import manifest

LAYER = "Expert layer"
UNIT = "trips/call"
SOURCE = "program_counter"
MOVES = "round_s.p50"
TRIPS = "moe_window_trips"


def read(ctx):
    def sibling(name):
        return manifest.find_module("layer_metrics", name,
                                    ctx.cell.files_root)

    counts = [a for a in sibling("moe.dropped_assignments").window_counts(ctx)
              or () if TRIPS in a]
    layers = sibling("moe.experts_roofline").expert_layers(
        ctx.cell.config["model"])
    calls = sum(layers * a["local_steps"] * a["clients_resident"]
                for a in counts)
    if not calls:
        return None
    return sum(a[TRIPS] for a in counts) / calls
