"""How often the program's routers and the float32 reference's choose the
same experts, at the benchmark cell's sizes, on the chip.

    python scripts/lfm2_routing_agreement.py [--seed N] [--sequences 8]
        [--workload lfm2_moe_ep8.8_silo_1k]

Builds the model of ``--workload``'s configuration (``lfm2_moe_ep8``'s, or
another whose expert layers are ``DroplessMoE`` and whose reference has
``chosen_experts``) from its configuration file with seeded
weights, runs one forward pass of the program's model (bfloat16 matmul
inputs, the router in float32) over ``--sequences`` sequences of the
configuration's generator, and the reference's forward pass
(``benchmark/reference/<model>.py``, float32 at ``highest``) over the same
sequences one at a time, and prints the share of (token, slot) choices on
which the two agree, a layer and overall: a top-k choice can flip where two
scores nearly tie, because the layers before the router feed it activations
that differ in the last bfloat16 bits. Not part of a benchmark run; PERF.md
section 2 quotes its reading beside the check's limits. Refuses the CPU.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import check, manifest
    from olearning_sim_tpu.engine.client_data import (
        make_synthetic_text_dataset)
    from olearning_sim_tpu.models import get_model

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2**31 + 28)
    parser.add_argument("--sequences", type=int, default=8)
    parser.add_argument("--workload", default="lfm2_moe_ep8.8_silo_1k")
    args = parser.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU: a routing agreement share is a chip reading",
              file=sys.stderr)
        return 1
    cell = manifest.load_cell(args.workload)
    params = manifest.engine_params(manifest.compose_task(cell, args.seed))
    model_cfg, syn = params["model"], params["data"]["synthetic"]
    model = get_model(model_cfg["name"]).build(**model_cfg["overrides"])
    tokens = jnp.asarray(make_synthetic_text_dataset(
        seed=args.seed, num_clients=1, n_local=args.sequences,
        seq_len=model_cfg["input_shape"][0], num_classes=syn["num_classes"],
        vocab_size=syn["vocab_size"],
        dirichlet_alpha=syn["dirichlet_alpha"]).x[0])
    weights = jax.jit(lambda k: model.init(k, tokens[:1])["params"])(
        jax.random.key(args.seed % 2**31))
    _, inter = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["intermediates"]))(weights, tokens)
    leaves = jax.tree_util.tree_flatten_with_path(inter)[0]
    program = np.stack([np.asarray(leaf) for path, leaf in leaves
                        if "moe_chosen" in jax.tree_util.keystr(path)])
    program = program.reshape(program.shape[0], args.sequences, -1,
                              program.shape[-1])      # [layers, n, L, k]
    reference = manifest.find_module("reference", cell.config["reference"])
    flat = reference.prepare(check.flatten(weights))
    agree = np.zeros(program.shape[:2])
    for i in range(args.sequences):
        want = reference.chosen_experts(flat, tokens[i])    # [layers, L, k]
        same = (program[:, i, :, :, None] == want[:, :, None, :]).any(-1)
        agree[:, i] = same.mean((-1, -2))
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "tokens": int(tokens.size),
        "agreement_by_layer": agree.mean(1).tolist(),
        "agreement": float(agree.mean())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
