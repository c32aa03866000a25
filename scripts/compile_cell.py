"""Compile one benchmark cell's round program for a TPU v5e that is described
and not attached, and print what the compiler says it needs.

    JAX_PLATFORMS=cpu python scripts/compile_cell.py \\
        [--workload nemotron_twotower_ep16.8_silo_2k] [--seed N]

Builds the cell's ``FedCore`` as the task bridge does (the composed task's
model, algorithm and ``fedcore`` block, one chip, the population padded as
``ClientDataset.pad_for`` pads it) over one device of a ``v5e:2x2`` topology
(``jax.experimental.topologies``: the TPU's compiler is installed where no
chip is), lowers ``round_step`` from shapes alone, compiles it, and prints
one JSON line: the compiler's temporaries, arguments, generated code and
their sum (GiB), its ``peak_memory``, the generated code in GB, and the
size of the serialized executable under zstd, which is what the program's
entry in the compilation cache takes (PERF.md section 7 item 9k adds the
cells' entries up against the chip machine's cap).

**Which of these decides the fit: none of the sums printed, only whether the
compile raises.** The compiler's own check is ``reserved 0.258 GiB +
arguments + HLO temporaries <= 15.75 GiB``, and a program that fails it
raises here what it would raise on the chip (``RESOURCE_EXHAUSTED ... Used
16.06G of 15.75G hbm``, with the three terms). ``temporaries_gib``
(``temp_size_in_bytes``) counts the round program's outputs too, which share
the donated arguments' memory, so ``total_gib`` overstates what the chip must
hold by about the arguments: ``kimi_linear_ep32.8_silo_2k`` compiles at a
``total_gib`` of 17.85, and ``temporaries_gib - arguments_gib`` has stood
within 0.1 GiB of the check's HLO temporaries (PERF.md section 6, PR 42).
Programs far past the limit by that arithmetic have compiled too, with a third
of the generated code (0.11 GB for 0.34): the compiler then fits them some
other way, and what that costs only a chip run says. The chip's measured
peak (``device.hbm_peak_gb``) has stood 0.4-0.6 GiB over ``peak_memory_gib``.

Nothing runs and nothing is timed: these are the compiler's own sizes, not
a chip run. Not part of a benchmark run; PERF.md quotes its output wherever
a change is sized before a chip call.
"""

import argparse
import dataclasses
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GIB = 1024 ** 3


def lower_round_step(workload: str, seed: int):
    """The cell's ``round_step`` lowered for one described v5e chip."""
    import jax
    import numpy as np
    from jax.experimental import topologies

    from benchmark import manifest
    from olearning_sim_tpu.engine.algorithms import from_config
    from olearning_sim_tpu.engine.client_data import (
        make_synthetic_text_dataset)
    from olearning_sim_tpu.engine.fedcore import FedCoreConfig, build_fedcore
    from olearning_sim_tpu.engine.task_bridge import NEXT_TOKEN_TASK_TYPES
    from olearning_sim_tpu.parallel.mesh import make_mesh_plan

    cell = manifest.load_cell(workload)
    task = manifest.compose_task(cell, seed)
    params = manifest.engine_params(task)
    model, syn = params["model"], params["data"]["synthetic"]
    cfg = FedCoreConfig.from_dict(params.get("fedcore", {}))
    if {d["task_type"] for d in task["target"]["data"]} & set(
            NEXT_TOKEN_TASK_TYPES):
        cfg = dataclasses.replace(cfg, task="next_token")
    algorithm = dict(params["algorithm"])
    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    plan = make_mesh_plan(devices=[device], dp=1, mp=1)
    core = build_fedcore(
        model["name"], from_config(algorithm.pop("name"), **algorithm), plan,
        cfg, model_overrides=model.get("overrides"),
        input_shape=tuple(model["input_shape"]))
    ds = make_synthetic_text_dataset(
        seed=seed, num_clients=int(cell.traffic["clients"]),
        n_local=int(syn["n_local"]), seq_len=int(model["input_shape"][0]),
        num_classes=int(syn["num_classes"]),
        dirichlet_alpha=syn.get("dirichlet_alpha"),
        # Only the decoder cells size their vocabulary; shapes alone matter.
        **({"vocab_size": int(syn["vocab_size"])} if "vocab_size" in syn
           else {}),
    ).pad_for(plan, cfg.block_clients)
    clients = (ds.num_clients,)

    def shape(a, dtype=None):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)

    # A described device holds no array: the state from shapes, the key
    # made inside ``eval_shape``; the arguments in ``round_step``'s order.
    state = jax.eval_shape(lambda: core.init_state(jax.random.key(0)))
    return core._round_step.lower(
        state, shape(ds.x), shape(ds.y), shape(ds.num_samples, np.int32),
        jax.ShapeDtypeStruct(clients, np.int32),        # num_steps
        shape(ds.client_uid, np.int32), shape(ds.weight, np.float32))


def sizes(compiled) -> dict:
    """What the compiler says a compiled program needs, by the docstring's
    names."""
    import zstandard
    from jax.experimental import serialize_executable

    mem = compiled.memory_analysis()
    parts = (mem.temp_size_in_bytes, mem.argument_size_in_bytes,
             mem.generated_code_size_in_bytes)
    return {
        "temporaries_gib": round(parts[0] / GIB, 3),
        "arguments_gib": round(parts[1] / GIB, 3),
        "generated_code_gb": round(parts[2] / 1e9, 4),
        "total_gib": round(sum(parts) / GIB, 3),
        "peak_memory_gib": round(mem.peak_memory_in_bytes / GIB, 3),
        "cache_entry_mb": round(len(zstandard.compress(
            serialize_executable.serialize(compiled)[0])) / 1e6, 1),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload",
                        default="nemotron_twotower_ep16.8_silo_2k")
    parser.add_argument("--seed", type=int, default=2**31 + 40)
    args = parser.parse_args(argv)
    import jax

    # What is compiled for a described device cannot be read back.
    jax.config.update("jax_enable_compilation_cache", False)
    compiled = lower_round_step(args.workload, args.seed).compile()
    print(json.dumps({"workload": args.workload, "device": "TPU v5e "
                      "(described, not attached)", **sizes(compiled)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
