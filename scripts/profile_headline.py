"""Profile the headline bench (cnn4/CIFAR-10 shapes, 10k clients) on the
real chip: block-size sweep, sample-mode ablation, and HLO cost analysis.

Usage: python scripts/profile_headline.py [--quick]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from olearning_sim_tpu.engine import build_fedcore, fedavg, make_synthetic_dataset
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.parallel.mesh import make_mesh_plan


def time_config(plan, *, block, sample_mode="auto", num_clients=10_000,
                n_local=20, batch=32, local_steps=10, rounds=3, unroll=1,
                block_unroll=1, ds=None):
    cfg = FedCoreConfig(batch_size=batch, max_local_steps=local_steps,
                        block_clients=block, sample_mode=sample_mode,
                        step_unroll=unroll, block_unroll=block_unroll)
    core = build_fedcore("cnn4", fedavg(0.05), plan, cfg)
    if ds is None:
        ds = make_synthetic_dataset(
            seed=0, num_clients=num_clients, n_local=n_local,
            input_shape=(32, 32, 3), num_classes=10, dirichlet_alpha=0.5,
        ).pad_for(plan, block).place(plan)
    state = core.init_state(jax.random.key(0))

    t0 = time.perf_counter()
    state, m = core.round_step(state, ds)
    float(m.mean_loss)
    compile_s = time.perf_counter() - t0

    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        state, m = core.round_step(state, ds)
        float(m.mean_loss)
        times.append(time.perf_counter() - t0)
    return {
        "block": block, "sample_mode": sample_mode, "unroll": unroll,
        "block_unroll": block_unroll,
        "round_s": round(float(np.mean(times)), 4),
        "rounds_per_sec": round(1.0 / float(np.mean(times)), 4),
        "compile_s": round(compile_s, 1),
    }


def cost_analysis(plan, block=256):
    """FLOP estimate + top HLO ops of the compiled round program."""
    cfg = FedCoreConfig(batch_size=32, max_local_steps=10, block_clients=block)
    core = build_fedcore("cnn4", fedavg(0.05), plan, cfg)
    ds = make_synthetic_dataset(
        seed=0, num_clients=10_000, n_local=20,
        input_shape=(32, 32, 3), num_classes=10,
    ).pad_for(plan, block).place(plan)
    state = core.init_state(jax.random.key(0))
    lowered = core._round_step.lower(
        state, ds.x, ds.y, ds.num_samples,
        jax.numpy.full((ds.num_clients,), 10, jax.numpy.int32),
        ds.client_uid, ds.weight,
    )
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = ca.get("flops", 0.0)
    print(f"cost_analysis flops/round: {flops:.3e}")
    print(f"  bytes accessed: {ca.get('bytes accessed', 0.0):.3e}")
    # top HLO op categories by line count of the optimized HLO
    txt = compiled.as_text()
    import collections, re
    ops = collections.Counter()
    for mm in re.finditer(r"= \w+\[[^\]]*\] (\w+)", txt):
        ops[mm.group(1)] += 1
    print("top HLO ops:", ops.most_common(15))
    convs = re.findall(r"convolution\([^)]*\)[^\n]*", txt)
    print(f"{len(convs)} convolution ops; first 3:")
    for c in convs[:3]:
        print("   ", c[:220])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--cost", action="store_true")
    args = ap.parse_args()

    plan = make_mesh_plan()
    print("backend:", jax.default_backend())

    if args.cost:
        cost_analysis(plan)

    # One dataset for the whole sweep: padded to a multiple of every sweep
    # block (10_000 -> 10_240 with block 256, also divisible by 32/64/128).
    shared_ds = make_synthetic_dataset(
        seed=0, num_clients=10_000, n_local=20,
        input_shape=(32, 32, 3), num_classes=10, dirichlet_alpha=0.5,
    ).pad_for(plan, 256).place(plan)

    results = []
    sweeps = [
        dict(block=16, unroll=10),            # shipped headline config
        dict(block=16, unroll=10, block_unroll=2),
        dict(block=16, unroll=10, block_unroll=4),
        dict(block=32, unroll=10),
        dict(block=8, unroll=10),
        dict(block=64, unroll=5),
    ]
    if args.quick:
        sweeps = sweeps[:2]
    for kw in sweeps:
        r = time_config(plan, ds=shared_ds, **kw)
        results.append(r)
        print(json.dumps(r), flush=True)

    if args.trace:
        # Trace the SHIPPED headline config (bench.py: block 16, unroll 10).
        cfg = FedCoreConfig(batch_size=32, max_local_steps=10,
                            block_clients=16, step_unroll=10)
        core = build_fedcore("cnn4", fedavg(0.05), plan, cfg)
        state = core.init_state(jax.random.key(0))
        state, m = core.round_step(state, shared_ds)
        float(m.mean_loss)
        with jax.profiler.trace("/tmp/headline_trace"):
            state, m = core.round_step(state, shared_ds)
            float(m.mean_loss)
        print("trace written to /tmp/headline_trace")


if __name__ == "__main__":
    main()
