"""AOT-compile the FULL-SIZE headline round program and record its memory
footprint — against the REAL TPU lowering whenever possible.

The XLA:CPU lowering tiles convolutions and chooses temp buffers
differently from XLA:TPU, so its memory analysis is indicative only.
``jax.experimental.topologies`` builds a PJRT TopologyDescription from
libtpu WITHOUT claiming any device — it works on a host with no chip — and
a jit can be lowered and compiled against one device of that topology from
pure ShapeDtypeStructs (no data, no execution). That yields the
authoritative XLA:TPU memory analysis for the exact 10k-client program the
bench runs.

Modes (auto-selected):
  1. topology AOT (default): v5e topology, devices[0], abstract args.
  2. ``--live`` or OLS_COMPILE_LIVE=1: compile on the session's default
     backend (the old behavior; works on CPU via JAX_PLATFORMS=cpu).

Also compiles the bf16-carry variant of the same program (VERDICT r3
next #4). Writes COMPILE_fullsize.json:
  {"backend": ..., "programs": {"f32_carry": {...}, "bf16_carry": {...}}}

Run: python scripts/compile_fullsize.py          # topology AOT, no device
     JAX_PLATFORMS=cpu python scripts/compile_fullsize.py --live  # CPU
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# An explicit JAX_PLATFORMS=cpu implies the live-CPU path (the documented
# pre-topology invocation keeps working on machines without libtpu).
LIVE = ("--live" in sys.argv or os.environ.get("OLS_COMPILE_LIVE") == "1"
        or os.environ.get("JAX_PLATFORMS", "").startswith("cpu"))

if not LIVE:
    # Topology mode claims no device: pinning the process platform to cpu
    # keeps an accidental concrete op (e.g. jax.random.key) off the chip;
    # the AOT compile itself targets TPU via the topology's devices.
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.engine import build_fedcore
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

GB = 1024 ** 3


def get_device():
    """One device to compile against + the backend label."""
    if LIVE:
        return jax.devices()[0], jax.default_backend(), len(jax.devices())
    from jax.experimental import topologies

    # v5e:2x2 is the smallest layout divisible by the default 2x2x1
    # chips-per-host bounds; we compile against ONE of its devices, which
    # is exactly the single-chip headline target. No device is claimed.
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    return topo.devices[0], "tpu (v5e topology AOT, no device claimed)", 1


def abstract_args(core, fam, plan):
    """ShapeDtypeStructs for round_step at the exact benchmarked shapes —
    no data materialized (topology devices cannot hold arrays). Identical
    for the f32 and bf16-carry programs: carry_dtype only changes the
    scan carry inside the program, never the argument shapes."""
    from olearning_sim_tpu.parallel.mesh import shard_clients

    padded, _ = shard_clients(fam["num_clients"], plan, fam["block"])
    C, n = padded, fam["n_local"]
    feat = tuple(fam["input_shape"])
    sds = jax.ShapeDtypeStruct
    # Key creation stays INSIDE eval_shape: a concrete jax.random.key(0)
    # would initialize the default backend (see the platform pin above).
    state = jax.eval_shape(lambda: core.init_state(jax.random.key(0)))
    return (
        state,
        sds((C, n) + feat, jnp.bfloat16),   # x, as ClientDataset.place casts
        sds((C, n), jnp.int32),              # y
        sds((C,), jnp.int32),                # num_samples
        sds((C,), jnp.int32),                # num_steps
        sds((C,), jnp.int32),                # client_uid
        sds((C,), jnp.float32),              # weight
    )


def compile_one(fam, device, carry=None):
    plan = make_mesh_plan(devices=[device], dp=1, mp=1)
    cfg = FedCoreConfig(
        batch_size=fam["batch"], max_local_steps=fam["local_steps"],
        block_clients=fam["block"], step_unroll=fam["unroll"],
        carry_dtype=jnp.bfloat16 if carry == "bf16" else None,
    )
    from olearning_sim_tpu.parallel.mesh import shard_clients

    # Blocks per device of the compiled scan — from the SAME padding
    # arithmetic that shapes the program's arguments (abstract_args), so
    # the FLOP multiplier can't drift from what actually runs.
    padded, _ = shard_clients(fam["num_clients"], plan, fam["block"])
    num_blocks = padded // (fam["block"] * plan.dp)
    import bench

    core = build_fedcore(
        fam["model"], bench.make_algorithm(fam["algorithm"]), plan, cfg
    )
    args = abstract_args(core, fam, plan)
    t0 = time.time()
    lowered = core._round_step.lower(*args)
    lower_s = time.time() - t0
    t1 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t1
    mem = compiled.memory_analysis()
    # TPU-lowered FLOP/byte counts for the roofline (DESIGN.md §2): the
    # compiler's own accounting of the optimized executable, replacing the
    # analytic per-layer estimate. Available from the same topology-AOT
    # compile that needs no device.
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    ca = ca if isinstance(ca, dict) else {}
    flops = ca.get("flops")  # None (not 0.0) when the backend omits it

    def gb(x):
        return round(x / GB, 3)

    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
            - mem.alias_size_in_bytes)
    return {
        "carry": carry or "f32",
        "lower_sec": round(lower_s, 1),
        "compile_sec": round(compile_s, 1),
        "argument_gb": gb(mem.argument_size_in_bytes),
        "output_gb": gb(mem.output_size_in_bytes),
        "temp_gb": gb(mem.temp_size_in_bytes),
        "alias_gb": gb(mem.alias_size_in_bytes),
        "generated_code_gb": gb(mem.generated_code_size_in_bytes),
        # generated code occupies HBM alongside buffers on TPU targets.
        "peak_estimate_gb": gb(peak),
        "fits_v5e_16gb": bool(peak < 16 * GB),
        # XLA cost analysis counts ONE iteration of the outer client-block
        # scan (whose body contains the fully-unrolled 10-step inner
        # loop): flops * num_blocks is the whole round. Cross-check: the
        # 43.5 GF body ~= 16 clients x 20 samples x 10 steps x 13.6
        # MF/sample-step (fwd+bwd ~= 2.64x fwd) — compiler-grade
        # confirmation of DESIGN.md §2's analytic roofline. null = the
        # backend produced no cost analysis (distinct from a measured 0).
        "cost_flops_scan_body": None if flops is None else float(flops),
        "cost_bytes_accessed_scan_body_gb": (
            None if "bytes accessed" not in ca
            else gb(float(ca["bytes accessed"]))),
        "num_client_blocks": num_blocks,
        "cost_tflops_per_round": (
            None if flops is None
            else round(float(flops) * num_blocks / 1e12, 1)),
    }


def main():
    import bench

    fam = bench.HEADLINE_FAMILY  # the exact headline configuration
    device, backend, ndev = get_device()
    rec = {
        "program": (
            f"headline round_step, {fam['num_clients']} clients x "
            f"{fam['local_steps']} steps x batch {fam['batch']}, "
            f"{fam['model']} shapes, block {fam['block']} / "
            f"unroll {fam['unroll']}"
        ),
        "backend": backend,
        "devices": ndev,
        "v5e_hbm_gb": 16,
        "programs": {},
    }
    for carry in (None, "bf16"):
        key = "bf16_carry" if carry else "f32_carry"
        rec["programs"][key] = compile_one(fam, device, carry)
        print(json.dumps({key: rec["programs"][key]}), flush=True)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "COMPILE_fullsize.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({k: v for k, v in rec.items() if k != "programs"}))


if __name__ == "__main__":
    main()
