"""Tier-1 wiring for scripts/check_hlo_collectives.py: the aggregation-
stage memory guard runs with the normal suite, so a PR cannot silently
reintroduce an O(clients x params) all-gather into the defended round
program (it must stay O(clients x params / dp) per chip)."""

import os
import sys

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts")


def _lint():
    sys.path.insert(0, SCRIPTS)
    try:
        import check_hlo_collectives

        return check_hlo_collectives
    finally:
        sys.path.remove(SCRIPTS)


def test_defended_round_program_has_no_big_all_gather():
    lint = _lint()
    problems = lint.check(dp=2)
    assert problems == [], "\n".join(problems)


def test_sharded_server_update_program_also_clean():
    lint = _lint()
    problems = lint.check(dp=2, shard_server_update=True, record=False)
    assert problems == [], "\n".join(problems)


def test_lint_catches_the_gathered_formulation():
    """The guard itself works: a program that all_gathers the per-client
    delta matrix (the pre-sharding formulation) is flagged."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from olearning_sim_tpu.engine import hlo_stats
    from olearning_sim_tpu.parallel.mesh import make_mesh_plan

    dp = 2
    plan = make_mesh_plan(devices=jax.devices()[:dp], dp=dp, mp=1)
    clients, params = 16, 64

    def gathered(deltas):
        # The old defense_gather shape: every device materializes all
        # clients x all params.
        d_all = jax.lax.all_gather(deltas, "dp", tiled=True)
        return jnp.median(d_all, axis=0)

    # all_gather's result is typed device-varying (every device holds its
    # own copy), so the per-device medians leave through P("dp").
    fn = jax.jit(jax.shard_map(
        gathered, mesh=plan.mesh, in_specs=(P("dp"),), out_specs=P("dp"),
        axis_names=frozenset({"dp"}),
    ))
    x = np.zeros((clients, params), np.float32)
    text = fn.lower(x).compile().as_text()
    found = hlo_stats.parse_collectives(text)
    threshold = clients * params * 4 // dp
    assert any(c["op"] == "all-gather" and c["bytes"] >= threshold
               for c in found), found


def test_collective_byte_parsing():
    """hlo_stats parses result shapes (single and tuple) into bytes."""
    from olearning_sim_tpu.engine import hlo_stats

    text = """
  %all-gather.1 = f32[16,1200]{1,0} all-gather(f32[8,1200]{1,0} %p), channel_id=1
  %all-to-all.2 = (f32[4,3]{1,0}, f32[4,3]{1,0}) all-to-all(f32[4,3]{1,0} %a, f32[4,3]{1,0} %b)
  %all-reduce.1 = f32[] all-reduce(f32[] %r), to_apply=%region
"""
    got = {c["op"]: c["bytes"] for c in hlo_stats.parse_collectives(text)}
    assert got["all-gather"] == 16 * 1200 * 4
    assert got["all-to-all"] == 2 * 4 * 3 * 4
    assert got["all-reduce"] == 4
    assert hlo_stats.dominant_collectives(text)["all-gather"] == 16 * 1200 * 4
