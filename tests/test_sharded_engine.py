"""Scale-out round engine: parity oracles + resume acceptance (ISSUE 6).

- sharded robust aggregation (all_to_all coordinate shards) matches the
  gathered formulation exactly: function-level shard_map harness vs
  ``defense.robust_aggregate`` on the full matrix, and engine-level dp=1
  vs dp=2 round results bitwise (a dp=1 "shard" IS the gathered matrix);
- Krum anomaly scores from psum'd per-shard partial distances match the
  gathered ``distance_scores`` to float tolerance;
- the cross-replica sharded server update (reduce-scatter + sharded
  optimizer state) matches the replicated update within allclose, with
  the optimizer state laid out O(params/dp) per device;
- a sharded-opt_state run checkpoints and resumes bitwise through the
  PR 4 manifest/checkpointer machinery (fresh-runner supervisor-style
  resume);
- the persistent XLA compilation cache: a second process compiling the
  same program records cache hits, not compiles.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from olearning_sim_tpu.engine import (
    build_fedcore,
    fedadam,
    fedavg,
    make_synthetic_dataset,
)
from olearning_sim_tpu.engine import defense as defense_mod
from olearning_sim_tpu.engine.defense import DefenseConfig
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.engine.runner import (
    DataPopulation,
    OperatorSpec,
    SimulationRunner,
)
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

NUM_CLIENTS = 16
INPUT_SHAPE = (8,)
MODEL_KW = dict(model_overrides={"hidden": [8], "num_classes": 3},
                input_shape=INPUT_SHAPE)


def _leaves(state):
    return jax.tree.leaves(jax.device_get(state.params))


def _build(plan, algorithm=None, **cfg_kw):
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2,
                        **cfg_kw)
    return build_fedcore("mlp2", algorithm or fedavg(0.1), plan, cfg,
                         **MODEL_KW)


def _dataset(plan, seed=7):
    return make_synthetic_dataset(
        seed, NUM_CLIENTS, 6, INPUT_SHAPE, 3, class_sep=3.0
    ).pad_for(plan, 2).place(plan)


@pytest.fixture(scope="module")
def plan8():
    return make_mesh_plan()  # all 8 CPU devices


@pytest.fixture(scope="module")
def ds8(plan8):
    return _dataset(plan8)


@pytest.fixture(scope="module")
def adam_cores(plan8):
    """(replicated, shard_server_update) fedadam cores — shared across the
    parity and resume tests so each compiled program is paid for once."""
    return (_build(plan8, algorithm=fedadam(0.1)),
            _build(plan8, algorithm=fedadam(0.1), shard_server_update=True))


# ------------------------------------------------- function-level oracles
@pytest.mark.parametrize("aggregator", ["trimmed_mean", "median"])
def test_sharded_aggregate_matches_gathered_bitwise(aggregator):
    """The coordinate-sharded robust aggregate (all_to_all + per-shard
    sort/window + placement) equals ``robust_aggregate`` over the full
    gathered matrix BITWISE: every coordinate's client column is intact
    under the resharding, so the statistics are the same computation."""
    dp = 2
    plan = make_mesh_plan(devices=jax.devices()[:dp], dp=dp, mp=1)
    rng = np.random.default_rng(3)
    C = 12
    tree = {
        "w": rng.normal(size=(C, 5, 3)).astype(np.float32),
        "b": rng.normal(size=(C, 7)).astype(np.float32),  # 7 % dp != 0: pads
    }
    mask_np = rng.random(C) > 0.3
    trim = jnp.float32(0.2)

    gathered = defense_mod.robust_aggregate(
        tree, jnp.asarray(mask_np), aggregator, trim
    )

    def body(d_tree, mask):
        shards = jax.tree.map(
            lambda a: defense_mod.shard_client_deltas(a, "dp", dp), d_tree
        )
        agg_shards = jax.tree.map(
            lambda s: defense_mod.robust_leaf_aggregate(
                s, mask, aggregator, trim
            ),
            shards,
        )
        return jax.tree.map(
            lambda s, a: defense_mod.place_coordinate_shard(
                s, "dp", dp, a.shape[1:]
            ),
            agg_shards, d_tree,
        )

    spec = jax.tree.map(lambda _: P("dp"), tree)
    sharded = jax.jit(jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(spec, P()), out_specs=jax.tree.map(lambda _: P(), tree),
        axis_names=frozenset({"dp"}),
    ))(tree, mask_np)

    for got, want in zip(jax.tree.leaves(sharded), jax.tree.leaves(gathered)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_distance_scores_match_gathered():
    """psum'd per-shard partial squared distances == the gathered
    ``distance_scores`` (allclose: the coordinate sum is re-associated
    across shards)."""
    dp = 2
    plan = make_mesh_plan(devices=jax.devices()[:dp], dp=dp, mp=1)
    rng = np.random.default_rng(4)
    C = 12
    tree = {
        "w": rng.normal(size=(C, 5, 3)).astype(np.float32),
        "b": rng.normal(size=(C, 7)).astype(np.float32),
    }
    mask_np = rng.random(C) > 0.3
    trim = jnp.float32(0.2)

    center = defense_mod.robust_aggregate(
        tree, jnp.asarray(mask_np), "median", trim
    )
    want = defense_mod.distance_scores(tree, center, jnp.asarray(mask_np))

    def body(d_tree, mask):
        shards = jax.tree.map(
            lambda a: defense_mod.shard_client_deltas(a, "dp", dp), d_tree
        )
        centers = jax.tree.map(
            lambda s: defense_mod.robust_leaf_aggregate(s, mask, "median",
                                                        trim),
            shards,
        )
        partial = sum(
            defense_mod.partial_distance_sq(s, c)
            for s, c in zip(jax.tree.leaves(shards), jax.tree.leaves(centers))
        )
        return jnp.where(mask, jnp.sqrt(jax.lax.psum(partial, "dp")), 0.0)

    spec = jax.tree.map(lambda _: P("dp"), tree)
    got = jax.jit(jax.shard_map(
        body, mesh=plan.mesh, in_specs=(spec, P()), out_specs=P(),
        axis_names=frozenset({"dp"}),
    ))(tree, mask_np)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------- engine-level parity
def test_defended_round_dp1_vs_dp2_bitwise():
    """The defended round program produces bitwise-identical global params
    on dp=1 and dp=2 meshes: per-client RNG streams are resharding-stable
    and the sharded robust aggregate is the gathered computation — a dp=1
    run IS the gathered oracle (its single shard holds the full matrix).
    median is the aggregator here (it doubles as the score center);
    trimmed_mean's bitwise parity is covered by the function-level oracle
    above plus the existing dp=8 numpy oracles in test_defense.py."""
    defense = DefenseConfig(clip_norm=1.0, aggregator="median",
                            trim_fraction=0.2, anomaly_threshold=4.0)
    results = {}
    for dp in (1, 2):
        plan = make_mesh_plan(devices=jax.devices()[:dp], dp=dp, mp=1)
        core = _build(plan)
        ds = _dataset(plan)
        state, metrics = core.round_step(
            core.init_state(jax.random.key(0)), ds, defense=defense
        )
        scores = np.asarray(jax.device_get(metrics.anomaly_score))
        results[dp] = (_leaves(state), scores, float(metrics.clipped))
    for a, b in zip(results[1][0], results[2][0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Scores: same participants, same values up to the psum re-association.
    np.testing.assert_allclose(results[1][1], results[2][1],
                               rtol=1e-5, atol=1e-6)
    assert results[1][2] == results[2][2]


def test_sharded_server_update_matches_replicated(plan8, ds8, adam_cores):
    """shard_server_update=True (reduce-scatter + sharded Adam state +
    shard-stitched params) stays allclose to the replicated update across
    chained rounds, and the optimizer state really is O(params/dp) per
    device: flat dp-sharded leaves whose per-device shard is 1/dp of the
    padded coordinate count."""
    plan, ds = plan8, ds8
    dp = plan.dp
    core_rep, core_sh = adam_cores

    s_rep = core_rep.init_state(jax.random.key(0))
    s_sh = core_sh.init_state(jax.random.key(0))
    for a, b in zip(_leaves(s_rep), _leaves(s_sh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # Layout: every non-scalar opt_state leaf is flat [D_pad] sharded over
    # dp with a 1/dp addressable shard per device.
    params_elems = sum(l.size for l in jax.tree.leaves(s_sh.params))
    opt_leaves = [l for l in jax.tree.leaves(s_sh.opt_state) if l.ndim >= 1]
    assert opt_leaves, "fedadam carries mu/nu state"
    sharded_elems = 0
    for leaf in opt_leaves:
        assert leaf.ndim == 1 and leaf.shape[0] % dp == 0
        shard = leaf.addressable_shards[0]
        assert shard.data.size == leaf.size // dp
        sharded_elems += leaf.size
    # mu + nu together: ~2x params (plus dp padding per leaf).
    assert sharded_elems >= 2 * params_elems

    for _ in range(3):
        s_rep, m_rep = core_rep.round_step(s_rep, ds)
        s_sh, m_sh = core_sh.round_step(s_sh, ds)
        np.testing.assert_allclose(float(m_rep.mean_loss),
                                   float(m_sh.mean_loss), rtol=1e-5)
    for a, b in zip(_leaves(s_rep), _leaves(s_sh)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=2e-5, atol=1e-6)


def test_sharded_update_composes_with_robust_aggregation(plan8, ds8):
    """Robust aggregate shards feed the sharded optimizer directly (same
    coordinate partition, no reconstruction collective): results match the
    replicated robust-aggregated update."""
    plan, ds = plan8, ds8
    defense = DefenseConfig(clip_norm=1.0, aggregator="trimmed_mean",
                            trim_fraction=0.2)
    core_rep = _build(plan)
    core_sh = _build(plan, shard_server_update=True)
    s_rep, _ = core_rep.round_step(
        core_rep.init_state(jax.random.key(0)), ds, defense=defense
    )
    s_sh, _ = core_sh.round_step(
        core_sh.init_state(jax.random.key(0)), ds, defense=defense
    )
    for a, b in zip(_leaves(s_rep), _leaves(s_sh)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=2e-5, atol=1e-6)


def test_shard_server_update_accepts_inert_param_specs(plan8, adam_cores,
                                                       ds8):
    """The mp x shard_server_update rejection is LIFTED (ISSUE 9): specs
    that shard nothing (mp=1 / all-replicated) leave the sharded-update
    build byte-identical to a spec-free one via the ``_tp_active`` gate.
    The really-sharded (dp x mp) composition is covered by
    tests/test_modelparallel.py."""
    from olearning_sim_tpu.engine.fedcore import FedCore

    plan = plan8
    core = adam_cores[1]  # spec-free shard_server_update donor
    specced = FedCore(
        core.apply_fn, core.init_params_fn, fedadam(0.1), plan,
        FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2,
                      shard_server_update=True),
        param_specs=jax.tree.map(
            lambda _: P(), jax.eval_shape(core.init_params_fn,
                                          jax.random.key(0))
        ),
    )
    assert not specced._tp_active
    s1 = core.init_state(jax.random.key(1))
    s2 = specced.init_state(jax.random.key(1))
    low1 = core.lower_round_step(s1, ds8).as_text()
    low2 = specced.lower_round_step(s2, ds8).as_text()
    assert low1 == low2


# --------------------------------------------------- checkpoint + resume
def _make_runner(core, ds, task_id, rounds, checkpointer=None):
    pop = DataPopulation(
        name="data_0", dataset=ds, device_classes=["c"],
        class_of_client=np.zeros(ds.num_clients, int),
        nums=[NUM_CLIENTS], dynamic_nums=[0],
    )
    return SimulationRunner(
        task_id=task_id, core=core, populations=[pop],
        operators=[OperatorSpec(name="train")], rounds=rounds,
        checkpointer=checkpointer,
    )


def test_sharded_opt_state_resumes_bitwise(tmp_path, plan8, ds8,
                                           adam_cores):
    """PR 4 crash-harness property with the sharded server update: a
    fresh-runner (supervisor-style) resume over the manifest-committed
    checkpoint finishes bitwise identical — params AND the flat-sharded
    optimizer state — to an uninterrupted run. One shared core: each
    runner owns its own state pytree, and reusing the compiled programs
    is exactly the production relaunch shape (and keeps tier-1 cheap)."""
    from olearning_sim_tpu.checkpoint import RoundCheckpointer

    ROUNDS = 6
    ds = ds8
    core = adam_cores[1]

    # Uninterrupted run.
    r_full = _make_runner(core, ds, "shard-ck", ROUNDS)
    r_full.run()

    # Interrupted at round 4, resumed by a FRESH runner over the same
    # checkpoint directory (the supervisor relaunch stand-in — exactly
    # test_crash_harness's recovery path, minus the subprocess).
    ck_a = RoundCheckpointer(str(tmp_path / "ck"), max_to_keep=4)
    _make_runner(core, ds, "shard-ck", 4, checkpointer=ck_a).run()
    ck_a.wait()
    assert os.path.isfile(
        str(tmp_path / "ck" / "manifests" / "step-3.json")
    ), "manifest commit (PR 4) must cover the sharded opt_state payload"
    ck_b = RoundCheckpointer(str(tmp_path / "ck"), max_to_keep=4)
    r_res = _make_runner(core, ds, "shard-ck", ROUNDS, checkpointer=ck_b)
    history = r_res.run()
    assert [h["round"] for h in history] == list(range(ROUNDS))

    for a, b in zip(_leaves(r_full.states["data_0"]),
                    _leaves(r_res.states["data_0"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    opt_full = jax.tree.leaves(jax.device_get(
        r_full.states["data_0"].opt_state))
    opt_res = jax.tree.leaves(jax.device_get(
        r_res.states["data_0"].opt_state))
    assert len(opt_full) == len(opt_res)
    for a, b in zip(opt_full, opt_res):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------- engine-params (task bridge)
def _bf16_config(mutate_fedcore=None):
    cfg_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "configs", "fedavg_mnist_mlp_bf16.json",
    )
    with open(cfg_path) as f:
        base = json.load(f)
    op_info = base["operatorflow"]["operators"][0]["logical_simulation"]
    params = json.loads(op_info["operator_params"])
    # Tiny shapes so bridge builds stay fast.
    params["model"]["overrides"] = {"hidden": [8], "num_classes": 3}
    params["fedcore"].update({"batch_size": 2, "max_local_steps": 1,
                              "block_clients": 1})
    params["data"] = {"synthetic": {"seed": 0, "n_local": 4,
                                    "num_classes": 3}}
    if mutate_fedcore:
        params["fedcore"].update(mutate_fedcore)
    op_info["operator_params"] = json.dumps(params)
    for td in base["target"]["data"]:
        td["total_simulation"]["nums"] = [4]
        td["total_simulation"]["dynamic_nums"] = [1]
        td["allocation"]["logical_simulation"] = [4]
    return base


def test_carry_dtype_and_shard_update_reach_fedcore_via_bridge():
    """The first-class bf16 carry: {"fedcore": {"carry_dtype": "bf16",
    "shard_server_update": true}} flows from task JSON into the built
    FedCoreConfig."""
    from olearning_sim_tpu.engine.task_bridge import (
        build_runner_from_taskconfig,
    )

    runner = build_runner_from_taskconfig(json.dumps(_bf16_config()))
    assert runner.core.config.carry_dtype == jnp.bfloat16
    assert runner.core.config.shard_server_update is True


def test_malformed_fedcore_params_rejected_at_submit():
    """Typos / wrong-typed fedcore knobs (incl. the new carry_dtype) fail
    at submit validation, never mid-round — and the shipped bf16 config
    stays valid."""
    from olearning_sim_tpu.taskmgr.codecs import json2taskconfig
    from olearning_sim_tpu.taskmgr.validation import validate_task_parameters

    for bad in (
        {"carry_dtype": "int32"},       # precision knob, not an int dtype
        {"carry_dtype": "nope"},        # not a dtype at all
        {"cary_dtype": "bf16"},         # typo'd key
        {"batch_size": 0},              # must be >= 1
        {"sample_mode": 7},             # wrong type
    ):
        tj = _bf16_config(mutate_fedcore=bad)
        ok, msg = validate_task_parameters(json2taskconfig(json.dumps(tj)))
        assert not ok and "fedcore" in msg, (bad, msg)

    cfg_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "configs", "fedavg_mnist_mlp_bf16.json",
    )
    with open(cfg_path) as f:
        ok, msg = validate_task_parameters(json2taskconfig(f.read()))
    assert ok, msg


# --------------------------------------------------------- compile cache
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CACHE_CHILD = """
import json
import jax
from olearning_sim_tpu.engine.compile_cache import (
    cache_stats, enable_compile_cache,
)
enabled = enable_compile_cache()
import jax.numpy as jnp
x = jnp.arange(64.0).reshape(8, 8)
y = jax.jit(lambda a: (a @ a.T).sum())(x)
float(y)
print("STATS " + json.dumps({
    **cache_stats(), "enabled": enabled,
    "config_dir": jax.config.jax_compilation_cache_dir,
}), flush=True)
"""


def _run_cache_child(cache_dir, code=_CACHE_CHILD):
    """One fresh 1-device CPU process; ``cache_dir`` (or None) is what the
    launcher puts in JAX_COMPILATION_CACHE_DIR."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    for name in ("OLS_COMPILE_CACHE", "XLA_FLAGS",
                 "JAX_COMPILATION_CACHE_DIR"):
        env.pop(name, None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=240, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("STATS ")][-1]
    return json.loads(line[len("STATS "):])


_PLACEMENT_CHILD = """
import json
import jax
from olearning_sim_tpu.engine import compile_cache as cc
updates = []
real_update = jax.config.update
jax.config.update = lambda name, value: (updates.append(name),
                                         real_update(name, value))[1]
enabled = cc.enable_compile_cache()
print("STATS " + json.dumps({
    "enabled": enabled, "updates": updates,
    "config_dir": jax.config.jax_compilation_cache_dir,
    "default_dir": cc.default_cache_dir(),
}), flush=True)
"""


def test_compile_cache_placed_from_outside(tmp_path):
    """One rule, one name. With JAX_COMPILATION_CACHE_DIR set, jax already
    has the directory and the program sets none in code (only the two
    thresholds); unset, the cache goes to the fixed
    ``artifacts/xla_compile_cache`` inside the checkout."""
    outside = str(tmp_path / "placed_by_launcher")
    got = _run_cache_child(outside, _PLACEMENT_CHILD)
    assert got["enabled"] == outside == got["config_dir"]
    assert "jax_compilation_cache_dir" not in got["updates"]
    assert sorted(got["updates"]) == [
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    ]

    got = _run_cache_child(None, _PLACEMENT_CHILD)
    fixed = os.path.join(REPO, "artifacts", "xla_compile_cache")
    assert got["enabled"] == fixed == got["config_dir"] == got["default_dir"]


def test_compile_cache_disable_switch(monkeypatch):
    """OLS_COMPILE_CACHE=0 turns the whole feature off."""
    from olearning_sim_tpu.engine import compile_cache as cc

    monkeypatch.setenv("OLS_COMPILE_CACHE", "0")
    monkeypatch.setitem(cc._state, "dir", None)
    assert cc.enable_compile_cache() is None
    assert cc.enabled_dir() is None


@pytest.mark.slow
def test_compile_cache_second_process_hits(tmp_path):
    """Two processes sharing the cache directory their launcher named: the
    first records a miss (entry written), the second a hit (entry
    deserialized, no compile). Slow-marked (two compiling jax processes);
    on the chip the same property is chip_smoke.py's second run."""
    cache_dir = str(tmp_path / "xla_cache")
    first = _run_cache_child(cache_dir)
    assert first["misses"] >= 1, first
    assert os.listdir(cache_dir), "no persistent cache entries written"
    second = _run_cache_child(cache_dir)
    assert second["hits"] >= 1, second
