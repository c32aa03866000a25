"""A second configuration arrives as new files and entries only, rehearsed
on the CPU: ``rehearsal.write`` lands the stand-in of ``data/rehearsal/``
(the registry's ``mlp2`` on dense float rows, FedAvg with its stateless
server step, a traffic mix with no deviceflow strategy and no evaluate
operator, one per-layer metric with a ``workloads`` list) beside a copy of
the real manifest, and the whole harness runs its cell: the first run of
the check's stateless-server branch and of a model that is no text encoder.
``test_benchmark_manifest.py`` holds the same tree to the manifest's
contract.

CPU runs: every number here is a count or a correctness fact, never a
speed."""

import filecmp
import json
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import config_contract
import rehearsal
from benchmark import check, harness, manifest, program_spans
from benchmark.reference import fedround

CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
SEED = 2**31 + 27
OURS = ["BENCHMARK.json", "benchmark", "tests/benchmark"]


def _git_status():
    try:
        proc = subprocess.run(
            ["git", "status", "--porcelain", "--", *OURS], cwd=manifest.ROOT,
            capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        return None
    return proc.stdout if proc.returncode == 0 else None


@pytest.fixture(scope="module")
def status_before():
    return _git_status()


@pytest.fixture(scope="module")
def path(status_before, tmp_path_factory):
    return rehearsal.write(str(tmp_path_factory.mktemp("second")))


@pytest.fixture(scope="module")
def sound(path):
    """One sound run of the stand-in's cell; its check is read a second
    time against a reference with the last local step left out."""
    return harness.run_cell(rehearsal.CELL, SEED, 0.3, False,
                            manifest_path=path, device=CPU, plant=True)


def test_the_second_configuration_is_new_files_and_entries_only(path):
    root = os.path.dirname(path)
    real = json.load(open(manifest.MANIFEST))
    doc = json.load(open(path))
    brought = json.load(open(os.path.join(rehearsal.DATA, "entries.json")))
    for key, value in real.items():
        if key in brought:       # the real entries, then the stand-in's
            assert doc[key] == value + brought[key], key
        else:
            assert doc[key] == value, key
    files = sorted(os.path.relpath(os.path.join(d, f), root)
                   for d, _, names in os.walk(root) for f in names)
    copied = sorted(
        [c["file"] for c in real["configs"]]
        + ["benchmark/traffic/%s.json" % t
           for t in {w["traffic"] for w in real["workloads"]}])
    for rel in copied:           # what the benchmark had, byte for byte
        assert filecmp.cmp(os.path.join(root, rel),
                           os.path.join(manifest.ROOT, rel), shallow=False)
    assert [f for f in files if f not in copied] == [
        "BENCHMARK.json", "benchmark/configs/mlp2_standin.json",
        "benchmark/layer_metrics/runner.rounds_in_window.py",
        "benchmark/reference/mlp2.py", "benchmark/traffic/16_full.json"]
    # Only the stand-in's cell reports the metric it brought.
    for w in doc["workloads"]:
        names = [m["name"] for m in
                 manifest.load_cell(w["name"], path).per_layer]
        assert ("runner.rounds_in_window" in names) == (
            w["name"] == rehearsal.CELL)


def test_the_stand_ins_model_is_what_its_file_states(path):
    """Its own contract, as a configuration's ``test_config_<name>.py``
    would hold it: the tree, the sizes, the FLOPs its reference counts."""
    from olearning_sim_tpu.models import get_model

    cell = manifest.load_cell(rehearsal.CELL, path)
    stated = cell.config["model"]
    task_model = manifest.engine_params(
        manifest.compose_task(cell, 1))["model"]
    spec = get_model(task_model["name"])
    assert spec.input_dtype == np.float32           # no tokens, no embedding
    assert task_model["input_shape"] == stated["input_shape"]
    shapes = config_contract.init_shapes(spec, task_model)
    (hidden,) = stated["hidden"]
    assert shapes == {
        "Dense_0/kernel": (stated["input_size"], hidden),
        "Dense_0/bias": (hidden,),
        "Dense_1/kernel": (hidden, stated["num_classes"]),
        "Dense_1/bias": (stated["num_classes"],)}
    reference = manifest.find_module("reference", "mlp2", cell.files_root)
    fc0, fc1 = reference.layers(stated)
    assert (fc0.macs, fc0.input_grad) == (32 * 64, False)
    assert (fc1.macs, fc1.input_grad) == (64 * 4, True)


def test_the_stand_ins_reference_matches_the_flax_model(path):
    """Within bfloat16: the program's MLP casts its input and hidden layer
    to bfloat16 and cannot be asked for float32."""
    from olearning_sim_tpu.models import get_model

    cell = manifest.load_cell(rehearsal.CELL, path)
    ref = manifest.find_module("reference", "mlp2", cell.files_root)
    model = get_model("mlp2").build(hidden=[64], num_classes=4)
    rng = np.random.default_rng(5)
    x = rng.standard_normal((12, 32)).astype(np.float32)
    y = rng.integers(0, 4, 12)
    sw = np.bincount(rng.integers(0, 12, 8), minlength=12) / np.float32(8)
    params = model.init(jax.random.key(0), jnp.asarray(x[:1]))["params"]

    def loss_fn(p):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            model.apply({"params": p}, jnp.asarray(x)), jnp.asarray(y))
        return (jnp.asarray(sw) * ce).sum()

    want_loss, want = jax.jit(jax.value_and_grad(loss_fn))(params)
    loss, grads = ref.loss_and_grad(ref.prepare(check.flatten(params)),
                                    x, y, sw)
    assert loss == pytest.approx(float(want_loss), rel=2e-2)
    worst = check.worst_leaf({k: np.asarray(v) for k, v in grads.items()},
                             check.flatten(want))
    assert worst["rel_l2"] < 0.05, worst


def test_the_stand_in_runs_the_whole_path_and_is_correct(sound):
    run, result = sound, sound.result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == len(run.ctx.window.rounds) >= 1
    assert set(result["metrics"]) == {"device_rounds_per_s", "round_s.p50",
                                      "setup_s"}
    # No strategy withholds anyone and nothing is evaluated.
    for rec in run.ctx.history:
        assert set(rec) >= {"round", "train"} and "evaluate" not in rec
        assert rec["train"]["data_0"]["clients_trained"] == 16
    assert run.ctx.device_rounds == 16 * result["attempted"]
    # The check took the stateless branch: no server memory to start from,
    # the pseudo-gradient is the parameters' change itself.
    checked = run.checks[0]
    assert checked.correct and len(checked.sample) == 8
    assert checked.detail["server_count"] is None
    assert checked.detail["local_steps"] == 3
    assert checked.numbers["pseudo_grad_global_rel_l2"] == (
        checked.numbers["param_delta_global_rel_l2"])
    assert set(checked.limits) == set(checked.numbers)
    for name, limit in checked.limits.items():
        assert checked.numbers[name] <= limit, name
    # Every number compared stands beside its limit in what run.py prints.
    compared = harness.compared(run)
    assert list(compared)[-1] == "failed_rounds"
    assert compared["failed_rounds"] == {"value": 0, "limit": 0}
    assert {k: v["limit"] for k, v in compared.items()
            if k != "failed_rounds"} == checked.limits
    json.dumps(compared)


def test_the_stand_ins_per_layer_metrics_are_numbers(sound):
    """Readers that need no device trace, on a run with no evaluate
    operator and no strategy: a number each, never nothing."""
    run = sound
    metrics = harness.assemble(run.ctx, run.verdict, run.checks[0],
                               trace=True)["metrics"]
    assert metrics["runner.rounds_in_window"]["value"] == len(
        run.ctx.window.rounds)
    # 12 local rows for a batch of 8, every row computed every step: the
    # multiplicity side of the minibatch rule. (The share itself also
    # counts the rows that pad 16 clients to the test mesh's 8 devices.)
    counted = program_spans.task_spans(run.ctx)["round.train.host_transfer"]
    assert {(s.attrs["samples_needed_per_step"],
             s.attrs["samples_computed_per_step"]) for s in counted} == {
                 (8, 12)}
    assert 0 < metrics["round_program.useful_work_share"]["value"] <= (
        100 * 8 / 12 + 1e-9)
    assert metrics["runner.eval_upload_ms"]["value"] == 0.0
    for name in ("runner.select.compile_trace_ms", "runner.select.place_ms",
                 "runner.select_ms.max", "runner.host_share",
                 "startup.window_compiles", "bridge.build_s"):
        assert metrics[name]["value"] >= 0.0, name
    mfu = manifest.find_module("layer_metrics", "round_program.mfu")
    needed = mfu.needed_flops(run.ctx)
    assert needed["evaluate"] == 0.0
    assert needed["train"] == 16 * 3 * 8 * (2 * 32 * 64 * 2 + 2 * 64 * 4 * 3)


def test_the_check_rejects_the_stand_ins_bf16_carry(path):
    run = harness.run_cell(rehearsal.CELL, 5, 0.3, False, manifest_path=path,
                           device=CPU,
                           fedcore_overrides={"carry_dtype": "bf16"})
    assert run.result["failed"] == 0          # it runs fine, and is wrong
    assert run.result["correct"] is False
    limits = run.checks[0].limits
    assert run.checks[0].numbers["pseudo_grad_global_rel_l2"] > (
        1.5 * limits["pseudo_grad_global_rel_l2"])


def test_the_check_rejects_a_left_out_local_step(path, sound, monkeypatch):
    """The reference takes one local step fewer than the program: every
    round completes and ``correct`` is false. (The planted reading of the
    sound run is the same comparison without a second run.)"""
    planted = sound.checks[0].detail["planted"]
    assert set(planted) == {"last_step_dropped"}    # no server memory to fault
    limit = sound.checks[0].limits["pseudo_grad_global_rel_l2"]
    assert planted["last_step_dropped"]["pseudo_grad_global_rel_l2"] > (
        2 * limit)
    whole = fedround.local_sgd

    def short(*args, steps, **kwargs):
        return whole(*args, steps=steps - 1, **kwargs)

    monkeypatch.setattr(fedround, "local_sgd", short)
    run = harness.run_cell(rehearsal.CELL, SEED, 0.3, False,
                           manifest_path=path, device=CPU)
    assert run.result["failed"] == 0 and run.result["attempted"] >= 1
    assert run.result["correct"] is False
    assert run.checks[0].numbers["pseudo_grad_global_rel_l2"] > 2 * limit


def test_the_rehearsal_leaves_the_checkout_as_it_found_it(
        status_before, sound):
    """Everything the rehearsal wrote is under pytest's temporary
    directory: git sees the benchmark's files as it did before."""
    if status_before is None:
        pytest.skip("not a git checkout")
    assert _git_status() == status_before
