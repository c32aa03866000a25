"""The harness end to end on the CPU: ``run.py`` refuses to measure without a
TPU; a tiny preset that lives here (never in BENCHMARK.json) drives the
whole harness through the real session — submit, scheduler, launcher, task
bridge, runner, FedCore, stopTask, check round; the check rejects the program's lower-precision path and a broken timed
path; and a configuration, a traffic mix and a per-layer metric are added as
new files and entries only.

CPU runs: every number here is a count or a correctness fact, never a
speed. The harness's look for a chip is skipped through ``device=``, which
``run.py`` never passes."""

import gzip
import json
import os
import subprocess
import sys

import pytest

import tiny_preset
from benchmark import harness, manifest, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_run_py_refuses_the_cpu_backend_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"),
         "--workload", json.load(open(manifest.MANIFEST))["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=manifest.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout and "status=" not in proc.stdout


def test_run_py_fails_cleanly_on_an_unknown_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"),
         "--workload", "no.such_cell", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=manifest.ROOT)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout


SEED = 2**31 + 11


@pytest.fixture(scope="module")
def tiny_path(tmp_path_factory):
    return tiny_preset.write(str(tmp_path_factory.mktemp("tiny")),
                             "distilbert_sent140", "128_spike",
                             extra_metric=True)


@pytest.fixture(scope="module")
def tiny_run(tiny_path):
    """One sound run of the tiny cell (FedAdam under the dispatch trace),
    with a throw-away per-layer metric added beside the built-in ones, and
    a second check on the same runner with the server-step faults planted."""
    return harness.run_cell("tiny.cell", SEED, 0.5, False,
                            manifest_path=tiny_path, device=CPU,
                            more_check_seeds=[2], plant=True)


def test_tiny_cell_runs_the_whole_path_and_is_correct(tiny_run):
    run, result = tiny_run, tiny_run.result
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    # (How many rounds fit is the machine's speed, not a fact to assert.)
    assert result["attempted"] == len(run.ctx.window.rounds) >= 1
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"device_rounds_per_s", "round_s.p50",
                                      "setup_s"}
    for metric in result["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    # Whole rounds only; the window is at least as long as asked and
    # shorter than one more round.
    assert run.ctx.window.rounds[0].idx == run.ctx.cell.traffic["warmup_rounds"]
    longest = max(r.seconds for r in run.ctx.window.rounds)
    assert 0.5 <= run.ctx.window.seconds < 0.5 + 2 * longest
    assert run.ctx.setup_s > 0 and run.ctx.t_running >= run.ctx.t_submitted
    json.dumps(result)                       # the last line is plain JSON
    # The trace withholds and drops clients: fewer than all, as released.
    rounds = {r["round"]: r["train"]["data_0"] for r in run.ctx.history}
    inside = [rounds[r.idx] for r in run.ctx.window.rounds]
    assert all(t["clients_trained"] == t["released"] <= 16 for t in inside)
    assert any(t["clients_trained"] < 16 for t in rounds.values())
    assert run.ctx.device_rounds == sum(t["clients_trained"] for t in inside)


def test_the_check_starts_from_the_windows_state_with_adams_memory(tiny_run):
    first, second = tiny_run.checks
    assert first.correct and second.correct
    assert first.sample != second.sample
    assert first.numbers["clients_trained_gap"] == 0
    assert any("limit=" in line for line in first.lines())
    # All local steps, from the state the window ended in: the server's
    # step count is the number of rounds the task ran, and the second check
    # starts where the first ended.
    assert first.detail["local_steps"] == 2
    # The 2-class head bias is read apart in both families (check.py's rule
    # on leaves of few elements), and is no worst leaf.
    for family in ("pseudo_grad", "param_delta"):
        assert first.detail[family + "_few_elements_gap"] >= 0.0
        assert first.detail["worst_leaf"][family] != "Dense_0/bias"
    assert first.detail["server_count"] == first.detail["round_idx"] >= len(
        tiny_run.ctx.window.rounds) + 1
    assert second.detail["server_count"] == first.detail["server_count"] + 1
    # A server step with a wrong b2, a wrong b1 or its memory dropped is
    # far over the parameter-delta limit, every time. (A count that is one
    # behind is read too, and moves the step by a few percent only: the
    # bias corrections hardly change from one count to the next.)
    limit = first.limits["param_delta_global_rel_l2"]
    for checked in (first, second):
        assert checked.numbers["param_delta_global_rel_l2"] < limit / 3
        planted = checked.detail["planted"]
        assert set(planted) == {"b2_0.999", "b1_0.8", "memory_dropped",
                                "count_not_advanced", "last_step_dropped"}
        # (b1's reading follows the round the host's speed lets the window
        # end in: 0.133 after 31 rounds, 0.245 after 32, so it is held to 3.)
        for name, times in (("b2_0.999", 5), ("b1_0.8", 3),
                            ("memory_dropped", 5)):
            assert planted[name]["param_delta_global_rel_l2"] > (
                times * limit), name
        assert planted["last_step_dropped"]["pseudo_grad_global_rel_l2"] > (
            2 * first.limits["pseudo_grad_global_rel_l2"])


def test_new_config_mix_and_metric_are_files_and_entries_only(tiny_path,
                                                              tiny_run):
    run = tiny_run
    root = os.path.dirname(tiny_path)
    added = sorted(os.path.relpath(os.path.join(d, f), root)
                   for d, _, files in os.walk(root) for f in files)
    assert added == ["BENCHMARK.json", "bench/configs/tiny.json",
                     "bench/layer_metrics/extra.rounds_seen.py",
                     "bench/traffic/cell.json"]
    # The traced result line carries the new metric beside the built-in
    # ones, read by the file that was added.
    cell = manifest.load_cell("tiny.cell", tiny_path)
    assert "extra.rounds_seen" in [m["name"] for m in cell.per_layer]
    with gzip.open(os.path.join(HERE, "data", "tiny_round.xplane.pb.gz")) as f:
        from jax.profiler import ProfileData

        run.ctx.trace = trace_reduce.reduce_profile(
            ProfileData.from_serialized_xspace(f.read()))
    run.ctx.sync_host_s = run.ctx.window.open
    run.ctx.trace_rounds = 9                 # the recorded stretch's
    run.ctx.peaks = harness.load_peaks()["TPU v5 lite"]
    traced = harness.assemble(run.ctx, run.verdict, run.checks[0], trace=True)
    assert set(traced) == RESULT_KEYS | {"breakdown", "device_scopes"}
    names = {m["name"] for m in cell.per_layer}
    assert set(traced["metrics"]) == names
    assert traced["metrics"]["extra.rounds_seen"]["value"] == len(run.ctx.rounds)
    assert traced["metrics"]["startup.window_compiles"]["value"] == 0
    assert traced["metrics"]["round_program.device_ms"]["value"] == (
        pytest.approx(1e3 * run.ctx.trace.busy_s / 9))
    assert 0 < traced["metrics"]["round_program.mfu"]["value"] < 100
    assert 0 <= traced["metrics"]["runner.host_share"]["value"] <= 100
    assert traced["device"]["busy_s"] > 0 and traced["device"]["window_s"] > 0
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    for key in ("device_ops", "idle_gaps"):
        assert 1 <= len(traced["breakdown"][key]) <= 10
    # The recorded trace has no op_name: all of it is unscoped, and the
    # by-scope metrics every cell reports are numbers all the same.
    assert traced["device_scopes"] == [
        [trace_reduce.UNSCOPED, pytest.approx(run.ctx.trace.op_seconds)]]
    assert traced["metrics"]["round_program.scoped_share"]["value"] == 0.0
    assert traced["metrics"]["client_train.device_ms"]["value"] == 0.0
    run.ctx.trace = None                    # readers with nothing to read
    untraced = harness.assemble(run.ctx, run.verdict, run.checks[0], trace=True)
    assert "round_program.mfu" not in untraced["metrics"]
    assert "breakdown" not in untraced and "device_scopes" not in untraced


def test_the_check_rejects_carry_dtype_bf16(tiny_path):
    run = harness.run_cell("tiny.cell", 2, 0.3, False, manifest_path=tiny_path,
                           device=CPU, fedcore_overrides={"carry_dtype": "bf16"})
    assert run.result["failed"] == 0          # it runs fine, and is wrong
    assert run.result["correct"] is False
    # Over the preset's own limit, wherever the host's speed lets the 0.3 s
    # window end (0.078 after 10 rounds on a loaded host, 0.106-0.130 after
    # 19-20: PERF.md section 7 item 6(d)), not over a second threshold.
    checked = run.checks[0]
    assert checked.numbers["pseudo_grad_global_rel_l2"] > (
        checked.limits["pseudo_grad_global_rel_l2"])


def test_a_broken_timed_path_comes_out_not_correct(tiny_path, tiny_run,
                                                   monkeypatch):
    """The round step returns its parameters unchanged: rounds complete,
    losses are finite, counts are right — and ``correct`` is false. Run on
    the sound run's seed: participation and the first round's loss are
    functions of the seed, so they come out the same twice."""
    import jax
    import jax.numpy as jnp

    from olearning_sim_tpu.engine.fedcore import FedCore

    original = FedCore.round_step

    def stuck(self, state, *args, **kwargs):
        kept = jax.tree.map(jnp.copy, state.params)
        new_state, metrics = original(self, state, *args, **kwargs)
        return new_state.replace(params=kept), metrics

    monkeypatch.setattr(FedCore, "round_step", stuck)
    run = harness.run_cell("tiny.cell", SEED, 0.3, False,
                           manifest_path=tiny_path, device=CPU)
    assert run.result["failed"] == 0 and run.result["attempted"] >= 1
    assert run.result["correct"] is False
    assert run.checks[0].numbers["param_delta_global_rel_l2"] == (
        pytest.approx(1.0))
    first = [r["train"]["data_0"] for r in tiny_run.ctx.history]
    again = [r["train"]["data_0"] for r in run.ctx.history]
    n = min(len(first), len(again))
    assert n >= 2                      # the warm-up round and the window's
    assert ([t["clients_trained"] for t in first[:n]]
            == [t["clients_trained"] for t in again[:n]])
    assert first[0]["mean_loss"] == again[0]["mean_loss"]


def test_require_device_refuses_what_it_was_not_given():
    with pytest.raises(harness.BenchmarkError, match="no TPU"):
        harness.require_device(1)
