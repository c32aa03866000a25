"""``phi4flash_vp8``'s own contract: no width in its file differs from the
catalog's config of Phi-4-mini-flash-reasoning, the cut it states is the one
the program is given, what the config does not carry is named under
``assumed``, the model the program builds for its task is the tree and the
parameter total the file's arithmetic gives, its reference counts the FLOPs
the issue's arithmetic gives (the window's pairs, not the causal half), and
its tiny preset runs the whole path on the CPU: task_type -> bridge ->
runner -> FedCore -> counters -> readers, with the control and the model's
own planted faults refused."""

import json
import os

import numpy as np
import pytest

import config_contract
import tiny_preset
from benchmark import (flops, harness, manifest, program_spans,
                       scope_metrics)

NAME = "phi4flash_vp8"
CELL = "phi4flash_vp8.8_silo_2k"
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
# https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/
# config.json, the catalog's ``config`` of it, key for key.
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
CUT = {"vocab_size": 25008, "max_position_embeddings": 2048}
# The family's sizes the config does not carry (``assumed.mamba``).
FAMILY = {"d_inner": 5120, "d_state": 16, "d_conv": 4, "expand": 2,
          "dt_rank": 160, "head_dim": 64}


def test_no_width_differs_from_the_published_config():
    config, _, _ = config_contract.load(NAME)
    assert config["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    assert len(config["source"]) <= 200
    for where in (config, config["model"]):     # top level = model block
        for key, value in PUBLISHED.items():
            assert where[key] == CUT.get(key, value), key
        for key, value in FAMILY.items():
            assert where[key] == value, key
        assert where["num_layers"] == 5 and where["layer_slice"] == [15, 19]
        assert where["vocab_size_published"] == 200064
        assert where["max_position_embeddings_published"] == 262144
    assert FAMILY["d_inner"] == FAMILY["expand"] * PUBLISHED["hidden_size"]
    assert FAMILY["dt_rank"] == -(-PUBLISHED["hidden_size"] // 16)
    assert FAMILY["head_dim"] * PUBLISHED["num_attention_heads"] == 2560
    # Every key that differs from the source is a stated cut, and none of
    # them is a width: six keys.
    assert config["reduced"] == [
        "num_layers", "vocab_size", "max_position_embeddings",
        "block_clients", "num_request", "clients"]
    assert set(CUT) <= set(config["reduced"])
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert "14.6 GB" in config["reduced_why"]["num_layers"]
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["why"]) <= 200
    for key in ("deployment", "reduced_why", "assumed", "guarantees",
                "precision"):
        assert config[key], key
    assert "divided by rows over 8 chips" in config["deployment"]
    assert "layer_slice [15, 19]" in config["deployment"]
    assert "carries a decay" in config["precision"]
    # What the config does not carry, each item by name.
    assert {"layer_kinds", "mamba", "gated_memory_unit",
            "differential_attention", "bias", "positions", "initializer",
            "scan_form", "activation_memory"} <= set(config["assumed"])
    assert config["algorithm"]["name"] == "fedavg"
    assert config["algorithm"]["server_lr"] == 1.0      # PERF.md 7.6a
    limits = config["check"]["limits"]
    assert limits["clients_trained_gap"] == 0 and len(limits) >= 5
    # Every limit between the largest sound reading and the smallest
    # faulty one, the file's own. Three are the accepted decoders'; the norm
    # gap's is not, and the file names it with its readings (PERF.md
    # section 2). The bfloat16 carry hardly moves the loss, so its loss gap
    # is kept under a name of its own and judges nothing.
    assert config["algorithm"]["local_lr"] == 0.02 and "0.1" in config[
        "algorithm_why"]
    params = config["task"]["operatorflow"]["operators"][0][
        "logical_simulation"]["operator_params"]
    assert params["algorithm"]["local_lr"] == config["algorithm"]["local_lr"]
    readings = config["check"]["readings"]
    faulty = [v for k, v in readings.items()
              if k.startswith(("control_", "planted_"))]
    assert len(faulty) == 5
    for name in ("client_loss_gap", "pseudo_grad_global_rel_l2",
                 "pseudo_grad_rel_l2", "pseudo_grad_norm_gap"):
        assert readings["sound_first_checks"][name][1] < limits[name] < min(
            r[name] for r in faulty if name in r), name
        twin = name.replace("pseudo_grad", "param_delta")
        assert limits[twin] == limits[name]
    assert (limits["client_loss_gap"], limits["pseudo_grad_global_rel_l2"],
            limits["pseudo_grad_rel_l2"]) == (0.001, 0.2, 0.5)
    assert "norm gap" in config["check"]["reason"]
    # A leaf left unchanged reads 1 on the norm gap: that limit is under it.
    assert limits["pseudo_grad_norm_gap"] < 1.0
    assert "PLACEHOLDER" not in json.dumps(config)


def test_the_program_is_given_the_cut_the_file_states():
    config, task_model, spec = config_contract.load(NAME)
    stated = config["model"]
    reference = manifest.find_module("reference", config["reference"])
    lo, hi = stated["layer_slice"]
    assert [reference.kind(i) for i in range(lo, hi + 1)] == stated[
        "layer_kinds"] == ["S", "M*", "F", "G", "C"]
    assert reference.FIRST_LAYER == lo == task_model["overrides"][
        "layer_slice"][0]
    assert reference.WINDOW == stated["sliding_window"] == 512
    assert hi - lo + 1 == stated["num_layers"]
    assert stated["vocab_size"] * 8 == stated["vocab_size_published"]
    assert stated["sequence_length"] % stated["sliding_window"] == 0
    # The task is a next-token task, on ids inside the vocabulary slice.
    data = config["task"]["target"]["data"][0]
    assert data["task_type"] == "next_token_prediction"
    params = config["task"]["operatorflow"]["operators"][0][
        "logical_simulation"]["operator_params"]
    assert params["model"]["name"] == "phi4flash"
    assert params["data"]["synthetic"]["vocab_size"] == stated["vocab_size"]
    assert params["data"]["eval_n"] == 16
    fed = params["fedcore"]
    assert fed["batch_size"] * stated["sequence_length"] == 4096
    assert (fed["batch_size"], fed["max_local_steps"]) == (
        config["algorithm"]["batch_size"], config["algorithm"]["local_steps"])
    assert set(fed) == {"batch_size", "max_local_steps", "eval_batch_size",
                        "step_unroll"}
    assert spec.vmap_clients is False
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "8_silo_2k"
    assert cell.traffic["fedcore"]["block_clients"] == 1
    assert (cell.traffic["clients"], cell.traffic["n_local"]) == (8, 12)
    assert cell.traffic["operators"] == ["train", "evaluate"]
    assert not cell.traffic.get("deviceflow")
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        why = next(w["why"] for w in json.load(f)["workloads"]
                   if w["name"] == CELL)
    assert "full attention carries 6x its share" in why and len(why) <= 200
    # At least these: a later PR appends its own.
    names = {m["name"] for m in cell.per_layer}
    assert {"phi4flash.selective_scan.device_ms",
            "phi4flash.mamba_projections.device_ms",
            "phi4flash.window_attention.device_ms",
            "phi4flash.full_attention.device_ms", "phi4flash.gmu.device_ms",
            "phi4flash.selective_scan_roofline",
            "phi4flash.window_attention_roofline",
            "phi4flash.window_attention.pairs_computed_over_needed",
            "device.hbm_data_gb", "device.hbm_state_gb",
            "device.hbm_program_gb", "round_program.mfu",
            "round_program.scoped_share"} <= names


def test_the_tree_and_the_parameter_total_from_shapes_alone():
    config, task_model, spec = config_contract.load(NAME)
    m = config["model"]
    shapes = config_contract.init_shapes(spec, task_model)
    W, Mi, Di, N, R, T = (m["hidden_size"], m["intermediate_size"],
                          m["d_inner"], m["d_state"], m["dt_rank"],
                          m["d_conv"])
    H, Hk, D = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    assert shapes["embed/embedding"] == (m["vocab_size"], W)
    assert "head" not in shapes                             # tied
    assert shapes["layers_0/attn/qkv_proj"] == (W, (H + 2 * Hk) * D) == (
        2560, 5120)
    assert shapes["layers_0/attn/qkv_bias"] == (5120,)
    assert shapes["layers_2/attn/out_proj"] == (H * D, W)
    assert shapes["layers_2/attn/out_bias"] == (W,)
    for leaf in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
        assert shapes[f"layers_4/attn/{leaf}"] == (D,)
    assert shapes["layers_4/attn/subln"] == (2 * D,)
    assert shapes["layers_4/attn/q_proj"] == (W, H * D)
    assert "layers_4/attn/qkv_proj" not in shapes   # no key, no value here
    assert shapes["layers_1/mamba/in_proj"] == (W, 2 * Di)
    assert shapes["layers_1/mamba/conv"] == (T, Di)
    assert shapes["layers_1/mamba/conv_bias"] == (Di,)
    assert shapes["layers_1/mamba/x_proj"] == (Di, R + 2 * N) == (5120, 192)
    assert shapes["layers_1/mamba/dt_proj"] == (R, Di)
    assert shapes["layers_1/mamba/A_log"] == (Di, N)
    assert shapes["layers_1/mamba/D"] == shapes[
        "layers_1/mamba/dt_bias"] == (Di,)
    assert shapes["layers_1/mamba/out_proj"] == (Di, W)
    assert shapes["layers_3/gmu/in_proj"] == (W, Di)
    assert shapes["layers_3/gmu/out_proj"] == (Di, W)
    for j in range(5):
        assert shapes[f"layers_{j}/mlp/w1"] == shapes[
            f"layers_{j}/mlp/w3"] == (W, Mi)
        assert shapes[f"layers_{j}/mlp/w2"] == (Mi, W)
        for norm in ("mixer_norm", "mlp_norm"):
            assert shapes[f"layers_{j}/{norm}/scale"] == shapes[
                f"layers_{j}/{norm}/bias"] == (W,)
    # The file's arithmetic (PERF.md section 4), every layer with its norms.
    mamba = (W * 2 * Di + T * Di + Di + Di * (R + 2 * N) + R * Di + Di
             + Di * N + Di + Di * W)
    attention = W * (H + 2 * Hk) * D + (H + 2 * Hk) * D + H * D * W + W + (
        4 * D + 2 * D)
    cross = 2 * (W * H * D + W) + 4 * D + 2 * D
    gmu, mlp, norms = 2 * W * Di, 3 * W * Mi, 4 * W
    assert (mamba, attention, cross, gmu, mlp, norms) == (
        41_241_600, 19_668_864, 13_112_704, 26_214_400, 78_643_200, 10_240)
    layers = [mixer + mlp + norms
              for mixer in (attention, mamba, attention, gmu, cross)]
    assert layers == [98_322_304, 119_895_040, 98_322_304, 104_867_840,
                      91_766_144] and sum(layers) == 513_173_632
    want = sum(layers) + m["vocab_size"] * W + 2 * W
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == want == 577_199_232
    assert total * 21 / 1e9 == pytest.approx(12.12, abs=0.01)
    # A sixth layer (14-19, a plain M before them) is what does not fit.
    assert (total + mamba + mlp + norms) * 21 / 1e9 == pytest.approx(
        14.6, abs=0.05)


def test_the_reference_counts_what_a_trained_token_needs():
    config, _, _ = config_contract.load(NAME)
    reference = manifest.find_module("reference", config["reference"])
    layers = reference.layers(config["model"])
    L = config["model"]["sequence_length"]
    by_name = {layer.name: layer for layer in layers}
    # 577 M weights a token less the table's lookup and the small leaves,
    # and the pairs of the three attentions.
    assert sum(layer.macs for layer in layers) / L == pytest.approx(
        596.3e6, rel=0.002)
    assert flops.train_flops(layers) / L == pytest.approx(3.578e9, rel=0.002)
    # The window layer counts its window's pairs, the others the causal half.
    window, causal = 917_760, L * (L + 1) // 2
    assert by_name["l15.scores"].macs == window * 40 * 64
    assert by_name["l15.context"].macs == window * 40 * 128
    for i in (17, 19):
        assert by_name[f"l{i}.scores"].macs == causal * 40 * 64
        assert by_name[f"l{i}.context"].macs == causal * 40 * 128
    assert by_name["l19.qkv"].macs == L * 2560 * 2560     # a query alone
    assert by_name["l16.selective_scan"].macs == L * 5120 * 16 * 2
    assert by_name["l18.gmu_in"].macs == by_name["l18.gmu_out"].macs == (
        L * 2560 * 5120)
    assert sum(1 for n in by_name if n.endswith(".mlp_in")) == 5
    assert by_name["head"].macs == (L - 1) * 2560 * 25008
    cell = manifest.load_cell(CELL)
    needed = flops.cell_round_flops(
        layers, manifest.engine_params(manifest.compose_task(cell, 1)),
        clients=8, evaluates=True)
    assert needed["train_samples"] == 8 * 2 * 2
    # 65,536 trained and 32,768 evaluated tokens: 273.6 TFLOP a round,
    # 1.389 s at the chip's 197 TFLOP/s.
    assert needed["total"] == pytest.approx(273.57e12, rel=0.001)


@pytest.fixture(scope="module")
def tiny_path(tmp_path_factory):
    return tiny_preset.write(str(tmp_path_factory.mktemp("tiny_phi4flash")),
                             NAME, "8_silo_2k")


@pytest.fixture(scope="module")
def tiny_run(tiny_path):
    # A window of 0.01 s closes at the first round start after its open:
    # one round, whatever the host's speed, so the check starts from the
    # state after the same rounds every time (PERF.md 7.6d).
    return harness.run_cell("tiny.cell", 2**31 + 34, 0.01, False,
                            manifest_path=tiny_path, device=CPU, plant=True)


def test_the_tiny_preset_rejects_carry_dtype_bf16(tiny_path):
    run = harness.run_cell("tiny.cell", 2**31 + 38, 0.01, False,
                           manifest_path=tiny_path, device=CPU,
                           fedcore_overrides={"carry_dtype": "bf16"})
    assert run.result["failed"] == 0 and run.result["correct"] is False


def test_the_tiny_preset_rejects_the_references_with_a_mechanism_left_out(
        tiny_path):
    """This model's own planted faults, as ``scripts/phi4flash_planted.py``
    plants them on the chip: the recurrence without its decay, differential
    attention without its second member. (The third, the S layer without its
    window, is no fault where a sequence is shorter than the published
    window, as the preset's 80 tokens are: the chip reads it.) A planted
    reading has to exceed the limit, not a multiple of it."""
    planted = manifest.load_module(
        os.path.join(os.path.dirname(manifest.HERE), "scripts"),
        "phi4flash_planted")
    assert sorted(planted.FAULTS) == ["decay", "lambda", "window"]
    sound, faulty = planted.run(
        "tiny.cell", 2**31 + 34, 0.01, ("decay", "lambda"),
        manifest_path=tiny_path, device=CPU)
    assert sound.correct is True and set(faulty) == {"decay", "lambda"}
    for fault, result in faulty.items():
        assert result.correct is False, fault
        assert result.numbers["pseudo_grad_rel_l2"] > sound.limits[
            "pseudo_grad_rel_l2"], fault


def test_the_tiny_preset_runs_the_whole_path_and_is_correct(tiny_run):
    run, result = tiny_run, tiny_run.result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == len(run.ctx.window.rounds) == 1
    rounds = {r["round"]: r for r in run.ctx.history}
    for r in run.ctx.window.rounds:
        assert rounds[r.idx]["train"]["data_0"]["clients_trained"] == 4
        evaluated = rounds[r.idx]["evaluate"]["data_0"]
        assert 0.0 <= evaluated["eval_acc"] <= 1.0
        assert np.isfinite(evaluated["eval_loss"])
    json.dumps(result)
    # A left-out local step is over the limit that catches it.
    checked = run.checks[0]
    dropped = checked.detail["planted"]["last_step_dropped"]
    assert dropped["pseudo_grad_rel_l2"] > checked.limits[
        "pseudo_grad_rel_l2"]


def test_the_scans_and_the_windows_counters_reach_their_readers(tiny_run):
    ctx = tiny_run.ctx
    ratio = manifest.find_module(
        "layer_metrics",
        "phi4flash.window_attention.pairs_computed_over_needed").read(ctx)
    # 80 tokens inside the published window of 512: one block, L x L scores
    # for the causal half.
    assert ratio == pytest.approx(80 * 80 / (80 * 81 / 2))
    spans = [s.attrs for name, found in program_spans.task_spans(ctx).items()
             if name.endswith(".host_transfer") for s in found
             if "sscan_tokens" in s.attrs]
    assert spans
    for attrs in spans:
        # Every resident client (the 4, and the padding up to the CPU
        # mesh's 8 devices) x 2 steps x 2 sequences x 80 tokens through one
        # scan layer (2 chunks of 64 a sequence) and one window layer.
        sequences = attrs["clients_resident"] * 2 * 2
        assert attrs["tokens_per_step"] == 2 * 80
        assert attrs["sscan_tokens"] == sequences * 80
        assert attrs["sscan_chunks"] == sequences * 2
        assert attrs["window_attn_pairs_needed"] == sequences * 80 * 81 // 2
        assert attrs["window_attn_pairs_computed"] == sequences * 80 * 80
    assert manifest.find_module(
        "layer_metrics", "round_program.useful_work_share"
    ).read(ctx) == pytest.approx(50.0)
    # No trace on the CPU: the by-scope readers leave their metrics out.
    assert scope_metrics.traced_round_counts(ctx) is None
    for name in ("phi4flash.selective_scan_roofline",
                 "phi4flash.window_attention_roofline",
                 "phi4flash.selective_scan.device_ms",
                 "phi4flash.mamba_projections.device_ms",
                 "phi4flash.window_attention.device_ms",
                 "phi4flash.full_attention.device_ms",
                 "phi4flash.gmu.device_ms"):
        assert manifest.find_module("layer_metrics", name).read(ctx) is None
