"""``lfm2_moe_ep8``'s own contract: no width in its file differs from the
catalog's config of LFM2-24B-A2B, the cut it states is the one the program
is given, the model the program builds for its task is the tree and the
parameter total the file's arithmetic gives, its reference counts the FLOPs
the issue's arithmetic gives, and its tiny preset runs the whole path on
the CPU: task_type -> bridge -> runner -> FedCore -> counters -> readers."""

import json

import numpy as np
import pytest

import config_contract
import tiny_preset
from benchmark import flops, harness, manifest

NAME = "lfm2_moe_ep8"
CELL = "lfm2_moe_ep8.8_silo_1k"
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
# https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json, the
# numbers at its top level (nested: layer_types, 10 full_attention among 40;
# rope_parameters).
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}
CUT = {"num_dense_layers": 1, "num_experts": 8, "vocab_size": 8192,
       "max_position_embeddings": 1024}


def test_no_width_differs_from_the_published_config():
    config, _, _ = config_contract.load(NAME)
    assert config["source"] == ("https://huggingface.co/LiquidAI/"
                                "LFM2-24B-A2B/blob/main/config.json")
    assert len(config["source"]) <= 200
    for where in (config, config["model"]):     # top level = model block
        for key, value in PUBLISHED.items():
            assert where[key] == CUT.get(key, value), key
        assert where["model_type"] == "lfm2_moe"
        assert where["rope_parameters"] == {"rope_theta": 1000000,
                                            "rope_type": "default"}
        assert len(where["layer_types"]) == 40
        assert where["layer_types"].count("full_attention") == 10
        assert where["num_layers"] == 5
        assert where["num_experts_published"] == 64
    # Every key that differs from the source is a stated cut, and none of
    # them is a width.
    assert set(CUT) | {"num_layers"} <= set(config["reduced"])
    for key in ("deployment", "reduced_why", "assumed", "guarantees",
                "precision"):
        assert config[key], key
    assert "eight chips share each layer" in config["deployment"]
    assert "router in float32" in config["precision"]
    assert config["algorithm"]["name"] == "fedavg"
    assert config["algorithm"]["server_lr"] == 1.0      # PERF.md 7.6a


def test_the_program_is_given_the_cut_the_file_states():
    config, task_model, spec = config_contract.load(NAME)
    stated = config["model"]
    lo, hi = stated["layer_slice"]
    run_types = stated["layer_types"][lo:hi]
    assert run_types == ["conv", "full_attention", "conv", "conv", "conv"]
    assert task_model["overrides"]["layer_types"] == run_types
    assert len(run_types) == stated["num_layers"]
    assert task_model["overrides"]["rope_theta"] == stated[
        "rope_parameters"]["rope_theta"]
    assert stated["held_experts"] == list(range(stated["num_experts"]))
    assert stated["head_size"] * stated["num_attention_heads"] == stated[
        "hidden_size"]
    # The task is a next-token task, on ids inside the vocabulary slice.
    data = config["task"]["target"]["data"][0]
    assert data["task_type"] == "next_token_prediction"
    params = config["task"]["operatorflow"]["operators"][0][
        "logical_simulation"]["operator_params"]
    assert params["data"]["synthetic"]["vocab_size"] == stated["vocab_size"]
    assert params["fedcore"]["batch_size"] * stated[
        "sequence_length"] == 8192
    assert spec.vmap_clients is False
    cell = manifest.load_cell(CELL)
    assert cell.traffic["fedcore"]["block_clients"] == 1
    assert (cell.traffic["clients"], cell.traffic["n_local"]) == (8, 24)
    assert cell.traffic["operators"] == ["train", "evaluate"]
    assert not cell.traffic.get("deviceflow")
    assert [m["name"] for m in cell.per_layer if m["layer"] == "Expert layer"
            ] == ["moe.dropped_assignments", "moe.expert_load_max_over_mean"]


def test_the_tree_and_the_parameter_total_from_shapes_alone():
    config, task_model, spec = config_contract.load(NAME)
    m = config["model"]
    shapes = config_contract.init_shapes(spec, task_model)
    W, I, M = (m["hidden_size"], m["intermediate_size"],
               m["moe_intermediate_size"])
    kv = m["num_key_value_heads"] * m["head_size"]
    held, routed = m["num_experts"], m["num_experts_published"]
    assert shapes["embed/embedding"] == (m["vocab_size"], W)
    assert shapes["layers_0/conv/in_proj"] == (W, 3 * W)
    assert shapes["layers_0/conv/conv"] == (m["conv_L_cache"], W)
    assert shapes["layers_0/mlp/w1"] == (W, I)
    assert shapes["layers_1/attn/k_proj"] == (W, kv)
    assert shapes["layers_1/attn/q_norm/scale"] == (m["head_size"],)
    assert shapes["layers_1/moe/gate"] == (W, routed)
    assert shapes["layers_1/moe/expert_bias"] == (routed,)
    assert shapes["layers_4/moe/expert_w1"] == (held, W, M)
    assert shapes["layers_4/moe/expert_w2"] == (held, M, W)
    assert not any(k.endswith("bias") and "expert_bias" not in k
                   for k in shapes)                     # conv_bias: false
    assert "head" not in {k.split("/")[0] for k in shapes}   # tied
    # The file's arithmetic (PERF.md section 4), norms and taps included.
    conv = 4 * W * W + m["conv_L_cache"] * W
    attn = 2 * W * W + 2 * W * kv + 2 * m["head_size"]
    moe = W * routed + routed + held * 3 * W * M
    want = (2 * W + conv + 3 * W * I                    # dense layer
            + 2 * W + attn + moe                        # attention layer
            + 3 * (2 * W + conv + moe)                  # conv expert layers
            + m["vocab_size"] * W + W)                  # embedding, norm
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == want == 469_285_248
    assert total * 16 / 1e9 == pytest.approx(7.5, abs=0.05)


def test_the_reference_counts_what_a_trained_token_needs():
    config, _, _ = config_contract.load(NAME)
    reference = manifest.find_module("reference", config["reference"])
    layers = reference.layers(config["model"])
    L = config["model"]["sequence_length"]
    macs = sum(layer.macs for layer in layers) / L
    assert macs == pytest.approx(188e6, rel=0.01)       # forward, a token
    assert flops.train_flops(layers) / L == pytest.approx(1.13e9, rel=0.01)
    experts = [layer for layer in layers if layer.name.endswith(".experts")]
    assert len(experts) == 4
    # Half an expert a token: 4 chosen x 8 held / 64 routed.
    assert experts[0].macs == L * 0.5 * 3 * 2048 * 1536
    scores = next(layer for layer in layers if layer.name.endswith(".scores"))
    assert scores.macs < L * L * 2048 * 0.51            # the causal half
    cell = manifest.load_cell(CELL)
    needed = flops.cell_round_flops(
        layers, manifest.engine_params(manifest.compose_task(cell, 1)),
        clients=8, evaluates=True)
    assert needed["train_samples"] == 8 * 2 * 8
    assert needed["train"] == pytest.approx(16 * 9.3e12, rel=0.02)


@pytest.fixture(scope="module")
def tiny_path(tmp_path_factory):
    return tiny_preset.write(str(tmp_path_factory.mktemp("tiny_lfm2")),
                             NAME, "8_silo_1k")


@pytest.fixture(scope="module")
def tiny_run(tiny_path):
    return harness.run_cell("tiny.cell", 2**31 + 28, 0.5, False,
                            manifest_path=tiny_path, device=CPU, plant=True)


def test_the_tiny_preset_rejects_carry_dtype_bf16(tiny_path):
    run = harness.run_cell("tiny.cell", 2**31 + 29, 0.3, False,
                           manifest_path=tiny_path, device=CPU,
                           fedcore_overrides={"carry_dtype": "bf16"})
    assert run.result["failed"] == 0 and run.result["correct"] is False


def test_the_tiny_preset_runs_the_whole_path_and_is_correct(tiny_run):
    run, result = tiny_run, tiny_run.result
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == len(run.ctx.window.rounds) >= 1
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
    assert dropped["pseudo_grad_global_rel_l2"] > 1.5 * checked.limits[
        "pseudo_grad_global_rel_l2"]


def test_the_expert_layers_counters_reach_their_readers(tiny_run):
    ctx = tiny_run.ctx
    spans = [s for s in manifest.find_module(
        "layer_metrics", "moe.dropped_assignments").window_counts(ctx)]
    assert len(spans) == len(ctx.window.rounds)
    for attrs in spans:
        # Every resident client (the 4, and the padding up to the CPU
        # mesh's 8 devices) x 2 steps x 4 sequences x 16 tokens x top-4,
        # in each of 2 expert layers.
        assert attrs["tokens_per_step"] == 4 * 16
        assert attrs["moe_assignments_total"] == (
            2 * attrs["clients_resident"] * 2 * 4 * 16 * 4)
        assert 0 < attrs["moe_assignments_local"] == attrs[
            "moe_assignments_computed"] < attrs["moe_assignments_total"]
    read = {name: manifest.find_module("layer_metrics", name).read(ctx)
            for name in ("moe.dropped_assignments",
                         "moe.expert_load_max_over_mean")}
    assert read["moe.dropped_assignments"] == 0
    assert read["moe.expert_load_max_over_mean"] >= 1.0


def test_the_readers_report_nothing_where_the_program_counts_nothing():
    """The parent's program, or another configuration's: no counts on the
    spans, so the line leaves the metrics out and nothing raises."""
    class Window:
        rounds = []

    class Ctx:
        task = {"task_id": "no-such-task"}
        t_submitted = 0.0
        window = Window()
        cell = manifest.load_cell(CELL)

    for name in ("moe.dropped_assignments", "moe.expert_load_max_over_mean"):
        assert manifest.find_module("layer_metrics", name).read(Ctx()) is None
