"""``kimi_linear_ep32``'s own contract: no width in its file differs from
the catalog's config of Kimi-Linear-48B-A3B-Instruct, the cut it states is
the one the program is given, the model the program builds for its task is
the tree and the parameter total the file's arithmetic gives, its reference
counts the FLOPs the issue's arithmetic gives, and its tiny preset runs the
whole path on the CPU: task_type -> bridge -> runner -> FedCore -> counters
-> readers."""

import json
import os

import numpy as np
import pytest

import config_contract
import tiny_preset
from benchmark import flops, harness, manifest

NAME = "kimi_linear_ep32"
CELL = "kimi_linear_ep32.8_silo_2k"
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
# https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/
# config.json, the numbers at its top level (nested: linear_attn_config).
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512, "mla_use_nope": True,
    "model_max_length": 1048576, "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
    "vocab_size": 163840,
}
CUT = {"num_experts": 8, "vocab_size": 20480, "model_max_length": 2048}
KDA_LAYERS = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
              23, 25, 26]
MLA_LAYERS = [4, 8, 12, 16, 20, 24, 27]


def test_no_width_differs_from_the_published_config():
    config, _, _ = config_contract.load(NAME)
    assert config["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert len(config["source"]) <= 200
    for where in (config, config["model"]):     # top level = model block
        for key, value in PUBLISHED.items():
            assert where[key] == CUT.get(key, value), key
        assert where["model_type"] == "kimi_linear"
        assert where["linear_attn_config"] == {
            "full_attn_layers": MLA_LAYERS, "head_dim": 128,
            "kda_layers": KDA_LAYERS, "num_heads": 32,
            "short_conv_kernel_size": 4}
        assert where["num_layers"] == 5
        assert where["num_experts_published"] == 256
    # Every key that differs from the source is a stated cut, and none of
    # them is a width.
    assert set(CUT) | {"num_layers"} <= set(config["reduced"])
    assert set(config["reduced"]) == set(config["reduced_why"])
    for key in ("deployment", "reduced_why", "assumed", "guarantees",
                "precision"):
        assert config[key], key
    assert config["deployment"].startswith("32 chips share each layer")
    assert "router in float32" in config["precision"]
    assert "carries a decay" in config["precision"]
    assert "head_dim" in config["assumed"]      # 72: used by neither mixer
    assert config["algorithm"]["name"] == "fedavg"
    assert config["algorithm"]["server_lr"] == 1.0      # PERF.md 7.6a
    limits = config["check"]["limits"]
    assert limits["clients_trained_gap"] == 0 and len(limits) >= 5
    assert "PLACEHOLDER" not in json.dumps(config)


def test_the_program_is_given_the_cut_the_file_states():
    config, task_model, spec = config_contract.load(NAME)
    stated = config["model"]
    reference = manifest.find_module("reference", config["reference"])
    run_types = reference.run_layer_types(stated)
    # Published layers 1-5: the leading dense layer and one whole period.
    assert stated["layer_slice"] == [1, 5]
    assert run_types == ["kda", "kda", "kda", "mla", "kda"]
    assert task_model["overrides"]["layer_types"] == run_types
    assert len(run_types) == stated["num_layers"]
    assert run_types[stated["first_k_dense_replace"]:].count("kda") == 3
    assert stated["held_experts"] == list(range(stated["num_experts"]))
    assert stated["num_layers"] - stated["num_dense_layers"] == 4
    assert stated["kda_head_dim"] == stated["linear_attn_config"]["head_dim"]
    assert stated["vocab_size"] * 8 == stated["vocab_size_published"]
    # The task is a next-token task, on ids inside the vocabulary slice.
    data = config["task"]["target"]["data"][0]
    assert data["task_type"] == "next_token_prediction"
    params = config["task"]["operatorflow"]["operators"][0][
        "logical_simulation"]["operator_params"]
    assert params["data"]["synthetic"]["vocab_size"] == stated["vocab_size"]
    assert params["data"]["eval_n"] == 16
    fed = params["fedcore"]
    assert fed["batch_size"] * stated["sequence_length"] == 4096
    assert (fed["batch_size"], fed["max_local_steps"]) == (
        config["algorithm"]["batch_size"], config["algorithm"]["local_steps"])
    assert spec.vmap_clients is False
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1
    assert cell.traffic["fedcore"]["block_clients"] == 1
    assert (cell.traffic["clients"], cell.traffic["n_local"]) == (8, 12)
    assert cell.traffic["operators"] == ["train", "evaluate"]
    assert not cell.traffic.get("deviceflow")
    # A held expert's tokens a step against the deployment's, as the why says.
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        why = next(w["why"] for w in json.load(f)["workloads"]
                   if w["name"] == CELL)
    assert 4096 * 8 // 256 == 128 and "128 tokens a step, 1/32" in why
    # At least these: a later PR appends its own.
    names = {m["name"] for m in cell.per_layer}
    assert {"kda.chunk_scan.device_ms", "kda.projections.device_ms",
            "mla.attention.device_ms", "moe.shared_expert.device_ms",
            "kda.chunk_scan_roofline", "round_program.mfu",
            "round_program.scoped_share"} <= names


def test_the_tree_and_the_parameter_total_from_shapes_alone():
    config, task_model, spec = config_contract.load(NAME)
    m = config["model"]
    shapes = config_contract.init_shapes(spec, task_model)
    W, I, M = (m["hidden_size"], m["intermediate_size"],
               m["moe_intermediate_size"])
    kda = m["linear_attn_config"]
    H, D, T = kda["num_heads"], kda["head_dim"], kda["short_conv_kernel_size"]
    R, Dn, Dr, Dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    heads = m["num_attention_heads"]
    held, routed = m["num_experts"], m["num_experts_published"]
    assert shapes["embed/embedding"] == (m["vocab_size"], W)
    assert shapes["head"] == (W, m["vocab_size"])           # untied
    assert shapes["layers_0/kda/q_proj"] == (W, H * D) == (2304, 4096)
    assert shapes["layers_0/kda/v_conv"] == (T, H * D)
    assert shapes["layers_0/kda/f_a"] == (W, D)
    assert shapes["layers_0/kda/f_b"] == (D, H * D)
    assert shapes["layers_0/kda/A_log"] == (H,)
    assert shapes["layers_0/kda/dt_bias"] == (H * D,)
    assert shapes["layers_0/kda/b_proj"] == (W, H)
    assert shapes["layers_0/kda/o_norm"] == (D,)
    assert shapes["layers_0/mlp/w1"] == (W, I)
    assert shapes["layers_3/mla/q_proj"] == (W, heads * (Dn + Dr))
    assert shapes["layers_3/mla/kv_a"] == (W, R + Dr) == (2304, 576)
    assert shapes["layers_3/mla/kv_b"] == (R, heads * (Dn + Dv))
    assert shapes["layers_3/mla/out_proj"] == (heads * Dv, W)
    assert shapes["layers_1/moe/gate"] == (W, routed)
    assert shapes["layers_1/moe/expert_bias"] == (routed,)
    assert shapes["layers_4/moe/expert_w1"] == (held, W, M)
    assert shapes["layers_4/shared/w2"] == (M, W)
    assert "layers_0/shared/w1" not in shapes               # the dense layer
    assert not any(k.endswith("bias") and "expert_bias" not in k
                   and "dt_bias" not in k for k in shapes)
    # The file's arithmetic (PERF.md section 4), norms and taps included.
    mixer_kda = (4 * W * H * D + 2 * (W * D + D * H * D) + W * H
                 + 3 * T * H * D + H + H * D + D)
    mixer_mla = (W * heads * (Dn + Dr) + W * (R + Dr) + R
                 + R * heads * (Dn + Dv) + heads * Dv * W)
    experts = W * routed + routed + (held + 1) * 3 * W * M
    assert mixer_kda == 39_514_272 and mixer_mla == 29_114_880
    want = (2 * W + mixer_kda + 3 * W * I                   # layer 1
            + 3 * (2 * W + mixer_kda + experts)             # layers 2, 3, 5
            + 2 * W + mixer_mla + experts                   # layer 4
            + 2 * m["vocab_size"] * W + W)       # embedding, head, norm
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == want == 602_434_432
    assert total * 21 / 1e9 == pytest.approx(12.65, abs=0.01)


def test_the_reference_counts_what_a_trained_token_needs():
    config, _, _ = config_contract.load(NAME)
    reference = manifest.find_module("reference", config["reference"])
    layers = reference.layers(config["model"])
    L = config["model"]["sequence_length"]
    macs = sum(layer.macs for layer in layers) / L
    assert macs == pytest.approx(352.6e6, rel=0.002)    # forward, a token
    assert flops.train_flops(layers) / L == pytest.approx(2.12e9, rel=0.005)
    experts = [layer for layer in layers if layer.name.endswith(".experts")]
    assert len(experts) == 4
    # A quarter of a routed expert a token (8 chosen x 8 held / 256) and
    # the shared expert whole.
    assert experts[0].macs == L * 0.25 * 3 * 2304 * 1024
    shared = [layer for layer in layers
              if layer.name.endswith(".shared_expert")]
    assert len(shared) == 4 and shared[0].macs == L * 3 * 2304 * 1024
    scans = [layer for layer in layers if layer.name.endswith(".delta_rule")]
    assert len(scans) == 4 and scans[0].macs == L * 32 * 3 * 128 * 128
    scores = next(layer for layer in layers if layer.name.endswith(".scores"))
    assert scores.macs < L * L * 32 * 192 * 0.51        # the causal half
    cell = manifest.load_cell(CELL)
    needed = flops.cell_round_flops(
        layers, manifest.engine_params(manifest.compose_task(cell, 1)),
        clients=8, evaluates=True)
    assert needed["train_samples"] == 8 * 2 * 2
    # 65,536 trained and 32,768 evaluated tokens: 162 TFLOP a round.
    assert needed["total"] == pytest.approx(162e12, rel=0.01)


@pytest.fixture(scope="module")
def tiny_path(tmp_path_factory):
    return tiny_preset.write(str(tmp_path_factory.mktemp("tiny_kimi")),
                             NAME, "8_silo_2k")


@pytest.fixture(scope="module")
def tiny_run(tiny_path):
    # A window of 0.01 s closes at the first round start after its open:
    # one round, whatever the host's speed, so the check starts from the
    # state after the same rounds every time (PERF.md 7.6d).
    return harness.run_cell("tiny.cell", 2**31 + 34, 0.01, False,
                            manifest_path=tiny_path, device=CPU, plant=True)


def test_the_tiny_preset_rejects_carry_dtype_bf16(tiny_path):
    run = harness.run_cell("tiny.cell", 2**31 + 36, 0.01, False,
                           manifest_path=tiny_path, device=CPU,
                           fedcore_overrides={"carry_dtype": "bf16"})
    assert run.result["failed"] == 0 and run.result["correct"] is False


def test_the_tiny_preset_rejects_the_reference_without_its_decay(tiny_path):
    """This model's own planted fault, as ``scripts/
    kimi_linear_planted_decay.py`` plants it on the chip: alpha = 1."""
    planted_decay = manifest.load_module(
        os.path.join(os.path.dirname(manifest.HERE), "scripts"),
        "kimi_linear_planted_decay")
    sound, planted = planted_decay.run(
        "tiny.cell", 2**31 + 38, 0.01, manifest_path=tiny_path, device=CPU)
    assert sound.correct is True and planted.correct is False
    assert planted.numbers["param_delta_global_rel_l2"] > 2 * sound.limits[
        "param_delta_global_rel_l2"]


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
    assert dropped["pseudo_grad_rel_l2"] > 1.5 * checked.limits[
        "pseudo_grad_rel_l2"]


def test_the_scans_and_the_expert_layers_counters_reach_their_readers(
        tiny_run):
    ctx = tiny_run.ctx
    spans = manifest.find_module(
        "layer_metrics", "moe.dropped_assignments").window_counts(ctx)
    assert len(spans) == len(ctx.window.rounds)
    for attrs in spans:
        # Every resident client (the 4, and the padding up to the CPU
        # mesh's 8 devices) x 2 steps x 2 sequences x 80 tokens, through 2
        # KDA layers (2 chunks a sequence) and 2 expert layers (top-8).
        steps = attrs["clients_resident"] * 2
        assert attrs["tokens_per_step"] == 2 * 80
        assert attrs["kda_scan_tokens"] == 2 * steps * 2 * 80
        assert attrs["kda_scan_chunks"] == 2 * steps * 2 * 2
        assert attrs["moe_assignments_total"] == 2 * steps * 2 * 80 * 8
        assert 0 < attrs["moe_assignments_local"] == attrs[
            "moe_assignments_computed"] < attrs["moe_assignments_total"]
        # The untied embedding is trained by the rows a step reads.
        assert attrs["table_rows_total"] == 128
        assert attrs["table_rows_written_per_step"] == 2 * 80
    read = {name: manifest.find_module("layer_metrics", name).read(ctx)
            for name in ("moe.dropped_assignments",
                         "moe.expert_load_max_over_mean",
                         "round_program.table_rows_written_share",
                         "round_program.useful_work_share")}
    assert read["moe.dropped_assignments"] == 0
    assert read["moe.expert_load_max_over_mean"] >= 1.0
    # Ids a step reads over the table's rows (a tiny table is read more
    # than once over; the cell's is 4,096 of 20,480).
    assert read["round_program.table_rows_written_share"] == pytest.approx(
        100.0 * 160 / 128)
    # Half: the CPU mesh pads the 4 clients to 8 computed rows.
    assert read["round_program.useful_work_share"] == pytest.approx(50.0)
    # No trace on the CPU: the by-scope readers leave their metrics out.
    for name in ("kda.chunk_scan_roofline", "kda.chunk_scan.device_ms"):
        assert manifest.find_module("layer_metrics", name).read(ctx) is None
