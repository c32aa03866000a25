"""``nemotron_twotower_ep16``'s own contract: no width in its file differs
from the catalog's config of Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, the
cut it states is the one the program is given, the tower that is not built
is said not to be, the model the program builds for its task is the tree and
the parameter total the file's arithmetic gives, its reference counts the
FLOPs the issue's arithmetic gives, its scan's roofline counts the
recurrence's work, and its tiny preset runs the whole path on the CPU:
task_type -> bridge -> runner -> FedCore -> counters -> readers."""

import json
import os

import numpy as np
import pytest

import config_contract
import tiny_preset
from benchmark import flops, harness, manifest, roofline, roofline_ssd

NAME = "nemotron_twotower_ep16"
CELL = "nemotron_twotower_ep16.8_silo_2k"
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/
# blob/main/config.json, the numbers and flags at its top level.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072,
}
CUT = {"n_routed_experts": 8, "vocab_size": 16384,
       "max_position_embeddings": 2048}


def test_no_width_differs_from_the_published_config():
    config, _, _ = config_contract.load(NAME)
    assert config["source"] == (
        "https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-"
        "BF16/blob/main/config.json")
    assert len(config["source"]) <= 200
    for where in (config, config["model"]):     # top level = model block
        for key, value in PUBLISHED.items():
            assert where[key] == CUT.get(key, value), key
        assert where["model_type"] == "nemotron_h"
        assert where["mlp_hidden_act"] == "relu2"
        assert where["mamba_hidden_act"] == "silu"
        assert where["hybrid_override_pattern"] == PATTERN
        assert where["num_layers"] == 7 and where["layer_slice"] == [1, 7]
        assert where["n_routed_experts_published"] == 128
        assert where["vocab_size_published"] == 131072
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (
        23, 23, 6) and len(PATTERN) == 52
    # Every key that differs from the source is a stated cut, and none of
    # them is a width.
    assert set(CUT) | {"num_layers"} <= set(config["reduced"])
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert len(config["reduced"]) <= 16
    for key in ("deployment", "reduced_why", "assumed", "guarantees",
                "precision"):
        assert config[key], key
    assert config["deployment"].startswith("16 chips share each layer")
    assert "router in float32" in config["precision"]
    assert "carries a decay" in config["precision"]
    assert "expand" in config["assumed"]["d_inner"]     # 2: not used
    assert config["algorithm"]["name"] == "fedavg"
    assert config["algorithm"]["server_lr"] == 1.0      # PERF.md 7.6a
    limits = config["check"]["limits"]
    assert limits["clients_trained_gap"] == 0 and len(limits) >= 5
    assert "PLACEHOLDER" not in json.dumps(config)


def test_the_tower_that_is_not_built_is_said_not_to_be():
    """The catalog row describes a second, denoising tower; the config has
    no key of it. The file says so in a sentence of its own, and nothing in
    the program or the reference builds any of it."""
    config, _, _ = config_contract.load(NAME)
    said = config["assumed"]["second_tower"]
    assert ("denoising tower and block-diffusion objective are not built "
            "because the published config carries no key of theirs") in said
    assert "next-token loss" in said
    assert "second_tower" in config["deployment"]
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        why = next(c["why"] for c in json.load(f)["configs"]
                   if c["name"] == NAME)
    assert "no denoising tower" in why and len(why) <= 200
    root = os.path.dirname(manifest.HERE)
    for path in ("olearning_sim_tpu/models/nemotron_h.py",
                 "benchmark/reference/nemotron_h.py"):
        with open(os.path.join(root, path), encoding="utf-8") as f:
            source = f.read().lower()
        assert "adaln" not in source and "noise_schedule" not in source
        assert "def denois" not in source and "class denois" not in source


def test_the_program_is_given_the_cut_the_file_states():
    config, task_model, spec = config_contract.load(NAME)
    stated = config["model"]
    reference = manifest.find_module("reference", config["reference"])
    # The first seven letters: the unit the pattern repeats from its start.
    assert reference.run_pattern(stated) == "MEMEM*E" == PATTERN[:7]
    assert stated["layer_pattern"] == "MEMEM*E"
    assert task_model["overrides"]["pattern"] == "MEMEM*E"
    assert PATTERN.startswith("MEMEM*E" * 5)
    assert len(stated["layer_pattern"]) == stated["num_layers"]
    assert stated["held_experts"] == list(range(stated["n_routed_experts"]))
    assert stated["n_routed_experts"] * 16 == stated[
        "n_routed_experts_published"]
    assert stated["vocab_size"] * 8 == stated["vocab_size_published"]
    assert stated["sequence_length"] % stated["chunk_size"] == 0
    # The task is a next-token task, on ids inside the vocabulary slice.
    data = config["task"]["target"]["data"][0]
    assert data["task_type"] == "next_token_prediction"
    params = config["task"]["operatorflow"]["operators"][0][
        "logical_simulation"]["operator_params"]
    assert params["model"]["name"] == "nemotron_h"
    assert params["data"]["synthetic"]["vocab_size"] == stated["vocab_size"]
    assert params["data"]["eval_n"] == 16
    fed = params["fedcore"]
    assert fed["batch_size"] * stated["sequence_length"] == 4096
    assert (fed["batch_size"], fed["max_local_steps"]) == (
        config["algorithm"]["batch_size"], config["algorithm"]["local_steps"])
    # The two local steps unrolled: the grouped-matmul kernels keep the
    # training stage's scope (PERF.md section 6, PR 38).
    assert fed["step_unroll"] == 2 and set(fed) == {
        "batch_size", "max_local_steps", "eval_batch_size", "step_unroll"}
    assert spec.vmap_clients is False
    cell = manifest.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "8_silo_2k"
    assert cell.traffic["fedcore"]["block_clients"] == 1
    assert (cell.traffic["clients"], cell.traffic["n_local"]) == (8, 12)
    assert cell.traffic["operators"] == ["train", "evaluate"]
    assert not cell.traffic.get("deviceflow")
    # A held expert's tokens a step against the deployment's, as the why says.
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        why = next(w["why"] for w in json.load(f)["workloads"]
                   if w["name"] == CELL)
    assert 4096 * 6 // 128 == 192 and "192 tokens a step, 1/16" in why
    assert len(why) <= 200
    # At least these: a later PR appends its own.
    names = {m["name"] for m in cell.per_layer}
    assert {"ssd.chunk_scan.device_ms", "ssd.projections.device_ms",
            "nemotron_h.attention.device_ms", "ssd.chunk_scan_roofline",
            "moe.dropped_assignments", "moe.expert_load_max_over_mean",
            "round_program.mfu", "round_program.scoped_share"} <= names


def test_the_tree_and_the_parameter_total_from_shapes_alone():
    config, task_model, spec = config_contract.load(NAME)
    m = config["model"]
    shapes = config_contract.init_shapes(spec, task_model)
    W, M, Ms = (m["hidden_size"], m["moe_intermediate_size"],
                m["moe_shared_expert_intermediate_size"])
    Hm, P, N, G, T = (m["mamba_num_heads"], m["mamba_head_dim"],
                      m["ssm_state_size"], m["n_groups"], m["conv_kernel"])
    H, Hk, D = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    held, routed = m["n_routed_experts"], m["n_routed_experts_published"]
    d_inner, conv_dim = Hm * P, Hm * P + 2 * G * N
    assert (d_inner, conv_dim) == (4096, 6144)
    assert shapes["embed/embedding"] == (m["vocab_size"], W)
    assert shapes["head"] == (W, m["vocab_size"])           # untied
    assert shapes["layers_0/mamba/in_proj"] == (
        W, d_inner + conv_dim + Hm) == (2688, 10304)
    assert shapes["layers_0/mamba/conv"] == (T, conv_dim)
    assert shapes["layers_0/mamba/conv_bias"] == (conv_dim,)
    for leaf in ("A_log", "D", "dt_bias"):
        assert shapes[f"layers_2/mamba/{leaf}"] == (Hm,)
    assert shapes["layers_4/mamba/norm"] == (G, d_inner // G) == (8, 512)
    assert shapes["layers_4/mamba/out_proj"] == (d_inner, W)
    assert shapes["layers_5/attn/q_proj"] == (W, H * D) == (2688, 4096)
    assert shapes["layers_5/attn/k_proj"] == (W, Hk * D) == (2688, 256)
    assert shapes["layers_5/attn/v_proj"] == (W, Hk * D)
    assert shapes["layers_5/attn/out_proj"] == (H * D, W)
    assert shapes["layers_1/moe/gate"] == (W, routed)
    assert shapes["layers_1/moe/expert_bias"] == (routed,)
    assert shapes["layers_3/moe/expert_w1"] == (held, W, M)
    assert shapes["layers_6/moe/expert_w2"] == (held, M, W)
    assert shapes["layers_6/shared/w1"] == (W, Ms)
    assert shapes["layers_6/shared/w2"] == (Ms, W)
    # Two matrices an expert, no gate; a layer is one thing behind one norm.
    assert not any("w3" in k for k in shapes)
    assert sum(k.endswith("/norm/scale") for k in shapes) == 7
    assert not any(k.endswith("bias") and k.split("/")[-1] not in (
        "expert_bias", "dt_bias", "conv_bias") for k in shapes)
    # The file's arithmetic (PERF.md section 4), every layer with its norm.
    mamba = (W * (d_inner + conv_dim + Hm) + T * conv_dim + conv_dim
             + 3 * Hm + d_inner + d_inner * W + W)
    expert, shared = 2 * W * M, 2 * W * Ms
    experts = W * routed + routed + shared + held * expert + W
    attention = 2 * W * H * D + 2 * W * Hk * D + W
    assert mamba == 38_744_896 and attention == 23_399_040
    assert expert == 9_977_856 and shared == 19_955_712
    assert experts == 100_125_440
    want = 3 * mamba + attention + 3 * experts + 2 * m["vocab_size"] * W + W
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == want == 528_093_120
    assert total * 21 / 1e9 == pytest.approx(11.09, abs=0.01)
    # What a token multiplies (the issue's shares): the held experts at
    # their expected 6 x 8 / 128 a token.
    a_token = (3 * (mamba - W) + attention - W
               + 3 * (W * routed + shared + 0.375 * expert)
               + W * m["vocab_size"])
    assert a_token == pytest.approx(255.7e6, rel=0.002)


def test_the_reference_counts_what_a_trained_token_needs():
    config, _, _ = config_contract.load(NAME)
    reference = manifest.find_module("reference", config["reference"])
    layers = reference.layers(config["model"])
    L = config["model"]["sequence_length"]
    macs = sum(layer.macs for layer in layers) / L
    # 255.7 M weights a token, 3.1 M for the scan's two products, 8.4 M for
    # attention's causal half at 2,048 tokens, the taps.
    assert macs == pytest.approx(268.4e6, rel=0.005)
    assert flops.train_flops(layers) / L == pytest.approx(1.61e9, rel=0.005)
    experts = [layer for layer in layers if layer.name.endswith(".experts")]
    assert len(experts) == 3
    # 0.375 of a routed expert a token (6 chosen x 8 held / 128), two
    # products an expert, and the shared expert whole.
    assert experts[0].macs == L * 0.375 * 2 * 2688 * 1856
    shared = [layer for layer in layers
              if layer.name.endswith(".shared_expert")]
    assert len(shared) == 3 and shared[0].macs == L * 2 * 2688 * 3712
    scans = [layer for layer in layers if layer.name.endswith(".ssd")]
    assert len(scans) == 3 and scans[0].macs == L * 64 * 2 * 64 * 128
    scores = [layer for layer in layers if layer.name.endswith(".scores")]
    assert len(scores) == 1
    assert scores[0].macs < L * L * 32 * 128 * 0.51     # the causal half
    head = next(layer for layer in layers if layer.name == "head")
    assert head.macs == (L - 1) * 2688 * 16384
    cell = manifest.load_cell(CELL)
    needed = flops.cell_round_flops(
        layers, manifest.engine_params(manifest.compose_task(cell, 1)),
        clients=8, evaluates=True)
    assert needed["train_samples"] == 8 * 2 * 2
    # 65,536 trained and 32,768 evaluated tokens: 123 TFLOP a round.
    assert needed["total"] == pytest.approx(123e12, rel=0.01)


def test_the_scans_roofline_counts_the_recurrence_and_nothing_else():
    """A round's training: 8 clients x 2 steps x 4,096 tokens through 3
    layers, in 128-token chunks. The bytes bound it."""
    tokens, chunks = 8 * 2 * 4096 * 3, 8 * 2 * 2 * 16 * 3
    work = roofline_ssd.ssd(tokens, chunks, 64, 64, 128, 8)
    assert work.flops == 2.0 * 3 * tokens * 64 * 2 * 64 * 128
    a_token = (2 * 64 * 64 + 2 * 8 * 128) * 2 + 2 * 64 * 4
    assert work.bytes == 3 * tokens * a_token + 2 * chunks * 64 * 64 * 128 * 4
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = roofline.least_seconds(work, peaks)
    assert bound == "bytes" and seconds == pytest.approx(0.02298, rel=0.01)
    assert roofline.share_percent(work, 0.5, peaks) == pytest.approx(
        4.6, rel=0.01)
    # The same work whatever the chunks are called: the reader reads the
    # program's counters, and leaves the metric out where there are none.
    entry = next(m for m in json.load(open(manifest.MANIFEST))["per_layer"]
                 if m["name"] == "ssd.chunk_scan_roofline")
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    reader = manifest.find_module("layer_metrics", "ssd.chunk_scan_roofline")
    assert reader.SCOPE == "ssd.chunk_scan"


@pytest.fixture(scope="module")
def tiny_path(tmp_path_factory):
    return tiny_preset.write(str(tmp_path_factory.mktemp("tiny_nemotron")),
                             NAME, "8_silo_2k")


@pytest.fixture(scope="module")
def tiny_run(tiny_path):
    # A window of 0.01 s closes at the first round start after its open:
    # one round, whatever the host's speed, so the check starts from the
    # state after the same rounds every time (PERF.md 7.6d).
    return harness.run_cell("tiny.cell", 2**31 + 38, 0.01, False,
                            manifest_path=tiny_path, device=CPU, plant=True)


def test_the_tiny_preset_rejects_carry_dtype_bf16(tiny_path):
    run = harness.run_cell("tiny.cell", 2**31 + 40, 0.01, False,
                           manifest_path=tiny_path, device=CPU,
                           fedcore_overrides={"carry_dtype": "bf16"})
    assert run.result["failed"] == 0 and run.result["correct"] is False


def test_the_tiny_preset_rejects_the_reference_without_its_decay(tiny_path):
    """This model's own planted fault, as ``scripts/
    nemotron_h_planted_decay.py`` plants it on the chip: a_t = 1. The
    planted reading has to exceed the limit, not a multiple of it."""
    planted_decay = manifest.load_module(
        os.path.join(os.path.dirname(manifest.HERE), "scripts"),
        "nemotron_h_planted_decay")
    sound, planted = planted_decay.run(
        "tiny.cell", 2**31 + 44, 0.01, manifest_path=tiny_path, device=CPU)
    assert sound.correct is True and planted.correct is False
    assert planted.numbers["param_delta_global_rel_l2"] > sound.limits[
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
    assert dropped["pseudo_grad_rel_l2"] > checked.limits[
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
        # Mamba-2 layers (5 chunks a sequence) and 2 expert layers (top-6).
        steps = attrs["clients_resident"] * 2
        assert attrs["tokens_per_step"] == 2 * 80
        assert attrs["ssd_scan_tokens"] == 2 * steps * 2 * 80
        assert attrs["ssd_scan_chunks"] == 2 * steps * 2 * 5
        assert attrs["moe_assignments_total"] == 2 * steps * 2 * 80 * 6
        assert 0 < attrs["moe_assignments_local"] == attrs[
            "moe_assignments_computed"] < attrs["moe_assignments_total"]
        # The untied embedding is trained by the rows a step reads.
        assert attrs["table_rows_total"] == 128
        assert attrs["table_rows_written_per_step"] == 2 * 80
    read = {name: manifest.find_module("layer_metrics", name).read(ctx)
            for name in ("moe.dropped_assignments",
                         "moe.expert_load_max_over_mean",
                         "round_program.useful_work_share")}
    assert read["moe.dropped_assignments"] == 0
    assert read["moe.expert_load_max_over_mean"] >= 1.0
    # Half: the CPU mesh pads the 4 clients to 8 computed rows.
    assert read["round_program.useful_work_share"] == pytest.approx(50.0)
    # No trace on the CPU: the by-scope readers leave their metrics out.
    for name in ("ssd.chunk_scan_roofline", "ssd.chunk_scan.device_ms",
                 "ssd.projections.device_ms",
                 "nemotron_h.attention.device_ms"):
        assert manifest.find_module("layer_metrics", name).read(ctx) is None
