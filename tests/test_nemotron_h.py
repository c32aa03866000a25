"""The ``nemotron_h`` family at a small size on the CPU, against
``benchmark/reference/nemotron_h.py``: the chunked Mamba-2 scan against the
recurrence token by token (values and all gradients, the largest and the
smallest step size included), each mixer and the whole model (forward and
the gradient of the next-token loss), the expert layer's two forms against
hand-written sums, its sixteen shares with the shared expert counted once,
one federated round + evaluation through ``FedCore`` with the embedding
trained by rows and the scan's counts on the round's metrics, and which
layers the backward pass computes again: none (wrapping the Mamba-2 layers
again, the fallback, changes no value), and to no other family's round
program.

Counts and correctness facts only: never a speed."""

import dataclasses
import hashlib
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, manifest
from benchmark.reference import fedround
from olearning_sim_tpu.engine.algorithms import from_config
from olearning_sim_tpu.engine.client_data import (
    make_central_text_eval_set, make_synthetic_text_dataset)
from olearning_sim_tpu.engine.fedcore import FedCoreConfig, build_fedcore
from olearning_sim_tpu.models import get_model
from olearning_sim_tpu.models import nemotron_h as nm
from olearning_sim_tpu.models.moe import DroplessMoE
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

ref = manifest.find_module("reference", "nemotron_h")
F32 = jnp.float32
W, L, CHUNK = 32, 80, 16    # five whole chunks
# Top-6 of 16, four held, heads of 128: the reference's published TOP_K and
# HEAD_DIM; the Mamba-2 sizes are the leaves' own.
TINY = dict(vocab_size=128, max_len=L, width=W, pattern="MEM*E",
            mamba_heads=4, mamba_head_dim=8, state_size=16, groups=2,
            chunk_size=CHUNK, heads=2, kv_heads=1, head_dim=128,
            moe_mlp_dim=24, shared_mlp_dim=48, num_experts=16,
            experts_per_token=6, held_experts=[0, 1, 2, 3])


def _flat(tree, prefix=""):
    return {prefix + k: jnp.asarray(v) for k, v in check.flatten(tree).items()}


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert np.isfinite(got).all()
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def _x(seed, n=2, length=L):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (n, length, W)), F32)


@pytest.mark.parametrize("chunks,chunk,steps,tol", [
    (1, CHUNK, "drawn", 1e-4),      # one chunk
    (5, CHUNK, "drawn", 1e-4),      # several whole chunks
    (2, 128, "drawn", 1e-4),        # the published chunk size
    # time_step_max and over it under A_log at its largest, the published
    # chunk size: exp(sum of a chunk's log decays) underflows (the running
    # sum reaches 300, where a float32 difference of two carries 3e-5)
    (2, 128, "largest", 5e-4),
    # time_step_floor under A_log at its smallest: hardly a decay, every
    # token of a sequence still in the state at its end
    (3, CHUNK, "smallest", 1e-4),
])
def test_the_chunked_scan_is_the_recurrence_in_value_and_all_gradients(
        chunks, chunk, steps, tol):
    rng = np.random.default_rng(chunks + chunk)
    n, H, P, g, N = 2, 4, 8, 2, 16
    length = chunks * chunk
    x = jnp.asarray(rng.standard_normal((n, length, H, P)), F32)
    B = jnp.asarray(rng.standard_normal((n, length, g, N)), F32)
    C = jnp.asarray(rng.standard_normal((n, length, g, N)), F32)
    dt = {"drawn": np.logaddexp(0, rng.standard_normal((n, length, H)) - 3),
          "largest": rng.uniform(0.1, 0.2, (n, length, H)),
          "smallest": np.full((n, length, H), 1e-4)}[steps]
    A = {"drawn": -rng.uniform(1, 16, H), "largest": -np.full(H, 16.0),
         "smallest": -np.ones(H)}[steps]
    dt, A = jnp.asarray(dt, F32), jnp.asarray(A, F32)
    probe = jnp.asarray(rng.standard_normal((n, length, H, P)), F32)
    if steps == "largest":
        # A chunk's product of decays is below float32's smallest number.
        assert float((dt * A)[:, :chunk].sum(1).max()) < -200
    if steps == "smallest":
        assert float((dt * A).sum(1).min()) > -0.1

    def chunked(*a):
        y = nm.chunk_scan(*a, chunk)
        return (y * probe).sum(), y

    def stepwise(x, dt, A, B, C):
        y = jnp.stack([ref.ssd(x[i], dt[i], A,
                               jnp.repeat(B[i], H // g, axis=1),
                               jnp.repeat(C[i], H // g, axis=1))
                       for i in range(n)])
        return (y * probe).sum(), y

    args = (x, dt, A, B, C)
    (_, got), got_g = jax.value_and_grad(
        chunked, argnums=tuple(range(5)), has_aux=True)(*args)
    (_, want), want_g = jax.value_and_grad(
        stepwise, argnums=tuple(range(5)), has_aux=True)(*args)
    _close(got, want, tol)
    for a, b in zip(got_g, want_g):
        _close(a, b, tol)


def test_the_scan_takes_whole_chunks_only():
    """The traffic gives sequences of whole chunks; a tail is refused, not
    padded in silence."""
    a = jnp.zeros((1, 24, 4, 8), F32)
    b = jnp.zeros((1, 24, 2, 16), F32)
    with pytest.raises(ValueError, match="not whole chunks of 16"):
        nm.chunk_scan(a, jnp.zeros((1, 24, 4), F32), -jnp.ones(4), b, b, 16)


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_each_mixer_matches_the_reference(kind):
    module, reference = {
        "mamba": (nm.Mamba2(4, 8, 16, 2, chunk_size=CHUNK, dtype=F32),
                  lambda p, x: ref.mamba2(p, "", x)),
        "attention": (nm.Attention(2, 1, ref.HEAD_DIM, dtype=F32),
                      lambda p, x: ref.attention(p, "", x)),
    }[kind]
    x = _x(1)
    params = module.init(jax.random.key(0), x)["params"]
    if kind == "mamba":
        # Seeded as the family seeds them: 1 <= exp(A_log) <= 16, a
        # softplus(dt_bias) between time_step_min and time_step_max, D = 1.
        assert 0 <= float(params["A_log"].min()) <= float(
            params["A_log"].max()) <= np.log(16) + 1e-6
        step = np.asarray(jax.nn.softplus(params["dt_bias"]))
        assert 0.00099 <= step.min() and step.max() <= 0.1001
        assert np.asarray(params["D"]).tolist() == [1.0] * 4
        assert params["in_proj"].shape == (W, 32 + (32 + 2 * 2 * 16) + 4)
        assert params["conv"].shape == (4, 32 + 2 * 2 * 16)
        assert params["norm"].shape == (2, 16)
        # A bias, a skip and a scale that differ from their seeds.
        rng = np.random.default_rng(5)
        params = dict(params, **{
            k: params[k] + jnp.asarray(
                0.3 * rng.standard_normal(params[k].shape), F32)
            for k in ("conv_bias", "D", "norm")})

    def program(p, x):
        return (module.apply({"params": p}, x) ** 2).sum()

    def plain(p, x):
        return sum((reference(p, x[i]) ** 2).sum() for i in range(x.shape[0]))

    _close(module.apply({"params": params}, x)[1],
           reference(_flat(params), x[1]))
    got = jax.grad(program, argnums=(0, 1))(params, x)
    want = jax.grad(plain, argnums=(0, 1))(_flat(params), x)
    _close(got[1], want[1], 2e-4)
    for name, g in _flat(got[0]).items():
        _close(g, want[0][name], 2e-4)


def test_a_mamba_layer_counts_its_scans_tokens_and_chunks():
    layer = nm.Mamba2(4, 8, 16, 2, chunk_size=CHUNK, dtype=F32)
    x = _x(2, n=3)
    params = layer.init(jax.random.key(0), x)["params"]
    _, inter = layer.apply({"params": params}, x, mutable=["intermediates"])
    (stats,) = inter["intermediates"]["ssd_stats"]
    assert np.asarray(stats).tolist() == [3 * L, 3 * 5, 0, 0]
    # Two such layers' counts, gathered and named as the registry has it.
    counts = get_model("nemotron_h").work_counts
    row = counts.gather({"a": inter["intermediates"],
                         "b": inter["intermediates"]})
    assert counts.describe(np.asarray(row)) == {
        "ssd_scan_tokens": 2 * 3 * L, "ssd_scan_chunks": 2 * 3 * 5,
        "attend_pairs_needed": 0, "attend_pairs_computed": 0}


@pytest.mark.parametrize("held", [1, 2, 8])
def test_the_mixers_counts_ride_beside_the_expert_layers(held):
    """One array of a forward pass's counts: the expert layers' rows of 3 +
    ``held``, the mixers' four summed, the trips summed, with one held
    expert too (a row as wide as the mixers' names, and no wider)."""
    x = _x(2, n=1)
    mixer = nm.Mamba2(4, 8, 16, 2, chunk_size=CHUNK, dtype=F32)
    layer = DroplessMoE(8, 2, tuple(range(held)), 12, dtype=F32, gated=False)
    sown = []
    for module in (mixer, layer):
        params = module.init(jax.random.key(0), x)["params"]
        sown.append(module.apply(
            {"params": params}, x, mutable=["intermediates"])[1][
                "intermediates"])
    (stats,), (trips,) = sown[1]["moe_stats"], sown[1]["moe_window_trips"]
    counts = get_model("nemotron_h").work_counts
    got = np.asarray(counts.gather(
        {"mixer": sown[0], "first": sown[1], "second": sown[1]}))
    assert got.shape == (2 + 2, 3 + held)
    named = counts.describe(got)
    assert named["ssd_scan_tokens"] == L and named["ssd_scan_chunks"] == 5
    assert named["moe_window_trips"] == 2 * int(trips) > 0
    assert named["moe_assignments_total"] == 2 * 2 * L
    assert named["moe_assignments_local"] == 2 * int(stats[1])
    assert named["moe_expert_load_mean"] == pytest.approx(
        int(stats[1]) / held)


@pytest.mark.parametrize("gated", [True, False])
def test_both_expert_forms_keep_their_tree_and_their_arithmetic(gated):
    """The gated form (the default: three matrices, ``W2(silu(W1 h) * W3
    h)``) and the two-matrix form (``W2(relu(W1 h)^2)``), each against a sum
    written out by hand over every token and slot."""
    experts, top_k, M, scale = 8, 3, 12, 2.5
    x = _x(3, n=1, length=10)
    layer = DroplessMoE(experts, top_k, tuple(range(experts)), M,
                        routed_scaling_factor=scale, dtype=F32,
                        **({} if gated else {"gated": False}))
    assert layer.gated is gated
    p = layer.init(jax.random.key(1), x)["params"]
    matrices = (("expert_w1", "expert_w3", "expert_w2") if gated
                else ("expert_w1", "expert_w2"))
    assert set(p) == {"gate", "expert_bias", *matrices}
    assert p["expert_w1"].shape == (experts, W, M)
    assert p["expert_w2"].shape == (experts, M, W)
    got = np.asarray(layer.apply({"params": p}, x))[0]
    h, p = np.asarray(x[0], np.float64), {
        k: np.asarray(v, np.float64) for k, v in p.items()}
    scores = 1 / (1 + np.exp(-h @ p["gate"]))
    want = np.zeros_like(h)
    for t in range(h.shape[0]):
        chosen = np.argsort(-(scores[t] + p["expert_bias"]))[:top_k]
        total = scores[t, chosen].sum() + 1e-6
        for e in chosen:
            a = h[t] @ p["expert_w1"][e]
            if gated:
                hidden = a / (1 + np.exp(-a)) * (h[t] @ p["expert_w3"][e])
            else:
                hidden = np.maximum(a, 0) ** 2
            want[t] += (scale * scores[t, e] / total
                        * (hidden @ p["expert_w2"][e]))
    _close(got, want)


@pytest.mark.parametrize("sizes", [(5, 0, 9, 7), (0, 0, 0, 0), (30, 20, 10, 4)])
def test_whole_row_blocks_and_a_padded_width_change_no_value_or_gradient(
        sizes, monkeypatch):
    """The two-matrix form's grouped products are given a hidden width
    padded to the kernel's tile and whole blocks of rows (the last group
    lengthened over the zero rows that follow the groups, never past the
    array's end): the same values and gradients as the plain products."""
    from olearning_sim_tpu.models import moe

    monkeypatch.setattr(moe, "GROUPED_ROW_BLOCK", 16)
    rng = np.random.default_rng(0)
    rows, used, M, H = 64, sum(sizes), 12, 4
    sizes = jnp.asarray(sizes, jnp.int32)
    mask = (np.arange(rows) < used)[:, None]
    xs = jnp.asarray(rng.standard_normal((rows, W)) * mask, F32)
    probe = jnp.asarray(rng.standard_normal((rows, W)) * mask, F32)
    w1 = jnp.asarray(rng.standard_normal((H, W, M)), F32)
    w2 = jnp.asarray(rng.standard_normal((H, M, W)), F32)

    def plain(xs, w1, w2):
        a = jax.nn.relu(jax.lax.ragged_dot(xs, w1, sizes))
        return jax.lax.ragged_dot(a * a, w2, sizes)

    def blocked(xs, w1, w2):
        w1, w2 = moe._padded_width(w1, w2)
        blocks = moe._whole_blocks(sizes, rows)
        return jax.lax.ragged_dot(
            moe._hidden_rows(False, xs, (w1,), blocks), w2, blocks)

    def read(f):        # what the layer gathers back: the groups' rows
        return jax.value_and_grad(
            lambda *a: (jnp.where(mask, f(*a), 0) * probe).sum(),
            argnums=(0, 1, 2))(xs, w1, w2)

    (want, want_g), (got, got_g) = read(plain), read(blocked)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(got_g, want_g):
        _close(a, b, 1e-6)


@pytest.mark.parametrize("block,trips", [(144, 1), (8, 4), (4, 5)])
def test_the_two_matrix_forms_windows_against_the_reference(
        block, trips, monkeypatch):
    """The squared-ReLU layer by row windows, its width padded to the tile
    (12 to 16) and its last group lengthened to whole blocks inside each
    window, in one trip and in many, inside a caller's ``jax.checkpoint``
    too: the reference's layer in value and in every gradient."""
    from olearning_sim_tpu.models import moe

    monkeypatch.setattr(moe, "GROUPED_ROW_BLOCK", block)
    monkeypatch.setattr(moe, "GROUPED_TILE", 8)
    experts, top_k, M = 64, ref.TOP_K, 12
    x = _x(5, n=1, length=24)
    layer = DroplessMoE(experts, top_k, (0, 1, 2, 3), M,
                        routed_scaling_factor=ref.ROUTED_SCALING_FACTOR,
                        dtype=F32, gated=False)
    p = dict(layer.init(jax.random.key(2), x)["params"])
    # The four held experts favoured: 96 of the 144 assignments are
    # theirs, where an even router over 64 would send them 9.
    p["expert_bias"] = jnp.zeros(experts).at[:4].set(1.0)
    probe = _x(6, n=1, length=24)

    def want_fn(p, x):
        return (ref.experts(p, "", x[0], held=(0, 1, 2, 3))
                * probe[0]).sum()

    def got_fn(p, x):
        y, inter = layer.apply({"params": p}, x, mutable=["intermediates"])
        return (y * probe).sum(), inter["intermediates"]

    want, want_g = jax.value_and_grad(want_fn, argnums=(0, 1))(p, x)
    (got, inter), got_g = jax.value_and_grad(
        got_fn, argnums=(0, 1), has_aux=True)(p, x)
    local = int(inter["moe_stats"][0][1])
    window = moe.window_rows(24 * top_k, 4, experts)
    assert int(inter["moe_window_trips"][0]) == -(-local // window) == trips
    assert int(inter["moe_stats"][0][2]) == local
    again = jax.grad(jax.checkpoint(lambda p, x: got_fn(p, x)[0]),
                     argnums=(0, 1))(p, x)
    assert float(got) == pytest.approx(float(want), rel=2e-4)
    for grads in (got_g, again):
        _close(grads[1], want_g[1])
        _close(grads[0]["gate"], want_g[0]["gate"])
        for name in ("expert_w1", "expert_w2"):
            _close(grads[0][name], want_g[0][name])


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """16 chips of 8 experts each: their partial sums, with the shared
    expert (what every chip computes alike) counted once, are the uncut
    reference's expert layer; so are the gradients of what they share."""
    experts, top_k, M = 128, ref.TOP_K, 12
    x = _x(3, n=1, length=24)
    full = DroplessMoE(experts, top_k, tuple(range(experts)), M,
                       routed_scaling_factor=ref.ROUTED_SCALING_FACTOR,
                       dtype=F32, gated=False).init(
                           jax.random.key(1), x)["params"]
    assert "expert_w3" not in full
    shared = nm.ReLU2(2 * M, F32).init(jax.random.key(2), x)["params"]
    probe = _x(4, n=1, length=24)

    def uncut(p, s, x):
        y = ref.experts(p, "", x[0]) + ref.relu2(x[0], s["w1"], s["w2"])
        return (y * probe[0]).sum(), y

    (_, want), want_g = jax.value_and_grad(
        uncut, argnums=(0, 2), has_aux=True)(_flat(full), shared, x)
    total = nm.ReLU2(2 * M, F32).apply({"params": shared}, x)       # once
    g_x = jax.grad(lambda x: (nm.ReLU2(2 * M, F32).apply(
        {"params": shared}, x) * probe).sum())(x)
    g_gate, local = jnp.zeros_like(full["gate"]), 0
    for chip in range(16):
        held = tuple(range(8 * chip, 8 * chip + 8))
        layer = DroplessMoE(experts, top_k, held, M,
                            routed_scaling_factor=ref.ROUTED_SCALING_FACTOR,
                            dtype=F32, gated=False)
        share = {name: (leaf[np.asarray(held)]
                        if name.startswith("expert_w") else leaf)
                 for name, leaf in full.items()}

        def part(p, x):
            y, inter = layer.apply({"params": p}, x,
                                   mutable=["intermediates"])
            return (y * probe).sum(), (y, inter)

        (_, (y, inter)), g = jax.value_and_grad(
            part, argnums=(0, 1), has_aux=True)(share, x)
        total, g_x, g_gate = total + y, g_x + g[1], g_gate + g[0]["gate"]
        (stats,) = inter["intermediates"]["moe_stats"]
        local += int(stats[1])
        assert int(stats[1]) == int(stats[2])
        _close(g[0]["expert_w2"], want_g[0]["expert_w2"][np.asarray(held)])
    _close(total[0], want)
    _close(g_x, want_g[1])
    _close(g_gate, want_g[0]["gate"])
    # Every (token, slot) assignment lands on exactly one share.
    assert local == 24 * top_k


def _planted():
    return manifest.load_module(
        os.path.join(os.path.dirname(manifest.HERE), "scripts"),
        "nemotron_h_planted_decay")


def test_the_whole_model_matches_the_reference():
    model = get_model("nemotron_h").build(**TINY, dtype=F32)
    tokens = jnp.asarray(np.random.default_rng(8).integers(1, 128, (3, L)),
                         jnp.int32)
    params = model.init(jax.random.key(1), tokens)["params"]
    flat = _flat(params)
    assert flat["head"].shape == (W, 128)                   # untied
    # A layer is a mixer or a feed-forward part alone, behind one norm.
    assert set(params["layers_0"]) == {"norm", "mamba"}
    assert set(params["layers_1"]) == {"norm", "moe", "shared"}
    assert set(params["layers_3"]) == {"norm", "attn"}
    assert flat["layers_1/shared/w1"].shape == (W, 48)
    assert "layers_1/moe/expert_w3" not in flat             # no gate
    logits = model.apply({"params": params}, tokens)
    for i in range(3):
        _close(logits[i], ref.forward(flat, tokens[i]), 2e-4)
    sw = np.asarray([0.5, 0.0, 0.5], np.float32)

    def loss_fn(p):
        logits = model.apply({"params": p}, tokens)
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        ce = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
        return (jnp.asarray(sw) * ce.mean(-1)).sum()

    want_loss, want = jax.value_and_grad(loss_fn)(params)
    loss, grads = ref.loss_and_grad(ref.prepare(check.flatten(params)),
                                    np.asarray(tokens), None, sw)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    worst = check.worst_leaf({k: np.asarray(v) for k, v in grads.items()},
                             check.flatten(want))
    assert worst["rel_l2"] < 1e-3, worst
    assert set(grads) == set(check.flatten(want))
    assert not np.asarray(grads["layers_1/moe/expert_bias"]).any()
    assert ref.chosen_experts(flat, tokens[0]).shape == (2, L, ref.TOP_K)
    # The decay is in the loss: the reference with the fault planted that
    # the cell's check has to refuse (a_t = 1) reads otherwise.
    planted = _planted().leave_decay_out(
        manifest.find_module("reference", "nemotron_h"))
    plain = float(ref.sequence_loss(flat, tokens[0]))
    assert abs(float(planted.sequence_loss(flat, tokens[0]))
               - plain) > 1e-4 * plain


def _loss_and_grads(pattern):
    model = get_model("nemotron_h").build(
        **dict(TINY, pattern=pattern), dtype=F32)
    tokens = jnp.asarray(np.random.default_rng(8).integers(1, 128, (2, L)),
                         jnp.int32)
    params = model.init(jax.random.key(1), tokens)["params"]

    def loss_fn(p):
        logp = jax.nn.log_softmax(model.apply({"params": p}, tokens)[:, :-1])
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    return loss_fn, params


@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEMEM*E"])
def test_computing_a_layer_again_changes_no_value(pattern, monkeypatch):
    """Loss and every leaf's gradient of the model as it is (no layer
    wrapped) are those of the same model whose Mamba-2 layers are wrapped in
    ``nn.remat``: the first fallback where a fuller stage does not fit."""
    loss_fn, params = _loss_and_grads(pattern)
    got_loss, got = jax.value_and_grad(loss_fn)(params)
    again, plain = [], nm.Layer

    def layer(kind, **sizes):
        again.append(kind)
        return (nn.remat(plain) if kind == "M" else plain)(kind=kind, **sizes)

    monkeypatch.setattr(nm, "Layer", layer)
    want_loss, want = jax.value_and_grad(loss_fn)(params)
    assert "".join(again) == pattern
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-6)
    got, want = check.flatten(got), check.flatten(want)
    assert set(got) == set(want)
    for name in want:
        _close(got[name], want[name], 1e-5)


def _checkpoints(jaxpr):
    """The primitives inside every ``jax.checkpoint`` equation of a jaxpr,
    at any depth, one set an equation."""
    found = []

    def inner(value):
        value = getattr(value, "jaxpr", value)
        return value if hasattr(value, "eqns") else None

    def names(sub, into):
        for eqn in sub.eqns:
            into.add(eqn.primitive.name)
            for value in eqn.params.values():
                if inner(value) is not None:
                    names(inner(value), into)
        return into

    def walk(sub):
        for eqn in sub.eqns:
            if eqn.primitive.name == "remat2":      # jax.checkpoint's
                found.append(names(eqn.params["jaxpr"], set()))
            for value in eqn.params.values():
                if inner(value) is not None:
                    walk(inner(value))

    walk(jaxpr)
    return found


def test_the_backward_pass_computes_no_layer_again():
    """The intent, pinned (until PR 48 it was "only the Mamba-2 layers",
    and the test was named so): an attention layer's backward pass holds one
    ``jax.checkpoint`` equation, ``decoder_parts.attend``'s own around the scores
    (no norm inside it); an expert layer's holds none (its windows are
    computed again by its own backward loop, not by a checkpoint); a
    Mamba-2 layer's holds none, so its scan and its projections run once a
    step. ``nn.remat`` back around a Mamba-2 layer adds one with the
    pre-norm and the scan inside, and 0.25 s to the benchmark cell's 2.58 s
    round (PERF.md section 6, PR 48)."""
    def backward(pattern):
        loss_fn, params = _loss_and_grads(pattern)
        return _checkpoints(jax.make_jaxpr(jax.grad(loss_fn))(params).jaxpr)

    (scores,) = backward("*")
    assert "exp" in scores and "rsqrt" not in scores
    assert backward("E") == []
    assert backward("M") == []
    (whole,) = backward("MEMEM*E")
    assert "exp" in whole and "scan" not in whole and "rsqrt" not in whole


@pytest.mark.parametrize("config,digest", [
    ("lfm2_moe_ep8",
     "59191acdb37055bf5817e492f20350e34047bde0d744cc9469aac53471de34e3"),
    ("kimi_linear_ep32",
     "c661049ccebb1badcc0db45a39a96df0195037c816d4f95ef6dab230b5c6a503"),
])
def test_the_other_sparse_decoders_round_programs_did_not_move(
        config, digest):
    """The two families that share ``models/moe.py`` and ``decoder_parts.attend``
    with this one lower their small presets' ``round_step`` to the text
    they lowered to before this family chose its layers to compute again
    (PR 40: the digests are the parent commit's), so their compilation
    cache keys stand. An intended edit to those families pins them anew
    (the failure prints the new digest): ``kimi_linear_ep32``'s is PR 49's,
    an intended edit to that family alone (``KDA`` calls its scan through
    ``ops/kda_scan.py``'s custom VJP and sows one more count,
    ``kda_scan_kernel_chunks``; ``lfm2_moe_ep8``'s stood). Before it both
    were PR 47's, an intended edit
    to the module the three families share: ``models/moe.py``
    ``DroplessMoE`` works its sorted rows in windows inside a
    ``lax.while_loop`` with a backward loop of its own and sows
    ``moe_window_trips`` (one more row at the end of ``model_stats``). PR 45's before them: the attention layers'
    ``attend_pairs_*`` counts and ``decoder_parts.attend``'s mask from two
    ``iota``s."""
    def read(*path):
        with open(os.path.join(*path, config + ".json")) as f:
            return json.load(f)

    tiny = read(os.path.dirname(__file__), "benchmark", "data", "tiny")
    params = read(manifest.HERE, "configs")["task"]["operatorflow"][
        "operators"][0]["logical_simulation"]["operator_params"]
    plan = make_mesh_plan(devices=jax.devices()[:1])
    cfg = dataclasses.replace(
        FedCoreConfig.from_dict(
            dict(params["fedcore"], **tiny["traffic"]["fedcore"])),
        task="next_token")
    core = build_fedcore(
        params["model"]["name"], from_config("fedavg", local_lr=0.1), plan,
        cfg, model_overrides=tiny["overrides"],
        input_shape=tuple(tiny["input_shape"]))
    ds = make_synthetic_text_dataset(
        7, tiny["traffic"]["clients"], tiny["traffic"]["n_local"],
        tiny["input_shape"][0], num_classes=4,
        vocab_size=tiny["overrides"]["vocab_size"],
        dirichlet_alpha=0.3).pad_for(plan, 1).place(plan)
    text = core.lower_round_step(
        core.init_state(jax.random.key(0)), ds).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_the_references_program_is_not_left_in_the_compile_cache(monkeypatch):
    """While it compiles, nothing is written to a capped cache, and the
    setting comes back."""
    key = "jax_persistent_cache_min_compile_time_secs"
    before, seen = getattr(jax.config, key), []
    compiled = ref._sequence_value_and_grad
    monkeypatch.setattr(
        ref, "_sequence_value_and_grad",
        lambda *a: (seen.append(getattr(jax.config, key)), compiled(*a))[1])
    model = get_model("nemotron_h").build(**TINY, dtype=F32)
    tokens = np.random.default_rng(8).integers(1, 128, (2, L))
    params = model.init(jax.random.key(1), jnp.asarray(tokens))["params"]
    loss, grads = ref.loss_and_grad(ref.prepare(check.flatten(params)),
                                    tokens, None, [0.5, 0.5])
    assert seen == [float("inf")] * 2 and getattr(jax.config, key) == before
    assert np.isfinite(loss) and set(grads) == set(check.flatten(params))
    assert all(isinstance(v, np.ndarray) for v in ref.prepare(
        check.flatten(params)).values())        # kept on the host


def test_a_round_trains_the_embedding_by_rows_and_matches_the_reference():
    plan = make_mesh_plan(devices=jax.devices()[:1])
    cfg = FedCoreConfig(batch_size=2, max_local_steps=2, block_clients=1,
                        task="next_token", eval_batch_size=4)
    algorithm = {"name": "fedavg", "local_lr": 0.1, "server_lr": 1.0}
    core = build_fedcore(
        "nemotron_h", from_config("fedavg", local_lr=0.1), plan, cfg,
        model_overrides=dict(TINY, dtype=F32), input_shape=(L,))
    # The model marks its embedding as lookup-only and the builder finds
    # it: the untied head leaves the table to the lookup alone.
    assert core.lookup_tables.paths == (("embed", "embedding"),)
    assert core.lookup_tables.rows_total == 128
    assert core.vmap_clients is False
    assert core.use_multiplicity(6, (L,), np.int32) is False
    host = make_synthetic_text_dataset(
        2**31 + 9, 3, 6, L, num_classes=4, vocab_size=128,
        dirichlet_alpha=0.3)
    ds = host.pad_for(plan, 1).place(plan)
    state = core.init_state(jax.random.key(2))
    params0 = check.flatten(state.params)
    base_key = jax.random.wrap_key_data(
        np.asarray(jax.random.key_data(state.base_key)))
    state, metrics = core.round_step(state, ds)
    assert core.row_updates is True
    params1 = check.flatten(state.params)

    clients = [{"x": host.x[c], "y": host.y[c], "num_samples": 6,
                "uid": int(host.client_uid[c]), "weight": 6.0}
               for c in range(3)]
    server = manifest.find_module("reference", "server_fedavg")
    want = fedround.reference_round(
        ref, server, algorithm, params0, None, clients, base_key, 0,
        steps=2, batch_size=2)
    delta = {k: params1[k] - params0[k] for k in params0}
    worst = check.worst_leaf(delta, want["param_delta"])
    assert worst["global_rel_l2"] < 1e-3 and worst["rel_l2"] < 1e-2, worst
    np.testing.assert_allclose(np.asarray(metrics.client_loss),
                               want["client_loss"], rtol=1e-4)
    assert int(metrics.clients_trained) == 3
    # Rows no step looked up did not move; the head's all did.
    seen = np.unique(host.x)
    moved = np.abs(delta["embed/embedding"]).sum(-1) > 0
    assert moved.any() and not moved[np.setdiff1d(np.arange(128), seen)].any()
    assert (np.abs(delta["head"]).sum(0) > 0).all()
    # The round's work counts, both kinds: 3 clients x 2 steps x 2
    # sequences, through 2 Mamba-2 layers (5 chunks a sequence) and 2
    # expert layers (top-6).
    named = core.describe_stats(np.asarray(metrics.model_stats))
    assert named["ssd_scan_tokens"] == 2 * (3 * 2 * 2 * L)
    assert named["ssd_scan_chunks"] == 2 * (3 * 2 * 2 * 5)
    assert named["moe_assignments_total"] == 2 * (3 * 2 * 2 * L * 6)
    assert named["moe_assignments_local"] == named[
        "moe_assignments_computed"] > 0

    x, y = make_central_text_eval_set(2**31 + 9, 4, L, 4, vocab_size=128)
    loss, acc = core.evaluate(state.params, x, y)
    flat = ref.prepare(params1)
    losses = [float(ref.sequence_loss(flat, jnp.asarray(row))) for row in x]
    assert loss == pytest.approx(np.mean(losses), rel=1e-4)
    assert 0 <= acc <= 1


def test_the_engine_takes_this_models_clients_one_at_a_time():
    plan = make_mesh_plan(devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="one at a time"):
        build_fedcore("nemotron_h", from_config("fedavg"), plan,
                      FedCoreConfig(block_clients=2, task="next_token"),
                      model_overrides=TINY, input_shape=(L,))
    with pytest.raises(ValueError, match="max_len"):
        get_model("nemotron_h").build(**dict(TINY, max_len=8)).init(
            jax.random.key(0), jnp.zeros((1, L), jnp.int32))
    with pytest.raises(ValueError, match="unknown layer letter"):
        get_model("nemotron_h").build(**dict(TINY, pattern="M-")).init(
            jax.random.key(0), jnp.zeros((1, L), jnp.int32))
