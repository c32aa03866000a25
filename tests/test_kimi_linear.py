"""The ``kimi_linear`` family at a small size on the CPU, against
``benchmark/reference/kimi_linear.py``: the chunked delta-rule scan against
the recurrence token by token (values and gradients, the largest decay
included), each mixer and the whole model (forward and the gradient of the
next-token loss), the expert layer's shares with the shared expert counted
once, and one federated round + evaluation through ``FedCore`` with the
embedding trained by rows and the scan's counts on the round's metrics.

Counts and correctness facts only: never a speed."""

import os

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
from olearning_sim_tpu.models import kimi_linear as km
from olearning_sim_tpu.models.decoder_parts import SwiGLU
from olearning_sim_tpu.models.moe import DroplessMoE
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

ref = manifest.find_module("reference", "kimi_linear")
F32 = jnp.float32
W, L = 32, 80               # two chunks, the second one padded
# Top-8 of 16, four held: the reference's published TOP_K.
TINY = dict(vocab_size=128, max_len=L, width=W,
            layer_types=["kda", "mla", "kda"], num_dense_layers=1, heads=2,
            kda_head_dim=16, kv_rank=16, qk_nope_dim=8, qk_rope_dim=4,
            v_dim=8, mlp_dim=48, moe_mlp_dim=24, num_experts=16,
            experts_per_token=8, held_experts=[0, 1, 2, 3])


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


@pytest.mark.parametrize("length,decay_scale,alike", [
    (km.CHUNK, 1.0, False),             # one chunk
    (3 * km.CHUNK, 1.0, False),         # several chunks
    (2 * km.CHUNK + 22, 1.0, False),    # a padded tail
    (3 * km.CHUNK, 16.0, False),        # A_log at its largest: exp(sum g)
                                        # underflows
    (km.CHUNK + km.SUB + 5, 1.0, False),        # ends inside a sub-block
    # exp(G_r - G_j) underflows from one sub-block to the next
    (2 * km.CHUNK + km.SUB + 5, 16.0, False),
    # near-identical keys, beta near 1, hardly a decay: I + A at its worst
    # conditioning
    (2 * km.CHUNK, 0.01, True),
])
def test_the_chunked_scan_is_the_recurrence_in_value_and_gradient(
        length, decay_scale, alike):
    rng = np.random.default_rng(length + int(decay_scale))
    n, H, K, V = 2, 2, 16, 8

    def unit(shape):
        u = rng.standard_normal(shape)
        if alike:
            u = rng.standard_normal(shape[:1] + (1,) + shape[2:]) + 1e-2 * u
        return u / np.linalg.norm(u, axis=-1, keepdims=True)

    q = jnp.asarray(unit((n, length, H, K)) / np.sqrt(K), F32)
    k = jnp.asarray(unit((n, length, H, K)), F32)
    v = jnp.asarray(rng.standard_normal((n, length, H, V)), F32)
    g = jnp.asarray(-decay_scale * np.logaddexp(
        0, rng.standard_normal((n, length, H, K))), F32)
    beta = jnp.asarray(1 / (1 + np.exp(-(
        np.full((n, length, H), 7.0) if alike
        else rng.standard_normal((n, length, H))))), F32)
    probe = jnp.asarray(rng.standard_normal((n, length, H, V)), F32)
    if decay_scale > 1:
        # A chunk's product of decays is below float32's smallest number,
        # and so is every sub-block's.
        assert float(g[:, :km.CHUNK].sum(1).max()) < -200
        assert max(float(g[:, i:i + km.SUB].sum(1).max())
                   for i in range(0, length - km.SUB, km.SUB)) < -88
    if alike:
        # Every pair of a chunk's keys at a cosine over 0.99: A is close to
        # the strictly lower triangle of ones.
        cos = np.einsum("nthc,nshc->nhts", k[:, :km.CHUNK], k[:, :km.CHUNK])
        assert float(cos.min()) > 0.99 and float(beta.min()) > 0.999

    def chunked(*a):
        o = km.chunk_scan(*a)
        return (o * probe).sum(), o

    def stepwise(*a):
        o = jnp.stack([ref.delta_rule(*(x[i] for x in a)) for i in range(n)])
        return (o * probe).sum(), o

    args = (q, k, v, g, beta)
    (_, got), got_g = jax.value_and_grad(
        chunked, argnums=tuple(range(5)), has_aux=True)(*args)
    (_, want), want_g = jax.value_and_grad(
        stepwise, argnums=tuple(range(5)), has_aux=True)(*args)
    _close(got, want)
    for a, b in zip(got_g, want_g):
        _close(a, b)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("what", ["triangular_solve", "pairwise_array"])
def test_the_scans_program_holds_no_chunk_wide_solve_or_pairwise_array(what):
    """One head, one sequence of two chunks at the published head width:
    in the program of the scan and its gradient, everything between a
    chunk's sub-blocks is a matrix product."""
    K = 128
    x = jax.ShapeDtypeStruct((1, 2 * km.CHUNK, 1, K), F32)
    beta = jax.ShapeDtypeStruct((1, 2 * km.CHUNK, 1), F32)
    program = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jax.checkpoint(km.chunk_scan)(*a).sum(),
        argnums=tuple(range(5))))(x, x, x, x, beta)
    equations = list(_equations(program.jaxpr))
    assert any(e.primitive.name == "dot_general" for e in equations)
    if what == "triangular_solve":
        rows = [e.invars[0].aval.shape[-1] for e in equations
                if e.primitive.name == "triangular_solve"]
        assert all(r <= km.SUB for r in rows), rows
    else:
        # A head-chunk's largest float32 array: the pairwise decays of its
        # sub-blocks, CHUNK x SUB x K (CHUNK x CHUNK x K before PR 36).
        largest = max(int(np.prod(v.aval.shape)) for e in equations
                      for v in e.outvars if v.aval.dtype == F32)
        assert largest == km.CHUNK * km.SUB * K, largest


def _checkpoints(jaxpr, inside=False):
    """Every ``jax.checkpoint`` equation of a jaxpr, at any depth: whether
    it lies inside another one, and the primitives it holds."""
    for eqn in jaxpr.eqns:
        held = eqn.primitive.name == "remat2"
        if held:
            yield inside, {e.primitive.name
                           for e in _equations(eqn.params["jaxpr"])}
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _checkpoints(sub, inside or held)


@pytest.mark.parametrize("layer_types,dense", [
    (["kda"], 1), (["kda"], 0), (["mla"], 0),
    (["kda", "kda", "kda", "mla", "kda"], 1),       # the benchmark cell's
], ids=["kda_dense", "kda_experts", "mla_experts", "stack"])
def test_the_backward_pass_computes_no_block_again(layer_types, dense):
    """The intent, pinned: in the gradient's program no ``jax.checkpoint``
    holds a block (a pre-norm's ``rsqrt`` beside a scan, attention scores
    or grouped products). What it holds, a layer: a KDA mixer's three parts
    (the scan's, which the backward function of ``ops/kda_scan.py``'s custom
    VJP makes, with the two chunk bodies inside it, again checkpoints; the
    two around it with the L2 and per-head norms and no scan), the chunk
    bodies of the forward scans and ``decoder_parts.attend``'s around an MLA mixer's
    scores (no norm inside it); a dense MLP none, and an expert layer none
    since PR 47 (its backward pass is a loop of ``models/moe.py``'s own over
    the row windows, which computes a window's hidden products again inside
    its ``while``). ``nn.remat`` around ``Block`` again adds
    one that holds everything; a part-level checkpoint dropped takes its
    own away (PERF.md section 6, PR 42, has what each buys)."""
    model = get_model("kimi_linear").build(**dict(
        TINY, layer_types=layer_types, num_dense_layers=dense), dtype=F32)
    tokens = jnp.asarray(np.random.default_rng(8).integers(1, 128, (2, L)))
    params = model.init(jax.random.key(1), tokens)["params"]
    held = list(_checkpoints(jax.make_jaxpr(jax.grad(
        lambda p: (model.apply({"params": p}, tokens) ** 2).mean()))(
            params).jaxpr))
    outer = [names for inside, names in held if not inside]
    kda, mla = layer_types.count("kda"), layer_types.count("mla")
    experts = len(layer_types) - dense
    assert not any("rsqrt" in names and names & {
        "scan", "reduce_max", "ragged_dot_general"} for names in outer)
    scans = [names for names in outer if "scan" in names]
    assert len(scans) == kda and all("remat2" in names for names in scans)
    # ``_intra_chunk`` and ``_chunk_step`` inside each scan's checkpoint.
    assert len(held) - len(outer) == 2 * kda
    around = [names for names in outer if "rsqrt" in names]
    assert len(around) == 2 * kda
    assert all("logistic" in names for names in around)
    scores = [names for names in outer if "reduce_max" in names]
    assert len(scores) == mla and all("exp" in names for names in scores)
    assert not any("ragged_dot_general" in names for names in outer)
    # The rest: the two chunk bodies of each forward scan, and of the scan
    # that ``jax.vjp`` traces in the custom VJP's backward function before
    # the checkpoint's own, whose result nothing reads: the compiler drops it
    # (``test_kda_scan_kernel.py`` counts the compiled loops).
    assert len(outer) == 7 * kda + mla


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_each_mixer_matches_the_reference(kind):
    module, reference = {
        "kda": (km.KDA(2, 16, dtype=F32),
                lambda p, x: ref.kda(p, "", x)),
        "mla": (km.MLA(2, 16, 8, 4, 8, dtype=F32),
                lambda p, x: ref.mla(p, "", x)),
    }[kind]
    x = _x(1)
    params = module.init(jax.random.key(0), x)["params"]
    if kind == "kda":
        # Seeded as the family seeds them: 1 <= exp(A_log) <= 16, and a
        # softplus(dt_bias) between 0.001 and 0.1.
        assert 0 <= float(params["A_log"].min()) <= float(
            params["A_log"].max()) <= np.log(16) + 1e-6
        step = np.asarray(jax.nn.softplus(params["dt_bias"]))
        assert 0.00099 <= step.min() and step.max() <= 0.1001

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


def test_a_kda_layer_counts_its_scans_tokens_and_chunks():
    layer = km.KDA(2, 16, dtype=F32)
    x = _x(2, n=3)
    params = layer.init(jax.random.key(0), x)["params"]
    _, inter = layer.apply({"params": params}, x, mutable=["intermediates"])
    (stats,) = inter["intermediates"]["kda_stats"]
    # On the CPU the plain-JAX scan ran: no chunk was the kernel's.
    assert np.asarray(stats).tolist() == [3 * L, 3 * 2, 0, 0, 0]
    # Two such layers' counts, gathered and named as the registry has it.
    counts = get_model("kimi_linear").work_counts
    row = counts.gather({"a": inter["intermediates"],
                         "b": inter["intermediates"]})
    assert counts.describe(np.asarray(row)) == {
        "kda_scan_tokens": 2 * 3 * L, "kda_scan_chunks": 2 * 3 * 2,
        "attend_pairs_needed": 0, "attend_pairs_computed": 0,
        "kda_scan_kernel_chunks": 0}


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """32 chips of 8 experts each: their partial sums, with the shared
    expert (what every chip computes alike) counted once, are the uncut
    reference's expert layer; so are the gradients of what they share."""
    experts, top_k, M = 256, 8, 12
    x = _x(3, n=1, length=24)
    full = DroplessMoE(experts, top_k, tuple(range(experts)), M,
                       routed_scaling_factor=ref.ROUTED_SCALING_FACTOR,
                       dtype=F32).init(jax.random.key(1), x)["params"]
    shared = SwiGLU(M, F32).init(jax.random.key(2), x)["params"]
    probe = _x(4, n=1, length=24)

    def uncut(p, s, x):
        y = ref.experts(p, "", x[0]) + ref.swiglu(
            x[0], s["w1"], s["w3"], s["w2"])
        return (y * probe[0]).sum(), y

    (_, want), want_g = jax.value_and_grad(
        uncut, argnums=(0, 2), has_aux=True)(_flat(full), shared, x)
    total = SwiGLU(M, F32).apply({"params": shared}, x)      # once
    g_x = jax.grad(lambda x: (SwiGLU(M, F32).apply(
        {"params": shared}, x) * probe).sum())(x)
    g_gate, local = jnp.zeros_like(full["gate"]), 0
    for chip in range(32):
        held = tuple(range(8 * chip, 8 * chip + 8))
        layer = DroplessMoE(experts, top_k, held, M,
                            routed_scaling_factor=ref.ROUTED_SCALING_FACTOR,
                            dtype=F32)
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


def test_the_whole_model_matches_the_reference():
    model = get_model("kimi_linear").build(**TINY, dtype=F32)
    tokens = jnp.asarray(np.random.default_rng(8).integers(1, 128, (3, L)),
                         jnp.int32)
    params = model.init(jax.random.key(1), tokens)["params"]
    flat = _flat(params)
    assert flat["head"].shape == (W, 128)                   # untied
    assert flat["layers_1/shared/w1"].shape == (W, 24)
    assert "layers_0/shared/w1" not in flat                 # the dense layer
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
    # the cell's check has to refuse (alpha = 1) reads otherwise.
    planted = manifest.load_module(
        os.path.join(os.path.dirname(manifest.HERE), "scripts"),
        "kimi_linear_planted_decay").leave_decay_out(
            manifest.find_module("reference", "kimi_linear"))
    plain = float(ref.sequence_loss(flat, tokens[0]))
    assert abs(float(planted.sequence_loss(flat, tokens[0]))
               - plain) > 1e-4 * plain


def test_the_references_program_is_not_left_in_the_compile_cache(monkeypatch):
    """Its executable is 52 MB of a capped cache at the published widths:
    while it compiles, nothing is written, and the setting comes back."""
    key = "jax_persistent_cache_min_compile_time_secs"
    before, seen = getattr(jax.config, key), []
    compiled = ref._sequence_value_and_grad
    monkeypatch.setattr(
        ref, "_sequence_value_and_grad",
        lambda *a: (seen.append(getattr(jax.config, key)), compiled(*a))[1])
    model = get_model("kimi_linear").build(**TINY, dtype=F32)
    tokens = np.random.default_rng(8).integers(1, 128, (2, L))
    params = model.init(jax.random.key(1), jnp.asarray(tokens))["params"]
    loss, grads = ref.loss_and_grad(ref.prepare(check.flatten(params)),
                                    tokens, None, [0.5, 0.5])
    assert seen == [float("inf")] * 2 and getattr(jax.config, key) == before
    assert np.isfinite(loss) and set(grads) == set(check.flatten(params))


def test_a_round_trains_the_embedding_by_rows_and_matches_the_reference():
    plan = make_mesh_plan(devices=jax.devices()[:1])
    cfg = FedCoreConfig(batch_size=2, max_local_steps=2, block_clients=1,
                        task="next_token", eval_batch_size=4)
    algorithm = {"name": "fedavg", "local_lr": 0.1, "server_lr": 1.0}
    core = build_fedcore(
        "kimi_linear", from_config("fedavg", local_lr=0.1), plan, cfg,
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
    # sequences, through 2 KDA layers (2 chunks a sequence) and 2 expert
    # layers (top-8).
    named = core.describe_stats(np.asarray(metrics.model_stats))
    assert named["kda_scan_tokens"] == 2 * (3 * 2 * 2 * L)
    assert named["kda_scan_chunks"] == 2 * (3 * 2 * 2 * 2)
    assert named["moe_assignments_total"] == 2 * (3 * 2 * 2 * L * 8)
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
        build_fedcore("kimi_linear", from_config("fedavg"), plan,
                      FedCoreConfig(block_clients=2, task="next_token"),
                      model_overrides=TINY, input_shape=(L,))
    with pytest.raises(ValueError, match="max_len"):
        get_model("kimi_linear").build(**dict(TINY, max_len=8)).init(
            jax.random.key(0), jnp.zeros((1, L), jnp.int32))
