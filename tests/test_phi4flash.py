"""The ``phi4flash`` family at a small size on the CPU, against
``benchmark/reference/phi4flash.py``: the chunked selective scan against the
recurrence token by token (values and all gradients, several chunks, decays
near 0 and near 1), the banded window against L x L scores under the mask
and the window's edge at the published 512, the kind of all 32 published
layers, the whole model over a slice that holds all five kinds (forward, loss
and every leaf's gradient), the gradients that flow back into the layers
that hand on their memory and their keys and values, the vocabulary's eight
shares side by side, the parameter count from shapes, and one federated
round + evaluation through ``FedCore`` with the model's counts on the
round's metrics.

Counts and correctness facts only: never a speed."""

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
from olearning_sim_tpu.models import phi4flash as pf
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

F32 = jnp.float32
W, L, WINDOW = 32, 80, 16       # five whole windows, two scan chunks
# Heads of 8 (the lambda vectors' length), layers 15-19 = S, M*, F, G, C.
TINY = dict(vocab_size=128, max_len=L, width=W, layer_slice=[15, 19],
            heads=4, kv_heads=2, mlp_dim=48, window=WINDOW, d_inner=64,
            d_state=4, d_conv=4, dt_rank=2)


def _reference(window=WINDOW, first_layer=15):
    """A copy of the reference told the tiny window (its ``WINDOW`` and
    ``FIRST_LAYER`` are the published constants)."""
    ref = manifest.find_module("reference", "phi4flash")
    ref.WINDOW, ref.FIRST_LAYER = window, first_layer
    return ref


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert np.isfinite(got).all()
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def _params(model, tokens, seed=1):
    """Seeded weights with every bias, lambda vector and norm scale moved
    off its initial constant, so that none of them is tested at 0 or 1."""
    params = model.init(jax.random.key(seed), jnp.asarray(tokens[:1]))[
        "params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _tokens(seed, n=3):
    return np.random.default_rng(seed).integers(1, 128, (n, L)).astype(
        np.int32)


def _weighted_loss(model, tokens, sw):
    def loss(params):
        logits = model.apply({"params": params}, jnp.asarray(tokens))
        logp = jax.nn.log_softmax(logits[:, :-1], -1)
        ce = -jnp.take_along_axis(
            logp, jnp.asarray(tokens)[:, 1:, None], -1)[..., 0].mean(-1)
        return (jnp.asarray(sw) * ce).sum()
    return loss


@pytest.mark.parametrize("i,kind", enumerate(
    ["M", "S"] * 8 + ["M*", "F"] + ["G", "C"] * 7))
def test_the_kind_of_every_published_layer(i, kind):
    assert pf.layer_kind(i) == kind == _reference().kind(i)
    if i == 31:
        kinds = [pf.layer_kind(j) for j in range(32)]
        assert [kinds.count(k) for k in ("M", "S", "M*", "F", "G", "C")] == [
            8, 8, 1, 1, 7, 7]
        # The only five consecutive layers that hold every kind of
        # computation (M* is an M that also hands on its memory).
        assert [j for j in range(28) if set(kinds[j:j + 5]) >= {
            "S", "M*", "F", "G", "C"}] == [15]


@pytest.mark.parametrize("length,chunk,dt_scale", [
    (80, 16, 1.0),      # five chunks
    (70, 16, 1.0),      # a tail shorter than a chunk
    (48, 16, 40.0),     # decays near 0: exp(-dt A) underflows within a chunk
    (48, 16, 1e-4),     # decays near 1: the state carries over every chunk
    (24, 64, 1.0),      # one chunk longer than the sequence
])
def test_the_chunked_scan_is_the_recurrence_in_value_and_all_gradients(
        length, chunk, dt_scale):
    ref = _reference()
    n, D, N = 2, 12, 4
    ks = jax.random.split(jax.random.key(length + chunk), 6)
    x = jax.random.normal(ks[0], (n, length, D))
    dt = dt_scale * jax.nn.softplus(jax.random.normal(ks[1], (n, length, D)))
    A = -jax.random.uniform(ks[2], (D, N), minval=0.5, maxval=16.0)
    B = jax.random.normal(ks[3], (n, length, N))
    C = jax.random.normal(ks[4], (n, length, N))
    w = jax.random.normal(ks[5], (n, length, D))

    def program(x, dt, A, B, C):
        return (pf.selective_scan(x, dt, A, B, C, chunk) * w).sum()

    def token_loop(x, dt, A, B, C):
        return sum((ref.recurrence(x[i], dt[i], A, B[i], C[i]) * w[i]).sum()
                   for i in range(n))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(pf.selective_scan, static_argnums=5)(
            x, dt, A, B, C, chunk)
        want = jax.jit(jax.vmap(ref.recurrence, (0, 0, None, 0, 0)))(
            x, dt, A, B, C)
        grads = jax.jit(jax.grad(program, argnums=(0, 1, 2, 3, 4)))(
            x, dt, A, B, C)
        wants = jax.jit(jax.grad(token_loop, argnums=(0, 1, 2, 3, 4)))(
            x, dt, A, B, C)
    _close(got, want, 1e-5)
    for g, wanted in zip(grads, wants):
        _close(g, wanted, 1e-4)
    if dt_scale == 40.0:        # the decays really are near 0 ...
        assert float(jnp.exp(dt[..., None] * A).max()) < 0.05
    if dt_scale == 1e-4:        # ... and near 1
        assert float(jnp.exp(dt[..., None] * A).min()) > 0.99


def _masked_attention(q, k, v, window):
    """L x L scores under the causal-and-window mask, float32."""
    L_ = q.shape[1]
    scores = jnp.einsum("nqgrd,nkgd->ngrqk", q, k) / np.sqrt(q.shape[-1])
    t, s = np.arange(L_)[:, None], np.arange(L_)[None, :]
    seen = (s <= t) & (t - s <= window - 1)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return jnp.einsum("ngrqk,nkgd->nqgrd", probs, v)


@pytest.mark.parametrize("length,window", [
    (80, 16),       # five whole blocks
    (75, 16),       # a tail shorter than a block
    (16, 16),       # one block: the window is the causal prefix
    (10, 16),       # shorter than the window
    (33, 16),       # one key in the third block
])
def test_the_banded_window_is_the_masked_l_by_l_attention(length, window):
    ks = jax.random.split(jax.random.key(length), 4)
    q = jax.random.normal(ks[0], (2, length, 2, 2, 8))
    k = jax.random.normal(ks[1], (2, length, 2, 8))
    v = jax.random.normal(ks[2], (2, length, 2, 16))
    w = jax.random.normal(ks[3], (2, length, 2, 2, 16))
    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(
            lambda *a: (pf.window_attend(*a, window) * w).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        want, wants = jax.jit(jax.value_and_grad(
            lambda *a: (_masked_attention(*a, window) * w).sum(),
            argnums=(0, 1, 2)))(q, k, v)
        _close(jax.jit(pf.window_attend, static_argnums=3)(q, k, v, window),
               _masked_attention(q, k, v, window), 1e-5)
    _close(got, want, 1e-5)
    for g, wanted in zip(grads, wants):
        _close(g, wanted, 1e-4)
    needed, computed = pf.window_pairs(length, window)
    t, s = np.arange(length)[:, None], np.arange(length)[None, :]
    assert needed == int(((s <= t) & (t - s <= window - 1)).sum())
    assert needed == _reference().window_pairs(length, window)
    blocks = -(-length // window)
    assert computed == ((2 * blocks - 1) * window ** 2 if blocks > 1
                        else length ** 2)


def test_the_windows_edge_at_the_published_512():
    """Query t sees key t - 511 and not key t - 512, and the cell's 2,048
    tokens are scored in seven 512 x 512 blocks a head, not L x L."""
    length, t = 1100, 1050
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (1, length, 1, 1, 4))
    k = jax.random.normal(ks[1], (1, length, 1, 4))
    v = jax.random.normal(ks[2], (1, length, 1, 4))
    attend = jax.jit(lambda v: pf.window_attend(q, k, v, 512))
    out = attend(v)

    def moved_by(s):
        return float(jnp.abs(
            attend(v.at[0, s].add(100.0))[0, t] - out[0, t]).max())

    assert moved_by(t - 511) > 1e-3 and moved_by(t) > 1e-3
    assert moved_by(t - 512) == 0.0 and moved_by(t + 1) == 0.0
    needed, computed = pf.window_pairs(2048, 512)
    assert (needed, computed) == (917_760, 7 * 512 * 512)
    assert computed / needed == pytest.approx(1.9994, abs=1e-4)
    assert 2048 * 2048 / needed == pytest.approx(4.57, abs=0.005)


def test_the_whole_model_matches_the_reference_in_loss_and_every_gradient():
    ref = _reference()
    model = get_model("phi4flash").build(**TINY, dtype=F32)
    tokens = _tokens(3)
    params = _params(model, tokens)
    flat = ref.prepare(check.flatten(params))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, jnp.asarray(tokens))
        want = jax.jit(jax.vmap(ref.forward, (None, 0)))(
            {k: jnp.asarray(v) for k, v in flat.items()},
            jnp.asarray(tokens))
    assert got.shape == (3, L, 128) and got.dtype == F32
    _close(got, want, 1e-4)
    sw = np.array([0.5, 0.0, 0.5], np.float32)
    with jax.default_matmul_precision("highest"):
        wanted_loss, wanted = jax.jit(jax.value_and_grad(
            _weighted_loss(model, tokens, sw)))(params)
    loss, grads = ref.loss_and_grad(flat, tokens, None, sw)
    assert loss == pytest.approx(float(wanted_loss), rel=1e-5)
    assert set(grads) == set(check.flatten(wanted))
    worst = check.worst_leaf(
        {k: np.asarray(v) for k, v in check.flatten(wanted).items()}, grads)
    assert worst["rel_l2"] < 1e-3 and worst["global_rel_l2"] < 1e-4, worst
    # In bfloat16 the program is the same function, a rounding apart.
    model16 = get_model("phi4flash").build(**TINY)
    got16 = jax.jit(lambda p, t: model16.apply({"params": p}, t))(
        params, jnp.asarray(tokens))
    assert float(jnp.linalg.norm(got16 - want)) < 0.05 * float(
        jnp.linalg.norm(want))


def test_the_handed_on_values_gradients_reach_the_layers_that_made_them():
    """M*'s and F's parameters take gradient through the G and C layers
    that read their memory and their keys and values: the program's
    gradients are the reference's, and they are not the gradients of a
    reference whose G and C read those values as constants."""
    ref, cut = _reference(), _reference()
    gmu, attention = cut.gmu, cut.diff_attention
    cut.gmu = lambda p, prefix, u, m: gmu(
        p, prefix, u, jax.lax.stop_gradient(m))
    cut.diff_attention = lambda p, prefix, u, index, seen, kv=None: attention(
        p, prefix, u, index, seen,
        None if kv is None else jax.lax.stop_gradient(kv))
    model = get_model("phi4flash").build(**TINY, dtype=F32)
    tokens = _tokens(4, n=2)
    params = _params(model, tokens, seed=6)
    flat = ref.prepare(check.flatten(params))
    sw = np.array([0.5, 0.5], np.float32)
    with jax.default_matmul_precision("highest"):
        got = check.flatten(jax.jit(jax.grad(
            _weighted_loss(model, tokens, sw)))(params))
    _, whole = ref.loss_and_grad(flat, tokens, None, sw)
    _, without = cut.loss_and_grad(flat, tokens, None, sw)

    def gap(a, b):
        return float(np.linalg.norm(np.asarray(a) - b) / np.linalg.norm(b))

    made_them = [k for k in got if k.startswith(("layers_1/mamba/",
                                                 "layers_2/attn/qkv_"))]
    assert len(made_them) == 9 + 2
    for leaf in made_them:
        assert gap(got[leaf], whole[leaf]) < 1e-3, leaf
        assert gap(without[leaf], whole[leaf]) > 0.05, leaf
    # The layers after them read no constant of their own: untouched.
    for leaf in ("layers_4/attn/q_proj", "layers_3/gmu/in_proj",
                 "layers_4/mlp/w2"):
        assert gap(without[leaf], whole[leaf]) < 1e-5, leaf
    # A slice that starts after the layers that hand on has nothing to read.
    for first, names in ((18, "16"), (19, "17")):
        with pytest.raises(ValueError, match=f"reads what layer {names}"):
            get_model("phi4flash").build(
                **dict(TINY, layer_slice=[first, 19])).init(
                jax.random.key(0), jnp.asarray(tokens))


def test_the_eight_vocabulary_shares_side_by_side_are_the_whole_tables():
    """A chip's logits are the final hidden state against the rows it
    holds and nothing else: with the ids drawn inside the first share, a
    model that holds the first share's rows (to look the ids up) and share
    j's gives, for share j, the whole table's logits of those rows."""
    whole = get_model("phi4flash").build(**TINY)
    tokens = np.random.default_rng(7).integers(1, 16, (2, L)).astype(np.int32)
    params = _params(whole, tokens, seed=8)

    def logits(model):
        apply = jax.jit(lambda p, t: model.apply({"params": p}, t))
        return lambda rows: np.asarray(apply(
            dict(params, embed={"embedding": rows}), jnp.asarray(tokens)))

    table = params["embed"]["embedding"]
    want = logits(whole)(table)
    sides = [logits(get_model("phi4flash").build(
        **dict(TINY, vocab_size=16)))(table[:16])]
    two = logits(get_model("phi4flash").build(**dict(TINY, vocab_size=32)))
    for j in range(1, 8):
        sides.append(two(jnp.concatenate(
            [table[:16], table[16 * j:16 * j + 16]]))[..., 16:])
    # The same products row by row; a product of another shape may add in
    # another order.
    np.testing.assert_allclose(np.concatenate(sides, -1), want,
                               rtol=1e-4, atol=1e-6)


def test_the_parameter_count_from_shapes_at_the_published_widths():
    spec = get_model("phi4flash")
    model = spec.build(vocab_size=25008, max_len=2048, layer_slice=[15, 19])
    leaves, _ = jax.tree_util.tree_flatten_with_path(jax.eval_shape(
        lambda x: model.init(jax.random.key(0), x),
        jax.ShapeDtypeStruct((1, 2048), np.int32))["params"])
    shapes = {"/".join(p.key for p in path): leaf for path, leaf in leaves}

    def count(prefix):
        return sum(int(np.prod(v.shape)) for k, v in shapes.items()
                   if k.startswith(prefix))

    assert count("layers_1/mamba/") == 41_241_600
    assert count("layers_0/attn/") == count("layers_2/attn/") == 19_668_864
    assert count("layers_4/attn/") == 13_112_704
    assert count("layers_3/gmu/") == 26_214_400
    assert all(count(f"layers_{j}/mlp/") == 78_643_200 for j in range(5))
    assert [count(f"layers_{j}/") for j in range(5)] == [
        98_322_304, 119_895_040, 98_322_304, 104_867_840, 91_766_144]
    assert count("embed/") == 64_020_480 and count("final_norm/") == 5_120
    assert count("") == 577_199_232
    assert not any(k.startswith(("head", "lm_head")) for k in shapes)  # tied
    assert spec.defaults["layer_slice"] == [0, 31]
    assert spec.vmap_clients is False


def test_the_references_program_is_not_left_in_the_compile_cache(monkeypatch):
    """While it compiles, nothing is written to a capped cache, and the
    setting comes back."""
    ref = _reference()
    key = "jax_persistent_cache_min_compile_time_secs"
    before, seen = getattr(jax.config, key), []
    compiled = ref._sequence_value_and_grad
    monkeypatch.setattr(
        ref, "_sequence_value_and_grad",
        lambda *a: (seen.append(getattr(jax.config, key)), compiled(*a))[1])
    model = get_model("phi4flash").build(**TINY, dtype=F32)
    tokens = _tokens(8, n=2)
    params = model.init(jax.random.key(1), jnp.asarray(tokens))["params"]
    loss, grads = ref.loss_and_grad(ref.prepare(check.flatten(params)),
                                    tokens, None, [0.5, 0.5])
    assert seen == [float("inf")] * 2 and getattr(jax.config, key) == before
    assert np.isfinite(loss) and set(grads) == set(check.flatten(params))
    assert all(isinstance(v, np.ndarray) for v in ref.prepare(
        check.flatten(params)).values())        # kept on the host


def test_a_round_and_evaluation_match_the_reference_round_and_count_their_work():
    ref = _reference()
    plan = make_mesh_plan(devices=jax.devices()[:1])
    cfg = FedCoreConfig(batch_size=2, max_local_steps=2, block_clients=1,
                        task="next_token", eval_batch_size=4)
    algorithm = {"name": "fedavg", "local_lr": 0.1, "server_lr": 1.0}
    core = build_fedcore(
        "phi4flash", from_config("fedavg", local_lr=0.1), plan, cfg,
        model_overrides=dict(TINY, dtype=F32), input_shape=(L,))
    assert core.vmap_clients is False
    host = make_synthetic_text_dataset(
        2**31 + 9, 3, 6, L, num_classes=4, vocab_size=128,
        dirichlet_alpha=0.3)
    ds = host.pad_for(plan, 1).place(plan)
    state = core.init_state(jax.random.key(2))
    params0 = check.flatten(state.params)
    base_key = jax.random.wrap_key_data(
        np.asarray(jax.random.key_data(state.base_key)))
    state, metrics = core.round_step(state, ds)
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
    # The tied table takes a dense gradient through the head: every row moves.
    assert (np.abs(delta["embed/embedding"]).sum(-1) > 0).all()
    # The round's work counts: 3 clients x 2 steps x 2 sequences through one
    # scan layer (2 chunks of 64 a sequence), one window layer (5 blocks) and
    # the F and C layers' causal prefix (one block: L x L).
    named = core.describe_stats(np.asarray(metrics.model_stats))
    sequences = 3 * 2 * 2
    assert named == {
        "sscan_tokens": sequences * L, "sscan_chunks": sequences * 2,
        "window_attn_pairs_needed": sequences * pf.window_pairs(L, WINDOW)[0],
        "window_attn_pairs_computed": sequences * 9 * WINDOW * WINDOW,
        "attend_pairs_needed": sequences * 2 * (L * (L + 1) // 2),
        "attend_pairs_computed": sequences * 2 * L * L}

    x, y = make_central_text_eval_set(2**31 + 9, 4, L, 4, vocab_size=128)
    loss, acc = core.evaluate(state.params, x, y)
    flat = ref.prepare(params1)
    losses = [float(ref.sequence_loss(flat, jnp.asarray(row))) for row in x]
    assert loss == pytest.approx(np.mean(losses), rel=1e-4)
    assert 0 <= acc <= 1


def test_the_engine_takes_this_models_clients_one_at_a_time():
    plan = make_mesh_plan(devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="one at a time"):
        build_fedcore("phi4flash", from_config("fedavg"), plan,
                      FedCoreConfig(block_clients=2, task="next_token"),
                      model_overrides=TINY, input_shape=(L,))
    with pytest.raises(ValueError, match="max_len"):
        get_model("phi4flash").build(**dict(TINY, max_len=8)).init(
            jax.random.key(0), jnp.zeros((1, L), jnp.int32))
    with pytest.raises(ValueError, match="not inside the 32 published"):
        get_model("phi4flash").build(**dict(TINY, layer_slice=[30, 32])).init(
            jax.random.key(0), jnp.zeros((1, L), jnp.int32))
