"""The ``lfm2`` family and its dropless expert layer at a small size on the
CPU, against ``benchmark/reference/lfm2_moe.py``: every block kind and the
whole model (forward and the gradient of the next-token loss), the expert
layer's contract (the shares add up to the uncut layer, nothing is dropped
whatever the imbalance, ``expert_bias`` steers the selection only), and one
federated round + evaluation of the next-token task through ``FedCore``
against the plain reference round.

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
from olearning_sim_tpu.models import decoder_parts
from olearning_sim_tpu.models import lfm2 as lfm2_model
from olearning_sim_tpu.models.decoder_parts import describe_stats
from olearning_sim_tpu.models.moe import DroplessMoE
from olearning_sim_tpu.parallel.expert_parallel import ep_param_specs
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

ref = manifest.find_module("reference", "lfm2_moe")
F32 = jnp.float32
W, L = 64, 16
# Top-4 of 16, two held: the reference's published TOP_K.
TINY = dict(vocab_size=128, max_len=L, width=W,
            layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1, heads=4, kv_heads=2, mlp_dim=96,
            moe_mlp_dim=48, num_experts=16, experts_per_token=4,
            held_experts=[0, 1])


def _flat(tree, prefix=""):
    return {prefix + k: jnp.asarray(v) for k, v in check.flatten(tree).items()}


def _close(got, want, tol=2e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def _x(seed, n=2):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (n, L, W)), F32)


@pytest.mark.parametrize("kind", ["conv", "attention", "mlp"])
def test_each_dense_block_kind_matches_the_reference(kind):
    module, reference = {
        "conv": (lfm2_model.ShortConv(3, F32),
                 lambda p, x: ref.short_conv(p, "", x)),
        "attention": (lfm2_model.CausalGQA(4, 2, dtype=F32),
                      lambda p, x: ref.attention(p, "", x)),
        "mlp": (decoder_parts.SwiGLU(96, F32),
                lambda p, x: ref.swiglu(x, p["w1"], p["w3"], p["w2"])),
    }[kind]
    x = _x(1)
    params = module.init(jax.random.key(0), x)["params"]

    def program(p, x):
        return (module.apply({"params": p}, x) ** 2).sum()

    def plain(p, x):
        return sum((reference(p, x[i]) ** 2).sum() for i in range(x.shape[0]))

    _close(module.apply({"params": params}, x)[1],
           reference(_flat(params), x[1]))
    got = jax.grad(program, argnums=(0, 1))(params, x)
    want = jax.grad(plain, argnums=(0, 1))(_flat(params), x)
    _close(got[1], want[1])
    for name, g in _flat(got[0]).items():
        _close(g, want[0][name])


def _plain_attend(q, k, v):
    """The form ``decoder_parts.attend`` replaced (PR 45): all L x L scores, the
    upper triangle masked, one float32 softmax a row."""
    n_keys, D = k.shape[1], q.shape[-1]
    scores = jnp.einsum("nqgrd,nkgd->ngrqk", q, k,
                        preferred_element_type=F32) / np.sqrt(D)
    causal = np.tril(np.ones((n_keys, n_keys), bool))
    probs = jax.nn.softmax(
        jnp.where(causal, scores, jnp.finfo(F32).min), -1)
    return jnp.einsum("ngrqk,nkgd->nqgrd", probs.astype(q.dtype), v)


# A block of 16 queries, so that four blocks run in seconds here.
B = 16
# (key/value heads, query heads a key/value head, D, Dv): GQA; MLA's one
# query head a key head and 192-wide keys for 128-wide values; phi4flash's
# 64-wide keys for a pair's 128-wide values.
HEADS = {"gqa": (2, 3, 8, 8), "mla": (4, 1, 12, 8), "diff": (2, 2, 8, 16)}


def _qkv(length, heads, seed=0, lead=(2,)):
    G, R, D, Dv = HEADS[heads]
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(lead + shape), F32)
                 for shape in ((length, G, R, D), (length, G, D),
                               (length, G, Dv)))


def _loss_and_grads(attend, over_clients=False):
    """``sum(attend(q, k, v) ** 2)`` and its gradients, compiled."""
    program = jax.value_and_grad(
        lambda q, k, v: (attend(q, k, v) ** 2).sum(), (0, 1, 2))
    return jax.jit(jax.vmap(program) if over_clients else program)


def _blocked(q, k, v):
    return decoder_parts.attend(q, k, v, B)


@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("length", [B // 2, B, 2 * B, 3 * B + 37])
def test_attention_by_blocks_is_the_masked_l_by_l_attention(length, heads):
    """Values and the gradients of q, k and v: below a block, one block,
    whole blocks, and a last block shorter than the others."""
    q, k, v = _qkv(length, heads, seed=length)
    _close(jax.jit(_blocked)(q, k, v), _plain_attend(q, k, v), 1e-5)
    (got, got_grads), (want, want_grads) = (
        _loss_and_grads(f)(q, k, v) for f in (_blocked, _plain_attend))
    _close(got, want, 1e-5)
    for g, w in zip(got_grads, want_grads):
        _close(g, w, 1e-5)


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_attention_by_blocks_over_clients(heads):
    """Three clients, each with its own q, k and v, under ``jax.vmap``."""
    args = _qkv(3 * B + 37, heads, seed=4, lead=(3, 2))
    (got, got_grads), (want, want_grads) = (
        _loss_and_grads(f, over_clients=True)(*args)
        for f in (_blocked, _plain_attend))
    _close(got, want, 1e-5)
    for g, w in zip(got_grads, want_grads):
        _close(g, w, 1e-5)


def _equations(jaxpr):
    """Every equation of a jaxpr, at any depth."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _equations(inner)


def test_the_blocked_program_holds_no_l_by_l_array_and_one_checkpoint():
    """At four blocks: the float32 scores a head are the blocks'
    (``attend_pairs``), never L x L, forward and backward; one
    ``jax.checkpoint`` equation around all of them."""
    length = 4 * B
    q, k, v = _qkv(length, "gqa")
    heads = q.shape[0] * q.shape[2] * q.shape[3]
    forward = list(_equations(jax.make_jaxpr(_blocked)(q, k, v).jaxpr))
    scores = [e.outvars[0].aval.shape for e in forward
              if e.primitive.name == "exp"]
    assert len(scores) == 4 and {np.prod(s[:-2]) for s in scores} == {heads}
    assert sum(s[-2] * s[-1] for s in scores) == decoder_parts.attend_pairs(
        length, B)[1] == B * B * (1 + 2 + 3 + 4)
    backward = list(_equations(jax.make_jaxpr(jax.grad(
        lambda *a: (_blocked(*a) ** 2).sum(), (0, 1, 2)))(q, k, v).jaxpr))
    assert sum(e.primitive.name == "remat2" for e in backward) == 1
    assert any(e.primitive.name == "exp" for e in backward)
    for eqn in forward + backward:
        for var in eqn.outvars:
            assert np.prod(var.aval.shape) < heads * length * length, (
                eqn.primitive.name, var.aval.shape)


@pytest.mark.parametrize("length,block", [
    (1, 16), (8, 16), (16, 16), (17, 16), (64, 16), (85, 16),
    (1024, 256), (2048, 256), (2048, 512), (2085, 512)])
def test_attend_pairs_against_a_brute_count(length, block):
    rows = np.arange(length)
    # Row i is in the block that ends at key ``end[i]`` (exclusive).
    end = np.minimum((rows // block + 1) * block, length)
    assert decoder_parts.attend_pairs(length, block) == (
        int((rows + 1).sum()), int(end.sum()))
    if length <= block:
        assert decoder_parts.attend_pairs(length, block)[1] == length * length


def test_an_attention_layer_sows_the_pairs_of_its_sequences():
    layer = lfm2_model.CausalGQA(4, 2, dtype=F32)
    x = _x(2, n=3)
    params = layer.init(jax.random.key(0), x)["params"]
    _, inter = layer.apply({"params": params}, x, mutable=["intermediates"])
    (stats,) = inter["intermediates"]["lfm2_stats"]
    assert np.asarray(stats).tolist() == [3 * L * (L + 1) // 2, 3 * L * L]
    assert lfm2_model.STATS == ("attend_pairs_needed",
                                "attend_pairs_computed")


def _moe(held, top_k=2, experts=8, **kw):
    return DroplessMoE(experts, top_k, tuple(held), 48, dtype=F32, **kw)


def _moe_params(seed=0, experts=8, top_k=2):
    """The uncut layer's parameters (every expert held)."""
    layer = _moe(range(experts), top_k, experts)
    return layer.init(jax.random.key(seed), _x(0))["params"]


def _share(params, held):
    ids = np.asarray(held)
    return {k: (v[ids] if k.startswith("expert_w") else v)
            for k, v in params.items()}


def test_the_shares_add_up_to_the_uncut_reference_layer():
    """Four chips of two experts each: their partial sums, and their
    gradients of what every chip holds alike, add up to the uncut layer."""
    full, x = _moe_params(), _x(2)
    probe = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, L, W)), F32)

    def uncut(p, x):
        y = jnp.stack([ref.experts(p, "", x[i], top_k=2) for i in range(2)])
        return (y * probe).sum(), y

    (_, want), want_g = jax.value_and_grad(uncut, argnums=(0, 1),
                                           has_aux=True)(_flat(full), x)
    total, g_x = 0.0, 0.0
    g_gate = jnp.zeros_like(full["gate"])
    loads = []
    for held in ([0, 1], [2, 3], [4, 5], [6, 7]):
        layer = _moe(held)

        def part(p, x):
            y, inter = layer.apply({"params": p}, x,
                                   mutable=["intermediates"])
            return (y * probe).sum(), (y, inter)

        (_, (y, inter)), g = jax.value_and_grad(
            part, argnums=(0, 1), has_aux=True)(_share(full, held), x)
        total, g_x, g_gate = total + y, g_x + g[1], g_gate + g[0]["gate"]
        for name in ("expert_w1", "expert_w3", "expert_w2"):
            _close(g[0][name], want_g[0][name][np.asarray(held)])
        assert not np.asarray(g[0]["expert_bias"]).any()
        (stats,) = inter["intermediates"]["moe_stats"]
        loads.append(np.asarray(stats))
    _close(total, want)
    _close(g_x, want_g[1])
    _close(g_gate, want_g[0]["gate"])
    loads = np.stack(loads)
    # Every (token, slot) assignment lands on exactly one share.
    assert loads[:, 1].sum() == loads[0, 0] == 2 * L * 2
    assert (loads[:, 1] == loads[:, 2]).all()
    assert (loads[:, 3:].sum(1) == loads[:, 1]).all()


@pytest.mark.parametrize("favoured,local", [((0, 5), 2 * L), ((5, 6), 0)])
def test_nothing_is_dropped_and_nothing_computed_for_nothing(favoured, local):
    """Every token of the batch picks the same two experts: the held one
    takes all of them (a capacity would have dropped most), and where none
    is held the layer returns exact zeros."""
    full, x = _moe_params(seed=4), _x(5)
    bias = np.zeros(8, np.float32)
    bias[list(favoured)] = [10.0, 9.0]
    p = dict(_share(full, [0, 1]), expert_bias=jnp.asarray(bias))
    y, inter = _moe([0, 1]).apply({"params": p}, x,
                                  mutable=["intermediates"])
    (stats,) = inter["intermediates"]["moe_stats"]
    assert np.asarray(stats).tolist() == [2 * L * 2, local, local, local, 0]
    want = jnp.stack([ref.experts(_flat(p), "", x[i], top_k=2)
                      for i in range(2)])
    if local:
        assert float(jnp.abs(want).max()) > 1e-3
        _close(y, want)
    else:
        assert not np.asarray(y).any() and not np.asarray(want).any()
    named = describe_stats(np.asarray(stats)[None])
    assert named["moe_assignments_local"] == named[
        "moe_assignments_computed"] == local
    assert named["moe_expert_load_max"] == local


def test_expert_bias_steers_the_selection_and_never_the_weights():
    full, x = _moe_params(seed=6), _x(7, n=1)
    assert float(jnp.abs(full["expert_bias"]).max()) > 0    # seeded, not zero
    # ... and at a scale that moves some of 16 tokens' choices over 8
    # experts (the seeded scale is sized for a 64-wide router).
    full = dict(full, expert_bias=5 * full["expert_bias"])
    flat = _flat(full)
    chosen, weights = ref.route(flat, "", x[0], top_k=2)
    plain, _ = ref.route(dict(flat, expert_bias=jnp.zeros(8)), "", x[0],
                         top_k=2)
    moved = (np.sort(chosen, -1) != np.sort(plain, -1)).any(-1)
    assert 0 < moved.sum() < L          # some selections change, not all
    scores = np.asarray(jax.nn.sigmoid(x[0] @ flat["gate"]))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    _close(weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), 1e-6)
    # The program's layer makes the reference's choices, and the bias gets
    # no gradient.
    layer = _moe(range(8))
    _close(layer.apply({"params": full}, x)[0],
           ref.experts(flat, "", x[0], top_k=2))
    g = jax.grad(lambda p: (layer.apply({"params": p}, x) ** 2).sum())(full)
    assert not np.asarray(g["expert_bias"]).any()
    assert np.asarray(g["gate"]).any()


# The row windows (PR 47). Two of 16 experts held, top-2 of 16 tokens: an
# even router would send the held ones A / 8 = 4 of the A = 32 assignments,
# so with ``GROUPED_ROW_BLOCK`` at 4 a window is C = 8 sorted rows.
WINDOW = 8


def _steered(first, second, seed=0, gated=True):
    """A layer, its parameters and 16 tokens of which exactly the first
    ``first`` pick held expert 0 and the last ``second`` held expert 1:
    feature 0 (1) of a token is +-6 and only expert 0 (1) reads it, so its
    score is 0.998 or 0.002 beside the others' 0.2-0.8."""
    layer = DroplessMoE(16, 2, (0, 1), 40, dtype=F32, gated=gated)
    rng = np.random.default_rng(seed)
    x = 0.5 * rng.standard_normal((1, L, W))
    x[0, :, 0] = np.where(np.arange(L) < first, 6.0, -6.0)
    x[0, :, 1] = np.where(np.arange(L) >= L - second, 6.0, -6.0)
    x = jnp.asarray(x, F32)
    params = dict(layer.init(jax.random.key(seed), x)["params"])
    gate = 0.1 * rng.standard_normal((W, 16))
    gate[:2] = 0
    gate[0, 0] = gate[1, 1] = 1
    params["gate"] = jnp.asarray(gate, F32)
    return layer, params, x


def _plain_experts(p, x, held, top_k, gated):
    """The layer as a float32 loop over the held experts, every token
    through every one of them."""
    x = x[0]
    scores = jax.nn.sigmoid(jnp.dot(x, p["gate"], precision="highest"))
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["expert_bias"]), top_k)
    weights = jnp.take_along_axis(scores, chosen, -1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    y = jnp.zeros_like(x)
    for h, e in enumerate(held):
        a = jnp.dot(x, p["expert_w1"][h], precision="highest")
        hidden = (jax.nn.silu(a) * jnp.dot(
            x, p["expert_w3"][h], precision="highest") if gated
                  else jax.nn.relu(a) ** 2)
        y = y + (weights * (chosen == e)).sum(-1)[:, None] * jnp.dot(
            hidden, p["expert_w2"][h], precision="highest")
    return y[None]


def _windowed(layer, p, x):
    y, inter = layer.apply({"params": p}, x, mutable=["intermediates"])
    inter = inter["intermediates"]
    return y, (inter["moe_stats"][0], inter["moe_window_trips"][0])


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("first,second", [
    (0, 0),         # no row: no trip
    (3, 2),         # under one window
    (8, 0),         # exactly C
    (8, 1),         # C + 1: a second trip for one row
    (5, 6),         # a group cut by a window's edge
    (16, 0),        # every token on one held expert
    (16, 16),       # every assignment held: the most trips there can be
])
def test_the_windowed_layer_is_the_plain_loop_over_the_experts(
        first, second, gated, monkeypatch):
    """Value and every gradient (tokens, ``gate``, the expert matrices)
    whatever the held experts' load, and the trips are those the load
    needs: ceil(local / C)."""
    from olearning_sim_tpu.models import moe

    monkeypatch.setattr(moe, "GROUPED_ROW_BLOCK", 4)
    assert moe.window_rows(2 * L, 2, 16) == WINDOW
    layer, p, x = _steered(first, second, seed=first + second, gated=gated)
    probe = jnp.asarray(np.random.default_rng(9).standard_normal(
        (1, L, W)), F32)
    (_, (got, (stats, trips))), got_g = jax.value_and_grad(
        lambda p, x: ((_windowed(layer, p, x)[0] * probe).sum(),
                      _windowed(layer, p, x)), argnums=(0, 1),
        has_aux=True)(p, x)
    (_, want), want_g = jax.value_and_grad(
        lambda p, x: ((_plain_experts(p, x, (0, 1), 2, gated)
                       * probe).sum(),
                      _plain_experts(p, x, (0, 1), 2, gated)),
        argnums=(0, 1), has_aux=True)(p, x)
    local = first + second
    assert np.asarray(stats).tolist() == [
        2 * L, local, local, first, second]
    assert int(trips) == -(-local // WINDOW)
    _close(got, want)
    _close(got_g[1], want_g[1])
    for name in want_g[0]:
        if local or name != "expert_bias":
            _close(got_g[0][name], want_g[0][name])
    assert not np.asarray(got_g[0]["expert_bias"]).any()
    if not local:
        assert not np.asarray(got).any()
        assert not any(np.asarray(g).any() for g in got_g[0].values())


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
def test_a_block_of_clients_whose_trip_counts_differ(gated, monkeypatch):
    """``jax.vmap`` over two clients' weights and tokens, one trip for the
    first and three for the second: each is what it is alone."""
    from olearning_sim_tpu.models import moe

    monkeypatch.setattr(moe, "GROUPED_ROW_BLOCK", 4)
    (layer, p0, x0), (_, p1, x1) = (
        _steered(3, 2, seed=1, gated=gated),
        _steered(16, 5, seed=2, gated=gated))
    probe = jnp.asarray(np.random.default_rng(9).standard_normal(
        (1, L, W)), F32)

    def one(p, x):
        (_, (_, counts)), g = jax.value_and_grad(
            lambda p, x: ((_windowed(layer, p, x)[0] * probe).sum(),
                          _windowed(layer, p, x)), argnums=(0, 1),
            has_aux=True)(p, x)
        return counts, g

    (stats, trips), both = jax.vmap(one)(
        jax.tree.map(lambda a, b: jnp.stack([a, b]), p0, p1),
        jnp.stack([x0, x1]))
    assert np.asarray(trips).tolist() == [1, 3]
    assert np.asarray(stats)[:, 1:3].tolist() == [[5, 5], [21, 21]]
    for i, (p, x) in enumerate([(p0, x0), (p1, x1)]):
        _, alone = one(p, x)
        for got, want in zip(jax.tree.leaves(both), jax.tree.leaves(alone)):
            _close(got[i], want, 1e-5)


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
def test_the_windows_inside_a_checkpoint(gated, monkeypatch):
    """Computed again in a caller's backward pass (``nn.remat`` around a
    block, ``jax.checkpoint`` around a part): the same gradients."""
    from olearning_sim_tpu.models import moe

    monkeypatch.setattr(moe, "GROUPED_ROW_BLOCK", 4)
    layer, p, x = _steered(16, 5, seed=3, gated=gated)

    def loss(p, x):
        return (layer.apply({"params": p}, x) ** 2).sum()

    want = jax.grad(loss, argnums=(0, 1))(p, x)
    got = jax.grad(jax.checkpoint(loss), argnums=(0, 1))(p, x)
    assert np.asarray(want[1]).any()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 1e-6)


def _equations_in_loops(jaxpr, looped=False):
    """Every equation of a jaxpr at any depth, with whether a ``while``
    holds it."""
    for eqn in jaxpr.eqns:
        yield eqn, looped
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations_in_loops(
                sub, looped or eqn.primitive.name == "while")


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
def test_the_windowed_program_holds_no_array_a_row_an_assignment(
        gated, monkeypatch):
    """The forward-and-backward program of a tiny expert layer (S = 24
    tokens, K = 2, A = 48, C = 12; every other size a number of its own):
    no floating-point array has a row an assignment (A rows, or ``[S, K]``
    before a wider axis) beyond the routing vectors' K columns, and every
    grouped product is inside a ``while``: the forward loop's, or the
    backward loop's, where the window's products run again."""
    from olearning_sim_tpu.models import moe

    monkeypatch.setattr(moe, "GROUPED_ROW_BLOCK", 4)
    S, K = 24, 2
    layer = DroplessMoE(16, K, (0, 1), 40, dtype=F32, gated=gated)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, S, W)), F32)
    p = layer.init(jax.random.key(0), x)["params"]
    assert moe.window_rows(S * K, 2, 16) == 12
    program = jax.make_jaxpr(jax.grad(
        lambda p, x: (layer.apply({"params": p}, x) ** 2).sum(),
        argnums=(0, 1)))(p, x)
    products, rows = [], set()
    for eqn, looped in _equations_in_loops(program.jaxpr):
        if eqn.primitive.name.startswith("ragged_dot"):
            products.append(looped)
        for var in eqn.outvars:
            shape = var.aval.shape
            if not jnp.issubdtype(var.aval.dtype, jnp.floating):
                continue
            rows.add(shape[:1])
            assert not (shape[:1] == (S * K,) and np.prod(shape[1:]) > K), (
                eqn.primitive.name, shape)
            assert not (shape[:2] == (S, K) and len(shape) > 2), (
                eqn.primitive.name, shape)
    # Forward 3 (2), the same again in the backward loop (the last of them
    # unused there: the compiler drops it), and there the products of two
    # cotangents a forward one.
    assert len(products) == (12 if gated else 8)
    assert all(products)
    assert (12,) in rows and (S,) in rows


def test_the_whole_model_matches_the_reference():
    model = get_model("lfm2").build(**TINY, dtype=F32)
    tokens = jnp.asarray(np.random.default_rng(8).integers(1, 128, (3, L)),
                         jnp.int32)
    params = model.init(jax.random.key(1), tokens)["params"]
    flat = _flat(params)
    for i in range(3):
        _close(model.apply({"params": params}, tokens)[i],
               ref.forward(flat, tokens[i]))
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


def test_a_next_token_round_and_evaluation_match_the_reference_round():
    plan = make_mesh_plan(devices=jax.devices()[:1])
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=1,
                        task="next_token", eval_batch_size=4)
    algorithm = {"name": "fedavg", "local_lr": 0.1, "server_lr": 1.0}
    core = build_fedcore(
        "lfm2", from_config("fedavg", local_lr=0.1), plan, cfg,
        model_overrides=dict(TINY, dtype=F32), input_shape=(L,))
    assert core.use_multiplicity(10, (L,), np.int32) is False
    host = make_synthetic_text_dataset(
        2**31 + 9, 4, 10, L, num_classes=4, vocab_size=128,
        dirichlet_alpha=0.3)
    ds = host.pad_for(plan, 1).place(plan)
    state = core.init_state(jax.random.key(2))
    params0 = check.flatten(state.params)
    base_key = jax.random.wrap_key_data(
        np.asarray(jax.random.key_data(state.base_key)))
    state, metrics = core.round_step(state, ds)
    params1 = check.flatten(state.params)

    clients = [{"x": host.x[c], "y": host.y[c], "num_samples": 10,
                "uid": int(host.client_uid[c]), "weight": 10.0}
               for c in range(4)]
    server = manifest.find_module("reference", "server_fedavg")
    want = fedround.reference_round(
        ref, server, algorithm, params0, None, clients, base_key, 0,
        steps=2, batch_size=4)
    delta = {k: params1[k] - params0[k] for k in params0}
    worst = check.worst_leaf(delta, want["param_delta"])
    assert worst["global_rel_l2"] < 1e-3 and worst["rel_l2"] < 1e-2, worst
    np.testing.assert_allclose(np.asarray(metrics.client_loss),
                               want["client_loss"], rtol=1e-4)
    assert int(metrics.clients_trained) == 4
    # expert_bias: no gradient, so no delta, whatever the server step.
    for name, d in delta.items():
        assert d.any() != name.endswith("expert_bias"), name
    # The round's work counts: 4 clients x 2 steps x 4 sequences x L tokens
    # x top-4, a layer; every local assignment computed.
    named = core.describe_stats(np.asarray(metrics.model_stats))
    assert named["moe_assignments_total"] == 2 * (4 * 2 * 4 * L * 4)
    assert named["moe_assignments_local"] == named[
        "moe_assignments_computed"] > 0
    assert named["moe_expert_load_max"] >= named["moe_expert_load_mean"] > 0
    # One attention layer: a sequence's causal half, and its one block.
    assert named["attend_pairs_needed"] == 4 * 2 * 4 * (L * (L + 1) // 2)
    assert named["attend_pairs_computed"] == 4 * 2 * 4 * L * L

    x, y = make_central_text_eval_set(2**31 + 9, 8, L, 4, vocab_size=128)
    loss, acc = core.evaluate(state.params, x, y)
    flat = ref.prepare(params1)
    losses, hits = [], []
    for row in x:
        logits = ref.forward(flat, jnp.asarray(row))
        losses.append(float(ref.sequence_loss(flat, jnp.asarray(row))))
        hits.append(np.mean(np.asarray(logits[:-1].argmax(-1)) == row[1:]))
    assert loss == pytest.approx(np.mean(losses), rel=1e-4)
    assert acc == pytest.approx(np.mean(hits), abs=1e-6) and 0 <= acc <= 1


def test_the_engine_takes_this_models_clients_one_at_a_time():
    plan = make_mesh_plan(devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="one at a time"):
        build_fedcore("lfm2", from_config("fedavg"), plan,
                      FedCoreConfig(block_clients=2, task="next_token"),
                      model_overrides=TINY, input_shape=(L,))
    with pytest.raises(ValueError, match="unknown fedcore task"):
        FedCoreConfig(task="regression")
    with pytest.raises(ValueError, match="unknown fedcore config keys"):
        FedCoreConfig.from_dict({"task": "next_token"})   # task_type sets it


def test_ep_param_specs_shard_the_dropless_layers_expert_leaves():
    from jax.sharding import PartitionSpec as P

    shapes = jax.eval_shape(lambda: _moe_params())
    specs = ep_param_specs({"moe": shapes}, ep=4)["moe"]
    for name in ("expert_w1", "expert_w3", "expert_w2"):
        assert specs[name] == P("ep", None, None), name
    # The router is whole on every chip: its kernel and its bias.
    assert specs["gate"] == P() and specs["expert_bias"] == P()
