"""Long-context sequence parallelism: ring attention reachable end to end.

The critical property: a model TRAINED with dense attention evaluates
bit-for-bit-compatibly (same param tree) under ring attention with the
sequence sharded over sp — so long-context eval of FL global models is a
mesh knob, not a retrain.
"""

import numpy as np
import jax
import pytest

from olearning_sim_tpu.models import get_model
from olearning_sim_tpu.parallel.long_context import sp_evaluate, sp_forward
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

OVERRIDES = dict(vocab_size=96, max_len=32, width=32, depth=2, heads=4,
                 mlp_dim=64, num_classes=3)


def build_pair():
    spec = get_model("distilbert")
    dense = spec.build(**OVERRIDES)
    ring = spec.build(**OVERRIDES, attention_impl="ring")
    tokens = np.array(
        jax.random.randint(jax.random.key(1), (8, 32), 1, 96), np.int32
    )
    # pad tail of some rows to exercise masking across chunks (with sp=4
    # the chunks are 8 tokens: row 2's padding starts mid-chunk-2, row 5's
    # mid-chunk-1, so partially-masked K/V chunks are always in play)
    tokens[2, 20:] = 0
    tokens[5, 9:] = 0
    params = dense.init(jax.random.key(0), tokens[:1])["params"]
    return dense, ring, params, tokens


def test_ring_params_compatible_and_match_dense():
    dense, ring, params, tokens = build_pair()
    plan = make_mesh_plan(dp=2, mp=1, sp=4)
    ref = dense.apply({"params": params}, tokens)
    got = np.asarray(sp_forward(ring, params, tokens, plan))
    np.testing.assert_allclose(np.asarray(ref), got, atol=2e-2, rtol=2e-2)


def test_sp_evaluate_matches_dense_eval():
    import optax

    dense, ring, params, tokens = build_pair()
    labels = np.asarray(tokens[:, 0] % 3, np.int32)
    plan = make_mesh_plan(dp=2, mp=1, sp=4)
    loss, acc = sp_evaluate(ring, params, tokens, labels, plan, batch=6)
    ref_logits = np.asarray(dense.apply({"params": params}, tokens))
    ref_loss = float(optax.softmax_cross_entropy_with_integer_labels(
        ref_logits, labels).mean())
    ref_acc = float((ref_logits.argmax(-1) == labels).mean())
    assert acc == pytest.approx(ref_acc)
    assert loss == pytest.approx(ref_loss, rel=2e-2)


def test_sp_forward_validates_mesh_and_shapes():
    dense, ring, params, tokens = build_pair()
    with pytest.raises(ValueError, match="sp axis"):
        sp_forward(ring, params, tokens, make_mesh_plan(dp=8))
    plan = make_mesh_plan(dp=2, mp=1, sp=4)
    with pytest.raises(ValueError, match="must divide the sequence"):
        sp_forward(ring, params, tokens[:, :30], plan)


def test_sp_forward_rejects_beyond_max_len():
    dense, ring, params, tokens = build_pair()
    plan = make_mesh_plan(dp=2, mp=1, sp=4)
    long_tokens = np.concatenate([tokens, tokens], axis=1)  # L=64 > max_len=32
    with pytest.raises(ValueError, match="max_len"):
        sp_forward(ring, params, long_tokens, plan)


def test_sp_train_step_matches_dense_training():
    """Gradients through the ring (ppermute + online-softmax merge) must be
    the dense gradients: one optimizer step on the dp x sp mesh lands on the
    same params as a single-device dense step on the same global batch."""
    import optax

    from olearning_sim_tpu.parallel.long_context import sp_train_step

    dense, ring, params, tokens = build_pair()
    labels = np.array([0, 1, 2, 0, 1, 2, 0, 1], np.int32)
    plan = make_mesh_plan(dp=2, mp=1, sp=4)

    opt = optax.sgd(0.1)
    # dense reference step on one device
    def dense_loss(p):
        logits = dense.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    dloss, dgrads = jax.value_and_grad(dense_loss)(params)
    dupdates, _ = opt.update(dgrads, opt.init(params), params)
    dense_params = optax.apply_updates(params, dupdates)

    ring_params, _, rloss = sp_train_step(
        ring, params, opt.init(params), tokens, labels, opt, plan
    )
    assert float(rloss) == pytest.approx(float(dloss), rel=2e-2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-2, rtol=2e-2,
        ),
        jax.device_get(dense_params), jax.device_get(ring_params),
    )


def test_sp_train_step_learns():
    import optax

    from olearning_sim_tpu.parallel.long_context import sp_train_step

    _, ring, params, tokens = build_pair()
    labels = np.array([0, 1, 2, 0, 1, 2, 0, 1], np.int32)
    plan = make_mesh_plan(dp=2, mp=1, sp=4)
    opt = optax.sgd(0.1)
    opt_state = opt.init(params)
    losses = []
    for _ in range(6):
        params, opt_state, loss = sp_train_step(
            ring, params, opt_state, tokens, labels, opt, plan
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_sp_train_step_validates_mesh():
    import optax

    from olearning_sim_tpu.parallel.long_context import sp_train_step

    _, ring, params, tokens = build_pair()
    labels = np.zeros(8, np.int32)
    opt = optax.sgd(0.1)
    with pytest.raises(ValueError, match="sp axis"):
        sp_train_step(ring, params, opt.init(params), tokens, labels, opt,
                      make_mesh_plan(dp=8))
