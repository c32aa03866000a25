"""The delta-rule scan's forward kernel (``ops/kda_scan.py``) on the CPU, in
the Pallas interpreter at small shapes: against the plain-JAX ``chunk_scan``
of ``models/kimi_linear.py`` and against the recurrence token by token of
``benchmark/reference/kimi_linear.py``; the wrapper's choice of code by the
platform a program is lowered for, its gradients and what its backward pass
computes; and, where a v5e can be described here, that the kernel compiles
for it inside the round program's kind of ``shard_map``.

Counts and correctness facts only: never a speed."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import manifest, trace_reduce
from olearning_sim_tpu.models import kimi_linear as km
from olearning_sim_tpu.ops import kda_scan, lowering

ref = manifest.find_module("reference", "kimi_linear")
F32 = jnp.float32
H, K = 2, 128
MOSAIC = "tpu_custom_call"


def _inputs(seed, n, length, decay_scale=1.0, alike=False):
    """Unit keys, queries a sqrt(K)-th of one, log decays ``-decay_scale
    softplus(.)``; ``alike``: near-identical keys written at full strength
    (``I + A`` at its worst conditioning)."""
    rng = np.random.default_rng(seed)

    def unit(shape):
        u = rng.standard_normal(shape)
        if alike:
            u = rng.standard_normal(shape[:1] + (1,) + shape[2:]) + 1e-2 * u
        return u / np.linalg.norm(u, axis=-1, keepdims=True)

    shape = (n, length, H, K)
    q = jnp.asarray(unit(shape) / np.sqrt(K), F32)
    k = jnp.asarray(unit(shape), F32)
    v = jnp.asarray(rng.standard_normal(shape), F32)
    g = jnp.asarray(-decay_scale * np.logaddexp(
        0, rng.standard_normal(shape)), F32)
    beta = jnp.asarray(1 / (1 + np.exp(-(
        np.full(shape[:3], 7.0) if alike
        else rng.standard_normal(shape[:3])))), F32)
    return q, k, v, g, beta


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = float(np.abs(want).max())
    assert np.isfinite(got).all() and scale > 0
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


CASES = [
    (1, 2 * km.CHUNK, 1.0, False),          # whole chunks
    (2, 3 * km.CHUNK + 8, 1.0, False),      # a padded tail (L 200)
    # exp(G_t) exp(-G_j) would overflow inside a sub-block: a chunk's
    # product of decays is below float32's smallest number, a sub-block's too
    (2, 2 * km.CHUNK, 16.0, False),
    (1, 3 * km.CHUNK + 8, 16.0, False),
    (1, 2 * km.CHUNK, 0.01, True),          # I + A nearly all ones below
]
IDS = ["whole", "ragged", "strong_decay", "strong_decay_ragged", "alike"]


@pytest.fixture(scope="module", params=CASES, ids=IDS)
def case(request):
    """One case's inputs and the kernel's output in the interpreter."""
    n, length, decay_scale, alike = request.param
    xs = _inputs(length + int(decay_scale), n, length, decay_scale, alike)
    if decay_scale > 1:
        g = xs[3]
        assert float(g[:, :km.CHUNK].sum(1).max()) < -200
        assert max(float(g[:, i:i + km.SUB].sum(1).max())
                   for i in range(0, length - km.SUB, km.SUB)) < -88
    return xs, kda_scan.scan_forward(*xs, interpret=True)


def test_the_kernel_is_the_plain_chunked_scan(case):
    xs, got = case
    assert got.shape == xs[2].shape and got.dtype == F32
    _close(got, km.chunk_scan(*xs), 1e-5)


def test_the_kernel_is_the_recurrence_token_by_token(case):
    xs, got = case
    want = jnp.stack([ref.delta_rule(*(x[i] for x in xs))
                      for i in range(xs[0].shape[0])])
    _close(got, want, 1e-4)


def test_the_kernels_chunks_are_the_plain_codes():
    assert (kda_scan.CHUNK, kda_scan.SUB) == (km.CHUNK, km.SUB)


def test_a_step_of_one_head_gives_what_a_step_of_two_does(monkeypatch):
    xs = _inputs(3, 1, km.CHUNK + 8)
    assert kda_scan.HEADS_PER_STEP % H == 0     # both heads in one step
    two = kda_scan.scan_forward(*xs, interpret=True)
    monkeypatch.setattr(kda_scan, "HEADS_PER_STEP", 1)
    _close(kda_scan.scan_forward(*xs, interpret=True), two, 1e-6)


def test_widths_the_kernel_has_not_are_refused_and_left_to_the_plain_code():
    *wide, beta = _inputs(4, 1, km.CHUNK)
    q, k, v, g = (x[..., :16] for x in wide)
    with pytest.raises(ValueError, match="multiple of 128"):
        kda_scan.scan_forward(q, k, v, g, beta, interpret=True)
    traced = jax.jit(lambda *xs: kda_scan.chunk_scan(
        km.chunk_scan, *xs)).trace(q, k, v, g, beta)
    assert MOSAIC not in traced.lower(lowering_platforms=("tpu",)).as_text()


def _wrapped(*xs):
    return kda_scan.chunk_scan(km.chunk_scan, *xs)


def test_the_platform_chooses_the_code():
    xs = _inputs(5, 1, 2 * km.CHUNK)
    o, kernel_chunks = jax.jit(_wrapped)(*xs)
    # On the CPU: the plain code, to the bit, and no chunk the kernel's.
    assert int(kernel_chunks) == 0 and kernel_chunks.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(o), np.asarray(
        jax.jit(km.chunk_scan)(*xs)))
    traced = jax.jit(_wrapped).trace(*xs)
    assert MOSAIC not in traced.lower(lowering_platforms=("cpu",)).as_text()
    for_tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert for_tpu.count(MOSAIC) == 1
    # ... which counts every chunk: 1 sequence x 2 chunks.
    assert re.search(r"stablehlo.constant dense<2> : tensor<i32>", for_tpu)


def _loss(scan, probe):
    def fn(*xs):
        out = scan(*xs)
        return ((out[0] if isinstance(out, tuple) else out) * probe).sum()
    return jax.jit(jax.grad(fn, argnums=tuple(range(5))))


@pytest.mark.parametrize("length,decay_scale", [
    (2 * km.CHUNK, 1.0), (km.CHUNK + 22, 16.0)])
def test_the_wrappers_gradients_are_the_plain_scans(length, decay_scale):
    xs = _inputs(6, 2, length, decay_scale)
    probe = jnp.asarray(np.random.default_rng(7).standard_normal(
        xs[2].shape), F32)
    got = _loss(_wrapped, probe)(*xs)
    want = _loss(km.chunk_scan, probe)(*xs)
    for a, b in zip(got, want):
        _close(a, b, 1e-6)


def test_the_backward_pass_computes_the_forward_once_more_and_labels_it():
    """The custom VJP's backward function is ``jax.vjp`` of the checkpointed
    plain scan: one forward computed again, labelled as every recomputation
    is (``benchmark/trace_reduce.py`` finds ``rematted_computation``), and
    ``jax.vjp``'s own first forward, which nothing reads, gone from the
    compiled program: as many loops as ``jax.checkpoint(chunk_scan)``'s
    gradient has."""
    xs = _inputs(8, 1, 2 * km.CHUNK)

    def gradient(scan):
        # Not linear in ``o``: the forward pass stays in the program.
        return jax.jit(jax.grad(lambda *xs: (jax.named_scope(
            "kda.chunk_scan")(scan)(*xs)[0] ** 2).sum(),
            argnums=tuple(range(5)))).lower(*xs).compile().as_text()

    wrapped = gradient(_wrapped)
    found = {(trace_reduce.innermost_scope(path), which)
             for path, which in map(trace_reduce.scope_path, re.findall(
                 r'op_name="([^"]*)"', wrapped))}
    assert {("kda.chunk_scan", which) for which in (
        trace_reduce.FORWARD, trace_reduce.RECOMPUTED,
        trace_reduce.BACKWARD)} <= found

    def loops(text):
        return len(re.findall(r" while\(", text))

    assert loops(wrapped) == loops(gradient(
        lambda *xs: (jax.checkpoint(km.chunk_scan)(*xs),)))


def test_outside_a_mesh_the_helper_is_the_function_itself():
    calls = []

    def fn(x):
        calls.append(jax.sharding.get_abstract_mesh().empty)
        return x + 1

    assert float(lowering.manual_over_auto_axes(fn)(jnp.float32(1))) == 2
    assert calls == [True]


def test_inside_a_map_over_dp_the_helper_holds_mp_too():
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "mp"))
    seen = []

    def fn(x):
        seen.append(jax.sharding.get_abstract_mesh().manual_axes)
        return 2 * x

    out = jax.jit(jax.shard_map(
        lowering.manual_over_auto_axes(fn), mesh=mesh, in_specs=P("dp"),
        out_specs=P("dp"), axis_names=frozenset({"dp"})))(jnp.arange(4.0))
    assert set(seen[0]) == {"dp", "mp"}
    np.testing.assert_array_equal(np.asarray(out), 2 * np.arange(4.0))


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e, where the TPU's compiler is installed."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # whatever a missing compiler raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_the_wrapper_compiles_for_a_v5e_inside_a_map_over_dp(v5e):
    """``FedCore``'s boundary: manual over ``dp``, ``mp`` left auto. The
    gradient through the wrapper holds the one kernel, the forward's."""
    from jax.experimental.compilation_cache import compilation_cache
    mesh = Mesh(np.array([v5e]).reshape(1, 1), ("dp", "mp"))
    n, length = 2, 4 * km.CHUNK

    def body(q, k, v, g, beta):
        def loss(*xs):
            o, chunks = _wrapped(*xs)
            return (o ** 2).sum(), chunks
        (_, chunks), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(q, k, v, g, beta)
        return grads[0], chunks[None]

    spec = P("dp")
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec,) * 5, out_specs=(spec, spec),
        axis_names=frozenset({"dp"})))
    x = jax.ShapeDtypeStruct((n, length, H, K), F32,
                             sharding=NamedSharding(mesh, spec))
    beta = jax.ShapeDtypeStruct((n, length, H), F32,
                                sharding=NamedSharding(mesh, spec))
    # What is compiled for a described device cannot be read back.
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = fn.lower(x, x, x, x, beta).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert compiled.as_text().count(MOSAIC) == 1
