"""The device program names its stages: ``jax.named_scope`` ``client_train``,
``delta_transform``, ``aggregate`` and ``server_update`` in every
round-program builder and ``evaluate`` in the evaluate program, so a
profile's device ops carry a name that a refactor does not change
(docs/observability.md). Metadata only — ``tests/test_analysis.py`` holds
the compiled programs to their budgets and to one trace.

Lowered on the audit grid's tiny shapes, one case per builder variant the
grid lowers (dp=2 runs the same builders as dp=1); nothing is compiled.
Reads ``as_text(debug_info=True)``, where a scope is part of an op's
location: ``loc("jit(round_step)/server_update/neg")``,
``loc("client_train/vmap()/min")`` inside the block scan."""

import re

import pytest

from olearning_sim_tpu.analysis import grid

ROUND_SCOPES = ("client_train", "delta_transform", "aggregate",
                "server_update")
VARIANTS = [v for v in grid.variant_grid()
            if v.program != "stream" and v.model == grid.MODEL
            and (v.dp == 1 or v.mp > 1)]


def scopes_in(lowered) -> set:
    """The path components of every op location in the lowering."""
    text = lowered.as_text(debug_info=True)
    return {part for path in re.findall(r'loc\("([^"]*)"', text)
            for part in path.split("/")}


@pytest.mark.parametrize("variant", VARIANTS, ids=lambda v: v.name)
def test_round_program_ops_are_under_the_four_scopes(variant):
    core, state, ds = grid._core_state_ds(
        variant.shard_server_update, variant.dp, variant.mp, variant.model)
    fn, args = core._prepare_round_args(
        state, ds, **grid._knob_kwargs(variant.program, core, ds, "a"))
    found = scopes_in(fn.lower(*args))
    assert set(ROUND_SCOPES) <= found
    assert "evaluate" not in found


def test_streamed_partial_and_finalize_programs_split_the_scopes():
    """The streamed round is two programs: the per-block partial step
    trains, transforms and accumulates; the finalize step reduces across
    replicas and applies the server update."""
    import numpy as np

    from olearning_sim_tpu.engine.client_data import (
        HostClientStore, make_synthetic_dataset)

    core, state, _ = grid._core_state_ds(False, 1, 1, grid.MODEL)
    host = make_synthetic_dataset(
        0, grid.NUM_CLIENTS, 6, grid.INPUT_SHAPE, grid.NUM_CLASSES
    ).pad_for(core.plan, core.config.block_clients)
    store = HostClientStore.from_dataset(host)
    knobs = dict(participate=np.ones(host.num_clients, np.float32),
                 num_steps=np.full(host.num_clients, 2, np.int32))
    partial = scopes_in(core.lower_stream_step(
        state, store, grid.STREAM_ROWS, **knobs))
    assert {"client_train", "delta_transform", "aggregate"} <= partial
    assert "server_update" not in partial
    prep = core._prepare_stream(store, grid.STREAM_ROWS, **knobs)
    finalize = scopes_in(prep["finalize_fn"].lower(
        state, prep["zero_acc_fn"]()))
    assert {"aggregate", "server_update"} <= finalize
    assert "client_train" not in finalize


def test_evaluate_program_ops_are_under_the_evaluate_scope():
    import jax.numpy as jnp

    core, state, _ = grid._core_state_ds(False, 1, 1, grid.MODEL)
    x = jnp.zeros((4,) + grid.INPUT_SHAPE, jnp.float32)
    y = jnp.zeros((4,), jnp.int32)
    found = scopes_in(core._evaluate.lower(state.params, x, y))
    assert "evaluate" in found
    assert not set(ROUND_SCOPES) & found
