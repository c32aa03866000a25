"""Device time by named scope: ``trace_reduce``'s scope paths, the reader of
the event metadata (``xplane_reader``), and the by-scope readers, against a
recorded trace with scopes.

``data/tiny_scoped_round.xplane.pb.gz`` is one traced round, with 1.5 ms
either side, of the tests' tiny ``lfm2_moe_ep8`` preset on one TPU v5e
chip, run from a warm compilation cache (PR 32; ``data/strip_xplane.py``
cut it from the kept trace: the ``XLA Ops`` and ``XLA Modules`` events, and
of their metadata the name and the ``tf_op`` stat). The numbers below were
read from it once, and the whole kept trace read the same ones."""

import gzip
import os
import types

import pytest

from benchmark import manifest, trace_reduce as tr, xplane_reader

HERE = os.path.dirname(os.path.abspath(__file__))
# The harness's stretch of that run, on its host clock, and the host clock
# at the sync annotation.
STRETCH = (10.700233190000006, 10.715016109999993)
SYNC_HOST_S = 10.685850591000005
STAGES = ("client_train", "delta_transform", "aggregate", "server_update",
          "evaluate")


def _raw(name):
    with gzip.open(os.path.join(HERE, "data", name)) as f:
        return f.read()


def _reduce(raw, *stretch, with_metadata=True):
    from jax.profiler import ProfileData

    return tr.reduce_profile(
        ProfileData.from_serialized_xspace(raw), *stretch,
        metadata=xplane_reader.read_planes(raw) if with_metadata else ())


@pytest.fixture(scope="module")
def summary():
    return _reduce(_raw("tiny_scoped_round.xplane.pb.gz"), STRETCH,
                   SYNC_HOST_S)


def test_the_stretch_reads_what_the_whole_kept_trace_read(summary):
    assert summary.sync_s == pytest.approx(0.048550348, abs=1e-9)
    assert summary.window_s == pytest.approx(0.01478292, rel=1e-6)
    assert summary.busy_s == pytest.approx(0.001412442, rel=1e-6)
    # The plain sum of the operations is less than the union here: the
    # ``while`` containers cover the gaps between their bodies' operations.
    assert summary.op_seconds == pytest.approx(0.001321432, rel=1e-6)
    assert summary.devices[0].modules["jit_round_step"][0] == 2
    assert summary.devices[0].modules["jit_evaluate"][0] == 4


@pytest.mark.parametrize("components,which,seconds", [
    (("client_train",), None, 0.001015951),
    (("evaluate",), None, 0.000178141),
    (("delta_transform",), None, 3.764e-05),
    (("aggregate",), None, 3.038e-06),
    (("server_update",), None, 7.56e-07),
    (("lm_loss",), None, 2.4515e-05),
    (("moe.experts",), None, 1.577e-05),
    (("client_train", "moe.experts"), None, 1.2104e-05),
    (("evaluate", "moe.experts"), None, 3.666e-06),
    (("moe.experts", "client_train"), None, 0.0),        # not in this order
    ((tr.UNSCOPED,), None, 8.5738e-05),
    (("client_train",), tr.FORWARD, 0.000609721),
    (("client_train",), tr.BACKWARD, 0.000402427),
    (("client_train",), tr.RECOMPUTED, 3.803e-06),
    (("moe.experts",), tr.FORWARD, 7.515e-06),
    (("moe.experts",), tr.BACKWARD, 6.374e-06),
    (("moe.experts",), tr.RECOMPUTED, 1.881e-06),
    # The compiler's grouped-matmul kernel lost its scope and is found by
    # the primitive that ends its path; the evaluation's has no path at all.
    (("client_train", "ragged-dot-none"), None, 0.000254265),
    (("ragged-dot-none",), None, 0.000287959),
])
def test_scope_seconds(summary, components, which, seconds):
    assert summary.scope_seconds(*components, which=which) == pytest.approx(
        seconds, rel=1e-4, abs=1e-12)


def test_the_stages_and_unscoped_are_all_of_the_operations_time(summary):
    total = (sum(summary.scope_seconds(s) for s in STAGES)
             + summary.scope_seconds(tr.UNSCOPED))
    # Short by two gathers of the evaluation whose path kept its kernel
    # scope (``LFM2/layers_1/moe/moe.dispatch/gather``) and lost the stage.
    assert total == pytest.approx(summary.op_seconds - 1.68e-7, rel=1e-6)
    passes = sum(summary.scope_seconds("client_train", which=w)
                 for w in (tr.FORWARD, tr.BACKWARD, tr.RECOMPUTED))
    assert passes == pytest.approx(summary.scope_seconds("client_train"))
    top = summary.top_scopes(12)
    assert [name for name, _ in top[:3]] == [
        "client_train", "moe.route", "moe.dispatch"]
    assert tr.UNSCOPED in dict(top) and len(top) == 12
    assert sum(s for _, s in summary.top_scopes(99)) == pytest.approx(
        summary.op_seconds, rel=1e-9)


@pytest.mark.parametrize("op_name,path,which", [
    ("jit(round_step)/while/body/closed_call/client_train/closed_call/"
     "jvp(LFM2)/layers_1/moe/moe.experts/mul:",
     ("client_train", "LFM2", "layers_1", "moe", "moe.experts", "mul"),
     tr.FORWARD),
    ("transpose(jvp(moe.experts))/dot_general", ("moe.experts", "dot_general"),
     tr.BACKWARD),
    ("checkpoint/rematted_computation/moe.experts/mul:",
     ("moe.experts", "mul"), tr.RECOMPUTED),
    ("jit(f)/jit(main)/while/body/client_train/vmap(jvp(jit(_take)))/gather",
     ("client_train", "gather"), tr.FORWARD),
    ("jit(f)/cond/branch_1_fun/pjit/shard_map/evaluate/jvp(lm_loss)/sub",
     ("evaluate", "lm_loss", "sub"), tr.FORWARD),
    ("transpose(jvp(LFM2))/layers_1/moe/moe.experts/jvp(LFM2)/layers_1/moe/"
     "moe.experts/checkpoint/rematted_computation/mul:",
     ("LFM2", "layers_1", "moe", "moe.experts", "LFM2", "layers_1", "moe",
      "moe.experts", "mul"), tr.RECOMPUTED),
    ("jit(round_step)/while:", (), tr.FORWARD),
    ("ragged-dot-none:", ("ragged-dot-none",), tr.FORWARD),
    ("params['embed']['embedding']:", ("params['embed']['embedding']",),
     tr.FORWARD),
    ("", (), tr.FORWARD),
])
def test_the_wrappers_are_stripped_and_the_pass_is_kept(op_name, path, which):
    assert tr.scope_path(op_name) == (path, which)


def test_what_counts_as_a_scope_of_the_programs():
    for name in tr.PROGRAM_SCOPES + ("moe.experts", "lfm2.short_conv",
                                     "kda.chunk_scan"):
        assert tr.is_scope(name), name
    for name in ("LFM2", "layers_1", "Dense_0", "mul", "ragged-dot-none",
                 "FedCore._local_train", "Embed_0.attend", "a.b.c", ""):
        assert not tr.is_scope(name), name
    assert tr.innermost_scope(("client_train", "LFM2", "moe.route", "top_k")
                              ) == "moe.route"
    assert tr.innermost_scope(("client_train", "LFM2", "mlp", "dot_general")
                              ) == "client_train"
    assert tr.innermost_scope(()) == tr.UNSCOPED


PLANTED = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.3 = (f32[4]) while(f32[4] %p)"
    stats { metadata_id: 7 str_value: "jit(round_step)/while:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = f32[4]{0} fusion(f32[4] %p), kind=kOutput"
    stats { metadata_id: 7 str_value: "jit(round_step)/while/body/client_train/transpose(jvp(M))/moe.experts/dot_general:" } } }
  event_metadata { key: 3 value { id: 3 name: "%copy-done.1 = f32[4]{0} copy-done(f32[4] %q)" } }
  event_metadata { key: 4 value { id: 4 name: "%add.9 = f32[4]{0} add(f32[4] %a, f32[4] %b)"
    stats { metadata_id: 7 ref_value: 8 } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "jit(round_step)/aggregate/add:" } }
}
"""


def _planted(text=PLANTED, **kw):
    from jax.profiler import ProfileData

    return _reduce(ProfileData.text_proto_to_serialized_xspace(text), **kw)


def test_a_fusion_goes_whole_to_its_roots_path_and_containers_nowhere():
    s = _planted()
    assert s.busy_s == pytest.approx(10e-6)           # the while spans all
    assert s.op_seconds == pytest.approx(9e-6)        # and is not summed
    assert s.devices[0].scopes == {
        (("client_train", "M", "moe.experts", "dot_general"), tr.BACKWARD):
            pytest.approx(5e-6),                      # both executions
        ((), tr.FORWARD): pytest.approx(2e-6),        # no op_name at all
        (("aggregate", "add"), tr.FORWARD): pytest.approx(2e-6),  # a ref stat
    }
    assert s.scope_seconds("moe.experts", which=tr.BACKWARD) == pytest.approx(
        5e-6)
    assert s.scope_seconds(tr.UNSCOPED) == pytest.approx(2e-6)
    assert dict(s.top_scopes()) == {
        "moe.experts": pytest.approx(5e-6), "aggregate": pytest.approx(2e-6),
        tr.UNSCOPED: pytest.approx(2e-6)}


def test_metadata_of_another_trace_is_refused():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(PLANTED)
    other = xplane_reader.read_planes(
        ProfileData.text_proto_to_serialized_xspace(
            PLANTED.replace("%fusion.7", "%fusion.8")))
    with pytest.raises(ValueError, match="not the same trace"):
        tr.reduce_profile(ProfileData.from_serialized_xspace(raw),
                          metadata=other)
    shorter = xplane_reader.read_planes(
        ProfileData.text_proto_to_serialized_xspace(PLANTED.replace(
            "events { metadata_id: 4 offset_ps: 8000000 duration_ps: 2000000 }",
            "")))
    with pytest.raises(ValueError, match="not the same trace"):
        tr.reduce_profile(ProfileData.from_serialized_xspace(raw),
                          metadata=shorter)


@pytest.mark.parametrize("name", ["tiny_round.xplane.pb.gz",
                                  "tiny_scoped_round.xplane.pb.gz"])
def test_the_reader_pairs_every_event_with_its_metadata(name):
    from jax.profiler import ProfileData

    raw = _raw(name)
    planes = xplane_reader.read_planes(raw)
    profile = ProfileData.from_serialized_xspace(raw)
    assert [p.name for p in planes] == [p.name for p in profile.planes]
    paired = 0
    for plane, meta in zip(profile.planes, planes):
        for line in plane.lines:
            events = list(line.events)
            ids = meta.line_event_ids[line.name]
            assert len(ids) == len(events)
            assert all(meta.event_names[i] == e.name
                       for i, e in zip(ids, events))
            paired += len(events)
    assert paired > 3000
    assert xplane_reader.read_planes(b"") == []
    with pytest.raises(ValueError, match="not an xplane"):
        xplane_reader.read_planes(b"\x0b\x00")        # a group: wire type 3


def _ctx(summary, cell="lfm2_moe_ep8.8_silo_1k", rounds=1):
    return types.SimpleNamespace(trace=summary, trace_rounds=rounds,
                                 cell=manifest.load_cell(cell))


def _read(name, ctx):
    return manifest.find_module("layer_metrics", name).read(ctx)


def test_a_trace_without_scopes_is_all_unscoped():
    """``tiny_round`` was recorded with names only: no metadata stat."""
    old = _reduce(_raw("tiny_round.xplane.pb.gz"))
    bare = _reduce(_raw("tiny_round.xplane.pb.gz"), with_metadata=False)
    for s in (old, bare):
        assert s.scope_seconds(tr.UNSCOPED) == pytest.approx(s.op_seconds)
        assert s.op_seconds == pytest.approx(0.004512157, rel=1e-6)
        assert s.top_scopes() == [[tr.UNSCOPED, pytest.approx(s.op_seconds)]]
        ctx = _ctx(s)
        assert _read("round_program.scoped_share", ctx) == pytest.approx(0.0)
        assert _read("client_train.device_ms", ctx) == 0.0
        assert _read("evaluate.device_ms", ctx) == 0.0
    assert old.busy_s == bare.busy_s and old.devices[0].ops == bare.devices[0].ops


@pytest.mark.parametrize("name,milliseconds", [
    ("client_train.device_ms", 1.015951),
    ("aggregate.device_ms", 0.037640 + 0.003038),
    ("server_update.device_ms", 0.000756),
    ("evaluate.device_ms", 0.178141),
    ("moe.experts.device_ms", 0.015770 + 0.287959),
    ("moe.route_dispatch_combine.device_ms", 0.628575),
    ("lfm2.attention.device_ms", 0.087491),
    ("lfm2.short_conv.device_ms", 0.037949),
    ("lm_loss.device_ms", 0.024515),
])
def test_the_by_scope_readers_give_a_rounds_share_in_milliseconds(
        summary, name, milliseconds):
    assert _read(name, _ctx(summary)) == pytest.approx(milliseconds, rel=1e-4)
    assert _read(name, _ctx(summary, rounds=2)) == pytest.approx(
        milliseconds / 2, rel=1e-4)
    assert _read(name, _ctx(None)) is None            # a run with no trace
    assert _read(name, _ctx(summary, rounds=0)) is None


def test_the_scoped_share_and_the_sum_against_the_union(summary):
    share = _read("round_program.scoped_share", _ctx(summary))
    assert share == pytest.approx(100.0 * (1 - 8.5738e-05 / 0.001321432),
                                  rel=1e-4)
    assert _read("round_program.scoped_share", _ctx(None)) is None
    stages = sum(_read(n + ".device_ms", _ctx(summary)) for n in
                 ("client_train", "aggregate", "server_update", "evaluate"))
    assert stages + 1e3 * summary.scope_seconds(tr.UNSCOPED) == pytest.approx(
        1e3 * summary.op_seconds, rel=2e-4)


def test_the_manifest_lists_the_kernel_metrics_for_the_cell_that_has_them(
        listed_manifest):
    """A list names the cells whose model opens the metric's scope, whose
    task has a next-token loss, whose traffic evaluates — found from the
    models and the traffic files (``model_facts``), in the manifest's
    order, so a cell a later PR appends is asked for where it has something
    to read and nowhere else."""
    import json

    import model_facts

    with open(listed_manifest, encoding="utf-8") as f:
        doc = json.load(f)
    by_name = {m["name"]: m for m in doc["per_layer"]}
    everywhere = ("round_program.scoped_share", "client_train.device_ms",
                  "aggregate.device_ms", "server_update.device_ms")
    sparse = {
        "moe.experts.device_ms": lambda f: "moe.experts" in f.scopes,
        "moe.route_dispatch_combine.device_ms": lambda f: {
            "moe.route", "moe.dispatch", "moe.combine"} <= f.scopes,
        "moe.experts_roofline": lambda f: "moe.experts" in f.scopes,
        "lfm2.attention.device_ms": lambda f: "lfm2.attention" in f.scopes,
        "lfm2.short_conv.device_ms": lambda f: "lfm2.short_conv" in f.scopes,
        "lm_loss.device_ms": lambda f: f.next_token,
    }
    for name in everywhere:
        assert "workloads" not in by_name[name]
    for name, emits in sparse.items():
        assert by_name[name]["workloads"] == model_facts.cells_where(
            listed_manifest, emits), name
        assert by_name[name]["moves"] == "device_rounds_per_s"
    assert by_name["evaluate.device_ms"]["workloads"] == (
        model_facts.cells_where(listed_manifest, lambda f: f.evaluates))
    assert by_name["lfm2.attention.device_ms"]["workloads"] == [
        "lfm2_moe_ep8.8_silo_1k"]             # the derivation finds something
