"""``benchmark/roofline_selective_scan.py``: the selective scan's and the
window attention's work from shapes, which bound holds for each at the
cell's sizes, and the readers that divide by the scopes' time."""

import json

import pytest

from benchmark import manifest, roofline, roofline_selective_scan

CELL = "phi4flash_vp8.8_silo_2k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_scans_roofline_counts_the_recurrence_and_nothing_else():
    """A round's training: 8 clients x 2 steps x 4,096 tokens through one
    layer, in 64-token chunks. The bytes bound it: no matrix product."""
    tokens, chunks = 8 * 2 * 4096, 8 * 2 * 2 * 32
    work = roofline_selective_scan.selective_scan(tokens, chunks, 5120, 16)
    assert work.flops == 2.0 * 3 * tokens * 5120 * 16 * 2
    a_token = (2 * 5120 + 2 * 16) * 2 + 5120 * 4
    assert work.bytes == 3 * tokens * a_token + 2 * chunks * 5120 * 16 * 4
    seconds, bound = roofline.least_seconds(work, PEAKS)
    assert bound == "bytes" and seconds == pytest.approx(0.01067, rel=0.01)
    assert work.flops / PEAKS["bf16_flops_per_s"] == pytest.approx(
        0.000327, rel=0.01)
    assert roofline.share_percent(work, 1.0, PEAKS) == pytest.approx(
        1.067, rel=0.01)


def test_the_windows_roofline_counts_the_windows_pairs_and_nothing_else():
    """A round's training: 32 sequences of 917,760 pairs a head, 40 heads,
    64 MACs of score and 128 of context a pair. The FLOPs bound it."""
    pairs, tokens = 8 * 2 * 2 * 917_760, 8 * 2 * 4096
    work = roofline_selective_scan.window_attention(pairs, tokens, 40, 20, 64)
    assert work.flops == 2.0 * 3 * pairs * 40 * 192
    assert work.bytes == 3 * tokens * (2 * 40 + 2 * 20) * 64 * 2
    seconds, bound = roofline.least_seconds(work, PEAKS)
    assert bound == "flops" and seconds == pytest.approx(0.00687, rel=0.01)
    assert work.bytes / PEAKS["hbm_bytes_per_s"] == pytest.approx(
        0.00369, rel=0.01)


@pytest.mark.parametrize("metric,scope,counter", [
    ("phi4flash.selective_scan_roofline", "phi4flash.selective_scan",
     "sscan_tokens"),
    ("phi4flash.window_attention_roofline", "phi4flash.window_products",
     "window_attn_pairs_needed"),
])
def test_the_readers_divide_by_their_scopes_time_in_the_cell(
        metric, scope, counter):
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == metric)
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert entry["moves"] == "device_rounds_per_s"
    reader = manifest.find_module("layer_metrics", metric)
    assert reader.SCOPE == scope and reader.UNIT == "%"

    class NoTrace:
        trace = None

    assert reader.read(NoTrace()) is None
    # The scope is one the program names, and the counter one it counts.
    from olearning_sim_tpu.models import phi4flash
    import inspect
    assert f'"{scope}"' in inspect.getsource(phi4flash)
    assert counter in phi4flash.STATS
