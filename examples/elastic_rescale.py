"""Elastic rescale tour: grow a RUNNING task's world 2 -> 4 workers.

A JAX SPMD world is compiled for a fixed topology, so the TPU-native
analogue of the reference's live KubeRay replica patch is
checkpoint-restart elasticity — also how real TPU pod slices resize:

    segment over world(2) -> checkpoint -> modify_slice(4) ->
    relaunch world(4) -> restore -> next segment

Each segment is a real multi-process `jax.distributed` world (one
subprocess per "host"). FedCore's (uid, round) RNG streams make the
round program resharding-stable, so the rescaled run CONTINUES the same
training trajectory — the grown world picks up exactly where the small
one checkpointed.

Runs on the 8-device virtual mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/elastic_rescale.py
"""

import _bootstrap  # noqa: F401 — repo path

import tempfile

import jax

from olearning_sim_tpu.clustermgr.elastic import ElasticWorldRunner
from olearning_sim_tpu.clustermgr.slice_manager import ClusterManager


def main():
    mgr = ClusterManager(devices=jax.devices())
    mgr.create_slice("demo", 2, user_id="u1")
    print(f"slice 'demo': {mgr.query_slice('demo')['num_devices']} devices")

    with tempfile.TemporaryDirectory() as ckdir:
        runner = ElasticWorldRunner(
            mgr, "demo", ckdir, segment_rounds=2, coordinator_port=29480,
        )

        def controller(segment_idx, completed_rounds):
            if segment_idx == 1:   # decision lands mid-task
                print(f"after round {completed_rounds}: requesting "
                      "rescale 2 -> 4 workers")
                runner.request_rescale(4)

        history = runner.run(total_rounds=4, between_segments=controller)
        print(f"world sizes per segment: {history}")
        assert history == [2, 4]
        assert mgr.query_slice("demo")["num_devices"] == 4
        summary = runner.overhead_summary()
        print(f"rescale overhead: {summary['overhead_per_segment_sec']:.1f}s "
              "per segment (spawn + dist-init + compile + restore + ckpt)")
    print("ok: task grew 2 -> 4 workers mid-flight and completed on the "
          "same trajectory")


if __name__ == "__main__":
    main()
