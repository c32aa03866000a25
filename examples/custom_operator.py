"""Custom-operator escape hatch: plug YOUR code into the round loop.

In the reference, everything users care about lives in operator scripts —
zip archives whose entry file subclasses the operator ABC and receives a
``--params`` JSON per client batch. This demo writes such a script to a
temp dir, wires it into a round flow AFTER the built-in training + eval
operators, and runs the loop: each round the engine advances every client
through compiled local SGD, evaluates the global model, and then the
platform shells out to the user's operator once per client batch, turning
its exit codes into the per-class success/failed accounting that the
status calculus consumes.

The user script here computes a per-batch "contribution report" — stand-in
for whatever custom logic (secure aggregation checks, device-side metrics
upload, A/B hooks) the reference's users ship in their operator zips.

Runs anywhere: python examples/custom_operator.py
"""

import _bootstrap  # noqa: F401 — repo path

import json
import os
import tempfile
import textwrap

import numpy as np

from olearning_sim_tpu.engine import build_fedcore, fedavg, make_synthetic_dataset
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.engine.runner import (
    DataPopulation,
    OperatorSpec,
    SimulationRunner,
)
from olearning_sim_tpu.operators import external_operator_spec
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

USER_OPERATOR = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {repo_root!r})
    from olearning_sim_tpu.operators import OperatorABC

    class ContributionReport(OperatorABC):
        def run(self):
            p = self.params
            report = {{
                "round": p["current_round"],
                "clients": p["client_ids"],
                "weight": p["params"].get("report_weight", 1.0),
            }}
            path = os.path.join({outdir!r},
                                f"report_r{{p['current_round']}}_"
                                f"c{{p['client_ids'][0]}}.json")
            with open(path, "w") as f:
                json.dump(report, f)
            return 0   # exit code IS the success signal

    ContributionReport().main()
""")


def main():
    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=8, max_local_steps=3, block_clients=4)
    core = build_fedcore("mlp2", fedavg(0.1), plan, cfg,
                         model_overrides={"hidden": (32,), "num_classes": 4},
                         input_shape=(12,))
    ds = make_synthetic_dataset(
        seed=1, num_clients=16, n_local=8, input_shape=(12,), num_classes=4
    ).pad_for(plan, cfg.block_clients).place(plan)
    pop = DataPopulation(
        name="data_0", dataset=ds, device_classes=["hpc"],
        class_of_client=np.zeros(ds.num_clients, int),
        nums=[16], dynamic_nums=[4],
    )

    with tempfile.TemporaryDirectory() as tmp:
        outdir = os.path.join(tmp, "reports")
        os.makedirs(outdir)
        code_dir = os.path.join(tmp, "opcode")
        os.makedirs(code_dir)
        with open(os.path.join(code_dir, "entry.py"), "w") as f:
            f.write(USER_OPERATOR.format(repo_root=REPO_ROOT, outdir=outdir))

        operators = [
            OperatorSpec(name="train", kind="train"),
            OperatorSpec(name="eval", kind="eval"),
            external_operator_spec(
                "contribution_report", code_dir, "entry.py",
                operator_params=json.dumps({"report_weight": 0.5}),
                batch_size=4,
            ),
        ]
        runner = SimulationRunner(
            task_id="custom-op-demo", core=core, populations=[pop],
            operators=operators, rounds=2,
        )
        history = runner.run()

        for r, round_result in enumerate(history):
            acct = round_result["contribution_report"]["data_0"]
            print(f"round {r}: train loss="
                  f"{round_result['train']['data_0']['mean_loss']:.4f} "
                  f"custom operator success={acct['success']}/16 "
                  f"failed={acct['failed']}")
            assert acct["success"] == 16 and acct["failed"] == 0
        reports = sorted(os.listdir(outdir))
        print(f"user operator wrote {len(reports)} batch reports "
              f"(4 batches x 2 rounds); first: {reports[0]}")
        sample = json.load(open(os.path.join(outdir, reports[0])))
        assert sample["weight"] == 0.5
    print("ok: user operator code ran inside the round flow with exit-code "
          "accounting")


if __name__ == "__main__":
    main()
