"""Chip smoke: the task-JSON -> FedCore round path, once, on the accelerator.

Drives ``configs/fedavg_cifar10_cnn.json`` as the file has it (cnn4 at its
default widths, CIFAR-10 shapes, 1000 clients x 50 samples, batch 32, 10
local steps, train + evaluate operators) with only the round count cut to
3 and a fresh task id, through the entry points a user calls: an in-process
``SimulatorSession`` (in-memory repos, ephemeral port), a gRPC
``submitTask``, the scheduler, the job launcher, the task bridge, the
runner, ``FedCore.round_step`` and ``FedCore.evaluate``. Then it checks
what came out, printing each fact as it goes.

One process: it holds the chip from the first JAX call to exit and starts
no child. Without a TPU backend (``JAX_PLATFORMS=cpu``, or no accelerator)
it exits non-zero before any round runs; a failed check exits non-zero
with the reason on stderr. On success the last stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Run: ``python chip_smoke.py`` from the checkout root. The compile cache
goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``artifacts/xla_compile_cache`` (engine/compile_cache.py).
"""

from __future__ import annotations

import importlib.metadata
import json
import math
import os
import sys
import time
import uuid

ROOT = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(ROOT, "configs", "fedavg_cifar10_cnn.json")
ROUNDS = 3
TASK_TIMEOUT_S = 1000.0


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(**facts) -> None:
    print(" ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


def device_facts() -> dict:
    """The device as JAX reports it (first backend touch of the process)."""
    import jax

    devices = jax.devices()
    facts = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(backend=jax.default_backend(), **facts)
    say(**{p: importlib.metadata.version(p)
           for p in ("jax", "jaxlib", "libtpu")})
    return facts


def load_task() -> dict:
    with open(CONFIG, encoding="utf-8") as f:
        task = json.load(f)
    task["operatorflow"]["flow_setting"]["round"] = ROUNDS
    task["task_id"] = f"chip-smoke-{uuid.uuid4().hex[:8]}"
    return task


def run_task(task: dict):
    """Submit ``task`` over gRPC to an in-process platform and poll it to a
    terminal status. Returns ``(runner, topology)``: the finished job's
    SimulationRunner and the ResourceMgr's topology answer."""
    import grpc

    from olearning_sim_tpu.config import build_session
    from olearning_sim_tpu.services.grpc_services import ResourceMgrClient
    from olearning_sim_tpu.taskmgr.codecs import json2taskconfig
    from olearning_sim_tpu.taskmgr.grpc_service import TaskMgrClient
    from olearning_sim_tpu.taskmgr.status import TaskStatus
    from olearning_sim_tpu.utils.clocks import Deadline

    task_id = task["task_id"]
    session = build_session({
        "session": {"services": ["taskmgr", "resourcemgr", "performancemgr"],
                    "address": "127.0.0.1:0"},
        "taskmgr": {"schedule_interval": 0.2, "release_interval": 0.2,
                    "interrupt_interval": 3600},
    })
    with session:
        with grpc.insecure_channel(f"127.0.0.1:{session.port}") as channel:
            topology = ResourceMgrClient(channel).get_resource()["topology"]
            client = TaskMgrClient(channel)
            accepted = client.submitTask(json2taskconfig(json.dumps(task)))
            require(accepted.is_success, "submitTask was refused")
            deadline = Deadline(TASK_TIMEOUT_S)
            status = last = None
            while not deadline.expired():
                status = TaskStatus(client.getTaskStatus(task_id).taskStatus)
                if status != last:
                    say(task=task_id, status=status.name)
                    last = status
                if status in (TaskStatus.SUCCEEDED, TaskStatus.FAILED,
                              TaskStatus.STOPPED):
                    break
                time.sleep(0.5)
        job = session.supervisor.launcher.get_job(f"job-{task_id}")
        require(status == TaskStatus.SUCCEEDED,
                f"task ended {status.name if status else None}, not "
                f"SUCCEEDED: {job.error if job is not None else 'no job'}")
        return job.runner, topology


def check_run(runner, task: dict, topology: dict, device: dict) -> None:
    """Every claim of the smoke about the finished run."""
    import jax

    from olearning_sim_tpu.engine.compile_cache import (
        cache_stats,
        enabled_dir,
    )
    from olearning_sim_tpu.telemetry import default_tracer

    task_id = task["task_id"]
    data = task["target"]["data"][0]
    pop_name = data["name"]
    n_clients = sum(data["allocation"]["logical_simulation"])
    devices = jax.devices()

    # Topology the platform reports to schedulers and users.
    say(topology=json.dumps(topology))
    require(topology["platform"] == device["platform"],
            f"ResourceMgr topology platform {topology['platform']!r}")
    require(topology["device_kinds"] == [device["kind"]],
            f"ResourceMgr topology kinds {topology['device_kinds']}")
    require(topology["num_chips"] == device["count"],
            f"ResourceMgr topology num_chips {topology['num_chips']}")

    # Mesh over every visible chip; 1/dp of the client rows on each.
    plan = runner.core.plan
    ds = next(p.dataset for p in runner.populations if p.name == pop_name)
    rows = {s.device.id: s.data.shape[0] for s in ds.x.addressable_shards}
    say(dp=plan.dp, mp=plan.mp, padded_clients=ds.num_clients,
        rows_per_device=json.dumps(rows))
    require(plan.dp == len(devices),
            f"mesh dp={plan.dp} but {len(devices)} devices are visible")
    require(set(rows) == {d.id for d in devices},
            f"client rows sit on devices {sorted(rows)}")
    require(all(r * plan.dp == ds.num_clients for r in rows.values()),
            f"client rows per device {rows} are not 1/dp of "
            f"{ds.num_clients}")

    # The trained state lives on the accelerator, not the host.
    leaves = jax.tree.leaves(runner.states[pop_name].params)
    homes = {d for leaf in leaves for d in leaf.devices()}
    say(param_leaves=len(leaves),
        param_devices=json.dumps(sorted(str(d) for d in homes)))
    require(homes == set(devices),
            "trained params are not resident on every visible device")
    require({d.platform for d in homes} == {device["platform"]},
            "trained params are not on the accelerator platform")

    # Per-round history: what was trained and evaluated.
    history = runner.history
    require(len(history) == ROUNDS,
            f"{len(history)} round records, expected {ROUNDS}")
    losses = []
    for rec in history:
        train = rec["train"][pop_name]
        evaluated = rec["evaluate"][pop_name]
        say(round=rec["round"], mean_loss=f"{train['mean_loss']:.6f}",
            clients_trained=train["clients_trained"],
            eval_loss=f"{evaluated['eval_loss']:.6f}",
            eval_acc=f"{evaluated['eval_acc']:.4f}")
        require(train["clients_trained"] == n_clients,
                f"round {rec['round']}: clients_trained="
                f"{train['clients_trained']}, expected {n_clients}")
        require(math.isfinite(train["mean_loss"]),
                f"round {rec['round']}: mean_loss not finite")
        require(math.isfinite(evaluated["eval_loss"]),
                f"round {rec['round']}: eval_loss not finite")
        require(0.0 <= evaluated["eval_acc"] <= 1.0,
                f"round {rec['round']}: eval_acc outside [0, 1]")
        losses.append(train["mean_loss"])
    require(losses[-1] < losses[0],
            f"mean_loss did not fall: {losses[0]} -> {losses[-1]}")

    # Seconds per round from the runner's own spans; every span closes
    # after a host read of that operator's result (float(mean_loss) for
    # train, the eval loss/accuracy floats for evaluate).
    seconds = [0.0] * ROUNDS
    for op in ("train", "evaluate"):
        for span in default_tracer().spans(f"round.{op}"):
            if span.attrs.get("task_id") == task_id:
                seconds[span.attrs["round_idx"]] += span.duration_s
    say(first_round_s=f"{seconds[0]:.3f}",
        later_round_s=json.dumps([round(s, 3) for s in seconds[1:]]))
    require(all(s > 0.0 for s in seconds), f"missing round spans: {seconds}")

    stats = cache_stats()
    say(compile_cache_dir=enabled_dir(), cache_hits=int(stats["hits"]),
        cache_misses=int(stats["misses"]))
    require(enabled_dir() is not None, "compile cache is not enabled")


def main() -> int:
    try:
        device = device_facts()
        require(device["platform"] == "tpu",
                f"no TPU: JAX reports platform {device['platform']!r}")
        task = load_task()
        runner, topology = run_task(task)
        check_run(runner, task, topology, device)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
