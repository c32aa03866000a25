"""The readings the check's limits are set from, and the control that has to
come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2:45 --seconds 3 \\
        [--control-seeds 4] [--checks 6] [--plant]

In ONE process (it holds the chip throughout): for every seed a short run of
the cell through the whole harness, then ``--checks`` check rounds on that
run's runner, each from another seed (another sample of clients, and it
starts from the state the check before it left, so the server optimizer's
step count and memory differ too; the population is generated once per
run, which is most of a run's set-up), printing each check's numbers and,
with ``--plant``, what the parameter-delta numbers read against a server
step with a fault planted in it (``check.perturbed_references``); then, for
the control seeds, the same with the program's own lower-precision path
switched on — ``fedcore.carry_dtype: "bf16"``, the nearest precision below
the float32 local-SGD carry the configurations state, and the step that
would tempt a later PR. The last line is one JSON object with every reading,
the largest sound reading and the smallest control reading per number.

Not part of a benchmark run: the driver never calls it.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROL = {"carry_dtype": "bf16"}


def main(argv=None) -> int:
    from benchmark import harness

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--checks", type=int, default=1)
    parser.add_argument("--plant", action="store_true",
                        help="also read the planted faults (sound runs)")
    args = parser.parse_args(argv)
    readings = {"sound": {}, "control": {}}
    for kind, seeds, overrides in (
            ("sound", args.seeds, None),
            ("control", args.control_seeds, CONTROL)):
        for item in [s for s in seeds.split(",") if s]:
            # "<seed>" or "<seed>:<seconds>" (a window of its own length)
            seed, _, seconds = item.partition(":")
            seed, seconds = int(seed), float(seconds or args.seconds)
            more = [seed * 1000 + i for i in range(1, args.checks)]
            run = harness.run_cell(
                args.workload, seed, seconds, False,
                fedcore_overrides=overrides, more_check_seeds=more,
                plant=args.plant and kind == "sound")
            for check_seed, checked in zip([seed] + more, run.checks):
                readings[kind][check_seed] = dict(
                    checked.numbers, correct=checked.correct,
                    failed_rounds=run.result["failed"])
                for name, numbers in checked.detail.get("planted",
                                                        {}).items():
                    readings.setdefault("planted." + name, {})[
                        check_seed] = numbers
                print(f"reading {kind} seed={check_seed} "
                      + json.dumps(readings[kind][check_seed]), flush=True)
            del run
            gc.collect()
    summary = {}
    names = [n for r in readings["sound"].values() for n in r
             if n not in ("correct", "failed_rounds")]
    for name in dict.fromkeys(names):
        summary[name] = {
            "sound_max": max(r[name] for r in readings["sound"].values()),
            "control_min": (min(r[name] for r in readings["control"].values())
                            if readings["control"] else None),
        }
        for kind, by_seed in readings.items():
            if kind.startswith("planted.") and name in next(
                    iter(by_seed.values())):
                summary[name][kind + "_min"] = min(
                    r[name] for r in by_seed.values())
    print(json.dumps({"workload": args.workload, "readings": readings,
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
