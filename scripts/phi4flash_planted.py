"""What the check reads against a reference of ``phi4flash`` with one of the
model's own mechanisms left out: the planted faults that are this model's.

    python scripts/phi4flash_planted.py [--fault decay --fault window
        --fault lambda] [--seed N] [--seconds 3]

One short run of ``phi4flash_vp8.8_silo_2k`` through the whole harness; its
own check (sound, a run's first, with the reference's last local step left
out read beside it) and then, for every ``--fault`` named (all three where
none is), one more check round on the same runner, from the state the check
before left, against ``benchmark/reference/phi4flash.py`` with that fault:

- ``decay``: the recurrence without its decay, ``exp(step x A) -> 1``;
- ``window``: the S layer without its window: it reads the whole causal
  prefix;
- ``lambda``: differential attention without its second member,
  ``a_1 - lambda a_2 -> a_1``.

Prints every check's numbers beside the limits; exits 0 where the sound
check is correct and no planted one is. A cell that passed a planted
reference would not guard the mechanism it was added for. The reference has
no switch: a fault is planted in the copy of its module that the check loads
(:data:`FAULTS`), as ``scripts/nemotron_h_planted_decay.py`` does. Not part
of a benchmark run; PERF.md section 2 quotes its readings. Refuses the CPU
(``run``'s ``device`` is for a rehearsal at the tiny preset's size).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "phi4flash_vp8.8_silo_2k"
REFERENCE = "phi4flash"


def leave_decay_out(reference):
    recurrence = reference.recurrence
    reference.recurrence = (
        lambda x, dt, A, B, C: recurrence(x, dt, 0.0 * A, B, C))


def leave_window_out(reference):
    mask = reference.mask
    reference.mask = lambda L, window=0: mask(L)


def leave_lambda_out(reference):
    reference.differential = lambda a1, a2, lam: a1


# Each plants its fault in a loaded copy of the reference that has not run.
FAULTS = {"decay": leave_decay_out, "window": leave_window_out,
          "lambda": leave_lambda_out}


def run(workload, seed, seconds, faults=tuple(FAULTS), **run_cell_kwargs):
    """(the sound check, {fault: the check against the reference with that
    fault}), all ``benchmark.check.CheckResult``; the sound check's detail
    holds the left-out last local step's reading (``plant=True``)."""
    from benchmark import check, harness, manifest

    find_module, run_check = manifest.find_module, check.run_check
    planted = {}

    def faulty(plant):
        def find(kind, name, *where):
            module = find_module(kind, name, *where)    # a new copy a call
            if (kind, name) == ("reference", REFERENCE):
                plant(module)
            return module
        return find

    def all_checks(runner, cell, task, check_seed, plant=False):
        sound = run_check(runner, cell, task, check_seed, plant)
        for i, fault in enumerate(faults):
            manifest.find_module = faulty(FAULTS[fault])
            try:
                planted[fault] = run_check(
                    runner, cell, task, check_seed + 1 + i)
            finally:
                manifest.find_module = find_module
        return sound

    check.run_check = all_checks
    try:
        done = harness.run_cell(workload, seed, seconds, False, plant=True,
                                **run_cell_kwargs)
    finally:
        check.run_check = run_check
    return done.checks[0], planted


def main(argv=None) -> int:
    import jax

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--fault", action="append", choices=sorted(FAULTS))
    parser.add_argument("--seed", type=int, default=2**31 + 441)
    parser.add_argument("--seconds", type=float, default=3.0)
    args = parser.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU: the check's readings at the cell's size are chip "
              "readings", file=sys.stderr)
        return 1
    sound, planted = run(CELL, args.seed, args.seconds,
                         tuple(args.fault or FAULTS))
    for fault, result in planted.items():
        for line in result.lines():
            print(f"planted {fault} " + line, flush=True)
    print(json.dumps({
        "seed": args.seed, "limits": sound.limits,
        "sound": dict(sound.numbers, correct=sound.correct),
        "last_step_left_out": sound.detail["planted"]["last_step_dropped"],
        **{fault + "_left_out": dict(r.numbers, correct=r.correct)
           for fault, r in planted.items()}}))
    return 0 if sound.correct and not any(
        r.correct for r in planted.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
