"""What the check reads when the reference's KDA recurrence leaves its
decay out (``alpha = 1``): the planted fault that is ``kimi_linear``'s own.

    python scripts/kimi_linear_planted_decay.py [--seed N] [--seconds 3]

One short run of ``kimi_linear_ep32.8_silo_2k`` through the whole harness;
its own check (sound, a run's first) and then a second check round on the
same runner, from the state the first left, against
``benchmark/reference/kimi_linear.py`` with its log decay zeroed. Prints both
checks' numbers beside the limits; exits 0 where the sound check is correct
and the planted one is not. A cell that passed the planted reference would
not guard the mechanism it was added for. ``benchmark/check.py`` and the
harness and the reference are used as they are: the fault is planted in the
copy of the reference module that the check loads (:func:`leave_decay_out`).
Not part of a benchmark run; PERF.md
section 2 quotes its readings. Refuses the CPU (``run``'s ``device`` is for
a rehearsal at the tiny preset's size).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "kimi_linear_ep32.8_silo_2k"


def leave_decay_out(reference):
    """Plant the fault in ``reference`` (a loaded copy of
    ``benchmark/reference/kimi_linear.py`` that has not run yet): its
    recurrence gets a log decay of zero, ``alpha = 1``."""
    delta_rule = reference.delta_rule
    reference.delta_rule = (
        lambda q, k, v, g, beta: delta_rule(q, k, v, 0.0 * g, beta))
    return reference


def run(workload, seed, seconds, *, reference="kimi_linear",
        plant=leave_decay_out, **run_cell_kwargs):
    """(the sound check, the check against the reference without its
    decay), both ``benchmark.check.CheckResult``. ``reference`` names the
    module the fault goes into and ``plant`` plants it (another model's
    script of this kind gives its own two)."""
    from benchmark import check, harness, manifest

    find_module, run_check = manifest.find_module, check.run_check
    planted = []

    def without_decay(kind, name, *where):
        module = find_module(kind, name, *where)    # a new copy every call
        if (kind, name) == ("reference", reference):
            plant(module)
        return module

    def both_checks(runner, cell, task, check_seed, plant=False):
        sound = run_check(runner, cell, task, check_seed, plant)
        manifest.find_module = without_decay
        try:
            planted.append(run_check(runner, cell, task, check_seed + 1))
        finally:
            manifest.find_module = find_module
        return sound

    check.run_check = both_checks
    try:
        done = harness.run_cell(workload, seed, seconds, False,
                                **run_cell_kwargs)
    finally:
        check.run_check = run_check
    return done.checks[0], planted[0]


def main(argv=None, cell=CELL, doc=__doc__, **fault) -> int:
    import jax

    parser = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2**31 + 341)
    parser.add_argument("--seconds", type=float, default=3.0)
    args = parser.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("no TPU: the check's readings at the cell's size are chip "
              "readings", file=sys.stderr)
        return 1
    sound, planted = run(cell, args.seed, args.seconds, **fault)
    for line in planted.lines():
        print("planted " + line, flush=True)
    print(json.dumps({
        "seed": args.seed, "limits": sound.limits,
        "sound": dict(sound.numbers, correct=sound.correct),
        "decay_left_out": dict(planted.numbers, correct=planted.correct)}))
    return 0 if sound.correct and not planted.correct else 1


if __name__ == "__main__":
    sys.exit(main())
