"""What the check reads when the reference's Mamba-2 recurrence leaves its
decay out (``a_t = 1``): the planted fault that is ``nemotron_h``'s own.

    python scripts/nemotron_h_planted_decay.py [--seed N] [--seconds 3]

One short run of ``nemotron_twotower_ep16.8_silo_2k`` through the whole
harness; its own check (sound, a run's first) and then a second check round
on the same runner, from the state the first left, against
``benchmark/reference/nemotron_h.py`` with ``A`` zeroed in its recurrence.
Prints both checks' numbers beside the limits; exits 0 where the sound check
is correct and the planted one is not. The machinery is
``scripts/kimi_linear_planted_decay.py``'s; the reference itself has no
switch: the fault is planted in the copy of its module that the check loads
(:func:`leave_decay_out`). Not part of a benchmark run; PERF.md section 2
quotes its readings. Refuses the CPU (``run``'s ``device`` is for a
rehearsal at the tiny preset's size).
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import kimi_linear_planted_decay as machinery  # noqa: E402

CELL = "nemotron_twotower_ep16.8_silo_2k"


def leave_decay_out(reference):
    """Plant the fault in ``reference`` (a loaded copy of
    ``benchmark/reference/nemotron_h.py`` that has not run yet): its
    recurrence gets a log decay of zero, ``a_t = 1``."""
    ssd = reference.ssd
    reference.ssd = lambda x, dt, A, B, C: ssd(x, dt, 0.0 * A, B, C)
    return reference


run = functools.partial(machinery.run, reference="nemotron_h",
                        plant=leave_decay_out)


def main(argv=None) -> int:
    return machinery.main(argv, CELL, __doc__, reference="nemotron_h",
                          plant=leave_decay_out)


if __name__ == "__main__":
    sys.exit(main())
