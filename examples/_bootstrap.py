"""Shared example preamble: put the repo on ``sys.path``. The examples run
on whatever backend JAX finds — the chip on a TPU host; set
``JAX_PLATFORMS=cpu`` to run them on the CPU."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
