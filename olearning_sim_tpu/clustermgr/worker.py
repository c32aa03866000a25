"""Worker entrypoint for :class:`MultiHostLauncher`.

``python -m olearning_sim_tpu.clustermgr.worker --target pkg.module:function``
joins the JAX distributed world configured by the ``OLS_*`` environment
variables, then calls ``function()`` (it receives any remaining CLI args).
The reference analogue is the Ray job entrypoint
``python3 run_task.py --task '<json>'`` (``task_runner.py:44``).
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--target", required=True,
                        help="import path 'pkg.module:function'")
    args, rest = parser.parse_known_args(argv)

    platform = os.environ.get("OLS_PLATFORM", "")
    if platform:
        # Must happen before the first backend touch.
        import jax

        jax.config.update("jax_platforms", platform)

    from olearning_sim_tpu.clustermgr.launcher import initialize_distributed

    initialize_distributed()

    mod_name, _, fn_name = args.target.partition(":")
    if not fn_name:
        print(f"--target must be 'module:function', got {args.target!r}",
              file=sys.stderr)
        return 2
    fn = getattr(importlib.import_module(mod_name), fn_name)
    result = fn(*rest) if rest else fn()
    return int(result) if isinstance(result, int) else 0


if __name__ == "__main__":
    sys.exit(main())
