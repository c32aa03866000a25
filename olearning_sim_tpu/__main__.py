"""``python -m olearning_sim_tpu --config platform.yaml`` — stand up the
full platform (the reference's per-service ``test/*/..._srv.py`` entry
points + ``config/config.conf`` wiring, as one command)."""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="olearning_sim_tpu",
        description="Boot the device-simulation platform from a config file.",
    )
    ap.add_argument("--config", required=True, help="platform YAML or INI file")
    ap.add_argument(
        "--print-port", action="store_true",
        help="print the bound gRPC port on stdout once serving",
    )
    ap.add_argument(
        "--platform", default=None,
        help="force the JAX platform (e.g. 'cpu' for control-plane-only "
        "hosts); same effect as the JAX_PLATFORMS environment variable",
    )
    args = ap.parse_args(argv)

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from olearning_sim_tpu.config import session_from_file

    session = session_from_file(args.config)
    session.start()
    print(
        f"olearning_sim_tpu platform serving on port {session.port} "
        f"(services: {', '.join(session.services)})",
        file=sys.stderr,
    )
    if args.print_port:
        print(session.port, flush=True)

    stop = threading.Event()

    def _sig(_signum, _frame):
        stop.set()

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    stop.wait()
    session.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
