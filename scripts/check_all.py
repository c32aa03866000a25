#!/usr/bin/env python
"""One driver for every static analyzer in the repo.

Runs the program-analysis suite (``olearning_sim_tpu/analysis/``) and the
four pre-existing check scripts under uniform exit codes and an optional
JSON report:

====================  =====================================================
analyzer              what it guards
====================  =====================================================
ast_rules             repo invariants: wall-clock discipline, sqlite
                      routing, host-sync-free engine, no invisible
                      exception swallows (analysis/ast_rules)
metrics               telemetry naming/catalog (scripts/check_metrics)
event_kinds           resilience event vocabulary + docs
                      (scripts/check_event_kinds)
injection_points      chaos points documented + tested
                      (scripts/check_injection_points)
tp_coverage           every mp>1 task config shards >=50% of parameter
                      elements (analysis/tp_coverage; pure eval_shape,
                      no compile)
convergence           model quality vs blessed envelopes: a fixed-seed
                      convergence grid (clean / async / attacked+defended
                      / attacked-undefended / drift) re-run and diffed
                      against analysis/convergence.json
                      (analysis/convergence_gate; ~15 s of tiny CPU
                      training — --skip it for a sub-second lint pass)
hlo_collectives       defended program has no O(clients x params)
                      all-gather (scripts/check_hlo_collectives; shares
                      the grid compile below)
hlo_audit             per-variant HLO budgets: collective bytes, largest
                      buffer, dtype census, donation survival vs
                      analysis/budgets.json (analysis/hlo_audit)
retrace               per-round scalar knobs are data — one executable
                      per variant across knob settings (analysis/retrace)
====================  =====================================================

Exit codes: 0 = all clean, 1 = findings, 2 = an analyzer itself crashed.

Usage::

    python scripts/check_all.py                  # everything
    python scripts/check_all.py --only ast_rules,metrics
    python scripts/check_all.py --skip hlo_audit,retrace,hlo_collectives
    python scripts/check_all.py --json report.json
    python scripts/check_all.py --bless          # re-bless budgets.json
    python scripts/check_all.py --list

The three HLO analyzers AOT-compile the whole round-program variant grid
once (shared cache); on a laptop CPU that is the bulk of the runtime —
``--skip`` them for a fast pre-commit pass. Standalone entrypoints of the
absorbed scripts keep working unchanged.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")

if __name__ == "__main__":
    # The HLO analyzers need a multi-device CPU platform BEFORE jax
    # initializes a backend (mirrors tests/conftest.py).
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    # Direct assignment, not setdefault: the analyzers' budgets are blessed
    # on the CPU lowering, whatever platform the caller's environment names.
    os.environ["JAX_PLATFORMS"] = "cpu"

for p in (REPO, SCRIPTS):
    if p not in sys.path:
        sys.path.insert(0, p)

HLO_ANALYZERS = ("hlo_collectives", "hlo_audit", "retrace")


def build_registry(grid_artifacts=None):
    """name -> zero-arg check() callable, cheap analyzers first. The HLO
    entries share one grid compile via a lazy artifacts thunk
    (``grid_artifacts`` injects precomputed ones — tests)."""
    import check_event_kinds
    import check_injection_points
    import check_metrics

    from olearning_sim_tpu.analysis import (
        ast_rules,
        convergence_gate,
        hlo_audit,
        retrace,
        tp_coverage,
    )

    cache = {"arts": grid_artifacts}

    def arts():
        if cache["arts"] is None:
            from olearning_sim_tpu.analysis import grid

            cache["arts"] = grid.grid_artifacts(
                progress=lambda name: print(f"  lowering {name}",
                                            file=sys.stderr)
            )
        return cache["arts"]

    def hlo_collectives_check():
        import check_hlo_collectives

        # The guard's target program is the defended dp=2 replicated-
        # update variant — reuse the grid's compile of exactly that.
        art = arts()["defense/shard0/dp2"]
        return check_hlo_collectives.check(
            dp=2,
            prebuilt=(art["compiled"], art["params_bytes"], art["clients"]),
        )

    return {
        "ast_rules": ast_rules.check,
        "metrics": check_metrics.check,
        "event_kinds": check_event_kinds.check,
        "injection_points": check_injection_points.check,
        "tp_coverage": tp_coverage.check,
        "convergence": convergence_gate.check,
        "hlo_collectives": hlo_collectives_check,
        "hlo_audit": lambda: hlo_audit.check(artifacts_by_name=arts()),
        "retrace": lambda: retrace.check(artifacts_by_name=arts()),
    }


def run(only=None, skip=None, grid_artifacts=None):
    """(report dict, exit code). See module docstring for codes."""
    from olearning_sim_tpu.analysis import run_analyzers

    registry = build_registry(grid_artifacts)
    unknown = [n for n in (only or []) + (skip or []) if n not in registry]
    if unknown:
        raise SystemExit(
            f"check_all: unknown analyzer(s) {unknown}; "
            f"known: {', '.join(registry)}"
        )
    report = run_analyzers(registry, only=only, skip=skip)
    if any(r["error"] for r in report.values()):
        code = 2
    elif any(not r["ok"] for r in report.values()):
        code = 1
    else:
        code = 0
    return report, code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run all static analyzers (see module docstring)")
    ap.add_argument("--only", default=None,
                    help="comma-separated analyzer names to run")
    ap.add_argument("--skip", default=None,
                    help="comma-separated analyzer names to skip")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the machine-readable report here")
    ap.add_argument("--list", action="store_true",
                    help="list analyzer names and exit")
    ap.add_argument("--bless", action="store_true",
                    help="re-measure the variant grid and rewrite "
                         "analysis/budgets.json (after an INTENTIONAL "
                         "program change; commit the diff)")
    ap.add_argument("--bless-convergence", action="store_true",
                    help="re-run the convergence gate grid and rewrite "
                         "analysis/convergence.json (after an INTENTIONAL "
                         "quality change; commit the diff)")
    args = ap.parse_args(argv)

    if args.list:
        for name in build_registry():
            print(name)
        return 0
    if args.bless:
        from olearning_sim_tpu.analysis import hlo_audit

        budgets = hlo_audit.bless()
        print(f"check_all: blessed {len(budgets['variants'])} variants "
              f"-> {hlo_audit.BUDGETS_PATH}")
        return 0
    if args.bless_convergence:
        from olearning_sim_tpu.analysis import convergence_gate

        envelopes = convergence_gate.bless()
        print(f"check_all: blessed {len(envelopes['entries'])} convergence "
              f"entries -> {convergence_gate.ENVELOPES_PATH}")
        return 0

    only = args.only.split(",") if args.only else None
    skip = args.skip.split(",") if args.skip else None
    report, code = run(only=only, skip=skip)

    width = max(len(n) for n in report) if report else 0
    for name, r in report.items():
        if r["error"]:
            status = f"ERROR ({r['error']})"
        elif r["ok"]:
            status = "ok"
        else:
            status = f"{len(r['problems'])} finding(s)"
        print(f"check_all: {name:<{width}}  {status}  [{r['seconds']}s]")
        for p in r["problems"]:
            print(f"  {name}: {p}", file=sys.stderr)

    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump({"ok": code == 0, "exit_code": code,
                       "analyzers": report}, f, indent=1)
            f.write("\n")
        print(f"check_all: report -> {args.json}")
    print(f"check_all: {'CLEAN' if code == 0 else 'FAILED'} (exit {code})")
    return code


if __name__ == "__main__":
    sys.exit(main())
