"""Program-analysis suite tests (olearning_sim_tpu/analysis/ +
scripts/check_all.py).

Two halves, mirroring the suite's contract:

- **clean on HEAD** — each analyzer passes over the real repo /
  a representative sub-grid of real compiled round programs (the FULL
  grid runs in scripts/check_all.py, wired into CI; a slow-marked test
  covers it here).
- **mutation tests** — each analyzer FAILS on a planted bad program /
  source snippet / budget, proving the lints actually bite. The four
  absorbed check scripts additionally prove their standalone entrypoints
  exit non-zero on seeded violations (not just pass on clean input).
"""

import json
import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)

from olearning_sim_tpu.analysis import (  # noqa: E402
    ast_rules, hlo_audit, retrace, run_analyzers,
)
from olearning_sim_tpu.analysis.grid import Variant  # noqa: E402

# Every program structure + both shard modes + both dp, in 5 compiles
# (maximal = deadline+attack+defense in one program; async_defense is the
# one async program whose anomaly scoring no other tier-1 test traces).
# The full grid is check_all's job; tier-1 keeps the compile bill bounded.
SUBSET = [
    Variant("plain", False, 1),
    Variant("deadline", False, 2),
    Variant("defense", False, 2),
    Variant("maximal", True, 2),
    Variant("async_defense", False, 2),
]


@pytest.fixture(scope="module")
def sub_grid():
    from olearning_sim_tpu.analysis import grid

    return {v.name: grid.artifacts(v) for v in SUBSET}


def _subset_budgets(names):
    budgets = hlo_audit.load_budgets()
    return {
        "tolerances": budgets.get("tolerances", {}),
        "variants": {n: budgets["variants"][n] for n in names},
    }


# --------------------------------------------------------------- hlo_audit

def test_hlo_audit_clean_on_head(sub_grid):
    budgets = _subset_budgets(sub_grid)
    problems = hlo_audit.check(artifacts_by_name=sub_grid, budgets=budgets)
    assert problems == [], "\n".join(problems)


def test_hlo_audit_measures_real_programs(sub_grid):
    m = hlo_audit.measure(sub_grid["defense/shard0/dp2"])
    # The sharded robust aggregation must be visible as an all-to-all,
    # and the donate_argnums donations must survive to the executable.
    assert "all-to-all" in m["collectives"]
    assert m["donated_inputs"] > 0
    assert m["aliased_outputs"] > 0
    assert "f64" not in m["dtypes"]


def _clean_entry():
    return {
        "collectives": {"all-reduce": 512, "all-to-all": 4096},
        "largest_buffer_bytes": 9000,
        "largest_buffer_op": "parameter",
        "dtypes": ["bf16", "f32", "s32"],
        "donated_inputs": 6,
        "aliased_outputs": 6,
    }


def test_hlo_audit_mutations_bite():
    golden = _clean_entry()

    # f64 leakage always fails.
    m = _clean_entry()
    m["dtypes"] = ["f32", "f64"]
    assert any("f64" in p for p in hlo_audit.compare("v", m, golden))

    # A new collective kind (the gathered formulation returning).
    m = _clean_entry()
    m["collectives"] = dict(golden["collectives"], **{"all-gather": 30000})
    assert any("new collective kind 'all-gather'" in p
               for p in hlo_audit.compare("v", m, golden))

    # A vanished collective (sharded path silently gone).
    m = _clean_entry()
    del m["collectives"]["all-to-all"]
    assert any("disappeared" in p for p in hlo_audit.compare("v", m, golden))

    # Collective bytes blow-up past tolerance.
    m = _clean_entry()
    m["collectives"]["all-to-all"] = 4096 * 16
    assert any("grew" in p for p in hlo_audit.compare("v", m, golden))

    # Largest-buffer regression (clients x params intermediate).
    m = _clean_entry()
    m["largest_buffer_bytes"] = int(9000 * 1.3)
    assert any("largest live buffer" in p
               for p in hlo_audit.compare("v", m, golden))

    # A lost donation.
    m = _clean_entry()
    m["donated_inputs"] = 0
    assert any("donation" in p for p in hlo_audit.compare("v", m, golden))

    # All clean: no findings.
    assert hlo_audit.compare("v", _clean_entry(), golden) == []


def test_hlo_audit_catches_planted_bad_program():
    """End-to-end: a synthetic compiled artifact whose program all-gathers
    a big buffer, lost its donations, and leaked f64 fails the audit."""
    bad_compiled = textwrap.dedent("""\
        HloModule jit_round_step, is_scheduled=true, entry_computation_layout={(f32[16,128]{1,0})->(f32[16,128]{1,0})}

        ENTRY %main (p0: f32[16,128]) -> (f32[16,128]) {
          %p0 = f32[16,128]{1,0} parameter(0)
          %ag = f32[32,128]{1,0} all-gather(f32[16,128]{1,0} %p0), dimensions={0}
          %leak = f64[16,128]{1,0} convert(f32[16,128]{1,0} %p0)
          ROOT %t = (f32[16,128]{1,0}) tuple(f32[16,128]{1,0} %p0)
        }
        """)
    art = {
        "compiled": bad_compiled,
        "lowered_a": "func.func public @main(%arg0: tensor<16x128xf32>)",
        "params_bytes": 512, "clients": 16, "memory": None,
    }
    golden = {
        "collectives": {}, "largest_buffer_bytes": 8192,
        "dtypes": ["f32"], "donated_inputs": 6, "aliased_outputs": 6,
    }
    problems = hlo_audit.compare("bad", hlo_audit.measure(art), golden)
    joined = "\n".join(problems)
    assert "f64" in joined
    assert "all-gather" in joined
    assert "donation" in joined or "aliases" in joined


def test_hlo_audit_grid_budget_drift(sub_grid):
    budgets = _subset_budgets(sub_grid)
    # A variant the budgets never heard of -> must be blessed.
    extra = dict(sub_grid)
    extra["novel/shard0/dp2"] = sub_grid["plain/shard0/dp1"]
    problems = hlo_audit.check(artifacts_by_name=extra, budgets=budgets)
    assert any("missing from budgets.json" in p for p in problems)
    # A budget entry whose variant left the grid -> stale.
    smaller = {k: v for k, v in sub_grid.items()
               if k != "plain/shard0/dp1"}
    problems = hlo_audit.check(artifacts_by_name=smaller, budgets=budgets)
    assert any("no longer in the variant grid" in p for p in problems)


def test_hlo_audit_missing_budget_file(tmp_path):
    problems = hlo_audit.check(
        artifacts_by_name={}, budgets=None,
        budgets_path=str(tmp_path / "nope.json"),
    )
    assert problems and "--bless" in problems[0]


# ----------------------------------------------------------------- retrace

def test_retrace_clean_on_head(sub_grid):
    problems = retrace.check(artifacts_by_name=sub_grid)
    assert problems == [], "\n".join(problems)


def test_retrace_catches_baked_constant_jit():
    """A program builder that closes over its knob (the pre-PR 5 bug
    shape) produces knob-dependent lowerings AND distinct functions —
    both layers of the detector fire."""
    import jax
    import jax.numpy as jnp

    def build(clip):  # the WRONG way: knob captured at trace time
        return jax.jit(lambda x: jnp.minimum(x, clip))

    fa, fb = build(1.0), build(2.0)
    x = jnp.zeros((4,), jnp.float32)
    art = {
        "variant": "baked", "same_fn": fa is fb, "trace_count": 1,
        "lowered_a": fa.lower(x).as_text(),
        "lowered_b": fb.lower(x).as_text(),
    }
    problems = retrace.compare_variant(art)
    joined = "\n".join(problems)
    assert "DIFFERENT compiled functions" in joined
    assert "baked into the traced program" in joined
    assert "constant" in joined  # the diff pointer names the leak


def test_retrace_catches_recompile_and_retrace_counts():
    base = {"variant": "v", "same_fn": True, "trace_count": 1,
            "lowered_a": "m", "lowered_b": "m"}
    assert retrace.compare_variant(base) == []
    assert any("traced 2 times" in p for p in retrace.compare_variant(
        dict(base, trace_count=2)))
    assert any("DIFFERENT compiled functions" in p
               for p in retrace.compare_variant(dict(base, same_fn=False)))


# --------------------------------------------------------------- ast_rules

def test_ast_rules_clean_on_head():
    problems = ast_rules.check()
    assert problems == [], "\n".join(problems)


def test_the_client_block_stage_is_single_and_builders_import_stages():
    """The finiteness gate is called from exactly one function of the
    package (the block stage every round-program builder calls), and the
    builders beside fedcore import the stages, never fedcore: builders ->
    ``engine/round_stages.py``, one way."""
    import ast

    pkg = os.path.join(REPO, "olearning_sim_tpu")
    callers, fedcore_imports = [], []

    def calls(node, fn, rel):
        """Every call of the gate under ``node``, by innermost function."""
        for child in ast.iter_child_nodes(node):
            inner = (child.name if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)) else fn)
            if isinstance(child, ast.Call) and getattr(
                    child.func, "id", getattr(child.func, "attr", None)
            ) == "_finite_client_mask":
                callers.append(f"{rel}:{fn}")
            calls(child, inner, rel)

    for path in ast_rules._py_files(pkg):
        rel = os.path.relpath(path, pkg)
        tree = ast.parse(open(path, encoding="utf-8").read())
        calls(tree, "<module>", rel)
        if rel in ("engine/async_rounds.py", "engine/pp_rounds.py"):
            for node in ast.walk(tree):
                names = []
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    names = [node.module or ""] + [
                        f"{node.module}.{a.name}" for a in node.names]
                fedcore_imports += [
                    f"{rel}:{node.lineno}" for n in names
                    if n.endswith("engine.fedcore")]
    assert callers == ["engine/round_stages.py:client_block"], callers
    assert fedcore_imports == [], fedcore_imports


MODELS_DIR = os.path.join(REPO, "olearning_sim_tpu", "models")
MODEL_MODULES = sorted(
    name[:-3] for name in os.listdir(MODELS_DIR)
    if name.endswith(".py") and name not in ("__init__.py", "registry.py"))
DECODERS = ("lfm2", "kimi_linear", "nemotron_h", "phi4flash")


@pytest.mark.parametrize("module", MODEL_MODULES)
def test_a_model_module_imports_no_siblings_private_name(module):
    """What two model families share has a public name and an owner: no
    file under ``models/`` takes an underscored name from another file
    there (imported, or read off an imported module), and no decoder family
    imports another's module, so an edit to a family's file is an edit to
    its own benchmark cells (the shared parts are
    ``models/decoder_parts.py``'s and ``models/moe.py``'s)."""
    import ast

    pkg = "olearning_sim_tpu.models"
    tree = ast.parse(open(os.path.join(MODELS_DIR, module + ".py"),
                          encoding="utf-8").read())
    siblings, private = {}, []              # local name -> sibling module
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith(pkg + "."):
                    siblings[a.asname or a.name] = a.name[len(pkg) + 1:]
        elif isinstance(node, ast.ImportFrom):
            source = node.module or ""
            if node.level:                  # from . import x / from .x import
                source = pkg + ("." + source if source else "")
            if source == pkg:
                for a in node.names:
                    if os.path.exists(os.path.join(MODELS_DIR,
                                                   a.name + ".py")):
                        siblings[a.asname or a.name] = a.name
            elif source.startswith(pkg + "."):
                siblings[source] = source[len(pkg) + 1:]
                private += [f"{source}.{a.name}" for a in node.names
                            if a.name.startswith("_")]
    private += [
        f"{siblings[node.value.id]}.{node.attr}" for node in ast.walk(tree)
        if isinstance(node, ast.Attribute) and node.attr.startswith("_")
        and isinstance(node.value, ast.Name) and node.value.id in siblings]
    assert private == [], private
    if module in DECODERS:
        others = sorted(set(siblings.values()) & set(DECODERS) - {module})
        assert others == [], others


def _documents():
    docs = ["README.md", "PARITY.md", "examples/README.md",
            ".claude/skills/verify/SKILL.md"]
    docs += sorted("docs/" + name for name in os.listdir(
        os.path.join(REPO, "docs")) if name.endswith(".md"))
    return [d for d in docs if os.path.exists(os.path.join(REPO, d))]


@pytest.mark.parametrize("doc", _documents())
def test_documents_name_only_files_that_exist(doc):
    """A document that names a script, an example, a config, another
    document or a record in the root names one that is there: a command the
    reader is sent to run, or a file quoted as evidence, does not outlive
    the file."""
    import re

    text = open(os.path.join(REPO, doc), encoding="utf-8").read()
    names = set(re.findall(
        r"(?<![\w/.-])(?:scripts|examples|configs|docs)/[\w./-]+"
        r"\.(?:py|json|md|sh)\b", text))
    names |= set(re.findall(
        r"(?<![\w/.*-])(?:[A-Z][A-Z_]+[\w.]*\.json|bench\.py)\b", text))
    missing = sorted(n for n in names
                     if not os.path.exists(os.path.join(REPO, n)))
    assert missing == [], missing


def test_ast_rules_wall_clock_rule():
    hits = ast_rules.lint_source(
        "import time\nnow = time.time()\n", "olearning_sim_tpu/x.py")
    assert [h["rule"] for h in hits] == ["wall-clock"]
    # Through aliases and from-imports too.
    hits = ast_rules.lint_source(
        "from time import time as now\nt = now()\n",
        "olearning_sim_tpu/x.py")
    assert [h["rule"] for h in hits] == ["wall-clock"]
    # monotonic()/perf_counter() are fine; clocks.py itself is exempt.
    assert ast_rules.lint_source(
        "import time\nt = time.monotonic()\n",
        "olearning_sim_tpu/x.py") == []
    assert ast_rules.lint_source(
        "import time\nt = time.time()\n",
        "olearning_sim_tpu/utils/clocks.py") == []


def test_ast_rules_sqlite_rule():
    src = "import sqlite3 as s\nconn = s.connect('/tmp/db')\n"
    hits = ast_rules.lint_source(src, "olearning_sim_tpu/taskmgr/x.py")
    assert [h["rule"] for h in hits] == ["sqlite-connect"]
    assert ast_rules.lint_source(
        src, "olearning_sim_tpu/utils/repo.py") == []


def test_ast_rules_host_sync_rule():
    src = ("import jax\n"
           "def f(m):\n"
           "    a = jax.device_get(m)\n"
           "    m.block_until_ready()\n")
    hits = ast_rules.lint_source(
        src, "olearning_sim_tpu/engine/fedcore.py")
    assert [h["rule"] for h in hits] == ["host-sync", "host-sync"]
    # The runner is ALLOWED to sync (it accounts host_transfer).
    assert ast_rules.lint_source(
        src, "olearning_sim_tpu/engine/runner.py") == []


def test_ast_rules_silent_except_rule():
    bad = "try:\n    f()\nexcept Exception:\n    pass\n"
    hits = ast_rules.lint_source(bad, "olearning_sim_tpu/x.py")
    assert [h["rule"] for h in hits] == ["silent-except"]
    # Bare except and BaseException count too.
    assert ast_rules.lint_source(
        "try:\n    f()\nexcept:\n    pass\n",
        "olearning_sim_tpu/x.py")
    # Narrowed or logged handlers are fine.
    assert ast_rules.lint_source(
        "try:\n    f()\nexcept ValueError:\n    pass\n",
        "olearning_sim_tpu/x.py") == []
    assert ast_rules.lint_source(
        "try:\n    f()\nexcept Exception:\n    log()\n",
        "olearning_sim_tpu/x.py") == []


def _write_pkg(tmp_path, relfile, src):
    pkg = tmp_path / "olearning_sim_tpu"
    path = pkg / relfile
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    return str(pkg)


def test_ast_rules_waiver_policy(tmp_path):
    marker = ast_rules.MARKERS["wall-clock"]
    rel = "olearning_sim_tpu/leases.py"
    src = f"import time\nnow = time.time()  # {marker}: cross-process\n"

    # Marked AND documented in the table: waived.
    pkg = _write_pkg(tmp_path, "leases.py", src)
    waivers = {"wall-clock": {rel: "cross-process lease math"},
               "silent-except": {}, "sqlite-connect": {}, "host-sync": {}}
    assert ast_rules.check(pkg_root=pkg, waivers=waivers) == []

    # Marked but NOT in the table: undocumented waiver.
    no_table = {r: {} for r in ast_rules.MARKERS}
    problems = ast_rules.check(pkg_root=pkg, waivers=no_table)
    assert any("not in the ast_rules WAIVERS table" in p for p in problems)

    # In the table but no marker: violation + stale table entry.
    pkg2 = _write_pkg(tmp_path, "leases.py",
                      "import time\nnow = time.time()\n")
    problems = ast_rules.check(pkg_root=pkg2, waivers=waivers)
    assert any("[wall-clock] time.time()" in p for p in problems)
    assert any("no live waived site" in p for p in problems)

    # A stale marker with no flagged site nearby is itself flagged.
    pkg3 = _write_pkg(tmp_path, "leases.py",
                      f"x = 1  # {marker}: nothing here\n")
    problems = ast_rules.check(pkg_root=pkg3, waivers=waivers)
    assert any("stale waiver marker" in p for p in problems)


def test_ast_rules_planted_bad_package(tmp_path):
    """The package-walk path flags a seeded source file end to end."""
    pkg = _write_pkg(tmp_path, "engine/fedcore.py", textwrap.dedent("""\
        import time
        import sqlite3
        import jax

        def step(m):
            t = time.time()
            c = sqlite3.connect("/tmp/x.db")
            v = jax.device_get(m)
            try:
                c.close()
            except Exception:
                pass
            return t, v
        """))
    waivers = {r: {} for r in ast_rules.MARKERS}
    problems = ast_rules.check(pkg_root=pkg, waivers=waivers)
    rules = {p.split("[")[1].split("]")[0] for p in problems if "[" in p}
    assert rules == {"wall-clock", "sqlite-connect", "host-sync",
                     "silent-except"}, problems


# ----------------------------- absorbed check scripts: seeded violations

def test_check_metrics_exits_nonzero_on_seeded_violation(monkeypatch):
    import check_metrics

    from olearning_sim_tpu import telemetry

    bad = dict(telemetry.CATALOG)
    bad["ols_engine_bogus"] = (telemetry.COUNTER, "bad unit + dead", ())
    monkeypatch.setattr(telemetry, "CATALOG", bad)
    assert check_metrics.check() != []
    assert check_metrics.main() == 1
    monkeypatch.undo()
    assert check_metrics.main() == 0


def test_check_event_kinds_exits_nonzero_on_seeded_violation(
        monkeypatch, tmp_path):
    import check_event_kinds as cek

    # A declared kind that is neither documented nor emitted.
    events = tmp_path / "events.py"
    real = open(os.path.join(REPO, "olearning_sim_tpu", "resilience",
                             "events.py"), encoding="utf-8").read()
    events.write_text(real + '\nGHOST_KIND = "ghost_kind"\n')
    problems = cek.check(events=str(events))
    assert any("ghost_kind" in p and "not documented" in p
               for p in problems)
    assert any("dead kind" in p for p in problems)
    monkeypatch.setattr(cek, "EVENTS", str(events))
    assert cek.main() == 1


def test_check_injection_points_exits_nonzero_on_seeded_violation(
        monkeypatch, tmp_path):
    import check_injection_points as cip

    # A doc with no injection-point section at all: every consulted point
    # is undocumented.
    doc = tmp_path / "resilience.md"
    doc.write_text("# empty\n\n## Something else\n")
    problems = cip.check(doc_path=str(doc))
    assert any("not documented" in p for p in problems)
    monkeypatch.setattr(cip, "DOC", str(doc))
    assert cip.main() == 1


def test_check_hlo_collectives_exits_nonzero_on_seeded_violation(
        monkeypatch):
    import check_hlo_collectives as chc

    # The pre-sharding formulation: an all-gather of the whole per-client
    # delta matrix, and no all-to-all anywhere.
    clients, params_bytes, dp = 16, 512, 2
    n = clients * params_bytes // 4
    gathered = (f"  %ag = f32[{n}]{{0}} all-gather(f32[{n // dp}]{{0}} "
                f"%p), dimensions={{0}}\n")
    problems = chc.check(prebuilt=(gathered, params_bytes, clients))
    assert any("all-gathers" in p for p in problems)
    assert any("no all-to-all" in p for p in problems)
    monkeypatch.setattr(
        chc, "build_defended_lowering",
        lambda **kw: (gathered, params_bytes, clients))
    assert chc.main() == 1


# ------------------------------------------------------- check_all driver

def _import_check_all():
    import check_all

    return check_all


def test_check_all_cheap_analyzers_clean():
    check_all = _import_check_all()
    report, code = check_all.run(
        only=["ast_rules", "metrics", "event_kinds", "injection_points"])
    assert code == 0, report
    assert set(report) == {"ast_rules", "metrics", "event_kinds",
                           "injection_points"}
    assert all(r["ok"] and r["error"] is None for r in report.values())


def test_check_all_hlo_analyzers_share_injected_grid(sub_grid):
    check_all = _import_check_all()
    # hlo_collectives consumes the grid's defended dp=2 compile directly —
    # no second build.
    report, code = check_all.run(only=["hlo_collectives"],
                                 grid_artifacts=sub_grid)
    assert code == 0, report
    assert report["hlo_collectives"]["ok"]


def test_check_all_exit_codes(monkeypatch):
    check_all = _import_check_all()
    from olearning_sim_tpu.analysis import ast_rules as ar

    monkeypatch.setattr(ar, "check", lambda **kw: ["seeded finding"])
    report, code = check_all.run(only=["ast_rules"])
    assert code == 1
    assert report["ast_rules"]["problems"] == ["seeded finding"]

    def boom():
        raise RuntimeError("analyzer crashed")

    monkeypatch.setattr(ar, "check", boom)
    report, code = check_all.run(only=["ast_rules"])
    assert code == 2
    assert "RuntimeError" in report["ast_rules"]["error"]

    with pytest.raises(SystemExit):
        check_all.run(only=["no_such_analyzer"])


def test_check_all_json_report(tmp_path, monkeypatch):
    check_all = _import_check_all()
    out = tmp_path / "report.json"
    code = check_all.main(["--only", "ast_rules,metrics",
                           "--json", str(out)])
    assert code == 0
    report = json.loads(out.read_text())
    assert report["ok"] is True and report["exit_code"] == 0
    assert set(report["analyzers"]) == {"ast_rules", "metrics"}


def test_run_analyzers_uniform_report():
    report = run_analyzers({
        "clean": lambda: [],
        "dirty": lambda: ["p1", "p2"],
    })
    assert report["clean"]["ok"] and not report["dirty"]["ok"]
    assert report["dirty"]["problems"] == ["p1", "p2"]
    assert report["clean"]["error"] is None


@pytest.mark.slow
def test_check_all_full_grid_clean():
    """The acceptance run: every analyzer over the FULL 20-variant grid
    (this is what CI executes via scripts/check_all.py)."""
    check_all = _import_check_all()
    report, code = check_all.run()
    assert code == 0, {k: v for k, v in report.items() if not v["ok"]}
