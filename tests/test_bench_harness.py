"""bench.py harness mechanics (no model runs).

bench.py measures the chip and nothing else: without a TPU it must refuse
before measuring, and its families are plain data whose shapes do not
depend on the backend.
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    """Import bench.py as a module without running it."""
    spec = importlib.util.spec_from_file_location(
        "bench_module", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_module"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_require_tpu_refuses_the_cpu_backend(bench):
    """The test suite runs on the CPU backend: every bench mode's first
    call must end the process with a non-zero code there."""
    with pytest.raises(SystemExit) as exc:
        bench.require_tpu()
    assert exc.value.code not in (0, None)
    assert "not tpu" in str(exc.value.code)


@pytest.mark.parametrize("mode", ["main", "run_multichip",
                                  "run_modelparallel", "run_async_bench",
                                  "run_trace_bench",
                                  "run_convergence_bench"])
def test_every_mode_refuses_before_measuring(bench, mode, monkeypatch):
    """No mode builds or runs anything when there is no TPU."""
    def boom(*a, **k):
        raise AssertionError("a family ran without a TPU")

    for name in ("run_family", "run_trace_family", "run_async_multiplex",
                 "_bank"):
        monkeypatch.setattr(bench, name, boom)
    with pytest.raises(SystemExit):
        getattr(bench, mode)()


def test_families_are_plain_data(bench):
    """The benchmark PR seeds its cells from these tables: they must stay
    JSON-serializable dicts that ``make_algorithm`` can build."""
    families = [bench.HEADLINE_FAMILY] + bench.SUITE_FAMILIES
    names = [f["name"] for f in families]
    assert len(set(names)) == len(names)
    for fam in families:
        json.dumps(fam)
        assert bench.make_algorithm(fam["algorithm"]) is not None
    assert bench.HEADLINE_FAMILY["num_clients"] == 10_000


def test_bank_is_atomic(bench, tmp_path):
    path = str(tmp_path / "suite.json")
    assert bench._bank([{"family": "a"}], path) == path
    bench._bank([{"family": "a"}, {"family": "b"}], path)
    with open(path) as f:
        assert [e["family"] for e in json.load(f)] == ["a", "b"]
    assert os.listdir(tmp_path) == ["suite.json"]  # no .tmp left behind
