"""``correct`` comes out false under the control and under each fault the
cells can have, and ``bench/run.py`` refuses to run where it should."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"), os.path.dirname(__file__))
                if p not in sys.path]

from bench import harness  # noqa: E402
from bench_sizes import tiny  # noqa: E402

CELL = "tahoe-csr.block16-f256"


def _run(tmp_path, cell=CELL, **kw):
    return harness.run(cell, 2**31 + 29, 0.5, False, data_dir=str(tmp_path / "data"),
                       overrides=tiny(), **kw)


def test_control_bf16_is_not_correct(tmp_path):
    r = _run(tmp_path, variant="control_bf16")
    assert r["correct"] is False
    assert r["checks"]["loss_rel_gap"]["value"] > r["checks"]["loss_rel_gap"]["limit"]


@pytest.mark.parametrize("variant,number", [("state_unchanged", "change3_norm_gap"),
                                            ("half_batch", "grad1_norm_gap")])
def test_consumer_fault_is_not_correct(tmp_path, variant, number):
    r = _run(tmp_path, variant=variant)
    assert r["correct"] is False
    assert r["checks"][number]["value"] > r["checks"][number]["limit"]


@pytest.mark.parametrize("cell", ["tahoe-h5ad.block16-f256", CELL])
@pytest.mark.parametrize("fault,number", [("wrong_row", "rows_mismatched_batches"),
                                          ("altered_value", "values_mismatched")])
def test_loader_fault_is_not_correct(tmp_path, cell, fault, number):
    r = _run(tmp_path, cell, fault=fault)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"][number]["value"] > 0


def _bench(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_a_platform_other_than_tpu():
    p = _bench(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 1 tpu chip" in p.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".data", ".trace", ".jax_cache",
                                                  "__pycache__"))
    p = _bench(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    with pytest.raises(json.JSONDecodeError):
        json.loads(p.stdout or "x")
