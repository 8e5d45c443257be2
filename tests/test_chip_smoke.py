"""chip_smoke.py rehearsed on the CPU at a tiny size, and the compile-cache path.

The script's checks (platform, kernel backend, data location, model config)
are steered from here by patching its module attributes; the script itself
has no option for them.  The four-device case runs in a subprocess, since
``--xla_force_host_platform_device_count`` must be set before JAX starts.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import pytest

import chip_smoke
from repro.configs import smoke_config
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Sizes(
    n_cells=2_000, n_genes=256, total_counts=64, chunk=512, fetch_factor=4,
    n_batches=4, lm_batch=2, lm_seq=16, lm_steps=2,
    dp_n_cells=2_000, dp_fetch_factor=2, dp_steps=2,
)


@pytest.fixture
def tiny_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "SIZES", TINY)
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "KERNEL_BACKEND", "interpret")
    monkeypatch.setattr(chip_smoke, "DATA_ROOT", str(tmp_path / "data"))
    monkeypatch.setattr(chip_smoke, "get_config", smoke_config)
    # set after JAX started: the helper then leaves JAX's cache untouched
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return tmp_path


@pytest.mark.parametrize("env", [None, "/some/cache/dir"])
def test_compile_cache_dir(monkeypatch, env):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        want = env
    assert compile_cache.compile_cache_dir() == want


def test_refuses_a_platform_that_is_not_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0 and "'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_phases_at_tiny_size(tiny_cpu, capsys):
    assert chip_smoke.main(["--seed", "1"]) == 0
    out = capsys.readouterr().out
    assert "bitwise equal to host on every batch" in out
    assert "[token] 2 steps" in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_wrong_loss_fails_the_cell_phase(tiny_cpu, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "reference_loss", lambda *a: 1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="step 1 loss"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


_FOUR = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, {src!r}]
    import chip_smoke
    chip_smoke.SIZES = chip_smoke.Sizes(**json.loads({sizes!r}))
    chip_smoke.REQUIRED_PLATFORM = "cpu"
    chip_smoke.DATA_ROOT = {data!r}
    sys.exit(chip_smoke.main(["--chips", "4"]))
""")


def test_four_device_data_parallel_phase(tmp_path):
    script = _FOUR.format(root=ROOT, src=os.path.join(ROOT, "src"),
                          sizes=json.dumps(dataclasses.asdict(TINY)),
                          data=str(tmp_path / "data"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    lines = r.stdout.strip().splitlines()
    ranks = [ln for ln in lines if ln.startswith("[dp] rank ")]
    assert len(ranks) == 4 and len({ln.split(" on ")[1] for ln in ranks}) == 4
    assert "[cell]" not in r.stdout and "[token]" not in r.stdout
    assert json.loads(lines[-1])["device"]["count"] == 4
