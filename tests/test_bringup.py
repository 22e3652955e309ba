"""Hermetic checks of what decides how the program runs on a chip: the
compile-cache location, the hardware table, the kernel-backend choice, the
serve entry point's depth cut, and chip_smoke.py's refusal to run off-TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro import hw as HW
from repro.kernels import ops as kops
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_compile_cache_honours_env_and_else_uses_fixed_checkout_path():
    env = {compile_cache.ENV: "/elsewhere/jax-cache"}
    assert compile_cache.cache_dir_to_set(env) is None
    path = compile_cache.cache_dir_to_set({})
    assert path == str(ROOT / ".jax_cache")
    assert compile_cache.cache_dir_to_set({}) == path   # never moves
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored, "the cache directory must be git-ignored"


def test_hardware_table_by_device_kind():
    assert HW.for_device_kind("TPU v5 lite") is HW.TPU_V5E
    with pytest.raises(ValueError, match="no hardware entry"):
        HW.for_device_kind("TPU v9 imaginary")


@pytest.mark.parametrize("requested", [None, "pallas", "blocked", "ref"])
def test_backend_selector_never_interprets_on_tpu(requested):
    got = kops.select_backend("tpu", requested)
    assert got != "interpret"
    if requested is None:
        assert got == "pallas"


def test_backend_selector_refuses_interpret_on_tpu_and_unknowns():
    with pytest.raises(ValueError, match="interpreter"):
        kops.select_backend("tpu", "interpret")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        kops.select_backend("cpu", "fast")
    assert kops.select_backend("cpu") == "blocked"
    assert kops.select_backend("cpu", "interpret") == "interpret"


def test_serve_with_depth_cut_completes():
    from repro.launch import serve
    out = {}
    rc = serve.main(["--arch", "mistral-nemo-12b", "--reduced",
                     "--depth", "2", "--requests", "2",
                     "--prompt-lens", "4", "--gen-lens", "2",
                     "--kv", "paged"], out=out)
    assert rc == 0
    assert [len(r.completions) for r in out["reports"]] == [2]
    assert out["promised_bytes"] < out["budget_bytes"]


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            if "ok" in json.loads(line):
                return True
        except ValueError:
            continue
    return False


def test_chip_smoke_refuses_cpu():
    res = _run_smoke(ROOT)
    assert res.returncode != 0
    assert "needs a TPU" in res.stderr
    assert not _printed_result(res.stdout)


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run_smoke(tmp_path)
    assert res.returncode != 0
    assert not _printed_result(res.stdout)
