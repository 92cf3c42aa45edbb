"""Off a TPU, or on a device kind with no published peaks, the command
exits non-zero and prints no result line."""
from __future__ import annotations

import os
import subprocess
import sys
import types

import pytest

from chipbench import cells, harness


def test_command_off_a_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.environ.get("TMPDIR", "/tmp") + "/jc_nochip")
    p = subprocess.run(
        [sys.executable, str(cells.BENCH_DIR / "run.py"), "--workload",
         "qwen3-4b.decode-batch", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def _fake(platform, kind, n=1):
    return [types.SimpleNamespace(platform=platform, device_kind=kind)] * n


@pytest.mark.parametrize("devs,chips", [
    (_fake("cpu", "cpu"), 1),
    (_fake("tpu", "TPU v9 imaginary"), 1),
    (_fake("tpu", "TPU v5 lite"), 4),
])
def test_device_check_refuses(monkeypatch, devs, chips):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: devs)
    with pytest.raises(harness.NoChip):
        harness.device_of(chips)


def test_device_check_accepts_a_known_chip(monkeypatch):
    import jax

    devs = _fake("tpu", "TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: devs)
    assert harness.device_of(1) is devs[0]
