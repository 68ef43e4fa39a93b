"""chip_smoke.py's phases at a tiny size on the CPU, and its refusals:
the final contract line is only ever built for a TPU, and the script
exits non-zero without one."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_contract_line_refuses_non_tpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.contract_line(jax.devices()[:1])
    line = json.loads(chip_smoke.contract_line([_FakeTpu()] * 4))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_script_exits_nonzero_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       cwd=tmp_path, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "no TPU" in r.stderr


def test_phase_golden_on_cpu():
    out = chip_smoke.phase_golden(jax.devices()[:1], dtype="float64")
    assert abs(out["eobj"] - chip_smoke.GOLDEN_EOBJ) < 1.0


def test_phase_full_size_tiny():
    out = chip_smoke.phase_full_size(jax.devices()[:1], S=4, mult=2,
                                     iters_cap=3000)
    assert len(out["convs"]) == 3 and np.isfinite(out["trivial"])
    assert out["certify_s"] >= 0.0


def test_phase_mesh_parity_four_devices():
    out = chip_smoke.phase_mesh_parity(jax.devices()[:4], S=8, mult=2)
    assert len(out["convs"][0]) == 4


def test_phase_mpmd_collective_four_devices():
    out = chip_smoke.phase_mpmd_collective(jax.devices()[:4], S=3,
                                           iters=3)
    assert out["counters"]["wheel_corrupt_reads"] == 0
