"""chip_smoke.py on the CPU: its phases at a tiny size (kernels in
interpret mode), its checks against planted faults, and its refusal to run
anywhere but on a TPU from a checkout."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import DropConfig

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(cs):
    return cs.tenant_data(cs.TENANTS, seed=0, max_rows=256)


CFG = DropConfig(target_tlb=0.98, seed=0, min_iterations=99)


def test_serve_and_kernel_phases_pass_at_tiny_size(cs, tiny, monkeypatch):
    checks = cs.Checks()
    base = cs.serve_phase("a", tiny, CFG, checks)
    assert checks.failed == []
    assert set(base["extra"]) == {"dbscan", "kde"}
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    cs.kernel_phase(tiny, CFG, checks, base)
    assert checks.failed == []


def test_subscription_phase_passes_at_tiny_size(cs):
    checks = cs.Checks()
    cs.subscription_phase(checks, rows=400, dim=32)
    assert checks.failed == []


def test_checks_fire_on_planted_downstream_error(cs, tiny, monkeypatch):
    from repro.pipeline import optimizer

    def boom(*a, **k):
        raise RuntimeError("planted")

    monkeypatch.setattr(optimizer, "run_downstream", boom)
    checks = cs.Checks()
    data = {cs.ANALYTICS_TENANT: tiny[cs.ANALYTICS_TENANT]}
    cs.serve_phase("a", data, CFG, checks)
    assert any("error downstream: RuntimeError: planted" in f for f in checks.failed)
    assert any("downstream_failures" in f for f in checks.failed)


def test_checks_fire_on_planted_wrong_answers(cs, tiny):
    from repro.serve_drop import DropService

    name = cs.ANALYTICS_TENANT
    data = {name: tiny[name]}
    checks = cs.Checks()
    out = cs.serve_phase("a", data, CFG, checks, service=DropService())
    assert checks.failed == []
    knn = out["cold"][name]
    knn.downstream = np.roll(np.asarray(knn.downstream), 1)  # wrong neighbours
    kde = out["extra"]["kde"]
    kde.downstream = np.asarray(kde.downstream) * 1.01
    knn.result.k = 1  # a basis far below the TLB target
    cs.reference_checks("a", data, out, checks, seed=0)
    assert any("host TLB" in f for f in checks.failed)
    assert any("kNN excess" in f for f in checks.failed)
    assert any("KDE rel err" in f for f in checks.failed)


def test_dbscan_bits_check_fires_on_a_flipped_bit(cs, tiny):
    from repro.analytics.pairwise import pairwise_dbscan

    xt = tiny[cs.ANALYTICS_TENANT][:, :4]
    eps = 0.9 * float(np.median(np.linalg.norm(xt - xt[0], axis=1)))
    counts, packed = pairwise_dbscan(xt, eps)
    eps2 = float(np.float32(eps * eps))
    assert counts.min() >= 1 and counts.max() > 1
    assert cs.dbscan_bits_error(xt, eps2, counts, packed)[0] == 0
    packed = packed.copy()
    packed[3, 0] ^= np.uint32(1 << 5)  # row 3, column 5
    assert cs.dbscan_bits_error(xt, eps2, counts, packed)[0] == 2  # bit, count


def _run_cli(script, env_extra=None, cwd=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("REPRO_PALLAS_INTERPRET", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=300, env=env, cwd=cwd,
    )


def _no_result(out):
    last = (out.stdout.strip().splitlines() or [""])[-1]
    assert '"ok"' not in last
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)


def test_cli_fails_on_cpu_only_host():
    out = _run_cli(SMOKE, cwd=ROOT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    _no_result(out)


def test_cli_fails_outside_a_checkout(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    out = _run_cli(alone, cwd=tmp_path)
    assert out.returncode != 0
    assert "no repository" in out.stderr
    _no_result(out)


def test_cli_refuses_interpret_mode():
    out = _run_cli(SMOKE, {"REPRO_PALLAS_INTERPRET": "1"}, cwd=ROOT)
    assert out.returncode != 0
    assert "REPRO_PALLAS_INTERPRET" in out.stderr
    _no_result(out)
