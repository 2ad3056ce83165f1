"""The chip smoke's phases, run on the CPU at a tiny size (tens of tenants,
a 64-task x 8-node DAG), so its path stays covered without a chip; and
its refusal to report a result anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.join(os.path.dirname(__file__), "..")
TINY = chip_smoke.Sizes(tenants=40, refresh_tasks=64, refresh_tenants=8,
                        ingest_records=200, replan_tasks=64, replan_nodes=8)


@pytest.fixture(scope="module")
def records():
    lines = []
    clock = chip_smoke.CompileClock()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(chip_smoke, "PLATFORM", "cpu")
        try:
            recs = chip_smoke.run(TINY, 0, clock, emit=lines.append)
        finally:
            clock.close()
    assert [json.loads(line) for line in lines] == recs
    return {r["phase"]: r for r in recs}


@pytest.mark.parametrize("phase", ["store", "predict", "refresh", "ingest",
                                   "replan"])
def test_phase_passes_on_cpu(records, phase):
    rec = records[phase]
    assert rec["ok"], rec
    assert rec["max_err"] <= rec["tol"]
    assert rec["compile_s"] >= 0.0 and rec["cold_s"] > 0.0
    assert rec["device"]["platform"] == ("host" if phase == "ingest"
                                         else "cpu")


def test_phase_sizes_reach_their_targets(records):
    assert records["store"]["namespaces"] == TINY.tenants
    assert records["predict"]["dispatches"] == 1
    assert records["predict"]["queries_per_dispatch"] == \
        TINY.tenants * chip_smoke.QUERIES_PER_TENANT
    assert not records["predict"]["pallas"]          # no Pallas off-TPU
    assert records["refresh"]["dispatches"] == 1
    assert records["refresh"]["tasks"] >= TINY.refresh_tasks
    assert records["refresh"]["tenants"] >= TINY.refresh_tenants
    assert records["ingest"]["records"] == TINY.ingest_records
    assert records["replan"]["tenants"] == len(chip_smoke.REPLAN_QUANTILES)
    assert records["replan"]["sweep_device"] == "cpu"
    assert records["replan"]["moved"] == 0


def _smoke(cwd, script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_refuses_without_tpu():
    r = _smoke(ROOT, "chip_smoke.py")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok": true' not in r.stdout


def test_smoke_fails_alone_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _smoke(str(tmp_path), "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
