"""The dry-run machinery itself, exercised end-to-end in a subprocess with 8
placeholder devices and reduced configs (the production 512-device matrix is
run by `python -m repro.launch.dryrun --all`; results in results/dryrun)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"     # placeholder CPU devices only
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun"] + args,
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=560)


@pytest.mark.parametrize("arch,shape", [
    ("smollm-360m", "train_small"),
    ("mixtral-8x7b", "decode_small"),
    ("recurrentgemma-9b", "prefill_small"),
    ("deepseek-v2-236b", "train_small"),
])
def test_dryrun_reduced_cell(arch, shape, tmp_path):
    r = _run(["--arch", arch, "--shape", shape, "--reduced",
              "--devices", "8", "--out", str(tmp_path)])
    assert r.returncode == 0, r.stdout + r.stderr
    files = list(tmp_path.glob("*.json"))
    assert len(files) == 1
    rec = json.loads(files[0].read_text())
    assert rec["status"] == "ok"
    assert rec["roofline"]["flops_per_dev"] > 0
    assert rec["roofline"]["t_memory"] > 0


def test_production_matrix_results_exist():
    """the full 512-device matrix must have been produced (deliverable e)."""
    out = os.path.join(ROOT, "results", "dryrun")
    if not os.path.isdir(out):
        pytest.skip("production dry-run results not generated yet")
    recs = [json.loads(open(os.path.join(out, f)).read())
            for f in os.listdir(out) if f.endswith(".json")]
    baseline = [r for r in recs if not r.get("tag")]
    meshes = {r["mesh"] for r in baseline}
    assert "16x16" in meshes
    ok = [r for r in baseline if r["status"] == "ok"]
    assert len(ok) == len(baseline)
