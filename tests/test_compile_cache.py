"""Where `enable_compile_cache` puts JAX's persistent compilation cache.
Each case runs in a child process pinned to the CPU: the helper changes
process-wide JAX settings that must not leak into other tests."""
import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
PROBE = """
import json, jax
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
if COMPILE:
    jax.jit(lambda x: x * 2.0 + 1.0)(3.0).block_until_ready()
cfg = jax.config
print(json.dumps({"path": path, "config": cfg.jax_compilation_cache_dir,
                  "min_s": cfg.jax_persistent_cache_min_compile_time_secs}))
"""


def _probe(env_dir, compile_: bool) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c",
                        PROBE.replace("COMPILE", str(compile_))],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_as_is(tmp_path):
    cache = str(tmp_path / "cache")
    out = _probe(cache, compile_=True)
    assert out["path"] == out["config"] == cache
    assert out["min_s"] == 0.0
    assert os.listdir(cache)            # the compile was cached there


def test_default_dir_is_fixed_in_the_checkout():
    out = _probe(None, compile_=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert out["path"] == out["config"] == want
    assert out["min_s"] == 0.0
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
