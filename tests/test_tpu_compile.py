"""Ahead-of-time compiles of the main path's device programs for a TPU v5e
that is described, not attached: the compiler refuses here, at no chip
time, what it would refuse on the chip (misaligned slices, too much fast
memory, programs that do not fit).  Nothing runs, so these say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles,
since an entry compiled for a described chip cannot be read back here."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bayes_fit as bf
from repro.kernels import decision_plane as dp
from repro.kernels.bayes_fit import bayes_fit_ragged, bayes_predict


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_bayes_predict_compiles_at_16k_queries(one_chip):
    q = 16384
    f32 = jnp.float32
    post = {"mu": _spec((q, 2), f32, one_chip),
            "sigma": _spec((q, 2, 2), f32, one_chip),
            **{k: _spec((q,), f32, one_chip)
               for k in ("beta_prec", "x_mu", "x_sd", "y_mu", "y_sd")}}
    text = _compile_text(bayes_predict, _spec((q,), f32, one_chip), post)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [8, 128])
def test_packed_predict_entry_is_one_named_kernel(one_chip, rows):
    """The jitted predict entry compiles to the Pallas call alone, and the
    call's instruction keeps the entry's name `_bayes_predict_jit`: a
    device trace finds the kernel by it."""
    from repro.kernels import ops
    planes = _spec((bf.PREDICT_PLANES, rows, bf.LANE), jnp.float32, one_chip)
    text = ops._bayes_predict_jit.lower(planes, "pallas").compile().as_text()
    entry = text[text.index("ENTRY"):]
    calls = [ln for ln in entry.splitlines() if " custom-call(" in ln]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    assert calls[0].split("=", 1)[0].split()[-1].startswith(
        "%_bayes_predict_jit")
    assert "fusion(" not in entry


@pytest.mark.parametrize("tasks", [4096, 130])
def test_bayes_fit_ragged_compiles(one_chip, tasks):
    arg = _spec((tasks, 64), jnp.float32, one_chip)
    text = _compile_text(bayes_fit_ragged, arg, arg, arg)
    assert "tpu_custom_call" in text


def test_eft_sweep_x64_compiles_at_1024x128(one_chip):
    t, n, d = 1024, 128, 4
    with jax.enable_x64(True):
        f64, i32 = jnp.float64, jnp.int32
        args = (_spec((t, n), f64, one_chip),          # W
                _spec((t,), i32, one_chip),            # order_arr
                _spec((t, d), i32, one_chip),          # dep_rows
                _spec((t,), f64, one_chip),            # gb8
                _spec((t, n), f64, one_chip),          # ready0
                _spec((n,), f64, one_chip),            # avail
                _spec((n, n), jnp.bool_, one_chip),    # same
                _spec((n, n), f64, one_chip))          # gbps_min
        compiled = dp.eft_sweep.lower(*args, S=dp.DEFAULT_SLOTS).compile()
    assert compiled.memory_analysis() is not None
