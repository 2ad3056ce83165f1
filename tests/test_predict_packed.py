"""The predictive's packed path (`kernels.ops.bayes_predict`): the queries
and their gathered posteriors are padded and packed on the host into one
float32 buffer, then one transfer, one jitted dispatch and one readback
per batch, with no eager device program around them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import bayes
from repro.kernels import bayes_fit as bf
from repro.kernels import ops
from repro.store.compute import LEAVES, predict_stacked

from test_online import _fitted_post

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _batch(rng, q):
    """q queries over five fitted posteriors, gathered per query as float64
    leaves nudged off the float32 grid, as the store holds them."""
    posts = [_fitted_post(rng, slope=10 + 7 * i, base=1 + 2 * i)
             for i in range(5)]
    idx = rng.integers(0, len(posts), q)
    post = {}
    for k in LEAVES:
        v = np.stack([posts[i][k] for i in idx]).astype(np.float64)
        post[k] = v * (1.0 + 1e-9 * rng.standard_normal(v.shape))
    return rng.uniform(0.2, 9.0, q), post


@pytest.mark.parametrize("q", [1, 6, 285, 1023, 1024, 1025, 2100])
def test_packed_predict_matches_kernel_and_reference(rng, q):
    """Bitwise the dict-API kernel on the same inputs, within 1e-4 of the
    float64 reference, and finite over the padded lanes."""
    x, post = _batch(rng, q)
    mean, std = predict_stacked(x, post, impl="interpret")
    assert mean.dtype == std.dtype == np.float64
    assert mean.shape == std.shape == (q,)

    km, ks = bf.bayes_predict(x, post, interpret=True)
    np.testing.assert_array_equal(mean, np.asarray(km, np.float64))
    np.testing.assert_array_equal(std, np.asarray(ks, np.float64))

    rm, rs = bayes.predict_blr_np(post, x)
    np.testing.assert_allclose(mean, rm, rtol=1e-4)
    np.testing.assert_allclose(std, rs, rtol=1e-4)

    planes = bf.pack_predict_planes(x, post)
    qp = -(-q // bf.PREDICT_TILE) * bf.PREDICT_TILE
    assert planes.shape == (bf.PREDICT_PLANES, qp // bf.LANE, bf.LANE)
    assert planes.dtype == np.float32
    flat = planes.reshape(bf.PREDICT_PLANES, qp)
    np.testing.assert_array_equal(
        flat[:, q:], np.broadcast_to(np.float32(bf.PLANE_PAD)[:, None],
                                     (bf.PREDICT_PLANES, qp - q)))
    out = np.asarray(ops._bayes_predict_jit(jax.device_put(planes),
                                            "interpret"))
    assert np.isfinite(out).all()


def test_one_program_per_bucket(rng):
    """Every Q up to a tile shares one compiled program; the next tile
    compiles one more."""
    ops._bayes_predict_jit.clear_cache()
    sizes = []
    for q in (6, 285, 870, 1024):
        predict_stacked(*_batch(rng, q), impl="interpret")
        sizes.append(ops._bayes_predict_jit._cache_size())
    assert sizes == [1, 1, 1, 1]
    predict_stacked(*_batch(rng, 1025), impl="interpret")
    assert ops._bayes_predict_jit._cache_size() == 2


def test_one_transfer_dispatch_readback(rng, monkeypatch):
    """One `predict_stacked` call: one `device_put` of the packed buffer,
    one call of the jitted entry, one pad span and one readback, and the
    counters give the bytes of the packed buffer and of the output."""
    x, post = _batch(rng, 285)
    predict_stacked(x, post, impl="interpret")        # compile outside
    puts, calls = [], []
    real_put, real_jit = jax.device_put, ops._bayes_predict_jit

    def put(*a, **kw):
        puts.append(a[0])
        return real_put(*a, **kw)

    def dispatch(*a):
        calls.append(a)
        return real_jit(*a)

    def no_pad(*a, **kw):
        raise AssertionError("eager jnp.pad on the predict path")

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(ops, "_bayes_predict_jit", dispatch)
    monkeypatch.setattr(jnp, "pad", no_pad)
    obs.reset()
    obs.enable()
    try:
        mean, std = predict_stacked(x, post, impl="interpret")
        snap = obs.snapshot()
    finally:
        obs.disable()
        obs.reset()
    assert len(puts) == 1 and len(calls) == 1
    assert puts[0].shape == (bf.PREDICT_PLANES, 8, bf.LANE)
    sp, ct = snap["spans"], snap["counters"]
    for name in ("lotaru.compute.predict", "lotaru.compute.pad",
                 "lotaru.compute.readback"):
        assert sp[name]["count"] == 1, name
    assert ct["lotaru.compute.h2d_bytes"] == 4 * 11 * 1024
    assert ct["lotaru.compute.d2h_bytes"] == 4 * 2 * 1024
    rm, rs = bayes.predict_blr_np(post, x)
    np.testing.assert_allclose(mean, rm, rtol=1e-4)
    np.testing.assert_allclose(std, rs, rtol=1e-4)


def test_one_program_per_call(rng):
    """With every cache cleared, a call lowers exactly one program, the
    jitted entry: no eager pad, slice, stack or convert beside it."""
    x, post = _batch(rng, 285)
    lowered = []

    def listen(event, secs, **kw):
        if event == LOWERED:
            lowered.append(event)

    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        mean, std = predict_stacked(x, post, impl="interpret")
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(lowered) == 1
    assert isinstance(mean, np.ndarray) and isinstance(std, np.ndarray)
