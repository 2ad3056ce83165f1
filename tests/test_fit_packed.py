"""The fleet fit's packed path (`store.compute.fit_stacked` on the kernel):
the padded observation buffers are packed on the host into one float32
buffer, task count bucketed to the kernel's block, then one transfer, one
call of the jitted `kernels.ops.bayes_fit` and one readback per pass, with
no eager device program around them and no trace after the first pass of
a bucket."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import bayes
from repro.kernels import bayes_fit as bf
from repro.kernels import ops
from repro.store.compute import fit_stacked

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CHECKED = ("mu", "sigma", "alpha", "beta_prec", "x_mu", "x_sd", "y_mu",
           "y_sd", "n")


def _buffers(rng, t):
    """t ragged observation buffers (3..20 points, linear with noise),
    padded and masked as the maintenance plane pads them."""
    xs, ys = [], []
    for i in range(t):
        n = 3 + i % 18
        x = rng.uniform(0.1, 5.0, n)
        xs.append(x)
        ys.append(2 + (4 + i % 7) * x + rng.normal(0, 0.05, n))
    return bf.pad_ragged(xs, ys)


def _assert_matches_fit_blr(post, x, y, m):
    """Per row against `bayes.fit_blr` (vmapped) on the same buffers."""
    ref = bayes.fit_blr_batch(x, y, m)
    for k in CHECKED:
        assert post[k].dtype == np.float64, k
        np.testing.assert_allclose(post[k], np.asarray(ref[k]),
                                   rtol=5e-3, atol=5e-4, err_msg=k)


def test_one_trace_for_two_passes_in_one_bucket(rng):
    """130 and 200 due tasks both pad to 256 rows: the jitted entry is
    traced once, and both passes match the per-row fit."""
    ops.bayes_fit.clear_cache()
    bufs = [_buffers(rng, t) for t in (130, 200)]
    assert {b[0].shape for b in bufs} == {(130, 64), (200, 64)}
    obs.reset()
    obs.enable()
    try:
        posts = [fit_stacked(*b, impl="interpret") for b in bufs]
        snap = obs.snapshot()
    finally:
        obs.disable()
        obs.reset()
    assert snap["counters"]["lotaru.compute.fit_traces"] == 1
    assert ops.bayes_fit._cache_size() == 1
    for post, b in zip(posts, bufs):
        assert post["mu"].shape == (len(b[0]), 2)
        assert post["sigma"].shape == (len(b[0]), 2, 2)
        _assert_matches_fit_blr(post, *b)


def test_one_transfer_dispatch_readback(rng, monkeypatch):
    """One `fit_stacked` call: one `device_put` of the packed buffer, one
    call of the jitted entry, one pad span and one readback, and the
    counters give the bytes of the packed buffer and of the result."""
    x, y, m = _buffers(rng, 130)
    fit_stacked(x, y, m, impl="interpret")          # compile outside
    puts, calls = [], []
    real_put, real_jit = jax.device_put, ops.bayes_fit

    def put(*a, **kw):
        puts.append(a[0])
        return real_put(*a, **kw)

    def dispatch(*a, **kw):
        calls.append(a)
        return real_jit(*a, **kw)

    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(ops, "bayes_fit", dispatch)
    obs.reset()
    obs.enable()
    try:
        fit_stacked(x, y, m, impl="interpret")
        snap = obs.snapshot()
    finally:
        obs.disable()
        obs.reset()
    assert len(puts) == 1 and len(calls) == 1
    assert puts[0].shape == (3, 256, 64) and puts[0].dtype == np.float32
    sp, ct = snap["spans"], snap["counters"]
    for name in ("lotaru.compute.fit", "lotaru.compute.fit_pad",
                 "lotaru.compute.fit_readback"):
        assert sp[name]["count"] == 1, name
    assert "lotaru.compute.fit_traces" not in ct
    assert ct["lotaru.compute.fit_h2d_bytes"] == 4 * 3 * 256 * 64
    assert ct["lotaru.compute.fit_d2h_bytes"] == 4 * 256 * bf.FIT_COLS


def test_one_program_per_call(rng):
    """With every cache cleared, a call lowers exactly one program, the
    jitted entry: no eager pad, slice, reshape or convert beside it."""
    x, y, m = _buffers(rng, 70)
    lowered = []

    def listen(event, secs, **kw):
        if event == LOWERED:
            lowered.append(event)

    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        post = fit_stacked(x, y, m, impl="interpret")
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(lowered) == 1
    assert all(isinstance(v, np.ndarray) for v in post.values())


@pytest.mark.parametrize("t", [1, 127, 128, 129, 300])
def test_pack_pads_tasks_to_the_block_with_masked_rows(rng, t):
    x, y, m = _buffers(rng, t)
    planes = bf.pack_fit_planes(x, y, m)
    tp = -(-t // bf.DEFAULT_BLOCK_TASKS) * bf.DEFAULT_BLOCK_TASKS
    assert planes.shape == (3, tp, x.shape[1])
    assert planes.dtype == np.float32
    np.testing.assert_array_equal(planes[:, :t], np.stack([x, y, m]))
    assert not planes[:, t:].any()


def test_unpack_inverts_the_column_layout(rng):
    """The ref branch's columns read back as the leaves they were built
    from, row for row, padding rows dropped."""
    x, y, m = _buffers(rng, 5)
    planes = bf.pack_fit_planes(x, y, m)
    out = np.asarray(ops.bayes_fit(jax.device_put(planes), impl="ref"))
    assert out.shape == (128, bf.FIT_COLS)
    post = bf.unpack_fit(out, 5)
    _assert_matches_fit_blr(post, x, y, m)
