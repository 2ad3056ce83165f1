"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp oracle in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("b,s,h,kh,hd", [
    (1, 128, 4, 4, 32),      # MHA
    (2, 128, 4, 2, 32),      # GQA 2:1
    (1, 256, 8, 1, 64),      # MQA
    (1, 128, 4, 2, 128),     # MXU-width head dim
])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_sweep(b, s, h, kh, hd, window):
    rng = np.random.default_rng(hash((b, s, h, kh, hd, window)) % 2 ** 31)
    q = jnp.asarray(rng.standard_normal((b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kh, hd)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              impl="interpret")
    exp = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, impl="interpret")
    exp = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("b,t,w,bt,bw", [
    (1, 256, 128, 128, 128),
    (2, 512, 256, 256, 128),
    (1, 128, 384, 64, 128),
])
def test_rglru_scan_sweep(b, t, w, bt, bw):
    rng = np.random.default_rng(hash((b, t, w)) % 2 ** 31)
    a = jnp.asarray(rng.uniform(0.7, 0.999, (b, t, w)), jnp.float32)
    gx = jnp.asarray(rng.standard_normal((b, t, w)) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((b, w)), jnp.float32)
    from repro.kernels.rglru_scan import rglru_scan
    out = rglru_scan(a, gx, h0, block_t=bt, block_w=bw, interpret=True)
    exp = ref.rglru_scan_ref(a, gx, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t,n", [(64, 6), (128, 10)])
def test_bayes_fit_kernel_sweep(t, n):
    rng = np.random.default_rng(t * n)
    x = rng.uniform(0.1, 5, (t, n)).astype(np.float32)
    a = rng.uniform(1, 10, (t, 1))
    b = rng.uniform(5, 50, (t, 1))
    y = (b + a * x + rng.normal(0, 0.05, (t, n))).astype(np.float32)
    m = np.ones((t, n), np.float32)
    m[:, n - 2:] = 0.0
    from repro.kernels.bayes_fit import pack_fit_planes, unpack_fit
    out = unpack_fit(np.asarray(ops.bayes_fit(
        jnp.asarray(pack_fit_planes(x, y, m)), impl="interpret")), t)
    exp = ref.bayes_fit_ref(jnp.asarray(x), jnp.asarray(y), jnp.asarray(m))
    for key in ("mu", "sigma", "alpha", "beta_prec", "x_mu", "y_sd"):
        np.testing.assert_allclose(np.asarray(out[key]), np.asarray(exp[key]),
                                   rtol=5e-3, atol=5e-4, err_msg=key)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 20))
def test_property_flash_rows_sum_to_one_effect(seed):
    """attention output of constant V must be that constant (softmax rows
    normalize), for any mask pattern the kernel produces."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    v = jnp.ones((1, 128, 2, 32), jnp.float32) * 3.5
    out = ops.flash_attention(q, k, v, impl="interpret")
    np.testing.assert_allclose(np.asarray(out), 3.5, rtol=1e-5)
