import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bayes


def test_recovers_linear_ground_truth(rng):
    x = rng.uniform(0.5, 8.0, 12).astype(np.float32)
    y = (3.0 + 11.0 * x).astype(np.float32)
    post = bayes.fit_blr(x, y)
    mean, std = bayes.predict_blr(post, np.float32(20.0))
    assert abs(float(mean) - (3 + 11 * 20)) / (3 + 11 * 20) < 0.05
    assert float(std) < 0.2 * float(mean)


def test_uncertainty_covers_truth(rng):
    x = rng.uniform(0.5, 5.0, 8).astype(np.float32)
    y = (10 + 4 * x + rng.normal(0, 1.0, 8)).astype(np.float32)
    post = bayes.fit_blr(x, y)
    lo, hi = bayes.credible_interval(post, np.float32(10.0), z=3.0)
    truth = 10 + 4 * 10
    assert float(lo) < truth < float(hi)


def test_masked_fit_ignores_padding(rng):
    x = rng.uniform(1, 5, 10).astype(np.float32)
    y = (2 + 7 * x).astype(np.float32)
    xp = np.concatenate([x, np.full(6, 1e6, np.float32)])
    yp = np.concatenate([y, np.zeros(6, np.float32)])
    m = np.concatenate([np.ones(10), np.zeros(6)]).astype(np.float32)
    post_m = bayes.fit_blr(xp, yp, m)
    post = bayes.fit_blr(x, y)
    a = bayes.predict_blr(post_m, np.float32(8.0))[0]
    b = bayes.predict_blr(post, np.float32(8.0))[0]
    assert abs(float(a) - float(b)) < 1e-2 * abs(float(b)) + 1e-3


def test_batched_matches_single(rng):
    x = rng.uniform(0.5, 6, (5, 7)).astype(np.float32)
    y = (1 + 3 * x + rng.normal(0, 0.05, (5, 7))).astype(np.float32)
    m = np.ones((5, 7), np.float32)
    batch = bayes.fit_blr_batch(x, y, m)
    for i in range(5):
        single = bayes.fit_blr(x[i], y[i], m[i])
        np.testing.assert_allclose(np.asarray(batch["mu"][i]),
                                   np.asarray(single["mu"]), rtol=1e-4,
                                   atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(slope=st.floats(0.5, 50), intercept=st.floats(0.0, 100),
       n=st.integers(4, 16))
def test_property_noiseless_linear_exact(slope, intercept, n):
    x = np.linspace(1.0, 9.0, n).astype(np.float32)
    y = (intercept + slope * x).astype(np.float32)
    post = bayes.fit_blr(x, y)
    mean, _ = bayes.predict_blr(post, np.float32(5.0))
    expect = intercept + slope * 5.0
    assert abs(float(mean) - expect) <= 0.05 * abs(expect) + 0.5


@settings(max_examples=15, deadline=None)
@given(scale=st.floats(0.1, 100.0))
def test_property_time_rescaling_equivariance(scale):
    """scaling runtimes by c scales predictions by ~c (unit coherence)."""
    x = np.linspace(1, 8, 6).astype(np.float32)
    y = (5 + 2 * x).astype(np.float32)
    m1, _ = bayes.predict_blr(bayes.fit_blr(x, y), np.float32(4.0))
    m2, _ = bayes.predict_blr(bayes.fit_blr(x, y * scale), np.float32(4.0))
    assert abs(float(m2) - scale * float(m1)) <= 0.02 * abs(scale * float(m1)) + 1e-3


# --- stacked NIG lift and export -------------------------------------------------
def _lift_per_task(post):
    """The per-task lift as plain scalar arithmetic: one `inv` per 2x2."""
    sigma = np.asarray(post["sigma"], np.float64)
    beta = float(post["beta_prec"])
    a = max(float(post["n"]) / 2.0, 1.0)
    v = sigma * beta
    return {"mu": np.asarray(post["mu"], np.float64).copy(), "v": v,
            "prec": np.linalg.inv(v), "a": a, "b": a / beta,
            "x_mu": float(post["x_mu"]), "x_sd": float(post["x_sd"]),
            "y_mu": float(post["y_mu"]), "y_sd": float(post["y_sd"]),
            "n0": float(post["n"]), "n_obs": 0.0, "s2_lift": 1.0 / beta}


def _export_per_task(nig):
    s2 = nig["b"] / nig["a"]
    return {"mu": nig["mu"].astype(np.float32),
            "sigma": (s2 * nig["v"]).astype(np.float32),
            "alpha": np.float32(1.0), "beta_prec": np.float32(1.0 / s2),
            "x_mu": np.float32(nig["x_mu"]), "x_sd": np.float32(nig["x_sd"]),
            "y_mu": np.float32(nig["y_mu"]), "y_sd": np.float32(nig["y_sd"]),
            "n": np.float32(nig["n0"] + nig["n_obs"])}


def _stacked_posts(rng, t=257):
    """Random fitted posteriors, float64 as `fit_stacked` returns them:
    some near-singular sigma, some n below 2 (a clamps to 1)."""
    a = rng.normal(size=(t, 2, 2))
    sigma = a @ a.transpose(0, 2, 1) + 1e-6 * np.eye(2)
    eps = 10.0 ** -rng.uniform(6, 12, 8)
    sigma[:8] = np.stack([[[1.0, 1.0 - e], [1.0 - e, 1.0]] for e in eps])
    n = rng.integers(2, 40, t).astype(np.float64)
    n[8:16] = [0.0, 1.0, 1.5, 0.5, 1.0, 0.0, 1.9, 1.0]
    return {"mu": rng.normal(0, 3, (t, 2)), "sigma": sigma,
            "alpha": rng.uniform(0.1, 10, t),
            "beta_prec": 10.0 ** rng.uniform(-3, 3, t),
            "x_mu": rng.normal(0, 5, t), "x_sd": rng.uniform(0.1, 4, t),
            "y_mu": rng.normal(0, 500, t), "y_sd": rng.uniform(0.1, 300, t),
            "n": n}


def _bits(v):
    return type(v), np.asarray(v).dtype, np.asarray(v).tobytes()


def test_stacked_lift_and_export_equal_per_task_bitwise(rng):
    post = _stacked_posts(rng)
    assert np.linalg.cond(post["sigma"][:8]).min() > 1e5   # near-singular
    rows = bayes.nig_rows(bayes.nig_from_blr_stacked(post))
    lifted = [bayes.nig_from_blr({k: v[i] for k, v in post.items()})
              for i in range(len(rows))]
    exported = bayes.nig_to_blr_stacked(bayes.nig_stack(rows))
    for i, (row, one) in enumerate(zip(rows, lifted)):
        want = _lift_per_task({k: v[i] for k, v in post.items()})
        assert list(row) == list(one) == list(want)
        for k in want:
            assert _bits(row[k]) == _bits(one[k]) == _bits(want[k]), (i, k)
        if post["n"][i] < 2:
            assert row["a"] == 1.0
        want_blr = _export_per_task(want)
        one_blr = bayes.nig_to_blr(row)
        assert list(one_blr) == list(want_blr)
        for k in want_blr:
            assert _bits(one_blr[k]) == _bits(want_blr[k]), (i, k)
            assert exported[k][i].tobytes() == \
                np.asarray(want_blr[k]).tobytes(), (i, k)


def test_observe_on_one_refreshed_task_leaves_the_others(rng):
    """Rows of one stacked lift share its arrays' buffers: an update of
    one task must never show in another."""
    from repro.online import FleetRefresher, OnlinePredictor, RefreshPolicy
    from repro.store import PosteriorStore
    from test_refresh import _fit, _observe_local
    tasks = ("bwa", "idx", "sam")
    online = OnlinePredictor(_fit(tasks))
    store = PosteriorStore()
    store.bind("acme", "w", online)
    for t in tasks:
        _observe_local(online, t, 4, rng)
    assert FleetRefresher(store, RefreshPolicy(every_n=4)).refresh(
        ).n_tasks == 3
    states = [online.tasks[t].nig for t in tasks]
    assert not any(np.shares_memory(a[k], b[k]) for k in ("mu", "v", "prec")
                   for i, a in enumerate(states) for b in states[i + 1:])
    kept = {t: {k: _bits(v) for k, v in online.tasks[t].nig.items()}
            for t in tasks}
    _observe_local(online, "idx", 3, rng)
    assert {k: _bits(v) for k, v in online.tasks["idx"].nig.items()} \
        != kept["idx"]
    for t in ("bwa", "sam"):
        assert {k: _bits(v) for k, v in online.tasks[t].nig.items()} \
            == kept[t]
