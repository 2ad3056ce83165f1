"""Whole runs of every cell on the CPU at a small size: sound runs are
correct, the control fails a limit, and a run whose timed path is broken
underneath comes out not correct."""
import numpy as np
import pytest

from bench import run as harness
from bench.tests import _small
from bench.tools import readings

CELLS = ["nfcore-serve-2048.plan-serial", "nfcore-serve-2048.refresh-fleet"]


@pytest.mark.parametrize("cell", CELLS)
def test_small_run_is_correct(monkeypatch, cell):
    res = _small.run_small(monkeypatch, cell)
    assert res["correct"], res["stderr"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s=" in res["stderr"]
    last = res["stdout"].strip().splitlines()[-1]
    assert last.startswith('{"correct": true')


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(monkeypatch, cell):
    monkeypatch.setattr(_small.serve, "profile",
                        lambda workflows, s, spans: _small.experiments(s))
    rec, = readings.readings(cell, [_small.SEED], 1.5, cpu=True,
                             edit=_small.small)
    lim = harness.load_cell(cell)["cfg"]["limits"]
    key = next(k for k in rec
               if k.endswith("_rel_err") and not k.startswith("control."))
    assert rec[key] <= lim[key]
    assert rec["control." + key] > 3 * lim[key]


def _scale_means(out):
    out = np.array(out, copy=True)
    out[:, 0] *= 1.001
    return out


def test_altered_answers_are_not_correct(monkeypatch):
    import repro.store.frontend as fe
    real = fe.finalize
    monkeypatch.setattr(fe, "finalize",
                        lambda *a, **k: _scale_means(real(*a, **k)))
    res = _small.run_small(monkeypatch, CELLS[0])
    assert not res["correct"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    import repro.store.frontend as fe
    real = fe.predict_stacked

    def half(x, post, impl="auto"):
        n = max(len(x) // 2, 1)
        mean, std = real(x[:n], {k: v[:n] for k, v in post.items()}, impl)
        return (np.concatenate([mean, np.full(len(x) - n, mean.mean())]),
                np.concatenate([std, np.full(len(x) - n, std.mean())]))
    monkeypatch.setattr(fe, "predict_stacked", half)
    res = _small.run_small(monkeypatch, CELLS[0])
    assert not res["correct"]


def test_refresh_that_leaves_state_unchanged_is_not_correct(monkeypatch):
    from repro.online.predictor import OnlinePredictor
    monkeypatch.setattr(OnlinePredictor, "apply_refresh",
                        lambda self, task, post, seq=None: True)
    res = _small.run_small(monkeypatch, "nfcore-serve-2048.refresh-fleet")
    assert not res["correct"]


def test_refresh_fitting_half_the_batch_is_not_correct(monkeypatch):
    import repro.store.compute as compute
    real = compute.fit_stacked

    def half(x, y, m, impl="auto"):
        n = max(len(x) // 2, 1)
        post = real(x[:n], y[:n], m[:n], impl)
        return {k: np.concatenate([v, np.repeat(v[:1], len(x) - n, 0)])
                for k, v in post.items()}
    monkeypatch.setattr(compute, "fit_stacked", half)
    res = _small.run_small(monkeypatch, "nfcore-serve-2048.refresh-fleet")
    assert not res["correct"]


def test_altered_fit_is_not_correct(monkeypatch):
    import repro.store.compute as compute
    real = compute.fit_stacked

    def shifted(x, y, m, impl="auto"):
        post = real(x, y, m, impl)
        post["mu"] = post["mu"] * 1.001
        return post
    monkeypatch.setattr(compute, "fit_stacked", shifted)
    res = _small.run_small(monkeypatch, "nfcore-serve-2048.refresh-fleet")
    assert not res["correct"]


def test_observe_that_leaves_state_unchanged_is_not_correct(monkeypatch):
    from repro.online.predictor import OnlinePredictor
    monkeypatch.setattr(OnlinePredictor, "observe", lambda self, c: 0)
    res = _small.run_small(monkeypatch, CELLS[0], seconds=3.0)
    assert not res["correct"]
