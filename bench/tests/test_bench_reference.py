"""The plain reference, and the comparison that decides `correct`."""
import math

import numpy as np
import pytest

from bench import reference as ref
from bench.common import load_json
from bench.drivers import plan, serve
from bench.tests import _small

LIMITS = load_json(_small.harness.os.path.join(
    _small.harness.common.BENCH, "configs",
    "nfcore-serve-2048.json"))["limits"]


@pytest.fixture(scope="module")
def models():
    return serve.reference_models(_small.experiments())


def _queries(model, dag, n=200):
    out = []
    for i, t in enumerate(list(dag.tasks.values())[:n]):
        out.extend((t.task_name, node, t.input_gb * (0.5 + (i % 4) / 2))
                   for node in plan.NODES)
    return out


def test_answers_in_bfloat16_fail_the_predict_limit(models):
    exps = _small.experiments()
    for w, m in models.items():
        qs = _queries(m, exps[w].dag)
        want = [m.predict(qs)]
        mean, std, _ = m.predict(qs, ref.bfloat16())
        bf16 = [np.stack([mean, mean - 1.96 * std, mean + 1.96 * std], 1)]
        f64 = [np.stack([want[0][0], want[0][0] - 1.96 * want[0][1],
                         want[0][0] + 1.96 * want[0][1]], 1)]
        assert plan.compare(f64, want) < 1e-12
        assert plan.compare(bf16, want) > 3 * LIMITS["predict_rel_err"]


def test_fit_in_bfloat16_fails_the_fit_limit(models):
    m = models["eager"]
    t = next(s for s in m.tasks.values() if s.nig is not None)
    x = np.asarray(t.fit_x)[None]
    y = np.asarray(t.fit_y)[None]
    f64 = ref.fit_evidence(x, y, np.ones_like(x))
    bf = ref.fit_evidence(x, y, np.ones_like(x), ref.bfloat16())
    at = np.linspace(x.min(), 10 * x.max(), 16)
    m64, s64 = ref.predictive({k: v[0] for k, v in f64.items()}, at)
    mbf, sbf = ref.predictive({k: v[0] for k, v in bf.items()}, at)
    err = max(ref.rel_err(mbf, m64, float(f64["y_sd"][0])),
              ref.rel_err(sbf, s64))
    assert err > 3 * LIMITS["fit_rel_err"]


def test_reference_matches_the_program_on_the_cpu(models):
    """On the CPU the program serves float64 predictions from float32
    fits: it agrees with the reference far inside the limit."""
    from repro.online import OnlinePredictor, PredictionQuery
    from repro.online import PredictionService
    exps = _small.experiments()
    for w, e in exps.items():
        svc = PredictionService(OnlinePredictor(e.predictors["lotaru-g"],
                                                benches=e.benches),
                                e.benches)
        m = models[w].copy()
        qs = _queries(m, e.dag, 40)
        # local completions move both sides the same way
        for i, t in enumerate(list(e.dag.tasks.values())[:12]):
            y = 10.0 + 3.0 * i
            from repro.online import TaskCompletion
            svc.predictor.observe(TaskCompletion(w, f"u{i}", t.task_name,
                                                 "local", t.input_gb, y))
            m.observe(t.task_name, "local", t.input_gb, y)
        got = svc.predict_batch([PredictionQuery(*q) for q in qs])
        assert plan.compare([got], [m.predict(qs)]) < \
            LIMITS["predict_rel_err"] / 3


def test_rel_err_refuses_a_missing_answer():
    assert ref.rel_err(np.ones(3), np.ones(4)) == math.inf
    assert ref.rel_err([1.0, np.nan], [1.0, 1.0]) == math.inf
