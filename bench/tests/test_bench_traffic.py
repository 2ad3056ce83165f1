"""Every mix is a function of its seed alone."""
from types import SimpleNamespace

import numpy as np

from bench.drivers import plan, refresh
from repro.workflow.generator import (WORKFLOWS, GroundTruth,
                                      build_workflow)

SEED = 2 ** 40 + 3


def _fleet(seed=SEED, tenants=30):
    dags = {w: build_workflow(w, seed) for w in WORKFLOWS}
    inputs = {}
    for w, d in dags.items():
        per = {}
        for t in d.tasks.values():
            per.setdefault(t.task_name, []).append(t.input_gb)
        inputs[w] = {k: np.asarray(v) for k, v in per.items()}
    return SimpleNamespace(
        services=[SimpleNamespace(tenant=f"tenant-{i:04d}",
                                  workflow=WORKFLOWS[i % len(WORKFLOWS)])
                  for i in range(tenants)],
        dags=dags, topo={w: d.topo_order() for w, d in dags.items()},
        inputs=inputs,
        experiments={w: SimpleNamespace(gt=GroundTruth(w, seed))
                     for w in WORKFLOWS})


TRAFFIC = {"zipf_s": 0.99, "observe_share": 0.05, "reshuffle_requests": 100}


def _stream(seed, n=300, traffic=TRAFFIC):
    mix = plan.Mix(_fleet(), traffic, seed)
    out = []
    for i in range(n):
        r = mix.make(i)
        out.append((r.tenant, r.k, None if r.xf is None else
                    tuple(r.xf), r.obs))
    return out


def test_plan_mix_is_deterministic_in_the_seed():
    assert _stream(SEED) == _stream(SEED)
    assert _stream(SEED) != _stream(SEED + 1)


def test_plan_mix_sizes_and_shares():
    fl = _fleet()
    s = _stream(SEED, n=3000, traffic=dict(TRAFFIC, reshuffle_requests=3000))
    share = sum(obs is not None for *_, obs in s) / len(s)
    assert 0.03 < share < 0.07
    # k uniform in 1..|DAG| of the tenant's workflow
    for t, k, xf, obs in s:
        if obs is None:
            dag = fl.dags[fl.services[t].workflow]
            assert 1 <= k <= len(dag.tasks) and len(xf) == k
            assert all(0.5 <= f <= 2.0 for f in xf)
    ks = [k for _, k, _, obs in s if obs is None]
    assert min(ks) == 1 and max(ks) > 100
    # Zipf(0.99) over 30 tenants: the most popular takes about a quarter
    top = max(np.bincount([t for t, *_ in s]))
    assert 0.18 < top / len(s) < 0.33


def _feed(seed, tasks=40):
    fl = _fleet()
    reg = {w: sorted(fl.inputs[w])[:3] for w in WORKFLOWS}
    for svc in fl.services:
        svc.predictor = svc.tenant
    rng = np.random.default_rng([seed, 5])
    s = refresh.State(fl, None, {}, {"completions_per_task": 4,
                                     "tasks_per_pass": tasks}, seed, None,
                      reg, rng.permutation(len(fl.services)), rng)
    return refresh._feed(s)


def test_refresh_feed_is_deterministic_in_the_seed():
    a, b = _feed(SEED), _feed(SEED)
    assert a.keys == b.keys and (a.x == b.x).all() and (a.y == b.y).all()
    c = _feed(SEED + 1)
    assert c.keys != a.keys or not np.array_equal(c.x, a.x)
    assert a.x.shape == a.y.shape == (40, 4)
    assert (a.x > 0).all() and (a.y > 0).all()


def test_refresh_feed_makes_exactly_the_pass_due():
    f = _feed(SEED, tasks=41)
    assert len(f.keys) == len(set(f.keys)) == 41
    fed = [(c.task, c.input_gb, c.runtime_s) for _, cs in f.batches
           for c in cs]
    assert len(fed) == 41 * 4
    assert fed == [(task, float(a), float(b))
                   for (_, task), xs, ys in zip(f.keys, f.x, f.y)
                   for a, b in zip(xs, ys)]
    # one observe_many batch per tenant, in the order of the feed
    tenants = [t for t, _ in f.keys]
    assert len(f.batches) == len([t for j, t in enumerate(tenants)
                                  if j == 0 or tenants[j - 1] != t])
