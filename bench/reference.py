"""Plain reference of Lotaru's semantics, independent of the program.

It imports nothing of `src/` and takes nothing the program made: it reads
the raw data a run generates (local profiling traces, microbenchmark
readings, the completions a run fed in) and recomputes, in float64 NumPy,
what the service must answer:

  * the Lotaru-G task model (Bader et al., Section 4.5-4.6): a Pearson gate
    at |r| >= 0.75, a Bayesian linear regression whose hyperparameters are
    set by MacKay's evidence fixed point (30 steps, standardized [1, x]
    design), else the median with a MAD spread; Eq. 4 factors
    0.5 cpu_l/cpu_t + 0.5 io_l/io_t;
  * the online layer: the fitted regression lifted to a Normal-Inverse-
    Gamma state and updated exactly by each local completion; median tasks
    re-estimated from a ring of 256 observations and promoted to a
    regression once |r| >= 0.75; per-machine corrections from the median
    log ratio of observed to predicted runtime across tasks (dead band
    0.12, shrinkage n / (n + 2), clipped to [1/4, 4]);
  * the evidence refresh: the same fixed point over fit-time points plus
    the ring, lifted again.

The 2x2 algebra is written out so that the predictive and the fit can
also run in bfloat16 (`ml_dtypes`): the control of every correctness limit
is this reference, computed one precision below float32.
"""
from __future__ import annotations

import math
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

N_ITERS = 30
EPS = 1e-9
GATE = 0.75               # Pearson |r| for a regression model
RING = 256                # streamed observations kept per task
NODE_LOGS = 64            # log ratios kept per (machine, task)
SHRINK_K = 2.0
CLIP = 4.0
DEADBAND = 0.12
MATURE_N = 5              # machine ratios before remote runs feed a median


def bfloat16():
    import ml_dtypes
    return np.dtype(ml_dtypes.bfloat16)


def rel_err(got, ref, floor=0.0) -> float:
    """Largest |got - ref| / max(|ref|, floor).  A predicted mean is
    measured against the larger of itself and its task's runtime spread
    (y_sd times the factor): a mean near zero is the difference of two
    terms of that size, and float32 keeps it only to eps * y_sd."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    den = np.maximum(np.abs(ref), np.asarray(floor, np.float64))
    out = np.abs(got - ref) / den
    if not np.all(np.isfinite(out)):
        return math.inf
    return float(np.max(out))


# ---------------------------------------------------------------------------
# the evidence fit and the predictive, dtype-generic
# ---------------------------------------------------------------------------

def fit_evidence(x, y, m, dt=np.float64) -> dict:
    """MacKay evidence fixed point over (T, N) masked rows of a
    standardized [1, x] design.  Returns per-row leaves mu (T, 2),
    sigma (T, 2, 2), beta, x_mu, x_sd, y_mu, y_sd, n, all in `dt`."""
    c = lambda v: np.asarray(v, np.float64).astype(dt)
    x, y, m = c(x), c(y), c(m)
    one, eps = c(1.0), c(EPS)
    n = np.maximum(m.sum(1), one)
    x_mu = (x * m).sum(1) / n
    y_mu = (y * m).sum(1) / n
    x_sd = np.sqrt(((x - x_mu[:, None]) ** 2 * m).sum(1) / n + eps)
    y_sd = np.sqrt(((y - y_mu[:, None]) ** 2 * m).sum(1) / n + eps)
    xs = (x - x_mu[:, None]) / x_sd[:, None] * m
    ys = (y - y_mu[:, None]) / y_sd[:, None] * m
    g11, g12, g22 = m.sum(1), xs.sum(1), (xs * xs).sum(1)
    p1, p2 = ys.sum(1), (xs * ys).sum(1)
    alpha = np.ones_like(n)
    beta = np.ones_like(n)

    def posterior(alpha, beta):
        a11, a12, a22 = alpha + beta * g11, beta * g12, alpha + beta * g22
        det = a11 * a22 - a12 * a12
        i11, i12, i22 = a22 / det, -a12 / det, a11 / det
        return (beta * (i11 * p1 + i12 * p2), beta * (i12 * p1 + i22 * p2),
                i11, i12, i22)

    for _ in range(N_ITERS):
        mu1, mu2, _, _, _ = posterior(alpha, beta)
        b11, b12, b22 = beta * g11, beta * g12, beta * g22
        tr, det = b11 + b22, b11 * b22 - b12 * b12
        disc = np.sqrt(np.maximum(tr * tr / c(4.0) - det, c(0.0)))
        l1, l2 = tr / c(2.0) - disc, tr / c(2.0) + disc
        gamma = l1 / (alpha + l1) + l2 / (alpha + l2)
        resid = ((ys - (mu1[:, None] + mu2[:, None] * xs) * m) ** 2).sum(1)
        alpha = np.clip(gamma / np.maximum(mu1 * mu1 + mu2 * mu2, eps),
                        c(1e-6), c(1e6))
        beta = np.clip(np.maximum(n - gamma, eps) / np.maximum(resid, eps),
                       c(1e-6), c(1e8))
    mu1, mu2, i11, i12, i22 = posterior(alpha, beta)
    sigma = np.stack([np.stack([i11, i12], -1), np.stack([i12, i22], -1)],
                     -2)
    return {"mu": np.stack([mu1, mu2], -1), "sigma": sigma, "beta": beta,
            "x_mu": x_mu, "x_sd": x_sd, "y_mu": y_mu, "y_sd": y_sd, "n": n}


def predictive(post: dict, x, dt=np.float64) -> Tuple[np.ndarray, np.ndarray]:
    """Gaussian predictive mean and std at x, elementwise over leading
    dims shared by x and the leaves (mu (..., 2), sigma (..., 2, 2))."""
    c = lambda v: np.asarray(v, np.float64).astype(dt)
    mu, sig = c(post["mu"]), c(post["sigma"])
    xs = (c(x) - c(post["x_mu"])) / c(post["x_sd"])
    mean_s = mu[..., 0] + mu[..., 1] * xs
    var_s = (c(1.0) / c(post["beta"]) + sig[..., 0, 0]
             + c(2.0) * sig[..., 0, 1] * xs + sig[..., 1, 1] * xs * xs)
    mean = mean_s * c(post["y_sd"]) + c(post["y_mu"])
    std = np.sqrt(np.maximum(var_s, c(0.0))) * c(post["y_sd"])
    return (np.asarray(mean, np.float64), np.asarray(std, np.float64))


# ---------------------------------------------------------------------------
# the online task model
# ---------------------------------------------------------------------------

@dataclass
class Bench:
    """Microbenchmark readings of one machine (the data Eq. 4 reads)."""
    name: str
    cpu: float
    io_read: float
    io_write: float

    @property
    def io(self) -> float:
        return 0.5 * (self.io_read + self.io_write)


@dataclass
class TaskState:
    nig: Optional[dict]             # regression: mu, v, a, b, x/y stats
    median: float
    spread: float
    xs: deque = field(default_factory=lambda: deque(maxlen=RING))
    ys: deque = field(default_factory=lambda: deque(maxlen=RING))
    fit_x: List[float] = field(default_factory=list)
    fit_y: List[float] = field(default_factory=list)


def lift(post: dict, i: int = 0) -> dict:
    """Regression posterior row i -> Normal-Inverse-Gamma state whose
    predictive equals the Gaussian one: E[s2] = 1/beta, V = sigma beta,
    a = max(n/2, 1) pseudo-observations of noise."""
    beta = float(post["beta"][i])
    a = max(float(post["n"][i]) / 2.0, 1.0)
    return {"mu": np.asarray(post["mu"][i], np.float64).copy(),
            "v": np.asarray(post["sigma"][i], np.float64) * beta,
            "a": a, "b": a / beta,
            "x_mu": float(post["x_mu"][i]), "x_sd": float(post["x_sd"][i]),
            "y_mu": float(post["y_mu"][i]), "y_sd": float(post["y_sd"][i])}


def nig_observe(nig: dict, x: float, y: float) -> dict:
    """Exact conjugate update by one observation, in matrix form."""
    xs = (x - nig["x_mu"]) / nig["x_sd"]
    ys = (y - nig["y_mu"]) / nig["y_sd"]
    phi = np.array([1.0, xs])
    prec = np.linalg.inv(nig["v"])
    prec_n = prec + np.outer(phi, phi)
    v_n = np.linalg.inv(prec_n)
    mu_n = v_n @ (prec @ nig["mu"] + phi * ys)
    b_n = nig["b"] + 0.5 * (ys * ys + nig["mu"] @ prec @ nig["mu"]
                            - mu_n @ prec_n @ mu_n)
    out = dict(nig)
    out.update(mu=mu_n, v=v_n, a=nig["a"] + 0.5, b=max(b_n, 1e-12))
    return out


def nig_post(nig: dict) -> dict:
    """The NIG state as a Gaussian predictive posterior (one row)."""
    s2 = nig["b"] / nig["a"]
    return {"mu": nig["mu"], "sigma": s2 * nig["v"], "beta": 1.0 / s2,
            "x_mu": nig["x_mu"], "x_sd": nig["x_sd"],
            "y_mu": nig["y_mu"], "y_sd": nig["y_sd"]}


def _corr(x, y) -> float:
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    if len(x) < 2 or np.std(x) < 1e-12 or np.std(y) < 1e-12:
        return 0.0
    return float(np.corrcoef(x, y)[0, 1])


def _fit_one(x, y) -> dict:
    x = np.asarray(x, np.float64)[None, :]
    y = np.asarray(y, np.float64)[None, :]
    return fit_evidence(x, y, np.ones_like(x))


class NodeStats:
    """Observed / predicted log ratios on one machine, grouped by task."""

    def __init__(self):
        self.logs: Dict[str, deque] = {}

    @property
    def n(self) -> int:
        return sum(len(v) for v in self.logs.values())

    def add(self, task: str, ratio: float) -> None:
        self.logs.setdefault(task, deque(maxlen=NODE_LOGS)).append(
            math.log(max(ratio, 1e-6)))

    def correction(self) -> float:
        meds = [float(np.median(list(v))) for v in self.logs.values() if v]
        if len(meds) < 2:
            return 1.0
        med = float(np.median(meds))
        sd = 1.4826 * float(np.median(np.abs(np.asarray(meds) - med)))
        if abs(med) < max(DEADBAND, 2.0 * 1.2533 * sd / math.sqrt(len(meds))):
            return 1.0
        w = self.n / (self.n + SHRINK_K)
        return float(np.clip(math.exp(w * med), 1.0 / CLIP, CLIP))


class Model:
    """One tenant's online Lotaru-G predictor, in float64."""

    def __init__(self, tasks: Dict[str, TaskState], local: Bench,
                 benches: Dict[str, Bench]):
        self.tasks = tasks
        self.local = local
        self.benches = benches
        self.nodes: Dict[str, NodeStats] = {}

    @classmethod
    def from_traces(cls, rows: Sequence[Tuple[str, float, float]],
                    local: Bench, benches: Dict[str, Bench]) -> "Model":
        """rows: (task, input_gb, runtime_s) of local profiling runs."""
        by_task: Dict[str, Tuple[list, list]] = {}
        for task, x, y in rows:
            xs, ys = by_task.setdefault(task, ([], []))
            xs.append(float(x))
            ys.append(float(y))
        tasks = {}
        for task, (x, y) in by_task.items():
            y_arr = np.asarray(y, np.float64)
            med = float(np.median(y_arr))
            st = TaskState(nig=None, median=med, spread=float(
                1.4826 * np.median(np.abs(y_arr - med)) + 1e-6))
            if abs(_corr(x, y)) >= GATE:
                st.nig = lift(_fit_one(x, y))
                st.fit_x, st.fit_y = list(x), list(y)
            tasks[task] = st
        return cls(tasks, local, benches)

    def copy(self) -> "Model":
        tasks = {t: TaskState(nig=None if s.nig is None else dict(s.nig),
                              median=s.median, spread=s.spread,
                              xs=deque(s.xs, maxlen=RING),
                              ys=deque(s.ys, maxlen=RING),
                              fit_x=list(s.fit_x), fit_y=list(s.fit_y))
                 for t, s in self.tasks.items()}
        out = Model(tasks, self.local, self.benches)
        for name, ns in self.nodes.items():
            c = NodeStats()
            c.logs = {t: deque(v, maxlen=NODE_LOGS) for t, v in ns.logs.items()}
            out.nodes[name] = c
        return out

    # ---- factors ------------------------------------------------------------
    def bench(self, node: Optional[str]) -> Optional[Bench]:
        if node is None:
            return None
        b = self.benches.get(node)
        if b is None and "-" in node:
            b = self.benches.get(node.rsplit("-", 1)[0])
        return b

    def base_factor(self, node: Optional[str]) -> float:
        b = self.bench(node)
        if node is None or b is None or b.name == self.local.name:
            return 1.0
        return 0.5 * (self.local.cpu / b.cpu) + 0.5 * (self.local.io / b.io)

    def correction(self, node: Optional[str]) -> float:
        b = self.bench(node)
        if b is None or b.name not in self.nodes:
            return 1.0
        return self.nodes[b.name].correction()

    def factor(self, node: Optional[str]) -> float:
        return self.base_factor(node) * self.correction(node)

    # ---- prediction -----------------------------------------------------------
    def posterior(self, task: str) -> dict:
        st = self.tasks[task]
        if st.nig is not None:
            return nig_post(st.nig)
        return {"mu": np.zeros(2), "sigma": np.zeros((2, 2)), "beta": 1.0,
                "x_mu": 0.0, "x_sd": 1.0, "y_mu": st.median,
                "y_sd": max(st.spread, 1e-6)}

    def predict(self, queries: Sequence[Tuple[str, Optional[str], float]],
                dt=np.float64):
        """(task, node, input_gb) -> (mean, std, floor) arrays in seconds
        on the node; floor is the task's runtime spread on that node."""
        posts = [self.posterior(t) for t, _, _ in queries]
        stacked = {k: np.asarray([p[k] for p in posts], np.float64)
                   for k in posts[0]}
        x = np.asarray([q[2] for q in queries], np.float64)
        mean, std = predictive(stacked, x, dt)
        f = np.asarray([self.factor(n) for _, n, _ in queries], np.float64)
        return np.maximum(mean, 1e-3) * f, std * f, stacked["y_sd"] * f

    # ---- learning -------------------------------------------------------------
    def observe(self, task: str, node: Optional[str], x: float,
                y: float) -> None:
        if task not in self.tasks:
            return
        st = self.tasks[task]
        local = node in (None, "", "local", self.local.name)
        bench = None
        if not local:
            bench = self.bench(node)
            if bench is None:
                return
            local = bench.name == self.local.name
        stats = None
        if not local:
            mean, _ = predictive(self.posterior(task), x)
            static = max(float(mean), 1e-3) * self.base_factor(bench.name)
            stats = self.nodes.setdefault(bench.name, NodeStats())
            stats.add(task, y / max(static, 1e-6))
        if st.nig is not None:
            if local:
                st.nig = nig_observe(st.nig, x, y)
                st.xs.append(x)
                st.ys.append(y)
            return
        if not local and stats.n < MATURE_N:
            return
        f = 1.0 if local else self.factor(bench.name)
        st.xs.append(x)
        st.ys.append(y / max(f, 1e-6))
        ys = np.asarray(st.ys, np.float64)
        st.median = float(np.median(ys))
        mad = 1.4826 * float(np.median(np.abs(ys - st.median)))
        st.spread = max(mad, 0.05 * abs(st.median), 1e-3)
        if len(st.xs) >= 4 and abs(_corr(st.xs, st.ys)) >= GATE:
            st.nig = lift(_fit_one(list(st.xs), list(st.ys)))

    def refresh(self, task: str, dt=np.float64) -> dict:
        """The evidence refresh of one regression task: the fixed point
        over fit-time points plus the ring.  Returns the fitted row (not
        lifted), computed in `dt`."""
        st = self.tasks[task]
        x = np.asarray(st.fit_x + list(st.xs), np.float64)[None, :]
        y = np.asarray(st.fit_y + list(st.ys), np.float64)[None, :]
        return fit_evidence(x, y, np.ones_like(x), dt)
