"""Online autotuner for fusion threshold and cycle time.

Equivalent of the reference's ``horovod/common/parameter_manager.cc`` +
``horovod/common/optim/bayesian_optimization.cc`` / ``gaussian_process.cc``:
when ``HOROVOD_AUTOTUNE=1``, the engine scores each sample of
(fusion_threshold, cycle_time) by observed throughput (bytes reduced per
second), and a Gaussian-process surrogate with an expected-improvement
acquisition proposes the next sample.  After convergence (or
``HOROVOD_AUTOTUNE_STEPS`` samples) the best point is pinned.

The search space mirrors the reference: fusion threshold over
{0..64} MiB-scale powers of two, cycle time over 1..25 ms.  Scores and
samples are appended to ``HOROVOD_AUTOTUNE_LOG`` as CSV when set.

A native C++ implementation with the same algorithm lives in
``horovod_tpu/core`` for the TCP world; this module drives the in-process
engine and is also importable for tests of the math itself.
"""

from __future__ import annotations

import math
import os
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

# Search space (log2 bytes, ms).
_FUSION_CHOICES = [1 << p for p in range(20, 28)]  # 1 MiB .. 128 MiB
_CYCLE_CHOICES = [1.0, 2.5, 5.0, 10.0, 25.0]


class GaussianProcess:
    """Minimal RBF-kernel GP regressor (reference: gaussian_process.cc)."""

    def __init__(self, length_scale: float = 1.0, noise: float = 1e-6,
                 alpha: float = 1e-10):
        self.length_scale = length_scale
        self.noise = noise
        self.alpha = alpha
        self._x: Optional[np.ndarray] = None
        self._y: Optional[np.ndarray] = None
        self._l: Optional[np.ndarray] = None
        self._a: Optional[np.ndarray] = None

    def _kernel(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        d = ((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)
        return np.exp(-0.5 * d / (self.length_scale ** 2))

    def _log_marginal_likelihood(self, ls: float) -> float:
        """LML of the stored (x, y) at a candidate length-scale."""
        saved = self.length_scale
        try:
            self.length_scale = ls
            k = self._kernel(self._x, self._x)
        finally:
            self.length_scale = saved
        k[np.diag_indices_from(k)] += self.noise + self.alpha
        try:
            low = np.linalg.cholesky(k)
        except np.linalg.LinAlgError:
            return -np.inf
        a = np.linalg.solve(low.T, np.linalg.solve(low, self._y))
        n = len(self._y)
        return float(-0.5 * self._y @ a
                     - np.log(np.diag(low)).sum()
                     - 0.5 * n * math.log(2.0 * math.pi))

    def optimize_length_scale(self, lo: float = 0.1, hi: float = 10.0,
                              iters: int = 24):
        """Max-marginal-likelihood length-scale via golden-section
        search on the 1-D log length-scale (reference fits kernel
        hyperparameters with lbfgs in optim/; one bounded 1-D search
        needs no lbfgs dependency)."""
        a, b = math.log(lo), math.log(hi)
        inv_phi = (math.sqrt(5.0) - 1.0) / 2.0
        c = b - inv_phi * (b - a)
        d = a + inv_phi * (b - a)
        fc = self._log_marginal_likelihood(math.exp(c))
        fd = self._log_marginal_likelihood(math.exp(d))
        for _ in range(iters):
            if fc > fd:
                b, d, fd = d, c, fc
                c = b - inv_phi * (b - a)
                fc = self._log_marginal_likelihood(math.exp(c))
            else:
                a, c, fc = c, d, fd
                d = a + inv_phi * (b - a)
                fd = self._log_marginal_likelihood(math.exp(d))
        self.length_scale = math.exp((a + b) / 2.0)
        return self.length_scale

    def fit(self, x: np.ndarray, y: np.ndarray,
            optimize_length_scale: bool = False):
        self._x = np.atleast_2d(np.asarray(x, dtype=np.float64))
        self._y = np.asarray(y, dtype=np.float64)
        if optimize_length_scale and len(self._y) >= 4:
            self.optimize_length_scale()
        k = self._kernel(self._x, self._x)
        k[np.diag_indices_from(k)] += self.noise + self.alpha
        self._l = np.linalg.cholesky(k)
        self._a = np.linalg.solve(
            self._l.T, np.linalg.solve(self._l, self._y))

    def predict(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        x = np.atleast_2d(np.asarray(x, dtype=np.float64))
        ks = self._kernel(x, self._x)
        mu = ks @ self._a
        v = np.linalg.solve(self._l, ks.T)
        var = np.clip(1.0 - (v ** 2).sum(0), 1e-12, None)
        return mu, np.sqrt(var)


def expected_improvement(mu: np.ndarray, sigma: np.ndarray,
                         best: float, xi: float = 0.01) -> np.ndarray:
    """EI acquisition (reference: bayesian_optimization.cc)."""
    z = (mu - best - xi) / sigma
    cdf = 0.5 * (1.0 + np.vectorize(math.erf)(z / math.sqrt(2.0)))
    pdf = np.exp(-0.5 * z ** 2) / math.sqrt(2.0 * math.pi)
    return (mu - best - xi) * cdf + sigma * pdf


class BayesianOptimizer:
    """GP + EI over the discrete (fusion, cycle) grid."""

    def __init__(self):
        self.grid = np.array(
            [[math.log2(f), math.log2(c + 1.0)]
             for f in _FUSION_CHOICES for c in _CYCLE_CHOICES])
        self.points: List[np.ndarray] = []
        self.scores: List[float] = []
        self.gp = GaussianProcess(length_scale=1.5)

    def _normalize(self):
        y = np.asarray(self.scores)
        s = y.std()
        return (y - y.mean()) / (s if s > 0 else 1.0)

    def record(self, point_idx: int, score: float):
        self.points.append(self.grid[point_idx])
        self.scores.append(score)

    def next_index(self) -> int:
        if len(self.scores) < 2:
            # Bootstrap with spread-out samples.
            return [0, len(self.grid) - 1][len(self.scores)]
        self.gp.fit(np.stack(self.points), self._normalize(),
                    optimize_length_scale=True)
        mu, sigma = self.gp.predict(self.grid)
        ei = expected_improvement(mu, sigma, float(self._normalize().max()))
        return int(np.argmax(ei))

    def best_index(self) -> int:
        by_point = {}
        for p, s in zip(self.points, self.scores):
            by_point.setdefault(tuple(p), []).append(s)
        best_p = max(by_point, key=lambda p: np.mean(by_point[p]))
        return int(np.argmin(((self.grid - np.array(best_p)) ** 2).sum(1)))


class PlanTuner:
    """GP/EI proposer over one (op, size_class)'s candidate plan grid —
    the widened search space of ROADMAP item 1: hier-vs-flat leg
    choice x cross-host codec engagement (``utils/plancache.py`` builds
    the candidate list from what the world actually supports).

    Each candidate is a coordinate (e.g. ``(hier, codec)`` in {0,1}^2);
    every candidate is bootstrapped once, then the same GP surrogate +
    expected-improvement acquisition as the fusion/cycle tuner proposes
    further samples until ``max_samples``, after which :meth:`best` is
    the argmax-by-mean.  SPMD contract: in a multi-member world the
    caller must cross-rank AVERAGE each score before :meth:`record`
    (``tune_collective_plans`` does) — proposals and the final argmax
    are then pure functions of identical state on every member, so all
    members pin the same plan.
    """

    def __init__(self, coords: Sequence[Sequence[float]],
                 max_samples: Optional[int] = None, xi: float = 0.01):
        self.coords = np.atleast_2d(np.asarray(coords, np.float64))
        # atleast_2d turns an empty list into shape (1, 0); size catches
        # that where len() would not.
        self.n = len(self.coords) if self.coords.size else 0
        if self.n < 1:
            raise ValueError("PlanTuner needs at least 1 candidate")
        self.max_samples = int(max_samples or max(2 * self.n, self.n + 1))
        self.xi = float(xi)
        self.points: List[int] = []
        self.scores: List[float] = []
        self.gp = GaussianProcess(length_scale=0.8)

    @property
    def samples(self) -> int:
        return len(self.scores)

    @property
    def converged(self) -> bool:
        if self.n == 1:
            return self.samples >= 1
        return self.samples >= self.max_samples

    def propose(self) -> int:
        """Next candidate index to sample: each candidate once first
        (deterministic bootstrap), then EI over the grid."""
        sampled = set(self.points)
        for i in range(self.n):
            if i not in sampled:
                return i
        y = np.asarray(self.scores)
        s = y.std()
        yn = (y - y.mean()) / (s if s > 0 else 1.0)
        self.gp.fit(self.coords[self.points], yn)
        mu, sigma = self.gp.predict(self.coords)
        ei = expected_improvement(mu, sigma, float(yn.max()), self.xi)
        return int(np.argmax(ei))

    def record(self, index: int, score: float):
        if not 0 <= index < self.n:
            raise IndexError("candidate index %d out of range [0, %d)"
                             % (index, self.n))
        self.points.append(int(index))
        self.scores.append(float(score))

    def mean_scores(self) -> List[Optional[float]]:
        by: dict = {}
        for p, s in zip(self.points, self.scores):
            by.setdefault(p, []).append(s)
        return [float(np.mean(by[i])) if i in by else None
                for i in range(self.n)]

    def best(self) -> int:
        if not self.scores:
            raise RuntimeError("no samples recorded")
        means = self.mean_scores()
        return int(max((i for i in range(self.n)
                        if means[i] is not None),
                       key=lambda i: means[i]))


class AutotuneLog:
    """Crash-safe autotune CSV writer (the r11 journal conventions).

    The old ``open(path, "w")`` writer clobbered peers' logs and
    interleaved partial lines across a multi-process world.  This one
    rank-stamps the filename (``<path>.r<rank>``, pid fallback — one
    writer per file, like ``events-<writer>.jsonl``) and appends each
    record as ONE ``os.write`` on an ``O_APPEND`` fd: concurrent
    writers can interleave lines, never bytes, and a crash tears at
    most nothing (a line is a single atomic append).  The header is
    written only when this writer's file is empty, so restarted runs
    append instead of restamping."""

    HEADER = "sample,fusion_bytes,cycle_ms,score_bytes_per_s"

    def __init__(self, path: str, tag: Optional[str] = None):
        if tag is None:
            rank = os.environ.get("HOROVOD_RANK")
            tag = "r%s" % rank if rank is not None \
                else "pid%d" % os.getpid()
        self.path = "%s.%s" % (path, tag)
        self._fd: Optional[int] = None
        try:
            self._fd = os.open(
                self.path, os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
            if os.fstat(self._fd).st_size == 0:
                self.write_line(self.HEADER)
        except OSError:
            # A bad log path degrades observability, never tuning.
            self._fd = None

    def write_line(self, line: str):
        if self._fd is None:
            return
        try:
            os.write(self._fd, (line + "\n").encode())
        except OSError:
            pass

    def close(self):
        if self._fd is not None:
            try:
                os.close(self._fd)
            finally:
                self._fd = None

    def __del__(self):
        # GC-finalizer parity with the file-object writer this
        # replaced: a ParameterManager dropped at shutdown/re-init
        # must not leak its O_APPEND fd across elastic init cycles.
        self.close()


class ParameterManager:
    """Drives sampling from the engine's cycle loop (parameter_manager.cc).

    ``observe(bytes, secs)`` is called once per non-empty cycle; samples are
    scored by aggregate throughput over ``steps_per_sample`` cycles.

    ``warm_start=(fusion, cycle_ms, converged)`` adopts a persisted
    plan's operating point (``utils/plancache.py``): a converged plan
    freezes the tuner entirely (warm-up skipped — the rerun cold-starts
    where the last run ended instead of re-walking the grid); an
    unconverged one runs the adopted point through a single warm-up
    cycle (the fresh process's compile skew must not enter the GP) and
    then resumes the sweep.
    """

    def __init__(self, fusion_threshold: int, cycle_time_ms: float,
                 log_path: Optional[str] = None, warmup: int = 3,
                 steps_per_sample: int = 10, max_samples: int = 30,
                 warm_start: Optional[Tuple[int, float, bool]] = None,
                 log_tag: Optional[str] = None):
        self.bo = BayesianOptimizer()
        self.fusion_threshold = fusion_threshold
        self.cycle_time_ms = cycle_time_ms
        self.warmup = warmup
        self.steps_per_sample = steps_per_sample
        self.max_samples = max_samples
        self._log = AutotuneLog(log_path, log_tag) if log_path else None
        self._cycle_bytes = 0.0
        self._max_secs = 0.0
        self._cycles_seen = 0
        self._last_obs_end = 0.0
        self._samples_done = 0
        self._current_idx: Optional[int] = None
        self.frozen = False
        if warm_start is not None:
            f, c, converged = warm_start
            self.fusion_threshold = int(f)
            self.cycle_time_ms = float(c)
            # Converged: nothing left to sample, skip warm-up outright.
            # Unconverged: keep ONE warm-up cycle — the rerun's first
            # observation carries fresh-process compile skew, exactly
            # what the warm-up window exists to discard.
            self.warmup = 0 if converged else min(int(warmup), 1)
            self.frozen = bool(converged)
            if self._log:
                self._log.write_line(
                    "# warm-start: fusion=%d cycle=%.3f converged=%d"
                    % (self.fusion_threshold, self.cycle_time_ms,
                       int(self.frozen)))

    @property
    def samples_done(self) -> int:
        return self._samples_done

    @property
    def warmup_left(self) -> int:
        return max(int(self.warmup), 0)

    def _apply(self, idx: int):  # graftlint: spmd-uniform -- in-process tuner: ParameterManager is installed only by the single-process engine (common/basics.py, mode == "inprocess"), so there is no peer to diverge from; the multi-member planes tune through tune_collective_plans' cross-rank-averaged sweep instead
        f_log, c_log = self.bo.grid[idx]
        self.fusion_threshold = int(2 ** f_log)
        self.cycle_time_ms = float(2 ** c_log - 1.0)
        self._current_idx = idx

    def observe(self, nbytes: int, secs: float):  # graftlint: spmd-uniform -- in-process tuner: installed only by the single-process engine (common/basics.py, mode == "inprocess"); its wall-clock scores feed a private BO with no peer to diverge from, and the multi-member sweep (tune_collective_plans) cross-rank-averages before ITS tuner sees a score
        if self.frozen:
            return
        if self.warmup > 0:
            self.warmup -= 1
            return
        if self._current_idx is None:
            self._apply(self.bo.next_index())
        now = time.monotonic()
        s = max(secs, 0.0)
        if self._cycles_seen > 0:
            # LONG application idle inside a window (eval pauses, data
            # stalls) is not the candidate's fault — EXCLUDE it from
            # the scored denominator (shift the window start forward)
            # rather than discarding the window, so workloads whose
            # steps are spaced beyond the threshold still fill windows
            # and record samples.  Normal inter-step compute gaps stay
            # below the threshold and keep counting as wall time.
            gap = (now - self._last_obs_end) - s
            if gap > max(5.0, 50.0 * self.cycle_time_ms / 1e3):
                self._sample_t0 += gap
        if self._cycles_seen == 0:
            # observe() runs at cycle END; backdate by this cycle's
            # active time so the window covers every accumulated cycle.
            self._sample_t0 = now - s
        self._last_obs_end = now
        self._cycle_bytes += nbytes
        self._max_secs = max(self._max_secs, secs, 1e-9)
        self._cycles_seen += 1
        if self._cycles_seen < self.steps_per_sample:
            return
        # Score by WALL time across the sample window: the cycle pause
        # and any contention the candidate point causes must count, or
        # short cycle times look free.  Observations may overlap
        # (pipelined device groups), so the clock guard is the LONGEST
        # single observation, never their sum.
        wall = max(time.monotonic() - self._sample_t0,
                   self._max_secs, 1e-9)
        score = self._cycle_bytes / wall
        self.bo.record(self._current_idx, score)
        self._samples_done += 1
        if self._log:
            self._log.write_line("%d,%d,%.3f,%.1f" % (
                self._samples_done, self.fusion_threshold,
                self.cycle_time_ms, score))
        self._cycle_bytes = self._max_secs = 0.0
        self._cycles_seen = 0
        if self._samples_done >= self.max_samples:
            self._apply(self.bo.best_index())
            self.frozen = True
            if self._log:
                self._log.write_line("# converged: fusion=%d cycle=%.3f"
                                     % (self.fusion_threshold,
                                        self.cycle_time_ms))
        else:
            self._apply(self.bo.next_index())
