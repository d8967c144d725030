"""Autotuner behavior tests (Python mirror of parameter_manager.cc +
bayesian_optimization.cc; the C++ twin is driven by the tcp worlds)."""

import numpy as np

import pytest

from horovod_tpu.utils.autotune import (BayesianOptimizer,
                                        GaussianProcess,
                                        ParameterManager,
                                        expected_improvement)


def test_gp_fits_and_predicts():
    gp = GaussianProcess(length_scale=1.0)
    x = np.array([[0.0], [1.0], [2.0]])
    y = np.array([0.0, 1.0, 0.0])
    gp.fit(x, y)
    mu, sigma = gp.predict(np.array([[1.0], [10.0]]))
    # near a training point: confident and close; far away: uncertain
    assert abs(mu[0] - 1.0) < 0.2
    assert sigma[1] > sigma[0]


def test_expected_improvement_prefers_uncertain_high_mean():
    mu = np.array([0.0, 1.0, 1.0])
    sigma = np.array([0.1, 0.1, 1.0])
    ei = expected_improvement(mu, sigma, best=0.5)
    assert ei[2] > ei[1] > ei[0]


def test_bayesian_optimizer_converges_to_better_region():
    bo = BayesianOptimizer()
    # synthetic objective: reward large fusion + small cycle (the
    # common real-world optimum); the BO should concentrate samples
    # toward the high-scoring corner
    rng = np.random.default_rng(0)
    for _ in range(20):
        idx = bo.next_index()
        f_log, c_log = bo.grid[idx]
        score = float(2 * f_log - c_log + rng.normal(0, 0.1))
        bo.record(idx, score)
    best = bo.grid[bo.best_index()]
    assert best[0] >= np.median(bo.grid[:, 0])  # large fusion chosen


def test_gp_length_scale_fit_recovers_smoothness():
    # Samples from a smooth surface (true scale ~2) vs a jagged one
    # (scale ~0.2): the max-marginal-likelihood fit must order the
    # learned length-scales accordingly — that is exactly the sample-
    # efficiency knob the fixed-scale GP lacked.
    x = np.linspace(0.0, 6.0, 24)[:, None]
    smooth = np.sin(x[:, 0] / 2.0)
    jagged = np.sin(x[:, 0] * 8.0)
    gp_s = GaussianProcess(noise=1e-4)
    gp_s.fit(x, smooth, optimize_length_scale=True)
    gp_j = GaussianProcess(noise=1e-4)
    gp_j.fit(x, jagged, optimize_length_scale=True)
    assert gp_s.length_scale > 1.0, gp_s.length_scale
    assert gp_j.length_scale < 0.5, gp_j.length_scale
    assert gp_s.length_scale > 3 * gp_j.length_scale
    # The refit GP interpolates the smooth surface well between samples.
    mu, _ = gp_s.predict(np.array([[1.1]]))
    assert abs(mu[0] - np.sin(1.1 / 2.0)) < 0.05


def test_bo_with_ls_fit_converges_on_synthetic_throughput_surface():
    # Synthetic throughput surface with a known interior optimum (not a
    # grid corner): fusion sweet spot at ~2^24 with a cycle-time
    # penalty.  After a budget of samples, the chosen point must sit in
    # the top decile of the true surface — the convergence bar for the
    # hyperparameter-fitting BO.
    bo = BayesianOptimizer()

    def surface(f_log, c_log):
        return -((f_log - 24.0) ** 2) - 0.5 * (c_log - 1.0) ** 2

    rng = np.random.default_rng(1)
    for _ in range(16):
        idx = bo.next_index()
        f_log, c_log = bo.grid[idx]
        bo.record(idx, float(surface(f_log, c_log)
                             + rng.normal(0, 0.05)))
    truth = np.array([surface(f, c) for f, c in bo.grid])
    chosen = truth[bo.best_index()]
    assert chosen >= np.quantile(truth, 0.9), (
        chosen, float(truth.max()))


def test_parameter_manager_samples_and_freezes(tmp_path):
    # The r14 crash-safe writer rank-stamps the path (one writer per
    # file); pin the tag so the read-back path is deterministic.
    log = tmp_path / "autotune.csv.r0"
    pm = ParameterManager(fusion_threshold=1 << 20, cycle_time_ms=5.0,
                          log_path=str(tmp_path / "autotune.csv"),
                          warmup=1, steps_per_sample=2, max_samples=3,
                          log_tag="r0")
    # throughput is higher for larger fusion thresholds
    for _ in range(1 + 2 * 3 + 2):
        pm.observe(nbytes=pm.fusion_threshold, secs=1e-3)
    lines = log.read_text().strip().splitlines()
    assert lines[0].startswith("sample,")
    assert len(lines) >= 4  # header + 3 samples
    # after max_samples the manager settles on the best point
    settled = pm.fusion_threshold
    pm.observe(nbytes=123, secs=1e-3)
    assert pm.fusion_threshold == settled


def test_engine_skips_observations_on_compile_cycles(hvd_world):
    # A cycle that compiled a new XLA executable must not feed its
    # wall time to the tuner (it measures the compiler, not comm).
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import basics

    class FakePM:
        fusion_threshold = 1 << 20
        cycle_time_ms = 1.0

        def __init__(self):
            self.observed = []

        def observe(self, nbytes, secs):
            self.observed.append(nbytes)

    eng = basics._get_engine()
    pm, old = FakePM(), eng.parameter_manager
    eng.parameter_manager = pm
    try:
        # fresh odd single-tensor shape -> this cycle compiles
        x = np.ones((hvd.size(), 97), np.float32)
        hvd.allreduce(x, op=hvd.Sum, name="atune_compile_skip_1")
        after_compile = len(pm.observed)
        # same shape again -> cached executable, observation recorded
        hvd.allreduce(x, op=hvd.Sum, name="atune_compile_skip_2")
        assert after_compile == 0, "compile cycle was observed"
        assert len(pm.observed) >= 1, "steady-state cycle not observed"
    finally:
        eng.parameter_manager = old
