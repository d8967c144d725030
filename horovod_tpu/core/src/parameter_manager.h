// Online autotuner: Bayesian optimization of fusion threshold and cycle
// time (reference: horovod/common/parameter_manager.cc +
// optim/bayesian_optimization.cc).  Enabled by HOROVOD_AUTOTUNE=1; the
// coordinator samples (fusion_bytes, cycle_ms), scores each sample by
// observed reduced-bytes/sec, fits a GP, maximizes expected improvement
// over the discrete grid, and converges to the best point; chosen values
// are broadcast to workers in the CycleResponse.  CSV log via
// HOROVOD_AUTOTUNE_LOG.
#ifndef HVD_TPU_PARAMETER_MANAGER_H
#define HVD_TPU_PARAMETER_MANAGER_H

#include <chrono>
#include <cstdint>
#include <cstdio>
#include <map>
#include <mutex>
#include <string>
#include <utility>
#include <vector>

#include "common.h"
#include "gaussian_process.h"

namespace hvdtpu {

class BayesianOptimization {
 public:
  BayesianOptimization();
  // Record a scored sample by grid index.
  void Record(int grid_index, double score);
  int NextSample();  // grid index maximizing EI
  int BestSample() const;
  const std::vector<std::vector<double>>& grid() const { return grid_; }

 private:
  std::vector<std::vector<double>> grid_;
  std::vector<int> sampled_idx_;
  std::vector<double> scores_;
  GaussianProcess gp_;
};

// Categorical argmax-by-mean tuner for kernel launch parameters: a
// caller reports (choice, score) samples here; Best() is the choice with
// the highest mean score.  The discrete choice set is tiny, so no GP is
// warranted.  No Python code reports samples since the flash kernels'
// blocks came from the call's shape alone.
class KernelTuner {
 public:
  void Record(int choice, double score) EXCLUDES(mu_);
  int Best() const EXCLUDES(mu_);     // -1 when no samples recorded
  int Samples() const EXCLUDES(mu_);

 private:
  mutable std::mutex mu_;
  // choice -> (sum, n)
  std::map<int, std::pair<double, int>> agg_ GUARDED_BY(mu_);
};

class ParameterManager {
 public:
  void Configure(uint64_t fusion_threshold, double cycle_time_ms,
                 bool enabled, const std::string& log_path,
                 int warmup_cycles = 5, int cycles_per_sample = 20,
                 int max_samples = 25) EXCLUDES(mu_);
  // Called once per non-empty cycle with reduced bytes and cycle seconds.
  // Returns true if the tuned values changed (so the coordinator should
  // re-broadcast them).
  // Thread-safe: called from the background cycle loop AND, in
  // multihost mode, from the Python executor reporting device-plane
  // completion times (hvd_tcp_autotune_observe).
  bool Observe(uint64_t bytes, double secs) EXCLUDES(mu_);

  // Plan-cache warm start (hvd_tcp_autotune_warm_start): adopt a
  // persisted tuned operating point — sampling starts AT the point
  // with the warm-up window skipped, and a converged plan freezes the
  // tuner entirely, so a rerun never re-walks the grid it already
  // searched.
  void WarmStart(uint64_t fusion_threshold, double cycle_time_ms,
                 bool converged) EXCLUDES(mu_);

  // Snapshot for plan persistence (hvd_tcp_autotune_state); any out
  // pointer may be null.
  void State(uint64_t* fusion, double* cycle_ms, int* converged,
             int* samples_done, int* warmup_left) const EXCLUDES(mu_);

  uint64_t fusion_threshold() const {
    std::lock_guard<std::mutex> lk(mu_);
    return fusion_threshold_;
  }
  double cycle_time_ms() const {
    std::lock_guard<std::mutex> lk(mu_);
    return cycle_time_ms_;
  }
  bool converged() const {
    std::lock_guard<std::mutex> lk(mu_);
    return converged_;
  }

 private:
  void Apply(int grid_index) REQUIRES(mu_);

  mutable std::mutex mu_;
  BayesianOptimization bo_ GUARDED_BY(mu_);
  uint64_t fusion_threshold_ GUARDED_BY(mu_) = 64ull << 20;
  double cycle_time_ms_ GUARDED_BY(mu_) = 5.0;
  bool enabled_ GUARDED_BY(mu_) = false;
  bool converged_ GUARDED_BY(mu_) = false;
  int warmup_ GUARDED_BY(mu_) = 5;
  int cycles_per_sample_ GUARDED_BY(mu_) = 20;
  int max_samples_ GUARDED_BY(mu_) = 25;
  int current_idx_ GUARDED_BY(mu_) = -1;
  int cycles_seen_ GUARDED_BY(mu_) = 0;
  int samples_done_ GUARDED_BY(mu_) = 0;
  double acc_bytes_ GUARDED_BY(mu_) = 0;
  double max_secs_ GUARDED_BY(mu_) = 0;
  std::chrono::steady_clock::time_point sample_start_ GUARDED_BY(mu_){};
  std::chrono::steady_clock::time_point last_obs_end_ GUARDED_BY(mu_){};
  FILE* log_ GUARDED_BY(mu_) = nullptr;
};

}  // namespace hvdtpu

#endif  // HVD_TPU_PARAMETER_MANAGER_H
