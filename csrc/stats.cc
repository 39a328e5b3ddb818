// Stat monitor: named int64 totals behind a C API (reference
// platform/monitor.h StatRegistry). Host spans and counters live in Python
// (paddle_tpu/profiler: jax.profiler.TraceAnnotation into the XPlane trace,
// and the metrics registry); no native code records a span.
#include <cstdint>
#include <map>
#include <mutex>
#include <string>

#include "common.h"

namespace paddle_tpu {
namespace {

// ---- stat monitor (reference platform/monitor.h StatRegistry) ----------
class StatRegistry {
 public:
  static StatRegistry& Instance() {
    static StatRegistry r;
    return r;
  }
  void Add(const std::string& name, int64_t v) {
    std::lock_guard<std::mutex> g(mu_);
    stats_[name] += v;
  }
  int64_t Get(const std::string& name) {
    std::lock_guard<std::mutex> g(mu_);
    auto it = stats_.find(name);
    return it == stats_.end() ? 0 : it->second;
  }
  std::string List() {
    std::lock_guard<std::mutex> g(mu_);
    std::string out;
    for (auto& kv : stats_) {
      if (!out.empty()) out += "\n";
      out += kv.first + "=" + std::to_string(kv.second);
    }
    return out;
  }

 private:
  std::mutex mu_;
  std::map<std::string, int64_t> stats_;
};

}  // namespace
}  // namespace paddle_tpu

using paddle_tpu::StatRegistry;

extern "C" {

void pt_stat_add(const char* name, int64_t v) {
  StatRegistry::Instance().Add(name, v);
}
int64_t pt_stat_get(const char* name) {
  return StatRegistry::Instance().Get(name);
}
const char* pt_stat_list() {
  static thread_local std::string out;
  out = StatRegistry::Instance().List();
  return out.c_str();
}

}  // extern "C"
