// Chrome-tracing timeline for the native core (reference:
// horovod/common/timeline.{h,cc} — writer thread + activity events;
// coordinator-only file, operations.cc:459-475; dynamic start/stop via the
// C API, operations.cc:1011-1041; activity classes common.h:73-105).
#pragma once

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdio>
#include <mutex>
#include <queue>
#include <string>
#include <thread>
#include <unordered_map>

namespace hvd {

// Tensor names come from user code: escape them before embedding in
// hand-rolled JSON (timeline events, engine-state snapshots) or a name
// with a quote/backslash corrupts the whole document exactly when a
// post-mortem needs it.
inline std::string JsonEscape(const std::string& s) {
  std::string out;
  out.reserve(s.size());
  for (unsigned char c : s) {
    switch (c) {
      case '"': out += "\\\""; break;
      case '\\': out += "\\\\"; break;
      case '\n': out += "\\n"; break;
      case '\r': out += "\\r"; break;
      case '\t': out += "\\t"; break;
      default:
        if (c < 0x20) {
          char buf[8];
          std::snprintf(buf, sizeof(buf), "\\u%04x", c);
          out += buf;
        } else {
          out += (char)c;
        }
    }
  }
  return out;
}

class Timeline {
 public:
  // path empty or rank != 0 -> disabled until Start() is called
  Timeline(int rank, const std::string& path, bool mark_cycles = false);
  ~Timeline();
  bool enabled() const { return enabled_.load(std::memory_order_relaxed); }
  bool mark_cycles() const {
    return mark_cycles_.load(std::memory_order_relaxed);
  }
  // Dynamic control (reference: horovod_start_timeline/_stop_timeline).
  // Coordinator-only: non-zero ranks no-op and return OK. Start on an
  // already-running timeline reopens at the new path.
  bool Start(const std::string& path, bool mark_cycles);
  void Stop();
  void Begin(const std::string& tid, const std::string& name);
  void End(const std::string& tid);
  void Instant(const std::string& name);
  // Per-collective span ids (diagnostics cross-rank trace): every rank
  // counts enqueues per tensor name, so "<name>#<count>" is the SAME id
  // the Python layer computes (horovod_tpu/diagnostics/spans.py) — no
  // wire traffic, correlation by construction. NoteEnqueue bumps the
  // counter; Begin/End attach the current span as event args.
  void NoteEnqueue(const std::string& name);
  // Explicit-span instant for the C API (hvd_timeline_mark): the Python
  // enqueue path stamps its span id straight into the engine trace.
  void MarkSpan(const std::string& name, const std::string& span);
  void Close() { Stop(); }

 private:
  struct Event {
    char ph;
    std::string tid, name;
    double ts_us;
    std::string span;  // "" = no args emitted
  };
  std::string SpanLocked(const std::string& name);  // caller holds mu_
  void WriterLoop(FILE* file);
  void StopUnlocked();  // caller holds lifecycle_mu_
  double Now();
  int rank_;
  FILE* file_ = nullptr;
  std::atomic<bool> enabled_{false};
  std::atomic<bool> mark_cycles_{false};
  std::chrono::steady_clock::time_point t0_;
  // lifecycle_mu_ serializes whole Start()/Stop() operations (a concurrent
  // Stop/Start/destructor pair must never join the same writer thread
  // twice or double-close the FILE*); mu_ protects the event queue and is
  // the only lock the hot Begin/End path or the writer ever takes.
  std::mutex lifecycle_mu_;
  std::mutex mu_;  // queue (+ file_ presence check on the event path)
  std::condition_variable cv_;
  std::queue<Event> q_;
  // per-name enqueue counts -> span ids; counted even while disabled so
  // a timeline started mid-run still agrees with the Python layer's
  // per-name counters (both count from process start)
  std::unordered_map<std::string, uint64_t> span_seq_;
  bool closing_ = false;
  std::thread writer_;
};

}  // namespace hvd
