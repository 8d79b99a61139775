#include "core.h"

#include <pthread.h>
#include <unistd.h>

#include <algorithm>
#include <cstring>
#include <sstream>

#include "bayes_opt.h"
#include "logging.h"
#include "timeline.h"
#include "wire.h"

namespace hvd {

namespace {
// Tag space per coordination domain: domain*16 + channel
constexpr int kTagNegotiate = 0;  // worker -> coordinator request lists
constexpr int kTagResponse = 1;   // coordinator -> worker response lists
constexpr int kTagData = 2;       // collective payload (uses +1 too)
constexpr int kTagAdasum = 8;     // VHDD channels [8, 12]
constexpr int kTagBarrier = 13;

int32_t DomTag(int domain, int channel) { return domain * 16 + channel; }

constexpr size_t kAlign = 64;  // fusion alignment (reference common.h:146)
size_t AlignUp(size_t n) { return (n + kAlign - 1) & ~(kAlign - 1); }
}  // namespace

// ---------------------------------------------------------------------------
// TensorQueue (reference: tensor_queue.cc)
// ---------------------------------------------------------------------------

bool TensorQueue::Push(TensorTableEntry entry, Request req) {
  std::lock_guard<std::mutex> lk(mu_);
  if (table_.count(entry.name)) return false;  // reference: DUPLICATE_NAME
  table_[entry.name] = std::move(entry);
  requests_.push_back(std::move(req));
  return true;
}

std::vector<Request> TensorQueue::PopRequests() {
  std::lock_guard<std::mutex> lk(mu_);
  std::vector<Request> out(requests_.begin(), requests_.end());
  requests_.clear();
  return out;
}

bool TensorQueue::Take(const std::string& name, TensorTableEntry* out) {
  std::lock_guard<std::mutex> lk(mu_);
  auto it = table_.find(name);
  if (it == table_.end()) return false;
  *out = std::move(it->second);
  table_.erase(it);
  return true;
}

void TensorQueue::FinalizeAllWithError(const Status& s) {
  std::lock_guard<std::mutex> lk(mu_);
  for (auto& kv : table_)
    if (kv.second.callback) kv.second.callback(s);
  table_.clear();
  requests_.clear();
}

size_t TensorQueue::pending() const {
  std::lock_guard<std::mutex> lk(mu_);
  return table_.size();
}

// ---------------------------------------------------------------------------
// ResponseCache (reference: response_cache.cc)
// ---------------------------------------------------------------------------

std::string ResponseCache::Key(const Request& r) {
  std::ostringstream os;
  os << r.name << '|' << (int)r.type << '|' << (int)r.dtype << '|'
     << (int)r.op << '|' << r.root_rank << '|' << r.prescale << '|'
     << r.postscale << '|' << r.group_id << '|' << r.group_size;
  for (auto d : r.shape) os << ',' << d;
  return os.str();
}

int ResponseCache::Lookup(const std::string& key) const {
  auto it = index_.find(key);
  return it == index_.end() ? -1 : it->second;
}

void ResponseCache::Touch(int bit) {
  auto it = lru_pos_.find(bit);
  if (it == lru_pos_.end()) return;
  lru_.erase(it->second);
  lru_.push_front(bit);
  it->second = lru_.begin();
}

int ResponseCache::Insert(const std::string& key, const Response& resp,
                          Response* evicted, bool* did_evict) {
  auto it = index_.find(key);
  if (it != index_.end()) {
    Touch(it->second);  // coordinated point: refresh recency
    return it->second;
  }
  int bit;
  if (entries_.size() < capacity_) {
    bit = (int)entries_.size();
    entries_.emplace_back(key, resp);
  } else {
    // evict the least-recently-used entry and reuse its bit (reference:
    // response_cache.cc eviction; recency only changes at coordinated
    // points, so every rank evicts the same entry on the same cycle)
    if (capacity_ == 0) return -1;
    bit = lru_.back();
    if (evicted) *evicted = entries_[bit].second;
    if (did_evict) *did_evict = true;
    evictions_++;
    index_.erase(entries_[bit].first);
    lru_.pop_back();
    lru_pos_.erase(bit);
    entries_[bit] = {key, resp};
  }
  index_[key] = bit;
  lru_.push_front(bit);
  lru_pos_[bit] = lru_.begin();
  return bit;
}

const Response& ResponseCache::Get(int bit) const {
  return entries_[bit].second;
}

// ---------------------------------------------------------------------------
// StallInspector (reference: stall_inspector.cc)
// ---------------------------------------------------------------------------

void StallInspector::RecordPending(const std::string& name,
                                   const std::vector<int>& ranks, int size) {
  auto it = pending_.find(name);
  if (it == pending_.end()) {
    pending_[name] = {std::chrono::steady_clock::now(), ranks, false};
  } else {
    it->second.ready_ranks = ranks;
  }
}

void StallInspector::RemoveReady(const std::string& name) {
  pending_.erase(name);
}

std::string StallInspector::Check(double warn_seconds, int* newly_warned,
                                  int* currently_stalled) {
  auto now = std::chrono::steady_clock::now();
  std::ostringstream os;
  int warned = 0, stalled = 0;
  for (auto& kv : pending_) {
    double waited =
        std::chrono::duration<double>(now - kv.second.first_seen).count();
    if (waited <= warn_seconds) continue;
    stalled++;
    if (!kv.second.warned) {
      kv.second.warned = true;
      warned++;
      os << "tensor '" << kv.first << "' stalled " << (int)waited
         << "s; ready ranks: ";
      for (int r : kv.second.ready_ranks) os << r << ' ';
      os << '\n';
    }
  }
  if (newly_warned) *newly_warned = warned;
  if (currently_stalled) *currently_stalled = stalled;
  return os.str();
}

std::vector<StallInspector::PendingEntry> StallInspector::Pending() const {
  auto now = std::chrono::steady_clock::now();
  std::vector<PendingEntry> out;
  out.reserve(pending_.size());
  for (auto& kv : pending_) {
    out.push_back(
        {kv.first,
         std::chrono::duration<double>(now - kv.second.first_seen).count(),
         kv.second.ready_ranks});
  }
  return out;
}

std::vector<std::string> StallInspector::FatallyStalled(
    double shutdown_seconds) {
  std::vector<std::string> out;
  if (shutdown_seconds <= 0) return out;
  auto now = std::chrono::steady_clock::now();
  for (auto& kv : pending_) {
    double waited =
        std::chrono::duration<double>(now - kv.second.first_seen).count();
    if (waited > shutdown_seconds) out.push_back(kv.first);
  }
  return out;
}

// ---------------------------------------------------------------------------
// ParameterManager — GP/expected-improvement Bayesian optimization over
// (log fusion threshold, log cycle time), scored by bytes/sec
// (reference: parameter_manager.h + optim/bayesian_optimization.cc)
// ---------------------------------------------------------------------------

namespace {
// normalized [0,1] <-> parameter ranges (log scale)
constexpr double kFusionLogMin = 20.0;   // 2^20 = 1 MB
constexpr double kFusionLogMax = 28.0;   // 2^28 = 256 MB
constexpr double kCycleLogMin = -1.0;    // 2^-1 = 0.5 ms
constexpr double kCycleLogMax = 3.5;     // 2^3.5 ~= 11 ms

int64_t DenormFusion(double u) {
  return (int64_t)std::pow(
      2.0, kFusionLogMin + u * (kFusionLogMax - kFusionLogMin));
}
double DenormCycle(double u) {
  return std::pow(2.0, kCycleLogMin + u * (kCycleLogMax - kCycleLogMin));
}
double NormFusion(int64_t f) {
  double l = std::log2((double)std::max<int64_t>(f, 1));
  return std::min(1.0, std::max(0.0, (l - kFusionLogMin) /
                                          (kFusionLogMax - kFusionLogMin)));
}
double NormCycle(double c) {
  double l = std::log2(std::max(c, 1e-3));
  return std::min(1.0, std::max(0.0, (l - kCycleLogMin) /
                                          (kCycleLogMax - kCycleLogMin)));
}
}  // namespace

void ParameterManager::Enable(int64_t init_fusion, double init_cycle,
                              int warmup_samples, int max_samples,
                              double gp_noise,
                              const std::string& log_path,
                              double window_secs, bool allow_hier) {
  enabled_ = true;
  allow_hier_ = allow_hier;
  warmup_samples_ = warmup_samples;
  max_samples_ = max_samples;
  gp_noise_ = gp_noise;
  window_secs_ = window_secs;
  // sample trace (reference: HOROVOD_AUTOTUNE_LOG, parameter_manager.cc
  // writes a CSV of tried parameters and scores)
  if (log_) {
    fclose(log_);  // elastic re-init: close the previous generation's file
    log_ = nullptr;
  }
  if (!log_path.empty()) log_ = fopen(log_path.c_str(), "w");
  if (log_)
    fprintf(log_,
            "sample,fusion_bytes,cycle_ms,hierarchical,cache,"
            "bytes_per_sec\n");
  // 4-D space: (log fusion, log cycle, hierarchical, cache) — the
  // categorical dims the reference's ParameterManager also explores
  // (parameter_manager.h:42-105)
  bo_ = std::make_shared<BayesianOptimizer>(4, 17, gp_noise_);
  window_start_ = std::chrono::steady_clock::now();
}

void ParameterManager::Record(int64_t bytes) { bytes_acc_ += bytes; }

bool ParameterManager::Tune(int64_t* fusion_bytes, double* cycle_ms,
                            bool* hierarchical, bool* cache_enabled) {
  if (!enabled_) return false;
  auto now = std::chrono::steady_clock::now();
  double secs = std::chrono::duration<double>(now - window_start_).count();
  if (secs < window_secs_) return false;  // scoring window (seconds)
  double score = bytes_acc_ / secs;
  bytes_acc_ = 0;
  window_start_ = now;
  samples_++;
  if (log_) {
    fprintf(log_, "%d,%lld,%g,%d,%d,%g\n", samples_,
            (long long)*fusion_bytes, *cycle_ms, *hierarchical ? 1 : 0,
            *cache_enabled ? 1 : 0, score);
    fflush(log_);
  }
  // discard warmup samples (reference: AUTOTUNE_WARMUP_SAMPLES) so
  // startup transients don't poison the GP
  if (samples_ <= warmup_samples_) return false;
  bo_->AddSample({NormFusion(*fusion_bytes), NormCycle(*cycle_ms),
                  *hierarchical ? 1.0 : 0.0, *cache_enabled ? 1.0 : 0.0},
                 score);
  std::vector<double> x;
  if (samples_ > warmup_samples_ + max_samples_) {  // converge to best
    x = bo_->BestSample();
    enabled_ = false;
  } else {
    x = bo_->NextSample();
  }
  *fusion_bytes = DenormFusion(x[0]);
  *cycle_ms = DenormCycle(x[1]);
  *hierarchical = allow_hier_ && x[2] >= 0.5;
  *cache_enabled = x[3] >= 0.5;
  return true;
}

// ---------------------------------------------------------------------------
// Core
// ---------------------------------------------------------------------------

Core& Core::Get() {
  static Core core;
  return core;
}

Core::~Core() { Shutdown(); }

int Core::NewHandle(TensorTableEntry*) {
  int h = next_handle_.fetch_add(1);
  auto hs = std::make_shared<HandleState>();
  std::lock_guard<std::mutex> lk(handles_mu_);
  handles_[h] = hs;
  return h;
}

std::shared_ptr<Core::HandleState> Core::GetHandle(int h) {
  std::lock_guard<std::mutex> lk(handles_mu_);
  auto it = handles_.find(h);
  return it == handles_.end() ? nullptr : it->second;
}

void Core::PushToDomain(int domain, TensorTableEntry e, Request r) {
  // span bookkeeping FIRST, before any rejection path: the Python layer
  // allocates its span id per eager call unconditionally (spans.py), so
  // the engine must count every attempt too — a DUPLICATE_NAME
  // rejection that only one side counted would desynchronize the two
  // per-name counters for the rest of the run.  Internal names
  // (__barrier__/__join__, _hvd.* plumbing like the clock-sync
  // allgathers) never get Python-side spans and are excluded.
  if (timeline_ && e.name.rfind("__", 0) != 0 &&
      e.name.rfind("_hvd.", 0) != 0)
    timeline_->NoteEnqueue(e.name);
  if (loop_done_.load()) {
    if (e.callback)
      e.callback(Status::Aborted(
          loop_error_.empty()
              ? "hvdcore background loop is not running"
              : "hvdcore background loop is not running: " + loop_error_));
    return;
  }
  std::lock_guard<std::mutex> lk(domains_mu_);
  // re-check under the same lock the dying loop's finalize pass takes:
  // an entry pushed after that pass would otherwise never resolve (its
  // waiter would hang — exactly the failure mode this PR hunts)
  if (loop_done_.load()) {
    if (e.callback)
      e.callback(Status::Aborted(
          loop_error_.empty()
              ? "hvdcore background loop is not running"
              : "hvdcore background loop is not running: " + loop_error_));
    return;
  }
  auto it = domains_.find(domain);
  if (it == domains_.end()) {
    if (e.callback)
      e.callback(Status::Error("unknown process set / coordination domain"));
    return;
  }
  if (it->second->group.my_index < 0) {
    if (e.callback)
      e.callback(Status::Error(
          "this rank is not a member of the process set"));
    return;
  }
  auto cb = e.callback;
  std::string name = e.name;
  if (!it->second->queue.Push(std::move(e), std::move(r))) {
    if (cb)
      cb(Status::Error("duplicate tensor name submitted before previous "
                       "operation on '" + name + "' completed (reference: "
                       "DUPLICATE_NAME error)"));
    return;
  }
  KickCycle();
}

void Core::KickCycle() {
  {
    std::lock_guard<std::mutex> lk(cycle_mu_);
    cycle_kick_ = true;
  }
  cycle_cv_.notify_one();
}

Status Core::Init(const CoreConfig& cfg) {
  if (initialized_) return Status::OK();
  cfg_ = cfg;
  loop_error_.clear();  // a prior generation's exit cause is not ours
  LogRank() = cfg.rank;  // stamp every later log line with our rank
  HVD_LOG(Info) << "core init: size=" << cfg.size << " coordinator="
                << cfg.coord_addr << ":" << cfg.coord_port
                << " fusion=" << cfg.fusion_threshold
                << "B cycle=" << cfg.cycle_time_ms << "ms";
  transport_.reset(
      new Transport(cfg.rank, cfg.size, cfg.coord_addr, cfg.coord_port,
                    cfg.rendezvous_timeout_secs,
                    cfg.transport_timeout_secs,
                    cfg.wire_checksum));
  // fresh transport, fresh per-life counters: re-baseline the mirror
  // so counters_ keeps accumulating instead of absorbing a reset-to-0
  seen_transport_chaos_ = 0;
  seen_transport_checksum_ = 0;
  auto st = transport_->Init();
  if (!st.ok()) return st;
  timeline_.reset(new Timeline(cfg.rank, cfg.timeline_path,
                               cfg.timeline_mark_cycles));
  if (cfg.autotune)
    param_mgr_.Enable(cfg.fusion_threshold, cfg.cycle_time_ms,
                      cfg.autotune_warmup_samples,
                      cfg.autotune_max_samples, cfg.autotune_gp_noise,
                      // only the coordinator tunes (Tune() is rank-0-
                      // gated); a worker opening the same path would
                      // truncate the coordinator's trace on shared
                      // filesystems
                      cfg.rank == 0 ? cfg.autotune_log : std::string(),
                      cfg.autotune_window_secs,
                      /*allow_hier=*/cfg.local_size > 1 &&
                          cfg.size == cfg.local_size * cfg.cross_size);

  auto global = std::unique_ptr<CoordDomain>(new CoordDomain());
  global->id = 0;
  global->group.ranks.resize(cfg.size);
  for (int i = 0; i < cfg.size; ++i) global->group.ranks[i] = i;
  global->group.my_index = cfg.rank;
  global->cache.reset(new ResponseCache(cfg.cache_capacity));
  global->joined_ranks.assign(cfg.size, false);
  {
    std::lock_guard<std::mutex> lk(domains_mu_);
    domains_[0] = std::move(global);
  }
  // hierarchical allreduce topology (reference enables it only on
  // homogeneous clusters — operations.cc:514-538)
  hier_topology_ok_ = cfg.local_size > 1 &&
                      cfg.size == cfg.local_size * cfg.cross_size;
  hier_enabled_ = cfg.hierarchical_allreduce && hier_topology_ok_;
  hier_ag_enabled_ = cfg.hierarchical_allgather && hier_topology_ok_;
  if (hier_topology_ok_) {
    local_group_.ranks.clear();
    for (int i = 0; i < cfg.local_size; ++i)
      local_group_.ranks.push_back(cfg.cross_rank * cfg.local_size + i);
    local_group_.my_index = cfg.local_rank;
    cross_group_.ranks.clear();
    for (int i = 0; i < cfg.cross_size; ++i)
      cross_group_.ranks.push_back(i * cfg.local_size);
    cross_group_.my_index = cfg.cross_rank;
  }
  shutdown_requested_ = false;
  loop_done_ = false;
  last_straggler_report_ = std::chrono::steady_clock::now();
  initialized_ = true;
  loop_ = std::thread([this] { Loop(); });
  HVD_LOG(Debug) << "background loop started"
                 << (hier_enabled_ ? " (hierarchical allreduce on)" : "");
  return Status::OK();
}

void Core::Shutdown(bool force) {
  if (!initialized_) return;
  HVD_LOG(Info) << "core shutdown requested" << (force ? " (forced)" : "");
  shutdown_requested_ = true;
  KickCycle();  // cast the shutdown vote without waiting out a cycle
  // Prefer the negotiated shutdown (all ranks vote, coordinator emits a
  // SHUTDOWN response — reference: operations.cc:994-1005); if a peer died
  // mid-collective the loop may be blocked in Recv, so force-close the
  // transport after a grace period to unblock it. force=true skips the
  // grace entirely — the caller KNOWS a peer is dead (elastic in-place
  // shrink), so consensus can never complete and waiting 10s per
  // survivor would just stall the re-rendezvous.
  for (int i = 0; !force && i < 100 && !loop_done_.load(); ++i)
    std::this_thread::sleep_for(std::chrono::milliseconds(100));
  if (!loop_done_.load() && transport_) transport_->Shutdown();
  if (loop_.joinable()) loop_.join();
  {
    std::lock_guard<std::mutex> lk(domains_mu_);
    for (auto& kv : domains_)
      kv.second->queue.FinalizeAllWithError(
          Status::Aborted("hvdcore shut down"));
  }
  if (timeline_) timeline_->Close();
  if (transport_) transport_->Shutdown();
  initialized_ = false;
}

// -- enqueue ----------------------------------------------------------------

int Core::EnqueueAllreduce(int domain, const std::string& name,
                           const void* in, void* out, DataType dt,
                           const std::vector<int64_t>& shape, ReduceOp op,
                           double prescale, double postscale,
                           int group_id, int group_size) {
  int h = NewHandle(nullptr);
  auto hs = GetHandle(h);
  TensorTableEntry e;
  e.name = name;
  e.type = Request::kAllreduce;
  e.input = in;
  e.output = out;
  e.dtype = dt;
  e.shape = shape;
  e.op = op;
  e.prescale = prescale;
  e.postscale = postscale;
  e.callback = [hs](const Status& s) {
    std::lock_guard<std::mutex> lk(hs->mu);
    hs->status = s;
    hs->done = true;
    hs->cv.notify_all();
  };
  Request r;
  r.type = Request::kAllreduce;
  r.rank = cfg_.rank;
  r.name = name;
  r.dtype = dt;
  r.shape = shape;
  r.op = op;
  r.prescale = prescale;
  r.postscale = postscale;
  r.group_id = group_id;
  r.group_size = group_size;
  PushToDomain(domain, std::move(e), std::move(r));
  return h;
}

int Core::EnqueueAllgather(int domain, const std::string& name,
                           const void* in, DataType dt,
                           const std::vector<int64_t>& shape) {
  int h = NewHandle(nullptr);
  auto hs = GetHandle(h);
  TensorTableEntry e;
  e.name = name;
  e.type = Request::kAllgather;
  e.input = in;
  e.dtype = dt;
  e.shape = shape;
  e.result = std::make_shared<std::vector<uint8_t>>();
  e.result_shape = std::make_shared<std::vector<int64_t>>();
  e.callback = [hs](const Status& s) {
    std::lock_guard<std::mutex> lk(hs->mu);
    hs->status = s;
    hs->done = true;
    hs->cv.notify_all();
  };
  // share the result buffers with the handle so Execute's writes are
  // visible through the handle-query API
  hs->entry = e;
  Request r;
  r.type = Request::kAllgather;
  r.rank = cfg_.rank;
  r.name = name;
  r.dtype = dt;
  r.shape = shape;
  PushToDomain(domain, std::move(e), std::move(r));
  return h;
}

int Core::EnqueueBroadcast(int domain, const std::string& name,
                           const void* in, void* out, int root, DataType dt,
                           const std::vector<int64_t>& shape) {
  int h = NewHandle(nullptr);
  auto hs = GetHandle(h);
  TensorTableEntry e;
  e.name = name;
  e.type = Request::kBroadcast;
  e.input = in;
  e.output = out;
  e.root_rank = root;
  e.dtype = dt;
  e.shape = shape;
  e.callback = [hs](const Status& s) {
    std::lock_guard<std::mutex> lk(hs->mu);
    hs->status = s;
    hs->done = true;
    hs->cv.notify_all();
  };
  Request r;
  r.type = Request::kBroadcast;
  r.rank = cfg_.rank;
  r.name = name;
  r.dtype = dt;
  r.shape = shape;
  r.root_rank = root;
  PushToDomain(domain, std::move(e), std::move(r));
  return h;
}

int Core::EnqueueAlltoall(int domain, const std::string& name,
                          const void* in, const std::vector<int64_t>& splits,
                          DataType dt, const std::vector<int64_t>& shape) {
  int h = NewHandle(nullptr);
  auto hs = GetHandle(h);
  TensorTableEntry e;
  e.name = name;
  e.type = Request::kAlltoall;
  e.input = in;
  e.dtype = dt;
  e.shape = shape;
  e.splits = splits;
  e.result = std::make_shared<std::vector<uint8_t>>();
  e.result_shape = std::make_shared<std::vector<int64_t>>();
  e.recv_splits = std::make_shared<std::vector<int64_t>>();
  e.callback = [hs](const Status& s) {
    std::lock_guard<std::mutex> lk(hs->mu);
    hs->status = s;
    hs->done = true;
    hs->cv.notify_all();
  };
  hs->entry = e;
  Request r;
  r.type = Request::kAlltoall;
  r.rank = cfg_.rank;
  r.name = name;
  r.dtype = dt;
  r.shape = shape;
  PushToDomain(domain, std::move(e), std::move(r));
  return h;
}

int Core::EnqueueJoin(int domain) {
  int h = NewHandle(nullptr);
  auto hs = GetHandle(h);
  TensorTableEntry e;
  e.name = "__join__";
  e.type = Request::kJoin;
  e.callback = [hs](const Status& s) {
    std::lock_guard<std::mutex> lk(hs->mu);
    hs->status = s;
    hs->done = true;
    hs->cv.notify_all();
  };
  Request r;
  r.type = Request::kJoin;
  r.rank = cfg_.rank;
  r.name = "__join__";
  PushToDomain(domain, std::move(e), std::move(r));
  return h;
}

Status Core::ExecBarrier(int domain) {
  int h = NewHandle(nullptr);
  auto hs = GetHandle(h);
  TensorTableEntry e;
  e.name = "__barrier__";
  e.type = Request::kBarrier;
  e.callback = [hs](const Status& s) {
    std::lock_guard<std::mutex> lk(hs->mu);
    hs->status = s;
    hs->done = true;
    hs->cv.notify_all();
  };
  Request r;
  r.type = Request::kBarrier;
  r.rank = cfg_.rank;
  r.name = "__barrier__";
  PushToDomain(domain, std::move(e), std::move(r));
  auto st = WaitHandle(h, 600.0);
  FreeHandle(h);
  return st;
}

// -- handles ----------------------------------------------------------------

bool Core::Poll(int h) {
  auto hs = GetHandle(h);
  if (!hs) return true;
  std::lock_guard<std::mutex> lk(hs->mu);
  return hs->done;
}

Status Core::WaitHandle(int h, double timeout_s) {
  auto hs = GetHandle(h);
  if (!hs) return Status::Error("unknown handle");
  std::unique_lock<std::mutex> lk(hs->mu);
  if (!hs->cv.wait_for(lk, std::chrono::duration<double>(timeout_s),
                       [&] { return hs->done; }))
    return Status{StatusType::kInProgress, "timeout waiting for collective"};
  return hs->status;
}

std::vector<int64_t> Core::ResultShape(int h) {
  auto hs = GetHandle(h);
  if (!hs || !hs->entry.result_shape) return {};
  std::lock_guard<std::mutex> lk(hs->mu);
  return *hs->entry.result_shape;
}

std::vector<int64_t> Core::RecvSplits(int h) {
  auto hs = GetHandle(h);
  if (!hs || !hs->entry.recv_splits) return {};
  std::lock_guard<std::mutex> lk(hs->mu);
  return *hs->entry.recv_splits;
}

Status Core::CopyResult(int h, void* dst, int64_t max_bytes) {
  auto hs = GetHandle(h);
  if (!hs) return Status::Error("unknown handle");
  std::lock_guard<std::mutex> lk(hs->mu);
  if (!hs->entry.result) return Status::Error("handle has no result buffer");
  int64_t n = std::min<int64_t>(max_bytes, hs->entry.result->size());
  memcpy(dst, hs->entry.result->data(), n);
  return Status::OK();
}

void Core::FreeHandle(int h) {
  std::lock_guard<std::mutex> lk(handles_mu_);
  handles_.erase(h);
}

// -- process sets -----------------------------------------------------------

int Core::AddProcessSet(const std::vector<int>& ranks) {
  std::lock_guard<std::mutex> lk(domains_mu_);
  int id = next_domain_++;
  auto d = std::unique_ptr<CoordDomain>(new CoordDomain());
  d->id = id;
  d->group.ranks = ranks;
  std::sort(d->group.ranks.begin(), d->group.ranks.end());
  auto it = std::find(d->group.ranks.begin(), d->group.ranks.end(),
                      cfg_.rank);
  d->group.my_index = it == d->group.ranks.end()
                          ? -1
                          : (int)(it - d->group.ranks.begin());
  d->cache.reset(new ResponseCache(cfg_.cache_capacity));
  d->joined_ranks.assign(d->group.ranks.size(), false);
  // Multi-process: the set stays INACTIVE (no lockstep negotiation rounds)
  // until the domain-0 coordinator confirms every rank registered it; a
  // member cycling a set its peers don't know yet would withhold its
  // domain-0 traffic and deadlock the whole mesh (reference coordinates
  // dynamic registration through the background thread the same way,
  // operations.cc:587-623). Submissions queue and run on activation.
  d->active = cfg_.size <= 1;
  d->registered_at = std::chrono::steady_clock::now();
  domains_[id] = std::move(d);
  return id;
}

void Core::RemoveProcessSet(int id) {
  std::lock_guard<std::mutex> lk(domains_mu_);
  if (id == 0) return;
  auto it = domains_.find(id);
  if (it == domains_.end()) return;
  if (cfg_.size <= 1) {
    domains_.erase(it);
    return;
  }
  // Multi-process: ALWAYS go through retire consensus — even for a
  // still-inactive set. Erasing an inactive set locally races the
  // activation broadcast (this rank may already have announced it; the
  // coordinator could activate it this very cycle, and peers would then
  // block on a member that no longer has the domain). Retiring stops the
  // announcements, so an inactive set simply never activates and is erased
  // everywhere once every rank votes.
  it->second->retiring = true;
}

int Core::last_join_rank(int domain) {
  std::lock_guard<std::mutex> lk(domains_mu_);
  auto it = domains_.find(domain);
  return it == domains_.end() ? -1 : it->second->join_count;
}

// -- dynamic timeline (reference: operations.cc:1011-1041) ------------------

Status Core::StartTimeline(const std::string& path, bool mark_cycles) {
  if (!initialized_ || !timeline_)
    return Status::Error("hvdcore not initialized");
  if (!timeline_->Start(path, mark_cycles))
    return Status::Error("could not open timeline file: " + path);
  return Status::OK();
}

Status Core::StopTimeline() {
  if (!initialized_ || !timeline_)
    return Status::Error("hvdcore not initialized");
  timeline_->Stop();
  return Status::OK();
}

// -- background loop (reference: BackgroundThreadLoop / RunLoopOnce) --------

void Core::Loop() {
  if (cfg_.thread_affinity >= 0) {
    // pin the background loop (reference: HOROVOD_THREAD_AFFINITY)
    cpu_set_t cpus;
    CPU_ZERO(&cpus);
    long ncpu = sysconf(_SC_NPROCESSORS_ONLN);
    CPU_SET(cfg_.thread_affinity % std::max(1L, ncpu), &cpus);
    pthread_setaffinity_np(pthread_self(), sizeof(cpus), &cpus);
  }
  while (RunOnce()) {
    // idle-poll at the (autotunable) cycle time, but wake immediately on
    // a fresh enqueue — a lone eager op should pay the negotiation RTT,
    // not the poll latency
    std::unique_lock<std::mutex> lk(cycle_mu_);
    cycle_cv_.wait_for(
        lk, std::chrono::duration<double, std::milli>(cfg_.cycle_time_ms),
        [this] { return cycle_kick_; });
    cycle_kick_ = false;
  }
  MirrorTransportCounters();
  loop_done_ = true;
  // Abnormal exits (peer death mid-collective) leave waiters pending —
  // finalize them with the real error instead of letting them time out
  // (reference: operations.cc finalizes the tensor queue at shutdown).
  std::string why = loop_error_.empty()
      ? "hvdcore background loop terminated (peer failure or shutdown)"
      : "hvdcore background loop terminated: " + loop_error_;
  if (!loop_error_.empty()) {
    HVD_LOG(Error) << "background loop exiting: " << loop_error_;
  }
  std::lock_guard<std::mutex> lk(domains_mu_);
  for (auto& kv : domains_)
    kv.second->queue.FinalizeAllWithError(Status::Aborted(why));
}

namespace {
// negotiation-phase names (reference: timeline.h NEGOTIATING state +
// activity classes common.h:73-105)
const char* NegotiatePhase(Request::Type t) {
  switch (t) {
    case Request::kAllreduce: return "NEGOTIATE_ALLREDUCE";
    case Request::kAllgather: return "NEGOTIATE_ALLGATHER";
    case Request::kBroadcast: return "NEGOTIATE_BROADCAST";
    case Request::kAlltoall: return "NEGOTIATE_ALLTOALL";
    case Request::kBarrier: return "NEGOTIATE_BARRIER";
    default: return "NEGOTIATE";
  }
}
}  // namespace

void Core::HandleRequests(CoordDomain& d, int from_rank,
                          std::vector<Request>& reqs) {
  int gsize = d.group.size();
  for (auto& r : reqs) {
    if (r.type == Request::kJoin) {
      int idx = (int)(std::find(d.group.ranks.begin(), d.group.ranks.end(),
                                from_rank) -
                      d.group.ranks.begin());
      if (!d.joined_ranks[idx]) {
        d.joined_ranks[idx] = true;
        d.join_count = from_rank;  // last joiner (reference: join returns it)
      }
      continue;
    }
    // Keyed by NAME (reference: controller.cc IncrementTensorCount) —
    // allgather ranks legitimately differ in dim 0.
    auto& slot = d.ready_table_[r.name];
    if (slot.second.empty()) {
      slot.first = r;
      d.announce_time_[r.name] = std::chrono::steady_clock::now();
      // per-tensor negotiation phase opens at the FIRST announcement and
      // closes when all ranks are in (CollectReady) — the coordinator's
      // view of who is holding whom up (reference: timeline.h:48-183)
      if (timeline_ && timeline_->enabled())
        timeline_->Begin(r.name, NegotiatePhase(r.type));
    } else {
      // duplicate announcement from the same rank must not count twice
      if (std::find(slot.second.begin(), slot.second.end(), from_rank) !=
          slot.second.end())
        continue;
      // validate agreement (reference: ConstructResponse mismatch errors)
      const Request& first = slot.first;
      bool mismatch = first.dtype != r.dtype || first.type != r.type ||
                      (int)first.op != (int)r.op ||
                      first.group_id != r.group_id ||
                      first.group_size != r.group_size;
      if (!mismatch && r.type == Request::kAllreduce &&
          first.shape != r.shape)
        mismatch = true;
      if (!mismatch && r.type != Request::kAllreduce) {
        if (first.shape.size() != r.shape.size()) {
          mismatch = true;  // ndim must agree even when dim 0 is ragged
        } else {
          for (size_t k = 1; k < r.shape.size(); ++k)
            if (first.shape[k] != r.shape[k]) mismatch = true;
        }
      }
      if (mismatch)
        d.error_table_[r.name] =
            "mismatched dtype/shape/op for tensor '" + r.name + "'";
    }
    slot.second.push_back(from_rank);
  }
  (void)gsize;
}

void Core::HandleCacheBits(CoordDomain& d, int from_rank,
                           const std::vector<int32_t>& bits) {
  for (auto b : bits) {
    auto& ranks = d.bit_ready_[b];
    if (ranks.empty())
      d.bit_time_[b] = std::chrono::steady_clock::now();
    if (ranks.empty() && timeline_ && timeline_->enabled()) {
      // cached tensors skip negotiation; the wait for the remaining
      // ranks' bits is still visible (reference activity name:
      // WAIT_FOR_OTHER_TENSOR_DATA, common.h:76)
      const Response& cr = d.cache->Get(b);
      if (!cr.names.empty())
        timeline_->Begin(cr.names[0], "WAIT_FOR_OTHER_TENSOR_DATA");
    }
    ranks.push_back(from_rank);
  }
}

std::vector<Response> Core::CollectReady(CoordDomain& d) {
  // A tensor/bit is ready when every non-joined rank announced it
  // (reference: controller.cc IncrementTensorCount).
  int needed = 0;
  for (size_t i = 0; i < d.joined_ranks.size(); ++i)
    if (!d.joined_ranks[i]) needed++;
  auto now = std::chrono::steady_clock::now();
  // negotiation wait = first announce -> all in, charged to the LAST
  // announcing rank — the one everyone else waited on
  auto charge = [&](const std::vector<int>& ranks,
                    std::chrono::steady_clock::time_point first_seen) {
    if (ranks.empty()) return;
    ChargeStraggler(
        ranks.back(),
        std::chrono::duration<double>(now - first_seen).count());
  };

  std::vector<Response> out;
  // 1) steady-state fast path: common cache bits, ascending (identical
  //    caches on every rank → identical responses)
  std::vector<int> ready_bits;
  for (auto it = d.bit_ready_.begin(); it != d.bit_ready_.end();) {
    if ((int)it->second.size() >= needed && needed > 0) {
      ready_bits.push_back(it->first);
      auto ts = d.bit_time_.find(it->first);
      if (ts != d.bit_time_.end()) {
        charge(it->second, ts->second);
        d.bit_time_.erase(ts);
      }
      it = d.bit_ready_.erase(it);
    } else {
      ++it;
    }
  }
  std::sort(ready_bits.begin(), ready_bits.end());
  for (int b : ready_bits) {
    Response resp = d.cache->Get(b);
    resp.from_cache = true;
    if (!resp.names.empty()) {
      d.stall.RemoveReady(resp.names[0]);
      if (timeline_ && timeline_->enabled())
        timeline_->End(resp.names[0]);  // closes WAIT_FOR_OTHER_TENSOR_DATA
    }
    out.push_back(std::move(resp));
  }
  // partial cache bits are stalls too: without this, a cached tensor one
  // rank stops submitting would evade the stall inspector entirely
  for (auto& kv : d.bit_ready_) {
    const Response& r = d.cache->Get(kv.first);
    if (!r.names.empty())
      d.stall.RecordPending(r.names[0], kv.second, d.group.size());
  }

  // 2) negotiated tensors
  std::vector<std::pair<std::string, Request>> ready;
  for (auto it = d.ready_table_.begin(); it != d.ready_table_.end();) {
    if ((int)it->second.second.size() >= needed && needed > 0) {
      ready.emplace_back(it->first, it->second.first);
      d.stall.RemoveReady(it->second.first.name);
      auto ts = d.announce_time_.find(it->first);
      if (ts != d.announce_time_.end()) {
        charge(it->second.second, ts->second);
        d.announce_time_.erase(ts);
      }
      it = d.ready_table_.erase(it);
    } else {
      d.stall.RecordPending(it->second.first.name, it->second.second,
                            d.group.size());
      ++it;
    }
  }
  std::sort(ready.begin(), ready.end(),
            [](auto& a, auto& b) { return a.first < b.first; });
  for (auto& kv : ready) {
    auto& r = kv.second;
    if (timeline_ && timeline_->enabled())
      timeline_->End(r.name);  // closes the NEGOTIATE_* phase
    auto err = d.error_table_.find(r.name);
    bool poisoned = r.group_id >= 0 &&
                    d.poisoned_groups_.count(r.group_id) > 0;
    if (err != d.error_table_.end() || poisoned) {
      Response resp;
      resp.type = Response::kError;
      resp.names = {r.name};
      resp.error_message = err != d.error_table_.end()
                               ? err->second
                               : "another member of this tensor group "
                                 "failed";
      if (err != d.error_table_.end()) d.error_table_.erase(err);
      // error in a group: fail the held members too so no handle waits
      // forever
      if (r.group_id >= 0) {
        d.poisoned_groups_.insert(r.group_id);
        auto git = d.groups_.find(r.group_id);
        if (git != d.groups_.end()) {
          for (auto& held : git->second.second) {
            Response e2;
            e2.type = Response::kError;
            e2.names = held.names;
            e2.error_message = resp.error_message;
            out.push_back(std::move(e2));
          }
          d.groups_.erase(git);
        }
      }
      out.push_back(std::move(resp));
      continue;
    }
    Response resp;
    resp.type = (Response::Type)r.type;
    resp.names = {r.name};
    resp.dtypes = {r.dtype};
    resp.shapes = {r.shape};
    resp.root_rank = r.root_rank;
    resp.op = r.op;
    resp.prescale = r.prescale;
    resp.postscale = r.postscale;
    resp.group_id = r.group_id;
    resp.group_size = r.group_size;
    if (r.type == Request::kAllreduce && r.group_id >= 0) {
      // hold back until the whole group is ready (group-COMPLETE
      // negotiation; reference: GroupTable readiness,
      // controller.cc:207-231). Fusion still bounds unit sizes.
      auto& slot = d.groups_[r.group_id];
      if (slot.first == 0) slot.first = r.group_size;
      slot.second.push_back(std::move(resp));
      if ((int)slot.second.size() >= slot.first && slot.first > 0) {
        std::sort(slot.second.begin(), slot.second.end(),
                  [](const Response& a, const Response& b) {
                    return a.names[0] < b.names[0];
                  });
        for (auto& gr : slot.second) out.push_back(std::move(gr));
        d.groups_.erase(r.group_id);
        d.poisoned_groups_.erase(r.group_id);
      }
      continue;
    }
    out.push_back(std::move(resp));
  }

  // all ranks joined → emit Join response and reset
  bool all_joined =
      !d.joined_ranks.empty() &&
      std::all_of(d.joined_ranks.begin(), d.joined_ranks.end(),
                  [](bool b) { return b; });
  if (all_joined) {
    Response resp;
    resp.type = Response::kJoin;
    resp.last_joined_rank = d.join_count;
    out.push_back(resp);
    std::fill(d.joined_ranks.begin(), d.joined_ranks.end(), false);
  }
  return out;
}

std::vector<Response> Core::FuseResponses(
    const std::vector<Response>& singles) {
  std::vector<Response> out;
  std::map<std::string, Response> open;  // fuse-group key -> accumulating
  std::map<std::string, int64_t> open_bytes;
  for (auto& s : singles) {
    std::string key;
    if (s.type == Response::kAllreduce) {
      std::ostringstream gk;
      gk << "ar|" << (int)s.dtypes[0] << '|' << (int)s.op << '|'
         << s.prescale << '|' << s.postscale;
      if (cfg_.disable_group_fusion)
        gk << "|g" << s.group_id;  // keep groups (and loose tensors) apart
      key = gk.str();
    } else if (s.type == Response::kAllgather) {
      // fused allgathers share one size-exchange + one data round with
      // per-tensor displacement math (reference: controller.cc:793 fuses
      // allgathers; ops/collective_operations.h:209-273); embedding-heavy
      // steps gather many small tensors per cycle
      std::ostringstream gk;
      gk << "ag|" << (int)s.dtypes[0];
      if (cfg_.disable_group_fusion)
        gk << "|g" << s.group_id;  // keep groups (and loose tensors) apart
      key = gk.str();
    } else {
      out.push_back(s);
      continue;
    }
    int64_t sz = DataTypeSize(s.dtypes[0]);
    for (auto dim : s.shapes[0]) sz *= dim;
    auto it = open.find(key);
    if (it != open.end() &&
        open_bytes[key] + sz > cfg_.fusion_threshold) {
      out.push_back(std::move(it->second));
      open.erase(it);
      open_bytes.erase(key);
      it = open.end();
    }
    if (it == open.end()) {
      open[key] = s;
      open_bytes[key] = sz;
    } else {
      it->second.names.push_back(s.names[0]);
      it->second.dtypes.push_back(s.dtypes[0]);
      it->second.shapes.push_back(s.shapes[0]);
      open_bytes[key] += sz;
    }
  }
  for (auto& kv : open) out.push_back(std::move(kv.second));
  return out;
}

namespace {
hvd::Request RequestFromSingleResponse(const hvd::Response& r) {
  // must mirror the Request an announcing rank would send for this op
  hvd::Request q;
  q.type = hvd::Request::kAllreduce;
  q.name = r.names[0];
  q.dtype = r.dtypes[0];
  q.shape = r.shapes[0];
  q.op = r.op;
  q.prescale = r.prescale;
  q.postscale = r.postscale;
  q.root_rank = 0;
  q.group_id = r.group_id;
  q.group_size = r.group_size;
  return q;
}

std::string KeyFromSingleResponse(const hvd::Response& r) {
  // must match ResponseCache::Key(Request) for an allreduce request
  return hvd::ResponseCache::Key(RequestFromSingleResponse(r));
}
}  // namespace

namespace {
uint64_t HashRanks(const std::vector<int>& ranks) {
  uint64_t h = 1469598103934665603ull;  // FNV-1a
  for (int r : ranks) {
    h ^= (uint64_t)(uint32_t)r;
    h *= 1099511628211ull;
  }
  return h;
}
}  // namespace

void Core::ApplyDomainLifecycle(const std::vector<int32_t>& activate,
                                const std::vector<int32_t>& retired) {
  std::lock_guard<std::mutex> lk(domains_mu_);
  for (auto id : activate) {
    auto it = domains_.find(id);
    if (it != domains_.end()) it->second->active = true;
  }
  for (auto id : retired) {
    auto it = domains_.find(id);
    if (it != domains_.end()) {
      it->second->queue.FinalizeAllWithError(
          Status::Aborted("process set removed"));
      domains_.erase(it);
    }
  }
}

// Mirror the transport's chaos-injection and checksum-failure counts
// into the long-lived Counters struct: only the loop thread may touch
// transport_ (the metrics scraper reads counters_ concurrently with
// elastic re-init).  Deltas, not absolute stores — a checksum failure
// tears its transport down, and the replacement transport's 0 must not
// erase the recorded evidence (Init re-baselines seen_*).
void Core::MirrorTransportCounters() {
  if (!transport_) return;
  uint64_t chaos = transport_->chaos_injected();
  if (chaos > seen_transport_chaos_) {
    counters_.transport_chaos_injected.fetch_add(
        chaos - seen_transport_chaos_, std::memory_order_relaxed);
    seen_transport_chaos_ = chaos;
  }
  uint64_t ck = transport_->checksum_failures();
  if (ck > seen_transport_checksum_) {
    counters_.transport_checksum_failures.fetch_add(
        ck - seen_transport_checksum_, std::memory_order_relaxed);
    seen_transport_checksum_ = ck;
  }
}

bool Core::RunOnce() {
  MirrorTransportCounters();
  bool want_shutdown = shutdown_requested_.load();
  counters_.cycles++;
  if (timeline_ && timeline_->enabled() && timeline_->mark_cycles())
    timeline_->Instant("CYCLE_START");  // HOROVOD_TIMELINE_MARK_CYCLES

  std::vector<int> domain_ids;
  std::vector<wire::DomainAnnounce> my_announce;
  std::vector<int32_t> my_retire;
  {
    std::lock_guard<std::mutex> lk(domains_mu_);
    for (auto& kv : domains_) {
      domain_ids.push_back(kv.first);
      CoordDomain* cd = kv.second.get();
      if (cd->retiring) {
        my_retire.push_back(kv.first);
      } else if (!cd->active) {
        wire::DomainAnnounce a;
        a.id = kv.first;
        a.ranks_hash = HashRanks(cd->group.ranks);
        my_announce.push_back(a);
        if (!cd->inactive_warned && cd->queue.pending() > 0 &&
            std::chrono::duration<double>(std::chrono::steady_clock::now() -
                                          cd->registered_at)
                    .count() > cfg_.stall_warning_secs) {
          HVD_LOG(Warning)
              << "collectives pending on process set " << kv.first
              << " which not all ranks have registered after "
              << cfg_.stall_warning_secs << "s";
          cd->inactive_warned = true;
        }
      }
    }
  }

  bool got_shutdown_response = false;
  int cycle_stalled = 0;  // tensors past the warn threshold this cycle
  for (int id : domain_ids) {
    CoordDomain* d;
    {
      std::lock_guard<std::mutex> lk(domains_mu_);
      auto it = domains_.find(id);
      if (it == domains_.end()) continue;  // retired during this cycle
      d = it->second.get();
      // re-read under the lock: the domain-0 phase of THIS cycle may have
      // just activated it (every rank then activates in the same cycle, so
      // all members enter its first negotiate round together)
      if (!d->active) continue;
    }
    if (d->group.my_index < 0) continue;  // not a member

    // partition my requests: allreduce cache hits travel as bits (the
    // steady-state fast path, reference: response_cache.h CacheCoordinator);
    // everything else as full requests
    auto popped = d->queue.PopRequests();
    std::vector<Request> misses;
    std::vector<int32_t> my_bits;
    for (auto& r : popped) {
      if (r.type == Request::kAllreduce && cfg_.cache_enabled) {
        int bit = d->cache->Lookup(ResponseCache::Key(r));
        if (bit >= 0) {
          my_bits.push_back(bit);
          counters_.cache_hits++;
          continue;
        }
        counters_.cache_misses++;
      }
      misses.push_back(r);
    }

    int coord = d->group.global(0);
    bool is_coord = d->group.my_index == 0;

    std::vector<Response> singles;
    if (d->group.size() == 1) {
      HandleRequests(*d, cfg_.rank, misses);
      HandleCacheBits(*d, cfg_.rank, my_bits);
      singles = CollectReady(*d);
      if (want_shutdown && id == 0) got_shutdown_response = true;
      if (id == 0 && has_pending_knobs_) {  // no peers to synchronize with
        ApplyKnobFlags(pending_knob_flags_);
        has_pending_knobs_ = false;
      }
    } else if (is_coord) {
      // gather (lockstep cycle; reference: MPIController::RecvReadyTensors)
      HandleRequests(*d, cfg_.rank, misses);
      HandleCacheBits(*d, cfg_.rank, my_bits);
      auto note_announce = [&](int from,
                               const std::vector<wire::DomainAnnounce>& as) {
        for (auto& a : as) {
          auto& c = announce_table_[a.id];
          if (c.ranks.empty()) c.ranks_hash = a.ranks_hash;
          if (c.ranks_hash != a.ranks_hash && !c.mismatch_warned) {
            HVD_LOG(Error)
                << "ranks disagree on the member list of process set "
                << a.id << "; the set will never activate";
            c.mismatch_warned = true;
          }
          c.ranks.insert(from);
        }
      };
      auto note_retire = [&](int from, const std::vector<int32_t>& rs) {
        for (auto r : rs) retire_table_[r].insert(from);
      };
      if (id == 0) {
        note_announce(cfg_.rank, my_announce);
        note_retire(cfg_.rank, my_retire);
      }
      int shutdown_votes = want_shutdown ? 1 : 0;
      for (int i = 1; i < d->group.size(); ++i) {
        std::vector<uint8_t> buf;
        auto st = transport_->Recv(d->group.global(i),
                                   DomTag(id, kTagNegotiate), &buf);
        if (!st.ok()) { loop_error_ = st.reason; return false; }
        bool sd;
        std::vector<int32_t> bits;
        std::vector<wire::DomainAnnounce> ann;
        std::vector<int32_t> ret;
        auto rl = wire::DecodeRequestList(buf.data(), buf.size(), &sd, &bits,
                                          &ann, &ret);
        if (sd) shutdown_votes++;
        if (id == 0) {
          note_announce(d->group.global(i), ann);
          note_retire(d->group.global(i), ret);
        }
        HandleRequests(*d, d->group.global(i), rl);
        HandleCacheBits(*d, d->group.global(i), bits);
      }
      // registration/retire consensus (domain 0 only): a set goes live —
      // on every rank in THIS cycle — once all ranks announced it
      std::vector<int32_t> activate, retired;
      if (id == 0) {
        for (auto it = announce_table_.begin();
             it != announce_table_.end();) {
          if (!it->second.mismatch_warned &&
              (int)it->second.ranks.size() >= cfg_.size) {
            activate.push_back(it->first);
            it = announce_table_.erase(it);
          } else {
            ++it;
          }
        }
        for (auto it = retire_table_.begin(); it != retire_table_.end();) {
          if ((int)it->second.size() >= cfg_.size) {
            retired.push_back(it->first);
            announce_table_.erase(it->first);  // drop a half-done activation
            it = retire_table_.erase(it);
          } else {
            ++it;
          }
        }
      }
      singles = CollectReady(*d);
      // fatally stalled tensors (some ranks never submitted) error out to
      // their waiters instead of hanging forever (reference:
      // HOROVOD_STALL_SHUTDOWN_TIME_SECONDS; surfaced here as a per-tensor
      // HorovodInternalError so elastic recovery can engage)
      for (auto& name : d->stall.FatallyStalled(cfg_.stall_shutdown_secs)) {
        int group_id = -1;
        auto rit = d->ready_table_.find(name);
        if (rit != d->ready_table_.end()) {
          group_id = rit->second.first.group_id;
          d->ready_table_.erase(rit);
        }
        d->announce_time_.erase(name);
        // the stalled submission may be a partial CACHE BIT
        for (auto it2 = d->bit_ready_.begin();
             it2 != d->bit_ready_.end();) {
          const Response& cr = d->cache->Get(it2->first);
          if (!cr.names.empty() && cr.names[0] == name) {
            group_id = cr.group_id;
            d->bit_time_.erase(it2->first);
            it2 = d->bit_ready_.erase(it2);
          } else {
            ++it2;
          }
        }
        d->stall.RemoveReady(name);
        HVD_LOG(Error) << "tensor '" << name << "' fatally stalled ("
                       << cfg_.stall_shutdown_secs
                       << "s); erroring its waiters";
        if (timeline_ && timeline_->enabled())
          timeline_->End(name);  // close the open NEGOTIATE_*/WAIT_* span
        Response e;
        e.type = Response::kError;
        e.names = {name};
        e.error_message =
            "tensor '" + name + "' stalled beyond "
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS (" +
            std::to_string((int)cfg_.stall_shutdown_secs) +
            "s): one or more ranks never submitted it";
        // a stalled GROUP member must fail its held siblings too (same
        // contract as the negotiated-error path: no handle waits forever)
        if (group_id >= 0) {
          d->poisoned_groups_.insert(group_id);
          auto git = d->groups_.find(group_id);
          if (git != d->groups_.end()) {
            for (auto& held : git->second.second) {
              Response e2;
              e2.type = Response::kError;
              e2.names = held.names;
              e2.error_message = e.error_message;
              singles.push_back(std::move(e2));
            }
            d->groups_.erase(git);
          }
        }
        singles.push_back(std::move(e));
      }
      if (id == 0 && shutdown_votes == d->group.size()) {
        Response sd;
        sd.type = Response::kShutdown;
        singles.push_back(sd);
      }
      uint8_t knobs = (id == 0 && has_pending_knobs_)
                          ? pending_knob_flags_ : KnobFlags();
      auto payload = wire::EncodeResponseList(singles, cfg_.fusion_threshold,
                                              activate, retired, knobs);
      for (int i = 1; i < d->group.size(); ++i) {
        auto st = transport_->Send(d->group.global(i),
                                   DomTag(id, kTagResponse), payload.data(),
                                   payload.size());
        if (!st.ok()) { loop_error_ = st.reason; return false; }
      }
      if (id == 0) ApplyDomainLifecycle(activate, retired);
      if (id == 0 && has_pending_knobs_) {
        // apply to ourselves only now that the packet carrying the flags
        // to every worker is on the wire: the whole world flips at this
        // cycle boundary (workers apply at the matching receive)
        ApplyKnobFlags(pending_knob_flags_);
        has_pending_knobs_ = false;
      }
      // stall check (reference: controller.cc:132-143); counts feed the
      // hvd_stall_warnings_total counter and stalled-tensor gauge on
      // /metrics (docs/OBSERVABILITY.md)
      int newly_warned = 0, stalled_now = 0;
      auto warn = d->stall.Check(cfg_.stall_warning_secs, &newly_warned,
                                 &stalled_now);
      if (newly_warned > 0) counters_.stall_warnings += newly_warned;
      cycle_stalled += stalled_now;
      if (!warn.empty()) {
        HVD_LOG(Warning) << "STALL:\n" << warn;
      }
    } else {
      auto payload = wire::EncodeRequestList(
          misses, want_shutdown, my_bits,
          id == 0 ? my_announce : std::vector<wire::DomainAnnounce>{},
          id == 0 ? my_retire : std::vector<int32_t>{});
      auto st = transport_->Send(coord, DomTag(id, kTagNegotiate),
                                 payload.data(), payload.size());
      if (!st.ok()) { loop_error_ = st.reason; return false; }
      std::vector<uint8_t> buf;
      st = transport_->Recv(coord, DomTag(id, kTagResponse), &buf);
      if (!st.ok()) { loop_error_ = st.reason; return false; }
      int64_t coord_threshold = cfg_.fusion_threshold;
      std::vector<int32_t> activate, retired;
      uint8_t knobs = KnobFlags();
      singles = wire::DecodeResponseList(buf.data(), buf.size(),
                                         &coord_threshold, &activate,
                                         &retired, &knobs);
      if (id == 0) ApplyDomainLifecycle(activate, retired);
      // adopt the coordinator's threshold so FuseResponses groups
      // identically on every rank (autotune is coordinator-only), and its
      // categorical knobs at the same cycle boundary the coordinator
      // applied them (the packet that carries them)
      cfg_.fusion_threshold = coord_threshold;
      if (id == 0) ApplyKnobFlags(knobs);
    }

    // every rank inserts newly negotiated allreduce responses in identical
    // (broadcast) order — and Touches cached ones in the same order — so
    // cache bit spaces AND LRU recency stay aligned across ranks
    if (cfg_.cache_enabled) {
      for (auto& s : singles) {
        if (s.type != Response::kAllreduce) continue;
        if (s.from_cache) {
          int bit = d->cache->Lookup(KeyFromSingleResponse(s));
          if (bit >= 0) d->cache->Touch(bit);
          continue;
        }
        Response evicted;
        bool did_evict = false;
        int bit = d->cache->Insert(KeyFromSingleResponse(s), s, &evicted,
                                   &did_evict);
        if (!did_evict) continue;
        counters_.cache_evictions++;
        // Coordinator: a pending (partial) bit announcement for the
        // evicted entry can no longer complete as a bit — the bit now
        // names the new entry, and ranks that miss post-eviction will
        // announce full requests. Migrate the announced ranks into
        // full-request negotiation so the tensor still completes.
        // (Reference coordinates this with explicit invalid-bit sync,
        // response_cache.h:135-139; deterministic eviction lets us
        // migrate locally instead.) Workers have no bit_ready_ state.
        auto bit_it = d->bit_ready_.find(bit);
        if (bit_it == d->bit_ready_.end() || evicted.names.empty())
          continue;
        Request q = RequestFromSingleResponse(evicted);
        auto& slot = d->ready_table_[q.name];
        auto bt = d->bit_time_.find(bit);
        // keep the straggler clock running across the bit->request
        // migration: the wait started at the EARLIEST announcement on
        // either path, and bit ranks that announced before the full
        // request must stay ahead of it in slot order — charge() blames
        // ranks.back(), so appending early announcers last would pin the
        // wait on the wrong rank
        bool bits_first = false;
        if (slot.second.empty()) {
          slot.first = q;
          d->announce_time_[q.name] =
              bt != d->bit_time_.end() ? bt->second
                                       : std::chrono::steady_clock::now();
        } else if (bt != d->bit_time_.end()) {
          auto at = d->announce_time_.find(q.name);
          if (at == d->announce_time_.end() || bt->second < at->second) {
            d->announce_time_[q.name] = bt->second;
            bits_first = true;
          }
        }
        d->bit_time_.erase(bit);
        size_t pos = 0;
        for (int rk : bit_it->second)
          if (std::find(slot.second.begin(), slot.second.end(), rk) ==
              slot.second.end()) {
            if (bits_first)
              slot.second.insert(slot.second.begin() + pos++, rk);
            else
              slot.second.push_back(rk);
          }
        d->bit_ready_.erase(bit_it);
      }
    }

    auto units = FuseResponses(singles);
    for (auto& resp : units) {
      if (resp.names.size() > 1) {
        counters_.fused_units++;
        counters_.tensors_fused += resp.names.size();
      }
    }
    for (auto& resp : units) {
      if (resp.type == Response::kShutdown) {
        got_shutdown_response = true;
        continue;
      }
      Execute(*d, resp);
    }
  }

  if (got_shutdown_response) return false;

  // autotune (reference: RunLoopOnce -> ParameterManager). Coordinator
  // only: workers adopt the tuned fusion threshold from the response list,
  // keeping fusion grouping identical across ranks.
  if (cfg_.rank == 0) {
    int64_t fusion = cfg_.fusion_threshold;
    double cycle = cfg_.cycle_time_ms;
    bool hier = hier_enabled_;
    bool cache = cfg_.cache_enabled;
    if (param_mgr_.Tune(&fusion, &cycle, &hier, &cache)) {
      cfg_.fusion_threshold = fusion;
      cfg_.cycle_time_ms = cycle;
      // categorical knobs must flip on every rank at the same cycle
      // boundary: stage them for the next domain-0 response broadcast
      // instead of applying locally now (see pending_knob_flags_)
      pending_knob_flags_ = (uint8_t)((hier ? 0x1 : 0) | (cache ? 0x2 : 0));
      has_pending_knobs_ = true;
    }
  }
  counters_.stalled_tensors.store(cycle_stalled);
  // mirror the (possibly autotuned) knob values for the metrics scrape
  // thread — every rank, every cycle: workers adopt tuned values via the
  // response fusion threshold + knob flags, so their mirrors track too
  counters_.autotune_fusion_bytes.store(cfg_.fusion_threshold);
  counters_.autotune_cycle_us.store(
      (uint64_t)(cfg_.cycle_time_ms * 1000.0));
  counters_.autotune_hierarchical.store(hier_enabled_ ? 1 : 0);
  counters_.autotune_cache_enabled.store(cfg_.cache_enabled ? 1 : 0);
  // periodic rank-attributed negotiation-wait summary (coordinator only
  // accumulates attribution; HVD_TPU_STRAGGLER_REPORT_SECONDS)
  if (cfg_.rank == 0) MaybeReportStragglers();
  PublishEngineState();
  return true;
}

// Serialize per-domain negotiation state into the published snapshot
// (<=2 Hz; EngineStateJson readers get the latest copy). Runs on the
// loop thread, the only mutator of domain internals.
void Core::PublishEngineState() {
  auto now = std::chrono::steady_clock::now();
  if (std::chrono::duration<double>(now - last_state_pub_).count() < 0.5)
    return;
  last_state_pub_ = now;
  std::ostringstream os;
  os << "{\"rank\":" << cfg_.rank << ",\"size\":" << cfg_.size
     << ",\"coordinator\":" << (cfg_.rank == 0 ? "true" : "false")
     << ",\"domains\":[";
  bool first_d = true;
  {
    std::lock_guard<std::mutex> lk(domains_mu_);
    for (auto& kv : domains_) {
      CoordDomain* d = kv.second.get();
      if (!first_d) os << ",";
      first_d = false;
      os << "{\"id\":" << kv.first << ",\"active\":"
         << (d->active ? "true" : "false")
         << ",\"queue_pending\":" << d->queue.pending()
         << ",\"joined_count\":" << d->join_count << ",\"pending\":[";
      bool first_p = true;
      for (auto& p : d->stall.Pending()) {
        if (!first_p) os << ",";
        first_p = false;
        os << "{\"name\":\"" << JsonEscape(p.name) << "\",\"waited_s\":"
           << p.waited_s << ",\"ready_ranks\":[";
        for (size_t i = 0; i < p.ready_ranks.size(); ++i)
          os << (i ? "," : "") << p.ready_ranks[i];
        os << "],\"missing_ranks\":[";
        // missing = domain members that have not announced this tensor
        bool first_m = true;
        for (int r : d->group.ranks) {
          if (std::find(p.ready_ranks.begin(), p.ready_ranks.end(), r) !=
              p.ready_ranks.end())
            continue;
          os << (first_m ? "" : ",") << r;
          first_m = false;
        }
        os << "]}";
      }
      os << "]}";
    }
  }
  os << "]}";
  std::lock_guard<std::mutex> lk(engine_state_mu_);
  engine_state_json_ = os.str();
}

std::string Core::EngineStateJson() const {
  std::lock_guard<std::mutex> lk(engine_state_mu_);
  return engine_state_json_;
}

bool Core::TimelineEnabled() const {
  return timeline_ && timeline_->enabled();
}

void Core::TimelineMark(const std::string& name, const std::string& span) {
  if (timeline_) timeline_->MarkSpan(name, span);
}

// -- straggler attribution --------------------------------------------------

void Core::ChargeStraggler(int last_rank, double waited) {
  if (waited < 0) waited = 0;
  std::lock_guard<std::mutex> lk(straggler_mu_);
  auto& pr = stragglers_.ranks[last_rank];
  pr.wait_seconds += waited;
  pr.held_count++;
  stragglers_.tensors_timed++;
  stragglers_.total_wait_seconds += waited;
}

void Core::MaybeReportStragglers() {
  if (cfg_.straggler_report_secs <= 0) return;
  auto now = std::chrono::steady_clock::now();
  if (std::chrono::duration<double>(now - last_straggler_report_).count() <
      cfg_.straggler_report_secs)
    return;
  last_straggler_report_ = now;
  std::ostringstream os;
  uint64_t timed = 0;
  {
    std::lock_guard<std::mutex> lk(straggler_mu_);
    timed = stragglers_.tensors_timed;
    for (auto& kv : stragglers_.ranks) {
      if (kv.second.held_count == 0) continue;
      os << " rank " << kv.first << ": last-in for "
         << kv.second.held_count << " tensors, peers waited "
         << kv.second.wait_seconds << "s total;";
    }
  }
  if (timed > 0) {
    HVD_LOG(Info) << "straggler report (" << timed
                  << " tensors timed since init):" << os.str();
  }
}

std::string Core::StragglersJson() const {
  std::ostringstream os;
  std::lock_guard<std::mutex> lk(straggler_mu_);
  os << "{\"tensors_timed\":" << stragglers_.tensors_timed
     << ",\"total_wait_seconds\":" << stragglers_.total_wait_seconds
     << ",\"ranks\":{";
  bool first = true;
  for (auto& kv : stragglers_.ranks) {
    if (!first) os << ',';
    first = false;
    os << '"' << kv.first << "\":{\"wait_seconds\":"
       << kv.second.wait_seconds << ",\"held_count\":"
       << kv.second.held_count << '}';
  }
  os << "}}";
  return os.str();
}

uint8_t Core::KnobFlags() const {
  return (uint8_t)((hier_enabled_ ? 0x1 : 0) |
                   (cfg_.cache_enabled ? 0x2 : 0));
}

void Core::ApplyKnobFlags(uint8_t flags) {
  bool hier = (flags & 0x1) != 0;
  bool cache = (flags & 0x2) != 0;
  if (hier != hier_enabled_ || cache != cfg_.cache_enabled) {
    HVD_LOG(Debug) << "autotune knob flip: hierarchical="
                   << (hier ? 1 : 0) << " cache=" << (cache ? 1 : 0);
  }
  // only honor hier when this rank's topology supports the two-level
  // path (identical on every rank: the coordinator proposes it only when
  // its own — identical — topology config allows)
  hier_enabled_ = hier && hier_topology_ok_;
  cfg_.cache_enabled = cache;
}

// -- execution (reference: PerformOperation, operations.cc:257-306) ---------

void Core::Execute(CoordDomain& d, const Response& r) {
  int id = d.id;
  int32_t dtag = DomTag(id, kTagData);
  counters_.responses_executed++;
  // sub-activity markers nested under EXECUTE on the unit's tid
  // (reference activity classes: MEMCPY_IN_FUSION_BUFFER /
  // MEMCPY_OUT_FUSION_BUFFER / <op> — common.h:73-105)
  bool tl = timeline_ && timeline_->enabled() && !r.names.empty();
  auto act_begin = [&](const char* a) {
    if (tl) timeline_->Begin(r.names[0], a);
  };
  auto act_end = [&] {
    if (tl) timeline_->End(r.names[0]);
  };
  if (tl) timeline_->Begin(r.names[0], "EXECUTE");

  switch (r.type) {
    case Response::kAllreduce: {
      // gather entries; joined ranks contribute zeros
      struct Slot {
        TensorTableEntry e;
        bool have;
        size_t off;
        int64_t bytes;
      };
      std::vector<Slot> slots(r.names.size());
      size_t total = 0;
      for (size_t i = 0; i < r.names.size(); ++i) {
        slots[i].have = d.queue.Take(r.names[i], &slots[i].e);
        int64_t n = DataTypeSize(r.dtypes[i]);
        for (auto dim : r.shapes[i]) n *= dim;
        slots[i].bytes = n;
        slots[i].off = total;
        total += AlignUp(n);
      }
      act_begin("MEMCPY_IN_FUSION_BUFFER");
      std::vector<uint8_t> fusion(total, 0);
      for (auto& s : slots)
        if (s.have)
          memcpy(fusion.data() + s.off, s.e.input, s.bytes);
      act_end();
      int64_t nelem = 0;
      // element count: all same dtype; compute from bytes
      size_t esz = DataTypeSize(r.dtypes[0]);
      nelem = total / esz;
      Status st;
      if (hier_enabled_ && d.id == 0 && d.group.size() > 1 &&
          r.op != ReduceOp::kAdasum) {
        // two-level path: intra-host reduce -> cross-host ring among
        // leaders -> intra-host broadcast
        act_begin("HIERARCHICAL_ALLREDUCE");
        st = HierarchicalAllreduce(*transport_, local_group_, cross_group_,
                                   cfg_.local_rank == 0, dtag,
                                   fusion.data(), nelem, r.dtypes[0], r.op,
                                   r.prescale, r.postscale);
        // counter documents that the path RAN successfully (matches the
        // hier_allgathers guard) — do not count failed attempts
        if (st.ok()) counters_.hier_allreduces++;
        act_end();
      } else if (r.op == ReduceOp::kAdasum && d.group.size() > 1) {
        act_begin("ADASUM_ALLREDUCE");
        ScaleBufferOp(fusion.data(), nelem, r.dtypes[0], r.prescale);
        st = AdasumAllreduce(*transport_, d.group, DomTag(d.id, kTagAdasum),
                             fusion.data(), nelem, r.dtypes[0]);
        ScaleBufferOp(fusion.data(), nelem, r.dtypes[0], r.postscale);
        act_end();
      } else {
        act_begin("RING_ALLREDUCE");
        st = RingAllreduce(*transport_, d.group, dtag, fusion.data(),
                           nelem, r.dtypes[0], r.op, r.prescale,
                           r.postscale);
        act_end();
      }
      param_mgr_.Record(total);
      counters_.bytes_allreduced += (uint64_t)total;
      act_begin("MEMCPY_OUT_FUSION_BUFFER");
      for (auto& s : slots) {
        if (!s.have) continue;
        if (st.ok() && s.e.output)
          memcpy(s.e.output, fusion.data() + s.off, s.bytes);
        if (s.e.callback) s.e.callback(st);
      }
      act_end();
      break;
    }
    case Response::kAllgather: {
      size_t k = r.names.size();
      struct AgSlot {
        TensorTableEntry e;
        bool have;
        int64_t row_bytes;
        int64_t my_bytes;
      };
      std::vector<AgSlot> slots(k);
      for (size_t i = 0; i < k; ++i) {
        slots[i].have = d.queue.Take(r.names[i], &slots[i].e);
        int64_t rb = DataTypeSize(r.dtypes[i]);
        for (size_t j = 1; j < r.shapes[i].size(); ++j)
          rb *= r.shapes[i][j];
        slots[i].row_bytes = std::max<int64_t>(rb, 1);
        slots[i].my_bytes =
            slots[i].have ? (int64_t)slots[i].e.ByteSize() : 0;
      }
      // two-level node-leader path (reference: MPIHierarchicalAllgather,
      // mpi_operations.cc) — global domain only: sub-sets have no
      // topology contract
      bool hier_ag = hier_ag_enabled_ && d.id == 0 && d.group.size() > 1;
      auto allgatherv = [&](const void* send, int64_t send_bytes,
                            std::vector<int64_t>* sizes,
                            std::vector<uint8_t>* out) {
        if (hier_ag)
          return HierarchicalAllgatherV(
              *transport_, local_group_, cross_group_,
              cfg_.local_rank == 0, dtag, send, send_bytes, sizes, out);
        return AllgatherV(*transport_, d.group, dtag, send, send_bytes,
                          sizes, out);
      };
      if (k == 1) {
        // single-tensor fast path: one round; per-rank sizes come back
        // from AllgatherV itself
        auto& s0 = slots[0];
        std::vector<int64_t> sizes;
        std::vector<uint8_t> out;
        static const uint8_t kEmpty = 0;
        act_begin(hier_ag ? "HIERARCHICAL_ALLGATHER" : "ALLGATHERV");
        auto st = allgatherv(
            s0.have && s0.e.input ? s0.e.input : &kEmpty,
            s0.my_bytes, &sizes, &out);
        act_end();
        if (hier_ag && st.ok()) counters_.hier_allgathers++;
        counters_.bytes_allgathered += (uint64_t)out.size();
        if (s0.have) {
          if (st.ok()) {
            *s0.e.result = std::move(out);
            int64_t rows = (int64_t)s0.e.result->size() / s0.row_bytes;
            *s0.e.result_shape = r.shapes[0];
            if (!s0.e.result_shape->empty())
              (*s0.e.result_shape)[0] = rows;
          }
          if (s0.e.callback) s0.e.callback(st);
        }
        break;
      }
      // Fused path (reference: fused allgather displacement math,
      // ops/collective_operations.h:209-273): (1) one fixed-size round
      // exchanging the k per-tensor byte counts of every rank, (2) one
      // data round gathering each rank's concatenated tensors, (3)
      // scatter rank-major slices into per-tensor results. 2 rounds
      // total instead of k.
      int n = d.group.size();
      std::vector<int64_t> my_sizes(k);
      int64_t send_total = 0;
      for (size_t i = 0; i < k; ++i) {
        my_sizes[i] = slots[i].my_bytes;
        send_total += my_sizes[i];
      }
      std::vector<int64_t> size_per_rank;
      std::vector<uint8_t> size_out;
      act_begin("ALLGATHER_SIZES");
      auto st = allgatherv(my_sizes.data(),
                           (int64_t)(k * sizeof(int64_t)), &size_per_rank,
                           &size_out);
      act_end();
      if (st.ok() && size_out.size() != k * sizeof(int64_t) * (size_t)n)
        st = Status::Error("fused allgather size exchange mismatch");
      std::vector<uint8_t> data;
      std::vector<int64_t> rank_off;
      const int64_t* all_sizes = nullptr;  // [n][k] row-major
      if (st.ok()) {
        all_sizes = (const int64_t*)size_out.data();
        act_begin("MEMCPY_IN_FUSION_BUFFER");
        std::vector<uint8_t> send((size_t)send_total);
        int64_t off = 0;
        for (size_t i = 0; i < k; ++i) {
          if (slots[i].have && slots[i].e.input && my_sizes[i] > 0)
            memcpy(send.data() + off, slots[i].e.input, my_sizes[i]);
          off += my_sizes[i];
        }
        act_end();
        std::vector<int64_t> per_rank;
        static const uint8_t kEmptyF = 0;
        act_begin(hier_ag ? "HIERARCHICAL_ALLGATHER" : "ALLGATHERV");
        st = allgatherv(send_total ? send.data() : &kEmptyF, send_total,
                        &per_rank, &data);
        act_end();
        if (st.ok()) {
          rank_off.assign(n + 1, 0);
          for (int rr = 0; rr < n; ++rr)
            rank_off[rr + 1] = rank_off[rr] + per_rank[rr];
          counters_.bytes_allgathered += (uint64_t)data.size();
          if (hier_ag) counters_.hier_allgathers++;  // once per collective
        }
      }
      act_begin("MEMCPY_OUT_FUSION_BUFFER");
      for (size_t i = 0; i < k; ++i) {
        auto& s = slots[i];
        if (!s.have) continue;
        if (st.ok()) {
          int64_t total_i = 0;
          for (int rr = 0; rr < n; ++rr)
            total_i += all_sizes[(size_t)rr * k + i];
          s.e.result->resize((size_t)total_i);
          int64_t dst = 0;
          for (int rr = 0; rr < n; ++rr) {
            // rank rr's block holds its tensors in announce order;
            // tensor i sits after rr's tensors 0..i-1
            int64_t src = rank_off[rr];
            for (size_t j = 0; j < i; ++j)
              src += all_sizes[(size_t)rr * k + j];
            int64_t len = all_sizes[(size_t)rr * k + i];
            if (len > 0)
              memcpy(s.e.result->data() + dst, data.data() + src, len);
            dst += len;
          }
          *s.e.result_shape = r.shapes[i];
          if (!s.e.result_shape->empty())
            (*s.e.result_shape)[0] = total_i / s.row_bytes;
        }
        if (s.e.callback) s.e.callback(st);
      }
      act_end();
      break;
    }
    case Response::kBroadcast: {
      TensorTableEntry e;
      bool have = d.queue.Take(r.names[0], &e);
      int64_t nbytes = DataTypeSize(r.dtypes[0]);
      for (auto dim : r.shapes[0]) nbytes *= dim;
      std::vector<uint8_t> scratch;
      void* buf;
      if (have) {
        if (d.group.global(d.group.my_index) == r.root_rank)
          memcpy(e.output, e.input, nbytes);
        buf = e.output;
      } else {
        scratch.resize(nbytes);
        buf = scratch.data();
      }
      int root_index =
          (int)(std::find(d.group.ranks.begin(), d.group.ranks.end(),
                          r.root_rank) -
                d.group.ranks.begin());
      auto st = Broadcast(*transport_, d.group, dtag, buf, nbytes,
                          root_index);
      if (have && e.callback) e.callback(st);
      break;
    }
    case Response::kAlltoall: {
      TensorTableEntry e;
      bool have = d.queue.Take(r.names[0], &e);
      int64_t row_bytes = DataTypeSize(r.dtypes[0]);
      auto shape = r.shapes[0];
      for (size_t i = 1; i < shape.size(); ++i) row_bytes *= shape[i];
      std::vector<int64_t> splits =
          have ? e.splits : std::vector<int64_t>(d.group.size(), 0);
      std::vector<int64_t> recv_splits;
      std::vector<uint8_t> out;
      static const uint8_t kEmpty2 = 0;
      auto st = AlltoallV(*transport_, d.group, dtag,
                          have && e.input ? e.input : &kEmpty2, splits,
                          row_bytes, &recv_splits, &out);
      if (have) {
        if (st.ok()) {
          *e.result = std::move(out);
          *e.recv_splits = recv_splits;
          int64_t rows = 0;
          for (auto s : recv_splits) rows += s;
          *e.result_shape = shape;
          if (!e.result_shape->empty()) (*e.result_shape)[0] = rows;
        }
        if (e.callback) e.callback(st);
      }
      break;
    }
    case Response::kBarrier: {
      TensorTableEntry e;
      bool have = d.queue.Take(r.names[0], &e);
      auto st = Barrier(*transport_, d.group, DomTag(id, kTagBarrier));
      if (have && e.callback) e.callback(st);
      break;
    }
    case Response::kError: {
      TensorTableEntry e;
      if (d.queue.Take(r.names[0], &e) && e.callback)
        e.callback(Status::Error(r.error_message));
      break;
    }
    case Response::kJoin: {
      TensorTableEntry e;
      bool have = d.queue.Take("__join__", &e);
      d.joined = false;
      d.join_count = r.last_joined_rank;
      if (have && e.callback) e.callback(Status::OK());
      break;
    }
    default:
      break;
  }
  if (tl) timeline_->End(r.names[0]);  // closes EXECUTE
}

}  // namespace hvd
