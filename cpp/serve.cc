#include "serve.h"

#include "util.h"

#include <fcntl.h>
#include <netinet/in.h>
#include <poll.h>
#include <signal.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cmath>
#include <cstdio>
#include <cstring>
#include <ctime>
#include <functional>

namespace tpk {

namespace {

Allocation AllocFromJson(const Json& j) {
  Allocation a;
  for (const auto& [name, n] : j.items()) {
    a.slices[name] = static_cast<int>(n.as_int());
  }
  return a;
}

Json AllocToJson(const Allocation& a) {
  Json j = Json::Object();
  for (const auto& [name, n] : a.slices) j[name] = n;
  return j;
}

}  // namespace

// --------------------------------------------------------------------------
// HttpProbe
// --------------------------------------------------------------------------

bool HttpProbe::Request(int port, const std::string& raw, std::string* body,
                        int* status) {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return false;
  fcntl(fd, F_SETFL, fcntl(fd, F_GETFL, 0) | O_NONBLOCK);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  addr.sin_port = htons(static_cast<uint16_t>(port));
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms_);
  auto left_ms = [&]() {
    return static_cast<int>(
        std::max<long long>(0, std::chrono::duration_cast<
                                   std::chrono::milliseconds>(
                                   deadline - std::chrono::steady_clock::now())
                                   .count()));
  };
  if (connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) < 0) {
    if (errno != EINPROGRESS) {
      close(fd);
      return false;
    }
    pollfd pfd{fd, POLLOUT, 0};
    if (poll(&pfd, 1, left_ms()) <= 0) {
      close(fd);
      return false;
    }
    int err = 0;
    socklen_t len = sizeof(err);
    if (getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &len) != 0 || err != 0) {
      close(fd);
      return false;
    }
  }
  const std::string& req = raw;
  size_t off = 0;
  while (off < req.size()) {
    ssize_t sent = write(fd, req.data() + off, req.size() - off);
    if (sent > 0) {
      off += sent;
      continue;
    }
    if (errno != EAGAIN && errno != EWOULDBLOCK) {
      close(fd);
      return false;
    }
    pollfd pfd{fd, POLLOUT, 0};
    if (poll(&pfd, 1, left_ms()) <= 0) {
      close(fd);
      return false;
    }
  }
  std::string resp;
  while (true) {
    char buf[4096];
    ssize_t got = read(fd, buf, sizeof(buf));
    if (got > 0) {
      resp.append(buf, got);
      if (resp.size() > (1u << 20)) break;  // cap
      continue;
    }
    if (got == 0) break;
    if (errno != EAGAIN && errno != EWOULDBLOCK) break;
    pollfd pfd{fd, POLLIN, 0};
    if (poll(&pfd, 1, left_ms()) <= 0) break;
  }
  close(fd);
  if (resp.compare(0, 5, "HTTP/") != 0) return false;
  size_t sp = resp.find(' ');
  *status = sp == std::string::npos ? 0 : atoi(resp.c_str() + sp + 1);
  size_t hdr_end = resp.find("\r\n\r\n");
  *body = hdr_end == std::string::npos ? "" : resp.substr(hdr_end + 4);
  return true;
}

bool HttpProbe::Get(int port, const std::string& path, std::string* body,
                    int* status) {
  return Request(port,
                 "GET " + path + " HTTP/1.0\r\nHost: 127.0.0.1\r\n\r\n",
                 body, status);
}

bool HttpProbe::Post(int port, const std::string& path,
                     const std::string& payload, int* status) {
  std::string body;
  return Request(
      port,
      "POST " + path + " HTTP/1.0\r\nHost: 127.0.0.1\r\n"
      "Content-Type: application/json\r\nContent-Length: " +
          std::to_string(payload.size()) + "\r\n\r\n" + payload,
      &body, status);
}

bool HttpProbe::Ready(int port) {
  std::string body;
  int status = 0;
  return Get(port, "/v2/health/ready", &body, &status) && status == 200;
}

bool HttpProbe::ModelReady(int port, const std::string& model,
                           const std::string& want_dir) {
  std::string body;
  int status = 0;
  if (!Get(port, "/v2/models/" + model + "/ready", &body, &status) ||
      status != 200) {
    return false;
  }
  if (want_dir.empty()) return true;
  try {
    return Json::parse(body).get("model_dir").as_string() == want_dir;
  } catch (const std::exception&) {
    return false;
  }
}

bool HttpProbe::Metrics(int port, std::string* body) {
  int status = 0;
  return Get(port, "/metrics", body, &status) && status == 200;
}

// --------------------------------------------------------------------------
// ServeController
// --------------------------------------------------------------------------

ServeController::ServeController(Store* store, ExecutorInterface* executor,
                                 Scheduler* scheduler, ProbeInterface* probe,
                                 std::string workdir, std::string python)
    : store_(store),
      executor_(executor),
      scheduler_(scheduler),
      probe_(probe),
      workdir_(std::move(workdir)),
      python_(std::move(python)) {
  mkdir(workdir_.c_str(), 0755);
}

std::string ServeController::ProcId(const std::string& name, int replica) {
  // "srv" segment keeps these ids disjoint from JAXJob's "<job>/<index>".
  return name + "/srv" + std::to_string(replica);
}

double ServeController::ParseRequestsTotal(const std::string& text) {
  double total = 0;
  size_t pos = 0;
  const std::string key = "tpk_serve_requests_total";
  while (pos < text.size()) {
    size_t nl = text.find('\n', pos);
    if (nl == std::string::npos) nl = text.size();
    std::string line = text.substr(pos, nl - pos);
    pos = nl + 1;
    if (line.compare(0, key.size(), key) != 0) continue;
    size_t sp = line.rfind(' ');
    if (sp != std::string::npos) total += atof(line.c_str() + sp + 1);
  }
  return total;
}

void ServeController::EnsureReplica(View& v, int index) {
  Json replicas = v.status.get("replicaState").is_array()
                      ? v.status.get("replicaState")
                      : Json::Array();
  while (static_cast<int>(replicas.size()) <= index) {
    replicas.push_back(Json());
  }
  Json rs = replicas.elements()[index];
  const std::string id = ProcId(v.res.name, index);

  // 0 = launched, 1 = no capacity (cheap, retry level-style), 2 = spawn
  // failure (must back off — retrying forks at tick rate).
  auto launch = [&](Json& rec) -> int {
    int devices =
        static_cast<int>(v.spec.get("devices_per_replica").as_int(1));
    Allocation alloc;
    if (!rec.get("alloc").is_object() || rec.get("alloc").size() == 0) {
      auto got = scheduler_->Allocate(devices, 1);
      if (!got) {
        rec = Json::Object();
        rec["pendingReason"] = "insufficient device capacity";
        return 1;
      }
      alloc = *got;
      rec["alloc"] = AllocToJson(alloc);
    }
    int port = FreePort();
    const Json& model = v.spec.get("model");
    LaunchSpec s;
    s.id = id;
    s.argv = {python_, "-m", "kubeflow_tpu.serve.server",
              "--port", std::to_string(port)};
    int grpc_port = 0;
    if (v.spec.get("grpc").as_bool(false)) {
      grpc_port = FreePort();
      s.argv.push_back("--grpc-port");
      s.argv.push_back(std::to_string(grpc_port));
    }
    if (!model.get("model_dir").as_string().empty()) {
      s.argv.push_back("--model-dir");
      s.argv.push_back(model.get("model_dir").as_string());
    } else if (!model.get("storage_uri").as_string().empty()) {
      s.argv.push_back("--storage-uri");
      s.argv.push_back(model.get("storage_uri").as_string());
    }
    if (!model.get("name").as_string().empty()) {
      s.argv.push_back("--name");
      s.argv.push_back(model.get("name").as_string());
    }
    // Tensor-parallel serving: model.mesh {"tensor": 8} → --mesh tensor=8
    // (admission already validated axes and the device budget).
    if (model.get("mesh").is_object()) {
      std::string mesh_arg;
      for (const auto& [axis, n] : model.get("mesh").items()) {
        if (!mesh_arg.empty()) mesh_arg += ",";
        mesh_arg += axis + "=" + std::to_string(n.as_int(1));
      }
      s.argv.push_back("--mesh");
      s.argv.push_back(mesh_arg);
    }
    if (v.spec.get("max_batch_size").is_number()) {
      s.argv.push_back("--max-batch-size");
      s.argv.push_back(
          std::to_string(v.spec.get("max_batch_size").as_int()));
    }
    if (v.spec.get("max_latency_ms").is_number()) {
      char buf[32];
      snprintf(buf, sizeof(buf), "%g",
               v.spec.get("max_latency_ms").as_number());
      s.argv.push_back("--max-latency-ms");
      s.argv.push_back(buf);
    }
    int cpu = static_cast<int>(v.spec.get("cpu_devices").as_int(0));
    if (cpu > 0) {
      s.argv.push_back("--cpu-devices");
      s.argv.push_back(std::to_string(cpu));
    }
    s.env["TPK_SERVICE"] = v.res.name;
    std::string dir = workdir_ + "/" + v.res.name;
    mkdir(dir.c_str(), 0755);
    // Request logger (KServe agent logger): spec.logger = true or
    // {"mode": "all"|"metadata"} → per-replica JSONL request log.
    const Json& logger = v.spec.get("logger");
    if (logger.as_bool(false) || logger.is_object()) {
      s.argv.push_back("--request-log");
      s.argv.push_back(dir + "/requests-" + std::to_string(index) +
                       ".jsonl");
      const std::string mode = logger.get("mode").as_string();
      if (!mode.empty()) {
        s.argv.push_back("--request-log-mode");
        s.argv.push_back(mode);
      }
    }
    s.stdout_path = dir + "/server-" + std::to_string(index) + ".log";
    s.stderr_path = dir + "/server-" + std::to_string(index) + ".err";
    std::string error;
    if (!executor_->LaunchGang({s}, &error)) {
      rec["pendingReason"] = "launch failed: " + error;
      return 2;
    }
    rec["id"] = id;
    rec["port"] = port;
    // Unconditional: a relaunch after spec.grpc was disabled must clear
    // the old port or status would advertise a dead gRPC endpoint.
    rec["grpc_port"] = grpc_port > 0 ? Json(grpc_port) : Json();
    rec["pid"] = executor_->Status(id).pid;
    rec["ready"] = false;
    rec["backoffUntil"] = Json();
    rec["pendingReason"] = Json();
    // Record what this replica serves, so a spec change (canary promote /
    // model update) triggers a rolling restart instead of being ignored.
    rec["model_dir"] = !model.get("model_dir").as_string().empty()
                           ? model.get("model_dir")
                           : model.get("storage_uri");
    metrics_.replica_starts++;
    return 0;
  };

  auto schedule_backoff = [&](Json& rec) {
    int64_t restarts = rec.get("restarts").as_int(0);
    rec["restarts"] = restarts + 1;
    double delay =
        std::min(60.0, std::pow(2.0, std::min<int64_t>(restarts, 6)));
    rec["backoffUntil"] = now_s_ + delay;
  };

  if (rs.is_null() || !rs.get("id").is_string()) {
    if (rs.is_null()) rs = Json::Object();
    // Spawn failures back off (forking at tick rate is a fork bomb);
    // capacity waits retry level-style — Allocate is cheap and the device
    // may free any moment.
    if (!rs.get("backoffUntil").is_number() ||
        now_s_ >= rs.get("backoffUntil").as_number(0)) {
      if (launch(rs) == 2) schedule_backoff(rs);
    }
    Json arr = Json::Array();
    for (size_t i = 0; i < replicas.size(); ++i) {
      arr.push_back(static_cast<int>(i) == index ? rs
                                                 : replicas.elements()[i]);
    }
    v.status["replicaState"] = arr;
    return;
  }

  auto st = executor_->Status(id);
  if (st.phase == ProcessStatus::Phase::kRunning) {
    // Model changed under this replica (e.g. canary promoted): bounce it —
    // ROLLING: at most one not-ready replica at a time, so a multi-replica
    // service keeps serving through a model update (a 1-replica service
    // unavoidably blips). backoffUntil=0 routes the relaunch through the
    // "backoff elapsed" branch immediately, without counting a crash.
    const Json& model = v.spec.get("model");
    const std::string want =
        !model.get("model_dir").as_string().empty()
            ? model.get("model_dir").as_string()
            : model.get("storage_uri").as_string();
    if (rs.get("model_dir").is_string() &&
        rs.get("model_dir").as_string() != want) {
      bool others_ready = true;
      for (size_t i = 0; i < replicas.size(); ++i) {
        if (static_cast<int>(i) == index) continue;
        const Json& other = replicas.elements()[i];
        if (other.is_object() && other.get("id").is_string() &&
            !other.get("ready").as_bool(false)) {
          others_ready = false;
          break;
        }
      }
      if (others_ready) {
        executor_->Kill(id);
        rs["ready"] = false;
        rs["backoffUntil"] = 0.0;
        Json arr2 = Json::Array();
        for (size_t i = 0; i < replicas.size(); ++i) {
          arr2.push_back(static_cast<int>(i) == index
                             ? rs
                             : replicas.elements()[i]);
        }
        v.status["replicaState"] = arr2;
        return;
      }
    }
    bool ready = rs.get("ready").as_bool(false);
    // Not-ready replicas probe every 1s; ready ones re-probe every 10s —
    // the kubelet liveness analog, so a wedged-but-alive server drops out
    // of the endpoint list instead of staying Ready forever.
    double interval = ready ? 10.0 : 1.0;
    if (now_s_ - rs.get("lastProbe").as_number(0) >= interval) {
      rs["lastProbe"] = now_s_;
      if (probe_->Ready(static_cast<int>(rs.get("port").as_int()))) {
        rs["probeFails"] = 0;
        if (!ready) {
          rs["ready"] = true;
          rs["readySince"] = now_s_;
        }
      } else if (ready) {
        int64_t fails = rs.get("probeFails").as_int(0) + 1;
        rs["probeFails"] = fails;
        if (fails >= 2) rs["ready"] = false;  // wedged: pull endpoint
      }
    }
  } else {
    // Server exited — crash-loop with exponential backoff. A long stable
    // run resets the streak so one crash a day doesn't accrue forever.
    rs["ready"] = false;
    if (!rs.get("backoffUntil").is_number()) {
      if (rs.get("readySince").is_number() &&
          now_s_ - rs.get("readySince").as_number(0) > 300) {
        rs["restarts"] = 0;
      }
      schedule_backoff(rs);
      rs["readySince"] = Json();
      metrics_.replica_restarts++;
    } else if (now_s_ >= rs.get("backoffUntil").as_number(0)) {
      if (launch(rs) == 2) schedule_backoff(rs);  // keeps alloc, new port
    }
  }
  Json arr = Json::Array();
  for (size_t i = 0; i < replicas.size(); ++i) {
    arr.push_back(static_cast<int>(i) == index ? rs
                                               : replicas.elements()[i]);
  }
  v.status["replicaState"] = arr;
}

void ServeController::StopReplica(View& v, int index) {
  const Json& replicas = v.status.get("replicaState");
  if (!replicas.is_array() ||
      index >= static_cast<int>(replicas.size())) {
    return;
  }
  const Json& rs = replicas.elements()[index];
  if (rs.is_object()) {
    if (rs.get("id").is_string()) {
      executor_->Kill(rs.get("id").as_string());
    }
    if (rs.get("alloc").is_object() && rs.get("alloc").size() > 0) {
      scheduler_->Release(AllocFromJson(rs.get("alloc")));
    }
  }
}

int ServeController::DesiredReplicas(View& v) {
  int64_t min_r = v.spec.get("min_replicas").as_int(
      v.spec.get("replicas").as_int(1));
  int64_t max_r = v.spec.get("max_replicas").as_int(min_r);
  double target = v.spec.get("target_rps").as_number(0);
  // Scale-to-zero (the Knative KPA capability, SURVEY.md §5.3): after
  // `scale_to_zero_after_s` with no served requests the replica count
  // drops to 0 (processes stopped, devices released). Cold start is an
  // explicit control-plane activation — clients that find the service
  // Idle bump `spec.wake` (TrainingClient.wake_service) and wait Ready;
  // a data-plane activator proxy that buffers the first request is the
  // production shape this stands in for.
  double idle_after = v.spec.get("scale_to_zero_after_s").as_number(0);
  bool rps_autoscale = target > 0 && max_r > min_r;
  if (!rps_autoscale && idle_after <= 0) {
    // Disabling scale-to-zero must clear a stale reaped marker, or
    // re-enabling it later would instantly reap the live service.
    if (v.status.get("idle").as_bool(false)) v.status["idle"] = false;
    return static_cast<int>(v.spec.get("replicas").as_int(min_r));
  }
  // Throughput autoscaler: rps over the scrape interval / target per
  // replica (KPA stand-in).
  Json as = v.status.get("autoscale").is_object()
                ? v.status.get("autoscale")
                : Json::Object();
  // Fixed-replica services must keep following spec.replicas updates —
  // only the rps autoscaler owns the persisted `desired`.
  int desired = static_cast<int>(
      rps_autoscale ? as.get("desired").as_int(min_r)
                    : v.spec.get("replicas").as_int(min_r));
  double interval = v.spec.get("scale_interval_s").as_number(10);
  double last_t = as.get("lastTime").as_number(0);
  if (now_s_ - last_t >= interval) {
    // Per-replica (per-port) counter deltas: a restarted replica resets its
    // counter to 0, and a replica whose scrape fails must be skipped — a
    // global total would read either case as negative load and scale the
    // service down under real traffic.
    Json baselines = as.get("perReplica").is_object()
                         ? as.get("perReplica")
                         : Json::Object();
    double delta = 0;
    bool scraped = false, attempted = false;
    const Json& replicas = v.status.get("replicaState");
    if (replicas.is_array()) {
      for (const auto& rs : replicas.elements()) {
        if (!rs.is_object() || !rs.get("ready").as_bool(false)) continue;
        attempted = true;
        std::string body;
        int port = static_cast<int>(rs.get("port").as_int());
        if (!probe_->Metrics(port, &body)) continue;  // baseline persists
        double t = ParseRequestsTotal(body);
        std::string key = std::to_string(port);
        if (baselines.has(key)) {
          double prev = baselines.get(key).as_number(0);
          // Counter went backwards ⇒ server restarted on the same port:
          // everything it now reports happened inside this window.
          delta += t >= prev ? t - prev : t;
        }
        // First successful scrape of a port only sets its baseline.
        baselines[key] = t;
        scraped = true;
      }
    }
    if (attempted) {
      // Record the attempt time even when every scrape failed, so a
      // wedged /metrics endpoint is retried once per interval, not once
      // per 50ms loop tick.
      as["lastTime"] = now_s_;
    }
    if (scraped) {
      as["lastScrapeOk"] = now_s_;
      if (delta > 0) {
        as["lastActive"] = now_s_;  // served traffic this window
      }
      if (rps_autoscale && last_t > 0) {
        double rps = delta / (now_s_ - last_t);
        desired = static_cast<int>(std::ceil(rps / target));
        desired = std::max(desired, static_cast<int>(min_r));
        desired = std::min(desired, static_cast<int>(max_r));
        if (desired != static_cast<int>(as.get("desired").as_int(min_r))) {
          metrics_.scale_events++;
          as["lastScaleTime"] = now_s_;
        }
      }
      as["perReplica"] = baselines;
      as["desired"] = desired;
    }
    v.status["autoscale"] = as;
  }
  // Idle reaping applies only when something would otherwise run — a
  // service scaled to zero BY HAND stays phase Ready, never Idle.
  if (idle_after > 0 && desired > 0) {
    bool reaped = v.status.get("idle").as_bool(false);
    double last_active = as.get("lastActive").as_number(0);
    // Activation: a wake timestamp newer than the last activity counts
    // as activity (and survives restarts — both live in the store).
    double wake = v.spec.get("wake").as_number(0);
    if (wake > last_active) {
      last_active = wake;
      as["lastActive"] = wake;
      v.status["autoscale"] = as;
    }
    // The idle clock only runs while the service can actually serve: a
    // replica still loading its model (cold start can exceed a short
    // idle window) or crash-looping must not be reaped as "idle" —
    // unless it is ALREADY reaped, where zero ready replicas is the
    // steady state and refreshing would immediately resurrect it.
    bool any_ready = false;
    const Json& reps = v.status.get("replicaState");
    if (reps.is_array()) {
      for (const auto& rs : reps.elements()) {
        if (rs.is_object() && rs.get("ready").as_bool(false)) {
          any_ready = true;
          break;
        }
      }
    }
    if (!reaped && !any_ready) {
      // Refresh at bounded granularity, not per tick — a long cold
      // start or crash loop must not append a WAL record per second.
      // The grain must not exceed idle_after: with idle_after <
      // interval, an interval-stale lastActive at the ready transition
      // would let the first post-cold-start scrape reap the service
      // before it served anything.
      double grain = std::min(interval, idle_after) / 2.0;
      if (now_s_ - last_active >= grain) {
        as["lastActive"] = now_s_;
        v.status["autoscale"] = as;
      }
      return desired;
    }
    if (last_active == 0) {
      // Defensive: ready with no recorded activity — start the clock.
      as["lastActive"] = now_s_;
      v.status["autoscale"] = as;
    } else if (as.get("lastScrapeOk").as_number(0) - last_active >=
               idle_after) {
      // Reap only on scrape EVIDENCE: a successful /metrics read at
      // least idle_after past the last activity. Comparing against
      // wall-clock `now` instead would reap a busy service whenever
      // idle_after < scale_interval_s (traffic lands between scrapes)
      // or whenever its metrics endpoint is wedged.
      if (!v.status.get("idle").as_bool(false)) {
        // Transition only: an idle service must not re-fire the metric
        // or rewrite its status (WAL churn) on every 50ms tick.
        metrics_.scale_events++;
        as["lastScaleTime"] = now_s_;
        v.status["autoscale"] = as;
        v.status["idle"] = true;
      }
      return 0;
    }
  }
  if (v.status.get("idle").as_bool(false)) v.status["idle"] = false;
  return desired;
}

void ServeController::Reconcile(const std::string& name) {
  auto res = store_->Get("InferenceService", name);
  if (!res || res->deleted) return;
  View v{*res, res->spec, res->status};

  if (v.status.get("phase").as_string().empty()) {
    metrics_.services_created++;
  }

  int desired = DesiredReplicas(v);
  desired = std::max(desired, 0);

  // Scale down: stop surplus replicas (highest index first).
  Json replicas = v.status.get("replicaState").is_array()
                      ? v.status.get("replicaState")
                      : Json::Array();
  if (static_cast<int>(replicas.size()) > desired) {
    for (int i = static_cast<int>(replicas.size()) - 1; i >= desired; --i) {
      StopReplica(v, i);
    }
    Json trimmed = Json::Array();
    for (int i = 0; i < desired; ++i) {
      trimmed.push_back(replicas.elements()[i]);
    }
    v.status["replicaState"] = trimmed;
  }
  // Scale up / keep alive.
  for (int i = 0; i < desired; ++i) {
    EnsureReplica(v, i);
  }

  // Aggregate status + endpoints.
  int running = 0, ready = 0;
  Json endpoints = Json::Array();
  const Json& rss = v.status.get("replicaState");
  if (rss.is_array()) {
    for (size_t i = 0; i < rss.size(); ++i) {
      const Json& rs = rss.elements()[i];
      if (!rs.is_object() || !rs.get("id").is_string()) continue;
      auto st = executor_->Status(rs.get("id").as_string());
      if (st.phase == ProcessStatus::Phase::kRunning) {
        ++running;
        if (rs.get("ready").as_bool(false)) {
          ++ready;
          Json ep = Json::Object();
          ep["replica"] = static_cast<int>(i);
          ep["url"] = "http://127.0.0.1:" +
                      std::to_string(rs.get("port").as_int());
          if (rs.get("grpc_port").is_number()) {
            ep["grpc"] = "127.0.0.1:" +
                         std::to_string(rs.get("grpc_port").as_int());
          }
          endpoints.push_back(ep);
        }
      }
    }
  }
  Json counts = Json::Object();
  counts["desired"] = desired;
  counts["running"] = running;
  counts["ready"] = ready;
  v.status["replicas"] = counts;

  // Canary rollout (KServe canaryTrafficPercent): spec.canary =
  // {model_dir, traffic_percent, replicas?} materializes a shadow
  // "<name>-canary" service running the candidate model; the primary's
  // endpoint list carries BOTH tracks with traffic weights. Promote =
  // update spec.model.model_dir to the canary dir and drop spec.canary
  // (replicas roll to the new model); rollback = drop spec.canary.
  const Json& canary = v.spec.get("canary");
  const std::string child_name = name + "-canary";
  const bool is_child = !v.spec.get("canary_of").as_string().empty();
  if (!is_child && canary.is_object() &&
      !canary.get("model_dir").as_string().empty()) {
    int64_t pct = canary.get("traffic_percent").as_int(10);
    pct = std::max<int64_t>(0, std::min<int64_t>(100, pct));
    Json cspec = Json::Object();
    for (const auto& [k, val] : v.spec.items()) {
      if (k == "canary" || k == "min_replicas" || k == "max_replicas" ||
          k == "target_rps") {
        continue;  // the canary track doesn't autoscale
      }
      cspec[k] = val;
    }
    Json cmodel = v.spec.get("model");
    cmodel["model_dir"] = canary.get("model_dir");
    cspec["model"] = cmodel;
    cspec["replicas"] = canary.get("replicas").as_int(1);
    cspec["canary_of"] = name;
    auto child = store_->Get("InferenceService", child_name);
    if (child && child->spec.get("canary_of").as_string() != name) {
      // A pre-existing unrelated service holds the shadow's name: refuse
      // to adopt it (updating would hijack — and later delete — a user's
      // service); surface the conflict instead.
      Json cstat = Json::Object();
      cstat["error"] = "canary blocked: service " + child_name +
                       " already exists and is not this service's shadow";
      v.status["canary"] = cstat;
    } else {
      if (!child) {
        store_->Create("InferenceService", child_name, cspec);
        metrics_.canary_rollouts++;
      } else if (child->spec.dump() != cspec.dump()) {
        store_->UpdateSpec("InferenceService", child_name, cspec);
      }
      // Weighted endpoint union: stable gets 100-pct, canary pct.
      Json weighted = Json::Array();
      for (const auto& ep : endpoints.elements()) {
        Json e = ep;
        e["track"] = "stable";
        e["weight"] = 100 - pct;
        weighted.push_back(e);
      }
      int canary_ready = 0;
      if (child) {
        for (const auto& ep : child->status.get("endpoints").elements()) {
          Json e = ep;
          e["track"] = "canary";
          e["weight"] = pct;
          weighted.push_back(e);
          ++canary_ready;
        }
      }
      endpoints = weighted;
      Json cstat = Json::Object();
      cstat["service"] = child_name;
      cstat["traffic_percent"] = pct;
      cstat["ready"] = canary_ready;
      v.status["canary"] = cstat;
    }
  } else if (!is_child) {
    // No canary configured: tear down a stale child of ours.
    auto child = store_->Get("InferenceService", child_name);
    if (child && child->spec.get("canary_of").as_string() == name) {
      store_->Delete("InferenceService", child_name);
    }
    if (v.status.has("canary")) v.status["canary"] = Json();
  }
  v.status["endpoints"] = endpoints;

  std::string phase;
  if (desired == 0) {
    // Idle = reaped by scale-to-zero (wake brings it back); Ready =
    // scaled to zero by hand.
    phase = v.status.get("idle").as_bool(false) ? "Idle" : "Ready";
  } else if (ready == desired) {
    phase = "Ready";
  } else if (running > 0) {
    phase = "Running";
  } else {
    phase = "Pending";
  }
  const std::string prev = v.status.get("phase").as_string();
  v.status["phase"] = phase;
  if (prev != phase) {
    if (!v.status.has("conditions")) v.status["conditions"] = Json::Array();
    Json cond = Json::Object();
    cond["type"] = phase;
    cond["status"] = "True";
    cond["reason"] = phase == "Ready"  ? "AllReplicasReady"
                     : phase == "Idle" ? "ScaledToZero"
                                       : "Reconciling";
    cond["message"] = std::to_string(ready) + "/" +
                      std::to_string(desired) + " replicas ready";
    cond["lastTransitionTime"] = Timestamp(now_s_);
    v.status["conditions"].push_back(cond);
    // Services have no terminal phase, so a crash-looping one flaps
    // forever: keep only the newest conditions or the status (and every
    // WAL rewrite of it) grows without bound.
    const Json& conds = v.status.get("conditions");
    if (conds.size() > 20) {
      Json trimmed = Json::Array();
      for (size_t i = conds.size() - 20; i < conds.size(); ++i) {
        trimmed.push_back(conds.elements()[i]);
      }
      v.status["conditions"] = trimmed;
    }
  }

  if (v.status.dump() != res->status.dump()) {
    store_->UpdateStatus("InferenceService", name, v.status);
  }
}

void ServeController::Tick(double now_s) {
  now_s_ = now_s;
  for (const auto& res : store_->List("InferenceService")) {
    Reconcile(res.name);
  }
}

void ServeController::OnDeleted(const Resource& res) {
  const Json& replicas = res.status.get("replicaState");
  if (replicas.is_array()) {
    for (const auto& rs : replicas.elements()) {
      if (!rs.is_object()) continue;
      if (rs.get("id").is_string()) {
        executor_->Kill(rs.get("id").as_string());
      }
      if (rs.get("alloc").is_object() && rs.get("alloc").size() > 0) {
        scheduler_->Release(AllocFromJson(rs.get("alloc")));
      }
    }
  }
  // Deleting a primary cascades to its canary shadow (whose own kDeleted
  // event then kills the canary replicas through this same path).
  const std::string child_name = res.name + "-canary";
  auto child = store_->Get("InferenceService", child_name);
  if (child && child->spec.get("canary_of").as_string() == res.name) {
    store_->Delete("InferenceService", child_name);
  }
}

void ServeController::Recover() {
  // Orphaned server processes from a previous control-plane incarnation:
  // kill by recorded pid and relaunch fresh (allocations were rebuilt
  // empty with the scheduler).
  for (const auto& res : store_->List("InferenceService")) {
    const Json& replicas = res.status.get("replicaState");
    if (!replicas.is_array() || replicas.size() == 0) continue;
    for (const auto& rs : replicas.elements()) {
      int pid = static_cast<int>(
          rs.is_object() ? rs.get("pid").as_int(-1) : -1);
      // Whole process group, like JaxJobController::Recover — the server
      // may have forked helpers (storage initializer) that must die too.
      if (pid > 1) kill(-pid, SIGKILL);
    }
    Json status = res.status;
    status["replicaState"] = Json::Array();
    status["phase"] = "Pending";
    store_->UpdateStatus("InferenceService", res.name, status);
  }
}

// -- TrainedModel controller -------------------------------------------------

namespace {
// Re-post the async load if no readiness after this long (covers a lost
// POST or a server that failed mid-load and cleared its error on retry).
constexpr double kLoadRepostSeconds = 60.0;
}  // namespace

void TrainedModelController::Tick(double now_s) {
  now_s_ = now_s;
  for (const auto& res : store_->List("TrainedModel")) Reconcile(res.name);
}

void TrainedModelController::Reconcile(const std::string& name) {
  auto r = store_->Get("TrainedModel", name);
  if (!r) return;
  const Json& spec = r->spec;
  Json status = r->status;
  const std::string parent = spec.get("inference_service").as_string();
  const Json& model = spec.get("model");
  const std::string mname = model.get("name").as_string();
  const std::string mdir = model.get("model_dir").as_string();

  auto update = [&](Json& next) {
    if (next.dump() != r->status.dump()) {  // WAL writes only on change
      store_->UpdateStatus("TrainedModel", name, next);
    }
  };

  auto isvc = store_->Get("InferenceService", parent);
  if (!isvc) {
    status["phase"] = "Pending";
    status["message"] = "waiting for InferenceService " + parent;
    status["loaded"] = Json::Object();
    status["posted"] = Json::Object();
    update(status);
    return;
  }

  // Name collisions silently hijack the parent's (or a sibling's) model in
  // the shared repository — reject instead (first created wins; creation
  // order via resource id).
  if (isvc->spec.get("model").get("name").as_string() == mname) {
    status["phase"] = "Failed";
    status["message"] = "model.name " + mname +
                        " collides with the parent's base model";
    update(status);
    return;
  }
  for (const auto& other : store_->List("TrainedModel")) {
    if (other.name == name) continue;
    if (other.spec.get("inference_service").as_string() == parent &&
        other.spec.get("model").get("name").as_string() == mname &&
        other.name < name) {  // deterministic winner (no creation ts kept)
      status["phase"] = "Failed";
      status["message"] = "model.name " + mname +
                          " collides with TrainedModel " + other.name;
      update(status);
      return;
    }
  }

  // Rename: RETIRE the previous name — unload it from every replica,
  // retrying across ticks until each current replica acknowledged (a
  // momentarily-unready replica must not keep the old model forever;
  // 404 counts as done — that server never had it, e.g. post-restart).
  const std::string prev = status.get("modelName").as_string();
  const Json& replicas = isvc->status.get("replicaState");
  Json retired = status.get("retired").is_object() ? status.get("retired")
                                                   : Json::Object();
  if (!prev.empty() && prev != mname) {
    if (!retired.has(prev)) retired[prev] = Json::Object();
    status["loaded"] = Json::Object();
    status["posted"] = Json::Object();
  }
  status["modelName"] = mname;
  if (replicas.is_array()) {
    Json retired_next = Json::Object();
    for (const auto& [rn, done0] : retired.items()) {
      if (rn == mname) continue;  // renamed back: live again, not retired
      Json done = done0.is_object() ? done0 : Json::Object();
      bool complete = true;
      for (const auto& rs : replicas.elements()) {
        if (!rs.is_object()) continue;
        const std::string key =
            std::to_string(rs.get("port").as_int()) + ":" +
            std::to_string(rs.get("pid").as_int(-1));
        if (done.get(key).as_bool(false)) continue;
        if (!rs.get("ready").as_bool(false)) {
          complete = false;  // retry when it comes back (or vanishes)
          continue;
        }
        int http = 0;
        if (probe_->Post(static_cast<int>(rs.get("port").as_int()),
                         "/v2/repository/models/" + rn + "/unload", "{}",
                         &http) &&
            (http / 100 == 2 || http == 404)) {
          done[key] = true;
          if (http / 100 == 2) metrics_.unloads++;
        } else {
          complete = false;
        }
      }
      if (!complete) retired_next[rn] = done;
    }
    retired = retired_next;
  }
  status["retired"] = retired;

  // Per-replica load state, keyed port:pid:spec-digest: a restarted
  // replica (new pid) re-loads, and a model_dir/name change (new digest)
  // re-loads on live replicas. Keys survive readiness blips — they are
  // pruned only when the replica itself is gone.
  // FNV-1a, not std::hash: std::hash is implementation-defined, so a
  // controller binary/stdlib upgrade would change every digest and
  // trigger a spurious re-load of every model on every replica.
  const std::string digest_src = mname + "|" + mdir;
  uint64_t fnv = 1469598103934665603ull;
  for (unsigned char c : digest_src) {
    fnv ^= c;
    fnv *= 1099511628211ull;
  }
  const std::string digest = std::to_string(fnv);
  const Json loaded_old = status.get("loaded").is_object()
                              ? status.get("loaded")
                              : Json::Object();
  const Json posted_old = status.get("posted").is_object()
                              ? status.get("posted")
                              : Json::Object();
  Json loaded = Json::Object();
  Json posted = Json::Object();
  int ready_n = 0, loaded_n = 0;
  if (replicas.is_array()) {
    Json payload = Json::Object();
    payload["model_dir"] = mdir;
    const std::string body = payload.dump();
    for (const auto& rs : replicas.elements()) {
      if (!rs.is_object()) continue;
      const int port = static_cast<int>(rs.get("port").as_int());
      const std::string key = std::to_string(port) + ":" +
                              std::to_string(rs.get("pid").as_int(-1)) +
                              ":" + digest;
      const bool was_loaded = loaded_old.get(key).as_bool(false);
      if (!rs.get("ready").as_bool(false)) {
        // Blip tolerance: a known-loaded replica that is momentarily
        // unready keeps its state — reloading a server that still has
        // the model would recompile for nothing.
        if (was_loaded) loaded[key] = true;
        continue;
      }
      ready_n++;
      if (was_loaded) {
        loaded[key] = true;
        loaded_n++;
        continue;
      }
      const double since = posted_old.get(key).as_number(0);
      // Readiness only counts AFTER we posted for this key: on a
      // model_dir change the server's previous version still answers
      // ready, and trusting it would skip the re-load entirely. (During
      // a version swap the old model serves until the new load lands —
      // readiness is optimistic for that window, by design.)
      if (since > 0 && probe_->ModelReady(port, mname, mdir)) {
        loaded[key] = true;
        loaded_n++;
        metrics_.loads++;
        continue;
      }
      if (since > 0 && now_s_ - since < kLoadRepostSeconds) {
        posted[key] = since;  // in flight; poll again next tick
        continue;
      }
      int http = 0;
      if (probe_->Post(port, "/v2/repository/models/" + mname + "/load",
                       body, &http) &&
          (http == 200 || http == 202)) {
        posted[key] = now_s_;
      } else {
        metrics_.load_failures++;  // retried next Tick
      }
    }
  }
  status["loaded"] = loaded;
  status["posted"] = posted;
  Json counts = Json::Object();
  counts["ready"] = ready_n;
  counts["loaded"] = loaded_n;
  status["replicas"] = counts;
  if (ready_n == 0) {
    status["phase"] = "Pending";
    status["message"] = "no ready replicas on " + parent;
  } else if (loaded_n == ready_n) {
    status["phase"] = "Ready";
    status["message"] = "";
  } else {
    status["phase"] = "Pending";
    status["message"] = "loading (" + std::to_string(loaded_n) + "/" +
                        std::to_string(ready_n) + " replicas)";
  }
  update(status);
}

void TrainedModelController::OnDeleted(const Resource& res) {
  // Best-effort unload from every replica that had it (the server marks
  // the model UNAVAILABLE; a vanished replica is already clean).
  const std::string parent = res.spec.get("inference_service").as_string();
  const std::string mname = res.spec.get("model").get("name").as_string();
  auto isvc = store_->Get("InferenceService", parent);
  if (!isvc || mname.empty()) return;
  const Json& replicas = isvc->status.get("replicaState");
  if (!replicas.is_array()) return;
  for (const auto& rs : replicas.elements()) {
    if (!rs.is_object() || !rs.get("ready").as_bool(false)) continue;
    int http = 0;
    if (probe_->Post(static_cast<int>(rs.get("port").as_int()),
                     "/v2/repository/models/" + mname + "/unload", "{}",
                     &http) &&
        http / 100 == 2) {
      metrics_.unloads++;
    }
  }
}

}  // namespace tpk
