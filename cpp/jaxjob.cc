#include "jaxjob.h"

#include "admission.h"

#include "events.h"

#include "util.h"

#include <netinet/in.h>
#include <sys/socket.h>
#include <signal.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <cstdio>
#include <ctime>

namespace tpk {

namespace {

bool IsTerminal(const std::string& phase) {
  return phase == "Succeeded" || phase == "Failed";
}

}  // namespace

JaxJobController::JaxJobController(Store* store, ExecutorInterface* executor,
                                   Scheduler* scheduler, std::string workdir,
                                   std::string python)
    : store_(store),
      executor_(executor),
      scheduler_(scheduler),
      workdir_(std::move(workdir)),
      python_(std::move(python)) {
  mkdir(workdir_.c_str(), 0755);
}

std::string JaxJobController::ProcId(const std::string& job, int replica) {
  return job + "/" + std::to_string(replica);
}

Allocation JaxJobController::AllocFromStatus(const Json& status) const {
  Allocation alloc;
  for (const auto& [name, n] : status.get("allocation").items()) {
    alloc.slices[name] = static_cast<int>(n.as_int());
  }
  return alloc;
}

namespace {

// The one normalization rule for tenancy lives in admission.h
// (SpecNamespace; Python mirror: controlplane/client.py namespace_of).
std::string NamespaceOf(const Json& spec) { return SpecNamespace(spec); }

// fsdp elasticity policy parsed from spec.elastic. Enabled iff
// elastic.min_fsdp >= 1 AND runtime.fsdp >= 1 (admission enforces both
// plus the divisibility contract; the re-checks here keep the controller
// safe against specs that predate admission).
struct FsdpPolicy {
  bool enabled = false;
  bool auto_resize = true;  // resize_policy "auto" (default) | "manual"
  int base = 0;             // runtime.fsdp as submitted
  int min = 0;              // elastic.min_fsdp
  int max = 0;              // elastic.max_fsdp (default: base)
};

FsdpPolicy FsdpPolicyOf(const Json& spec) {
  FsdpPolicy p;
  const Json& el = spec.get("elastic");
  if (!el.is_object()) return p;
  const int min_fsdp = static_cast<int>(el.get("min_fsdp").as_int(0));
  if (min_fsdp < 1) return p;
  const int base =
      static_cast<int>(spec.get("runtime").get("fsdp").as_int(0));
  if (base < 1) return p;
  p.enabled = true;
  p.base = base;
  p.min = min_fsdp;
  p.max = static_cast<int>(el.get("max_fsdp").as_int(base));
  if (p.max < base) p.max = base;
  p.auto_resize = el.get("resize_policy").as_string() != "manual";
  return p;
}

// Gang shape for an fsdp size. The fsdp axis spans the whole gang
// (admission pins runtime.fsdp == replicas * devices_per_proc), so a
// resize either drops workers at the spec'd per-proc device share
// (multi-worker downsize) or rescales the per-proc share across
// spec.replicas workers (single-proc CPU meshes, and upsizes past the
// base shape). Returns false when `fsdp` fits neither way — callers
// skip such candidates.
bool FsdpGangShape(const Json& spec, int fsdp, int* replicas, int* devices) {
  const int spec_r =
      std::max(1, static_cast<int>(spec.get("replicas").as_int(1)));
  const int dpp =
      std::max(1, static_cast<int>(spec.get("devices_per_proc").as_int(1)));
  if (fsdp >= dpp && fsdp % dpp == 0 && fsdp / dpp <= spec_r) {
    *replicas = fsdp / dpp;
    *devices = dpp;
    return true;
  }
  if (fsdp >= spec_r && fsdp % spec_r == 0) {
    *replicas = spec_r;
    *devices = fsdp / spec_r;
    return true;
  }
  return false;
}

// Largest resize target below `cur`: a divisor of max_fsdp (the
// master-state sharding plan is anchored there — every leaf dim the
// plan shards is divisible by max_fsdp, hence by any divisor, so the
// plan survives the resize), >= min_fsdp, expressible as a gang shape.
// 0 = no smaller topology exists.
int NextFsdpDown(const Json& spec, const FsdpPolicy& p, int cur) {
  int r = 0, d = 0;
  for (int t = std::min(cur - 1, p.max); t >= p.min; --t) {
    if (p.max % t != 0) continue;
    if (!FsdpGangShape(spec, t, &r, &d)) continue;
    return t;
  }
  return 0;
}

}  // namespace

void JaxJobController::SetPhase(JobView& job, const std::string& phase,
                                const std::string& reason,
                                const std::string& message, double now_s) {
  const std::string prev = job.status.get("phase").as_string();
  job.status["phase"] = phase;
  Json cond = Json::Object();
  cond["type"] = phase;
  cond["status"] = "True";
  cond["reason"] = reason;
  cond["message"] = message;
  cond["lastTransitionTime"] = Timestamp(now_s ? now_s : NowWall());
  if (!job.status.has("conditions")) job.status["conditions"] = Json::Array();
  const Json& conds = job.status.get("conditions");
  const std::string last_reason =
      conds.size() > 0
          ? conds.elements()[conds.size() - 1].get("reason").as_string()
          : "";
  // Record phase transitions AND reason changes within a phase (a Pending
  // job moving Unschedulable -> QuotaExceeded must not keep showing the
  // stale reason). Bounded: non-terminal reasons can flap.
  if (prev != phase || last_reason != reason) {
    job.status["conditions"].push_back(cond);
    if (job.status.get("conditions").size() > 20) {
      Json trimmed = Json::Array();
      const Json& all = job.status.get("conditions");
      for (size_t i = all.size() - 20; i < all.size(); ++i) {
        trimmed.push_back(all.elements()[i]);
      }
      job.status["conditions"] = trimmed;
    }
  }
}

void JaxJobController::AppendEvent(JobView& job, const std::string& type,
                                   const std::string& reason,
                                   const std::string& message,
                                   bool merge_same_reason) {
  job.status = AppendStatusEvent(job.status, type, reason, message,
                                 now_s_ ? now_s_ : NowWall(),
                                 merge_same_reason);
}

void JaxJobController::KillAll(const JobView& job) {
  int replicas = static_cast<int>(job.spec.get("replicas").as_int(1));
  for (int i = 0; i < replicas; ++i) {
    executor_->Kill(ProcId(job.res.name, i));
  }
}

void JaxJobController::ReleaseAlloc(JobView& job) {
  if (job.status.get("allocation").is_object() &&
      job.status.get("allocation").size() > 0) {
    scheduler_->Release(AllocFromStatus(job.status));
    job.status["allocation"] = Json::Object();
  }
}

void JaxJobController::ElasticResize(JobView& job, int target,
                                     const std::string& phase,
                                     const std::string& reason,
                                     const std::string& message,
                                     bool count_restart) {
  AppendEvent(job, "Normal", reason, message);
  job.status["effectiveReplicas"] = target;
  job.status["lastResizeUnix"] = now_s_ ? now_s_ : NowWall();
  if (count_restart) {
    job.status["restarts"] = job.status.get("restarts").as_int(0) + 1;
  }
  metrics_.elastic_resizes++;
  SetPhase(job, phase, reason, message, now_s_);
}

int64_t JaxJobController::UsedInNamespace(const std::string& ns,
                                          const std::string& exclude) const {
  int64_t used = 0;
  for (const auto& other : store_->List("JAXJob")) {
    if (other.name == exclude) continue;
    if (NamespaceOf(other.spec) != ns) continue;
    const Json& oalloc = other.status.get("allocation");
    if (oalloc.is_object() && oalloc.size() > 0) {
      for (const auto& [slice, n] : oalloc.items()) {
        (void)slice;
        used += n.as_int();
      }
    }
  }
  return used;
}

int JaxJobController::EffectiveReplicas(const JobView& job) const {
  int spec_r = static_cast<int>(job.spec.get("replicas").as_int(1));
  int eff = static_cast<int>(
      job.status.get("effectiveReplicas").as_int(spec_r));
  if (eff < 1) eff = 1;
  if (eff > spec_r) eff = spec_r;
  return eff;
}

int JaxJobController::EffectiveFsdp(const JobView& job) const {
  const FsdpPolicy p = FsdpPolicyOf(job.spec);
  if (!p.enabled) return 0;
  int eff = static_cast<int>(job.status.get("effectiveFsdp").as_int(p.base));
  if (eff < p.min) eff = p.min;
  if (eff > p.max) eff = p.max;
  return eff;
}

void JaxJobController::ElasticResizeFsdp(JobView& job, int from, int target,
                                         const std::string& phase,
                                         const std::string& reason,
                                         const std::string& detail,
                                         bool count_restart) {
  int from_r = 0, from_d = 0, to_r = 0, to_d = 0;
  FsdpGangShape(job.spec, from, &from_r, &from_d);
  FsdpGangShape(job.spec, target, &to_r, &to_d);
  // The event carries the old -> new topology in full (fsdp axis AND
  // the derived gang shape); merge is disabled so two distinct
  // transitions sharing this reason stay two entries (events.h).
  const std::string message =
      "fsdp " + std::to_string(from) + " -> " + std::to_string(target) +
      " (gang " + std::to_string(from_r) + "x" + std::to_string(from_d) +
      " -> " + std::to_string(to_r) + "x" + std::to_string(to_d) +
      " procs x devices): " + detail;
  AppendEvent(job, "Normal", reason, message, /*merge_same_reason=*/false);
  job.status["effectiveFsdp"] = target;
  if (to_r >= 1) job.status["effectiveReplicas"] = to_r;
  job.status["lastResizeUnix"] = now_s_ ? now_s_ : NowWall();
  if (count_restart) {
    job.status["restarts"] = job.status.get("restarts").as_int(0) + 1;
  }
  metrics_.elastic_resizes++;
  SetPhase(job, phase, reason, message, now_s_);
}

void JaxJobController::LaunchGang(JobView& job) {
  const std::string& name = job.res.name;
  int replicas = EffectiveReplicas(job);
  int devices = static_cast<int>(job.spec.get("devices_per_proc").as_int(1));
  int num_slices = static_cast<int>(job.spec.get("num_slices").as_int(1));
  const int spec_devices = devices;
  const FsdpPolicy fsdp_policy = FsdpPolicyOf(job.spec);
  const int eff_fsdp = fsdp_policy.enabled ? EffectiveFsdp(job) : 0;
  if (eff_fsdp >= 1) {
    // fsdp-elastic gangs derive their shape from the effective fsdp
    // size — the axis spans the gang's devices, so a resize is a new
    // (replicas, devices_per_proc) pair, re-derived here every launch
    // (status survives controller restarts; the shape must too).
    int r = 0, d = 0;
    if (FsdpGangShape(job.spec, eff_fsdp, &r, &d)) {
      replicas = r;
      devices = d;
    }
  }

  // Namespace device quota — the Profile-controller stub (SURVEY.md §2.5
  // row "Profile", §7.4 descope: namespace field + quota, no RBAC/Istio).
  // A Profile resource named like the namespace caps the devices its
  // running JAXJobs may hold; jobs without a namespace live in "default".
  const std::string ns = NamespaceOf(job.spec);
  auto profile = store_->Get("Profile", ns);
  if (profile) {
    int64_t quota = profile->spec.get("max_devices").as_int(-1);
    if (quota >= 0) {
      int64_t used = UsedInNamespace(ns, name);
      if (used + static_cast<int64_t>(replicas) * devices > quota) {
        AppendEvent(job, "Warning", "QuotaExceeded",
                    "namespace " + ns + " quota " + std::to_string(quota) +
                        " devices; " + std::to_string(used) + " in use");
        SetPhase(job, "Pending", "QuotaExceeded",
                 "namespace " + ns + " quota " + std::to_string(quota) +
                     " devices; " + std::to_string(used) + " in use",
                 now_s_);
        return;
      }
    }
  }

  auto alloc = scheduler_->Allocate(replicas * devices, num_slices);
  if (!alloc) {
    // Elastic downsize on scarce capacity: rather than pending forever at
    // the full size, walk the gang down toward elastic.min one step per
    // reconcile — the checkpoint-resume path reshards to whatever size
    // finally fits (SURVEY.md §2.6 Elastic DP).
    if (fsdp_policy.enabled && fsdp_policy.auto_resize &&
        eff_fsdp > fsdp_policy.min) {
      const int t = NextFsdpDown(job.spec, fsdp_policy, eff_fsdp);
      if (t >= 1) {
        // No gang attempt was consumed — the workers never launched.
        ElasticResizeFsdp(job, eff_fsdp, t, "Pending", "ElasticDownsize",
                          "insufficient capacity; retrying smaller",
                          /*count_restart=*/false);
        return;
      }
    }
    const Json& el = job.spec.get("elastic");
    int min_r = static_cast<int>(el.get("min").as_int(0));
    if (el.is_object() && min_r >= 1 && replicas > min_r) {
      // No gang attempt was consumed — the workers never launched.
      ElasticResize(job, replicas - 1, "Pending", "ElasticDownsize",
                    "insufficient capacity for " + std::to_string(replicas) +
                        " workers; retrying at " +
                        std::to_string(replicas - 1),
                    /*count_restart=*/false);
      return;
    }
    AppendEvent(job, "Warning", "Unschedulable",
                "insufficient slice capacity for gang");
    SetPhase(job, "Pending", "Unschedulable",
             "insufficient slice capacity for gang", now_s_);
    return;
  }
  // Allocation granted — the "Scheduled" moment (kube-scheduler's Bind
  // event analog): record which slices host the gang.
  {
    std::string placed;
    for (const auto& [slice, n] : alloc->slices) {
      if (!placed.empty()) placed += ",";
      placed += slice + "=" + std::to_string(n);
    }
    AppendEvent(job, "Normal", "Scheduled",
                std::to_string(replicas) + " worker(s) on " + placed);
  }

  // Job workdir: spec file + per-replica logs.
  std::string dir = workdir_ + "/" + name;
  mkdir(dir.c_str(), 0755);
  std::string spec_path = dir + "/runtime.json";
  {
    Json runtime = job.spec.get("runtime");
    // An fsdp resize lands in the worker through runtime.json: the
    // relaunched gang reads the resized topology at startup and
    // reshards its checkpoint to it — the spec itself is never edited
    // (the submitted runtime.fsdp stays the declared intent).
    if (eff_fsdp >= 1 && runtime.is_object() &&
        static_cast<int>(runtime.get("fsdp").as_int(0)) != eff_fsdp) {
      runtime["fsdp"] = eff_fsdp;
      if (runtime.get("mesh").is_object() &&
          runtime.get("mesh").has("fsdp")) {
        Json mesh = runtime.get("mesh");
        mesh["fsdp"] = eff_fsdp;
        runtime["mesh"] = mesh;
      }
    }
    FILE* f = fopen(spec_path.c_str(), "w");
    if (f) {
      std::string text = runtime.is_null() ? "{}" : runtime.dump();
      bool ok = fwrite(text.data(), 1, text.size(), f) == text.size();
      ok = fclose(f) == 0 && ok;
      // A torn spec must not reach the worker: a missing file fails the
      // replica loudly at startup instead of silently training a
      // truncated runtime config.
      if (!ok) remove(spec_path.c_str());
    }
  }

  int port = FreePort();
  std::string coordinator = "127.0.0.1:" + std::to_string(port);
  int cpu_devices =
      static_cast<int>(job.spec.get("cpu_devices_per_proc").as_int(0));
  // CPU meshes virtualize devices per proc — an fsdp resize must scale
  // the virtual-device count with the per-proc device share or the
  // relaunched worker would build the old mesh.
  if (cpu_devices > 0 && eff_fsdp >= 1 && devices != spec_devices &&
      (cpu_devices * devices) % spec_devices == 0) {
    cpu_devices = cpu_devices * devices / spec_devices;
  }

  std::vector<LaunchSpec> specs;
  for (int i = 0; i < replicas; ++i) {
    LaunchSpec s;
    s.id = ProcId(name, i);
    s.argv = {python_, "-m", "kubeflow_tpu.train.trainer", "--spec",
              spec_path};
    if (cpu_devices > 0) {
      s.argv.push_back("--cpu-devices");
      s.argv.push_back(std::to_string(cpu_devices));
      // Custom-command workers (e.g. the pipeline launcher) don't get
      // the --cpu-devices flag (the default argv is replaced below); the
      // launcher honors the env form instead (pipelines/launcher.py).
      s.env["TPK_CPU_DEVICES"] = std::to_string(cpu_devices);
    }
    if (job.spec.get("command").is_array()) {
      s.argv.clear();
      for (const auto& a : job.spec.get("command").elements()) {
        s.argv.push_back(a.as_string());
      }
    }
    if (replicas > 1) {
      s.env["TPK_COORDINATOR"] = coordinator;
    }
    s.env["TPK_NUM_PROCS"] = std::to_string(replicas);
    s.env["TPK_PROC_ID"] = std::to_string(i);
    s.env["TPK_NUM_SLICES"] = std::to_string(num_slices);
    s.env["TPK_SLICE_ID"] = std::to_string(i * num_slices / replicas);
    s.env["TPK_JOB_NAME"] = name;
    // The job's workdir (profiler traces land here: the runtime's
    // profile_start_step/profile_stop_step knobs default their trace
    // dir to $TPK_WORKDIR/profile) and the API socket (the runtime
    // posts CheckpointSaved events back into the job's event log).
    s.env["TPK_WORKDIR"] = dir;
    if (!socket_path_.empty()) {
      s.env["TPK_SOCKET"] = socket_path_;
    }
    // First-class fault injection (SURVEY.md §5.3): spec.fault =
    // {proc, step, signal?, every_attempt?} makes worker `proc` kill
    // itself at training step `step` — deterministic, step-precise chaos
    // replacing test-side pgrep/kill timing. By default the fault fires
    // only on the first attempt so the restarted gang can make progress.
    const Json& fault = job.spec.get("fault");
    if (fault.is_object() &&
        static_cast<int>(fault.get("proc").as_int(0)) == i &&
        (fault.get("every_attempt").as_bool(false) ||
         job.status.get("restarts").as_int(0) == 0)) {
      s.env["TPK_FAULT"] =
          "step=" + std::to_string(fault.get("step").as_int(0)) +
          ";signal=" + std::to_string(fault.get("signal").as_int(9));
    }
    s.stdout_path = dir + "/worker-" + std::to_string(i) + ".log";
    s.stderr_path = dir + "/worker-" + std::to_string(i) + ".err";
    specs.push_back(std::move(s));
  }

  std::string error;
  if (!executor_->LaunchGang(specs, &error)) {
    scheduler_->Release(*alloc);
    AppendEvent(job, "Warning", "LaunchFailed", error);
    SetPhase(job, "Pending", "LaunchFailed", error, now_s_);
    return;
  }

  Json alloc_json = Json::Object();
  for (const auto& [slice, n] : alloc->slices) alloc_json[slice] = n;
  job.status["allocation"] = alloc_json;
  job.status["coordinator"] = coordinator;
  job.status["active"] = true;
  // Record worker pids so a restarted control plane can reap the orphans
  // it can no longer waitpid (Recover()).
  Json pids = Json::Array();
  for (int i = 0; i < replicas; ++i) {
    pids.push_back(executor_->Status(ProcId(name, i)).pid);
  }
  job.status["pids"] = pids;
  if (!job.status.has("startTime")) {
    job.status["startTime"] = Timestamp(now_s_ ? now_s_ : NowWall());
    job.status["startUnix"] = now_s_ ? now_s_ : NowWall();
  }
  AppendEvent(job, "Normal", "Launched",
              "all " + std::to_string(replicas) + " workers launched");
  SetPhase(job, "Running", "GangLaunched",
           "all " + std::to_string(replicas) + " workers launched", now_s_);
}

void JaxJobController::HandleExits(JobView& job) {
  const std::string& name = job.res.name;
  int replicas = EffectiveReplicas(job);
  int succeeded = 0, failed = 0, running = 0;
  int first_fail_code = 0;
  for (int i = 0; i < replicas; ++i) {
    auto st = executor_->Status(ProcId(name, i));
    switch (st.phase) {
      case ProcessStatus::Phase::kSucceeded: ++succeeded; break;
      case ProcessStatus::Phase::kFailed:
        ++failed;
        if (!first_fail_code) first_fail_code = st.exit_code;
        break;
      case ProcessStatus::Phase::kRunning: ++running; break;
      case ProcessStatus::Phase::kPending: break;
    }
  }
  Json pstat = Json::Object();
  pstat["succeeded"] = succeeded;
  pstat["failed"] = failed;
  pstat["running"] = running;
  job.status["processes"] = pstat;

  if (succeeded == replicas) {
    job.status["active"] = false;
    ReleaseAlloc(job);
    job.status["completionUnix"] = now_s_ ? now_s_ : NowWall();
    AppendEvent(job, "Normal", "Succeeded", "all workers exited 0");
    SetPhase(job, "Succeeded", "AllWorkersSucceeded",
             "all workers exited 0", now_s_);
    metrics_.jobs_succeeded++;
    return;
  }
  if (failed == 0) return;  // still running

  // A worker failed: gang semantics = kill the rest, then decide restart.
  KillAll(job);
  job.status["active"] = false;
  ReleaseAlloc(job);

  const std::string policy =
      job.spec.get("restart_policy").as_string().empty()
          ? "OnFailure"
          : job.spec.get("restart_policy").as_string();
  int64_t backoff = job.spec.get("backoff_limit").as_int(3);
  int64_t restarts = job.status.get("restarts").as_int(0);

  bool retryable = policy == "OnFailure";
  if (policy == "ExitCode") {
    // Upstream training-operator semantics: 1–127 permanent, 128+ retryable.
    retryable = first_fail_code >= 128;
  }
  if (retryable && restarts < backoff) {
    job.status["restarts"] = restarts + 1;
    metrics_.gang_restarts++;
    // ONE event per restart cycle (failure + restart together). Each
    // relaunch still appends Scheduled/Launched between cycles, so
    // cycles don't merge — but total restart history is bounded by
    // backoff_limit (3 events per cycle), and past the 48-entry cap the
    // oldest entries expire like upstream Events; conditions keep the
    // phase transitions.
    AppendEvent(job, "Warning", "Restarted",
                "worker exited " + std::to_string(first_fail_code) +
                    "; gang restart " + std::to_string(restarts + 1) +
                    "/" + std::to_string(backoff));
    SetPhase(job, "Restarting", "WorkerFailed",
             "worker exited " + std::to_string(first_fail_code) +
                 "; gang restart " + std::to_string(restarts + 1) + "/" +
                 std::to_string(backoff),
             now_s_);
    // Relaunch happens on the next Reconcile pass (status write below
    // triggers a watch event → reconcile).
    return;
  }
  // Worker death past the backoff budget: instead of failing the job, an
  // elastic policy resumes at a smaller topology from the latest
  // checkpoint — params reshard to the new mesh (the e2e-proven
  // checkpoint-restart elasticity, now with an automatic trigger;
  // SURVEY.md §2.6 Elastic DP / §5.3 ElasticPolicy analog).
  if (retryable) {
    // fsdp elasticity first: the resize unit is the fsdp axis — pick the
    // next divisor of max_fsdp down (the master-state plan survives any
    // divisor), derive the gang shape, and let the relaunch reshard the
    // checkpoint. Mutually exclusive with replica elasticity (admission).
    const FsdpPolicy fp = FsdpPolicyOf(job.spec);
    const int cur_fsdp = fp.enabled ? EffectiveFsdp(job) : 0;
    if (fp.enabled && fp.auto_resize && cur_fsdp > fp.min) {
      const int target = NextFsdpDown(job.spec, fp, cur_fsdp);
      if (target >= 1) {
        // count_restart: this consumed a gang attempt — per-attempt
        // gates (spec.fault's first-attempt default) must see a nonzero
        // count or the fault would re-arm on every elastic relaunch.
        ElasticResizeFsdp(
            job, cur_fsdp, target, "Restarting", "ElasticDownsize",
            std::to_string(failed) + " worker exit(s) past backoff "
                "(first exit " + std::to_string(first_fail_code) +
                "); resuming from latest checkpoint",
            /*count_restart=*/true);
        return;
      }
    }
    const Json& el = job.spec.get("elastic");
    int min_r = static_cast<int>(el.get("min").as_int(0));
    if (el.is_object() && min_r >= 1 && replicas > min_r) {
      int target = replicas - failed;
      if (target < min_r) target = min_r;
      if (target < 1) target = 1;
      // count_restart: this consumed a gang attempt — per-attempt gates
      // (spec.fault's first-attempt default) must see a nonzero count or
      // the fault would re-arm on every elastic relaunch.
      ElasticResize(job, target, "Restarting", "ElasticDownsize",
                    std::to_string(failed) + " worker(s) lost past "
                        "backoff; resuming at " + std::to_string(target) +
                        "/" +
                        std::to_string(job.spec.get("replicas").as_int(1)) +
                        " from latest checkpoint",
                    /*count_restart=*/true);
      return;
    }
  }
  job.status["completionUnix"] = now_s_ ? now_s_ : NowWall();
  AppendEvent(job, "Warning", "Failed",
              std::string(retryable ? "BackoffLimitExceeded"
                                    : "PermanentFailure") +
                  ": worker exited " + std::to_string(first_fail_code));
  SetPhase(job, "Failed",
           retryable ? "BackoffLimitExceeded" : "PermanentFailure",
           "worker exited " + std::to_string(first_fail_code), now_s_);
  metrics_.jobs_failed++;
}

void JaxJobController::CheckHeartbeats(JobView& job) {
  // Hang detection: a worker that stops writing its log for longer than
  // elastic.heartbeat_timeout_s is treated as dead (the failure detector
  // for workers that wedge without exiting — e.g. a hung collective).
  // Killing it routes through the normal gang-failure path: restart
  // within backoff, elastic downsize past it. Wall-clock on purpose —
  // log mtimes are wall time. The timeout must exceed the job's slowest
  // logging interval (log_every steps).
  const Json& el = job.spec.get("elastic");
  double timeout = el.get("heartbeat_timeout_s").as_number(0);
  if (!(timeout > 0)) return;
  int replicas = EffectiveReplicas(job);
  double now_wall = NowWall();
  for (int i = 0; i < replicas; ++i) {
    std::string log_path = workdir_ + "/" + job.res.name + "/worker-" +
                           std::to_string(i) + ".log";
    struct stat st;
    if (stat(log_path.c_str(), &st) != 0) continue;  // not spawned by us
    double age = now_wall - static_cast<double>(st.st_mtime);
    if (age > timeout) {
      AppendEvent(job, "Warning", "HeartbeatTimeout",
                  "worker " + std::to_string(i) + " silent for " +
                      std::to_string(static_cast<int>(age)) +
                      "s; killing for gang restart");
      SetPhase(job, "Running", "HeartbeatTimeout",
               "worker " + std::to_string(i) + " silent for " +
                   std::to_string(static_cast<int>(age)) + "s (timeout " +
                   std::to_string(static_cast<int>(timeout)) +
                   "s); killing for gang restart",
               now_s_);
      executor_->Kill(ProcId(job.res.name, i));
    }
  }
}

void JaxJobController::MaybeUpsize(JobView& job) {
  // Capacity-driven upsize: a gang running below its desired size (after
  // an elastic downsize) grows back when freed devices can host it —
  // kill, release, relaunch larger; the runtime resumes from the latest
  // checkpoint and reshards up. Cooldown prevents thrash with the
  // downsize path.
  const Json& el = job.spec.get("elastic");
  if (!el.is_object()) return;
  if (FsdpPolicyOf(job.spec).enabled) {
    // fsdp-elastic gangs regrow along the fsdp axis, never the replica
    // path — effectiveReplicas is derived state here and the replica
    // upsize would fight the fsdp shape.
    MaybeUpsizeFsdp(job);
    return;
  }
  int spec_r = static_cast<int>(job.spec.get("replicas").as_int(1));
  int cap = static_cast<int>(el.get("max").as_int(spec_r));
  if (cap > spec_r) cap = spec_r;
  int eff = EffectiveReplicas(job);
  if (eff >= cap) return;
  double cooldown = el.get("upsize_cooldown_s").as_number(30.0);
  double last = job.status.get("lastResizeUnix").as_number(0);
  double now = now_s_ ? now_s_ : NowWall();
  if (last > 0 && now - last < cooldown) return;
  int devices = static_cast<int>(job.spec.get("devices_per_proc").as_int(1));
  int num_slices = static_cast<int>(job.spec.get("num_slices").as_int(1));

  // Find the largest target the scheduler would ACTUALLY grant by
  // probing real allocations (release current, try bigger, put a
  // same-size allocation back on failure). A free-device sum would
  // ignore per-slice fragmentation and num_slices divisibility and kill
  // a healthy gang for an upsize that can never launch. Single-threaded
  // controller: nothing races the probe.
  Allocation current = AllocFromStatus(job.status);
  scheduler_->Release(current);
  int target = 0;
  std::optional<Allocation> probe;
  for (int t = cap; t > eff; --t) {
    probe = scheduler_->Allocate(t * devices, num_slices);
    if (probe) {
      target = t;
      break;
    }
  }
  if (target == 0) {
    // Nothing bigger fits — restore the books for the running gang.
    auto back = scheduler_->Allocate(eff * devices, num_slices);
    if (back) {
      Json alloc_json = Json::Object();
      for (const auto& [slice, n] : back->slices) alloc_json[slice] = n;
      job.status["allocation"] = alloc_json;
    }
    return;
  }
  scheduler_->Release(*probe);  // LaunchGang re-allocates for real

  // Namespace quota headroom must admit the bigger gang too, or the
  // killed job would land in Pending/QuotaExceeded with zero workers.
  const std::string ns = NamespaceOf(job.spec);
  auto profile = store_->Get("Profile", ns);
  int64_t quota =
      profile ? profile->spec.get("max_devices").as_int(-1) : -1;
  if (quota >= 0 && UsedInNamespace(ns, job.res.name) +
                            static_cast<int64_t>(target) * devices >
                        quota) {
    auto back = scheduler_->Allocate(eff * devices, num_slices);
    if (back) {
      Json alloc_json = Json::Object();
      for (const auto& [slice, n] : back->slices) alloc_json[slice] = n;
      job.status["allocation"] = alloc_json;
    }
    return;
  }

  KillAll(job);
  job.status["active"] = false;
  job.status["allocation"] = Json::Object();  // already released above
  ElasticResize(job, target, "Restarting", "ElasticUpsize",
                "capacity freed; growing " + std::to_string(eff) + " -> " +
                    std::to_string(target) +
                    " workers, resuming from latest checkpoint",
                /*count_restart=*/false);
}

void JaxJobController::MaybeUpsizeFsdp(JobView& job) {
  // The fsdp twin of MaybeUpsize: a gang resized below max_fsdp grows
  // back when freed devices can host a bigger divisor — kill, release,
  // relaunch; the runtime reshards its checkpoint up. Same probe
  // discipline (real allocations, restore the books on failure) and the
  // same cooldown keyed on lastResizeUnix to prevent thrash.
  const FsdpPolicy fp = FsdpPolicyOf(job.spec);
  if (!fp.enabled || !fp.auto_resize) return;
  const int cur = EffectiveFsdp(job);
  if (cur >= fp.max) return;
  const Json& el = job.spec.get("elastic");
  double cooldown = el.get("upsize_cooldown_s").as_number(30.0);
  double last = job.status.get("lastResizeUnix").as_number(0);
  double now = now_s_ ? now_s_ : NowWall();
  if (last > 0 && now - last < cooldown) return;
  int num_slices = static_cast<int>(job.spec.get("num_slices").as_int(1));
  int cur_r = 0, cur_d = 0;
  if (!FsdpGangShape(job.spec, cur, &cur_r, &cur_d)) return;

  Allocation current = AllocFromStatus(job.status);
  scheduler_->Release(current);
  int target = 0, tgt_r = 0, tgt_d = 0;
  std::optional<Allocation> probe;
  for (int t = fp.max; t > cur; --t) {
    if (fp.max % t != 0) continue;
    int r = 0, d = 0;
    if (!FsdpGangShape(job.spec, t, &r, &d)) continue;
    probe = scheduler_->Allocate(r * d, num_slices);
    if (probe) {
      target = t;
      tgt_r = r;
      tgt_d = d;
      break;
    }
  }
  if (target == 0) {
    auto back = scheduler_->Allocate(cur_r * cur_d, num_slices);
    if (back) {
      Json alloc_json = Json::Object();
      for (const auto& [slice, n] : back->slices) alloc_json[slice] = n;
      job.status["allocation"] = alloc_json;
    }
    return;
  }
  scheduler_->Release(*probe);  // LaunchGang re-allocates for real

  const std::string ns = NamespaceOf(job.spec);
  auto profile = store_->Get("Profile", ns);
  int64_t quota =
      profile ? profile->spec.get("max_devices").as_int(-1) : -1;
  if (quota >= 0 && UsedInNamespace(ns, job.res.name) +
                            static_cast<int64_t>(tgt_r) * tgt_d >
                        quota) {
    auto back = scheduler_->Allocate(cur_r * cur_d, num_slices);
    if (back) {
      Json alloc_json = Json::Object();
      for (const auto& [slice, n] : back->slices) alloc_json[slice] = n;
      job.status["allocation"] = alloc_json;
    }
    return;
  }

  KillAll(job);
  job.status["active"] = false;
  job.status["allocation"] = Json::Object();  // already released above
  ElasticResizeFsdp(job, cur, target, "Restarting", "ElasticUpsize",
                    "capacity freed; resuming from latest checkpoint",
                    /*count_restart=*/false);
}

bool JaxJobController::MaybeApplyFsdpTarget(JobView& job) {
  // Explicit resize request: elastic.target_fsdp on a Running gang.
  // status.fsdpTargetApplied latches the last honored value — the
  // request fires once per distinct target, so automatic resizes that
  // later move effectiveFsdp away don't re-trigger a stale request.
  const FsdpPolicy fp = FsdpPolicyOf(job.spec);
  if (!fp.enabled) return false;
  const int target = static_cast<int>(
      job.spec.get("elastic").get("target_fsdp").as_int(0));
  if (target < fp.min || target > fp.max || fp.max % target != 0) {
    return false;  // admission refuses these; stale specs just no-op
  }
  const int applied = static_cast<int>(
      job.status.get("fsdpTargetApplied").as_int(0));
  if (target == applied) return false;
  const int cur = EffectiveFsdp(job);
  if (target == cur) {
    job.status["fsdpTargetApplied"] = target;  // already there: latch only
    return false;
  }
  int r = 0, d = 0;
  if (!FsdpGangShape(job.spec, target, &r, &d)) return false;
  KillAll(job);
  job.status["active"] = false;
  ReleaseAlloc(job);
  job.status["fsdpTargetApplied"] = target;
  ElasticResizeFsdp(job, cur, target, "Restarting", "ElasticResizeRequested",
                    "explicit resize request", /*count_restart=*/false);
  return true;
}

void JaxJobController::Recover() {
  // Control-plane restart with a WAL: jobs marked active reference worker
  // processes this process never spawned (reparented orphans) and slice
  // allocations in a scheduler that was rebuilt empty. Kill the orphans
  // (best effort, by recorded pgid), drop the stale allocation, and mark
  // the gang Restarting — the relaunch resumes from the latest checkpoint.
  for (const auto& res : store_->List("JAXJob")) {
    JobView job{res, res.spec, res.status};
    if (!job.status.get("active").as_bool(false)) continue;
    for (const auto& p : job.status.get("pids").elements()) {
      int pid = static_cast<int>(p.as_int(-1));
      if (pid > 1) kill(-pid, SIGKILL);
    }
    job.status["active"] = false;
    job.status["allocation"] = Json::Object();
    int64_t restarts = job.status.get("restarts").as_int(0);
    job.status["restarts"] = restarts + 1;  // counts toward backoff: a
    // crash-looping control plane must not restart gangs forever
    metrics_.gang_restarts++;
    AppendEvent(job, "Warning", "ControlPlaneRestarted",
                "orphaned gang reaped after control-plane restart");
    SetPhase(job, "Restarting", "ControlPlaneRestarted",
             "orphaned gang reaped after control-plane restart", NowWall());
    store_->UpdateStatus("JAXJob", res.name, job.status);
  }
}

void JaxJobController::OnDeleted(const Resource& res) {
  if (!res.status.get("active").as_bool(false)) return;
  JobView job{res, res.spec, res.status};
  KillAll(job);
  ReleaseAlloc(job);
}

void JaxJobController::Reconcile(const std::string& name) {
  metrics_.reconciles++;
  auto res = store_->Get("JAXJob", name);
  if (!res) return;
  JobView job{*res, res->spec, res->status};
  const std::string phase = job.status.get("phase").as_string();

  if (res->deleted) return;

  if (IsTerminal(phase)) {
    return;  // GC handled by Tick (TTL)
  }

  if (phase.empty()) {
    metrics_.jobs_created++;
    AppendEvent(job, "Normal", "Submitted", "job accepted");
    SetPhase(job, "Created", "JobCreated", "accepted", now_s_);
  }

  bool active = job.status.get("active").as_bool(false);
  if (!active) {
    // Created, Pending, or Restarting → try to launch the gang.
    LaunchGang(job);
  } else {
    HandleExits(job);
  }

  // Only write when something changed — UpdateStatus emits a watch event
  // which re-enqueues this reconcile; an unconditional write would loop.
  if (job.status.dump() != res->status.dump()) {
    store_->UpdateStatus("JAXJob", name, job.status);
  }
}

void JaxJobController::Tick(double now_s) {
  now_s_ = now_s;
  // 1) Reap process exits → reconcile owners.
  for (const auto& id : executor_->Poll()) {
    auto slash = id.find('/');
    if (slash != std::string::npos) {
      Reconcile(id.substr(0, slash));
    }
  }
  // 2) Deadlines, TTL GC, and level-triggered retries for non-terminal jobs.
  std::vector<std::string> pending;  // queued jobs; launched under a budget
  for (const auto& res : store_->List("JAXJob")) {
    JobView job{res, res.spec, res.status};
    const std::string phase = job.status.get("phase").as_string();
    if (IsTerminal(phase)) {
      int64_t ttl = job.spec.get("ttl_seconds_after_finished").as_int(-1);
      double done = job.status.get("completionUnix").as_number(0);
      if (ttl >= 0 && done > 0 && now_s - done > ttl) {
        store_->Delete("JAXJob", res.name);
      }
      continue;
    }
    int64_t deadline = job.spec.get("active_deadline_seconds").as_int(0);
    double started = job.status.get("startUnix").as_number(0);
    if (deadline > 0 && started > 0 && now_s - started > deadline &&
        job.status.get("active").as_bool(false)) {
      KillAll(job);
      job.status["active"] = false;
      ReleaseAlloc(job);
      job.status["completionUnix"] = now_s;
      AppendEvent(job, "Warning", "Failed",
                  "DeadlineExceeded: activeDeadlineSeconds exceeded");
      SetPhase(job, "Failed", "DeadlineExceeded",
               "activeDeadlineSeconds exceeded", now_s);
      metrics_.jobs_failed++;
      store_->UpdateStatus("JAXJob", res.name, job.status);
      continue;
    }
    if (phase == "Pending" || phase == "Restarting" || phase.empty()) {
      pending.push_back(res.name);
    }
    if (phase == "Running" && job.status.get("active").as_bool(false)) {
      // An explicit resize request supersedes this tick's health/upsize
      // checks — the gang it would inspect is already being replaced.
      if (!MaybeApplyFsdpTarget(job)) {
        CheckHeartbeats(job);  // hung-worker kills reaped on a later Poll
        MaybeUpsize(job);
      }
      if (job.status.dump() != res.status.dump()) {
        store_->UpdateStatus("JAXJob", res.name, job.status);
      }
    }
  }
  // Bounded round-robin launch sweep over the queue (see jaxjob.h note):
  // the rotating cursor keeps it fair, the budget keeps a 1000-job
  // backlog from monopolizing the event loop every tick.
  const size_t n = pending.size();
  const size_t budget = std::min(n, kMaxPendingLaunchPerTick);
  for (size_t k = 0; k < budget; ++k) {
    Reconcile(pending[(pending_cursor_ + k) % n]);
  }
  pending_cursor_ = n > 0 ? (pending_cursor_ + budget) % n : 0;
}

}  // namespace tpk
