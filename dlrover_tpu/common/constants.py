"""Shared constants and enums for the runtime.

Re-creates the vocabulary of the reference runtime
(``dlrover/python/common/constants.py``) for a TPU/JAX world: nodes are TPU
hosts, the data plane is ICI/DCN via XLA collectives, and elasticity operates
at slice granularity (``node_unit``).

Also home of :data:`ENV_KNOBS`, the typed registry of every ``DLROVER_*``
environment variable the runtime reads or writes — the single source of
truth the ``tpurun-lint`` env-knobs pass enforces (documented ⇔
registered ⇔ referenced; see docs/analysis.md). This module must stay
stdlib-pure: the lint suite loads it standalone, without importing the
package.
"""

import os
from dataclasses import dataclass
from typing import Dict, Optional


class NodeType:
    MASTER = "master"
    WORKER = "worker"  # a TPU host (worker VM) running one JAX process
    # Legacy role names kept so heterogeneous (CPU) role groups can reuse the
    # same node management machinery (reference: PS/chief/evaluator managers).
    PS = "ps"
    CHIEF = "chief"
    EVALUATOR = "evaluator"


class NodeStatus:
    INITIAL = "initial"
    PENDING = "pending"
    RUNNING = "running"
    SUCCEEDED = "succeeded"
    FAILED = "failed"
    DELETED = "deleted"
    BREAKDOWN = "breakdown"

    @classmethod
    def terminal(cls):
        return {cls.SUCCEEDED, cls.FAILED, cls.DELETED}


class NodeEventType:
    ADDED = "added"
    MODIFIED = "modified"
    DELETED = "deleted"
    # Health reported by the agent itself.
    NODE_HEALTHY = "node_healthy"
    NODE_UNHEALTHY = "node_unhealthy"


class NodeExitReason:
    SUCCEEDED = "succeeded"
    KILLED = "killed"
    OOM = "oom"
    FATAL_ERROR = "fatal_error"
    HARDWARE_ERROR = "hardware_error"
    PREEMPTED = "preempted"
    # The agent itself asked to be replaced (worker restart budget
    # exhausted / diagnosis said relaunch): the node-level relaunch
    # budget still bounds the loop, but the master MUST honor the
    # request — reporting FATAL_ERROR here silently stranded the node
    # (observed in the goodput storm: a replacement whose worker
    # crash-looped left the job permanently one host short).
    RELAUNCH_REQUESTED = "relaunch_requested"
    UNKNOWN = "unknown"

    # The relaunch gate is Node.should_relaunch(): every reason is
    # honored EXCEPT FATAL_ERROR (there is deliberately no allowlist —
    # an unforeseen exit reason defaults to recovering the node).


class JobStage:
    INIT = "init"
    PRE_CHECK = "pre_check"
    RUNNING = "running"
    SUSPENDED = "suspended"
    STOPPING = "stopping"
    STOPPED = "stopped"


class JobExitReason:
    SUCCEEDED = "succeeded"
    FATAL_ERROR = "fatal_error"
    MAX_RELAUNCH = "max_relaunch_exceeded"
    PENDING_TIMEOUT = "pending_timeout"
    NO_HEARTBEAT = "no_heartbeat"
    HANG = "hang"
    UNKNOWN = "unknown"


class RendezvousName:
    TRAINING = "training"
    NETWORK_CHECK = "network-check"


class NodeCheckConstants:
    # Rounds per check sequence: adjacent pairs, then fastest-with-slowest.
    # The agent's check loop and the master's round state machine must agree.
    CHECK_ROUNDS = 2


class PlatformType:
    LOCAL = "local"
    KUBERNETES = "k8s"
    GKE_TPU = "gke_tpu"
    RAY = "ray"


class Accelerators:
    TPU = "tpu"
    CPU = "cpu"  # CPU backend used for tests/virtual meshes


class DistributionStrategy:
    # Every TPU job is SPMD over a global mesh; LOCAL means single-host.
    SPMD = "spmd"
    LOCAL = "local"


class TrainingExceptionLevel:
    ERROR = "error"
    WARNING = "warning"
    INFO = "info"


class CheckpointConstant:
    TRACKER_FILE = "dlrover_latest.txt"
    DONE_DIR = ".done"
    STAGING_DIR = ".staging"
    META_NAME = "ckpt_meta"
    MODEL_STATE_NAME = "model_state"
    COMMIT_FILE = "commit_success"


class NodeEnv:
    """Per-process environment contract (agent → JAX process)."""

    MASTER_ADDR = "DLROVER_MASTER_ADDR"
    MASTER_SERVICE_TYPE = "DLROVER_MASTER_SERVICE_TYPE"
    JOB_NAME = "DLROVER_JOB_NAME"
    NODE_ID = "DLROVER_NODE_ID"
    NODE_RANK = "DLROVER_NODE_RANK"
    NODE_NUM = "DLROVER_NODE_NUM"
    # Static job maximum (ElasticLaunchConfig.max_nodes). NODE_NUM is
    # clobbered per rendezvous round with the CURRENT world size by the
    # agent's dynamic env; consumers that need the job's ceiling (the
    # compile-ahead shrink ladder) must read this one.
    MAX_NODES = "DLROVER_MAX_NODES"
    NODE_UNIT = "DLROVER_NODE_UNIT"
    # JAX distributed bootstrap (filled in by the rendezvous handler).
    COORDINATOR_ADDRESS = "DLROVER_COORDINATOR_ADDRESS"
    NUM_PROCESSES = "DLROVER_NUM_PROCESSES"
    PROCESS_ID = "DLROVER_PROCESS_ID"
    RESTART_COUNT = "DLROVER_RESTART_COUNT"
    AUTO_TUNNING = "DLROVER_AUTO_TUNNING"


class GRPC:
    MAX_SEND_MESSAGE_LENGTH = 256 * 1024 * 1024
    MAX_RECEIVE_MESSAGE_LENGTH = 256 * 1024 * 1024


class CommsType:
    GRPC = "grpc"
    HTTP = "http"


class PreCheckStatus:
    CHECKING = "checking"
    PASSED = "passed"
    FAILED = "failed"
    DISABLED = "disabled"


class DiagnosisConstants:
    ACTION_EXPIRY_S = 60 * 5
    MASTER_INSTANCE = -1
    ANY_INSTANCE = -2


class DefaultValues:
    SERVICE_TYPE = CommsType.GRPC
    MASTER_PORT = 0  # 0 → pick a free port
    RDZV_TIMEOUT_S = 600
    RDZV_LASTCALL_S = 30
    NODE_CHECK_TIMEOUT_S = 300
    HEARTBEAT_INTERVAL_S = 15
    HANG_DOWNTIME_S = 300
    MAX_RELAUNCH_COUNT = 3
    MONITOR_INTERVAL_S = 5
    SAVE_AT_BREAKPOINT = True
    SEC_TO_WAIT_PENDING_POD = 900


# ---------------------------------------------------------------------------
# DLROVER_* env-knob registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class EnvKnob:
    """One registered ``DLROVER_*`` environment variable.

    ``internal=True`` marks a process-contract variable: set BY the
    runtime for its own child processes (agent→worker env contract,
    harness→bench plumbing), never tuned by an operator — exempt from
    the documentation requirement but still registry-checked.
    ``context_field`` links a knob to the ``Context`` dataclass field it
    overrides via ``Context.apply_env`` (those knobs may never appear as
    a literal in source; the link is what keeps the registry's
    staleness check honest)."""

    name: str
    type: str = "str"  # str | int | float | bool
    doc: str = ""
    internal: bool = False
    context_field: str = ""

    def get(self, default=None, environ: Optional[Dict[str, str]] = None):
        """Typed read of the knob from ``environ`` (default
        ``os.environ``). The one sanctioned accessor for call sites
        that do not go through ``Context.apply_env``."""
        env = os.environ if environ is None else environ
        raw = env.get(self.name)
        if raw is None:
            return default
        if self.type == "int":
            return int(raw)
        if self.type == "float":
            return float(raw)
        if self.type == "bool":
            return raw.strip().lower() in ("1", "true", "yes", "on")
        return raw


def _knobs(*knobs: EnvKnob) -> Dict[str, EnvKnob]:
    reg: Dict[str, EnvKnob] = {}
    for k in knobs:
        if k.name in reg:
            raise ValueError(f"duplicate env knob {k.name}")
        reg[k.name] = k
    return reg


ENV_KNOBS: Dict[str, EnvKnob] = _knobs(
    # -- agent → worker process contract (internal) ------------------------
    EnvKnob(NodeEnv.MASTER_ADDR, doc="master control-plane address", internal=True),
    EnvKnob(NodeEnv.JOB_NAME, doc="job name", internal=True),
    EnvKnob(NodeEnv.NODE_ID, "int", doc="node id", internal=True),
    EnvKnob(NodeEnv.NODE_RANK, "int", doc="node rank this round", internal=True),
    EnvKnob(NodeEnv.NODE_NUM, "int", doc="CURRENT world size (clobbered per round)", internal=True),
    EnvKnob(NodeEnv.MAX_NODES, "int", doc="static job maximum world size", internal=True),
    EnvKnob(NodeEnv.NODE_UNIT, "int", doc="slice granularity (hosts per slice)", internal=True),
    EnvKnob(NodeEnv.COORDINATOR_ADDRESS, doc="jax.distributed coordinator", internal=True),
    EnvKnob(NodeEnv.NUM_PROCESSES, "int", doc="jax.distributed world size", internal=True),
    EnvKnob(NodeEnv.PROCESS_ID, "int", doc="jax.distributed process id", internal=True),
    EnvKnob(NodeEnv.RESTART_COUNT, "int", doc="restarts of this worker so far", internal=True),
    EnvKnob(NodeEnv.AUTO_TUNNING, "bool", doc="hyperparam auto-tuning contract flag", internal=True),
    EnvKnob("DLROVER_MASTER_HOST", doc="master bind host (launcher contract)", internal=True),
    EnvKnob("DLROVER_MASTER_SERVICE_ADDR", doc="master service address (unified contract)", internal=True),
    EnvKnob("DLROVER_NODE_SLOT", "int", doc="warm-spare slot index", internal=True),
    EnvKnob("DLROVER_JOB_UID", doc="k8s owner uid for pod GC scoping", internal=True),
    EnvKnob("DLROVER_REMESH_DIR", doc="soft-remesh handshake directory", internal=True),
    EnvKnob("DLROVER_REPLICA_TOKEN", doc="replica peer-fetch auth token", internal=True),
    EnvKnob("DLROVER_WARM_READY_FILE", doc="warm-spare readiness marker file", internal=True),
    EnvKnob("DLROVER_WORKER_COMMAND", doc="worker launch command (scaler contract)", internal=True),
    EnvKnob("DLROVER_WORKER_IMAGE", doc="worker container image (scaler contract)", internal=True),
    EnvKnob("DLROVER_IPC_NAMESPACE", doc="shm/socket namespace isolating saver IPC", internal=True),
    EnvKnob("DLROVER_TT_PORT", "int", doc="native interposer metrics port (agent contract)", internal=True),
    EnvKnob("DLROVER_UNIFIED_JOB", doc="unified job name (manager contract)", internal=True),
    EnvKnob("DLROVER_UNIFIED_COMM_TOKEN", doc="unified comm auth token", internal=True),
    EnvKnob("DLROVER_ROLE", doc="unified role name (manager contract)", internal=True),
    EnvKnob("DLROVER_ROLE_INDEX", "int", doc="rank within the unified role", internal=True),
    EnvKnob("DLROVER_ROLE_WORLD", "int", doc="unified role world size", internal=True),
    EnvKnob("DLROVER_ROLE_WORLDS", doc="JSON {role: world} map for peer groups", internal=True),
    EnvKnob("DLROVER_LOCAL_DEVICES", "int", doc="device count visible to a CPU-mesh worker", internal=True),
    # -- operator-tunable knobs -------------------------------------------
    EnvKnob("DLROVER_LOG_LEVEL", doc="runtime log level", context_field="log_level"),
    EnvKnob("DLROVER_EVENT_DIR", doc="crash/exit event JSON directory"),
    EnvKnob("DLROVER_IPC_DIR", doc="unix-socket directory for saver IPC"),
    EnvKnob("DLROVER_PIDFILE_DIR", doc="worker pidfile directory (orphan reaping)"),
    EnvKnob("DLROVER_TPU_PER_HOST", "int", doc="TPU chips per host for resource accounting"),
    EnvKnob("DLROVER_RECOVERY_DIR", doc="MTTR phase-attribution spool directory"),
    EnvKnob("DLROVER_FAULT_PLAN", doc="chaos fault plan (docs/chaos.md grammar)"),
    EnvKnob("DLROVER_FAULT_LOG", doc="chaos injection JSONL log path"),
    EnvKnob("DLROVER_LOCK_WITNESS", "bool", doc="lock-witness sanitizer: instrument runtime locks (docs/analysis.md)"),
    EnvKnob("DLROVER_LOCK_WITNESS_LOG", doc="lock-witness JSONL log path (edges + inversions)"),
    EnvKnob("DLROVER_LOCK_WITNESS_MODE", doc="lock-witness on inversion: report (default) or raise"),
    EnvKnob("DLROVER_CKPT_SAVER_TIMEOUT_S", "float", doc="saver-IPC wedge timeout before standalone fallback"),
    EnvKnob("DLROVER_INPUT_PREFETCH", "bool", doc="double-buffered input pipeline on/off", context_field="input_prefetch"),
    EnvKnob("DLROVER_COMPILE_CACHE_MIN_COMPILE_S", "float", doc="min compile time worth caching", context_field="compile_cache_min_compile_s"),
    EnvKnob("DLROVER_CKPT_PREFETCH_RESTORE", "bool", doc="overlapped restore prefetch on/off", context_field="ckpt_prefetch_restore"),
    EnvKnob("DLROVER_CKPT_REPLICA_TIMEOUT_S", "float", doc="peer-replica shard transfer deadline", context_field="ckpt_replica_timeout_s"),
    EnvKnob("DLROVER_PY_TRACE_TARGETS", doc="module:function list for the host tracer"),
    EnvKnob("DLROVER_STACK_DUMP_DIR", doc="hang-watchdog stack dump directory"),
    EnvKnob("DLROVER_PJRT_REAL_PLUGIN", doc="real libtpu path behind the interposer"),
    EnvKnob("DLROVER_UNIFIED_COMM_ADDR", doc="unified cluster KV/queue service address"),
    EnvKnob("DLROVER_UNIFIED_P2P", "bool", doc="unified payloads: direct P2P transfer on/off"),
    EnvKnob("DLROVER_UNIFIED_P2P_TTL_S", "float", doc="unified P2P payload TTL"),
    EnvKnob("DLROVER_UNIFIED_P2P_STORE_CAP", "int", doc="unified P2P store capacity (bytes)"),
    EnvKnob("DLROVER_UNIFIED_P2P_INLINE_MAX", "int", doc="unified payload inline-size threshold (bytes)"),
    # -- observability (dlrover_tpu/observability/, docs/observability.md) -
    EnvKnob("DLROVER_TRACE_ID", doc="inherited incident trace id (spawn contract)", internal=True),
    EnvKnob("DLROVER_TRACE_PARENT_SPAN", doc="inherited parent span id (spawn contract)", internal=True),
    EnvKnob("DLROVER_TRACE_DIR", doc="flight-recorder dump directory (empty = dumps off)"),
    EnvKnob("DLROVER_TRACE_RING_CAP", "int", doc="flight-recorder ring capacity (events kept per process)"),
    EnvKnob("DLROVER_METRICS_PORT", "int", doc="master /metrics port (unset = off, 0 = free port)"),
    EnvKnob("DLROVER_METRICS_AGENT_PORT", "int", doc="agent /metrics port (unset = off, 0 = free port)"),
    # -- Context-backed knobs (Context.apply_env reads DLROVER_<FIELD>) ----
    EnvKnob(NodeEnv.MASTER_SERVICE_TYPE, doc="master comms transport (grpc|http)", context_field="master_service_type"),
    EnvKnob("DLROVER_MASTER_PORT", "int", doc="master bind port (0 = free port)", context_field="master_port"),
    EnvKnob("DLROVER_MASTER_STATE_DIR", doc="master crash-tolerance journal directory (empty = no journal)", context_field="master_state_dir"),
    EnvKnob("DLROVER_MASTER_SNAPSHOT_EVERY", "int", doc="WAL records between master snapshot compactions", context_field="master_snapshot_every"),
    EnvKnob("DLROVER_MASTER_REATTACH_GRACE_S", "float", doc="post-replay wait for agent shard re-reports before requeue", context_field="master_reattach_grace_s"),
    EnvKnob("DLROVER_RPC_DEADLINE_S", "float", doc="per-call RPC transport deadline", context_field="rpc_deadline_s"),
    EnvKnob("DLROVER_RPC_RETRIES", "int", doc="RPC retry budget", context_field="rpc_retries"),
    EnvKnob("DLROVER_RPC_BACKOFF_BASE_S", "float", doc="RPC backoff base (equal jitter)", context_field="rpc_backoff_base_s"),
    EnvKnob("DLROVER_RPC_BACKOFF_CAP_S", "float", doc="RPC backoff cap", context_field="rpc_backoff_cap_s"),
    EnvKnob("DLROVER_RDZV_TIMEOUT_S", "float", doc="rendezvous deadline", context_field="rdzv_timeout_s"),
    EnvKnob("DLROVER_RDZV_LASTCALL_S", "float", doc="rendezvous last-call window", context_field="rdzv_lastcall_s"),
    EnvKnob("DLROVER_NODE_CHECK_TIMEOUT_S", "float", doc="node network-check deadline", context_field="node_check_timeout_s"),
    EnvKnob("DLROVER_MAX_RELAUNCH_COUNT", "int", doc="per-node relaunch budget", context_field="max_relaunch_count"),
    EnvKnob("DLROVER_RELAUNCH_ALWAYS", "bool", doc="relaunch regardless of exit reason", context_field="relaunch_always"),
    EnvKnob("DLROVER_RESTART_BUDGET_PER_NODE", "int", doc="agent-local worker restart budget", context_field="restart_budget_per_node"),
    EnvKnob("DLROVER_HEARTBEAT_INTERVAL_S", "float", doc="agent heartbeat interval", context_field="heartbeat_interval_s"),
    EnvKnob("DLROVER_HEARTBEAT_DEADLINE_S", "float", doc="master-side dead-node window", context_field="heartbeat_deadline_s"),
    EnvKnob("DLROVER_MASTER_LOST_TIMEOUT_S", "float", doc="agent aborts after master dark this long", context_field="master_lost_timeout_s"),
    EnvKnob("DLROVER_MONITOR_INTERVAL_S", "float", doc="resource monitor interval", context_field="monitor_interval_s"),
    EnvKnob("DLROVER_SECONDS_TO_WAIT_PENDING_POD", "float", doc="pending-pod wait budget", context_field="seconds_to_wait_pending_pod"),
    EnvKnob("DLROVER_PENDING_FAIL_STRATEGY", "int", doc="pending-pod strategy (0 ignore, 1 abort, 2 relaunch)", context_field="pending_fail_strategy"),
    EnvKnob("DLROVER_HANG_DOWNTIME_S", "float", doc="hang detector downtime threshold", context_field="hang_downtime_s"),
    EnvKnob("DLROVER_HANG_DETECTION_ENABLED", "bool", doc="hang detection on/off", context_field="hang_detection_enabled"),
    EnvKnob("DLROVER_SAVE_AT_BREAKPOINT", "bool", doc="checkpoint at breakpoint on failure", context_field="save_at_breakpoint"),
    EnvKnob("DLROVER_CKPT_REPLICA_COUNT", "int", doc="peer-memory replicas per shard", context_field="ckpt_replica_count"),
    EnvKnob("DLROVER_CKPT_KEEP_LATEST", "int", doc="committed steps kept on storage (0 = all)", context_field="ckpt_keep_latest"),
    EnvKnob("DLROVER_DURABLE_DIR", doc="durable checkpoint tier root (empty = tier off)", context_field="durable_dir"),
    EnvKnob("DLROVER_DURABLE_LINEAGE", doc="durable lineage (warm-pool key) this job writes under; empty = job name", context_field="durable_lineage"),
    EnvKnob("DLROVER_DURABLE_KEEP", "int", doc="committed durable generations kept per lineage (pins/leases always kept)", context_field="durable_keep"),
    EnvKnob("DLROVER_DURABLE_EVERY", "int", doc="drain every Nth flash-committed step to the durable tier", context_field="durable_every"),
    EnvKnob("DLROVER_DURABLE_COMMIT_TIMEOUT_S", "float", doc="durable commit: rank 0's wait for all shard-done signals", context_field="durable_commit_timeout_s"),
    EnvKnob("DLROVER_PRECHECK_ENABLED", "bool", doc="pre-check gate on/off", context_field="precheck_enabled"),
    EnvKnob("DLROVER_PRECHECK_TIMEOUT_S", "float", doc="pre-check deadline", context_field="precheck_timeout_s"),
    EnvKnob("DLROVER_NETWORK_CHECK_ENABLED", "bool", doc="network check rounds on/off", context_field="network_check_enabled"),
    EnvKnob("DLROVER_STRAGGLER_MEDIAN_RATIO", "float", doc="straggler threshold vs median", context_field="straggler_median_ratio"),
    EnvKnob("DLROVER_EXCLUDE_STRAGGLERS", "bool", doc="drop stragglers from the world", context_field="exclude_stragglers"),
    EnvKnob("DLROVER_AUTO_TUNING_ENABLED", "bool", doc="hyperparam auto-tuning on/off", context_field="auto_tuning_enabled"),
    EnvKnob("DLROVER_AUTO_SCALING_INTERVAL_S", "float", doc="auto-scaler evaluation interval", context_field="auto_scaling_interval_s"),
    EnvKnob("DLROVER_BRAIN_ADDR", doc="brain service address (empty = disabled)", context_field="brain_addr"),
    EnvKnob("DLROVER_BRAIN_REPORT_INTERVAL_S", "float", doc="brain stats report interval", context_field="brain_report_interval_s"),
    EnvKnob("DLROVER_HOST_MEMORY_MB", "float", doc="host RAM capacity hint for hyperparam strategies", context_field="host_memory_mb"),
    EnvKnob("DLROVER_INITIAL_BATCH_SIZE", "int", doc="starting per-host dataloader batch size", context_field="initial_batch_size"),
    # -- elastic hybrid parallelism (docs/elastic_parallelism.md) ----------
    EnvKnob("DLROVER_ELASTIC_REPLAN", "bool", doc="elastic: replan DP×TP×PP rungs on world change (off = accum-only)", context_field="elastic_replan"),
    EnvKnob("DLROVER_ELASTIC_MAX_TP", "int", doc="elastic: max tensor-parallel extent the rung ladder may trade into", context_field="elastic_max_tp"),
    EnvKnob("DLROVER_ELASTIC_MAX_PP", "int", doc="elastic: max pipeline depth the rung ladder may trade into", context_field="elastic_max_pp"),
    EnvKnob("DLROVER_ELASTIC_HBM_GB", "float", doc="elastic: per-device HBM budget for rung feasibility (0 = unconstrained)", context_field="elastic_hbm_gb"),
    EnvKnob("DLROVER_ELASTIC_OPT_DP_SHARD", "bool", doc="elastic: shard optimizer moments over dp, gathered at the update", context_field="elastic_opt_dp_shard"),
    # -- serving fleet (dlrover_tpu/fleet/, docs/serving_fleet.md) ---------
    EnvKnob("DLROVER_FLEET_REPLICAS", "int", doc="serving fleet: initial replica count"),
    EnvKnob("DLROVER_FLEET_MIN_REPLICAS", "int", doc="serving fleet: autoscaler lower bound"),
    EnvKnob("DLROVER_FLEET_MAX_REPLICAS", "int", doc="serving fleet: autoscaler upper bound"),
    EnvKnob("DLROVER_FLEET_HEALTH_INTERVAL_S", "float", doc="serving fleet: seconds between /healthz polls"),
    EnvKnob("DLROVER_FLEET_HEALTH_TIMEOUT_S", "float", doc="serving fleet: per-poll /healthz deadline"),
    EnvKnob("DLROVER_FLEET_HEALTH_FAILS", "int", doc="serving fleet: consecutive failed polls before a replica is declared dead"),
    EnvKnob("DLROVER_FLEET_START_TIMEOUT_S", "float", doc="serving fleet: STARTING-state deadline before a replica relaunch"),
    EnvKnob("DLROVER_FLEET_RELAUNCH_BUDGET", "int", doc="serving fleet: per-replica relaunch budget"),
    EnvKnob("DLROVER_FLEET_QUEUE_LIMIT", "int", doc="serving fleet: gateway in-flight bound before 429 admission rejects"),
    EnvKnob("DLROVER_FLEET_RETRY_AFTER_S", "float", doc="serving fleet: Retry-After hint on 429 rejects"),
    EnvKnob("DLROVER_FLEET_REQUEST_TIMEOUT_S", "float", doc="serving fleet: gateway-to-replica proxy deadline"),
    EnvKnob("DLROVER_FLEET_DRAIN_TIMEOUT_S", "float", doc="serving fleet: rollout per-replica drain deadline"),
    EnvKnob("DLROVER_FLEET_AUTOSCALE_INTERVAL_S", "float", doc="serving fleet: autoscaler evaluation interval (0 disables)"),
    EnvKnob("DLROVER_FLEET_QUEUE_HIGH", "float", doc="serving fleet: mean queued-per-replica threshold to grow"),
    EnvKnob("DLROVER_FLEET_P95_TARGET_S", "float", doc="serving fleet: p95 completion-latency target to grow (0 disables)"),
    EnvKnob("DLROVER_FLEET_PREFIX_CAPACITY", "int", doc="serving fleet: gateway prefix-registry LRU bound (refcount-aware eviction)"),
    EnvKnob("DLROVER_FLEET_PREFILL_REPLICAS", "int", doc="serving fleet: replicas dedicated to the prefill role (0 = no disaggregation)"),
    EnvKnob("DLROVER_DISAGG_MIN_PROMPT", "int", doc="disaggregation: minimum prompt tokens before the gateway hands prefill off"),
    EnvKnob("DLROVER_KV_BLOCK_SIZE", "int", doc="paged KV cache: tokens per block (tpurun-serve --cache-layout paged)"),
    EnvKnob("DLROVER_KV_POOL_BLOCKS", "int", doc="paged KV cache: pool size in blocks incl. the trash block (0 = dense-equivalent)"),
    # -- chip-pool arbiter (dlrover_tpu/pool/, docs/pool.md) ---------------
    EnvKnob("DLROVER_POOL_TOTAL_UNITS", "int", doc="chip pool: device-capacity units in the shared inventory"),
    EnvKnob("DLROVER_POOL_TRAIN_FLOOR", "int", doc="chip pool: units training is never revoked below"),
    EnvKnob("DLROVER_POOL_TRAIN_CEILING", "int", doc="chip pool: max units training may hold (0 = whole pool)"),
    EnvKnob("DLROVER_POOL_SERVE_FLOOR", "int", doc="chip pool: units serving is never revoked below"),
    EnvKnob("DLROVER_POOL_SERVE_CEILING", "int", doc="chip pool: max units serving may hold (0 = whole pool)"),
    EnvKnob("DLROVER_POOL_EVAL_INTERVAL_S", "float", doc="chip pool: arbiter evaluation interval (0 = manual stepping)"),
    EnvKnob("DLROVER_POOL_REVOKE_DEADLINE_S", "float", doc="chip pool: cooperative drain budget before escalation"),
    EnvKnob("DLROVER_POOL_HANDBACK_EVALS", "int", doc="chip pool: consecutive calm evaluations before training reclaims surge units"),
    EnvKnob("DLROVER_POOL_SPIKE_UNITS", "int", doc="chip pool: units moved per preempt/handback decision"),
    EnvKnob("DLROVER_POOL_QUEUE_HIGH", "float", doc="chip pool: mean queued-per-replica threshold that preempts training"),
    EnvKnob("DLROVER_POOL_P95_TARGET_S", "float", doc="chip pool: serving p95 latency target that preempts training (0 disables)"),
    EnvKnob("DLROVER_POOL_JOURNAL", doc="chip pool: decision-journal JSONL path (empty = in-memory only)"),
    EnvKnob("DLROVER_POOL_STATUS_TIMEOUT_S", "float", doc="chip pool: /pool/status HTTP client deadline"),
    # -- multi-tenant cluster scheduler (dlrover_tpu/cluster/, docs/cluster.md)
    EnvKnob("DLROVER_CLUSTER_TOTAL_UNITS", "int", doc="cluster scheduler: device-capacity units in the shared pool"),
    EnvKnob("DLROVER_CLUSTER_TENANTS", doc="cluster scheduler: tenant declarations, 'name:kind:priority[:floor[:ceiling[:node_unit]]]' joined by ';'"),
    EnvKnob("DLROVER_CLUSTER_PRIORITY_CLASSES", doc="cluster scheduler: named priority ranks, 'critical=0,high=10,...' (lower = more important)"),
    EnvKnob("DLROVER_CLUSTER_EVAL_INTERVAL_S", "float", doc="cluster scheduler: evaluation interval (0 = manual stepping)"),
    EnvKnob("DLROVER_CLUSTER_REVOKE_DEADLINE_S", "float", doc="cluster scheduler: cooperative drain budget before escalation"),
    EnvKnob("DLROVER_CLUSTER_HANDBACK_EVALS", "int", doc="cluster scheduler: consecutive calm evaluations before a serve tenant returns surge units"),
    EnvKnob("DLROVER_CLUSTER_SPIKE_UNITS", "int", doc="cluster scheduler: units moved per preemption-cascade decision"),
    EnvKnob("DLROVER_CLUSTER_QUEUE_HIGH", "float", doc="cluster scheduler: default mean queued-per-replica threshold that starts a cascade"),
    EnvKnob("DLROVER_CLUSTER_P95_TARGET_S", "float", doc="cluster scheduler: default serving p95 latency target that starts a cascade (0 disables)"),
    EnvKnob("DLROVER_CLUSTER_BRAIN_EVAL_S", "float", doc="cluster scheduler: brain feedback poll/evaluate interval (0 = manual)"),
    EnvKnob("DLROVER_CLUSTER_BRAIN_MIN_SAMPLES", "int", doc="cluster scheduler: metric samples a job needs before brain targets it"),
    EnvKnob("DLROVER_CLUSTER_JOURNAL", doc="cluster scheduler: decision-journal JSONL path (empty = in-memory only)"),
    EnvKnob("DLROVER_CLUSTER_STATUS_TIMEOUT_S", "float", doc="cluster scheduler: /cluster/status HTTP client deadline"),
)
