"""Global runtime configuration singleton.

Re-creates the reference's ``Context`` tunables singleton
(``dlrover/python/common/global_context.py:87``): one process-wide object
holding every knob, overridable from environment variables, so master, agent
and trainer code share a single source of truth.
"""

import os
import threading
from dataclasses import dataclass, field, fields
from typing import Any, Dict, List

from .constants import CommsType, DefaultValues

_ENV_PREFIX = "DLROVER_"


@dataclass
class Context:
    master_service_type: str = DefaultValues.SERVICE_TYPE
    master_port: int = DefaultValues.MASTER_PORT

    # Master crash tolerance (master/persistence.py): a non-empty state
    # dir makes the master journal its coordination state (atomic
    # snapshot + JSONL WAL) and stamp a per-boot epoch on every RPC
    # response; a restarted master replays the journal and agents
    # re-attach under the epoch fence without restarting workers.
    master_state_dir: str = ""
    # WAL records accumulated before the run loop compacts them into a
    # fresh snapshot.
    master_snapshot_every: int = 64
    # How long a replayed master waits for agents to re-report their
    # in-flight shards before requeueing unconfirmed ones.
    master_reattach_grace_s: float = 30.0

    # Master RPC client: per-call transport deadline and the jittered
    # exponential backoff between retries (DLROVER_RPC_* env overrides).
    rpc_deadline_s: float = 30.0
    rpc_retries: int = 3
    rpc_backoff_base_s: float = 0.5
    rpc_backoff_cap_s: float = 5.0

    # Rendezvous
    rdzv_timeout_s: float = DefaultValues.RDZV_TIMEOUT_S
    rdzv_lastcall_s: float = DefaultValues.RDZV_LASTCALL_S
    node_check_timeout_s: float = DefaultValues.NODE_CHECK_TIMEOUT_S

    # Fault tolerance
    max_relaunch_count: int = DefaultValues.MAX_RELAUNCH_COUNT
    relaunch_always: bool = False
    restart_budget_per_node: int = 3
    heartbeat_interval_s: float = DefaultValues.HEARTBEAT_INTERVAL_S
    heartbeat_deadline_s: float = 600.0
    # Orphan guard: agent aborts after the master has been unreachable
    # this long (0 disables). Mirrors the master's dead-node window so
    # neither side supervises a world the other has given up on.
    master_lost_timeout_s: float = 600.0
    monitor_interval_s: float = DefaultValues.MONITOR_INTERVAL_S
    seconds_to_wait_pending_pod: float = DefaultValues.SEC_TO_WAIT_PENDING_POD
    pending_fail_strategy: int = 1  # 0: ignore, 1: wait+abort, 2: wait+relaunch

    # Hang detection
    hang_downtime_s: float = DefaultValues.HANG_DOWNTIME_S
    hang_detection_enabled: bool = True

    # Checkpoint
    save_at_breakpoint: bool = DefaultValues.SAVE_AT_BREAKPOINT
    ckpt_replica_count: int = 0  # peer-memory replicas per shard
    # committed steps kept on storage (0 = unlimited); pruned by the
    # saver after each successful commit
    ckpt_keep_latest: int = 3
    # Warm-restart fast path (docs/recovery.md): engine starts the
    # host-side restore read (shm attach / peer replica fetch) in the
    # background at construction, so it overlaps model build + compile
    # instead of serializing after them.
    ckpt_prefetch_restore: bool = True
    # Peer-replica shard transfers (checkpoint/replica.py) move whole
    # shard images — their deadline is separate from the control-plane
    # rpc_deadline_s (DLROVER_CKPT_REPLICA_TIMEOUT_S override).
    ckpt_replica_timeout_s: float = 120.0
    # Durable checkpoint tier (checkpoint/durable/, docs/recovery.md):
    # empty root disables it. A background writer drains each
    # flash-committed image to <durable_dir>/<durable_lineage>/gen_<N>
    # behind a two-phase checksum-verified commit; restore reshards on
    # read, and other jobs can warm-start from the lineage.
    durable_dir: str = ""
    # Lineage (warm-pool key) this job writes under; empty → job name.
    durable_lineage: str = ""
    # Committed generations kept per lineage (pins/leases always kept).
    durable_keep: int = 3
    # Drain every Nth flash-committed step to the durable tier.
    durable_every: int = 1
    # Rank 0's wait for every host's shard-done signal before commit.
    durable_commit_timeout_s: float = 120.0

    # Persistent XLA compilation cache (common/compile_cache.py holds
    # the placement rule): only compilations at least this expensive
    # are persisted.
    compile_cache_min_compile_s: float = 1.0

    # Input pipeline: the train loop keeps one batch in flight on a
    # background thread (trainer/dataloader.py PrefetchIterator);
    # disable for strictly-replayable finite datasets that must not
    # consume a batch ahead of the step that uses it.
    input_prefetch: bool = True

    # Pre-check
    precheck_enabled: bool = True
    precheck_timeout_s: float = 600.0

    # Network check / straggler
    network_check_enabled: bool = False
    straggler_median_ratio: float = 2.0
    exclude_stragglers: bool = False

    # Auto scaling / tuning
    auto_tuning_enabled: bool = False
    auto_scaling_interval_s: float = 30.0
    # Brain service (cluster-level resource optimizer); empty = disabled.
    brain_addr: str = ""
    brain_report_interval_s: float = 30.0
    # Host RAM capacity and the job's starting per-host dataloader batch
    # size — inputs to the hyperparam strategy generator (0 = unknown,
    # generator disabled).
    host_memory_mb: float = 0.0
    initial_batch_size: int = 0

    # Elastic hybrid parallelism (parallel/replan.py,
    # docs/elastic_parallelism.md): on a world change the replanner
    # picks a DP×TP×PP rung instead of only stacking grad-accum.
    # Off by default — accum-only elasticity is the conservative
    # pre-rung behavior.
    elastic_replan: bool = False
    # ICI-bound caps on the extents the rung ladder may trade into.
    elastic_max_tp: int = 1
    elastic_max_pp: int = 1
    # Per-device HBM budget the cost model checks rung feasibility
    # against (0 = unconstrained; infeasible rungs pay a spill penalty).
    elastic_hbm_gb: float = 0.0
    # Cross-replica weight-update sharding (arXiv:2004.13336): Adam
    # moments shard dim 0 over ``dp``, gathered at the update — the
    # shrink floor stops being optimizer-memory-bound.
    elastic_opt_dp_shard: bool = False

    # Misc
    log_level: str = "INFO"
    extra: Dict[str, Any] = field(default_factory=dict)

    def apply_env(self) -> None:
        """Override fields from ``DLROVER_<UPPER_NAME>`` env vars."""
        for f in fields(self):
            env_key = _ENV_PREFIX + f.name.upper()
            raw = os.getenv(env_key)
            if raw is None:
                continue
            if f.type in (int, "int"):
                setattr(self, f.name, int(raw))
            elif f.type in (float, "float"):
                setattr(self, f.name, float(raw))
            elif f.type in (bool, "bool"):
                setattr(self, f.name, raw.lower() in ("1", "true", "yes"))
            elif f.type in (str, "str"):
                setattr(self, f.name, raw)

    def master_comms(self) -> str:
        if self.master_service_type not in (CommsType.GRPC, CommsType.HTTP):
            return CommsType.GRPC
        return self.master_service_type

    _singleton = None
    _lock = threading.Lock()

    @classmethod
    def singleton_instance(cls) -> "Context":
        if cls._singleton is None:
            with cls._lock:
                if cls._singleton is None:
                    ctx = cls()
                    ctx.apply_env()
                    cls._singleton = ctx
        return cls._singleton


def get_context() -> Context:
    return Context.singleton_instance()


# Registry of pre-check operator names enabled for the job (reference:
# global_context.get_pre_check_operators). Filled by dlrover_tpu.master.
PRE_CHECK_OPS: List[str] = ["scheduling", "connection"]
