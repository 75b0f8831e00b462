"""Launch configuration for the elastic agent.

Reference: ``ElasticLaunchConfig`` (dlrover/python/elastic_agent/torch/
training.py:180) which extends torch's LaunchConfig with network-check,
node-unit and auto-config knobs. The TPU version drops torchrun
inheritance and keeps the knobs that matter for a JAX-process-per-host
world.
"""

import os
from dataclasses import dataclass, field
from typing import Dict, List, Mapping, Optional

from ..common.compile_cache import CACHE_DIR_ENV, resolve_cache_dir
from ..common.constants import Accelerators, DefaultValues, NodeEnv

# The allocator policy a worker is born under (``worker_env``): what it
# has freed stays its own, so that a flash save's host copies land in the
# pages the last save's were freed from and not in pages the kernel has
# to find and zero again (1.49 GB a save for GPT-2 small). glibc reads
# the variable at the process's first instruction and takes ``size_t``
# (``mallopt`` takes an ``int``: 2 GiB at most); another libc ignores it.
# Setting either also switches off glibc's sliding mmap threshold.
WORKER_MALLOC_TUNABLES = ":".join(
    (
        # The top of the heap is never given back on ``free`` (the
        # default trims whatever passes 128 KiB, or twice the sliding
        # mmap threshold): size_t's largest is a threshold never reached.
        "glibc.malloc.trim_threshold=18446744073709551615",
        # No chunk gets a mapping of its own, which ``free`` would unmap:
        # the mmap threshold cannot pass 32 MiB, and an embedding and its
        # two Adam moments are leaves of 154.5 MB each. Every chunk comes
        # from the heap, and goes back to it.
        "glibc.malloc.mmap_max=0",
    )
)


def _caller_chose_an_allocator(environ: Mapping[str, str]) -> bool:
    """Whether ``environ`` already says how memory is to be allocated:
    glibc's own settings in either spelling, or a preloaded library
    (which may be another allocator)."""
    return (
        "glibc.malloc." in environ.get("GLIBC_TUNABLES", "")
        or bool(environ.get("LD_PRELOAD"))
        or any(key.startswith("MALLOC_") for key in environ)
    )


@dataclass
class ElasticLaunchConfig:
    """Everything the agent needs to launch and supervise one host."""

    min_nodes: int = 1
    max_nodes: int = 1
    # Valid world sizes are multiples of node_unit (≙ TPU slice shape:
    # hosts per slice). The rendezvous truncates to a multiple of it.
    node_unit: int = 1
    node_id: int = 0
    node_rank: int = 0
    # Devices supervised by this host's JAX process (local chip count).
    local_world_size: int = 1

    entrypoint: str = ""  # python script or module to run
    entry_args: List[str] = field(default_factory=list)
    run_module: bool = False  # entrypoint is a module (python -m style)

    master_addr: str = ""
    master_service_type: str = DefaultValues.SERVICE_TYPE
    job_name: str = "local_job"

    accelerator: str = Accelerators.TPU
    network_check: bool = False
    comm_perf_test: bool = False
    exclude_straggler: bool = False
    auto_config: bool = False
    # Worker-side ParalConfigTuner polls master tuning configs when set.
    auto_tunning: bool = False
    max_restarts: int = DefaultValues.MAX_RELAUNCH_COUNT
    monitor_interval: float = DefaultValues.MONITOR_INTERVAL_S
    rdzv_timeout: float = DefaultValues.RDZV_TIMEOUT_S
    save_at_breakpoint: bool = DefaultValues.SAVE_AT_BREAKPOINT
    training_port: int = 0  # 0 → pick a free port for the jax coordinator
    log_dir: Optional[str] = None
    numa_affinity: bool = False
    # Native PJRT profiling: "auto" enables it on TPU (the reference's
    # xpu_timer is passive and always-on); "on"/"off" force it.
    profile: str = "auto"
    profiler_port: int = 0  # worker tt /metrics port (0 → agent picks)
    profiler_daemon_port: int = 0  # rank-0 cluster daemon port (0 → any)
    profiler_scrape_interval_s: float = 30.0
    # Keep a pre-imported spare interpreter per agent so worker
    # restarts skip the CPython + jax/flax import tax (elastic MTTR).
    warm_spare: bool = True
    # Offer shape-compatible new worlds to a live worker at a step
    # boundary (trainer/remesh.py) before falling back to a restart.
    soft_remesh: bool = True
    soft_remesh_timeout_s: float = 15.0
    # Double-buffered input pipeline in ElasticTrainLoop (default on;
    # tpurun --sync-input turns it off for sources that must not see a
    # draw ahead of the step that consumes it).
    input_prefetch: bool = True
    extra_env: Dict[str, str] = field(default_factory=dict)

    def slice_id(self) -> int:
        """TPU slice this host belongs to. Ranks are assigned
        slice-contiguously (node_unit hosts per slice), so the slice is
        derivable from the rank — reported at rendezvous join so the
        master's TopologySorter and slice-granular relaunch see real
        membership instead of a uniform 0."""
        return self.node_rank // self.node_unit if self.node_unit > 1 else 0

    def profile_enabled(self) -> bool:
        if self.profile == "on":
            return True
        if self.profile == "off":
            return False
        return self.accelerator == Accelerators.TPU

    def auto_configure_params(self) -> None:
        """Fill node counts from the scheduler-provided env contract.

        Reference: training.py:227 — nnodes comes from NODE_NUM, and the
        network check is auto-enabled on jobs large enough (≥4 nodes)
        that a single bad host is both likely and hard to find by hand.
        """
        node_num = int(os.environ.get(NodeEnv.NODE_NUM, "0"))
        if node_num > 0:
            self.min_nodes = node_num
            self.max_nodes = node_num
        unit = int(os.environ.get(NodeEnv.NODE_UNIT, "0"))
        if unit > 0:
            self.node_unit = unit
        if self.auto_config and self.max_nodes >= 4:
            self.network_check = True

    def worker_env(self) -> Dict[str, str]:
        """Static part of the env contract handed to the JAX process."""
        env = dict(self.extra_env)
        env[NodeEnv.MASTER_ADDR] = self.master_addr
        env[NodeEnv.MASTER_SERVICE_TYPE] = self.master_service_type
        env[NodeEnv.JOB_NAME] = self.job_name
        env[NodeEnv.NODE_ID] = str(self.node_id)
        env[NodeEnv.NODE_RANK] = str(self.node_rank)
        env[NodeEnv.NODE_NUM] = str(self.max_nodes)
        # NODE_NUM above is overwritten per rendezvous round with the
        # live world size (_world_env); this one stays the job ceiling.
        env[NodeEnv.MAX_NODES] = str(self.max_nodes)
        env[NodeEnv.NODE_UNIT] = str(self.node_unit)
        if self.auto_tunning:
            env[NodeEnv.AUTO_TUNNING] = "1"
        # One compile cache for every incarnation and every later run:
        # the caller's JAX_COMPILATION_CACHE_DIR, else the fixed path in
        # the checkout (common/compile_cache.py). JAX reads the variable
        # itself, so the worker script need not call anything.
        env.setdefault(CACHE_DIR_ENV, resolve_cache_dir())
        # No hidden CPU: a TPU job whose caller pinned no platform pins
        # "tpu", so a failed TPU initialization raises in the worker
        # (and the agent sees a failed worker) instead of JAX warning
        # and training on the host. A caller's own JAX_PLATFORMS (tests,
        # virtual-CPU drills) is inherited untouched.
        if (
            self.accelerator == Accelerators.TPU
            and not os.environ.get("JAX_PLATFORMS")
        ):
            env.setdefault("JAX_PLATFORMS", "tpu")
        # What a worker frees it keeps (WORKER_MALLOC_TUNABLES): the cost
        # is host memory, the resident set no longer falls after a save
        # or a compilation. A caller's own allocator settings, in the
        # agent's environment or in extra_env, are inherited untouched
        # and nothing is added to them; its other tunables are kept.
        if not _caller_chose_an_allocator({**os.environ, **env}):
            theirs = os.environ.get("GLIBC_TUNABLES")
            env.setdefault(
                "GLIBC_TUNABLES",
                ":".join(filter(None, (theirs, WORKER_MALLOC_TUNABLES))),
            )
        if not self.input_prefetch:
            env["DLROVER_INPUT_PREFETCH"] = "0"
        return env
