"""dlrover_tpu — a TPU-native elastic distributed-training runtime.

A ground-up JAX/XLA rebuild of the capabilities of DLRover (the reference
elastic-training runtime): master-coordinated rendezvous, per-host elastic
agents, fault tolerance with automatic re-meshing, in-memory "flash"
checkpointing of jax pytrees, dynamic data sharding, node health checks and
straggler detection, diagnosis, auto-scaling, and native profiling.

Layer map (mirrors SURVEY.md §1, re-architected for TPU):

  L7  user API: ``tpurun`` CLI, :mod:`dlrover_tpu.trainer`, flash-checkpoint API
  L6  training integration: pytree checkpoint engines, elastic dataloader
  L5  per-host agent: :mod:`dlrover_tpu.agent`
  L4  job master: :mod:`dlrover_tpu.master`
  L3  plumbing: :mod:`dlrover_tpu.common`, :mod:`dlrover_tpu.rpc`
  L2  platform schedulers: :mod:`dlrover_tpu.scheduler`
  L0  native profiling: :mod:`dlrover_tpu.profiler`

The TPU compute path (models, parallelism, kernels) lives in
:mod:`dlrover_tpu.models`, :mod:`dlrover_tpu.parallel`, :mod:`dlrover_tpu.ops`.
"""

import time as _time

# The earliest stamp this package can take of its process: a start-up record
# (attribution/recovery.py) falls back on it where the kernel's own start
# time of the process cannot be read.
FIRST_LINE_UNIX_NS = _time.time_ns()

__version__ = "0.1.0"
