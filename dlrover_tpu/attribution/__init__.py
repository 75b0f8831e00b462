"""Performance attribution — what the program's own accounting reduces to.

Two reductions live here; both are read on every run:

- :mod:`~dlrover_tpu.attribution.phases` — the serving host/device
  split: the continuous-batching engine's round opens ``serve.*`` spans
  (:mod:`dlrover_tpu.observability.spans`) that book under admission,
  prefill, decode dispatch, host sync, retirement and overlap-hidden in a
  :class:`PhaseAccumulator`, which reduces them to ``serving_host_frac``
  plus a per-phase histogram (``/healthz``). The fleet gateway, the pool
  arbiter and the cluster scheduler keep accumulators of their own.
- :mod:`~dlrover_tpu.attribution.recovery` — the MTTR phase spool: where
  a recovery's time goes (rendezvous, restore, compile, first step).

Device time by operation is not accounted here any more: the interposer's
ring holds whole-executable envelopes, so its op buckets read ``other``.
Open the ``.xplane.pb`` of any ``jax.profiler`` session instead — the
program's spans sit there by name beside the device's operations
(``docs/observability.md``); ``benchmark/reduce_trace.py`` reduces it.
"""

from .phases import (  # noqa: F401
    DEVICE_PHASES,
    HOST_PHASES,
    PHASES,
    PhaseAccumulator,
    PhaseSplit,
)
from .recovery import (  # noqa: F401
    RECOVERY_DIR_ENV,
    aggregate as aggregate_recovery,
    record_phase_file,
)
from .recovery import PHASES as RECOVERY_PHASES  # noqa: F401

__all__ = [
    "RECOVERY_DIR_ENV",
    "RECOVERY_PHASES",
    "aggregate_recovery",
    "record_phase_file",
    "PHASES",
    "HOST_PHASES",
    "DEVICE_PHASES",
    "PhaseAccumulator",
    "PhaseSplit",
]
