"""Serving host/device split: scheduler-round phase accounting.

The continuous-batching engine's ``step()`` stamps five phase spans
per scheduler round (the VERDICT r5 #4 gap — a ~4.6x per-slot
throughput loss vs raw decode that nothing measured):

- ``admission``   host: queue pop, slot bookkeeping, swap adoption
- ``prefill``     device: prompt prefill + admit program
- ``decode_dispatch``  host: tracing/dispatching the decode chunk
- ``host_sync``   device: blocking fetch of the chunk's tokens — the
                  wait measures device execution on a sync backend
- ``retirement``  host: emit loop, completion bookkeeping
- ``overlap_hidden``  the pipelined scheduler's third category: host
                  work (admission, emission, retirement) performed
                  WHILE a decode chunk is in flight on the device.
                  The device is not idle during it, so it is neither
                  host nor device time — it is the host cost the
                  double-buffered round hid.

``serving_host_frac`` = host time / total — the fraction of a serving
round the DEVICE sits idle while the host schedules. Overlap-hidden
time counts toward the total but not toward host: the pipelined
scheduler's win shows up as a nonzero ``overlap_s`` and a reduced
``serving_host_frac`` over the same stream. The accumulator is pure
arithmetic over (phase, seconds) samples, so the split math is
unit-testable on synthetic timestamps without an engine.
"""

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from ..observability.spans import SpanAccumulator

PHASES = (
    "admission",
    "prefill",
    "decode_dispatch",
    "host_sync",
    "retirement",
    "overlap_hidden",
)
# Fleet gateway phases (dlrover_tpu/fleet/gateway.py) — a SEPARATE
# accumulator from the engine's: "route" and "redispatch" are
# gateway-host work (replica selection, failover bookkeeping);
# "proxy" is time spent waiting on the chosen replica's engine — the
# gateway's equivalent of device time, so a gateway accumulator's
# serving_host_frac reads as gateway overhead over end-to-end request
# time.
GATEWAY_PHASES = (
    "route",
    "proxy",
    "redispatch",
)
# Chip-pool arbiter phases (dlrover_tpu/pool/arbiter.py) — a third
# separate accumulator: "revoke" and "grant" are arbiter-host work
# (ledger transitions, dispatching the tenant call); "drain" is the
# wall time waiting on the tenant's cooperative reclaim (checkpointed
# training shrink, replica drain) — the arbiter's equivalent of
# backend time, so its host_frac reads as arbitration overhead over
# end-to-end capacity-move latency.
POOL_PHASES = (
    "revoke",
    "drain",
    "grant",
)
HOST_PHASES = frozenset(
    {
        "admission",
        "decode_dispatch",
        "retirement",
        "route",
        "redispatch",
        "revoke",
        "grant",
    }
)
DEVICE_PHASES = frozenset({"prefill", "host_sync", "proxy", "drain"})
OVERLAP_PHASES = frozenset({"overlap_hidden"})

@dataclass
class PhaseSplit:
    """One reduction of an accumulator: totals, fractions, histogram."""

    total_s: float
    host_s: float
    device_s: float
    serving_host_frac: float
    rounds: int
    phases: Dict[str, Dict]
    # host time hidden behind in-flight device chunks (the pipelined
    # scheduler's round): in total_s, in neither host_s nor device_s
    overlap_s: float = 0.0
    # the accumulator's counters (requests admitted, seconds waited, ...)
    counters: Dict[str, float] = field(default_factory=dict)

    def summary(self) -> Dict:
        """Compact dict for /healthz and bench extras (floats only,
        bounded key count — the 1,800-byte line budget applies). Only a
        phase's total ends in ``_ms``: readers sum every such key into the
        round's total. A counter rides as ``<name>_sum`` where it holds
        seconds (``*_s``) and as ``<name>_n`` where it counts."""
        out = {
            "serving_host_frac": round(self.serving_host_frac, 4),
            "rounds": self.rounds,
        }
        if self.overlap_s:
            out["overlap_hidden_s"] = round(self.overlap_s, 4)
        for name, stat in self.phases.items():
            out[f"{name}_ms"] = round(stat["total_s"] * 1e3, 2)
        for name, value in self.counters.items():
            if name.endswith("_s"):
                out[f"{name}_sum"] = round(value, 6)
            else:
                out[f"{name}_n"] = value
        return out


class PhaseAccumulator(SpanAccumulator):
    """A :class:`SpanAccumulator` whose names are phases of a round, with
    the host/device/hidden reduction over them. Fed by ``span(name,
    book=<phase>)`` in the serving engine and by ``add`` elsewhere. A
    phase's seconds are its spans' *self* time, so phases that nest (the
    prefill inside an admission) still partition the round."""

    def __init__(self):
        super().__init__()
        self.rounds = 0

    def add_round(
        self, spans: List[Tuple[str, float]]
    ) -> None:
        """One scheduler round's (phase, seconds) spans — the synthetic
        -timestamp entry point the tests drive."""
        for phase, dur_s in spans:
            self.add(phase, dur_s)
        self.rounds += 1

    def reset(self) -> None:
        super().reset()
        self.rounds = 0

    def split(self) -> PhaseSplit:
        # snapshot first: split() is read from other threads (/healthz
        # handler) while the driver's step() inserts phase keys —
        # dict(d) is a single C-level copy under the GIL, so the
        # iteration below never sees a resize
        stats = self.stats()
        host_s = sum(
            s.self_s for p, s in stats.items() if p in HOST_PHASES
        )
        overlap_s = sum(
            s.self_s for p, s in stats.items() if p in OVERLAP_PHASES
        )
        device_s = sum(
            s.self_s for p, s in stats.items()
            if p not in HOST_PHASES and p not in OVERLAP_PHASES
        )
        total_s = host_s + device_s + overlap_s
        return PhaseSplit(
            total_s=total_s,
            host_s=host_s,
            device_s=device_s,
            overlap_s=overlap_s,
            serving_host_frac=(host_s / total_s) if total_s > 0 else 0.0,
            rounds=self.rounds,
            counters=self.counters(),
            phases={
                name: {
                    "total_s": round(stat.self_s, 6),
                    "count": stat.count,
                    "mean_ms": round(
                        stat.self_s / stat.count * 1e3, 3
                    )
                    if stat.count
                    else 0.0,
                    "max_ms": round(stat.max_s * 1e3, 3),
                    "host": name in HOST_PHASES,
                    "hist_log2us": list(stat.hist),
                }
                for name, stat in stats.items()
            },
        )
