"""What the program's own spans and counters hold, for the readers under
``layer_metrics/`` that read them.

The program names its own time with ``dlrover_tpu/observability/spans.py``:
``jax.profiler.TraceAnnotation``s that land in the traced run's
``.xplane.pb`` beside the device planes (``ctx.trace.host[<name>]``, on
the host's clock, every thread's spans of one name in one sorted list),
and counters that ride in ``/healthz``'s ``phase_split`` under names that
end in ``_n`` (counts) or ``_s_sum`` (seconds), which the serving driver
carries whole from both ends of the window. A program that has no such
span or counter (the parent of the PR that added them) gives ``None``
everywhere here.
"""

import bisect
import statistics


def inside(spans, lo, hi):
    """The (start, end) pairs that lie wholly inside [lo, hi]."""
    return [(s, e) for s, e in spans if s >= lo and e <= hi]


def seconds(spans) -> float:
    return sum(e - s for s, e in spans) / 1e9


# -- the flash save ---------------------------------------------------------

def saves(trace):
    """One dict per ``ckpt.save`` span of the traced window: the whole
    span, and inside it the summed per-leaf waits on the device-to-host
    copy (``ckpt.save.d2h``) and copies into the segment
    (``ckpt.save.memcpy``), in seconds. None where there is no trace or
    no such span."""
    if trace is None:
        return None
    roots = inside(trace.host.get("ckpt.save", []), *trace.window)
    if not roots:
        return None
    d2h, memcpy = trace.host.get("ckpt.save.d2h", []), trace.host.get("ckpt.save.memcpy", [])
    return [dict(whole_s=(e - s) / 1e9, d2h_s=seconds(inside(d2h, s, e)),
                 memcpy_s=seconds(inside(memcpy, s, e))) for s, e in roots]


def faster_half(found: list) -> list:
    """The faster half of the traced saves by their whole span (the n // 2
    fastest, at least one), as ``save_stall_s`` takes its stalls: the save
    when nothing else gets in its way."""
    ranked = sorted(found, key=lambda f: f["whole_s"])
    return ranked[: max(1, len(ranked) // 2)]


def save_part(ctx, part):
    """Mean of ``part(save)`` over the faster half of the traced saves."""
    found = saves(ctx.trace)
    if not found:
        return None
    half = faster_half(found)
    return sum(part(f) for f in half) / len(half)


# -- the train loop ---------------------------------------------------------

def steps(trace):
    """One dict per ``train.step_dispatch`` span of the traced window whose
    ``train.data_wait`` before it and ``train.report`` after it lie in the
    window too: the three durations in seconds. None where there is no
    trace or no such span."""
    if trace is None:
        return None
    lo, hi = trace.window
    dispatch = inside(trace.host.get("train.step_dispatch", []), lo, hi)
    if not dispatch:
        return None
    waits = inside(trace.host.get("train.data_wait", []), lo, hi)
    reports = inside(trace.host.get("train.report", []), lo, hi)
    wait_ends, report_starts = [e for _, e in waits], [s for s, _ in reports]
    out = []
    for k, (s, e) in enumerate(dispatch):
        i = bisect.bisect_right(wait_ends, s) - 1  # the last wait that ended before it
        j = bisect.bisect_left(report_starts, e)  # the first report that began after it
        before = dispatch[k - 1][1] if k else lo
        after = dispatch[k + 1][0] if k + 1 < len(dispatch) else hi
        if i < 0 or j >= len(reports) or waits[i][0] < before or reports[j][1] > after:
            continue  # this step's own wait or report is outside the window
        out.append(dict(dispatch_s=(e - s) / 1e9,
                        data_wait_s=(waits[i][1] - waits[i][0]) / 1e9,
                        report_s=(reports[j][1] - reports[j][0]) / 1e9))
    return out or None


def step_median(ctx, part):
    found = steps(ctx.trace)
    return statistics.median(part(f) for f in found) if found else None


# -- the server's counters --------------------------------------------------

def counter_in_window(stamps: dict, key: str):
    """How far ``phase_split[key]`` moved between the window's two ends;
    None where the stamps are not a serving run's or the program has no
    such counter."""
    first = stamps.get("phase_split_open")
    last = (stamps.get("healthz") or {}).get("phase_split")
    if not first or not last or key not in last:
        return None
    return last[key] - first.get(key, 0)


def per_admitted_request(stamps: dict, key: str):
    """A ``*_s_sum`` counter's seconds in the window over the requests
    admitted in it."""
    total = counter_in_window(stamps, key)
    admitted = counter_in_window(stamps, "requests_admitted_n")
    if total is None or not admitted or admitted <= 0:
        return None
    return total / admitted


def describe(path_or_dir: str) -> None:
    """Every traced save's split and the loop's medians, by hand:
    ``python3 benchmark/program_spans.py <dir or .xplane.pb>``."""
    from benchmark import reduce_trace

    path = path_or_dir if path_or_dir.endswith(".pb") else reduce_trace.find_xplane(path_or_dir)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {path_or_dir}")
    trace = reduce_trace.load(None, path)
    lo, hi = trace.window
    for s, e in inside(trace.host.get("ckpt.save", []), lo, hi):
        parts = {name: seconds(inside(trace.host.get(f"ckpt.save.{name}", []), s, e))
                 for name in ("ready", "snapshot", "plan", "ensure", "d2h", "memcpy")}
        wrapper = [(ws, we) for ws, we in trace.host.get("save_call", []) if ws <= s and e <= we]
        print(f"ckpt.save at +{(s - lo) / 1e9:.3f} s: whole {(e - s) / 1e9:.6f} s",
              " ".join(f"{k} {v:.6f}" for k, v in parts.items()),
              f"save_call {(wrapper[0][1] - wrapper[0][0]) / 1e9:.6f}" if wrapper else "")
    found = steps(trace)
    if found:
        for key in ("data_wait_s", "dispatch_s", "report_s"):
            print(f"train {key}: median {statistics.median(f[key] for f in found):.6f} s over {len(found)} steps")
    for name in sorted(n for n in trace.host if n.startswith("serve.")):
        spans = inside(trace.host[name], lo, hi)
        if spans:
            print(f"{name}: {len(spans)} spans, {seconds(spans):.6f} s, "
                  f"median {statistics.median(e - s for s, e in spans) / 1e9:.6f}, "
                  f"max {max(e - s for s, e in spans) / 1e9:.6f}")


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    describe(sys.argv[1])
