"""From a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer readers and the result line's ``device`` and ``breakdown`` need.

Kept with the benchmark so that every PR computes the same number in the
same way. ``load(dir)`` finds the newest trace under a directory and
returns a :class:`Trace`, or None where there is none. A trace holds, on
one clock (nanoseconds):

- per device plane (``/device:TPU:<n>``) the events of its ``XLA Ops`` line
  (one per executed HLO operation) and of its ``XLA Modules`` line (one per
  executed program);
- the host's named spans (``jax.profiler.TraceAnnotation``), among them the
  two markers ``bench_trace_start`` / ``bench_trace_stop`` that the traced
  process writes right after the profiler starts and right before it
  stops: the traced window runs from the first's start to the second's end.
  A window cut out of a program that goes on running (a server's) carries
  ``bench_trace_live`` too, and runs from the first program the device
  began ``LIVE_SETTLE_S`` after the opening marker to the end of the
  device's record: of the program in flight at the stop the profiler keeps
  only what had finished, up to a whole chunk short of the marker, and the
  first launches under a profiler just started stall in PJRT's ``Execute``
  (4-71 ms of a dry device, within some 120 ms of the marker, in five runs
  of fourteen); both read as idleness of the server (``PERF.md`` section 6,
  PR 55).

The device planes' clocks are brought onto the host's first
(``clock_offset_ns``). Busy time is the union of a device's operation
intervals inside the window; idle is the rest. An idle gap is attributed to the named host span
that covers most of it.

``python3 benchmark/reduce_trace.py <dir>`` prints what a trace holds.
"""

import glob
import os
import re
import socket
import statistics
import sys
import time

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_START, MARK_STOP, MARK_LIVE = "bench_trace_start", "bench_trace_stop", "bench_trace_live"
LIVE_SETTLE_S = 0.25  # of a live window's head: twice the longest stall seen after the profiler's start


def start_trace(trace_dir: str) -> None:
    """Start the profiler as the benchmark wants it (no Python tracer: it
    slows the host it measures) and write the window's opening marker."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(MARK_START):
        pass


def stop_trace(log=None, live: bool = False) -> dict:
    """Write the window's closing marker (and, where the traced program goes
    on running, ``bench_trace_live``), stop the profiler and write the one
    file ``load`` opens: ``<dir>/plugins/profile/<stamp>/<host>.xplane.pb``.

    ``jax.profiler.stop_trace`` (JAX 0.9.0) is ``session.stop_and_export``,
    which beside that file converts every event to a ``trace.json.gz`` that
    no reader here opens: 154 of 229 s at 2.5 M events (``PERF.md`` section
    7 (14)). ``session.stop()`` returns the same serialized ``XSpace``, and
    its bytes are written as they are. Where JAX keeps its session elsewhere
    the fallback is ``jax.profiler.stop_trace()``, and ``wrote`` says which
    it was. Returns ``collect_s``, ``export_s``, ``xplane_bytes``, ``wrote``;
    ``log(text)`` is told each half as it ends, so that a stop nobody waits
    out has still said how far it came."""
    import jax

    say = log or (lambda text: None)
    with jax.profiler.TraceAnnotation(MARK_STOP):
        pass
    if live:
        with jax.profiler.TraceAnnotation(MARK_LIVE):
            pass
    t0 = time.time()
    held = _held_session()
    if held is None:
        say("trace_stop: no session handle, jax.profiler.stop_trace() collects and exports")
        jax.profiler.stop_trace()
        return dict(collect_s=None, export_s=time.time() - t0, xplane_bytes=None, wrote="jax.profiler.stop_trace")
    state, log_dir = held
    with state.lock:
        xspace = state.profile_session.stop()
        t1 = time.time()
        say(f"trace_stop: collect_s={t1 - t0:.2f} xplane_bytes={len(xspace)}")
        path = os.path.join(log_dir, "plugins", "profile", time.strftime("%Y_%m_%d_%H_%M_%S"),
                            socket.gethostname() + ".xplane.pb")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "wb") as f:
            f.write(xspace)
        state.reset()
    out = dict(collect_s=t1 - t0, export_s=time.time() - t1, xplane_bytes=len(xspace), wrote="xplane.pb")
    say(f"trace_stop: export_s={out['export_s']:.2f} wrote={path}")
    return out


def _held_session():
    """(JAX's profile state, its log directory) where a session runs and the
    state is shaped as in JAX 0.9.0; None where it is not, and the caller
    falls back on JAX's own stop."""
    try:
        from jax._src import profiler as jax_profiler

        state = jax_profiler._profile_state
        state.lock, state.reset, state.profile_session.stop  # noqa: B018 — what stop_trace will use
        if not state.log_dir or state.create_perfetto_trace:
            return None
        return state, str(state.log_dir)
    except (ImportError, AttributeError):  # another JAX, or no session: its own stop says so
        return None


def union(intervals):
    """Merged, sorted copy of [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def overlap(a, b) -> float:
    """Summed overlap of two merged, sorted interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def gaps(busy, lo, hi):
    """The idle intervals of a merged busy list inside [lo, hi]."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


class Trace:
    def __init__(self, devices: dict, host: dict):
        self.devices = devices  # plane -> {"ops": [(s, e, name)], "modules": [...]}
        self.host = host  # span name -> [(s, e)]
        self.events = {}  # ``load`` counts what it read of the file
        self._busy = {}
        self.head_cut_s = self.tail_cut_s = 0.0  # a live window: what went at either end
        starts = self.host.get(MARK_START) or []
        stops = self.host.get(MARK_STOP) or []
        if starts and stops:
            lo, hi = starts[0][0], stops[-1][1]
            if self.host.get(MARK_LIVE):  # whole programs under a running profiler, and nothing else
                settled = lo + LIVE_SETTLE_S * 1e9
                begun = [s for d in devices.values() for s, _, _ in d["modules"] if settled <= s < hi]
                ends = [e for d in devices.values() for s, e, _ in d["ops"] if lo < e and s < hi]
                first, last = min(begun, default=lo), min(hi, max(ends, default=hi))
                if first < last:
                    self.head_cut_s, self.tail_cut_s, lo, hi = (first - lo) / 1e9, (hi - last) / 1e9, first, last
            self.window = (lo, hi)
        else:  # no markers: from the first device operation to the last
            ops = [o for d in devices.values() for o in d["ops"]]
            self.window = (min(o[0] for o in ops), max(o[1] for o in ops)) if ops else (0, 0)

    # -- busy / idle ----------------------------------------------------
    def busy(self, plane: str):
        if plane not in self._busy:
            self._busy[plane] = union((s, e) for s, e, _ in self.devices[plane]["ops"])
        return self._busy[plane]

    def used_planes(self, chips: int = None):
        planes = sorted(p for p, d in self.devices.items() if d["ops"])
        return planes[:chips] if chips else planes

    def first_plane(self):
        planes = self.used_planes()
        return planes[0] if planes else None

    def busy_and_window(self, chips: int) -> dict:
        """``busy_s`` (averaged over the chips used) and ``window_s``."""
        lo, hi = self.window
        planes = self.used_planes(chips)
        if not planes:
            return {"busy_s": 0.0, "window_s": (hi - lo) / 1e9}
        busy = [total(clip(self.busy(p), lo, hi)) for p in planes]
        return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9}

    def idle_share_inside(self, spans, plane: str = None) -> float:
        """Idle share of the device inside the given host intervals (ns)."""
        plane = plane or self.first_plane()
        spans = union(clip(spans, *self.window))
        inside = total(spans)
        if inside <= 0:
            return None
        return 1.0 - overlap(self.busy(plane), spans) / inside

    # -- operations -----------------------------------------------------
    def op_seconds(self, plane: str = None) -> dict:
        """Operation name -> [seconds, count] inside the window."""
        plane = plane or self.first_plane()
        lo, hi = self.window
        out = {}
        for s, e, name in self.devices.get(plane, {}).get("ops", []):
            if e > lo and s < hi:
                acc = out.setdefault(name, [0.0, 0])
                acc[0] += (min(e, hi) - max(s, lo)) / 1e9
                acc[1] += 1
        return out

    def module_durations(self, plane: str = None) -> dict:
        """Program name -> list of device seconds of each whole execution."""
        plane = plane or self.first_plane()
        lo, hi = self.window
        out = {}
        for s, e, name in self.devices.get(plane, {}).get("modules", []):
            if s >= lo and e <= hi:
                out.setdefault(name.split("(")[0], []).append((e - s) / 1e9)
        return out

    def main_module(self, plane: str = None):
        """(name, durations) of the program that took most device time."""
        mods = self.module_durations(plane)
        if not mods:
            return None, []
        name = max(mods, key=lambda n: sum(mods[n]))
        return name, mods[name]

    # -- breakdown ------------------------------------------------------
    def idle_gaps_by_span(self, span_names, rest: str = "host_other", plane: str = None):
        """Idle seconds of the window by the named host span that covered
        them; what no named span covers goes under ``rest``."""
        plane = plane or self.first_plane()
        lo, hi = self.window
        idle = gaps(self.busy(plane), lo, hi)
        out, left = {}, total(idle) / 1e9
        for name in span_names:
            covered = overlap(idle, union(clip(self.host.get(name, []), lo, hi))) / 1e9
            out[name] = covered
            left -= covered
        out[rest] = max(left, 0.0)
        return out

    def breakdown(self, span_names, rest: str = "host_other") -> dict:
        if not self.used_planes():
            return {"device_ops": [], "idle_gaps": []}
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1][0])[:10]
        idle = sorted(self.idle_gaps_by_span(span_names, rest).items(), key=lambda kv: -kv[1])
        return {"device_ops": [[short_name(n), v[0]] for n, v in ops],
                "idle_gaps": [[n, v] for n, v in idle[:10]]}


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def short_name(name: str) -> str:
    """``fusion.5 f32[32,1024,50304]`` from an event that carries the whole
    HLO instruction: the instruction's name and its (first) result shape."""
    m = _HLO.match(name)
    if not m:
        return name[:96]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def find_xplane(trace_dir: str):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    return found[-1] if found else None


def clock_offset_ns(modules, enqueued, completed) -> float:
    """How far a device plane's clock lags the host's, in ns (a fixture
    recorded on the v5e read 7.8 ms): a program starts on the device after
    the host enqueued it and ends before its completion callback, and both
    host events carry the execution's ``run_id``. The offset is the least
    that puts every start after its enqueue; where a launch met an idle
    device that is the true offset less some 0.1 ms of launch latency."""
    lower = [enqueued[r] - s for r, (s, _) in modules.items() if r in enqueued]
    upper = [completed[r] - e for r, (_, e) in modules.items() if r in completed]
    if not lower:
        return 0.0
    lo = max(lower)
    return lo if not upper or lo <= min(upper) else (lo + min(upper)) / 2.0


def load(trace_dir: str, path: str = None):
    path = path or find_xplane(trace_dir)
    if path is None:
        return None
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # reading a file needs no chip
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, runs = {}, {}, {}
    enqueued, completed = {}, {}  # device ordinal -> run_id -> host ns
    n_host = 0  # every event of the host's lines, kept or not
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            entry = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if not key:
                    continue
                for e in line.events:
                    entry[key].append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                    if key == "modules":
                        run_id = dict(e.stats).get("run_id")
                        if run_id is not None:
                            runs.setdefault(plane.name, {})[run_id] = (
                                e.start_ns, e.start_ns + e.duration_ns)
            devices[plane.name] = entry
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    n_host += 1
                    name = e.name
                    if name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        stats = dict(e.stats)
                        table = enqueued if name == "DoEnqueueProgram" else completed
                        table.setdefault(stats.get("device_ordinal", 0), {})[stats.get("run_id")] = e.start_ns
                    if e.duration_ns >= 0 and not name.startswith(("$", "ThreadpoolListener")):
                        host.setdefault(name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    offsets = {}
    for name, entry in devices.items():
        ordinal = int(name.rsplit(":", 1)[1])
        shift = clock_offset_ns(runs.get(name, {}), enqueued.get(ordinal, {}), completed.get(ordinal, {}))
        offsets[name] = shift
        for key in ("ops", "modules"):
            entry[key] = [(s + shift, e + shift, n) for s, e, n in entry[key]]
    for spans in host.values():
        spans.sort()
    trace = Trace(devices, host)
    trace.clock_offsets_ns = offsets
    n_device = sum(len(d["ops"]) + len(d["modules"]) for d in devices.values())  # the two lines read
    trace.events = dict(device=n_device, host=n_host, file_bytes=os.path.getsize(path),
                        head_cut_s=trace.head_cut_s, tail_cut_s=trace.tail_cut_s)
    return trace


def describe(path_or_dir: str) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    path = path_or_dir if path_or_dir.endswith(".pb") else find_xplane(path_or_dir)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = {}
            for e in events:
                acc = names.setdefault(e.name, [0, 0.0])
                acc[0] += 1
                acc[1] += e.duration_ns / 1e9
            print(f"  LINE {line.name!r}: {len(events)} events")
            for name, (n, secs) in sorted(names.items(), key=lambda kv: -kv[1][1])[:12]:
                print(f"      {secs:10.6f} s  x{n:<6} {short_name(name)}")
    trace = load(None, path)
    print("clock offsets ns", trace.clock_offsets_ns)
    print("window_ns", trace.window, trace.busy_and_window(len(trace.used_planes()) or 1))
    name, durs = trace.main_module()
    if durs:
        print("main module", name, len(durs), "median_s", statistics.median(durs))


if __name__ == "__main__":
    describe(sys.argv[1])
