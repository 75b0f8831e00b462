"""Device time by the scopes the program wrote.

A ``jax.profiler`` trace recorded on the TPU carries, beside the device
planes, a plane ``/host:metadata`` with one entry a compiled program, named
as the ``XLA Modules`` events are (``jit_step(123...)``), whose one bytes
stat is the serialized ``HloProto`` of the *optimized* module: every
instruction with the ``op_name`` it was traced under
(``jit(step)/jvp(GPT)/Block_3/CausalSelfAttention_0/dot_general``).
``jax.profiler.ProfileData`` does not show that plane's metadata, so
``programs`` walks the protobuf wire format itself (standard library only).
``table`` joins it to the ``XLA Ops`` events of ``reduce_trace.Trace``: each
event's *self time* (its duration less the events it contains: a ``while``
spans its body's) is booked to a pass and a scope path.

- pass: ``backward`` where the ``op_name`` holds ``transpose(`` (a
  rematerialised forward runs there and is counted there), ``update`` under
  ``train.optimizer`` / ``train.grad_norm`` / ``train.accumulate``, else
  ``forward``;
- scope path: the ``op_name``'s components that are scopes
  (``lower.lower``, what ``jax.named_scope`` is given under ``dlrover_tpu/``)
  or flax modules (a capital first, or ``_<n>`` last: ``Mlp_0``, ``block_7``;
  a module named in lower case with no number, ``ln_f``, reads as a
  primitive and is left out), outermost first, ``/`` between them;
  a transform's parentheses are looked into (``transpose(jvp(train.loss))``);
- ``unscoped``: instructions with no ``op_name`` (copies, layout changes),
  by opcode, and those whose ``op_name`` holds no scope, by opcode and
  primitive; ``unjoined_s``: events whose program or instruction the
  metadata plane does not hold; ``mixed_s``: fusions whose called
  computation holds instructions on scope paths that diverge (``block_3/Mlp_0``
  beside ``block_3/LayerNorm_1``; booked by the fusion's own ``op_name`` all
  the same): how far fusion across scope borders blurs the deepest level;
- an instruction with no ``op_name`` of its own that calls a computation (a
  fusion XLA made of parts) is booked by the path all the instructions it
  calls share; one that calls nothing named (the ``async-done`` and
  ``copy-done`` of a transfer the compiler scheduled: a wait for a weight's
  slice) is booked to the instruction its data goes to, and the table's
  ``by_consumer_s`` says how much of it was booked so.

    python3 benchmark/trace_scopes.py <trace dir or .pb> [--program <regex>] [--depth n] [--json]
    python3 benchmark/trace_scopes.py <a traced run's device_scopes.json> [--depth n]

prints, for the program that took most device time (or those matching), its
executions, their median device time, then self seconds and share by pass
and scope path to the given depth. ``benchmark/program_spans.py`` is the
host's table; this is the device's.
"""

import argparse
import bisect
import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reduce_trace  # noqa: E402

METADATA_PLANE = "/host:metadata"
DECODE_CHUNK = r"^jit_chunk"  # the one name models/serving.py jits its chunk under
UPDATE_SCOPES = ("train.optimizer", "train.grad_norm", "train.accumulate")
UNJOINED_MOST = 0.01  # of the time asked about: beyond it a reader returns None
OPS_KEPT = 32  # the heaviest instructions a table names beside its rows
SCOPE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
MODULE = re.compile(r"^([A-Z]\w*|[a-z]\w*_\d+)$")  # GPT, CausalSelfAttention_0, block_7
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


# -- the wire format -----------------------------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are passed
    over (nothing on the path is one)."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not a protobuf message")
        yield tag >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _ints(value):
    """A repeated int64 field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _instruction(buf):
    name = opcode = op_name = ""
    called, operands, own_id = [], [], None
    for f, v in _fields(buf):
        if f == 1:
            name = _text(v)
        elif f == 2:
            opcode = _text(v)
        elif f == 7:
            for f2, v2 in _fields(v):
                if f2 == 2:
                    op_name = _text(v2)
        elif f == 35:
            own_id = v
        elif f == 36:
            operands.extend(_ints(v))
        elif f == 38:
            called.extend(_ints(v))
    return name, opcode, op_name, called, own_id, operands


CONSUMER_HOPS = 4  # a wait's data reaches its consumer through a bitcast, a tuple, a copy at most


def hlo_instructions(hlo_proto) -> dict:
    """{instruction: (opcode, op_name, [op_names inside what it calls],
    consumer's op_name)} of a serialized ``HloProto``: every computation's
    instructions (their names are unique in a module). The consumer's is
    given where the instruction has no name of its own nor calls anything
    named (an ``async-done`` or ``copy-done`` the compiler made: a wait for
    data): the ``op_name`` of the first instruction that takes its result,
    through at most ``CONSUMER_HOPS`` nameless ones."""
    out, calls, inside, users, ids = {}, {}, {}, {}, {}
    for f, module in _fields(memoryview(hlo_proto)):
        if f != 1:
            continue
        for f2, computation in _fields(module):
            if f2 != 3:
                continue
            comp_id, names = None, []
            for f3, v in _fields(computation):
                if f3 == 2:
                    name, opcode, op_name, called, own_id, operands = _instruction(v)
                    out[name] = (opcode, op_name)
                    ids[own_id] = name
                    for operand in operands:
                        users.setdefault(operand, []).append(own_id)
                    if called:
                        calls[name] = called
                    if op_name and opcode != "parameter":  # a parameter's is its argument's name
                        names.append(op_name)
                elif f3 == 5:
                    comp_id = v
            inside[comp_id] = sorted(set(names))
    by_name = {name: own_id for own_id, name in ids.items()}

    def consumer(name):
        at = [by_name[name]]
        for _ in range(CONSUMER_HOPS):
            at = [u for i in at for u in users.get(i, ())]
            named = [out[ids[u]][1] for u in at if out[ids[u]][1]]
            if named or not at:
                return named[0] if named else ""
        return ""

    held = {}
    for name, (opcode, op_name) in out.items():
        called = [n for c in calls.get(name, ()) for n in inside.get(c, ())]
        held[name] = (opcode, op_name, called, "" if op_name or called or opcode == "parameter" else consumer(name))
    return held


def programs(xplane_path: str, wanted=None) -> dict:
    """{program as the ``XLA Modules`` line prints it: ``hlo_instructions``
    of its optimized module} from a trace's ``/host:metadata`` plane; only the
    programs named in ``wanted`` are decoded where it is given (an XL module
    is megabytes)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1 or not any(f2 == 2 and _text(v) == METADATA_PLANE for f2, v in _fields(plane)):
            continue
        for f2, entry in _fields(plane):
            if f2 != 4:
                continue
            for f3, meta in _fields(entry):
                if f3 != 2:
                    continue
                name, protos = "", []
                for f4, v in _fields(meta):
                    if f4 == 2:
                        name = _text(v)
                    elif f4 == 5:
                        protos.extend(v2 for f5, v2 in _fields(v) if f5 == 6)
                if protos and (wanted is None or name in wanted):
                    out[name] = hlo_instructions(protos[0])
    return out


# -- op_name -> pass and scope path -------------------------------------------

def components(op_name: str) -> list:
    """``a/jvp(b)/c`` -> [a, jvp(b), c]: slashes inside parentheses stay."""
    out, depth, at = [], 0, 0
    for i, c in enumerate(op_name):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            out.append(op_name[at:i])
            at = i + 1
    out.append(op_name[at:])
    return out


def scope_path(op_name: str) -> list:
    """The scopes and flax modules of one ``op_name``, outermost first. A
    rematerialised block's names start again from the model inside the
    transform that recomputes it (``transpose(jvp(GPT))/jvp(GPT)/checkpoint/
    block_7/...``): the path starts again there too."""
    path = []
    for part in components(op_name):
        wrapped = False
        while part.endswith(")") and "(" in part:  # transpose(jvp(train.loss)) -> train.loss
            part, wrapped = part[part.index("(") + 1:-1], True
        if SCOPE.match(part) or MODULE.match(part):
            if wrapped and path and part == path[0]:
                path = []
            path.append(part)
    return path


def pass_of(op_name: str, path) -> str:
    if "transpose(" in op_name:
        return "backward"
    return "update" if any(p in UPDATE_SCOPES for p in path) else "forward"


def diverge(a, b) -> bool:
    """Two scope paths of which neither continues the other."""
    n = min(len(a), len(b))
    return a[:n] != b[:n]


def book(record) -> tuple:
    """(kind, pass, key, mixed) for one instruction's (opcode, op_name,
    called, consumer): kind ``scoped`` with key the scope path (``consumer``
    where the path is that of the instruction a nameless wait's data goes
    to), or ``unscoped`` with key the opcode (and the primitive, where an
    ``op_name`` has no scope).
    An instruction with a name is booked by it (the first, where XLA joined
    several by ``;``); one without by what all the instructions it calls
    share (a fusion XLA made of parts), else by its consumer's name.
    ``mixed``: a fusion's parts, or joined names, lie on paths that diverge."""
    opcode, op_name, called, consumer = record
    own = [n for n in op_name.split(";") if n]
    names = own or called or ([consumer] if consumer else [])
    if not names:
        return "unscoped", "forward", opcode, False
    paths = [scope_path(n) for n in names]
    path = paths[0]
    if not own:
        while path and any(q[:len(path)] != path for q in paths):
            path = path[:-1]
    passed = pass_of(names[0], path)
    if not path:
        return "unscoped", passed, f"{opcode} ({components(own[0])[-1]})" if own else opcode, False
    if not own and not called:
        return "consumer", passed, "/".join(path), False
    parts = paths + ([scope_path(n) for n in called] if own and opcode == "fusion" else [])
    longest = max(parts, key=len)
    return "scoped", passed, "/".join(path), any(diverge(longest, q) for q in parts)


# -- events -> table -----------------------------------------------------------

def self_times(events):
    """[(self ns, text)] of [(start, end, text)]: an event's duration less
    the union of the events that lie inside it (wholly: one that runs past
    another's end is its sibling)."""
    out, stack = [], []  # stack entries: [end, covered until, self ns, text]

    def close():
        _, _, own, text = stack.pop()
        out.append((own, text))

    for s, e, text in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] < max(e, s + 1):  # over, or a sibling that overlaps this one
            close()
        if stack:
            parent = stack[-1]
            parent[2] -= max(0, min(e, parent[0]) - max(s, parent[1]))
            parent[1] = max(parent[1], min(e, parent[0]))
        stack.append([e, s, e - s, text])
    while stack:
        close()
    return out


def executions_matching(trace, pattern: str):
    """[(start, end, name)] of the first device's whole program executions
    inside the traced window whose name matches, sorted."""
    plane = trace.first_plane()
    if plane is None:
        return []
    lo, hi = trace.window
    return sorted((s, e, n) for s, e, n in trace.devices[plane]["modules"]
                  if s >= lo and e <= hi and re.search(pattern, n))


def table(trace, held: dict, executions) -> dict:
    """The self time of the first device's operation events that start inside
    ``executions`` [(start, end, program)], booked by ``held`` (what
    ``programs`` gave). Seconds; ``rows`` sorted by time."""
    executions = sorted(executions)
    starts = [s for s, _, _ in executions]
    per_program = {}
    for s, e, text in trace.devices[trace.first_plane()]["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < executions[i][1]:
            per_program.setdefault(executions[i][2], []).append((s, e, text))
    rows, unscoped, passes = {}, {}, {"forward": 0.0, "backward": 0.0, "update": 0.0}
    unjoined = mixed = total = by_consumer = 0.0
    heaviest = {}  # (program, text) -> [self seconds, events, where it was booked]
    for program, events in per_program.items():
        instructions = held.get(program)
        booked = {}  # an event's text -> where it goes: a step repeats its texts
        for own, text in self_times(events):
            own /= 1e9
            total += own
            if text not in booked:
                m = _INSTRUCTION.match(text)
                record = instructions.get(m.group(1)) if instructions and m else None
                booked[text] = book(record) if record else None
                heaviest[program, text] = [0.0, 0, booked[text]]
            where = booked[text]
            seen = heaviest[program, text]
            seen[0] += own
            seen[1] += 1
            if where is None:
                unjoined += own
                continue
            kind, passed, key, is_mixed = where
            if kind == "unscoped":
                unscoped[key] = unscoped.get(key, 0.0) + own
                continue
            passes[passed] += own
            rows[(passed, key)] = rows.get((passed, key), 0.0) + own
            if is_mixed:
                mixed += own
            if kind == "consumer":
                by_consumer += own
    durations = [(e - s) / 1e9 for s, e, _ in executions]
    ops = []  # the compiler's numbered names beside the scopes they answer to
    for (_, text), (secs, n, where) in sorted(heaviest.items(), key=lambda kv: -kv[1][0])[:OPS_KEPT]:
        kind, passed, key, _ = where or ("unjoined", "", "", False)
        ops.append(dict(instruction=reduce_trace.short_name(text), self_s=secs, events=n,
                        scope=key if kind == "scoped" else f"{kind} {key}".strip(), **{"pass": passed}))
    return dict(
        programs=sorted({n for _, _, n in executions}), executions=len(executions),
        median_execution_s=statistics.median(durations) if durations else None,
        total_s=total, passes=passes, unscoped_s=sum(unscoped.values()), unjoined_s=unjoined, mixed_s=mixed,
        by_consumer_s=by_consumer,
        rows=[dict(zip(("pass", "scope", "self_s"), (p, k, v)))
              for (p, k), v in sorted(rows.items(), key=lambda kv: -kv[1])],
        unscoped=dict(sorted(unscoped.items(), key=lambda kv: -kv[1])), ops=ops)


def scope_seconds(tab: dict, wanted) -> float:
    """Self seconds of the rows one of whose path components ``wanted(name)``
    accepts, whatever their pass."""
    return sum(r["self_s"] for r in tab["rows"] if any(wanted(part) for part in r["scope"].split("/")))


# what a decode step's rows are counted as: a row goes to the first kind that
# accepts a component of its path, innermost component first (the cache's
# write inside ``CausalSelfAttention_0`` is the engine's, not the attention's)
DECODE_PARTS = (
    ("head_sample", lambda p: p.endswith(".head") or p in ("serve.sample", "serve.cache_write", "serve.counters")),
    ("attend", lambda p: ".attend" in p or p.endswith(".attn") or "Attention" in p),
    ("state", lambda p: p.startswith(("gdn.", "mamba.", "lfm2.conv"))),
    ("moe", lambda p: p.startswith("moe.")),
)


def decode_part(scope: str):
    for part in reversed(scope.split("/")):
        for kind, accepts in DECODE_PARTS:
            if accepts(part):
                return kind
    return None


def decode_part_seconds(tab: dict, kind: str) -> float:
    return sum(r["self_s"] for r in tab["rows"] if decode_part(r["scope"]) == kind)


def share(tab, seconds_of):
    """``seconds_of(tab)`` as a share of the table's self time in percent;
    None where there is no table, the seconds are none (the scopes are not in
    the traced program) or the join missed over ``UNJOINED_MOST`` of the time."""
    if tab is None or tab["total_s"] <= 0 or tab["unjoined_s"] > UNJOINED_MOST * tab["total_s"]:
        return None
    seconds = seconds_of(tab)
    return 100.0 * seconds / tab["total_s"] if seconds else None


def scoped_seconds(tab: dict) -> float:
    return tab["total_s"] - tab["unscoped_s"] - tab["unjoined_s"]


# -- what a per-layer reader calls -----------------------------------------------

def by_scope(ctx, executions, keep_as: str = None):
    """``table`` over a run's trace for [(start, end)] of the first device's
    ``XLA Modules`` events, with the seconds it took to make (``reader_s``);
    None where the trace holds no record of their programs (a CPU trace has
    no metadata plane). With ``keep_as`` the table goes where the run's
    records do."""
    if ctx.trace is None or not executions or not ctx.trace.used_planes():
        return None
    from benchmark import harness

    t0 = time.time()
    spans = set(executions)
    named = [(s, e, n) for s, e, n in ctx.trace.devices[ctx.trace.first_plane()]["modules"] if (s, e) in spans]
    path = reduce_trace.find_xplane(os.path.join(ctx.run.work, "trace"))
    held = programs(path, {n for _, _, n in named}) if path else {}
    if not held:
        return None
    tab = table(ctx.trace, held, named)
    tab["reader_s"] = time.time() - t0
    if keep_as:
        out = os.path.join(ctx.run.work, keep_as)
        with open(out, "w") as f:
            json.dump(tab, f, indent=1)
        harness.keep([out], f"{ctx.run.cell}.seed{ctx.run.seed}.trace1")
    return tab


def step_table(ctx):
    """The table of the training step: the executions of the program that
    took most device time (``Trace.main_module``, as ``step_device_s``)."""
    if ctx.trace is None or "cycles" not in ctx.stamps:
        return None
    return _main_table(ctx, lambda: "^" + re.escape(ctx.trace.main_module()[0] or "no program") + r"\(")


def decode_table(ctx):
    """The table of the decode chunk: ``trace_names.decode_chunk`` of the
    configuration, else the name ``models/serving.py`` jits its chunk under."""
    if ctx.trace is None or "healthz" not in ctx.stamps:
        return None
    return _main_table(ctx, lambda: (ctx.config.get("trace_names") or {}).get("decode_chunk") or DECODE_CHUNK)


def _main_table(ctx, pattern):
    """The run's one table, made by the first reader that asks and kept with
    the run's records as ``device_scopes.json``."""
    if not hasattr(ctx, "_device_scopes"):
        found = executions_matching(ctx.trace, pattern())
        ctx._device_scopes = by_scope(ctx, [(s, e) for s, e, _ in found], keep_as="device_scopes.json")
    return ctx._device_scopes


# -- the operator's table ----------------------------------------------------------

def render(tab: dict, depth: int) -> str:
    lines = [f"{' '.join(tab['programs'])}: {tab['executions']} executions, median "
             f"{tab['median_execution_s'] * 1e3:.3f} ms, self time {tab['total_s']:.6f} s"]
    total = tab["total_s"] or 1.0
    for name in ("forward", "backward", "update"):
        if tab["passes"][name]:
            lines.append(f"  {name:<9}{tab['passes'][name]:11.6f} s {100 * tab['passes'][name] / total:6.2f}%")
    merged = {}
    for r in tab["rows"]:  # the layers of a stack read as one: block_7 -> block_*
        parts = [re.sub(r"^([a-z]\w*)_\d+$", r"\1_*", part) for part in r["scope"].split("/")]
        key = (r["pass"], "/".join(parts[:depth]))
        merged[key] = merged.get(key, 0.0) + r["self_s"]
    for (passed, scope), secs in sorted(merged.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {secs:11.6f} s {100 * secs / total:6.2f}%  {passed:<9}{scope}")
    for key, secs in tab["unscoped"].items():
        lines.append(f"  {secs:11.6f} s {100 * secs / total:6.2f}%  unscoped {key}")
    for key in ("unscoped_s", "mixed_s", "by_consumer_s", "unjoined_s"):
        lines.append(f"  {key:<14}{tab.get(key, 0.0):11.6f} s {100 * tab.get(key, 0.0) / total:6.2f}%")
    for op in tab.get("ops", []):
        lines.append(f"  {op['self_s']:11.6f} s {100 * op['self_s'] / total:6.2f}%  x{op['events']:<6} "
                     f"{op['instruction']}  <- {op['pass']} {op['scope']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="device time of a traced program by the scopes the program wrote")
    ap.add_argument("trace", help="a trace directory, an .xplane.pb, or a run's kept device_scopes.json")
    ap.add_argument("--program", default=None,
                    help="regex over the XLA Modules names; default: the one that took most time")
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--json", action="store_true", help="print the table as JSON")
    ns = ap.parse_args(argv)
    if ns.trace.endswith(".json"):  # the table a traced run kept
        with open(ns.trace) as f:
            print(render(json.load(f), ns.depth))
        return 0
    path = ns.trace if ns.trace.endswith(".pb") else reduce_trace.find_xplane(ns.trace)
    trace = path and reduce_trace.load(None, path)
    if not trace or not trace.used_planes():
        sys.stderr.write(f"no device plane under {ns.trace}\n")
        return 1
    pattern = ns.program or "^" + re.escape(trace.main_module()[0]) + r"\("
    by_program = {}
    for s, e, n in executions_matching(trace, pattern):
        by_program.setdefault(n, []).append((s, e, n))
    held = programs(path, set(by_program))
    for name, found in sorted(by_program.items(), key=lambda kv: -sum(e - s for s, e, _ in kv[1])):
        tab = table(trace, held, found)
        print(json.dumps(tab) if ns.json else render(tab, ns.depth))
    return 0 if by_program else 1


if __name__ == "__main__":
    sys.exit(main())
