"""The program's own spans (dlrover_tpu/observability/spans.py).

A ``jax.profiler`` session on the CPU records host ``TraceAnnotation``s
too, so what the flash save, the train loop and the serving round write
onto the profiler's clock can be pinned here without a chip: every span
name of the contract appears, lies inside its parent on the same thread's
line, and leaves the same seconds in the accumulator that ``/healthz``
reads. The counters are checked where they are counted.
"""

import glob
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.attribution.phases import PhaseAccumulator
from dlrover_tpu.observability import spans
from dlrover_tpu.observability.spans import SpanAccumulator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
pytestmark = pytest.mark.filterwarnings("ignore:builtin type event_stats")

# the parent commit's ``phase_split`` keys that end in ``_ms`` for the
# stream below (benchmark/layer_metrics/serve_host_frac.py sums every such
# key into its total: a new one would silently change an accepted metric)
PARENT_MS_KEYS = {
    False: {"admission_ms", "prefill_ms", "decode_dispatch_ms",
            "host_sync_ms", "retirement_ms"},
    True: {"admission_ms", "prefill_ms", "decode_dispatch_ms",
           "host_sync_ms", "retirement_ms", "overlap_hidden_ms"},
}
STREAM = [[5, 9, 2], [7, 1], [3, 3, 8], [9], [2, 4], [6, 1, 1]]


def record(trace_dir, body):
    """Run ``body`` inside a profiler session; return (its result,
    {span name: [(line, start_ns, end_ns, stats)]}) of the host plane."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        result = body()
    finally:
        jax.profiler.stop_trace()
    found = sorted(
        glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                  recursive=True),
        key=os.path.getmtime,
    )
    data = jax.profiler.ProfileData.from_file(found[-1])
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for n, line in enumerate(plane.lines):
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (n, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                )
    return result, out


def booked(acc):
    return {k: (v.total_s, v.count) for k, v in acc.stats().items()}


def tiny_engine(overlap):
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    model = GPT(
        GPTConfig(
            vocab_size=64, max_seq_len=128, num_layers=1,
            num_heads=2, head_dim=8, embed_dim=16, use_remat=False,
        )
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return ContinuousBatchingEngine(
        model, params,
        SamplingConfig(max_new_tokens=4, temperature=0.0),
        batch_size=2, prompt_width=8, decode_chunk=2,
        cache_layout="per_row", overlap=overlap,
    )


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """One socket/shm namespace for the module's checkpoint engines."""
    import dlrover_tpu.common.multi_process as mp
    from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
    from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler

    root = tmp_path_factory.mktemp("spans")
    name = f"spans_{os.getpid()}"
    patch = pytest.MonkeyPatch()
    patch.setattr(mp, "SOCKET_TMP_DIR", str(root / "sockets"))
    patch.setenv("DLROVER_JOB_NAME", name)
    AsyncCheckpointSaver.reset()
    yield root
    AsyncCheckpointSaver.reset()
    for entry in os.listdir("/dev/shm"):
        if entry.startswith(f"dlrover_{name}_"):
            SharedMemoryHandler(
                0, name=entry.split(f"dlrover_{name}_", 1)[1]
            ).unlink()
    patch.undo()


@pytest.fixture(scope="module")
def ckpt_session(job):
    """A blocking save, then an async one, of a tiny tree."""
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.common import events

    seen = []

    class Sink(events.Exporter):
        def export(self, event):
            seen.append(event.to_dict())

    engine = CheckpointEngine(
        str(job / "ckpt"), standalone=True, replicate=False
    )
    engine._events._em = events.EventEmitter("trainer", Sink())
    tree = {
        "w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
        "b": jnp.ones((3,), jnp.bfloat16),
        "n": np.arange(4),
    }
    before = booked(spans.process_accumulator())

    def body():
        t0 = time.time_ns()
        assert engine.save_to_memory(1, tree)
        t1 = time.time_ns()
        assert engine.save_to_memory(2, tree, block=False)
        assert engine.wait_staged(30.0)
        return t0, t1

    try:
        wall, found = record(job / "trace_ckpt", body)
    finally:
        engine.shm.unlink()
        engine.close()
    return dict(spans=found, wall=wall, before=before, events=seen,
                after=booked(spans.process_accumulator()))


@pytest.fixture(scope="module")
def pooled_session(job):
    """Two blocking saves of a 40 MiB tree, on four CPUs: the copy pool's."""
    from dlrover_tpu.checkpoint import shm_handler
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.common import events

    seen = []

    class Sink(events.Exporter):
        def export(self, event):
            seen.append(event.to_dict())

    patch = pytest.MonkeyPatch()
    patch.setattr(shm_handler.os, "sched_getaffinity",
                  lambda pid: set(range(4)))
    engine = CheckpointEngine(
        str(job / "ckpt_pooled"), standalone=True, replicate=False
    )
    engine._events._em = events.EventEmitter("trainer", Sink())
    tree = {
        "w": jnp.arange(9 << 20, dtype=jnp.float32),
        "b": jnp.ones((3,), jnp.bfloat16),
        "n": np.arange(1 << 20),
    }
    before = booked(spans.process_accumulator())

    def body():
        assert engine.save_to_memory(1, tree)
        assert engine.save_to_memory(2, tree)
        return engine.shm.read_meta().total_bytes

    try:
        total, found = record(job / "trace_pooled", body)
    finally:
        engine.shm.unlink()
        engine.close()
        patch.undo()
    return dict(spans=found, total_bytes=total, before=before, events=seen,
                after=booked(spans.process_accumulator()))


@pytest.fixture(scope="module")
def train_session(job):
    """Four steps of ``ElasticTrainLoop`` over a toy step."""
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.trainer.loop import ElasticTrainLoop

    @jax.jit
    def step(state, x):
        return {"v": state["v"] + jnp.asarray(x).sum()}, state["v"].sum()

    engine = CheckpointEngine(
        str(job / "ckpt_train"), standalone=True, replicate=False
    )
    before = booked(spans.process_accumulator())

    def body():
        loop = ElasticTrainLoop(
            engine, step, max_steps=4, storage_every=100, memory_every=2
        )
        loop.run(
            {"v": jnp.zeros(3)},
            ((np.ones((2,), np.float32),) for _ in range(10)),
        )

    try:
        _, found = record(job / "trace_train", body)
    finally:
        engine.shm.unlink()
        engine.close()
    return dict(spans=found, before=before,
                after=booked(spans.process_accumulator()))


def serve_session(trace_dir, overlap):
    """The stream through a tiny engine, then two requests through the
    daemon (its ``serve.inbox`` / ``serve.complete``)."""
    from dlrover_tpu.launcher.serve import ServingDaemon

    eng = tiny_engine(overlap)
    eng.run(STREAM[:2])  # compile outside the session
    eng.phases.reset()
    before = booked(spans.process_accumulator())

    def body():
        done = eng.run(STREAM)
        daemon = ServingDaemon(eng).start()
        try:
            done.append(daemon.complete([5, 9, 2], timeout=60.0))
            done.append(daemon.complete([7, 1], timeout=60.0))
        finally:
            daemon.stop()
        return done

    done, found = record(trace_dir, body)
    return dict(spans=found, engine=eng, completions=done, before=before,
                after=booked(spans.process_accumulator()))


@pytest.fixture(scope="module")
def serve_sync(job):
    return serve_session(job / "trace_sync", overlap=False)


@pytest.fixture(scope="module")
def serve_overlapped(job):
    return serve_session(job / "trace_overlapped", overlap=True)


# (session fixture, span, the parent it must lie inside or None for a root)
CONTRACT = [
    ("ckpt_session", "ckpt.save", None),
    ("ckpt_session", "ckpt.save.ready", "ckpt.save"),
    ("ckpt_session", "ckpt.save.snapshot", "ckpt.save"),
    ("ckpt_session", "ckpt.save.plan", "ckpt.save"),
    ("ckpt_session", "ckpt.save.ensure", "ckpt.save"),
    ("ckpt_session", "ckpt.save.d2h", "ckpt.save"),
    ("ckpt_session", "ckpt.save.memcpy", "ckpt.save"),
    ("ckpt_session", "ckpt_save", "ckpt.save"),  # the DurationSpan's own
    ("ckpt_session", "ckpt.save.copy", "ckpt.save.memcpy"),  # inline: the caller's
    ("pooled_session", "ckpt.save.plan", "ckpt.save"),
    ("pooled_session", "ckpt.save.ensure", "ckpt.save"),
    ("pooled_session", "ckpt.save.d2h", "ckpt.save"),
    ("pooled_session", "ckpt.save.memcpy", "ckpt.save"),
    ("pooled_session", "ckpt.save.copy", None),  # on the pool's lines
    ("ckpt_session", "ckpt.stage", None),
    ("ckpt_session", "ckpt.save.plan", "ckpt.stage"),
    ("ckpt_session", "ckpt.save.d2h", "ckpt.stage"),
    ("ckpt_session", "ckpt.save.memcpy", "ckpt.stage"),
    ("ckpt_session", "ckpt_save", "ckpt.stage"),
    ("train_session", "train.data_wait", None),
    ("train_session", "train.step_dispatch", None),
    ("train_session", "train.report", None),
    ("train_session", "ckpt.save", None),  # the loop's saves: not wrapped again
    ("serve_sync", "serve.round", None),
    ("serve_sync", "serve.admission", "serve.round"),
    ("serve_sync", "serve.prefill", "serve.round"),
    ("serve_sync", "serve.decode_dispatch", "serve.round"),
    ("serve_sync", "serve.host_sync", "serve.round"),
    ("serve_sync", "serve.retirement", "serve.round"),
    ("serve_sync", "serve.inbox", None),
    ("serve_sync", "serve.complete", None),
    ("serve_overlapped", "serve.round", None),
    ("serve_overlapped", "serve.admission", "serve.round"),
    ("serve_overlapped", "serve.prefill", "serve.round"),
    ("serve_overlapped", "serve.decode_dispatch", "serve.round"),
    ("serve_overlapped", "serve.host_sync", "serve.round"),
    ("serve_overlapped", "serve.retirement", "serve.round"),
    ("serve_overlapped", "serve.inbox", None),
    ("serve_overlapped", "serve.complete", None),
]


@pytest.mark.parametrize(
    "session,name,parent", CONTRACT,
    ids=[f"{s}-{n}-in-{p or 'root'}" for s, n, p in CONTRACT],
)
def test_span_is_on_the_trace_inside_its_parent(request, session, name, parent):
    found = request.getfixturevalue(session)["spans"]
    assert found.get(name), f"{name} is not in the trace: {sorted(found)}"
    if parent is None:
        return
    parents = found.get(parent, [])
    inside = [
        (line, s, e) for line, s, e, _ in found[name]
        if any(pl == line and ps <= s and e <= pe for pl, ps, pe, _ in parents)
    ]
    assert inside, f"no {name} lies inside a {parent} on its thread's line"
    if session.startswith("serve") or parent == "ckpt.save":
        # these children have no other home: every one is inside
        others = {"ckpt.stage"} if parent == "ckpt.save" else set()
        stray = [
            (line, s, e) for line, s, e, _ in found[name]
            if (line, s, e) not in inside
            and not any(
                pl == line and ps <= s and e <= pe
                for o in others for pl, ps, pe, _ in found.get(o, [])
            )
        ]
        assert not stray, f"{len(stray)} {name} outside any {parent}"


def test_rare_roots_carry_the_wall_clock(ckpt_session):
    t0, t1 = ckpt_session["wall"]
    stats = [st for _, _, _, st in ckpt_session["spans"]["ckpt.save"]]
    first = min(stats, key=lambda st: st["unix_ns"])
    assert t0 <= first["unix_ns"] <= t1
    for st in stats + [st for _, _, _, st in ckpt_session["spans"]["ckpt.stage"]]:
        assert abs(st["unix_ns"] - t1) < 1e9 * 60  # same minute: a wall clock
        assert st["step"] in (1, 2)


def test_the_save_root_says_what_it_saved(ckpt_session):
    by_step = {st["step"]: st for _, _, _, st in ckpt_session["spans"]["ckpt.save"]}
    assert by_step[1]["blocking"] == 1 and by_step[2]["blocking"] == 0
    # 32 float32 + 3 bfloat16 + 4 int64, in three leaves
    assert by_step[1]["bytes"] == 32 * 4 + 3 * 2 + 4 * 8
    assert by_step[1]["leaves"] == 3
    stage = ckpt_session["spans"]["ckpt.stage"][0][3]
    assert stage["bytes"] == by_step[1]["bytes"] and stage["leaves"] == 3


def test_the_roots_and_the_event_count_the_same_pages(ckpt_session):
    """``minor_faults``: the pages the process was given during the
    staging, a stat of either root and a field of the event's end."""
    ends = {e["content"]["step"]: e["content"] for e in ckpt_session["events"]
            if e["name"] == "ckpt_save" and e["type"] == "end"}
    roots = {1: [st for _, _, _, st in ckpt_session["spans"]["ckpt.save"]
                 if st["step"] == 1][0],
             2: ckpt_session["spans"]["ckpt.stage"][0][3]}
    for step, root in roots.items():
        assert root["minor_faults"] == ends[step]["minor_faults"]


def test_every_saves_event_says_where_its_time_went(ckpt_session):
    """Traced or not: the ``ckpt_save`` end event carries the split."""
    ends = [e["content"] for e in ckpt_session["events"]
            if e["name"] == "ckpt_save" and e["type"] == "end"]
    assert [c["step"] for c in ends] == [1, 2]
    for c in ends:
        parts = [c["plan_s"], c["ensure_s"], c["d2h_s"], c["memcpy_s"]]
        assert all(p >= 0.0 for p in parts) and c["plan_s"] > 0.0
        assert sum(parts) <= c["duration_s"] + 1e-3
        assert isinstance(c["minor_faults"], int) and c["minor_faults"] >= 0


def saves_of(found):
    """[(line, start, end)] of the session's ``ckpt.save`` roots, in order."""
    return sorted((line, s, e) for line, s, e, _ in found["ckpt.save"])


def test_the_callers_waits_and_copies_never_overlap(pooled_session):
    """``save_d2h_s + save_memcpy_s + save_host_other_s`` is the whole save
    only if the two lie on the caller's line, one after another."""
    found = pooled_session["spans"]
    for line, lo, hi in saves_of(found):
        parts = sorted(
            (s, e) for name in ("ckpt.save.d2h", "ckpt.save.memcpy")
            for pl, s, e, _ in found[name] if lo <= s and e <= hi
            and pl == line
        )
        assert len(parts) == 2 * 3 + 1  # a wait and a hand-over a leaf, the join
        for (_, e0), (s1, _) in zip(parts, parts[1:]):
            assert e0 <= s1
        assert sum(e - s for s, e in parts) <= hi - lo
    strays = [pl for name in ("ckpt.save.d2h", "ckpt.save.memcpy")
              for pl, _, _, _ in found[name]
              if pl not in {line for line, _, _ in saves_of(found)}]
    assert not strays


def test_every_piece_is_a_copy_span_and_the_join_counts_them(pooled_session):
    found = pooled_session["spans"]
    for line, lo, hi in saves_of(found):
        copies = [(pl, st) for pl, s, e, st in found["ckpt.save.copy"]
                  if lo <= s and e <= hi]
        assert sum(st["bytes"] for _, st in copies) == pooled_session["total_bytes"]
        assert all(pl != line for pl, _ in copies)  # none on the caller's line
        joins = [st for _, s, e, st in found["ckpt.save.memcpy"]
                 if lo <= s and e <= hi and "threads" in st]
        assert len(joins) == 1  # the last one: it waits for the pieces
        assert joins[0]["threads"] == 4
        assert joins[0]["pieces"] == len(copies) == 3  # 40 MiB in 16 MiB pieces


@pytest.mark.parametrize("session,threads", [
    ("ckpt_session", 1), ("pooled_session", 4),
])
def test_every_saves_event_says_on_how_many_threads_it_copied(
    request, session, threads
):
    ends = [e["content"] for e in request.getfixturevalue(session)["events"]
            if e["name"] == "ckpt_save" and e["type"] == "end"]
    assert len(ends) == 2
    assert [c["copy_threads"] for c in ends] == [threads, threads]


def test_the_inline_join_says_one_thread(ckpt_session):
    joins = [st for _, _, _, st in ckpt_session["spans"]["ckpt.save.memcpy"]
             if "threads" in st]
    assert len(joins) == 2  # the blocking save's and the staging thread's
    assert all(st["threads"] == 1 and st["pieces"] == 1 for st in joins)
    copies = ckpt_session["spans"]["ckpt.save.copy"]
    assert [st["bytes"] for _, _, _, st in copies] == [32 * 4 + 3 * 2 + 4 * 8] * 2


@pytest.mark.parametrize("session,names", [
    ("ckpt_session", ["ckpt.save", "ckpt.stage", "ckpt.save.ready",
                      "ckpt.save.plan", "ckpt.save.ensure", "ckpt.save.d2h",
                      "ckpt.save.memcpy", "ckpt.save.copy"]),
    ("pooled_session", ["ckpt.save", "ckpt.save.plan", "ckpt.save.ensure",
                        "ckpt.save.d2h", "ckpt.save.memcpy",
                        "ckpt.save.copy"]),
    ("train_session", ["train.data_wait", "train.step_dispatch",
                       "train.report"]),
    ("serve_sync", ["serve.inbox", "serve.complete"]),
])
def test_process_totals_equal_the_recorded_spans(request, session, names):
    got = request.getfixturevalue(session)
    for name in names:
        recorded = got["spans"][name]
        total0, count0 = got["before"].get(name, (0.0, 0))
        total1, count1 = got["after"][name]
        assert count1 - count0 == len(recorded), name
        on_trace = sum(e - s for _, s, e, _ in recorded) / 1e9
        # the annotation opens just before the clock is read and closes
        # just after: a few microseconds a span
        assert total1 - total0 == pytest.approx(
            on_trace, abs=1e-3 + 5e-5 * len(recorded)), name


@pytest.mark.parametrize("session", ["serve_sync", "serve_overlapped"])
def test_phase_totals_equal_the_recorded_spans(request, session):
    """Every phase's seconds are its spans' self time: the spans that
    book under it, less what their children cover."""
    got = request.getfixturevalue(session)
    found, eng = got["spans"], got["engine"]
    stats = eng.phases.stats()
    serve = ["serve.admission", "serve.prefill", "serve.decode_dispatch",
             "serve.host_sync", "serve.retirement"]
    n_spans = sum(len(found[n]) for n in serve)
    assert sum(s.count for s in stats.values()) == n_spans
    # self time over all phases = the union of the phase spans: admission
    # holds its prefills, the rest are disjoint
    outer = sum(e - s for n in serve if n != "serve.prefill"
                for _, s, e, _ in found[n]) / 1e9
    eager = sum(e - s for _, s, e, st in found["serve.prefill"]
                if st.get("eager")) / 1e9
    assert sum(s.self_s for s in stats.values()) == pytest.approx(
        outer + eager, abs=1e-3 + 5e-5 * n_spans)
    rounds = sum(e - s for _, s, e, _ in found["serve.round"]) / 1e9
    assert eng.phases.split().total_s <= rounds
    assert eng.phases.rounds == len(found["serve.round"])
    for key in ("live", "queued", "inflight"):
        assert key in found["serve.round"][0][3]


@pytest.mark.parametrize("overlap", [False, True])
def test_phase_split_ms_keys_are_the_parent_commits(overlap):
    eng = tiny_engine(overlap)
    eng.run(STREAM)
    summary = eng.stats()["phase_split"]
    assert {k for k in summary if k.endswith("_ms")} == PARENT_MS_KEYS[overlap]
    for key in ("requests_admitted_n", "queue_wait_s_sum",
                "admit_to_first_token_s_sum", "chunks_n", "row_steps_n",
                "tokens_emitted_n"):
        assert key in summary, key


@pytest.mark.parametrize("session", ["serve_sync", "serve_overlapped"])
def test_counters_count_where_the_work_happens(request, session):
    got = request.getfixturevalue(session)
    eng, done = got["engine"], got["completions"]
    c = eng.phases.counters()
    assert c["requests_admitted"] == len(done) == len(STREAM) + 2
    assert c["tokens_emitted"] == sum(len(x.tokens) for x in done)
    assert c["row_steps"] == eng.B * eng.d * c["chunks"]
    assert 0 < c["tokens_emitted"] <= c["row_steps"]
    assert c["queue_wait_s"] == pytest.approx(
        sum(x.queue_s for x in done), abs=1e-6)
    assert c["admit_to_first_token_s"] == pytest.approx(
        sum(x.ttft_s for x in done), abs=1e-6)
    assert c["inbox_wait_s"] >= 0.0  # the two that came through the daemon


def test_inbox_wait_grows_while_the_driver_is_held_in_a_step():
    from dlrover_tpu.launcher.serve import ServingDaemon

    eng = tiny_engine(overlap=True)
    eng.run(STREAM[:2])
    eng.phases.reset()
    inner, held, release = eng.step, threading.Event(), threading.Event()

    def held_step(rng):
        if not held.is_set():
            held.set()
            assert release.wait(30.0)
        return inner(rng)

    eng.step = held_step
    daemon = ServingDaemon(eng).start()
    results = []
    try:
        first = threading.Thread(
            target=lambda: results.append(daemon.complete([5, 9, 2], 60.0)))
        first.start()
        assert held.wait(30.0)  # the driver is inside a step
        second = threading.Thread(
            target=lambda: results.append(daemon.complete([7, 1], 60.0)))
        second.start()
        deadline = time.monotonic() + 30.0
        while daemon._inbox.qsize() == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert daemon._inbox.qsize() == 1  # waiting behind the step
        time.sleep(0.2)
        release.set()
        first.join(60.0)
        second.join(60.0)
        assert not first.is_alive() and not second.is_alive()
    finally:
        release.set()
        daemon.stop()
    assert len(results) == 2
    waited = eng.phases.counters()["inbox_wait_s"]
    assert 0.2 <= waited < 30.0


def test_a_loaded_server_that_stands_still_says_where(monkeypatch):
    """One iteration of the daemon's loop that outlasts ``SLOW_ITERATION_S``
    while the engine has work is logged with its inbox and round seconds and
    the round's own phases; iterations of the usual length are not."""
    import logging

    from dlrover_tpu.common.log import logger
    from dlrover_tpu.launcher import serve

    monkeypatch.setattr(serve, "SLOW_ITERATION_S", 0.25)
    eng = tiny_engine(overlap=True)
    eng.run(STREAM[:2])
    inner, calls = eng.step, []

    def step(rng):
        calls.append(1)
        if len(calls) == 2:  # the engine is busy by now: stand still inside a phase
            with eng.phases.span("serve.host_sync", book="host_sync"):
                time.sleep(0.4)
        return inner(rng)

    eng.step = step
    seen = []
    handler = logging.Handler()
    handler.emit = lambda record: seen.append(record.getMessage())
    logger.addHandler(handler)
    daemon = serve.ServingDaemon(eng).start()
    try:
        daemon.complete([5, 9, 2], timeout=60.0)
    finally:
        daemon.stop()
        logger.removeHandler(handler)
    slow = [m for m in seen if m.startswith("slow serving iteration")]
    assert len(calls) > 2 and len(slow) == 1, seen
    assert "'host_sync': 0.4" in slow[0] and "round 0.4" in slow[0]


def test_tracing_the_gpt2_small_step_books_every_flash_kernel(tmp_path, monkeypatch):
    """``flash.kernel_built``: one span a kernel where a program is traced,
    with the path the call's shapes chose and what a head's grid does.
    GPT-2-small's b32x1024 step as ``chip_smoke.py``'s worker builds it: the
    backward kernels walk the one 1024 x 1024 tile in sub-blocks of 256
    (10 of its 16 sub-squares), the forward keeps the general kernel."""
    import dataclasses

    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.models.layers import cross_entropy_loss
    from dlrover_tpu.ops import flash_attention as fa
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.parallel.train_step import (
        build_train_step, default_optimizer, state_shardings,
    )

    cfg = dataclasses.replace(GPTConfig.gpt2_small(), attention_impl="flash")
    model, tx = GPT(cfg), default_optimizer()
    mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
    tokens = jax.ShapeDtypeStruct((32, cfg.max_seq_len), jnp.int32)
    abstract, shardings = state_shardings(
        model, jnp.zeros(tokens.shape, tokens.dtype), mesh, tx
    )
    step_fn = build_train_step(model, tx, cross_entropy_loss, mesh, shardings)
    acc = spans.process_accumulator()
    n_before = booked(acc).get("flash.kernel_built", (0, 0))[1]
    walked_bodies = {"n": 0}
    real = fa._walk_bwd_kernel

    def counted(*args, **kwargs):
        walked_bodies["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(fa, "_walk_bwd_kernel", counted)
    _, found = record(tmp_path, lambda: step_fn.lower(abstract, tokens, tokens))
    built = [st for _, _, _, st in found["flash.kernel_built"]]
    by_kernel = {}
    for st in built:
        by_kernel.setdefault(st["kernel"], []).append(st)
    # a layer's forward, its forward again under remat, dk/dv, dq
    n = cfg.num_layers
    assert {k: len(v) for k, v in by_kernel.items()} == {"fwd": 2 * n, "dkdv": n, "dq": n}
    for kernel, path, share in (("fwd", "general", 1.0), ("dkdv", "causal_tiled", 0.625),
                                ("dq", "causal_tiled", 0.625)):
        for st in by_kernel[kernel]:
            assert (st["path"], float(st["score_share"])) == (path, share)
            assert (st["tiles_visited"], st["tiles_run"]) == (1, 1)
    assert booked(acc)["flash.kernel_built"][1] == n_before + 4 * n
    # the walk's Python bodies ran at most once a kernel, not once a layer
    assert walked_bodies["n"] <= 2


@pytest.mark.parametrize("kernel", ["fwd", "dkdv", "dq"])
def test_tracing_a_windowed_layer_books_the_band(tmp_path, kernel):
    """``flash.kernel_built`` under a window, at the shape of the
    ``mellum2-train-ep4share`` cell's window layers (T 8,192 in tiles of
    1,024, a window of one tile): a row tile runs 2 key tiles and not 8, and
    ``tiles_visited`` is the grid as built (PR 58: ``band + 1`` = 2 steps a
    row tile, 16 a head, where the square grid had 64), so a head's grid runs
    15 of its 16 steps, the one left hanging over the sequence's edge, and
    computes 0.146 of the square (the mask itself leaves 0.117; the causal
    walk computes 0.516 in 36 of 64 steps, beside the 0.625 / 0.53125 of T
    1,024 / 4,096 held above). The span carries the plan and the stat
    ``window``; a full layer's carries none."""
    from dlrover_tpu.ops import flash_attention as fa

    plan = fa._kernel_plan(True, 8192, 8192, 1024, 1024, 256, 1024)
    assert plan == {"path": "window_tiled", "tiles_visited": 16, "tiles_run": 15,
                    "score_share": (8 * 10 + 7 * 10) / 16 / 64, "window": 1024}
    assert plan["score_share"] == pytest.approx(0.146, abs=5e-4)
    mask_share = (1024 * 1025 // 2 + 7168 * 1024) / 8192**2
    assert mask_share == pytest.approx(0.117, abs=5e-4)
    full = fa._kernel_plan(True, 8192, 8192, 1024, 1024, 256)
    assert (full["path"], full["tiles_run"], "window" in full) == ("causal_tiled", 36, False)
    assert full["score_share"] == pytest.approx(0.516, abs=5e-4)

    x = jax.ShapeDtypeStruct((1, 8192, 1, 128), jnp.bfloat16)

    def attend(window):
        f = lambda q, k, v: fa.flash_attention(q, k, v, True, window=window).astype(jnp.float32).sum()
        return jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(x, x, x)

    _, found = record(tmp_path, lambda: (attend(1024), attend(None)))
    built = [st for _, _, _, st in found["flash.kernel_built"] if st["kernel"] == kernel]
    banded = [st for st in built if st["path"] == "window_tiled"]
    assert banded and all(
        (int(st["window"]), st["tiles_run"], st["tiles_visited"]) == (1024, 15, 16)
        and float(st["score_share"]) == plan["score_share"] for st in banded)
    causal = [st for st in built if st["path"] == "causal_tiled"]
    assert causal and all(
        "window" not in st and (st["tiles_run"], st["tiles_visited"]) == (36, 64) for st in causal)


@pytest.mark.parametrize("overlap", [False, True])
def test_a_servers_phase_split_does_not_see_the_flash_span(overlap):
    """``flash.kernel_built`` is booked in the process's totals, where a
    program is traced; a server's ``phase_split`` (``/healthz``) and the
    benchmark's ``serve_host_frac``, which sums every ``*_ms`` key of it,
    read what they read before the name existed."""
    import runpy
    import types

    from dlrover_tpu.ops.flash_attention import flash_attention

    x = jnp.ones((1, 256, 1, 16), jnp.float32)
    jax.make_jaxpr(jax.grad(lambda q: flash_attention(q, q, q).sum()))(x)
    assert booked(spans.process_accumulator())["flash.kernel_built"][1] >= 3
    eng = tiny_engine(overlap)
    first = dict(eng.stats()["phase_split"])
    eng.run(STREAM)
    last = eng.stats()["phase_split"]
    assert not [k for k in last if "flash" in k]
    assert {k for k in last if k.endswith("_ms")} == PARENT_MS_KEYS[overlap]
    reader = runpy.run_path(
        os.path.join(ROOT, "benchmark", "layer_metrics", "serve_host_frac.py")
    )["read"]
    ctx = types.SimpleNamespace(
        stamps={"phase_split_open": first, "healthz": {"phase_split": last}}
    )
    spent = {k: last[k] - first.get(k, 0.0) for k in PARENT_MS_KEYS[overlap]}
    host = sum(spent[k] for k in ("admission_ms", "decode_dispatch_ms", "retirement_ms"))
    assert reader(ctx) == pytest.approx(100.0 * host / sum(spent.values()))


def test_no_session_records_nothing_and_raises_nothing(tmp_path):
    acc = SpanAccumulator()
    with acc.span("off.outer", step=3) as outer:
        outer.set(bytes=7)
        with acc.span("off.inner"):
            pass
    acc.count("off.things", 2)
    _, found = record(tmp_path / "trace", lambda: None)
    assert "off.outer" not in found and "off.inner" not in found
    stats = acc.stats()
    assert stats["off.outer"].count == 1 and stats["off.inner"].count == 1
    assert acc.counters() == {"off.things": 2}


def test_self_time_is_the_span_less_its_children():
    acc = SpanAccumulator()
    with acc.span("outer"):
        time.sleep(0.02)
        with acc.span("inner"):
            time.sleep(0.03)
        with acc.span("frame", book=""):  # books nothing, still a child
            time.sleep(0.01)
    stats = acc.stats()
    assert set(stats) == {"outer", "inner"}
    assert stats["outer"].total_s >= 0.06
    assert stats["outer"].self_s == pytest.approx(
        stats["outer"].total_s - stats["inner"].total_s - 0.01, abs=5e-3)
    assert stats["inner"].self_s == stats["inner"].total_s
    # booked under another key, a nested phase still partitions the round
    phases = PhaseAccumulator()
    with phases.span("serve.admission", book="admission"):
        with phases.span("serve.prefill", book="prefill"):
            time.sleep(0.02)
    split = phases.split()
    assert split.phases["prefill"]["total_s"] >= 0.02
    assert split.phases["admission"]["total_s"] < 0.01
    assert split.total_s == pytest.approx(
        phases.stats()["admission"].total_s, abs=1e-4)


def test_an_exception_closes_the_span_and_leaves_the_stack_clean():
    acc = SpanAccumulator()
    with pytest.raises(ValueError):
        with acc.span("outer"):
            with acc.span("inner"):
                raise ValueError("boom")
    with acc.span("after"):
        pass
    stats = acc.stats()
    assert stats["outer"].count == stats["inner"].count == 1
    assert stats["after"].self_s == stats["after"].total_s  # no stale parent


def test_many_threads_lose_no_update():
    acc = SpanAccumulator()
    n_threads, n_each = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_each):
                with acc.span("shared"):
                    pass
                acc.count("things")
                acc.count("seconds_s", 0.5)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert acc.stats()["shared"].count == n_threads * n_each
    assert sum(acc.stats()["shared"].hist) == n_threads * n_each
    assert acc.counters() == {"things": n_threads * n_each,
                              "seconds_s": 0.5 * n_threads * n_each}


def test_counters_ride_in_the_summary_under_names_that_do_not_end_in_ms():
    acc = PhaseAccumulator()
    acc.add_round([("admission", 0.001), ("host_sync", 0.002)])
    acc.count("inbox_wait_s", 0.25)
    acc.count("requests_admitted", 3)
    summary = acc.split().summary()
    assert summary["inbox_wait_s_sum"] == 0.25
    assert summary["requests_admitted_n"] == 3
    assert {k for k in summary if k.endswith("_ms")} == {
        "admission_ms", "host_sync_ms"}
    acc.reset()
    assert acc.split().summary() == {"serving_host_frac": 0.0, "rounds": 0}


def test_a_process_without_jax_opens_spans_and_stays_without_it():
    """The agent, the master and the launcher never import JAX: a span or
    a ``with`` DurationSpan there books and emits, and imports nothing."""
    code = (
        "import sys\n"
        "from dlrover_tpu.common.events import EventEmitter\n"
        "from dlrover_tpu.observability.spans import span, process_accumulator\n"
        "with span('agent.thing', n=1):\n"
        "    with EventEmitter('agent').duration('agent_metric_tick'):\n"
        "        pass\n"
        "assert process_accumulator().stats()['agent.thing'].count == 1\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop("DLROVER_EVENT_DIR", None)
    got = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert got.returncode == 0, got.stderr[-2000:]


def test_the_agents_periodic_jobs_are_incident_spans(tmp_path, monkeypatch):
    """``agent_metric_tick`` lands in the event stream with a duration, so
    ``tpurun-trace`` shows it beside the worker's ``ckpt_save``."""
    from dlrover_tpu.agent.metric_collector import ProfilerMetricCollector
    from dlrover_tpu.common import events

    seen = []

    class Sink(events.Exporter):
        def export(self, event):
            seen.append(event.to_dict())

    class Client:
        def report_node_metrics(self, gauges):
            pass

    collector = ProfilerMetricCollector(port=1, client=Client(), interval_s=0.01)
    collector._evt = events.EventEmitter("agent", Sink())
    collector.collect_once = lambda: {"tpu_timer_hang": 0.0}
    collector.start()
    deadline = time.monotonic() + 10.0
    while len(seen) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    collector.stop()
    names = [(e["name"], e["type"]) for e in seen[:2]]
    assert names == [("agent_metric_tick", "begin"), ("agent_metric_tick", "end")]
    assert seen[1]["content"]["gauges"] == 1
    assert seen[1]["content"]["duration_s"] >= 0.0


# -- the serving engine's held parameters (PR 31) -----------------------------


@pytest.fixture(scope="module")
def cast_session(job):
    """An engine built from a float32 tree, then one swap of a float32
    host payload, inside a profiler session."""
    before = booked(spans.process_accumulator())

    def body():
        eng = tiny_engine(True)
        given = eng.model.init(
            jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        first = dict(eng.stats())
        eng.set_params(jax.tree.map(np.asarray, jax.device_get(given)))
        return eng, given, first

    (eng, given, first), found = record(job / "trace_cast", body)
    return dict(spans=found, engine=eng, given=given, first=first,
                before=before, after=booked(spans.process_accumulator()))


def test_params_cast_is_on_the_trace_with_what_it_rounded(cast_session):
    """One ``serve.params_cast`` at construction and one where the swap's
    payload lands, each saying how many leaves it rounded and the bytes
    in and out; both booked in the process's totals, none in the
    engine's phases (``serve_host_frac`` sums those)."""
    got = cast_session
    recorded = got["spans"]["serve.params_cast"]
    assert len(recorded) == 2
    given, eng = got["given"], got["engine"]
    named = [
        leaf for leaf, dt in zip(
            jax.tree.leaves(given),
            jax.tree.leaves(eng.model.consumed_param_dtypes(given)),
        ) if leaf.dtype != dt
    ]
    for _, _, _, stats in recorded:
        assert int(stats["leaves"]) == len(named) > 0
        assert int(stats["bytes_in"]) == sum(leaf.nbytes for leaf in named)
        assert int(stats["bytes_out"]) == int(stats["bytes_in"]) // 2
    total0, count0 = got["before"].get("serve.params_cast", (0.0, 0))
    total1, count1 = got["after"]["serve.params_cast"]
    assert count1 - count0 == 2
    on_trace = sum(e - s for _, s, e, _ in recorded) / 1e9
    assert total1 - total0 == pytest.approx(on_trace, abs=1e-3)
    assert "serve.params_cast" not in eng.phases.stats()


def test_stats_say_what_the_engine_holds_and_how_often_it_rounded(cast_session):
    eng, first = cast_session["engine"], cast_session["first"]
    held = sum(leaf.nbytes for leaf in jax.tree.leaves(eng.params))
    given = sum(leaf.nbytes for leaf in jax.tree.leaves(cast_session["given"]))
    assert first["params_casts"] == 1
    assert first["params_device_bytes"] == held < given
    stats = eng.stats()
    assert stats["params_casts"] == 2
    assert stats["params_device_bytes"] == held
    eng.set_params_async({"not": "the tree"})  # aborted: nothing adopted
    assert eng.stats()["swap_failures"] == 1
    assert eng.stats()["params_casts"] == 2
