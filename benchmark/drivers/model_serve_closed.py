"""Driver ``model_serve_closed``: ``serve_closed``'s closed loop against a
server whose model is built from the configuration's ``model`` entry
(``tpurun-serve --family <family> --config <config>``,
``dlrover_tpu/models/build.py``). The requests, the streamed client, the
warm admissions, the control channel and the token counting are
``serve_closed``'s own functions, imported; what is here is the server's
command line, the comparison that decides ``correct`` and the run around
them (``run`` follows ``serve_closed.run`` line for line but for what
``benchmark/tests/test_metrics_lfm2_moe.py`` lists, until a ``benchmark``
issue gives that one the hooks), with two more reads of ``/healthz`` around
the traced seconds, so that a reader can set the trace's device time
against the program's counters of the same seconds (``phase_split_trace``).

``correct``: the teacher-forced positions against the plain float32
reference (``check_teacher`` / ``judge_teacher``: one greedy token after
each of a few hundred prefixes of fixed sequences, so that no position
depends on what the server wrote before; a second token after some of
them, the first decode step over the state and the cache the prefill left),
and every request of the window answered with the tokens it asked for.
``serve_closed.check_canary`` (a dozen greedy tokens after four random
prompts, the largest difference held to a limit) is not used: at random
weights most tokens' routers stand at a near-tie somewhere, a choice that
falls the other way in bf16 moves that token's log-probability by more
than rounding every expert to 8 bits moves them all, and the largest of a
few dozen differences says nothing of the precision. Hence sequences chosen
so that no router's choice along them is a near-tie
(``reference/make_teacher_sequences_lfm2_moe.py``), hundreds of positions,
and limits on medians; the traffic file gives each limit with its two
readings.
"""

import http.client
import json
import os
import statistics
import subprocess
import sys
import threading
import time

from benchmark import harness
from benchmark.drivers.serve_closed import (
    Control,
    http_json,
    make_requests,
    stream_completion,
    ttfts,
    warm_admissions,
)
from benchmark.harness import RunFailed

HEALTHZ_KEYS = (
    "serving_host_frac", "phase_split", "tokens_per_s", "latency_p50_s", "latency_p95_s",
    "decode_chunk", "overlap", "slots", "served", "cache_bytes_positional",
    "cache_bytes_state", "params_device_bytes",
)


def families() -> dict:
    """The program's registry of model families, or {} where this checkout's
    program has none (read without JAX: the parent never holds the chip)."""
    try:
        from dlrover_tpu.models.build import FAMILIES
    except ImportError:
        return {}
    return FAMILIES


def start_server(run, port: int, control_dir: str, log: str):
    p, model = run.traffic["params"], run.config["model"]
    if model["family"] not in families():
        # an older program: say so at once, before any process is started
        raise RunFailed(f"this checkout's program has no model family {model['family']!r} "
                        f"in dlrover_tpu/models/build.py: it cannot serve this configuration")
    cmd = [
        sys.executable, os.path.join(harness.BENCH_DIR, "workers", "serve_launcher.py"),
        "--control", control_dir, "--",
        "--port", str(port), "--family", model["family"], "--config", json.dumps(model["config"]),
        "--batch-size", str(p["batch_size"]), "--prompt-width", str(p["prompt_width"]),
        "--max-new-tokens", str(p["max_new_tokens"]), "--temperature", str(p["temperature"]),
    ] + (["--cpu"] if run.platform == "cpu" else [])
    return subprocess.Popen(
        cmd, cwd=harness.ROOT,
        env=harness.child_env(run.platform, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0),
        stdout=open(log, "w"), stderr=subprocess.STDOUT, start_new_session=True,
    )


def wait_healthz(proc, base: str, log: str, deadline_s: float) -> dict:
    deadline = time.monotonic() + deadline_s
    while True:
        if proc.poll() is not None:
            raise RunFailed(f"the server exited rc={proc.returncode}:\n{harness.tail(log)}")
        try:
            return http_json("GET", base + "/healthz", timeout=5)[1]
        except OSError:
            if time.monotonic() > deadline:
                raise RunFailed("the server never came up")
            time.sleep(0.25)


def judge_teacher(got: list, teacher: list, limits: dict):
    """``got``: what the server wrote after each teacher-forced prefix, in
    the order of the expected file's sequences and prompt lengths: (tokens,
    log-probabilities), one token, or two where the file lists a second.
    -> (ok, numbers).

    First tokens (the prefill, and the precision of everything in it): a
    token that differs from the reference's is a fault where the reference's
    two largest logits lie further apart than ``gap_tolerance`` and is left
    out otherwise; over the positions with the same token the *median*
    |log-probability difference| has the limit, and at least half of all
    positions have to be among them. Second tokens (the first decode step,
    which reads the state and the cache the prefill left; the token before it
    is the model's own, so its routers' near-ties fall either way and one
    position proves nothing): the share of listed positions at which both
    tokens are the reference's has a lower limit, and over them the median
    |log-probability difference| an upper one."""
    first, second, faults, listed, at = [], [], [], 0, 0
    for seq in teacher:
        follows = dict(zip(seq.get("second_at", []), zip(seq.get("second_tokens", []), seq.get("second_logprobs", []))))
        listed += len(follows)
        for j, (w_token, gap, w_logprob) in enumerate(zip(seq["tokens"], seq["top2_gap"], seq["logprobs"])):
            if at == len(got):
                raise ValueError(f"{len(got)} served positions, more expected")
            (tokens, logprobs), at = got[at], at + 1
            if tokens[0] != w_token:
                if gap > limits["gap_tolerance"]:
                    faults.append(dict(position=at - 1, got=tokens[0], want=w_token, gap=gap))
                continue
            first.append(abs(logprobs[0] - w_logprob))
            if j in follows and len(tokens) > 1 and tokens[1] == follows[j][0]:
                second.append(abs(logprobs[1] - follows[j][1]))
    if at != len(got):
        raise ValueError(f"{len(got)} served positions against {at} expected")
    median = statistics.median(first) if first else None
    median2 = statistics.median(second) if second else None
    numbers = dict(teacher_positions=at, teacher_positions_compared=len(first),
                   teacher_logprob_median_abs_diff=median,
                   teacher_logprob_max_abs_diff=max(first, default=None),
                   teacher_second_positions=listed, teacher_second_positions_compared=len(second),
                   teacher_second_logprob_median_abs_diff=median2,
                   teacher_mismatch=faults[:3], teacher_limits=limits)
    ok = (not faults and 2 * len(first) >= at and _within(median, limits["median_logprob_tolerance"])
          and len(second) >= limits["second_min_compared"] * listed
          and (not second or _within(median2, limits["second_median_logprob_tolerance"])))
    return ok, numbers


def _within(value, limit) -> bool:
    return limit is None or value <= limit


def check_teacher(port: int, run, checks: dict) -> bool:
    """One greedy token after every listed prefix of the expected file's
    sequences, two where it lists a second, as many requests at once as the
    loop has clients (after the warm admissions: no program is new here),
    judged by ``judge_teacher``."""
    name = run.config["name"] + (".rehearsal" if run.rehearse else "") + ".serve_canary.json"
    teacher = harness.load_json(os.path.join(harness.BENCH_DIR, "reference", "expected", name))["teacher"]
    asks = [(seq["sequence"][:n], 2 if j in seq["second_at"] else 1)
            for seq in teacher for j, n in enumerate(seq["prompt_lengths"])]
    got, lock, todo = [None] * len(asks), threading.Lock(), list(range(len(asks)))

    def client():
        while True:
            with lock:
                if not todo:
                    return
                i = todo.pop()
            got[i] = stream_completion(port, *asks[i])

    threads = [threading.Thread(target=client, daemon=True) for _ in range(run.traffic["params"]["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    for (prompt, n), rec in zip(asks, got):
        if rec is None or rec["status"] != 200 or len(rec["tokens"] or []) != n or not rec["final"].get("logprobs"):
            raise RunFailed(f"teacher-forced prompt of {len(prompt)} tokens: {rec and (rec['status'], rec['final'])}")
    ok, numbers = judge_teacher([(rec["tokens"], rec["final"]["logprobs"]) for rec in got], teacher,
                                run.traffic["params"]["canary"]["teacher"]["limits"])
    checks.update(numbers)
    return ok


def standstill(stamps: dict):
    """(seconds, offset into the window) of the longest time inside the
    window in which no streamed line reached any client."""
    lo, hi = stamps["t_open"], stamps["t_close"]
    times = sorted(t for r in stamps["requests"] for t, _ in r["arrivals"] if lo <= t <= hi)
    gaps = [(b - a, a - lo) for a, b in zip([lo] + times, times + [hi])]
    return max(gaps)


def run(run):
    p = run.traffic["params"]
    port = harness.free_port()
    control_dir = os.path.join(run.work, "control")
    log = os.path.join(run.work, "serve.log")
    proc = start_server(run, port, control_dir, log)
    base = f"http://127.0.0.1:{port}"
    ctl = Control(control_dir, proc)
    records, checks = [], {}
    try:
        device = wait_healthz(proc, base, log, run.deadline_s)["device"]
        t_up = time.time()
        warm_admissions(port, ctl, p, run.config["vocab_size"], checks)
        teacher_ok = check_teacher(port, run, checks)
        t_canary = time.time()

        requests = make_requests(p, run.config["vocab_size"], run.seed)
        lock, state, cut = threading.Lock(), dict(next=0, stop=False), []
        holders = [dict() for _ in range(p["clients"])]

        def client(holder):
            while True:
                with lock:
                    if state["stop"]:
                        return
                    prompt, n = requests[state["next"] % len(requests)]
                    state["next"] += 1
                try:
                    rec = stream_completion(port, prompt, n, holder)
                except (OSError, ValueError, http.client.HTTPException) as e:
                    if state["stop"]:
                        # cut off after the window: what arrived inside it counts
                        with lock:
                            cut.append(dict(holder.pop("rec"), prompt_len=len(prompt)))
                        return
                    rec = dict(t_send=None, t_first=None, t_done=time.time(), status=-1,
                               asked=n, tokens=None, final=dict(error=repr(e)[:200]))
                with lock:
                    records.append(dict(rec, prompt_len=len(prompt)))

        threads = [threading.Thread(target=client, args=(h,), daemon=True) for h in holders]
        for t in threads:
            t.start()
        time.sleep(p["warmup_seconds"])
        before = ctl.ask(cmd="stats")
        _, health_open = http_json("GET", base + "/healthz", timeout=30)
        t_open = time.time()
        t_close = t_open + run.seconds
        time.sleep(max(0.0, t_close - time.time()))
        _, health = http_json("GET", base + "/healthz", timeout=30)
        after = ctl.ask(cmd="stats")
        trace_dir = os.path.join(run.work, "trace")
        around_trace = None
        if run.trace:  # the same load, after the window
            time.sleep(p["trace_after_s"])
            ctl.ask(cmd="trace_start", dir=trace_dir)
            _, trace_open = http_json("GET", base + "/healthz", timeout=30)
            time.sleep(p["trace_seconds"])
            _, trace_close = http_json("GET", base + "/healthz", timeout=30)
            ctl.trace_stop(log, checks)
            around_trace = [trace_open.get("phase_split"), trace_close.get("phase_split")]
        with lock:
            state["stop"] = True
        for h in holders:  # abandon what is in flight: the server cancels it
            conn = h.get("conn")
            if conn is not None and conn.sock is not None:
                try:
                    conn.sock.shutdown(2)
                except OSError:
                    pass
        for t in threads:
            t.join(20)
    finally:
        harness.stop(proc)
    with open(os.path.join(run.work, "requests.jsonl"), "w") as f:
        f.write(json.dumps(dict(t_open=t_open, t_close=t_close)) + "\n")
        for r in records + cut:
            f.write(json.dumps({k: v for k, v in r.items() if k not in ("tokens", "final")}) + "\n")
    done = [r for r in records if r["t_done"] is not None and t_open <= r["t_done"] <= t_close]
    bad = [r for r in done if r["status"] != 200 or r["tokens"] is None or len(r["tokens"]) != r["asked"]]
    if not done:
        raise RunFailed("no request completed inside the window")
    checks.update(
        teacher_ok=teacher_ok,
        all_200_with_the_tokens_asked=not bad,
        programs_compiled_in_window=after["cache_misses"] - before["cache_misses"],
        programs_read_from_cache_in_window=after["cache_hits"] - before["cache_hits"],
        bad=[dict(status=r["status"], final=r["final"]) for r in bad[:3]],
    )
    stamps = dict(
        t_open=t_open, t_close=t_close, seconds=run.seconds,
        requests=[dict(t_send=r["t_send"], t_first=r["t_first"], t_done=r["t_done"],
                       arrivals=r.get("arrivals", []), asked=r["asked"], prompt_len=r.get("prompt_len"))
                  for r in records + cut if r["t_send"] is not None],
        healthz={k: health.get(k) for k in HEALTHZ_KEYS},
        phase_split_open=health_open.get("phase_split"),
        phase_split_trace=around_trace,
        server_up_s=t_up - run.t_start, canary_s=t_canary - t_up,
        memory_stats=after.get("memory_stats"),
        gap_spans=[], gap_rest="host_all",
    )
    first = ttfts(stamps)
    if first:  # per-layer numbers in a traced run; here for whoever reads an untraced line
        checks["ttft_p50_p90_s"] = [harness.percentile(first, 50), harness.percentile(first, 90)]
    # a run that stands still says so in its own line, with the engine's phases over the window
    checks["longest_standstill_s_at_s"] = list(standstill(stamps))
    opened, closed = health_open.get("phase_split") or {}, health.get("phase_split") or {}
    checks["window_phase_ms"] = {k: round(closed[k] - opened.get(k, 0.0), 1) for k in closed if k.endswith("_ms")}
    return dict(
        stamps=stamps,
        t_open=t_open,
        attempted=len(done),
        failed=len(bad),
        correct=teacher_ok and not bad,
        checks=checks,
        device=dict(platform=device["platform"], kind=device["kind"], count=device["count"],
                    memory_peak_bytes=after["memory_peak_bytes"]),
        trace_dir=trace_dir if run.trace else None,
        records=[os.path.join(run.work, "requests.jsonl"), log],
    )
