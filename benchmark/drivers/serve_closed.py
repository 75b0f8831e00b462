"""Driver ``serve_closed``: the serving daemon under a closed loop.

The parent (never on JAX) starts ``tpurun-serve`` through
``benchmark/workers/serve_launcher.py``, waits for ``/healthz``, sends the
canary prompts and checks them against the reference, then runs N client
threads, each sending its next streamed request when the last has
finished. The loop runs through a warm-up and straight on into the
window; the window is a pair of clock readings, and a streamed token counts
where it *arrived* inside it (a request that straddles an edge counts with
the tokens on the inside). Latency is taken from the client's side. A traced
run keeps the loop going past the window's close and traces there, so that
the profiler's start and stop touch no request the window's numbers come from.

The traffic is one fixed multiset of (prompt length, max_tokens) pairs
drawn from the traffic file's ``requests_key``; ``--seed`` orders it and
fills the prompts, so every seed offers the same sizes in another order.
"""

import http.client
import json
import math
import os
import random
import subprocess
import sys
import threading
import time
import urllib.request

from benchmark import harness
from benchmark.harness import RunFailed


def make_requests(params: dict, vocab: int, seed: int) -> list:
    """[(prompt tokens, max_tokens), ...]: sizes from the traffic's own
    key, order and tokens from the seed."""
    sizes = random.Random(params["requests_key"])
    lo, hi = params["prompt_len"]["lo"], params["prompt_len"]["hi"]
    pairs = [
        (int(round(math.exp(sizes.uniform(math.log(lo), math.log(hi))))),
         sizes.randint(params["max_tokens"]["lo"], params["max_tokens"]["hi"]))
        for _ in range(params["n_requests"])
    ]
    rng = random.Random(seed)
    rng.shuffle(pairs)
    return [([rng.randrange(vocab) for _ in range(n)], m) for n, m in pairs]


def ttfts(stamps: dict):
    """Send to first streamed token, on the client's clock, of the requests
    sent and first answered inside the window; None where the stamps are
    not a serving run's."""
    reqs = stamps.get("requests")
    if reqs is None:
        return None
    lo, hi = stamps["t_open"], stamps["t_close"]
    return [r["t_first"] - r["t_send"] for r in reqs
            if r["t_send"] is not None and r["t_first"] is not None
            and lo <= r["t_send"] and r["t_first"] <= hi]


def tokens_arrived(stamps: dict):
    """Streamed tokens whose line reached the client inside the window, or
    None where the stamps are not a serving run's."""
    reqs = stamps.get("requests")
    if reqs is None:
        return None
    lo, hi = stamps["t_open"], stamps["t_close"]
    return sum(n for r in reqs for t, n in r["arrivals"] if lo <= t <= hi)


def http_json(method, url, body=None, timeout=30.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


def stream_completion(port: int, prompt, max_tokens: int, holder: dict = None):
    """One streamed request. Returns its record: send time, first-token
    time, each line's arrival with its count of new tokens, end time,
    status, tokens and the final line. ``holder`` gets the record at once,
    so that what arrived of a request cut off at the end is still counted."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    if holder is not None:
        holder["conn"] = conn
    rec = dict(t_send=time.time(), t_first=None, t_done=None, status=None,
               asked=max_tokens, tokens=None, final=None, arrivals=[])
    if holder is not None:
        holder["rec"] = rec
    try:
        conn.request("POST", "/v1/completions",
                     json.dumps(dict(prompt=prompt, max_tokens=max_tokens, stream=True)),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["t_done"] = time.time()
            return rec
        while True:
            line = resp.readline()
            if not line:
                break
            obj, now = json.loads(line), time.time()
            if obj.get("done") or obj.get("error"):
                rec["final"] = obj
                # the done-line repeats every token: count what no line brought
                late = len(obj.get("tokens") or []) - sum(n for _, n in rec["arrivals"])
                if late > 0:
                    rec["arrivals"].append((now, late))
                break
            if obj.get("tokens"):
                if rec["t_first"] is None:
                    rec["t_first"] = now
                rec["arrivals"].append((now, len(obj["tokens"])))
        rec["t_done"] = time.time()
        if rec["final"] and rec["final"].get("done"):
            rec["tokens"] = rec["final"]["tokens"]
    finally:
        conn.close()
    return rec


# How long either serving driver waits for the launcher to answer
# ``trace_stop``: the one place it is written (``run.deadline_s`` is 1,100).
# A backstop: at ``trace_seconds`` 1.5, with ``reduce_trace.stop_trace``
# writing the ``xplane.pb`` alone, no cell's stop comes within a factor of
# three of it (``PERF.md`` section 6, PR 55); at 4 s and with JAX's own export
# GPT-2 XL's took 222-237 s of the 240 s that both drivers then allowed.
TRACE_STOP_WAIT_S = 600.0


class Control:
    """The launcher's control directory, from the parent's side."""

    def __init__(self, directory: str, proc):
        self.dir, self.proc, self.n = directory, proc, 0

    def ask(self, timeout: float = 120.0, **cmd) -> dict:
        self.n += 1
        req = os.path.join(self.dir, f"req_{self.n:04d}.json")
        with open(req + ".tmp", "w") as f:
            json.dump(cmd, f)
        os.replace(req + ".tmp", req)
        resp = req.replace("req_", "resp_")
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(resp):
                return harness.load_json(resp)
            if self.proc.poll() is not None:
                raise RunFailed(f"the server exited rc={self.proc.returncode}")
            time.sleep(0.02)
        raise RunFailed(f"the server's launcher did not answer {cmd} in the {timeout:.0f} s waited for it; "
                        f"its process was {'still alive' if self.proc.poll() is None else 'gone'}")

    def trace_stop(self, log: str, checks: dict) -> None:
        """Ask the launcher to stop the profiler and wait ``TRACE_STOP_WAIT_S``
        for its answer, whose timings go to ``checks["trace_stop"]`` with the
        seconds waited. A wait that runs out says what the launcher's log
        last said of the stop, and a stop that fell back on JAX's own export
        (another JAX: the slow way, ``collect_s`` unknown) says so on stderr."""
        t0 = time.monotonic()
        try:
            got = self.ask(cmd="trace_stop", timeout=TRACE_STOP_WAIT_S)
        except RunFailed as e:
            said = [line.strip() for line in harness.tail(log, 400).splitlines() if "trace_stop:" in line]
            raise RunFailed(f"{e}; the launcher's log on this stop: {said[-3:] or 'nothing'}") from None
        if not got.get("ok"):
            raise RunFailed(f"the launcher could not stop the trace: {got.get('error')}")
        checks["trace_stop"] = dict(
            {k: got.get(k) for k in ("collect_s", "export_s", "xplane_bytes", "wrote")},
            waited_s=time.monotonic() - t0, wait_limit_s=TRACE_STOP_WAIT_S)
        if got.get("wrote") != "xplane.pb":
            sys.stderr.write(f"benchmark: WARNING: the trace was stopped by {got.get('wrote')}, not written as "
                             f"reduce_trace.stop_trace writes it: {checks['trace_stop']['waited_s']:.0f} s of a "
                             f"{TRACE_STOP_WAIT_S:.0f} s wait; see reduce_trace._held_session\n")


def check_canary(port: int, run, checks: dict) -> bool:
    """Greedy tokens of the fixed prompts against the plain reference's, at
    every position up to the first where the reference's two largest logits
    lie closer than the tolerance (after a flip there the contexts differ
    and nothing more can be compared); and the chosen tokens' log-probability."""
    canary = run.traffic["params"]["canary"]
    name = run.config["name"] + (".rehearsal" if run.rehearse else "") + ".serve_canary.json"
    expected = harness.load_json(os.path.join(harness.BENCH_DIR, "reference", "expected", name))
    ok, compared, worst_lp = True, 0, 0.0
    for want in expected["canary"]:
        rec = stream_completion(port, want["prompt"], canary["max_tokens"])
        if rec["status"] != 200 or rec["tokens"] is None:
            # a server that cannot answer its first request answers none: stop here
            raise RunFailed(f"canary prompt of {len(want['prompt'])} tokens: status "
                            f"{rec['status']}, {rec['final']}")
        got, lps = rec["tokens"], rec["final"].get("logprobs") or []
        for i, (g, w) in enumerate(zip(got, want["tokens"])):
            if g != w:
                if want["top2_gap"][i] > canary["gap_tolerance"]:
                    ok = False
                    checks.setdefault("canary_mismatch", []).append(
                        dict(prompt_len=len(want["prompt"]), position=i, got=g, want=w,
                             gap=want["top2_gap"][i]))
                break
            compared += 1
            if i < len(lps):
                worst_lp = max(worst_lp, abs(lps[i] - want["logprobs"][i]))
    checks["canary_positions_compared"] = compared
    checks["canary_logprob_max_abs_diff"] = worst_lp
    checks["canary_logprob_tolerance"] = canary.get("logprob_tolerance")
    if canary.get("logprob_tolerance") is not None and worst_lp > canary["logprob_tolerance"]:
        ok = False
    return ok and compared > 0


def warm_admissions(port: int, ctl, p: dict, vocab: int, checks: dict) -> None:
    """The engine admits the K requests it finds queued in one jitted
    ``admit_many`` of K rows: one program for each K. Which K a closed loop
    meets is chance, so every K below the slot count is made to happen
    here: one long request keeps the server in its round while K short ones
    arrive together (K = all slots goes without the long one), and the launcher's count of programs says whether a
    new one was built (else the burst split over two rounds: try again)."""
    rng = random.Random(p["requests_key"])

    def prompt(n):
        return [rng.randrange(vocab) for _ in range(n)]

    def programs():
        got = ctl.ask(cmd="stats")
        return got["cache_hits"] + got["cache_misses"]

    missed = []
    for k in range(2, p["batch_size"] + 1):
        for _ in range(3):
            before = programs()
            others = []
            if k < p["batch_size"]:  # K = all slots needs them all free: no long one
                others.append(threading.Thread(
                    target=stream_completion,
                    args=(port, prompt(p["prompt_len"]["lo"]), p["max_new_tokens"] // 2)))
                others[0].start()
                time.sleep(0.15)
            burst = [threading.Thread(target=stream_completion,
                                      args=(port, prompt(p["prompt_len"]["lo"]), 2)) for _ in range(k)]
            for t in burst:
                t.start()
            for t in burst + others:
                t.join(120)
            if programs() > before:
                break
        else:
            missed.append(k)
    checks["admit_sizes_not_met_in_warmup"] = missed


def run(run):
    p = run.traffic["params"]
    port = harness.free_port()
    control_dir = os.path.join(run.work, "control")
    log = os.path.join(run.work, "serve.log")
    model = run.config["gpt_config"]
    cmd = [
        sys.executable, os.path.join(harness.BENCH_DIR, "workers", "serve_launcher.py"),
        "--control", control_dir, "--",
        "--port", str(port), "--config", json.dumps(model),
        "--batch-size", str(p["batch_size"]), "--prompt-width", str(p["prompt_width"]),
        "--max-new-tokens", str(p["max_new_tokens"]), "--temperature", str(p["temperature"]),
    ] + (["--cpu"] if run.platform == "cpu" else [])
    proc = subprocess.Popen(
        cmd, cwd=harness.ROOT,
        env=harness.child_env(run.platform, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0),
        stdout=open(log, "w"), stderr=subprocess.STDOUT, start_new_session=True,
    )
    base = f"http://127.0.0.1:{port}"
    ctl = Control(control_dir, proc)
    records, checks = [], {}
    try:
        deadline = time.monotonic() + run.deadline_s
        while True:
            if proc.poll() is not None:
                raise RunFailed(f"the server exited rc={proc.returncode}:\n{harness.tail(log)}")
            try:
                _, health = http_json("GET", base + "/healthz", timeout=5)
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise RunFailed("the server never came up")
                time.sleep(0.25)
        device = health["device"]
        t_up = time.time()
        canary_ok = check_canary(port, run, checks)
        warm_admissions(port, ctl, p, run.config["vocab_size"], checks)
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
                            cut.append(holder.pop("rec"))
                        return
                    rec = dict(t_send=None, t_first=None, t_done=time.time(), status=-1,
                               asked=n, tokens=None, final=dict(error=repr(e)[:200]))
                with lock:
                    records.append(rec)

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
        if run.trace:  # the same load, after the window
            time.sleep(p["trace_after_s"])
            ctl.ask(cmd="trace_start", dir=trace_dir)
            time.sleep(p["trace_seconds"])
            ctl.trace_stop(log, checks)
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
        canary_ok=canary_ok,
        all_200_with_the_tokens_asked=not bad,
        programs_compiled_in_window=after["cache_misses"] - before["cache_misses"],
        programs_read_from_cache_in_window=after["cache_hits"] - before["cache_hits"],
        bad=[dict(status=r["status"], final=r["final"]) for r in bad[:3]],
    )
    stamps = dict(
        t_open=t_open, t_close=t_close, seconds=run.seconds,
        requests=[dict(t_send=r["t_send"], t_first=r["t_first"], t_done=r["t_done"],
                       arrivals=r.get("arrivals", []), asked=r["asked"])
                  for r in records + cut if r["t_send"] is not None],
        healthz={k: health.get(k) for k in (
            "serving_host_frac", "phase_split", "tokens_per_s", "latency_p50_s",
            "latency_p95_s", "decode_chunk", "overlap", "slots", "served")},
        phase_split_open=health_open.get("phase_split"),
        server_up_s=t_up - run.t_start, canary_s=t_canary - t_up,
        memory_stats=after.get("memory_stats"),
        gap_spans=[], gap_rest="host_all",
    )
    first = ttfts(stamps)
    if first:  # per-layer numbers in a traced run; here for whoever reads an untraced line
        checks["ttft_p50_p90_s"] = [harness.percentile(first, 50), harness.percentile(first, 90)]
    return dict(
        stamps=stamps,
        t_open=t_open,
        attempted=len(done),
        failed=len(bad),
        correct=canary_ok and not bad,
        checks=checks,
        device=dict(platform=device["platform"], kind=device["kind"], count=device["count"],
                    memory_peak_bytes=after["memory_peak_bytes"]),
        trace_dir=trace_dir if run.trace else None,
        records=[os.path.join(run.work, "requests.jsonl"), log],
    )
