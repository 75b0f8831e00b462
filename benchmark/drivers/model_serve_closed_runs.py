"""Driver ``model_serve_closed_runs``: ``model_serve_closed``'s run (the
server built from the configuration's ``model`` entry, the warm
admissions, the teacher-forced first tokens, the closed loop, the window,
the trace) with one more comparison deciding ``correct``: **runs**.

``model_serve_closed.check_teacher`` asks for one token after a prefix, two
at most: it sees the prefill and the *first* decode step. A model whose
every decode step reads the state the last one wrote, and whose state
leaves and re-enters the chunk program every ``decode_chunk`` steps, needs
more: for each of the expected file's ``runs`` the server is asked for the
reference's whole greedy continuation (24 tokens after a prefix), and
``judge_runs`` compares position by position up to the first token that
differs: over the positions compared the *median* |log-probability
difference| has an upper limit, a first differing token is a fault where
the reference's two largest logits lie further apart than
``gap_tolerance``, and the positions compared **past the first chunk's**
(``past``: the state has been out of the chunk program and in again) have a
lower limit, as a share of all there are. The traffic file gives each
limit with its two readings.

Nothing of ``model_serve_closed`` is copied: its ``run`` calls
``check_teacher`` by its module's name, and for the length of the run that
name stands for both comparisons (until a ``benchmark`` issue gives
``run`` a hook for the comparison, as its docstring asks).
"""

import os
import statistics
import threading

from benchmark import harness
from benchmark.drivers import model_serve_closed
from benchmark.drivers.serve_closed import stream_completion
from benchmark.harness import RunFailed


def judge_runs(got: list, runs: list, limits: dict):
    """``got``: (tokens, log-probabilities) the server wrote after each of
    the expected file's ``runs`` prefixes, in their order. -> (ok, numbers)."""
    diffs, late, late_all, faults, whole = [], 0, 0, [], 0
    past = limits["past"]
    for (tokens, logprobs), want in zip(got, runs):
        n = len(want["tokens"])
        if len(tokens) != n or len(logprobs) != n:
            raise ValueError(f"a run of {len(tokens)} tokens against {n} expected")
        late_all += max(0, n - past)
        same = 0
        while same < n and tokens[same] == want["tokens"][same]:
            diffs.append(abs(logprobs[same] - want["logprobs"][same]))
            same += 1
        late += max(0, same - past)
        whole += same == n
        if same < n and want["top2_gap"][same] > limits["gap_tolerance"]:
            faults.append(dict(prompt_len=len(want["prompt"]), position=same, got=tokens[same],
                               want=want["tokens"][same], gap=want["top2_gap"][same]))
    if len(got) != len(runs):
        raise ValueError(f"{len(got)} served runs against {len(runs)} expected")
    median = statistics.median(diffs) if diffs else None
    numbers = dict(runs=len(runs), runs_whole=whole, runs_positions_compared=len(diffs),
                   runs_positions_past_first_chunk=late_all, runs_positions_past_first_chunk_compared=late,
                   runs_logprob_median_abs_diff=median, runs_logprob_max_abs_diff=max(diffs, default=None),
                   runs_mismatch=faults[:3], runs_limits=limits)
    ok = (not faults and median is not None and late >= limits["past_min_compared"] * late_all
          and (limits["median_logprob_tolerance"] is None or median <= limits["median_logprob_tolerance"]))
    return ok, numbers


def check_runs(port: int, run, checks: dict) -> bool:
    """The reference's greedy continuation after every listed prefix, as
    many requests at once as the loop has clients, judged by ``judge_runs``."""
    name = run.config["name"] + (".rehearsal" if run.rehearse else "") + ".serve_canary.json"
    runs = harness.load_json(os.path.join(harness.BENCH_DIR, "reference", "expected", name))["runs"]
    got, lock, todo = [None] * len(runs), threading.Lock(), list(range(len(runs)))

    def client():
        while True:
            with lock:
                if not todo:
                    return
                i = todo.pop()
            got[i] = stream_completion(port, runs[i]["prompt"], len(runs[i]["tokens"]))

    threads = [threading.Thread(target=client, daemon=True) for _ in range(run.traffic["params"]["clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    for want, rec in zip(runs, got):
        if (rec is None or rec["status"] != 200 or len(rec["tokens"] or []) != len(want["tokens"])
                or not rec["final"].get("logprobs")):
            raise RunFailed(f"run after a prompt of {len(want['prompt'])} tokens: {rec and (rec['status'], rec['final'])}")
    ok, numbers = judge_runs([(rec["tokens"], rec["final"]["logprobs"]) for rec in got], runs,
                             run.traffic["params"]["canary"]["runs"]["limits"])
    checks.update(numbers, runs_ok=ok)
    return ok


def run(run):
    first_tokens = model_serve_closed.check_teacher

    def both(port, run_, checks):
        ok = first_tokens(port, run_, checks)
        return check_runs(port, run_, checks) and ok

    model_serve_closed.check_teacher = both
    try:
        return model_serve_closed.run(run)
    finally:
        model_serve_closed.check_teacher = first_tokens
