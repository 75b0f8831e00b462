"""Driver ``model_serve_closed_blocks``: ``model_serve_closed``'s run (the
server built from the configuration's ``model`` entry, the warm admissions,
the closed loop, the window, the trace) against a server whose model is
*decoded a block at a time* (generation by diffusion over blocks:
``dlrover_tpu/models/serving.py: make_block_chunk``), with the comparison
that decides ``correct`` judging **blocks**.

What the table of ``benchmark/README.md`` would say of it: serving, PR 59; a
served answer is a run of *decisions*, one a pass of a block: which of the
block's undecided positions the pass fixed (the most confident) and with
which tokens. ``model_serve_closed.check_teacher`` asks for one token after a
prefix; here a pass fixes several tokens or none, and what a later pass or a
later block reads is what earlier passes decided and what a block's final
pass wrote into the cache. So for each of the expected file's ``runs`` (a
prefix of a fixed sequence, then 12 tokens: three blocks, or a first and a
last one cut and two whole between) the server is asked for the reference's
whole answer and ``judge_blocks`` walks its decisions in order:

- the **first decision** of a run (its first block's pass 0) depends on the
  prefill alone and is always compared; the later ones (a pass that reads
  the block's own earlier choices; a later block, which reads the keys and
  values a final pass wrote) along the run up to its first decision that
  differs: after it the contexts differ and nothing more can be compared;
- a decision that differs is a **fault** where the reference's runner-up
  lies further than ``gap_tolerance``: in confidence (log-probability) where
  other *positions* were fixed, in logit where another *token* was. One kind
  of difference has no gap in the file and is no fault: in a run's last
  block, where the cap cut it, a pass after the block's first may follow a
  pass that fixed positions past the cap, which no answer shows (served
  ``{233, 234}`` where the reference took ``{232, 234}`` reads as position
  232 fixed a pass late, at a forced pass whose gap is infinite). The
  comparison of the run ends there like at any difference, and nothing comes
  after a last block;
- over the tokens compared the *median* |log-probability difference| has an
  upper limit (``median_logprob_tolerance``);
- the tokens compared **past each run's first block** have a lower limit, as
  a share of all there are (``later_min_compared``): they are the ones that
  read a final pass's keys and values.

The traffic file gives each limit with the readings it was set from (the
served path and the controls, ``expected/<config>.readings.json``). And every
request of the window is answered with the tokens it asked for.

Nothing of ``model_serve_closed`` is copied: its ``run`` calls
``check_teacher`` by its module's name, and for the length of the run that
name stands for this comparison (as ``model_serve_closed_runs`` does, until
a ``benchmark`` issue gives ``run`` a hook). The traffic's prompts are drawn
below ``params.ids_below`` (the ids past it are the tokenizer's added ones,
the mask token among them): the run is handed the configuration with that as
its ``vocab_size``, which ``make_requests`` and ``warm_admissions`` read.
"""

import os
import statistics
import threading
import types

from benchmark import harness
from benchmark.drivers import model_serve_closed
from benchmark.drivers.serve_closed import stream_completion
from benchmark.harness import RunFailed


def decisions_of(prompt_len: int, tokens: list, passes: list, block_length: int) -> list:
    """An answer's tokens as decisions in the order they were taken: one a
    (block, pass) that fixed any of them, ``(block, pass, {position: token})``."""
    by = {}
    for i, (token, at_pass) in enumerate(zip(tokens, passes)):
        position = prompt_len + i
        by.setdefault((position // block_length, at_pass), {})[position] = token
    return [(block, at_pass, by[block, at_pass]) for block, at_pass in sorted(by)]


def judge_blocks(got: list, runs: list, limits: dict, block_length: int):
    """``got``: (tokens, log-probabilities, passes) the server wrote after
    each of the expected file's ``runs`` prompts, in their order.
    -> (ok, numbers)."""
    diffs, faults, whole, later, later_all, first_same = [], [], 0, 0, 0, 0
    if len(got) != len(runs):
        raise ValueError(f"{len(got)} served runs against {len(runs)} expected")
    for (tokens, logprobs, passes), want in zip(got, runs):
        n, start = len(want["tokens"]), len(want["prompt"])
        if not len(tokens) == len(logprobs) == len(passes) == n:
            raise ValueError(f"a run of {len(tokens)} tokens, {len(passes)} passes against {n} expected")
        served = decisions_of(start, tokens, passes, block_length)
        first_block = start // block_length
        later_all += sum(1 for i in range(n) if (start + i) // block_length > first_block)
        same = True
        for k, (block, at_pass, fixed) in enumerate(decisions_of(start, want["tokens"], want["passes"], block_length)):
            # a pass before this one may have fixed positions of the block past the cap, which the file does not hold
            unseen = at_pass > 0 and (block + 1) * block_length > start + n
            mine = served[k][2] if k < len(served) and served[k][:2] == (block, at_pass) else {}
            gap = None
            if set(mine) != set(fixed):  # other positions were the most confident
                gap = min(want["select_gap"][p - start] for p in fixed)
            else:
                differ = [p for p in fixed if mine[p] != fixed[p]]
                if differ:
                    gap = max(want["top2_gap"][p - start] for p in differ)
            if gap is not None:
                same = False
                if gap > limits["gap_tolerance"] and not unseen:
                    faults.append(dict(prompt_len=start, block=block, at_pass=at_pass, got=mine, want=fixed, gap=gap))
                break
            first_same += k == 0
            for p in fixed:
                diffs.append(abs(logprobs[p - start] - want["logprobs"][p - start]))
                later += block > first_block
        whole += same
    median = statistics.median(diffs) if diffs else None
    numbers = dict(blocks_runs=len(runs), blocks_runs_whole=whole, blocks_first_decisions_same=first_same,
                   blocks_tokens_compared=len(diffs), blocks_tokens_past_first_block=later_all,
                   blocks_tokens_past_first_block_compared=later, blocks_logprob_median_abs_diff=median,
                   blocks_logprob_max_abs_diff=max(diffs, default=None), blocks_mismatch=faults[:3],
                   blocks_limits=limits)
    ok = (not faults and median is not None and later >= limits["later_min_compared"] * later_all
          and (limits["median_logprob_tolerance"] is None or median <= limits["median_logprob_tolerance"]))
    return ok, numbers


def check_blocks(port: int, run, checks: dict) -> bool:
    """The reference's whole answer after every listed prompt, as many
    requests at once as the loop has clients (after the warm admissions: no
    program is new here), judged by ``judge_blocks``."""
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
        final = rec and rec["final"] or {}
        if (rec is None or rec["status"] != 200 or len(rec["tokens"] or []) != len(want["tokens"])
                or not final.get("logprobs") or final.get("passes") is None):
            raise RunFailed(f"run after a prompt of {len(want['prompt'])} tokens: {rec and (rec['status'], rec['final'])}")
    ok, numbers = judge_blocks(
        [(rec["tokens"], rec["final"]["logprobs"], rec["final"]["passes"]) for rec in got], runs,
        run.traffic["params"]["canary"]["blocks"]["limits"], run.config["model"]["config"]["block_length"])
    # a line a final block: the first answer's lines, as the client met them
    checks.update(numbers, blocks_ok=ok, blocks_first_answer_lines=[n for _, n in got[0]["arrivals"]])
    return ok


def run(run):
    first_tokens = model_serve_closed.check_teacher
    below = run.traffic["params"].get("ids_below", run.config["vocab_size"])
    model_serve_closed.check_teacher = check_blocks
    try:
        return model_serve_closed.run(types.SimpleNamespace(
            **{**vars(run), "config": dict(run.config, vocab_size=min(below, run.config["vocab_size"]))}))
    finally:
        model_serve_closed.check_teacher = first_tokens
