"""Makes the values ``drivers/model_serve_closed_runs.py`` holds a served
``qwen3_next`` configuration to, with the plain reference
(``benchmark/reference/qwen3_next.py``):

    python3 benchmark/reference/make_expected_qwen3_next.py qwen3-next-80b-a3b-ep4-l12 rag-closed-16-longprompt
    ... --rehearsal                  # the configuration's tiny rehearsal size (here, on the CPU)
    ... --matrix-bits 3              # control: every matrix rounded to 3 mantissa bits first
    ... --state-dtype bfloat16       # control: the state S rounded to bf16 after every token
    ... --engine served|mantissa3|state-zeroed|state-bfloat16
                                     # what the serving engine itself writes at the expected file's
                                     # positions, as served or under a control of its own

``teacher`` (the format ``model_serve_closed.check_teacher`` reads): fixed
random sequences and, at each of a list of prompt lengths ``n`` (log-uniform
over the traffic's prompt lengths: every prefill bucket), what the reference
predicts after the first ``n`` tokens; after every ``second_every``-th also
what follows the reference's own first token. ``runs``: for each of
``runs.count`` prompts the reference's greedy continuation of
``runs.tokens`` tokens with, at every one, its log-probability and the gap
between its two largest logits. The prompts are, of ``runs.candidates``
random ones, those whose smallest gap along the run is largest, as many
from each prefill bucket: a run that stands at a near-tie somewhere
compares nothing after it. The *program* (bf16, its own cached decode)
picks them and proposes each run; the reference, which has no cache, checks
a proposed run in one forward pass over prompt + run (where its own greedy
token differs it takes its own and looks again), so every listed run is the
reference's own greedy continuation.

A reference control writes, beside the expected file and from it, what the
reference computed in fewer bits says at the same positions
(``...<control>.serve_canary.json``). ``--engine`` builds the serving
engine in this process (the cell's prompt width and decode chunk, 4 slots),
asks it the expected file's prompts and writes what the driver's two
judgements make of its answers, and the answers, into ``...readings.json``
under the control's name: ``state-zeroed`` zeroes a row's recurrent and convolution
state between its prefill and its admission, ``state-bfloat16`` rounds the
matrix state after every step and every prefill, ``mantissa3`` rounds every
matrix the engine holds. ``benchmark/tests/test_metrics_qwen3_next.py``
holds the traffic file's limits to all of them.

The weights are the ones the program serves: its own start-up init from
``weights_key`` in the dtypes the server holds (``models/build.py:
init_params_as_consumed``; weights are data). The init's witnesses
(``reference/qwen3_next.py: layer_witnesses``) are measured on the way.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEP = 64  # contexts are right-padded to multiples of this: few shapes to compile
ROWS = 2  # contexts a forward pass of the reference (its attention keeps [rows, heads, T, T] scores)


def engine_readings(ns, config, traffic, params, stem):
    """The engine's own answers at the expected file's positions, judged as
    the driver judges the server's."""
    import jax
    import jax.numpy as jnp

    from benchmark.drivers.model_serve_closed import judge_teacher
    from benchmark.drivers.model_serve_closed_runs import judge_runs
    from benchmark.reference import qwen3_next as ref
    from dlrover_tpu.models import qwen3_next as program
    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    p, canary = traffic["params"], traffic["params"]["canary"]
    expected = json.load(open(stem + ".serve_canary.json"))
    if ns.engine == "mantissa3":
        params = jax.jit(lambda tree: jax.tree_util.tree_map_with_path(  # in place: two copies do not fit
            lambda path, a: (ref.round_mantissa(a, 3).astype(a.dtype)
                             if getattr(path[-1], "key", None) in ref.MATRICES else a), tree), donate_argnums=0)(params)
    if ns.engine == "state-bfloat16":
        bf16 = jnp.finfo(jnp.bfloat16)
        rounded = lambda s: jax.lax.reduce_precision(s, bf16.nexp, bf16.nmant)  # noqa: E731
        step, chunked = program.gated_delta_step, program.gated_delta_chunked
        program.gated_delta_step = lambda *a: (lambda o, s: (o, rounded(s)))(*step(*a))
        program.gated_delta_chunked = lambda *a: (lambda o, s: (o, rounded(s)))(*chunked(*a))
    model, _ = build_model(config["model"])
    engine = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=p["max_new_tokens"], temperature=p["temperature"]),
        batch_size=4, prompt_width=p["prompt_width"])
    if ns.engine == "state-zeroed":
        prefill, is_state = engine._prefill_fn, model.cache_state_leaves

        def forgetful(*args):
            row = prefill(*args)
            cache = jax.tree.map(lambda a, state: jnp.zeros_like(a) if state else a, row[0], is_state(row[0]))
            return (cache,) + tuple(row[1:])

        engine._prefill_fn = forgetful

    def ask(prompts_and_counts):
        uids = [engine.submit(prompt, max_new_tokens=n) for prompt, n in prompts_and_counts]
        done = {c.uid: c for c in engine.run()}
        return [(list(done[u].tokens), list(done[u].logprobs)) for u in uids]

    t0 = time.time()
    teacher = expected["teacher"]
    first = ask([(seq["sequence"][:n], 2 if j in seq["second_at"] else 1)
                 for seq in teacher for j, n in enumerate(seq["prompt_lengths"])])
    teacher_ok, numbers = judge_teacher(first, teacher, canary["teacher"]["limits"])
    runs = ask([(r["prompt"], len(r["tokens"])) for r in expected["runs"]])
    runs_ok, more = judge_runs(runs, expected["runs"], canary["runs"]["limits"])
    path = stem + ".readings.json"
    readings = json.load(open(path)) if os.path.exists(path) else {}
    readings[ns.engine] = dict(teacher=first, runs=runs, judged=dict(numbers, **more), teacher_ok=teacher_ok,
                               runs_ok=runs_ok, slots=4, device=jax.devices()[0].device_kind,
                               seconds=round(time.time() - t0))
    with open(path, "w") as f:
        json.dump(readings, f)
    print(path, ns.engine, "teacher_ok", teacher_ok, "runs_ok", runs_ok)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("traffic")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--matrix-bits", type=int, default=None)
    ap.add_argument("--state-dtype", default=None, choices=["bfloat16"])
    ap.add_argument("--engine", default=None, choices=["served", "mantissa3", "state-zeroed", "state-bfloat16"])
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import merge
    from benchmark.reference import qwen3_next as ref
    from benchmark.reference.make_expected import load_config
    from dlrover_tpu.models.build import build_model, init_params_as_consumed
    from dlrover_tpu.models.generation import decode_apply, prefill_prompt

    config = load_config(ns.config, ns.rehearsal)
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", ns.traffic + ".json")))
    if ns.rehearsal:
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    p, canary = traffic["params"], traffic["params"]["canary"]
    hp = config["model"]["config"]
    vocab, lo, hi = config["vocab_size"], p["prompt_len"]["lo"], p["prompt_len"]["hi"]
    model, _ = build_model(config["model"])
    t0 = time.time()
    params = init_params_as_consumed(model, jax.random.PRNGKey(config["weights_key"]))
    n_params = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
    print(f"{n_params} parameters made in {time.time() - t0:.0f} s", file=sys.stderr)
    stem = os.path.join(ROOT, "benchmark", "reference", "expected",
                        config["name"] + (".rehearsal" if ns.rehearsal else ""))
    if ns.engine:
        return engine_readings(ns, config, traffic, params, stem)
    control = dict(state_dtype=jnp.dtype(ns.state_dtype).type if ns.state_dtype else None,
                   matrix_bits=ns.matrix_bits)
    tag = (f"mantissa{ns.matrix_bits}" if ns.matrix_bits else "") + (f"state-{ns.state_dtype}" if ns.state_dtype else "")

    def describe(row):
        """One position's logits -> (the greedy token, the gap to the second, its log-probability)."""
        top = np.argsort(row)[-2:]
        row = row.astype(np.float64)
        return (int(top[1]), float(row[top[1]] - row[top[0]]),
                float(row[top[1]] - row.max() - np.log(np.exp(row - row.max()).sum())))

    def after(contexts, last: int = 1):
        """What the reference predicts at the last ``last`` positions of each
        of up to ``ROWS`` token lists, which may differ in length: padded on
        the right to one width (right-padding cannot reach an earlier
        position through a causal mask, a causal convolution or a
        recurrence). -> [[(token, gap, logprob)] * last] a context."""
        width = -(-max(len(c) for c in contexts) // STEP) * STEP
        padded = [c + [0] * (width - len(c)) for c in contexts] + [[0] * width] * (ROWS - len(contexts))
        x = ref.hidden(params, jnp.asarray(padded, jnp.int32), hp, **control)
        at = jnp.asarray([[len(c) - last + j for j in range(last)] for c in contexts])
        picked = x[jnp.arange(len(contexts))[:, None], at]  # [contexts, last, d]
        found = np.asarray(ref.head(picked, params["final_norm"]["scale"], params["lm_head"], hp["rms_norm_eps"],
                                    control["matrix_bits"]))
        return [[describe(row) for row in rows_] for rows_ in found]

    def batched(contexts, last=1):
        return [d for first in range(0, len(contexts), ROWS) for d in after(contexts[first:first + ROWS], last)]

    # -- a control: the same positions, computed in fewer bits -------------------------------------
    if tag:
        expected = json.load(open(stem + ".serve_canary.json"))
        teacher = []
        for seq in expected["teacher"]:
            first = batched([seq["sequence"][:n] for n in seq["prompt_lengths"]])
            second = batched([seq["sequence"][:seq["prompt_lengths"][i]] + [seq["tokens"][i]] for i in seq["second_at"]])
            teacher.append(dict(tokens=[d[0][0] for d in first], logprobs=[d[0][2] for d in first],
                                second_tokens=[d[0][0] for d in second], second_logprobs=[d[0][2] for d in second]))
        runs = []
        for said in batched([r["prompt"] + r["tokens"][:-1] for r in expected["runs"]],
                            last=len(expected["runs"][0]["tokens"])):
            runs.append(dict(tokens=[d[0] for d in said], logprobs=[d[2] for d in said]))
        with open(f"{stem}.{tag}.serve_canary.json", "w") as f:
            json.dump(dict(teacher=teacher, runs=runs, control=tag, config=config["name"],
                           device=jax.devices()[0].device_kind, seconds=round(time.time() - t0)), f)
        print(f"{stem}.{tag}.serve_canary.json")
        return

    # -- the teacher-forced first and second tokens -----------------------------------------------------
    rng = np.random.default_rng(config["canary_key"])
    spec, teacher = canary["teacher"], []
    for _ in range(spec["sequences"]):
        sequence = [int(t) for t in rng.integers(0, vocab, spec["length"])]
        lengths = sorted({int(round(n)) for n in np.exp(rng.uniform(np.log(lo), np.log(spec["length"]), spec["positions"]))})
        rows = np.asarray(ref.logits(params, jnp.asarray([sequence], jnp.int32), hp, at=[n - 1 for n in lengths]))[0]
        tokens, gaps, logprobs = zip(*(describe(row) for row in rows))
        second_at = list(range(0, len(lengths), spec["second_every"]))
        second = [d[0] for d in batched([sequence[:lengths[i]] + [tokens[i]] for i in second_at])]
        teacher.append(dict(sequence=sequence, prompt_lengths=lengths, tokens=list(tokens), top2_gap=list(gaps),
                            logprobs=list(logprobs), second_at=second_at,
                            second_tokens=[d[0] for d in second], second_top2_gap=[d[1] for d in second],
                            second_logprobs=[d[2] for d in second]))
        print(f"sequence of {len(sequence)}: {len(lengths)} positions ({len(second_at)} with a second token), "
              f"min gap {min(gaps):.5f} at {time.time() - t0:.0f} s", file=sys.stderr)

    # -- the runs: the program proposes, the reference disposes ------------------------------------------
    spec = canary["runs"]
    n_new, width = spec["tokens"], p["prompt_width"]

    @jax.jit
    def propose(params, tokens, mask):
        """The program's own greedy run after left-padded prompts (its cached
        decode in the served dtypes) -> (tokens [B, n], top-2 gaps [B, n])."""
        cache, logits, pos, kv_valid = prefill_prompt(model, params, tokens, mask)

        def step(carry, t):
            cache, kv_valid, logits, pos = carry
            top, idx = jax.lax.top_k(logits, 2)
            kv_valid = kv_valid | (jnp.arange(kv_valid.shape[1])[None, :] == width + t)
            out, cache = decode_apply(model, params, cache, idx[:, :1], (pos + 1)[:, None], kv_valid)
            return (cache, kv_valid, out[:, 0].astype(jnp.float32), pos + 1), (idx[:, 0], top[:, 0] - top[:, 1])

        _, (toks, gaps) = jax.lax.scan(step, (cache, kv_valid, logits, pos), jnp.arange(n_new))
        return toks.T, gaps.T

    lengths = [int(round(n)) for n in np.exp(rng.uniform(np.log(lo), np.log(hi), spec["candidates"]))]
    prompts = [[int(t) for t in rng.integers(0, vocab, n)] for n in lengths]
    proposed = []
    for first in range(0, len(prompts), spec["batch"]):
        some = prompts[first:first + spec["batch"]]
        some = some + [some[0]] * (spec["batch"] - len(some))
        toks = np.zeros((len(some), width), np.int32)
        mask = np.zeros((len(some), width), bool)
        for i, prompt in enumerate(some):
            toks[i, width - len(prompt):], mask[i, width - len(prompt):] = prompt, True
        run_tokens, run_gaps = (np.asarray(a) for a in propose(params, jnp.asarray(toks), jnp.asarray(mask)))
        proposed += [(float(g.min()), [int(t) for t in r]) for r, g in zip(run_tokens, run_gaps)][:len(prompts) - first]
    print(f"{len(proposed)} runs proposed at {time.time() - t0:.0f} s; smallest gaps' quartiles "
          f"{np.percentile([g for g, _ in proposed], [25, 50, 75]).round(4).tolist()}", file=sys.stderr)
    buckets = sorted({max(8, width // 4), max(8, width // 2), width})  # the engine's (ContinuousBatchingEngine._bucket_width)
    bucket_of = lambda n: next(b for b in buckets if n <= b)  # noqa: E731
    chosen = []
    for b in buckets:
        here = sorted((i for i in range(len(prompts)) if bucket_of(lengths[i]) == b), key=lambda i: -proposed[i][0])
        chosen += here[:-(-spec["count"] // len(buckets))]
    runs = [dict(prompt=prompts[i], tokens=list(proposed[i][1])) for i in chosen]
    unsettled = list(runs)
    for _ in range(n_new + 1):  # each pass settles at least one more token of every unsettled run
        said = batched([r["prompt"] + r["tokens"][:-1] for r in unsettled], last=n_new)
        moved = []
        for r, row in zip(unsettled, said):
            differs = [j for j in range(n_new) if row[j][0] != r["tokens"][j]]
            if differs:  # the reference's own token there; what follows is looked at again
                r["tokens"][differs[0]] = row[differs[0]][0]
                moved.append(r)
            else:
                r.update(top2_gap=[d[1] for d in row], logprobs=[d[2] for d in row])
        print(f"{len(unsettled)} runs checked, {len(moved)} moved, at {time.time() - t0:.0f} s", file=sys.stderr)
        unsettled = moved
        if not moved:
            break
    else:
        raise SystemExit("the runs did not settle")

    # -- what the init has to show: old state's share, what each branch adds ------------------------
    witnesses = {}
    if not ns.rehearsal:
        tokens = jnp.asarray(rng.integers(0, vocab, (2, 256)), jnp.int32)
        for layer in [i for i in (0, 5, 10) if i < hp["num_hidden_layers"] and not ref.is_attention(hp, i)]:
            witnesses[f"layer_{layer}"] = ref.layer_witnesses(params, tokens, hp, layer, 64)
        print(f"witnesses {witnesses} at {time.time() - t0:.0f} s", file=sys.stderr)

    with open(stem + ".serve_canary.json", "w") as f:
        json.dump(dict(teacher=teacher, runs=runs, witnesses=witnesses, config=config["name"],
                       traffic=ns.traffic, n_params=n_params, device=jax.devices()[0].device_kind,
                       seconds=round(time.time() - t0)), f)
    print(stem + ".serve_canary.json")


if __name__ == "__main__":
    main()
