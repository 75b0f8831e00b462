"""Makes the values ``drivers/model_serve_closed_runs.py`` holds a served
``olmo_hybrid`` configuration to, with the plain reference
(``benchmark/reference/olmo_hybrid.py``):

    python3 benchmark/reference/make_expected_olmo_hybrid.py olmo-hybrid-7b-pp4-l8 longdoc-closed-4
    ... --rehearsal                  # the configuration's tiny rehearsal size (here, on the CPU)
    ... --blocks                     # first: the program's prefill against the reference, block by block
    ... --blocks --dtype float32 --norm-init 1.0
                                     # the second witness: the program computing in float32 (products at
                                     # "highest"), here at the family's own start of the sublayers' norms
    ... --matrix-bits 3              # control: every matrix rounded to 3 mantissa bits first
    ... --engine served|mantissa3|state-zeroed|beta-halved|pad-unmasked
                                     # what the serving engine itself writes at the expected file's
                                     # positions, as served or under a control of its own

``teacher`` and ``runs`` are ``make_expected_qwen3_next.py``'s (its
docstring says what each holds and why the program proposes the runs and the
reference disposes), at this cell's lengths: teacher sequences as long as
the widest prompt, prefixes log-uniform over the traffic's prompt lengths
(every prefill bucket, each left-padded), contexts right-padded to whole
blocks of the reference's attention (right-padding cannot reach an earlier
position through a causal mask, a causal convolution or a recurrence).

``--blocks`` (ROADMAP's lesson of PR 42: read the program against the
reference block by block on the chip BEFORE the expected file is made)
runs one left-padded prompt of every bucket through the program's own
prefill a layer at a time and prints, after every block, the distance of
the program's stream from the reference's at the real positions. With
``--dtype float32`` the program itself computes in float32 with its products
at ``highest``: what is left between it and the reference is then the
program's arithmetic (the chunked form and its inverse, the tiled walk, the
padding rule) and no rounding of bf16, at whatever ``--norm-init`` starts the
sublayers' norms at; a fault of the program reads there as it does in bf16,
the rounding does not.

A reference control writes, beside the expected file and from it, what the
reference computed in fewer bits says at the same positions
(``...<control>.serve_canary.json``). ``--engine`` builds the serving
engine in this process (the cell's prompt width, slots and decode chunk),
asks it the expected file's prompts and writes what the driver's two
judgements make of its answers, and the answers, into ``...readings.json``
under the control's name: ``state-zeroed`` zeroes a row's recurrent and
convolution state between its prefill and its admission, ``mantissa3``
rounds every matrix the engine holds, ``beta-halved`` serves the mixer
without ``linear_allow_neg_eigval`` (``beta = sigmoid(b)``), ``pad-unmasked``
leaves the rows of a tiled prefill unturned, so that a real token sees the
padding before it. ``benchmark/tests/test_metrics_olmo_hybrid.py`` holds the
traffic file's limits to all of them.

The weights are the ones the program serves: its own start-up init from
``weights_key`` in the dtypes the server holds (``models/build.py:
init_params_as_consumed``; weights are data). The init's witnesses
(``reference/olmo_hybrid.py: layer_witnesses``) are measured on the way.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

ENGINE_CONTROLS = ["served", "mantissa3", "state-zeroed", "beta-halved", "pad-unmasked"]


def engine_readings(ns, config, traffic, params, stem):
    """The engine's own answers at the expected file's positions, judged as
    the driver judges the server's."""
    import jax
    import jax.numpy as jnp

    from benchmark.drivers.model_serve_closed import judge_teacher
    from benchmark.drivers.model_serve_closed_runs import judge_runs
    from benchmark.reference import olmo_hybrid as ref
    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.generation import SamplingConfig
    from dlrover_tpu.models.serving import ContinuousBatchingEngine

    p, canary = traffic["params"], traffic["params"]["canary"]
    expected = json.load(open(stem + ".serve_canary.json"))
    entry = config["model"]
    if ns.engine == "mantissa3":
        params = jax.jit(lambda tree: jax.tree_util.tree_map_with_path(  # in place: two copies need not fit
            lambda path, a: (ref.round_mantissa(a, 3).astype(a.dtype)
                             if getattr(path[-1], "key", None) in ref.MATRICES else a), tree), donate_argnums=0)(params)
    if ns.engine == "beta-halved":
        entry = dict(entry, config=dict(entry["config"], linear_allow_neg_eigval=False))
    if ns.engine == "pad-unmasked":
        jnp.roll = lambda a, shift, axis=None: a  # ``layers._tiled_prefill_attention`` turns its rows with it
    model, _ = build_model(entry)
    engine = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=p["max_new_tokens"], temperature=p["temperature"]),
        batch_size=p["batch_size"], prompt_width=p["prompt_width"])
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
                               runs_ok=runs_ok, slots=p["batch_size"], device=jax.devices()[0].device_kind,
                               seconds=round(time.time() - t0))
    with open(path, "w") as f:
        json.dump(readings, f)
    print(path, ns.engine, "teacher_ok", teacher_ok, "runs_ok", runs_ok,
          {k: v for k, v in dict(numbers, **more).items() if not k.endswith("limits")})


def blocks(config, traffic, params, vocab):
    """The program's prefill of one left-padded prompt a bucket against the
    reference, after every block (the stream's largest and RMS distance at
    the real positions, beside the stream's RMS), and in the last logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import olmo_hybrid as ref
    from dlrover_tpu.models.build import build_model
    from dlrover_tpu.models.olmo_hybrid import Block

    model, _ = build_model(config["model"])
    cfg, hp = model.config, config["model"]["config"]
    precision = "highest" if cfg.dtype == jnp.float32 else None  # None: the program as it is served
    print(f"the program computes in {jnp.dtype(cfg.dtype).name}, products at {precision or 'the default'}; "
          f"sublayer norms start at {cfg.sublayer_norm_init}", flush=True)
    width = traffic["params"]["prompt_width"]
    rng = np.random.default_rng(config["canary_key"] + 1)
    for bucket in sorted({max(8, width // 4), max(8, width // 2), width}):
        n = int(bucket * 0.8)
        prompt = rng.integers(0, vocab, n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, bucket - n:] = prompt
        mask = np.arange(bucket)[None, :] >= bucket - n
        kv_valid = jnp.zeros((1, cfg.max_seq_len), bool).at[:, :bucket].set(mask)
        x = params["wte"][jnp.asarray(toks)].astype(cfg.dtype)
        want = params["wte"][jnp.asarray(prompt)[None]].astype(jnp.float32)
        for i in range(cfg.num_hidden_layers):
            name = f"block_{i}"

            @jax.jit
            def one(p, x, i=i):  # decode mode from a fresh row's cache, as the prefill program runs it
                return Block(cfg, layer_idx=i).apply(
                    {"params": p}, x, decode=True, kv_valid=kv_valid, token_valid=jnp.asarray(mask),
                    mutable=["cache"])[0]

            with jax.default_matmul_precision(precision):
                x = one(params[name], x)
            want = ref._layer(want, params[name], ref._hashable(hp), ref.is_attention(hp, i), None, None, -1)
            d = np.asarray(x[0, bucket - n:].astype(jnp.float32) - want[0])
            print(f"bucket {bucket} real {n} after block {i} ({hp['layer_types'][i]}): max |d| {np.abs(d).max():.3g} "
                  f"rms d {np.sqrt((d ** 2).mean()):.3g} stream rms {float(jnp.sqrt(jnp.mean(want ** 2))):.3f}", flush=True)
        got = ref.head(x[:, -1:].astype(jnp.float32), params["final_norm"]["scale"], params["lm_head"], hp["rms_norm_eps"])
        ref_logits = ref.head(want[:, -1:], params["final_norm"]["scale"], params["lm_head"], hp["rms_norm_eps"])
        d = np.asarray(got - ref_logits)
        print(f"bucket {bucket}: last logits max |d| {np.abs(d).max():.3g} median |d| {np.median(np.abs(d)):.3g} "
              f"logits std {float(jnp.std(ref_logits)):.3f}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("traffic")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--blocks", action="store_true")
    ap.add_argument("--dtype", default=None, help="with --blocks: the program's compute dtype")
    ap.add_argument("--norm-init", type=float, default=None, help="with --blocks: sublayer_norm_init")
    ap.add_argument("--matrix-bits", type=int, default=None)
    ap.add_argument("--engine", default=None, choices=ENGINE_CONTROLS)
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import merge
    from benchmark.reference import olmo_hybrid as ref
    from benchmark.reference.make_expected import load_config
    from dlrover_tpu.models.build import build_model, init_params_as_consumed
    from dlrover_tpu.models.generation import decode_apply, prefill_prompt

    config = load_config(ns.config, ns.rehearsal)
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", ns.traffic + ".json")))
    if ns.rehearsal:
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    p, canary = traffic["params"], traffic["params"]["canary"]
    hp = config["model"]["config"]
    if (ns.dtype or ns.norm_init is not None) and not ns.blocks:
        ap.error("--dtype and --norm-init are --blocks' (the expected file is the configuration's own)")
    if ns.dtype:
        hp["dtype"] = ns.dtype
    if ns.norm_init is not None:
        hp["sublayer_norm_init"] = ns.norm_init
    vocab, lo, hi = config["vocab_size"], p["prompt_len"]["lo"], p["prompt_len"]["hi"]
    step = 8 if ns.rehearsal else ref.ATTENTION_ROWS  # contexts are padded to multiples of it: few shapes to compile
    model, _ = build_model(config["model"])
    t0 = time.time()
    params = init_params_as_consumed(model, jax.random.PRNGKey(config["weights_key"]))
    n_params = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
    print(f"{n_params} parameters made in {time.time() - t0:.0f} s", file=sys.stderr)
    stem = os.path.join(ROOT, "benchmark", "reference", "expected",
                        config["name"] + (".rehearsal" if ns.rehearsal else ""))
    if ns.blocks:
        return blocks(config, traffic, params, vocab)
    if ns.engine:
        return engine_readings(ns, config, traffic, params, stem)
    control = dict(matrix_bits=ns.matrix_bits)
    tag = f"mantissa{ns.matrix_bits}" if ns.matrix_bits else ""

    def describe(row):
        """One position's logits -> (the greedy token, the gap to the second, its log-probability)."""
        top = np.argsort(row)[-2:]
        row = row.astype(np.float64)
        return (int(top[1]), float(row[top[1]] - row[top[0]]),
                float(row[top[1]] - row.max() - np.log(np.exp(row - row.max()).sum())))

    def after(context, last: int = 1):
        """What the reference predicts at the last ``last`` positions of one
        token list, padded on the right to whole blocks. -> [(token, gap,
        logprob)] * last."""
        width = -(-len(context) // step) * step
        padded = context + [0] * (width - len(context))
        x = ref.hidden(params, jnp.asarray([padded], jnp.int32), hp, **control)
        picked = x[:, len(context) - last:len(context)]
        found = np.asarray(ref.head(picked, params["final_norm"]["scale"], params["lm_head"], hp["rms_norm_eps"],
                                    control["matrix_bits"]))
        return [describe(row) for row in found[0]]

    # -- a control: the same positions, computed in fewer bits -------------------------------------
    if tag:
        expected = json.load(open(stem + ".serve_canary.json"))
        teacher = []
        for seq in expected["teacher"]:
            first = [after(seq["sequence"][:n]) for n in seq["prompt_lengths"]]
            second = [after(seq["sequence"][:seq["prompt_lengths"][i]] + [seq["tokens"][i]]) for i in seq["second_at"]]
            teacher.append(dict(tokens=[d[0][0] for d in first], logprobs=[d[0][2] for d in first],
                                second_tokens=[d[0][0] for d in second], second_logprobs=[d[0][2] for d in second]))
            print(f"a sequence's positions at {time.time() - t0:.0f} s", file=sys.stderr)
        runs = []
        for r in expected["runs"]:
            said = after(r["prompt"] + r["tokens"][:-1], last=len(r["tokens"]))
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
        second = [after(sequence[:lengths[i]] + [tokens[i]])[0] for i in second_at]
        teacher.append(dict(sequence=sequence, prompt_lengths=lengths, tokens=list(tokens), top2_gap=list(gaps),
                            logprobs=list(logprobs), second_at=second_at,
                            second_tokens=[d[0] for d in second], second_top2_gap=[d[1] for d in second],
                            second_logprobs=[d[2] for d in second]))
        print(f"sequence of {len(sequence)}: {len(lengths)} positions ({len(second_at)} with a second token), "
              f"min gap {min(gaps):.5f} at {time.time() - t0:.0f} s", file=sys.stderr)

    # -- the runs: the program proposes, the reference disposes ------------------------------------------
    spec = canary["runs"]
    n_new, width = spec["tokens"], p["prompt_width"]
    buckets = sorted({max(8, width // 4), max(8, width // 2), width})  # the engine's (ContinuousBatchingEngine._bucket_width)
    bucket_of = lambda n: next(b for b in buckets if n <= b)  # noqa: E731

    @jax.jit
    def propose(params, tokens, mask):
        """The program's own greedy run after left-padded prompts (its cached
        decode in the served dtypes) -> (tokens [B, n], top-2 gaps [B, n])."""
        w = tokens.shape[1]
        cache, logits, pos, kv_valid = prefill_prompt(model, params, tokens, mask)

        def one(carry, t):
            cache, kv_valid, logits, pos = carry
            top, idx = jax.lax.top_k(logits, 2)
            kv_valid = kv_valid | (jnp.arange(kv_valid.shape[1])[None, :] == w + t)
            out, cache = decode_apply(model, params, cache, idx[:, :1], (pos + 1)[:, None], kv_valid,
                                      cache_slots=jnp.full((tokens.shape[0],), w + t, jnp.int32))
            return (cache, kv_valid, out[:, 0].astype(jnp.float32), pos + 1), (idx[:, 0], top[:, 0] - top[:, 1])

        _, (toks, gaps) = jax.lax.scan(one, (cache, kv_valid, logits, pos), jnp.arange(n_new))
        return toks.T, gaps.T

    lengths = [int(round(n)) for n in np.exp(rng.uniform(np.log(lo), np.log(hi), spec["candidates"]))]
    prompts = [[int(t) for t in rng.integers(0, vocab, n)] for n in lengths]
    proposed = [None] * len(prompts)
    for b in buckets:  # a bucket's prompts at the bucket's width, as the server prefills them
        here = [i for i in range(len(prompts)) if bucket_of(lengths[i]) == b]
        for first in range(0, len(here), spec["batch"]):
            some = here[first:first + spec["batch"]]
            some = some + [some[0]] * (spec["batch"] - len(some))
            toks, mask = np.zeros((len(some), b), np.int32), np.zeros((len(some), b), bool)
            for row, i in enumerate(some):
                toks[row, b - lengths[i]:], mask[row, b - lengths[i]:] = prompts[i], True
            run_tokens, run_gaps = (np.asarray(a) for a in propose(params, jnp.asarray(toks), jnp.asarray(mask)))
            for row, i in enumerate(some):
                proposed[i] = (float(run_gaps[row].min()), [int(t) for t in run_tokens[row]])
    print(f"{len(proposed)} runs proposed at {time.time() - t0:.0f} s; smallest gaps' quartiles "
          f"{np.percentile([g for g, _ in proposed], [25, 50, 75]).round(4).tolist()}", file=sys.stderr)
    chosen = []
    for b in buckets:
        here = sorted((i for i in range(len(prompts)) if bucket_of(lengths[i]) == b), key=lambda i: -proposed[i][0])
        chosen += here[:-(-spec["count"] // len(buckets))]
    runs = [dict(prompt=prompts[i], tokens=list(proposed[i][1])) for i in chosen]
    unsettled = list(runs)
    for _ in range(n_new + 1):  # each pass settles at least one more token of every unsettled run
        moved = []
        for r in unsettled:
            row = after(r["prompt"] + r["tokens"][:-1], last=n_new)
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

    # -- what the init has to show: old state's share, write strengths past 1 --------------------------
    witnesses = {}
    if not ns.rehearsal:
        tokens = jnp.asarray(rng.integers(0, vocab, (1, 1024)), jnp.int32)
        for layer in [i for i in (0, 5) if i < hp["num_hidden_layers"] and not ref.is_attention(hp, i)]:
            witnesses[f"layer_{layer}"] = ref.layer_witnesses(params, tokens, hp, layer, 64)
        print(f"witnesses {witnesses} at {time.time() - t0:.0f} s", file=sys.stderr)

    with open(stem + ".serve_canary.json", "w") as f:
        json.dump(dict(teacher=teacher, runs=runs, witnesses=witnesses, config=config["name"],
                       traffic=ns.traffic, n_params=n_params, device=jax.devices()[0].device_kind,
                       seconds=round(time.time() - t0)), f)
    print(stem + ".serve_canary.json")


if __name__ == "__main__":
    main()
