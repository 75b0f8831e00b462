"""Continuous batching scheduler (models/serving.py).

Keystone: greedy output through the slot-admission engine is
token-exact with the plain one-shot engine on every request — the
hole-slot admission and the reuse of a slot over a retired request's KV
must be invisible to the math. Plus the VERDICT r4 #5 done-criteria: a
stream of N >> B mixed-length prompts sustains >= 0.8x the
homogeneous-batch rate, and a mid-decode weight hot-swap has a measured
latency and changes subsequent output.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.generation import (
    SamplingConfig,
    build_generate_fn,
    left_pad_prompts,
)
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.serving import ContinuousBatchingEngine


def _model(seq=256):
    return GPT(
        GPTConfig(
            vocab_size=64,
            max_seq_len=seq,
            num_layers=2,
            num_heads=2,
            head_dim=8,
            embed_dim=16,
            use_remat=False,
        )
    )


def _params(model, seed=0):
    return model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _reference_completions(model, params, prompts, sampling):
    """Plain engine, one prompt at a time (no cross-prompt padding)."""
    out = []
    for p in prompts:
        toks, mask = left_pad_prompts([p], pad_id=sampling.pad_id)
        fn = build_generate_fn(model, sampling, prompt_width=toks.shape[1])
        t, m, _ = fn(params, toks, mask, jax.random.PRNGKey(0))
        t, m = np.asarray(t)[0], np.asarray(m)[0]
        out.append([int(x) for x, keep in zip(t, m) if keep])
    return out


def _mixed_prompts(n, rng_seed=0, lo=3, hi=14, vocab=64):
    r = np.random.default_rng(rng_seed)
    return [
        [int(x) for x in r.integers(1, vocab, r.integers(lo, hi))]
        for _ in range(n)
    ]


class TestGreedyExactness:
    def test_stream_matches_plain_decode(self):
        """12 mixed-length prompts through 4 slots, greedy: every
        completion equals the plain engine's on that prompt."""
        model = _model(seq=256)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=10, temperature=0.0)
        prompts = _mixed_prompts(12)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=4, prompt_width=16,
            decode_chunk=4,
        )
        got = eng.run(prompts)
        assert [c.uid for c in got] == list(range(12))
        want = _reference_completions(model, params, prompts, sampling)
        for c, w in zip(got, want):
            assert c.tokens == w, f"uid {c.uid}: {c.tokens} != {w}"
            assert len(c.logprobs) == len(c.tokens)
            # service metrics: first token can't precede admission and
            # can't come after retirement; queue wait is non-negative
            assert 0.0 <= c.ttft_s <= c.total_s
            assert c.queue_s >= 0.0
        # later uids waited in the queue behind a full batch
        assert got[-1].queue_s > got[0].queue_s

    def test_exactness_in_a_tight_cache(self):
        """max_seq_len barely above one request's own need: ten
        requests through three slots, each admission over a retired
        row's KV and its parked done-row write; greedy parity holds."""
        model = _model(seq=48)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        prompts = _mixed_prompts(10, rng_seed=3)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=3, prompt_width=16,
            decode_chunk=4,
        )
        got = eng.run(prompts)
        want = _reference_completions(model, params, prompts, sampling)
        for c, w in zip(got, want):
            assert c.tokens == w, f"uid {c.uid}: {c.tokens} != {w}"

    def test_per_request_cap_is_a_greedy_prefix(self):
        """A request capped below the engine budget retires early and
        its tokens are exactly the prefix of the uncapped output."""
        model = _model(seq=256)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=10, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=8,
            decode_chunk=4,
        )
        full_uid = eng.submit([5, 9, 2])
        capped_uid = eng.submit([5, 9, 2], max_new_tokens=3)
        rng = jax.random.PRNGKey(0)
        while eng.pending:
            rng, sub = jax.random.split(rng)
            eng.step(sub)
        by_uid = {c.uid: c for c in eng.drain_completions()}
        full, capped = by_uid[full_uid], by_uid[capped_uid]
        assert len(full.tokens) == 10 and len(capped.tokens) == 3
        assert capped.tokens == full.tokens[:3]
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1], max_new_tokens=11)  # above the cache budget

    def test_eos_retires_slot_early(self):
        """A model whose greedy output hits eos frees the slot before
        max_new_tokens; the completion keeps the eos token."""
        model = _model(seq=256)
        params = _params(model)
        base = SamplingConfig(max_new_tokens=12, temperature=0.0)
        ref = _reference_completions(model, params, [[5, 9, 2]], base)[0]
        eos = ref[2]  # force an early stop at the 3rd greedy token
        sampling = SamplingConfig(
            max_new_tokens=12, temperature=0.0, eos_id=eos
        )
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=8,
        )
        (c,) = eng.run([[5, 9, 2]])
        assert c.tokens == ref[: ref.index(eos) + 1]


class TestThroughput:
    def test_mixed_stream_within_80pct_of_homogeneous(self):
        """VERDICT r4 #5 done-criterion: N >> B mixed-length prompts
        through one engine sustain >= 0.8x the same engine's
        homogeneous-batch tokens/s (same total decode work)."""
        model = _model(seq=512)
        params = _params(model)
        N_TOK = 24
        sampling = SamplingConfig(max_new_tokens=N_TOK, temperature=0.0)
        B = 4

        def run_engine(prompts):
            eng = ContinuousBatchingEngine(
                model, params, sampling, batch_size=B, prompt_width=16,
                decode_chunk=8,
            )
            eng.run(prompts[:B])  # warmup: compiles prefill+chunk
            # best-of-3: host-scheduling noise only ever slows a run,
            # and this ratio gates CI — both sides get the same trials
            best = 0.0
            for _ in range(3):
                t0 = time.perf_counter()
                out = eng.run(prompts)
                dt = time.perf_counter() - t0
                best = max(best, sum(len(c.tokens) for c in out) / dt)
            return best

        # homogeneous: every prompt identical length (no padding waste
        # even in a static batch) — the best case continuous batching
        # is allowed to approach
        homog = [[7] * 12 for _ in range(24)]
        mixed = _mixed_prompts(24, rng_seed=5, lo=3, hi=14)
        rate_h = run_engine(homog)
        rate_m = run_engine(mixed)
        if rate_m < 0.8 * rate_h:
            # Observed once in a full tier-1 run under box
            # oversubscription (PR 8): a noise burst landing on only
            # ONE side of the comparison defeats per-side best-of-3.
            # Re-measure BOTH sides in one fresh window so the pair
            # shares scheduling conditions; the ratio gate itself is
            # unchanged and still fails on a real regression.
            rate_h = run_engine(homog)
            rate_m = run_engine(mixed)
        assert rate_m >= 0.8 * rate_h, (rate_m, rate_h)


class TestShardedServing:
    def test_tp_sharded_stream_matches_single_device(self):
        """The whole scheduler SPMD over a tp mesh with trainer-held
        param shardings: greedy stream output token-exact with the
        single-device engine (the serve-a-bigger-model shape)."""
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.parallel.train_step import (
            default_optimizer,
            init_train_state,
        )

        model = _model(seq=256)
        mesh = build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
        state, sh = init_train_state(
            model, jnp.zeros((4, 8), jnp.int32), mesh, default_optimizer()
        )
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        prompts = _mixed_prompts(7, rng_seed=11)

        eng_s = ContinuousBatchingEngine(
            model, state.params, sampling, batch_size=3, prompt_width=16,
            decode_chunk=4, mesh=mesh,
        )
        got = eng_s.run(prompts)

        host_params = jax.tree.map(jnp.asarray, jax.device_get(state.params))
        eng_1 = ContinuousBatchingEngine(
            model, host_params, sampling, batch_size=3, prompt_width=16,
            decode_chunk=4,
        )
        want = eng_1.run(prompts)
        for c, w in zip(got, want):
            assert c.tokens == w.tokens, (c.uid, c.tokens, w.tokens)

        # a WeightBus push delivers HOST arrays; the swap must restore
        # the tp shardings, not collapse the model onto one device
        host_push = jax.tree.map(
            lambda x: np.asarray(x), jax.device_get(state.params)
        )
        lat = eng_s.set_params(host_push)
        assert lat > 0
        shardings = {
            str(leaf.sharding)
            for leaf in jax.tree.leaves(eng_s.params)
        }
        assert any("tp" in s for s in shardings), shardings
        got2 = eng_s.run(prompts)
        for c, w in zip(got2, want):
            assert c.tokens == w.tokens


class TestWeightSwap:
    def test_hot_swap_mid_decode(self):
        """WeightBus-style swap between chunks: measured latency, and
        the swapped weights actually take effect (output diverges from
        the unswapped run after the swap point)."""
        model = _model(seq=256)
        p1, p2 = _params(model, 0), _params(model, 1)
        sampling = SamplingConfig(max_new_tokens=16, temperature=0.0)

        def run(swap):
            eng = ContinuousBatchingEngine(
                model, p1, sampling, batch_size=2, prompt_width=8,
                decode_chunk=4,
            )
            eng.submit([5, 9, 2])
            rng = jax.random.PRNGKey(0)
            lat = None
            for i in range(64):
                rng, sub = jax.random.split(rng)
                eng.step(sub)
                if i == 1 and swap:
                    lat = eng.set_params(p2)
                if not eng.pending:
                    break
            (comp,) = eng.drain_completions()
            return comp.tokens, comp.logprobs, lat

        base_toks, base_lps, _ = run(swap=False)
        swap_toks, swap_lps, lat = run(swap=True)
        assert lat is not None and lat > 0
        assert len(swap_toks) == len(base_toks) == 16
        # first chunk (4 tokens, sampled before the swap) agrees ...
        assert swap_toks[:4] == base_toks[:4]
        np.testing.assert_allclose(
            swap_lps[:4], base_lps[:4], rtol=1e-5, atol=1e-6
        )
        # ... and the post-swap tail runs under DIFFERENT weights:
        # greedy argmax of a degenerate tiny model may coincide, but the
        # logprobs cannot
        assert not np.allclose(
            swap_lps[4:], base_lps[4:], rtol=1e-3, atol=1e-4
        )

    def test_async_swap_adopts_at_chunk_boundary(self):
        """set_params_async never blocks the scheduler: the transfer
        is enqueued, decode keeps stepping, and adoption lands at the
        first step() boundary after the transfer completes — which on
        the host backend is the very next step, making the output
        token-exact with a blocking swap at the same point."""
        import numpy as np

        model = _model(seq=256)
        p1, p2 = _params(model, 0), _params(model, 1)
        sampling = SamplingConfig(max_new_tokens=16, temperature=0.0)

        def run(swap_fn):
            eng = ContinuousBatchingEngine(
                model, p1, sampling, batch_size=2, prompt_width=8,
                decode_chunk=4,
            )
            eng.submit([5, 9, 2])
            rng = jax.random.PRNGKey(0)
            for i in range(64):
                rng, sub = jax.random.split(rng)
                eng.step(sub)
                if i == 1:
                    swap_fn(eng)
                if not eng.pending:
                    break
            (comp,) = eng.drain_completions()
            return comp.tokens, comp.logprobs, eng

        def swap_async(e):
            e.set_params_async(p2)
            # the payload is rounded on the device as it lands: wait for
            # that here (step() never does), so that adoption happens
            # at the top of step i=2 — the blocking swap's boundary
            jax.block_until_ready(e._pending_params)

        blk_toks, blk_lps, _ = run(lambda e: e.set_params(p2))
        asy_toks, asy_lps, eng = run(swap_async)
        assert asy_toks == blk_toks
        np.testing.assert_allclose(asy_lps, blk_lps, rtol=1e-5, atol=1e-6)
        # adoption bookkeeping: pending cleared, latency recorded
        assert eng.stats()["swap_pending"] is False
        assert eng.swap_latency_s is not None and eng.swap_latency_s > 0

class TestPerRowLayout:
    """cache_layout='per_row' (the default): every row writes at its
    own next slot (layers._update_decode_cache cache_slots scatter) — no
    admission holes past the prompt bucket. The paged-KV property vLLM
    gets from block tables, here from per-row slot reuse in a static
    [B, L] cache."""

    def test_liveness_is_per_request(self):
        """The liveness bound is per-request (prompt + budget), not
        stream-wide: a max_seq_len with room for one request's own
        slots serves a stream exactly; one without is rejected at
        construction."""
        model = _model(seq=32)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        kwargs = dict(
            batch_size=2, prompt_width=16, decode_chunk=4,
        )
        with pytest.raises(ValueError, match="liveness"):
            ContinuousBatchingEngine(
                model, params,
                SamplingConfig(max_new_tokens=17, temperature=0.0),
                **kwargs
            )
        eng = ContinuousBatchingEngine(model, params, sampling, **kwargs)
        prompts = _mixed_prompts(6, rng_seed=7)
        got = eng.run(prompts)
        want = _reference_completions(model, params, prompts, sampling)
        for c, w in zip(got, want):
            assert c.tokens == w, f"uid {c.uid}: {c.tokens} != {w}"

    @pytest.mark.slow  # ~16 s: the long-tail stress variant; slot
    # reuse over stale KV stays in tier-1 via TestGreedyExactness's
    # test_exactness_in_a_tight_cache + test_stream_matches_plain_decode
    def test_per_row_long_stream_slot_reuse_over_stale_kv(self):
        """N >> B through 2 slots: every admission rewrites a slot that
        carries a previous request's full KV + a parked done-row write;
        exactness proves the stale rows are fully invisible."""
        model = _model(seq=64)
        params = _params(model)
        sampling = SamplingConfig(
            max_new_tokens=6, temperature=0.0, eos_id=3
        )
        prompts = _mixed_prompts(20, rng_seed=9)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=16,
            decode_chunk=4, cache_layout="per_row",
        )
        got = eng.run(prompts)
        want = _reference_completions(model, params, prompts, sampling)
        for c, w in zip(got, want):
            assert c.tokens == w, f"uid {c.uid}: {c.tokens} != {w}"

    @pytest.mark.parametrize("layout", ["ragged", "frontier"])
    def test_rejects_unknown_layout(self, layout):
        model = _model(seq=256)
        with pytest.raises(ValueError, match="cache_layout"):
            ContinuousBatchingEngine(
                model, _params(model),
                SamplingConfig(max_new_tokens=4), batch_size=2,
                prompt_width=8, cache_layout=layout,
            )


class TestPrefixCaching:
    """Shared-prefix caching (vLLM's prefix-caching capability): a
    registered prefix's KV is computed once per weight version; each
    admission prefills only its suffix and continues from the stored
    row. The keystone: completions equal the plain engine's on the
    CONCATENATED prompt."""

    def test_prefix_completions_match_concatenated(self):
        model = _model(seq=256)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        prefix = [11, 23, 5, 42, 9]
        suffixes = [[7, 1], [3, 3, 8, 2], [19], [4, 4, 4, 4, 4, 4]]
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=16,
            decode_chunk=4,
        )
        pid = eng.register_prefix(prefix)
        for sfx in suffixes:
            eng.submit(sfx, prefix_id=pid)
        got = eng.run()
        want = _reference_completions(
            model, params, [prefix + s for s in suffixes], sampling
        )
        for c, w in zip(got, want):
            assert c.tokens == w, f"uid {c.uid}: {c.tokens} != {w}"

    def test_prefix_prefilled_once_across_requests(self):
        model = _model(seq=256)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=6, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=16,
            decode_chunk=4, cache_layout="per_row",
        )
        pid = eng.register_prefix([11, 23, 5, 42, 9, 8, 7])
        calls = {"prefill": 0}
        real_prefill = eng._prefill_fn

        def counting_prefill(*a, **k):
            calls["prefill"] += 1
            return real_prefill(*a, **k)

        eng._prefill_fn = counting_prefill
        for sfx in ([7, 1], [3, 3], [19], [2, 2, 2], [5], [6, 6]):
            eng.submit(sfx, prefix_id=pid)
        eng.run()
        # one full prefill (the prefix itself); every request paid only
        # the suffix-continuation program
        assert calls["prefill"] == 1

    def test_weight_swap_invalidates_prefix(self):
        model = _model(seq=256)
        p1, p2 = _params(model, 0), _params(model, 1)
        sampling = SamplingConfig(max_new_tokens=6, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model, p1, sampling, batch_size=2, prompt_width=16,
            decode_chunk=4, cache_layout="per_row",
        )
        pid = eng.register_prefix([11, 23, 5])
        eng.submit([7, 1], prefix_id=pid)
        eng.run()
        eng.set_params(p2)
        eng.submit([7, 1], prefix_id=pid)
        got = eng.run()
        want = _reference_completions(
            model, p2, [[11, 23, 5, 7, 1]], sampling
        )
        assert got[0].tokens == want[0]

    def test_prefix_validation(self):
        model = _model(seq=256)
        eng = ContinuousBatchingEngine(
            model, _params(model), SamplingConfig(max_new_tokens=4),
            batch_size=2, prompt_width=16,
        )
        with pytest.raises(ValueError, match="unknown prefix_id"):
            eng.submit([1, 2], prefix_id=99)
        with pytest.raises(ValueError, match="empty prefix"):
            eng.register_prefix([])
        with pytest.raises(ValueError, match="no room"):
            eng.register_prefix(list(range(16)))
        pid = eng.register_prefix(list(range(7)))  # bucket width 8
        with pytest.raises(ValueError, match="prompt_width"):
            eng.submit(list(range(9)), prefix_id=pid)
        with pytest.raises(ValueError, match="non-empty suffix"):
            eng.submit([], prefix_id=pid)

    def test_bucket_overflow_geometry_rejected(self):
        """Code-review regression (confirmed corruption): admission
        pads the suffix to its BUCKET width, so the capacity check must
        bound prefix bucket + suffix bucket, not the raw lengths —
        Pw=32 with a 7-token prefix (bucket 8) and a 17-token suffix
        (bucket 32) would admit a 40-slot row whose KV the decode
        writes then silently overwrite."""
        model = _model(seq=256)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model, _params(model), sampling, batch_size=2,
            prompt_width=32, decode_chunk=4,
        )
        pid = eng.register_prefix(list(range(1, 8)))  # bucket 8
        with pytest.raises(ValueError, match="bucket"):
            eng.submit(list(range(17)), prefix_id=pid)  # bucket 32
        # a suffix whose bucket fits is served exactly
        sfx = list(range(1, 9))  # bucket 8: 8 + 8 <= 32
        eng.submit(sfx, prefix_id=pid)
        got = eng.run()
        want = _reference_completions(
            model, _params(model), [list(range(1, 8)) + sfx], sampling
        )
        assert got[0].tokens == want[0]

    def test_prefix_bucket_rounding_rejected_at_register(self):
        """A prefix whose BUCKET rounds up to prompt_width must be
        rejected at registration, not at every later submit (code-
        review regression)."""
        model = _model(seq=256)
        eng = ContinuousBatchingEngine(
            model, _params(model), SamplingConfig(max_new_tokens=4),
            batch_size=2, prompt_width=32,
        )
        with pytest.raises(ValueError, match="bucket"):
            eng.register_prefix(list(range(17)))  # bucket 32 == Pw


class TestPagedLayout:
    """Paged KV-cache serving memory (models/kv_blocks.py): the block
    pool + per-request tables must be INVISIBLE to the math (bit-exact
    with the dense layout), shared prefix blocks must be freed and
    refcounted correctly, and pool exhaustion must degrade into the
    bounded queue path — never a wedge, never corruption."""

    def test_paged_matches_dense_layout(self):
        model = _model(seq=128)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        prompts = _mixed_prompts(6, rng_seed=5)

        def run(layout):
            eng = ContinuousBatchingEngine(
                model, params, sampling, batch_size=3, prompt_width=32,
                decode_chunk=4, cache_layout=layout, kv_block_size=16,
            )
            return eng, eng.run(prompts)

        eng_p, got = run("paged")
        _, want = run("per_row")
        for c, w in zip(got, want):
            assert c.tokens == w.tokens, f"uid {c.uid}"
            assert c.logprobs == w.logprobs, f"uid {c.uid}"
        # every retired row's blocks came back to the pool
        st = eng_p.stats()
        assert st["blocks_free"] == st["blocks_total"]

    def test_prefix_sharing_exact_and_blocks_recovered(self):
        """COW prefix sharing: fully-covered prefix blocks are shared
        (refcounted) across admissions, output equals the plain engine
        on the concatenated prompt, and unregistering the prefix after
        the run returns the pool to full."""
        model = _model(seq=128)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=6, temperature=0.0)
        prefix = list(range(1, 18))  # bucket 32 -> 4 shared 8-blocks
        suffixes = [[7, 1], [3, 3, 8, 2], [19], [4, 4, 4]]
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=64,
            decode_chunk=4, cache_layout="paged", kv_block_size=8,
        )
        pid = eng.register_prefix(prefix)
        for sfx in suffixes:
            eng.submit(sfx, prefix_id=pid)
        got = eng.run()
        want = _reference_completions(
            model, params, [prefix + s for s in suffixes], sampling
        )
        for c, w in zip(got, want):
            assert c.tokens == w, f"uid {c.uid}: {c.tokens} != {w}"
        st = eng.stats()
        assert st["prefix_hits"] >= len(suffixes) - 1
        # rows retired, but the registry still holds the shared blocks
        assert st["blocks_free"] == st["blocks_total"] - 4
        eng.unregister_prefix(pid)
        st = eng.stats()
        assert st["blocks_free"] == st["blocks_total"]

    def test_out_of_blocks_queues_never_wedges(self):
        """A pool too small for two concurrent worst-case rows: a
        burst of 10 requests must serialize through the block planner
        (head-of-queue waits for frees) and ALL complete exactly."""
        model = _model(seq=128)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        prompts = _mixed_prompts(10, rng_seed=7)
        # 7 blocks = 6 allocatable; worst-case request needs 5
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=3, prompt_width=32,
            decode_chunk=4, cache_layout="paged", kv_block_size=8,
            kv_pool_blocks=7,
        )
        got = eng.run(prompts)
        want = _reference_completions(model, params, prompts, sampling)
        assert len(got) == len(prompts)
        for c, w in zip(got, want):
            assert c.tokens == w, f"uid {c.uid}: {c.tokens} != {w}"
        st = eng.stats()
        assert st["blocks_free"] == st["blocks_total"] == 6

    def test_pool_too_small_for_one_request_rejected(self):
        model = _model(seq=128)
        with pytest.raises(ValueError, match="kv_pool_blocks"):
            ContinuousBatchingEngine(
                model, _params(model),
                SamplingConfig(max_new_tokens=8, temperature=0.0),
                batch_size=2, prompt_width=32, cache_layout="paged",
                kv_block_size=8, kv_pool_blocks=4,
            )
        with pytest.raises(ValueError, match="must divide"):
            ContinuousBatchingEngine(
                model, _params(model),
                SamplingConfig(max_new_tokens=8, temperature=0.0),
                batch_size=2, prompt_width=32, cache_layout="paged",
                kv_block_size=24,
            )

    def test_idle_prefix_evicted_under_pool_pressure(self):
        """With the pool sized so a registered-but-idle prefix's
        blocks are needed by a new admission, the LRU idle-prefix
        eviction must free them (prefix_evictions counts) and the
        request must complete — not queue forever."""
        model = _model(seq=128)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        # 10 blocks = 9 allocatable; the idle prefix registry holds 4
        # (bucket 32 / 8), and three concurrent short admissions need
        # 3 blocks each — the pool can't host all three without
        # reclaiming the idle prefix blocks
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=3, prompt_width=64,
            decode_chunk=4, cache_layout="paged", kv_block_size=8,
            kv_pool_blocks=10,
        )
        pid = eng.register_prefix(list(range(1, 18)))
        eng.submit([7, 1], prefix_id=pid)  # materialize shared blocks
        eng.run()
        assert eng.stats()["blocks_free"] == 5  # registry holds 4
        prompts = _mixed_prompts(3, rng_seed=9)
        got = eng.run(prompts)
        want = _reference_completions(model, params, prompts, sampling)
        for c, w in zip(got, want):
            assert c.tokens == w
        st = eng.stats()
        assert st["prefix_evictions"] >= 1
        assert st["blocks_free"] == st["blocks_total"]
        # the evicted prefix's ENCODING survives (only its idle blocks
        # were reclaimed): a later prefix request still serves exactly
        eng.submit([7, 1], prefix_id=pid)
        got2 = eng.run()
        want2 = _reference_completions(
            model, params, [list(range(1, 18)) + [7, 1]], sampling
        )
        assert got2[0].tokens == want2[0]

    def test_unregister_rejected_while_queued(self):
        model = _model(seq=128)
        eng = ContinuousBatchingEngine(
            model, _params(model),
            SamplingConfig(max_new_tokens=4, temperature=0.0),
            batch_size=1, prompt_width=16, cache_layout="paged",
            kv_block_size=8,
        )
        pid = eng.register_prefix([1, 2, 3])
        eng.submit([9])  # fills the single slot
        eng.submit([4], prefix_id=pid)  # queued behind it
        with pytest.raises(ValueError, match="queued"):
            eng.unregister_prefix(pid)
        with pytest.raises(KeyError):
            eng.unregister_prefix(999)
        eng.run()
        eng.unregister_prefix(pid)  # drained: now fine

    def test_prefill_handoff_roundtrip_exact(self):
        """Disaggregation plumbing: export_prefill on one engine,
        submit_prefilled on another (JSON round-trip — the payload
        crosses HTTP in production) equals a direct submit."""
        import json as _json

        model = _model(seq=128)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        prompt = [5, 9, 2, 44, 17]

        def make():
            return ContinuousBatchingEngine(
                model, params, sampling, batch_size=2, prompt_width=16,
                decode_chunk=4, cache_layout="paged", kv_block_size=8,
            )

        prefiller, decoder = make(), make()
        payload = _json.loads(
            _json.dumps(prefiller.export_prefill(prompt))
        )
        decoder.submit_prefilled(payload)
        got = decoder.run()
        want = _reference_completions(model, params, [prompt], sampling)
        assert got[0].tokens == want[0]
        st = decoder.stats()
        assert st["blocks_free"] == st["blocks_total"]

    def test_prefilled_payload_shape_mismatch_rejected(self):
        model = _model(seq=128)
        small = _model(seq=64)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        src = ContinuousBatchingEngine(
            model, _params(model), sampling, batch_size=2,
            prompt_width=16, cache_layout="paged", kv_block_size=8,
        )
        dst = ContinuousBatchingEngine(
            small, _params(small), sampling, batch_size=2,
            prompt_width=16, cache_layout="paged", kv_block_size=8,
        )
        payload = src.export_prefill([5, 9, 2])
        with pytest.raises(ValueError, match="shape"):
            dst.submit_prefilled(payload)


class TestCancellation:
    """vLLM-abort semantics: a cancelled request stops consuming
    capacity — queued entries drop, decoding slots free for the next
    admission — and the survivors stay token-exact."""

    def test_cancel_queued_and_inflight(self):
        model = _model(seq=256)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=12, temperature=0.0)
        prompts = _mixed_prompts(6, rng_seed=4)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=16,
            decode_chunk=4,
        )
        uids = [eng.submit(p) for p in prompts]
        rng = jax.random.PRNGKey(0)
        rng, sub = jax.random.split(rng)
        eng.step(sub)  # uids 0,1 decoding; 2..5 queued
        assert eng.cancel(uids[1]) is True  # in-flight
        assert eng.cancel(uids[3]) is True  # queued
        assert eng.cancel(999) is False
        while eng.pending:
            rng, sub = jax.random.split(rng)
            eng.step(sub)
        got = {c.uid: c.tokens for c in eng.drain_completions()}
        assert set(got) == {uids[0], uids[2], uids[4], uids[5]}
        want = _reference_completions(model, params, prompts, sampling)
        for i in (0, 2, 4, 5):
            assert got[uids[i]] == want[i], i

    def test_daemon_timeout_cancels(self):
        from dlrover_tpu.launcher.serve import ServingDaemon

        model = _model(seq=256)
        params = _params(model)
        # long budget: a 0-second client timeout fires long before
        # the completion can
        sampling = SamplingConfig(max_new_tokens=24, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=1, prompt_width=8,
            decode_chunk=2, cache_layout="per_row",
        )
        daemon = ServingDaemon(eng).start()
        try:
            import concurrent.futures

            with pytest.raises(concurrent.futures.TimeoutError):
                daemon.complete([5, 9, 2], timeout=0.01)
            # the abandoned request must eventually STOP consuming the
            # slot: the engine drains with no completion recorded
            deadline = time.time() + 30
            while time.time() < deadline and eng.pending:
                time.sleep(0.1)
            assert not eng.pending
            assert daemon.served == 0
            # capacity is actually free again: a new request completes
            c = daemon.complete([7, 1], timeout=120)
            assert len(c.tokens) == 24
        finally:
            daemon.stop()


class TestOverlappedPipeline:
    """The double-buffered scheduler round (overlap=True, the engine
    default): chunk N+1 dispatches before chunk N's tokens are read,
    with per-row cap/stop enforcement on the device. Keystones: the
    emitted stream is BIT-IDENTICAL to the synchronous round;
    cancellation and async weight swaps landing mid-overlap neither
    lose nor duplicate tokens."""

    def _run(self, overlap, prompts, caps=None, seq=256,
             max_new=10, model=None, params=None):
        model = model or _model(seq=seq)
        params = params if params is not None else _params(model)
        sampling = SamplingConfig(max_new_tokens=max_new, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=3, prompt_width=16,
            decode_chunk=4, overlap=overlap,
        )
        for i, p in enumerate(prompts):
            eng.submit(p, max_new_tokens=(caps or {}).get(i))
        out = eng.run()
        return out, eng

    def test_bit_identical_with_sync_round(self):
        """Mixed stream with per-request caps through both schedulers:
        every completion's tokens AND logprobs must match exactly —
        including rows the device-side budget stops mid-chunk."""
        model = _model(seq=256)
        params = _params(model)
        # narrow length range: the plain-engine reference compiles one
        # program per distinct prompt length
        prompts = _mixed_prompts(10, rng_seed=21, lo=4, hi=9)
        caps = {1: 3, 4: 7, 9: 1}  # device-side budget paths
        sync_out, _ = self._run(
            False, prompts, caps, model=model, params=params
        )
        ovl_out, eng = self._run(
            True, prompts, caps, model=model, params=params
        )
        assert [c.uid for c in ovl_out] == [c.uid for c in sync_out]
        for o, s in zip(ovl_out, sync_out):
            assert o.tokens == s.tokens, (o.uid, o.tokens, s.tokens)
            np.testing.assert_allclose(
                o.logprobs, s.logprobs, rtol=1e-6, atol=1e-7
            )
        # the pipeline actually ran overlapped
        assert eng.phases.split().overlap_s > 0.0
        assert not eng._inflight  # drained at stream end

    def test_device_side_cap_stops_rows_mid_flight(self):
        """A capped request's tokens are exactly the uncapped prefix
        even though the engine dispatched a further chunk before the
        host saw the cap hit (the one-chunk lag window)."""
        model = _model(seq=256)
        params = _params(model)
        prompts = [[5, 9, 2], [5, 9, 2]]
        out, _ = self._run(
            True, prompts, caps={1: 3}, model=model, params=params,
        )
        full, capped = out[0], out[1]
        assert len(full.tokens) == 10 and len(capped.tokens) == 3
        assert capped.tokens == full.tokens[:3]

    def test_cancel_mid_overlap_no_lost_or_leaked_tokens(self):
        """Cancel while a chunk is in flight: the freed slot's
        re-admitted request must start from ITS OWN first token (the
        uid snapshot drops the stale chunk's emissions), survivors
        stay exact, and no uid appears twice."""
        model = _model(seq=256)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        prompts = _mixed_prompts(6, rng_seed=4, lo=4, hi=9)
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=16,
            decode_chunk=4, overlap=True,
        )
        uids = [eng.submit(p) for p in prompts]
        rng = jax.random.PRNGKey(0)
        rng, sub = jax.random.split(rng)
        eng.step(sub)  # chunk 0 in flight for uids 0,1; 2..5 queued
        assert eng._inflight  # cancel lands mid-overlap
        assert eng.cancel(uids[1]) is True  # in-flight
        assert eng.cancel(uids[3]) is True  # queued
        while eng.pending:
            rng, sub = jax.random.split(rng)
            eng.step(sub)
        got = eng.drain_completions()
        seen = [c.uid for c in got]
        assert len(seen) == len(set(seen))  # no duplicates
        by_uid = {c.uid: c.tokens for c in got}
        assert set(by_uid) == {uids[0], uids[2], uids[4], uids[5]}
        want = _reference_completions(model, params, prompts, sampling)
        for i in (0, 2, 4, 5):
            assert by_uid[uids[i]] == want[i], i

    def test_async_swap_lands_at_drain_point(self):
        """An async swap landing mid-overlap adopts at the pipeline
        drain: output equals the blocking swap at the same point, no
        token is lost or doubled, and bookkeeping settles."""
        model = _model(seq=256)
        p1, p2 = _params(model, 0), _params(model, 1)
        sampling = SamplingConfig(max_new_tokens=16, temperature=0.0)

        def run(swap_fn):
            eng = ContinuousBatchingEngine(
                model, p1, sampling, batch_size=2, prompt_width=8,
                decode_chunk=4, overlap=True,
            )
            eng.submit([5, 9, 2])
            rng = jax.random.PRNGKey(0)
            for i in range(64):
                rng, sub = jax.random.split(rng)
                eng.step(sub)
                if i == 1:
                    swap_fn(eng)
                if not eng.pending:
                    break
            (comp,) = eng.drain_completions()
            return comp, eng

        def swap_async(e):
            e.set_params_async(p2)
            # the rounding of the payload is a device computation the
            # next step() would not wait for: the same boundary needs it
            jax.block_until_ready(e._pending_params)

        blk, _ = run(lambda e: e.set_params(p2))
        asy, eng = run(swap_async)
        assert len(blk.tokens) == 16 and asy.tokens == blk.tokens
        np.testing.assert_allclose(
            asy.logprobs, blk.logprobs, rtol=1e-5, atol=1e-6
        )
        assert eng.stats()["swap_pending"] is False
        assert eng.swap_latency_s is not None and eng.swap_latency_s > 0


class TestConstrainedDecoding:
    """Per-request allowed_tokens (RL action spaces / structured
    output): sampling and behavior logprobs come from the masked
    distribution; unconstrained rows in the same batch are unaffected."""

    @staticmethod
    def _masked_reference(model, params, prompt, allowed, n):
        """Greedy decode constrained to `allowed`, built directly on
        the decode contract (the one-shot engine has no mask arg)."""
        from dlrover_tpu.models.generation import (
            decode_apply,
            left_pad_prompts,
            prefill_prompt,
        )

        toks, mask = left_pad_prompts([prompt])
        cache, last, pos, kvv = prefill_prompt(
            model, params, toks, mask
        )
        L = model.config.max_seq_len
        V = model.config.vocab_size
        allow = np.zeros((V,), bool)
        allow[allowed] = True
        T0 = toks.shape[1]
        out = []
        for t in range(n):
            logits = np.array(last)[0]  # writable copy
            logits[~allow] = -np.inf
            tok = int(np.argmax(logits))
            out.append(tok)
            kvv = kvv | (jnp.arange(L)[None, :] == T0 + t)
            pos = pos + 1
            nxt, cache = decode_apply(
                model, params, cache,
                jnp.asarray([[tok]], jnp.int32), pos[:, None], kvv,
            )
            last = nxt[:, 0].astype(jnp.float32)
        return out

    def test_constrained_matches_masked_reference(self):
        model = _model(seq=256)
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        allowed = [3, 9, 17, 33, 40]
        prompt = [5, 9, 2]
        eng = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=8,
            decode_chunk=4,
        )
        # one constrained and one unconstrained request share the batch
        uid_c = eng.submit(prompt, allowed_tokens=allowed)
        uid_u = eng.submit(prompt)
        rng = jax.random.PRNGKey(0)
        while eng.pending:
            rng, sub = jax.random.split(rng)
            eng.step(sub)
        got = {c.uid: c for c in eng.drain_completions()}
        want_c = self._masked_reference(model, params, prompt, allowed, 8)
        assert got[uid_c].tokens == want_c
        assert all(t in allowed for t in got[uid_c].tokens)
        want_u = _reference_completions(model, params, [prompt], sampling)
        assert got[uid_u].tokens == want_u[0]
        # behavior logprobs are from the MASKED distribution: finite
        assert all(np.isfinite(got[uid_c].logprobs))

    def test_allowed_tokens_validation(self):
        model = _model(seq=256)
        eng = ContinuousBatchingEngine(
            model, _params(model), SamplingConfig(max_new_tokens=4),
            batch_size=2, prompt_width=8,
        )
        with pytest.raises(ValueError, match="empty"):
            eng.submit([1], allowed_tokens=[])
        with pytest.raises(ValueError, match="outside"):
            eng.submit([1], allowed_tokens=[999])


# -- the two bf16 cache leaves through the engine --------------------------


def _cache_family(name):
    """One-layer models by the leaf their decode cache stores: folded
    ``[B, L, lanes]`` where queries and keys have the same heads (25 x 64
    = 1600 lanes stored as 1664, 12 x 64 = 768 as they are, an ungrouped
    Llama's 4 x 8 = 32 as 128), ``[B, L, KVH, Hd]`` under grouping."""
    from dlrover_tpu.models.llama import Llama, LlamaConfig

    if name.startswith("gpt_"):
        heads, head_dim = (int(n) for n in name[4:].split("x"))
        return GPT(
            GPTConfig(
                vocab_size=64, max_seq_len=64, num_layers=1, embed_dim=32,
                num_heads=heads, head_dim=head_dim, use_remat=False,
            )
        )
    kv_heads = {"llama_ungrouped": 4, "llama_grouped": 2}[name]
    return Llama(
        LlamaConfig.tiny(
            vocab_size=64, max_seq_len=64, num_layers=1,
            num_kv_heads=kv_heads,
        )
    )


CACHE_FAMILIES = ["gpt_25x64", "gpt_12x64", "llama_ungrouped", "llama_grouped"]


class TestCacheLeavesThroughTheEngine:
    PREFIX = [11, 23, 5, 42, 9]
    SUFFIXES = [[7, 1], [3, 3, 8, 2]]

    def _stream(self, model, params, overlap=True):
        eng = ContinuousBatchingEngine(
            model, params, SamplingConfig(max_new_tokens=6, temperature=0.0),
            batch_size=3, prompt_width=16, decode_chunk=4, overlap=overlap,
        )
        prompts = _mixed_prompts(5, rng_seed=3, lo=4, hi=9)
        for p in prompts:
            eng.submit(p)
        pid = eng.register_prefix(self.PREFIX)
        for sfx in self.SUFFIXES:
            eng.submit(sfx, prefix_id=pid)
        whole = prompts + [self.PREFIX + sfx for sfx in self.SUFFIXES]
        return eng, eng.run(), whole

    @pytest.mark.parametrize("name", CACHE_FAMILIES)
    def test_stream_matches_plain_decode_and_the_uncached_forward(self, name):
        """Rows admitted at different positions (per-row slots), one-token
        steps after left-padded prefills, and suffixes continued onto a
        stored prefix (a T > 1 call over a filled cache): greedy tokens
        equal the plain engine's, and each token's log-probability the
        full forward's over prompt + completion."""
        model = _cache_family(name)
        params = _params(model)
        eng, got, whole = self._stream(model, params)
        leaves = [
            leaf for leaf in jax.tree.leaves(eng._state[0]) if leaf.ndim
        ]
        cfg = model.config
        if name == "llama_grouped":
            want_shape = (3, 64, cfg.num_kv_heads, cfg.head_dim)
        else:
            lanes = -(-cfg.num_heads * cfg.head_dim // 128) * 128
            want_shape = (3, 64, lanes)
        assert [leaf.shape for leaf in leaves] == [want_shape] * 2
        sampling = SamplingConfig(max_new_tokens=6, temperature=0.0)
        want = _reference_completions(model, params, whole, sampling)
        for c, prompt, w in zip(got, whole, want):
            assert c.tokens == w, f"uid {c.uid}: {c.tokens} != {w}"
            logits = model.apply(
                {"params": params}, jnp.asarray([prompt + c.tokens])
            ).astype(jnp.float32)
            logp = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
            full = [
                logp[len(prompt) - 1 + i, t] for i, t in enumerate(c.tokens)
            ]
            np.testing.assert_allclose(
                c.logprobs, full, rtol=2e-2, atol=2e-2
            )

    @pytest.mark.parametrize("name", CACHE_FAMILIES)
    def test_overlapped_round_is_bit_identical_with_the_sync_round(self, name):
        model = _cache_family(name)
        params = _params(model)
        _, sync, _ = self._stream(model, params, overlap=False)
        _, ovl, _ = self._stream(model, params, overlap=True)
        assert [(c.uid, c.tokens, c.logprobs) for c in ovl] == [
            (c.uid, c.tokens, c.logprobs) for c in sync
        ]


# -- the engine holds its matrices in the dtype the model computes in ------


def _family(name):
    from dlrover_tpu.models.llama import Llama, LlamaConfig

    if name == "gpt":
        return _model(seq=128)
    kw = dict(vocab_size=64, max_seq_len=128)
    if name == "llama_moe":
        kw.update(num_experts=4, moe_every=2)
    return Llama(LlamaConfig.tiny(**kw))


def _says_nothing(model):
    """The same model with no ``consumed_param_dtypes``: the engine serves
    it from the float32 tree, through the same programs — the parent
    commit's server, and ``decode_apply`` over the float32 tree itself."""
    import types

    return types.SimpleNamespace(
        config=model.config, apply=model.apply, init=model.init
    )


def _stream(model, params, overlap=True, swap=None, prompts=None):
    """(tokens, logprobs) of every request, in uid order; ``swap(eng)``
    runs once before the stream."""
    eng = ContinuousBatchingEngine(
        model, params, SamplingConfig(max_new_tokens=10, temperature=0.0),
        batch_size=3, prompt_width=16, decode_chunk=4, overlap=overlap,
    )
    if swap is not None:
        swap(eng)
    done = eng.run(prompts or _mixed_prompts(7, rng_seed=5))
    return [(c.tokens, c.logprobs) for c in done], eng


def _named(model, params):
    return jax.tree.leaves(
        jax.tree.map(
            lambda leaf, dt: leaf.dtype != dt,
            params, model.consumed_param_dtypes(params),
        )
    )


class TestHeldParams:
    @pytest.mark.parametrize("name", ["gpt", "llama", "llama_moe"])
    def test_logits_equal_the_float32_trees_bit_for_bit(self, name):
        """``decode_apply`` under jit, the float32 tree against the tree
        the engine holds: a prompt's pass and a one-token step give the
        same logits and the same cache in every bit."""
        from dlrover_tpu.models.generation import decode_apply, init_cache

        model = _family(name)
        params = _params(model)
        eng = ContinuousBatchingEngine(
            model, params, SamplingConfig(max_new_tokens=4, temperature=0.0),
            batch_size=2, prompt_width=8,
        )
        fn = jax.jit(
            lambda p, cache, toks, pos, kv, slots=None: decode_apply(
                model, p, cache, toks, pos, kv, cache_slots=slots
            )
        )
        L = model.config.max_seq_len
        toks = jnp.asarray([[3, 9, 4, 1, 7, 2], [5, 5, 8, 2, 6, 1]], jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
        kv = jnp.zeros((2, L), bool).at[:, :6].set(True)
        one_tok = jnp.asarray([[11], [12]], jnp.int32)
        one_pos = jnp.full((2, 1), 6, jnp.int32)
        kv1 = kv.at[:, 6].set(True)
        slots = jnp.full((2,), 6, jnp.int32)
        outs = []
        for tree in (params, eng.params):
            logits, cache = fn(tree, init_cache(model, 2), toks, pos, kv)
            step_logits, cache = fn(tree, cache, one_tok, one_pos, kv1, slots)
            outs.append(jax.tree.leaves((logits, step_logits, cache)))
        for want, got in zip(*outs):
            assert want.dtype == got.dtype
            assert np.asarray(want).tobytes() == np.asarray(got).tobytes()

    @pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlapped"])
    @pytest.mark.parametrize("name", ["gpt", "llama"])
    def test_stream_equals_the_float32_trees(self, name, overlap):
        """Greedy tokens and their log-probabilities through the engine
        equal, bit for bit, those of the same programs over the float32
        tree (the model that says nothing: served as given)."""
        model = _family(name)
        params = _params(model)
        got, eng = _stream(model, params, overlap)
        want, ref = _stream(_says_nothing(model), params, overlap)
        assert all(
            leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(ref.params)
        )
        assert any(
            leaf.dtype == jnp.bfloat16 for leaf in jax.tree.leaves(eng.params)
        )
        assert got == want  # lists of ints and of floats: exact

    @pytest.mark.parametrize("name", ["gpt", "llama", "llama_moe"])
    def test_held_tree_rounds_what_the_model_named_and_nothing_else(
        self, name
    ):
        model = _family(name)
        params = _params(model)
        eng = ContinuousBatchingEngine(
            model, params, SamplingConfig(max_new_tokens=4, temperature=0.0),
            batch_size=2, prompt_width=8,
        )
        named = _named(model, params)
        assert any(named) and not all(named)
        for given, held, is_named in zip(
            jax.tree.leaves(params), jax.tree.leaves(eng.params), named
        ):
            if is_named:
                assert given.dtype == jnp.float32
                assert held.dtype == model.config.dtype == jnp.bfloat16
                np.testing.assert_array_equal(
                    np.asarray(held), np.asarray(given.astype(jnp.bfloat16))
                )
            else:  # LayerNorm / RMSNorm, the router: the leaf itself
                assert held is given and held.dtype == jnp.float32
        # the float32 tree is the caller's still: nothing was consumed
        assert all(
            not leaf.is_deleted() and leaf.dtype == jnp.float32
            for leaf in jax.tree.leaves(params)
        )

    def test_gpt_names_every_matrix_and_llama_keeps_its_router(self):
        model = _family("gpt")
        params = _params(model)
        for leaf, is_named in zip(jax.tree.leaves(params), _named(model, params)):
            assert is_named or leaf.ndim == 1  # 99.97% of the bytes at XL
        moe = _family("llama_moe")
        dtypes = moe.consumed_param_dtypes(_params(moe))
        flat = {
            jax.tree_util.keystr(path): dt
            for path, dt in jax.tree_util.tree_leaves_with_path(dtypes)
        }
        routers = [k for k in flat if "w_router" in k]
        scales = [k for k in flat if "scale" in k]
        assert routers and scales
        assert all(flat[k] == jnp.float32 for k in routers + scales)

    def test_a_model_that_says_nothing_is_served_from_the_tree_as_given(self):
        model = _family("gpt")
        params = _params(model)
        eng = ContinuousBatchingEngine(
            _says_nothing(model), params,
            SamplingConfig(max_new_tokens=4, temperature=0.0),
            batch_size=2, prompt_width=8,
        )
        assert eng.params is params
        assert eng.stats()["params_device_bytes"] == sum(
            leaf.nbytes for leaf in jax.tree.leaves(params)
        )

    @pytest.mark.parametrize("how", ["set_params", "set_params_async"])
    @pytest.mark.parametrize("name", ["gpt", "llama"])
    def test_a_float32_payload_is_adopted_rounded(self, name, how):
        """A swap's payload arrives as a trainer or a WeightBus holds it
        (float32, host arrays); the engine adopts the rounded tree, and
        serves what an engine built from that payload serves."""
        model = _family(name)
        p1, p2 = _params(model, 0), _params(model, 1)
        payload = jax.tree.map(np.asarray, jax.device_get(p2))

        def swap(eng):
            getattr(eng, how)(payload)
            if how == "set_params_async":  # the probe never blocks
                assert eng.stats()["swap_pending"] is True
                jax.block_until_ready(eng._pending_params)
                assert eng.poll_pending_swap() is True
            assert eng.stats()["swap_pending"] is False

        got, eng = _stream(model, p1, swap=swap)
        want, built = _stream(model, p2)
        assert got == want
        for held, twin, is_named in zip(
            jax.tree.leaves(eng.params), jax.tree.leaves(built.params),
            _named(model, p2),
        ):
            assert held.dtype == twin.dtype
            assert (held.dtype == jnp.bfloat16) == is_named
            np.testing.assert_array_equal(np.asarray(held), np.asarray(twin))
        assert eng.stats()["params_casts"] == 2

    def test_a_payload_in_the_held_dtypes_is_adopted_as_it_is(self):
        model = _family("gpt")
        p1, p2 = _params(model, 0), _params(model, 1)
        want, built = _stream(model, p2)
        got, eng = _stream(
            model, p1, swap=lambda e: e.set_params(built.params)
        )
        assert got == want
        for held, given in zip(
            jax.tree.leaves(eng.params), jax.tree.leaves(built.params)
        ):
            assert held.dtype == given.dtype
            assert (
                held.unsafe_buffer_pointer() == given.unsafe_buffer_pointer()
            )

    def test_second_swap_before_adoption_supersedes_the_first(self):
        model = _family("gpt")
        p1, p2, p3 = (_params(model, s) for s in range(3))

        def swap(eng):
            eng.set_params_async(p2)
            eng.set_params_async(p3)
            assert eng.stats()["swap_pending"] is True
            jax.block_until_ready(eng._pending_params)  # the probe never blocks
            assert eng.poll_pending_swap() is True

        got, eng = _stream(model, p1, swap=swap)
        want, _ = _stream(model, p3)
        assert got == want
        # two trees were rounded, one was adopted
        assert eng.stats()["params_casts"] == 2
        assert eng.stats()["swap_pending"] is False

    def test_a_failed_swap_leaves_the_old_rounded_weights_serving(self):
        model = _family("gpt")
        p1 = _params(model, 0)
        broken = dict(_params(model, 1))
        broken.pop("wte")  # not the tree the programs take

        held = {}

        def swap(eng):
            held["before"] = jax.tree.leaves(eng.params)
            eng.set_params_async(broken)
            assert eng.stats()["swap_pending"] is False

        got, eng = _stream(model, p1, swap=swap)
        want, _ = _stream(model, p1)
        assert got == want
        stats = eng.stats()
        assert stats["swap_failures"] == 1 and stats["last_swap_error"]
        assert stats["params_casts"] == 1
        assert all(
            a is b for a, b in zip(held["before"], jax.tree.leaves(eng.params))
        )

    def test_held_leaves_keep_their_shardings_under_a_mesh(self):
        """Two CPU devices, tp=2: the rounded leaves sit where the
        trainer's float32 leaves sat, at start-up and after a swap whose
        payload is host arrays."""
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.parallel.train_step import (
            default_optimizer,
            init_train_state,
        )

        model = _model(seq=128)
        mesh = build_mesh(MeshConfig(dp=1, tp=2), jax.devices()[:2])
        state, _ = init_train_state(
            model, jnp.zeros((4, 8), jnp.int32), mesh, default_optimizer()
        )
        eng = ContinuousBatchingEngine(
            model, state.params, SamplingConfig(max_new_tokens=4, temperature=0.0),
            batch_size=2, prompt_width=8, mesh=mesh,
        )

        def check():
            split = 0
            for given, held, is_named in zip(
                jax.tree.leaves(state.params), jax.tree.leaves(eng.params),
                _named(model, state.params),
            ):
                assert held.sharding.is_equivalent_to(given.sharding, given.ndim)
                assert (held.dtype == jnp.bfloat16) == is_named
                split += is_named and not held.sharding.is_fully_replicated
            assert split  # some rounded leaf really is split over tp

        check()
        eng.set_params(jax.tree.map(np.asarray, jax.device_get(state.params)))
        assert eng.stats()["params_casts"] == 2
        check()


class TestTiledPrefill:
    """A multi-token call whose scores would be too many to hold
    (``layers.prefill_is_tiled``; the threshold lowered here to the
    test's own shapes, the model in float32) attends in tiles: a fresh
    row through the flash forward kernel over its own keys with the
    left pad turned out of sight, a continued row over the whole row a
    block of queries at a time. Both are the masked product: key by key
    in the row, and in the last logits."""

    WIDTH, SEQ = 32, 64

    @pytest.fixture()
    def lowered(self, monkeypatch):
        from dlrover_tpu.models import layers

        assert not layers.prefill_is_tiled(2048, 2560)  # every served program before PR 56
        assert layers.prefill_is_tiled(2048, 8704) and not layers.prefill_is_tiled(1, 1 << 30)
        return lambda most=1024: monkeypatch.setattr(layers, "_WHOLE_SCORES_MAX", most)

    def _model(self):
        import dataclasses

        model = _model(seq=self.SEQ)
        return GPT(dataclasses.replace(model.config, dtype=jnp.float32))

    def _batch(self, lengths):
        r = np.random.default_rng(5)
        return left_pad_prompts(
            [[int(x) for x in r.integers(1, 64, n)] for n in lengths]
            + [[0] * self.WIDTH], pad_id=0,
        )

    def test_fresh_rows_equal_the_masked_product(self, lowered):
        from dlrover_tpu.models.generation import prefill_prompt

        model = self._model()
        params = _params(model)
        toks, mask = self._batch([5, 19, 32, 1])
        toks, mask = toks[:4], mask[:4]  # the last row only set the width
        want = prefill_prompt(model, params, toks, mask)
        lowered()
        got = prefill_prompt(model, params, toks, mask)
        np.testing.assert_allclose(got[1], want[1], atol=2e-5)  # the last logits
        assert float(jnp.max(jnp.abs(want[1]))) > 0.1
        for a, b in zip(jax.tree.leaves(got[0]), jax.tree.leaves(want[0])):
            # every key and value of the row, padding included: layer 2's are
            # computed from layer 1's attention
            real = np.asarray(mask)[:, :, None]
            if a.ndim == 3:
                np.testing.assert_allclose(
                    np.where(real, a[:, : self.WIDTH], 0), np.where(real, b[:, : self.WIDTH], 0), atol=2e-5)
            else:
                assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(got[3]), np.asarray(want[3]))

    def test_engine_counts_tiled_calls_and_continues_a_prefix(self, lowered):
        model = self._model()
        params = _params(model)
        sampling = SamplingConfig(max_new_tokens=8, temperature=0.0)
        prefix = [11, 23, 5, 42, 9, 3, 3, 8, 2]
        suffixes = [[7, 1], list(range(1, 13)), [19] * 16]

        def run():
            eng = ContinuousBatchingEngine(
                model, params, sampling, batch_size=2, prompt_width=self.WIDTH, decode_chunk=4)
            pid = eng.register_prefix(prefix)
            for sfx in suffixes:
                eng.submit(sfx, prefix_id=pid)
            eng.submit(list(range(2, 30)))
            return [c.tokens for c in eng.run()], eng.stats()["phase_split"]

        want, counters = run()
        assert counters["prefill_tiled_calls_n"] == 0
        lowered(512)
        got, counters = run()
        assert got == want
        # the prefix's row is 16 wide; the requests' own calls are 8, 16 and 16
        # wide continuations and a fresh 32, over 64 positions against 512
        assert counters["prefill_tiled_calls_n"] == 3
        n = sampling.max_new_tokens
        lengths = [len(prefix) + len(s) for s in suffixes] + [28]
        assert counters["kv_positions_valid_n"] == sum(n * m + n * (n + 1) // 2 for m in lengths)
        assert counters["kv_positions_held_n"] == counters["row_steps_n"] * self.SEQ


class TestSeveralTokensAtPerRowSlots:
    """PR 59: ``layers._update_decode_cache(cache_slots=)`` takes ``T >= 1``
    tokens a row at slots ``[s_b, s_b + T)`` (a block's pass of a model
    decoded by blocks). Under the causal rule every other family has, such a
    call is its tokens one step at a time; the autoregressive engine builds
    the decode chunk it always built and its completions carry no passes."""

    def test_a_call_of_three_tokens_is_three_one_token_steps(self):
        from dlrover_tpu.models.generation import decode_apply, init_cache

        cfg = GPTConfig.tiny()
        model = GPT(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
        L, slots = cfg.max_seq_len, jnp.asarray([5, 9], jnp.int32)  # rows at different write slots
        kv = jnp.arange(L)[None, :] < slots[:, None]
        toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8 + 3)), jnp.int32)
        _, cache = decode_apply(  # something in the rows to attend to: a prefill of 8
            model, params, init_cache(model, 2), toks[:, :8], jnp.broadcast_to(jnp.arange(8), (2, 8)),
            jnp.broadcast_to(jnp.arange(L) < 8, (2, L)))
        kv3 = jnp.arange(L)[None, :] < slots[:, None] + 3
        pos3 = slots[:, None] + jnp.arange(3)[None, :]
        at_once, cache3 = decode_apply(model, params, cache, toks[:, 8:], pos3, kv3, cache_slots=slots)
        stepped = []
        for t in range(3):
            kv = kv | (jnp.arange(L)[None, :] == (slots + t)[:, None])
            logits, cache = decode_apply(model, params, cache, toks[:, 8 + t:9 + t], pos3[:, t:t + 1], kv,
                                         cache_slots=slots + t)
            stepped.append(logits[:, 0])
        np.testing.assert_allclose(at_once, jnp.stack(stepped, axis=1), atol=2e-5, rtol=0)
        for a, b in zip(jax.tree_util.tree_leaves(cache3), jax.tree_util.tree_leaves(cache)):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-5, rtol=0)
        with pytest.raises(ValueError, match="incompatible with"):
            decode_apply(model, params, cache, toks[:, 8:], pos3, kv3, cache_slots=slots[:1])

    def test_an_autoregressive_engine_is_as_it_was(self):
        cfg = GPTConfig.tiny()
        model = GPT(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        eng = ContinuousBatchingEngine(
            model, params, SamplingConfig(max_new_tokens=6, temperature=0.0), batch_size=2, prompt_width=8,
            decode_chunk=8)
        assert eng.blocks is None and eng.d == 8  # no rounding of the chunk to whole blocks
        eng.submit([3, 1, 4])
        (c,) = eng.run()
        assert len(c.tokens) == 6 and c.passes is None
        assert not any(k.startswith("block.") for k in eng.phases.split().summary())

    def test_a_streamed_line_a_final_block(self):
        from dlrover_tpu.launcher.serve import _lines_of, _passes_of
        from dlrover_tpu.models.layers import BlockDecoding
        from dlrover_tpu.models.serving import Completion

        blocks = BlockDecoding(4, 2, 99)
        new = list(range(10))
        assert _lines_of(new, 7, None) == [new]  # an autoregressive model: what a poll found, in one line
        assert _lines_of(new, 8, blocks) == [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]  # the cap cut the last block
        assert _lines_of(new, 9, blocks) == [[0, 1, 2], [3, 4, 5, 6], [7, 8, 9]]  # the prompt began the first
        assert _lines_of(new[:2], 11, blocks) == [[0], [1]] and _lines_of([], 3, blocks) == []
        assert _passes_of(Completion(1, [5], [-1.0])) == {}
        assert _passes_of(Completion(1, [5], [-1.0], passes=[0])) == {"passes": [0]}
