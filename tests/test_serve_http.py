"""tpurun-serve HTTP daemon (launcher/serve.py).

The vLLM-deployment-shaped surface: an HTTP server over the
continuous-batching engine. Concurrent client requests batch into the
engine's decode slots; greedy completions stay token-exact with the
one-shot engine; weight reload hot-swaps from a flash checkpoint.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.launcher.serve import ServingDaemon, serve
from dlrover_tpu.models.generation import (
    SamplingConfig,
    generate,
    left_pad_prompts,
)
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.serving import ContinuousBatchingEngine


def _model():
    return GPT(
        GPTConfig(
            vocab_size=64, max_seq_len=256, num_layers=2, num_heads=2,
            head_dim=8, embed_dim=16, use_remat=False,
        )
    )


def _params(model, seed=0):
    return model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture()
def server():
    model = _model()
    params = _params(model)
    sampling = SamplingConfig(max_new_tokens=6, temperature=0.0)
    eng = ContinuousBatchingEngine(
        model, params, sampling, batch_size=3, prompt_width=16,
        decode_chunk=4,
    )
    daemon = ServingDaemon(eng).start()
    httpd = serve(daemon, port=0)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield base, model, params, sampling, daemon
    httpd.shutdown()
    httpd.server_close()
    daemon.stop()


def _post(base, path, payload, timeout=120):
    req = urllib.request.Request(
        base + path,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class TestServeHttp:
    def test_concurrent_completions_are_greedy_exact(self, server):
        base, model, params, sampling, _ = server
        prompts = [[5, 9, 2], [3], [7, 7], [1, 2, 3, 4], [11]]

        results = {}

        def hit(i):
            status, out = _post(base, "/v1/completions", {
                "prompt": prompts[i]
            })
            results[i] = (status, out)

        threads = [
            threading.Thread(target=hit, args=(i,))
            for i in range(len(prompts))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)

        for i, p in enumerate(prompts):
            status, out = results[i]
            assert status == 200
            toks, mask = left_pad_prompts([p], pad_id=0)
            want, _, _ = generate(
                model, params, toks, mask, jax.random.PRNGKey(0), sampling
            )
            assert out["tokens"] == [int(t) for t in np.asarray(want)[0]]
            assert len(out["logprobs"]) == len(out["tokens"])
            assert out["total_s"] >= out["ttft_s"] >= 0.0

    def test_healthz_and_bad_requests(self, server):
        base = server[0]
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            h = json.loads(r.read())
        assert h["slots"] == 3 and "served" in h
        # malformed prompt → 400, not a wedged daemon
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base, "/v1/completions", {"prompt": "not-ids"})
        assert e.value.code == 400
        # prompt longer than prompt_width → 400 with the engine's error
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base, "/v1/completions", {"prompt": list(range(40))})
        assert e.value.code == 400
        # reload without a ckpt dir configured → 400
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(base, "/v1/weights/reload", {})
        assert e.value.code == 400

    def test_stopped_daemon_fails_fast(self):
        """A dead driver must fail requests immediately — not leave
        clients blocking out their full timeout."""
        model = _model()
        eng = ContinuousBatchingEngine(
            model, _params(model),
            SamplingConfig(max_new_tokens=4, temperature=0.0),
            batch_size=2, prompt_width=8,
        )
        daemon = ServingDaemon(eng).start()
        daemon.stop()
        import time

        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="stopped"):
            daemon.complete([1, 2], timeout=60.0)
        assert time.perf_counter() - t0 < 5.0

    def test_weights_reload_from_checkpoint(self, tmp_path):
        """Full serve-side loop: ckpt → daemon → completions → a NEW
        checkpoint lands → /v1/weights/reload hot-swaps it."""
        from dlrover_tpu.checkpoint.engine import CheckpointEngine
        from dlrover_tpu.launcher.serve import _restore_params
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
        from dlrover_tpu.parallel.train_step import (
            default_optimizer,
            init_train_state,
        )

        model = _model()
        mesh = build_mesh(MeshConfig(dp=-1), jax.devices()[:1])
        tokens = jnp.zeros((1, 8), jnp.int32)
        state, _ = init_train_state(
            model, tokens, mesh, default_optimizer()
        )
        ckpt_dir = str(tmp_path / "ckpt")
        eng_ck = CheckpointEngine(ckpt_dir, mesh=mesh, standalone=True)
        try:
            assert eng_ck.save_to_storage(1, state)
            assert eng_ck.wait_saving(timeout=120)
        finally:
            eng_ck.shm.unlink()
            eng_ck.close()

        step, params = _restore_params(model, mesh, ckpt_dir)
        assert step == 1
        sampling = SamplingConfig(max_new_tokens=4, temperature=0.0)
        engine = ContinuousBatchingEngine(
            model, params, sampling, batch_size=2, prompt_width=8,
            decode_chunk=4,
        )
        daemon = ServingDaemon(engine).start()
        reload_fn = lambda: _restore_params(model, mesh, ckpt_dir)  # noqa: E731
        httpd = serve(daemon, port=0, reload_fn=reload_fn)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            status, out = _post(base, "/v1/completions", {"prompt": [5, 9]})
            assert status == 200 and len(out["tokens"]) == 4
            status, out = _post(base, "/v1/weights/reload", {})
            assert status == 200
            assert out["step"] == 1 and out["swap_latency_s"] > 0
            # still serves identically after the swap (same weights)
            status, again = _post(
                base, "/v1/completions", {"prompt": [5, 9]}
            )
            assert status == 200
        finally:
            httpd.shutdown()
            httpd.server_close()
            daemon.stop()


class TestPrefixHttp:
    def test_register_and_complete_with_prefix(self, server):
        base, model, params, sampling, _ = server
        prefix = [11, 23, 5]
        suffix = [7, 1]
        status, r = _post(base, "/v1/prefixes", {"tokens": prefix})
        assert status == 200
        pid = r["prefix_id"]
        status, got = _post(
            base, "/v1/completions", {"prompt": suffix, "prefix_id": pid}
        )
        assert status == 200
        toks, mask = left_pad_prompts([prefix + suffix])
        want_t, want_m, _ = generate(
            model, params, toks, mask, jax.random.PRNGKey(0), sampling
        )
        want = [
            int(x) for x, keep in zip(np.asarray(want_t)[0],
                                      np.asarray(want_m)[0]) if keep
        ]
        assert got["tokens"] == want

    def test_prefix_validation_http(self, server):
        base = server[0]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "/v1/prefixes", {"tokens": "nope"})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "/v1/completions",
                  {"prompt": [1, 2], "prefix_id": 404})
        assert ei.value.code == 400


class TestHealthzStats:
    def test_healthz_reports_engine_stats(self, server):
        base = server[0]
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            h = json.loads(r.read())
        assert h["cache_layout"] == "per_row"
        assert h["busy_slots"] == 0 and h["queue_depth"] == 0
        assert h["registered_prefixes"] == 0
        assert h["kv_cache_int8"] is False

    def test_healthz_carries_startup_phases_and_compile_totals(self, server):
        """The start-up phases and the compile totals of the process ride
        in ``phase_split`` as counters (no key of theirs ends in ``_ms``:
        readers sum those into a round's total), and ``compiles`` lists the
        last programs by name."""
        from dlrover_tpu.common.compile_cache import watch_compiles
        from dlrover_tpu.observability.spans import (
            process_accumulator,
            startup_span,
        )

        base, *_, daemon = server
        watch_compiles(quiet_after_startup=True)
        acc = process_accumulator()
        closed = acc.startup_closed
        acc.startup_closed = False
        try:
            with startup_span("engine"):
                pass
        finally:
            acc.startup_closed = closed
        _post(base, "/v1/completions", {"prompt": [4, 8, 1, 6]})
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            h = json.loads(r.read())
        split = h["phase_split"]
        assert split["rounds"] > 0
        ours = {k: v for k, v in split.items()
                if k.startswith(("startup.", "compile."))}
        assert "startup.engine_s_sum" in ours
        assert ours["compile.programs_n"] >= 1
        assert ours["compile.backend_s_sum"] + ours.get(
            "compile.cache_read_s_sum", 0.0
        ) > 0
        assert not any(k.endswith("_ms") for k in ours)
        # the engine's own summary is what it was: phases in _ms, its
        # counters, and nothing of the start-up inside the accumulator
        own = daemon.eng.phases.split().summary()
        assert not any(k.startswith(("startup.", "compile.")) for k in own)
        # (read a moment later: a phase may have been booked meanwhile)
        assert {k for k in split if k.endswith("_ms")} <= {
            k for k in own if k.endswith("_ms")
        }
        assert isinstance(h["compiles"], list) and h["compiles"]
        assert {"fun_name", "unix_ns", "trace_s", "lower_s", "backend_s",
                "cache", "thread"} <= set(h["compiles"][-1])

    def test_healthz_exposes_attribution_breakdown(self, server):
        """/healthz carries the host/device split: the top-level
        serving_host_frac headline plus the per-phase table."""
        base, *_ = server
        _post(base, "/v1/completions", {"prompt": [5, 9, 2]})
        # the reply leaves when the request's last token is read; the
        # chunk dispatched behind it is read a moment later, and only a
        # chunk with nothing in flight behind it books "retirement"
        deadline = time.monotonic() + 30
        while True:
            with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
                h = json.loads(r.read())
            if "retirement_ms" in h["phase_split"] or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        assert "serving_host_frac" in h
        split = h["phase_split"]
        assert split["rounds"] > 0
        assert 0.0 < split["serving_host_frac"] < 1.0
        for phase in ("admission", "prefill", "decode_dispatch",
                      "host_sync", "retirement"):
            assert f"{phase}_ms" in split


class TestIdleSwap:
    def test_async_swap_converges_on_idle_server(self):
        """An async weight swap submitted while NO request is live must
        still be adopted (the driver polls adoption in its idle branch;
        before the fix swap_pending stayed true until the next request
        arrived — indefinitely on a quiet server)."""
        import time

        model = _model()
        p1, p2 = _params(model, 0), _params(model, 1)
        sampling = SamplingConfig(max_new_tokens=6, temperature=0.0)
        eng = ContinuousBatchingEngine(
            model, p1, sampling, batch_size=2, prompt_width=16,
            decode_chunk=4,
        )
        daemon = ServingDaemon(eng).start()
        try:
            assert not eng.pending  # idle from the start
            assert daemon.swap_params_async(p2) is True
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if not eng.stats()["swap_pending"]:
                    break
                time.sleep(0.05)
            assert eng.stats()["swap_pending"] is False
            assert eng.stats()["last_swap_latency_s"] > 0
        finally:
            daemon.stop()


class TestStreaming:
    def test_stream_tokens_arrive_incrementally(self, server):
        """stream=true: chunked NDJSON with partial token lines, then a
        final done-line equal to the non-streamed completion."""
        base, model, params, sampling, _ = server
        prompt = [5, 9, 2]
        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps({"prompt": prompt, "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        lines = []
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.headers.get("Content-Type") == "application/x-ndjson"
            for raw in r:
                raw = raw.strip()
                if raw:
                    lines.append(json.loads(raw))
        assert lines, "no stream lines"
        final = lines[-1]
        assert final.get("done") is True
        streamed = [t for ln in lines[:-1] for t in ln["tokens"]]
        # the final line carries the full sequence; incremental lines
        # must concatenate to its prefix (the last poll may batch the
        # tail into the done-line)
        assert streamed == final["tokens"][: len(streamed)]
        _, plain = _post(base, "/v1/completions", {"prompt": prompt})
        assert final["tokens"] == plain["tokens"]

    def test_stream_and_plain_interleave(self, server):
        """A streaming request and plain requests share the decode
        slots; both finish with exact outputs."""
        base = server[0]
        results = {}

        def plain(i):
            _, results[i] = _post(
                base, "/v1/completions", {"prompt": [7, 1, i]}
            )

        t = threading.Thread(target=plain, args=(2,))
        t.start()
        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps(
                {"prompt": [5, 9, 2], "stream": True}
            ).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            lines = [json.loads(x) for x in r if x.strip()]
        t.join(120)
        assert lines[-1]["done"] is True
        assert len(results[2]["tokens"]) == 6


class TestStreamedDeadline:
    """A streamed request has ONE deadline, its own ``timeout``, and the
    wait for the serving loop to take it off the inbox counts against it
    (ROADMAP S0 (iv): a loop that compiles two prefill widths inside one
    round is away for over a minute, and the fixed 60 s of that wait
    answered 500 to fourteen requests that had asked for 300). The loop is
    held where it picks an item up, by an event the test sets."""

    @staticmethod
    def _hold_the_loop(daemon, monkeypatch):
        gate, handle = threading.Event(), daemon._handle_inbox

        def held(item):
            assert gate.wait(30), "the test never let the loop go"
            handle(item)

        monkeypatch.setattr(daemon, "_handle_inbox", held)
        return gate

    @staticmethod
    def _stream(base, **body):
        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps({"prompt": [5, 9], "stream": True, **body}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, [json.loads(x) for x in r if x.strip()]

    def test_a_held_loop_costs_the_request_its_own_timeout_and_no_more(
        self, server, monkeypatch
    ):
        """``"timeout": 1`` against a held loop: the error answer comes
        while the loop is still held, at about 1 s (not a 200 once it is let
        go, from a clock started after the wait: the timer that lets it go
        after 6 s is only there for that case, and the test lets it go itself
        as soon as it has its answer), the request cancelled on the engine
        once the loop is back, and the refusal named in the server's log."""
        import logging

        base, daemon = server[0], server[-1]
        gate = self._hold_the_loop(daemon, monkeypatch)
        cancels, cancel = [], daemon.eng.cancel

        def cancel_and_note(uid):
            cancels.append(cancel(uid))
            return cancels[-1]

        monkeypatch.setattr(daemon.eng, "cancel", cancel_and_note)
        records = []
        handler = logging.Handler()
        handler.emit = records.append
        log = logging.getLogger("dlrover_tpu")
        log.addHandler(handler)
        let_go = threading.Timer(6.0, gate.set)
        let_go.start()
        try:
            t0 = time.monotonic()
            with pytest.raises(urllib.error.HTTPError) as e:
                self._stream(base, timeout=1)
            waited, still_held = time.monotonic() - t0, not gate.is_set()
            assert e.value.code == 500 and "TimeoutError" in json.loads(e.value.read())["error"]
            assert still_held and waited >= 0.9, (still_held, waited)
            gate.set()
            # the loop is back: it takes the request, then the cancel behind it
            until = time.monotonic() + 10
            while not cancels and time.monotonic() < until:
                time.sleep(0.02)
        finally:
            gate.set()
            let_go.cancel()
            log.removeHandler(handler)
        assert cancels == [True]
        stats = daemon.eng.stats()
        assert stats["busy_slots"] == 0 and stats["queue_depth"] == 0
        assert daemon.served == 0 and not daemon._stream_uids
        refused = [r.getMessage() for r in records if "refused with 500" in r.getMessage()]
        assert len(refused) == 1 and refused[0].endswith("(2 prompt tokens)"), refused
        assert refused[0].startswith("streamed completion refused with 500: TimeoutError")

    def test_submit_streaming_has_no_clock_of_its_own(self, server):
        daemon = server[-1]
        with pytest.raises(TypeError, match="timeout"):
            daemon.submit_streaming([5, 9])

    def test_a_long_timeout_outlasts_a_held_loop(self, server, monkeypatch):
        """The guard of the other direction: the loop held for a fraction
        of the request's timeout, then let go: 200 and the tokens asked."""
        base, daemon = server[0], server[-1]
        gate = self._hold_the_loop(daemon, monkeypatch)
        let_go = threading.Timer(1.0, gate.set)
        let_go.start()
        try:
            t0 = time.monotonic()
            status, lines = self._stream(base, timeout=30)
            waited = time.monotonic() - t0
        finally:
            gate.set()
            let_go.cancel()
        assert status == 200 and lines[-1]["done"] is True
        assert len(lines[-1]["tokens"]) == 6
        assert waited >= 0.9, waited
        _, plain = _post(base, "/v1/completions", {"prompt": [5, 9]})
        assert lines[-1]["tokens"] == plain["tokens"]


class TestConstrainedHttp:
    def test_allowed_tokens_over_http(self, server):
        """allowed_tokens forwards through the daemon payload on both
        the blocking and streaming paths; bad values 400."""
        base = server[0]
        allowed = [3, 9, 17]
        _, c = _post(
            base, "/v1/completions",
            {"prompt": [5, 9, 2], "allowed_tokens": allowed},
        )
        assert c["tokens"] and all(t in allowed for t in c["tokens"])
        req = urllib.request.Request(
            base + "/v1/completions",
            data=json.dumps({
                "prompt": [5, 9, 2], "allowed_tokens": allowed,
                "stream": True,
            }).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            lines = [json.loads(x) for x in r if x.strip()]
        assert lines[-1]["done"] is True
        assert lines[-1]["tokens"] == c["tokens"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "/v1/completions",
                  {"prompt": [1], "allowed_tokens": "nope"})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "/v1/completions",
                  {"prompt": [1], "allowed_tokens": []})
        assert ei.value.code == 400
