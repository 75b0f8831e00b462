"""``tpurun-serve`` — HTTP rollout server over the continuous engine.

The reference's serving story is "deploy vLLM next to the trainer"
(examples/unified/rl/openrlhf/ppo/main.py:26-60 upstream); this is the
TPU-native equivalent in one process: restore params from a flash
checkpoint (zero format conversion — the trainer's pytree IS the
serving pytree), stand up the continuous-batching scheduler
(models/serving.py), and serve completions over HTTP:

    POST /v1/completions        {"prompt": [ids...],
                                 "max_tokens": n?,
                                 "prefix_id": id?,
                                 "stream": bool?}           → completion, or
                                 chunked NDJSON token stream with a final
                                 done-line when "stream": true
    POST /v1/prefixes           {"tokens": [ids...]}        → {"prefix_id"}
                                (shared system prompt: prefilled once,
                                 reused by every request that names it)
    POST /v1/weights/reload     {}                          → hot-swap from
                                                              the ckpt dir
    GET  /healthz                                           → stats, incl. the
                                rolling per-request latency percentiles
                                (latency_p50_s/latency_p95_s) and tokens_per_s
                                the fleet gateway routes on, and replica_id
                                when run under a ReplicaSupervisor

The engine is single-threaded by design (one driver thread owns every
device call); HTTP handler threads talk to it through an inbox of
futures, so concurrent requests batch into the engine's decode slots
naturally — that IS continuous batching. To serve more than one
engine's slots — replica supervision, zero-downtime weight rollout,
autoscaling — run N of these behind ``tpurun-fleet``
(dlrover_tpu/fleet/, docs/serving_fleet.md).

Run (CPU smoke):
    tpurun-serve --cpu --port 8311
    curl -d '{"prompt": [5, 9, 2]}' localhost:8311/v1/completions
"""

import argparse
import inspect
import json
import os
import queue
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from http.server import ThreadingHTTPServer
from typing import Optional

from ..common.constants import ENV_KNOBS
from ..common.events import EventEmitter
from ..common.log import logger
from ..observability.spans import span, startup_span

# A round of the loaded engine is a decode chunk long (~0.1 s), a burst of
# prefills a few times that: an iteration this long is a standstill.
SLOW_ITERATION_S = 1.0

__all__ = ["ServingDaemon", "main"]


class ServingDaemon:
    """Driver thread that owns a ContinuousBatchingEngine: requests and
    weight swaps arrive through a thread-safe inbox, completions resolve
    futures. Start/stop lifecycle; safe to call from many threads.

    With the overlapped (default) engine round the driver tolerates a
    one-chunk emission latency by construction: ``engine.pending``
    stays true while a dispatched chunk's results are unread, so the
    loop keeps stepping until the pipeline tail drains; streaming
    ``partial()`` reads simply lag the device by one chunk; and a
    cancel between steps frees the slot while the in-flight chunk's
    tokens for it are dropped at the engine's uid-snapshot check."""

    def __init__(self, engine, rng_seed: int = 0):
        import jax

        self.eng = engine
        self._rng = jax.random.PRNGKey(rng_seed)
        self._inbox: "queue.Queue[tuple]" = queue.Queue()
        self._waiters = {}
        self._stream_uids = set()
        self._stream_done = {}
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self.served = 0
        self._thread = threading.Thread(
            target=self._loop, name="serving-driver", daemon=True
        )

    def start(self) -> "ServingDaemon":
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._thread.join(timeout)
        self._fail_all(RuntimeError("serving daemon stopped"))

    # -- client surface (any thread) -----------------------------------

    def _submit_item(
        self, kind: str, payload, timeout: float,
        cancel_on_timeout: bool = False,
    ):
        if self._stop.is_set():
            # the loop is gone; an enqueued future would never resolve
            raise RuntimeError("serving daemon stopped")
        fut: Future = Future()
        # stamped HERE, on the caller's thread: the driver picks the
        # item up only between two engine steps, and that wait is in no
        # later stamp (the engine's submit_t starts after it)
        self._inbox.put((kind, payload, fut, time.perf_counter()))
        try:
            return fut.result(timeout)
        except FutureTimeout:
            if cancel_on_timeout:
                self._inbox.put(
                    ("cancel_fut", fut, None, time.perf_counter())
                )
            raise

    def complete(
        self, prompt, timeout: float = 300.0, max_new_tokens=None,
        prefix_id=None, allowed_tokens=None,
    ):
        """Submit one prompt; block until its Completion arrives.
        With ``prefix_id``, ``prompt`` is the suffix after that
        registered prefix. On timeout the request is CANCELLED on the
        engine (vLLM-abort semantics): its queue entry is dropped or
        its decode slot freed, so an abandoned client stops consuming
        serving capacity."""
        return self._submit_item(
            "req", (list(prompt), max_new_tokens, prefix_id,
                    allowed_tokens),
            timeout, cancel_on_timeout=True,
        )

    def submit_streaming(
        self, prompt, max_new_tokens=None, prefix_id=None,
        allowed_tokens=None, *, timeout: float,
    ) -> int:
        """Submit WITHOUT blocking for the completion: returns the uid
        as soon as the driver enqueues the request. Pair with
        :meth:`partial` to stream tokens as they are emitted and with
        :meth:`result` to collect the final Completion. ``timeout`` bounds
        the wait for the loop to take the request and has no default: it
        is part of the request's one deadline (ROADMAP S0 (iv))."""
        return self._submit_item(
            "req_stream", (list(prompt), max_new_tokens, prefix_id,
                           allowed_tokens),
            timeout, cancel_on_timeout=True,
        )

    def partial(self, uid: int):
        """(tokens emitted so far, finished) for a streaming uid.
        Reads the driver-owned slot state under the GIL (list appends
        are atomic; a torn read only under-reports by one token, which
        the next poll delivers). finished=True once the Completion is
        collectable via :meth:`result`."""
        with self._mu:
            done = self._stream_done.get(uid)
        if isinstance(done, Exception):
            raise done  # the driver failed this stream: fail fast
        if done is not None:
            return list(done.tokens), True
        toks = self.eng.partial(uid)
        if toks is not None:
            return toks, False
        # not in a slot and not finished: still queued (or cancelled)
        return [], False

    def result(self, uid: int, timeout: float = 300.0):
        """Block for a streaming request's final Completion."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._mu:
                c = self._stream_done.pop(uid, None)
            if isinstance(c, Exception):
                raise c
            if c is not None:
                return c
            if self._stop.is_set():
                raise RuntimeError("serving daemon stopped")
            time.sleep(0.02)
        self.cancel(uid)
        raise FutureTimeout(f"streaming uid {uid} timed out")

    def cancel(self, uid: int, timeout: float = 30.0) -> bool:
        """Abort a request by uid (streaming clients that disconnect)."""
        try:
            return self._submit_item("cancel_uid", uid, timeout)
        except Exception as e:  # noqa: BLE001 — daemon stopping
            logger.debug("cancel of uid=%s not delivered: %r", uid, e)
            return False

    # The two prefix calls keep a minute of their own: control calls, with
    # no request and so no request's timeout to wait by.
    def register_prefix(self, tokens, timeout: float = 60.0) -> int:
        """Register a shared prompt prefix on the engine (computed
        lazily, invalidated by weight swaps)."""
        return self._submit_item("prefix", list(tokens), timeout)

    def unregister_prefix(self, prefix_id: int,
                          timeout: float = 60.0) -> bool:
        """Drop a registered prefix (fleet prefix GC). Raises KeyError
        for an unknown id, ValueError while queued requests still
        reference it."""
        return self._submit_item("unprefix", int(prefix_id), timeout)

    def export_prefill(self, tokens, timeout: float = 300.0):
        """Run the prompt's prefill on this engine and return the
        hand-off payload (prefill-role half of disaggregation)."""
        return self._submit_item("prefill_export", list(tokens), timeout)

    def complete_prefilled(
        self, payload, timeout: float = 300.0, max_new_tokens=None,
        allowed_tokens=None,
    ):
        """Decode-role half of disaggregation: admit a row prefilled
        elsewhere and block for its Completion."""
        return self._submit_item(
            "req_prefilled", (payload, max_new_tokens, allowed_tokens),
            timeout, cancel_on_timeout=True,
        )

    def swap_params(self, params, timeout: float = 300.0) -> float:
        """Hand new params to the driver; returns the measured swap
        latency once the driver adopts them between chunks."""
        return self._submit_item("params", params, timeout)

    def swap_params_async(self, params, timeout: float = 300.0) -> bool:
        """Non-blocking swap: the driver only ENQUEUES the H2D
        transfer (engine.set_params_async) and keeps decoding; the new
        weights land at the first chunk boundary after the transfer
        completes. The measured latency appears in the engine stats
        (``swap_latency_s``) once adopted."""
        return self._submit_item("params_async", params, timeout)

    # -- driver thread --------------------------------------------------

    def _drain_inbox(self, block: bool):
        try:
            item = self._inbox.get(timeout=0.1 if block else 0.0)
        except queue.Empty:
            return
        # the span covers the handling, not the idle wait above
        with span("serve.inbox"):
            self._handle_inbox(item)

    def _handle_inbox(self, item):
        while item is not None:
            kind, payload, fut, t_put = item
            try:
                if kind in ("req", "req_stream", "req_prefilled"):
                    # the request reaches the engine now: what it
                    # waited in the inbox behind a running step
                    self.eng.phases.count(
                        "inbox_wait_s", time.perf_counter() - t_put
                    )
                if kind == "req":
                    prompt, cap, prefix_id, allowed = payload
                    uid = self.eng.submit(
                        prompt, max_new_tokens=cap, prefix_id=prefix_id,
                        allowed_tokens=allowed,
                    )
                    with self._mu:
                        self._waiters[uid] = fut
                elif kind == "req_stream":
                    prompt, cap, prefix_id, allowed = payload
                    uid = self.eng.submit(
                        prompt, max_new_tokens=cap, prefix_id=prefix_id,
                        allowed_tokens=allowed,
                    )
                    with self._mu:
                        self._stream_uids.add(uid)
                    fut.set_result(uid)
                elif kind == "cancel_uid":
                    with self._mu:
                        self._waiters.pop(payload, None)
                        self._stream_uids.discard(payload)
                        self._stream_done.pop(payload, None)
                    fut.set_result(self.eng.cancel(payload))
                elif kind == "cancel_fut":
                    # payload IS the abandoned future (fut slot None).
                    # A plain completion's future is findable in
                    # _waiters; a streaming submit's future resolved
                    # with the uid at enqueue time (FIFO guarantees the
                    # req_stream item was processed before this one).
                    with self._mu:
                        uid = next(
                            (u for u, f in self._waiters.items()
                             if f is payload), None,
                        )
                        if uid is not None:
                            self._waiters.pop(uid, None)
                    if uid is None and payload.done():
                        r = payload.result()
                        if isinstance(r, int):
                            uid = r
                            with self._mu:
                                self._stream_uids.discard(uid)
                                self._stream_done.pop(uid, None)
                    if uid is not None:
                        self.eng.cancel(uid)
                elif kind == "req_prefilled":
                    pre_payload, cap, allowed = payload
                    uid = self.eng.submit_prefilled(
                        pre_payload, max_new_tokens=cap,
                        allowed_tokens=allowed,
                    )
                    with self._mu:
                        self._waiters[uid] = fut
                elif kind == "prefix":
                    fut.set_result(self.eng.register_prefix(payload))
                elif kind == "unprefix":
                    self.eng.unregister_prefix(payload)
                    fut.set_result(True)
                elif kind == "prefill_export":
                    fut.set_result(self.eng.export_prefill(payload))
                elif kind == "params":
                    fut.set_result(self.eng.set_params(payload))
                elif kind == "params_async":
                    self.eng.set_params_async(payload)
                    fut.set_result(True)
            except Exception as e:  # noqa: BLE001 — per-request failure
                if fut is not None:  # cancel items carry no future
                    fut.set_exception(e)
            try:
                item = self._inbox.get_nowait()
            except queue.Empty:
                item = None

    def _resolve(self, completions) -> None:
        """Hand finished requests to their futures or stream records."""
        for c in completions:
            with self._mu:
                fut = self._waiters.pop(c.uid, None)
                streaming = c.uid in self._stream_uids
                if streaming:
                    self._stream_uids.discard(c.uid)
                    self._stream_done[c.uid] = c
            if fut is not None:
                fut.set_result(c)
                self.served += 1
            elif streaming:
                self.served += 1

    def _log_slow_iteration(self, began, stepped, phases) -> None:
        """A server under load that stands still for a second or more
        (seen once in ~8 benchmark runs, cause not found: PERF.md section 7)
        says here where the time went: the inbox, the engine's round, and
        the round's own phases."""
        now, after = time.perf_counter(), self.eng.phases.totals()
        spent = {k: round(v - phases.get(k, 0.0), 3) for k, v in after.items()
                 if v - phases.get(k, 0.0) >= 0.001}
        logger.warning(
            "slow serving iteration: %.3f s (inbox %.3f, round %.3f; phases %s; %s)",
            now - began, stepped - began, now - stepped, spent,
            {k: self.eng.stats()[k] for k in ("busy_slots", "queue_depth", "inflight_chunks")},
        )

    def _fail_all(self, exc: Exception) -> None:
        """Resolve every in-flight and queued future with ``exc`` — a
        dead driver must fail fast, not leave clients blocking out
        their timeouts against a server whose /healthz still says OK."""
        with self._mu:
            waiters, self._waiters = self._waiters, {}
            # fail in-flight STREAMS fast too: park the exception where
            # partial()/result() will find (and raise) it
            for uid in self._stream_uids:
                self._stream_done[uid] = exc
            self._stream_uids.clear()
        for fut in waiters.values():
            if fut is not None and not fut.done():
                fut.set_exception(exc)
        while True:
            try:
                _, _, fut, _ = self._inbox.get_nowait()
            except queue.Empty:
                break
            if fut is not None and not fut.done():
                fut.set_exception(exc)

    def _loop(self):
        import jax

        while not self._stop.is_set():
            try:
                # when idle, block briefly on the inbox, don't spin
                busy = bool(self.eng.pending)
                began, phases = time.perf_counter(), self.eng.phases.totals()
                self._drain_inbox(block=not busy)
                stepped = time.perf_counter()
                if self.eng.pending:
                    self._rng, sub = jax.random.split(self._rng)
                    self.eng.step(sub)
                    if busy and time.perf_counter() - began > SLOW_ITERATION_S:
                        self._log_slow_iteration(began, stepped, phases)
                else:
                    # idle-server swap convergence: step() (which
                    # adopts landed async swaps at chunk boundaries)
                    # never runs while no request is live, so an async
                    # reload on an idle server would leave
                    # swap_pending=true forever without this poll
                    self.eng.poll_pending_swap()
                done = self.eng.drain_completions()
                if done:
                    with span("serve.complete", n=len(done)):
                        self._resolve(done)
            except Exception as e:  # noqa: BLE001 — driver must not die silently
                logger.exception("serving driver error: %s", e)
                self._fail_all(RuntimeError(f"serving driver error: {e!r}"))


# ---------------------------------------------------------------------------
# Checkpoint restore + model construction
# ---------------------------------------------------------------------------


def _init_params(model):
    """Smoke-mode weights, made in the dtypes the engine holds (one jitted
    init whose outputs are already rounded: ``models/build.py``), so that
    start-up's peak is the held tree and never a float32 one beside it."""
    import jax

    from ..models.build import init_params_as_consumed

    with span("serve.params_init") as sp:
        params = init_params_as_consumed(model, jax.random.PRNGKey(0))
        leaves = jax.tree_util.tree_leaves(params)
        sp.set(leaves=len(leaves), bytes=sum(leaf.nbytes for leaf in leaves))
    return params


_RESTORE_LOCK = threading.Lock()


def _restore_params(model, mesh, ckpt_dir: str):
    """Flash-checkpoint → the trainer's params pytree (float32, no
    conversion: the engine rounds what its model reads rounded as it
    takes them). Returns (step, params); the template and the rest of
    the restored state go at the return, so the caller's ``params`` is
    the only float32 tree left.

    - Template uses a STATELESS optimizer: ``_restore_into_template``
      only looks up the template's leaves, so skipping Adam moments in
      the template skips allocating (and restoring) 2x params of
      optimizer state the server would immediately discard.
    - Runs under a serve-private IPC namespace: the engine's shm
      segment is named per host rank within a namespace, and a
      colocated TRAINER owns that name in the job's namespace — the
      unlink here must never destroy the trainer's flash-checkpoint
      channel. The lock serializes concurrent reload requests.
    """
    import jax.numpy as jnp
    import optax

    from ..checkpoint.engine import CheckpointEngine
    from ..parallel.train_step import init_train_state

    tokens = jnp.zeros((1, 8), jnp.int32)
    with _RESTORE_LOCK:
        template, _ = init_train_state(model, tokens, mesh, optax.sgd(0.0))
        prev_ns = os.environ.get("DLROVER_IPC_NAMESPACE")
        os.environ["DLROVER_IPC_NAMESPACE"] = f"tpurun_serve_{os.getpid()}"
        engine = None
        try:
            engine = CheckpointEngine(ckpt_dir, mesh=mesh, standalone=True)
            step, restored = engine.load(template)
            if restored is None:
                raise FileNotFoundError(
                    f"no restorable checkpoint under {ckpt_dir}"
                )
            return step, restored.params
        finally:
            if engine is not None:
                engine.shm.unlink()
                engine.close()
            if prev_ns is None:
                os.environ.pop("DLROVER_IPC_NAMESPACE", None)
            else:
                os.environ["DLROVER_IPC_NAMESPACE"] = prev_ns


# ---------------------------------------------------------------------------
# HTTP front-end
# ---------------------------------------------------------------------------


def _passes_of(c) -> dict:
    """The done-line's ``passes`` of a model decoded by blocks (the pass of
    its block at which each token was fixed); nothing for any other."""
    return {} if c.passes is None else {"passes": c.passes}


def _lines_of(new, position: int, blocks) -> list:
    """What a streamed completion writes a line each of the tokens ``new``,
    the first of which stands at ``position`` of its sequence: all of them
    in one, or for a model decoded by blocks (``engine.blocks``) one line a
    final block: a first block the prompt began and a last one the cap cut
    are shorter lines."""
    if blocks is None:
        return [new]
    Bl = blocks.block_length
    cuts = [0] + list(range(Bl - position % Bl, len(new), Bl)) + [len(new)]
    return [new[a:b] for a, b in zip(cuts, cuts[1:]) if b > a]


def _make_handler(daemon: ServingDaemon, reload_fn, replica_id=None,
                  role="decode"):
    from ..common.http import JsonRequestHandler

    class Handler(JsonRequestHandler):
        def log_message(self, fmt, *args):  # route through our logger
            logger.debug("serve: " + fmt, *args)

        def do_GET(self):
            if self.path == "/healthz":
                from ..common.compile_cache import compile_records
                from ..common.platform import device_summary

                stats = daemon.eng.stats()
                self._send(
                    200,
                    {
                        "device": device_summary(),
                        # which fleet member answered (None outside a
                        # fleet) — the supervisor asserts identity on
                        # relaunch and operators read it in curl output
                        "replica_id": replica_id,
                        # prefill/decode disaggregation role (purely
                        # observability: the gateway derives routing
                        # roles from its own config)
                        "role": role,
                        "served": daemon.served,
                        "pending": daemon.eng.pending,
                        "slots": daemon.eng.B,
                        "prompt_width": daemon.eng.Pw,
                        "max_new_tokens": daemon.eng.s.max_new_tokens,
                        # top-level for scrapers: the host/device split
                        # headline (full per-phase table under
                        # stats.phase_split)
                        "serving_host_frac": (
                            stats.get("phase_split") or {}
                        ).get("serving_host_frac"),
                        # the last programs built, by name and seconds
                        # (common/compile_cache.py): one built under load
                        # is here, and in the log, with its time
                        "compiles": compile_records(),
                        **stats,
                    },
                )
            else:
                self._send(404, {"error": f"unknown path {self.path}"})

        def _stream_completion(self, prompt, max_tokens, prefix_id,
                               allowed, timeout):
            """NDJSON chunked streaming: one {"tokens": [...]} line per
            poll with NEW tokens, then a final line with the full
            completion + metrics. ANY socket failure (client gone,
            reset, timeout) cancels the request on the engine — a dead
            client must not keep consuming decode capacity. ``timeout``
            is the request's one deadline: its wait for the serving loop
            to take it counts (a loop that compiles two prefill widths
            inside one round is away for over a minute: ROADMAP S0 (iv))."""
            deadline = time.monotonic() + timeout
            try:
                uid = daemon.submit_streaming(
                    prompt, max_new_tokens=max_tokens,
                    prefix_id=prefix_id, allowed_tokens=allowed,
                    timeout=timeout,
                )
            except ValueError as e:
                self._send(400, {"error": repr(e)[:200]})
                return
            except Exception as e:  # noqa: BLE001
                logger.warning(
                    "streamed completion refused with 500: %r (%d prompt tokens)",
                    e, len(prompt),
                )
                self._send(500, {"error": repr(e)[:200]})
                return

            def chunk(obj):
                data = (json.dumps(obj) + "\n").encode()
                self.wfile.write(f"{len(data):x}\r\n".encode())
                self.wfile.write(data + b"\r\n")
                self.wfile.flush()

            sent = 0
            try:
                self.send_response(200)
                self.send_header(
                    "Content-Type", "application/x-ndjson"
                )
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                while time.monotonic() < deadline:
                    toks, finished = daemon.partial(uid)
                    if len(toks) > sent:
                        for new in _lines_of(
                            toks[sent:], len(prompt) + sent,
                            daemon.eng.blocks,
                        ):
                            chunk({"uid": uid, "tokens": new})
                        sent = len(toks)
                    if finished:
                        c = daemon.result(uid, timeout=5.0)
                        chunk({
                            "uid": c.uid,
                            "done": True,
                            "tokens": c.tokens,
                            "logprobs": c.logprobs,
                            **_passes_of(c),
                            "queue_s": round(c.queue_s, 4),
                            "ttft_s": round(c.ttft_s, 4),
                            "total_s": round(c.total_s, 4),
                        })
                        self.wfile.write(b"0\r\n\r\n")
                        self.wfile.flush()
                        return
                    time.sleep(0.02)
                daemon.cancel(uid)
                chunk({"uid": uid, "error": "timeout"})
                self.wfile.write(b"0\r\n\r\n")
            except OSError:
                daemon.cancel(uid)  # client hung up: free the slot
            except Exception as e:  # noqa: BLE001 — driver-side failure
                daemon.cancel(uid)
                try:
                    chunk({"uid": uid, "error": repr(e)[:200]})
                    self.wfile.write(b"0\r\n\r\n")
                except OSError:
                    pass

        def _complete_prefilled(self, body):
            """Decode-role admission of a row prefilled on another
            replica ({"prefilled": <hand-off payload>}). Shape
            mismatches (a payload from a different model config) are
            the CLIENT's 400, never a cache corruption."""
            payload = body.get("prefilled")
            if not isinstance(payload, dict):
                self._send(
                    400, {"error": "prefilled must be a hand-off payload"}
                )
                return
            max_tokens = body.get("max_tokens")
            if max_tokens is not None and (
                isinstance(max_tokens, bool)
                or not isinstance(max_tokens, int)
            ):
                self._send(400, {"error": "max_tokens must be int"})
                return
            try:
                c = daemon.complete_prefilled(
                    payload,
                    timeout=float(body.get("timeout", 300.0)),
                    max_new_tokens=max_tokens,
                    allowed_tokens=body.get("allowed_tokens"),
                )
            except (ValueError, KeyError) as e:  # bad payload: client
                self._send(400, {"error": repr(e)[:200]})
                return
            except Exception as e:  # noqa: BLE001 — server-side
                self._send(500, {"error": repr(e)[:200]})
                return
            self._send(
                200,
                {
                    "uid": c.uid,
                    "tokens": c.tokens,
                    "logprobs": c.logprobs,
                    "queue_s": round(c.queue_s, 4),
                    "ttft_s": round(c.ttft_s, 4),
                    "total_s": round(c.total_s, 4),
                },
            )

        def do_DELETE(self):
            try:
                body = self._body()
            except ValueError as e:
                self._send(400, {"error": f"bad json: {e}"})
                return
            if self.path == "/v1/prefixes":
                pid = body.get("prefix_id")
                if isinstance(pid, bool) or not isinstance(pid, int):
                    self._send(400, {"error": "prefix_id must be int"})
                    return
                try:
                    daemon.unregister_prefix(pid)
                except KeyError:
                    self._send(
                        404, {"error": f"unknown prefix_id {pid}"}
                    )
                    return
                except ValueError as e:  # still referenced by queue
                    self._send(409, {"error": repr(e)[:200]})
                    return
                except Exception as e:  # noqa: BLE001
                    self._send(500, {"error": repr(e)[:200]})
                    return
                self._send(200, {"removed": pid})
            else:
                self._send(404, {"error": f"unknown path {self.path}"})

        def do_POST(self):
            try:
                body = self._body()
            except ValueError as e:
                self._send(400, {"error": f"bad json: {e}"})
                return
            if self.path == "/v1/completions":
                if "prefilled" in body:
                    self._complete_prefilled(body)
                    return
                prompt = body.get("prompt")
                if not isinstance(prompt, list) or not all(
                    isinstance(t, int) for t in prompt
                ):
                    self._send(
                        400, {"error": "prompt must be a list of token ids"}
                    )
                    return
                max_tokens = body.get("max_tokens")
                if max_tokens is not None and (
                    isinstance(max_tokens, bool)
                    or not isinstance(max_tokens, int)
                ):
                    self._send(400, {"error": "max_tokens must be int"})
                    return
                stream = bool(body.get("stream", False))
                allowed = body.get("allowed_tokens")
                if allowed is not None and (
                    not isinstance(allowed, list)
                    or not all(isinstance(t, int) for t in allowed)
                ):
                    self._send(
                        400,
                        {"error": "allowed_tokens must be a list of ids"},
                    )
                    return
                prefix_id = body.get("prefix_id")
                if prefix_id is not None and (
                    isinstance(prefix_id, bool)
                    or not isinstance(prefix_id, int)
                ):
                    self._send(400, {"error": "prefix_id must be int"})
                    return
                if stream:
                    try:
                        stream_timeout = float(body.get("timeout", 300.0))
                    except (TypeError, ValueError):
                        self._send(400, {"error": "timeout must be a number"})
                        return
                    self._stream_completion(
                        prompt, max_tokens, prefix_id, allowed,
                        stream_timeout,
                    )
                    return
                try:
                    c = daemon.complete(
                        prompt,
                        timeout=float(body.get("timeout", 300.0)),
                        max_new_tokens=max_tokens,
                        prefix_id=prefix_id,
                        allowed_tokens=allowed,
                    )
                except ValueError as e:  # client-side: bad prompt
                    self._send(400, {"error": repr(e)[:200]})
                    return
                except Exception as e:  # noqa: BLE001 — server-side
                    self._send(500, {"error": repr(e)[:200]})
                    return
                self._send(
                    200,
                    {
                        "uid": c.uid,
                        "tokens": c.tokens,
                        "logprobs": c.logprobs,
                        **_passes_of(c),
                        "queue_s": round(c.queue_s, 4),
                        "ttft_s": round(c.ttft_s, 4),
                        "total_s": round(c.total_s, 4),
                    },
                )
            elif self.path == "/v1/prefill":
                # prefill-role half of disaggregation: run the
                # prompt's prefill here, return the hand-off payload
                # the decode replica admits via {"prefilled": ...}
                tokens = body.get("tokens")
                if not isinstance(tokens, list) or not all(
                    isinstance(t, int) for t in tokens
                ):
                    self._send(
                        400, {"error": "tokens must be a list of token ids"}
                    )
                    return
                try:
                    payload = daemon.export_prefill(tokens)
                except ValueError as e:
                    self._send(400, {"error": repr(e)[:200]})
                    return
                except Exception as e:  # noqa: BLE001
                    self._send(500, {"error": repr(e)[:200]})
                    return
                self._send(200, {"prefilled": payload})
            elif self.path == "/v1/prefixes":
                tokens = body.get("tokens")
                if not isinstance(tokens, list) or not all(
                    isinstance(t, int) for t in tokens
                ):
                    self._send(
                        400, {"error": "tokens must be a list of token ids"}
                    )
                    return
                try:
                    pid = daemon.register_prefix(tokens)
                except ValueError as e:
                    self._send(400, {"error": repr(e)[:200]})
                    return
                except Exception as e:  # noqa: BLE001
                    self._send(500, {"error": repr(e)[:200]})
                    return
                self._send(200, {"prefix_id": pid})
            elif self.path == "/v1/weights/reload":
                if reload_fn is None:
                    self._send(
                        400, {"error": "no --ckpt-dir to reload from"}
                    )
                    return
                swap_async = bool(body.get("async", False))
                try:
                    step, params = reload_fn()
                    swap = (daemon.swap_params_async if swap_async
                            else daemon.swap_params)
                    lat = swap(params)
                    # the engine holds its own (rounded) tree by now:
                    # the float32 one goes with this reference
                    del params
                except Exception as e:  # noqa: BLE001
                    self._send(500, {"error": repr(e)[:200]})
                    return
                if swap_async:
                    # decode keeps running; adoption lands at the first
                    # chunk boundary after the transfer — the measured
                    # latency then shows in /healthz last_swap_latency_s
                    self._send(200, {"step": step, "accepted": True})
                else:
                    self._send(
                        200, {"step": step, "swap_latency_s": round(lat, 4)}
                    )
            else:
                self._send(404, {"error": f"unknown path {self.path}"})

    return Handler


def serve(daemon: ServingDaemon, port: int, reload_fn=None,
          replica_id=None, role="decode"):
    """Bind and return the HTTP server (caller runs serve_forever)."""
    httpd = ThreadingHTTPServer(
        ("0.0.0.0", port),
        _make_handler(daemon, reload_fn, replica_id=replica_id, role=role),
    )
    return httpd


DEFAULT_CONFIG = dict(
    vocab_size=256, max_seq_len=512, num_layers=2, num_heads=4,
    head_dim=16, embed_dim=64, use_remat=False,
)


def main(argv=None) -> int:
    from ..models.build import FAMILIES, build_model

    ap = argparse.ArgumentParser(
        prog="tpurun-serve",
        description="rollout/serving daemon over the continuous engine",
    )
    ap.add_argument(
        "--family", choices=sorted(FAMILIES), default="gpt",
        help="model family, from the registry trainer and server share "
        "(models/build.py); one with no decode path is refused",
    )
    ap.add_argument(
        "--config", default="",
        help="model config as JSON (the fields of the family's config "
        "class); default is a small smoke model",
    )
    ap.add_argument(
        "--ckpt-dir", default="",
        help="flash ckpt to restore. The restore goes through a float32 "
        "template of the trainer's state: a model whose float32 "
        "parameters do not fit the device beside the tree the engine "
        "holds cannot be restored this way yet",
    )
    ap.add_argument("--port", type=int, default=8311)
    ap.add_argument(
        "--replica-id", type=int, default=None,
        help="fleet member id (set by the ReplicaSupervisor; tags "
        "/healthz so the gateway can assert replica identity)",
    )
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--prompt-width", type=int, default=64)
    ap.add_argument("--max-new-tokens", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument(
        "--decode-chunk", type=int, default=None,
        help="steps a decode chunk (passes, for a model decoded by "
        "blocks), taken as given. Default: the engine's, 8, and for a "
        "model decoded by blocks the next whole number of blocks (9 at "
        "2 denoising steps)")
    ap.add_argument(
        "--sync-round", action="store_true",
        help="serve with the host-serialized scheduler round (the "
        "pre-pipeline behavior; A/B and debugging). Default is the "
        "double-buffered overlapped round: chunk N+1 dispatches "
        "before chunk N's tokens are read, hiding host scheduling "
        "behind device execution at a one-chunk emission latency.",
    )
    ap.add_argument(
        "--kv-int8", action="store_true",
        help="int8 decode KV cache (halves cache HBM; lossy — see "
        "docs/generation.md)",
    )
    ap.add_argument(
        "--cache-layout", choices=["per_row", "paged"],
        default="per_row",
        help="per_row: a dense [slots, length] cache in which each "
        "request writes at its own next position (default). paged: "
        "block-pool KV with copy-on-write prefix sharing "
        "(docs/generation.md).",
    )
    ap.add_argument(
        "--kv-block-size", type=int,
        default=ENV_KNOBS["DLROVER_KV_BLOCK_SIZE"].get() or 16,
        help="paged layout: tokens per KV block (must divide the "
        "total sequence length)",
    )
    ap.add_argument(
        "--kv-pool-blocks", type=int,
        default=ENV_KNOBS["DLROVER_KV_POOL_BLOCKS"].get() or 0,
        help="paged layout: total pool blocks incl. the reserved "
        "trash block; 0 sizes the pool to the dense footprint",
    )
    ap.add_argument(
        "--role", choices=["prefill", "decode"], default="decode",
        help="disaggregation role tag reported on /healthz (prefill "
        "replicas answer /v1/prefill; decode replicas finish "
        "prefilled requests)",
    )
    ap.add_argument(
        "--cpu", action="store_true",
        help="pin the virtual CPU backend (local smoke)",
    )
    ns = ap.parse_args(argv)

    from ..attribution.recovery import (
        startup_from_process_start,
        write_startup_record,
    )
    from ..common.compile_cache import watch_compiles
    from ..common.platform import force_virtual_cpu, pin_accelerator

    import jax

    from ..models.generation import SamplingConfig
    from ..models.serving import ContinuousBatchingEngine
    from ..parallel.mesh import MeshConfig, build_mesh

    # Start-up names its own time (attribution/recovery.py): the phases
    # below partition this thread's time up to the listening socket. The
    # programs are built later, on first requests; those seconds are
    # measured by the compile listeners, one INFO line a program, and
    # ride with the phases in /healthz (``phase_split``, ``compiles``).
    startup_from_process_start("imports")
    with startup_span("backend"):
        if ns.cpu:
            force_virtual_cpu(1)
        else:
            # no hidden CPU: without --cpu (or a caller's own
            # JAX_PLATFORMS) a failed TPU initialization raises here
            # instead of serving from the host
            pin_accelerator()
        # listeners only: the cache's own options stay the caller's
        watch_compiles(quiet_after_startup=True)
        devices = jax.devices()

    with startup_span("build_model"):
        config = dict(
            DEFAULT_CONFIG if not ns.config else json.loads(ns.config)
        )
        if ns.kv_int8:
            config["kv_cache_int8"] = True
        model, _ = build_model({"family": ns.family, "config": config})
        if "decode" not in inspect.signature(model.__call__).parameters:
            ap.error(
                f"--family {ns.family} has no decode path: it cannot be served"
            )
        mesh = build_mesh(MeshConfig(dp=-1), devices[:1])

    reload_fn = None
    with startup_span("params"):
        if ns.ckpt_dir:
            reload_fn = lambda: _restore_params(  # noqa: E731
                model, mesh, ns.ckpt_dir
            )
            step, params = reload_fn()
            logger.info(
                "restored checkpoint step %s from %s", step, ns.ckpt_dir
            )
        else:
            params = _init_params(model)
            logger.warning(
                "no --ckpt-dir: serving RANDOM weights (smoke mode)"
            )

    with startup_span("engine"):
        sampling = SamplingConfig(
            max_new_tokens=ns.max_new_tokens,
            temperature=ns.temperature,
            top_k=ns.top_k,
            top_p=ns.top_p,
            eos_id=ns.eos_id,
        )
        engine = ContinuousBatchingEngine(
            model, params, sampling,
            batch_size=ns.batch_size,
            prompt_width=ns.prompt_width,
            decode_chunk=ns.decode_chunk,
            cache_layout=ns.cache_layout,
            overlap=not ns.sync_round,
            kv_block_size=ns.kv_block_size,
            kv_pool_blocks=ns.kv_pool_blocks,
        )
        # the engine holds the tree its programs read (the matrices
        # rounded to the compute dtype); a restored float32 one goes with
        # this reference
        del params
    with startup_span("listen"):
        daemon = ServingDaemon(engine).start()
        httpd = serve(daemon, ns.port, reload_fn, replica_id=ns.replica_id,
                      role=ns.role)
    write_startup_record(
        "server", {"replica_id": ns.replica_id, "port": ns.port},
        emitter=EventEmitter("serve"),
    )
    logger.info(
        "tpurun-serve on :%s — %s slots × %s new tokens, prompt width %s",
        httpd.server_address[1], ns.batch_size, ns.max_new_tokens,
        ns.prompt_width,
    )
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        daemon.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
