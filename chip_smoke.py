"""Quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call,
as child processes — one owner of the chip at a time, each exited before
the next starts. This parent never imports JAX (asserted at exit): a
parent that has touched JAX holds the chip, and its children then fail
or hang.

    python chip_smoke.py             # one chip: train phase, serve phase
    python chip_smoke.py --chips 4   # ONLY the four-chip path and what
                                     # it is compared with

*train*: ``tpurun --standalone --nnodes 1 scripts/smoke_train_worker.py``
with the default ``--profile auto``: GPT-2-small at full width (12 layers,
12 x 64 heads, 768 wide, vocabulary 50,304, sequence 1024, batch 32, bf16,
flash attention), a few steps, a flash checkpoint staged every step, one
SIGKILL of the worker, the agent's restart, a resume from the staged step.
*serve*: ``python -m dlrover_tpu.launcher.serve`` at GPT-2-small widths,
16 slots, answering a plain, a streamed and a burst of requests.

The LAST line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
with the device as the children saw it. Any phase that fails, any child
that saw another platform than required, any kernel in interpret mode
makes the exit code non-zero and the line ``"ok": false``. Where JAX
finds no accelerator at all (or the repo is not around this file) the
script exits non-zero and prints NO result line.

The earlier lines are smoke output — what one run showed, named for what
it is — never benchmark results. ``JAX_PLATFORMS=cpu`` together with
``--tiny`` is the rehearsal (and the tests): the same phases at a toy
size on the host; the device line then says ``cpu``, which is not a pass
on the chip.
"""

import argparse
import glob
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".smoke_work")
KEEP = os.path.join(ROOT, "chiprun_out", "chip_smoke")  # events and logs
EXIT_PHASE_FAILED = 1
EXIT_NO_ACCELERATOR = 3

GPT2_SMALL_WIDTHS = dict(
    vocab_size=50304, max_seq_len=1024, num_layers=12, num_heads=12,
    head_dim=64, embed_dim=768, use_remat=False,
)
TINY_WIDTHS = dict(
    vocab_size=256, max_seq_len=128, num_layers=2, num_heads=4,
    head_dim=8, embed_dim=32, use_remat=False,
)

# Products of the native Makefiles. ``profiler/native.py`` trusts any
# .so newer than its sources, and the chip tool copies the tree as it
# lies on disk: a product of some other machine's build would ride
# along. Build from the committed sources, here, every time.
NATIVE_PRODUCTS = (
    "native/tpu_timer/libtpu_timer.so",
    "native/tpu_timer/test_tpu_timer",
    "native/tpu_timer/test_tsan",
    "native/pjrt_interposer/libpjrt_interposer.so",
    "native/pjrt_interposer/libfake_pjrt_plugin.so",
    "native/pjrt_interposer/test_driver",
    "native/pjrt_interposer/libpjrt_interposer_tsan.so",
    "native/pjrt_interposer/libfake_pjrt_plugin_tsan.so",
    "native/pjrt_interposer/test_driver_tsan",
)


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def child_env(**extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


JOB = f"smoke_{os.getpid()}"  # names this run's sockets and segments


def shm_segments() -> set:
    return set(glob.glob("/dev/shm/dlrover_*"))


def stop_job(proc: subprocess.Popen) -> None:
    """Stop a tpurun we started and free what its job staged: the flash
    checkpoint outlives the agent by design (1.5 GB of /dev/shm at
    GPT-2-small), and nobody comes back for a smoke run's."""
    stop(proc)
    for path in glob.glob(f"/dev/shm/dlrover_{JOB}_*"):
        os.unlink(path)


def cache_entries() -> int:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_compile_cache"
    )
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def stop(proc: subprocess.Popen, grace_s: float = 20.0) -> None:
    """Stop a child we started (its whole session), by saved pid."""
    if proc.poll() is not None:
        return
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            return
        try:
            proc.wait(grace_s)
            return
        except subprocess.TimeoutExpired:
            continue


def read_events(path: str) -> list:
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def wait_event(path, pred, proc, timeout_s, what):
    """First event matching ``pred``; fails if ``proc`` exits first."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for ev in read_events(path):
            if pred(ev):
                return ev
        if proc.poll() is not None:
            raise PhaseFailed(
                f"tpurun exited rc={proc.returncode} before {what}"
            )
        time.sleep(0.2)
    raise PhaseFailed(f"timed out after {timeout_s:.0f}s waiting for {what}")


def tail(path: str, n: int = 40) -> str:
    if not os.path.exists(path):
        return ""
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def dump_logs(log_dir: str) -> None:
    """The ends of a failed phase's worker logs, to stderr."""
    for path in sorted(glob.glob(os.path.join(log_dir, "*"))):
        sys.stderr.write(f"--- {path}\n{tail(path)}\n")


# -- probe ------------------------------------------------------------------

_PROBE = (
    "import json; "
    "from dlrover_tpu.common.platform import device_summary; "
    "print(json.dumps(device_summary()))"
)


def probe_device(required: str) -> dict:
    """What JAX finds, asked in a child that exits before any phase. The
    child pins the required platform exactly as the workers will."""
    env = child_env()
    if required == "tpu" and not env.get("JAX_PLATFORMS"):
        env["JAX_PLATFORMS"] = "tpu"
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE],
        env=env, capture_output=True, text=True, timeout=300,
    )
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-3000:])
        return {}
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- train phase ------------------------------------------------------------

def tpurun(worker: str, events: str, log_dir: str, required: str,
           tiny: bool, seed: int, **env) -> subprocess.Popen:
    cmd = [
        sys.executable, "-m", "dlrover_tpu.launcher.elastic_run",
        "--standalone", "--nnodes", "1", "--max_restarts", "2",
        "--log_dir", log_dir,
        os.path.join(ROOT, "scripts", worker),
    ]
    say(f"$ {' '.join(cmd[1:])}")
    return subprocess.Popen(
        cmd,
        cwd=ROOT,
        env=child_env(
            SMOKE_EVENTS=events,
            SMOKE_REQUIRE_PLATFORM=required,
            SMOKE_TINY="1" if tiny else "0",
            SMOKE_SEED=seed,
            DLROVER_JOB_NAME=JOB,
            **env,
        ),
        start_new_session=True,
    )


def phase_train(required: str, tiny: bool, seed: int) -> dict:
    kill_after, total = 3, 8
    events = os.path.join(WORK, "train_events.jsonl")
    log_dir = os.path.join(WORK, "train_logs")
    entries_before = cache_entries()
    proc = tpurun(
        "smoke_train_worker.py", events, log_dir, required, tiny, seed,
        SMOKE_TOTAL_STEPS=total,
        SMOKE_HOLD_AT_STEP=kill_after,
        SMOKE_CKPT_DIR=os.path.join(WORK, "train_ckpt"),
    )
    budget = 240 if tiny else 900
    try:
        first = wait_event(
            events, lambda e: e["event"] == "device", proc, budget,
            "the first worker's device line",
        )
        say(
            f"train: worker pid {first['pid']} sees {first['count']} x "
            f"{first['platform']} ({first['kind']}); compile cache at "
            f"{first['cache_dir']}; interposer {first['interposed'] or 'off'}"
        )
        check(first["platform"] == required,
              f"worker saw {first['platform']!r}, required {required!r}")
        built = wait_event(
            events, lambda e: e["event"] == "built", proc, budget, "the build"
        )
        say(
            f"train: {built['model']} {built['n_params'] / 1e6:.1f}M params, "
            f"batch {built['batch']} x {built['seq']}, state "
            f"{built['state_bytes'] / 1e9:.2f} GB, /dev/shm free "
            f"{built['dev_shm_free_bytes'] / 1e9:.2f} GB, "
            f"tpu_custom_call in the step: {built['tpu_custom_call']}"
        )
        if required == "tpu":
            check(built["tpu_custom_call"],
                  "the flash kernel is not in the step (interpret mode?)")
        marker = wait_event(
            events,
            lambda e: e["event"] == "step" and e["step"] == kill_after,
            proc, budget, f"step {kill_after} staged",
        )
        t_kill = time.time()
        os.kill(marker["pid"], signal.SIGKILL)
        say(f"train: SIGKILL worker pid {marker['pid']} after step "
            f"{marker['step']} was staged")
        second = wait_event(
            events,
            lambda e: e["event"] == "device" and e["pid"] != first["pid"],
            proc, budget, "the restarted worker's device line",
        )
        check(second["platform"] == required,
              f"restarted worker saw {second['platform']!r}")
        done = wait_event(
            events,
            lambda e: e["event"] == "done" and e["pid"] == second["pid"],
            proc, budget, "the resumed run's end",
        )
        rc = proc.wait(120)
        check(rc == 0, f"tpurun exited rc={rc}")
    except PhaseFailed:
        dump_logs(log_dir)
        raise
    finally:
        stop_job(proc)

    evs = read_events(events)
    run1 = [e for e in evs if e["event"] == "step" and e["pid"] == first["pid"]]
    run2 = [e for e in evs if e["event"] == "step" and e["pid"] == second["pid"]]
    restored = [e for e in evs
                if e["event"] == "restored" and e["pid"] == second["pid"]][0]
    for tag, run in (("first start", run1), ("after resume", run2)):
        say(f"train: losses {tag}: " + ", ".join(
            f"[{e['step']}] {e['loss']:.4f}" for e in run))
    losses = [e["loss"] for e in run1 + run2]
    check(all(l == l and abs(l) != float("inf") for l in losses),
          "a loss is not finite")
    staged = marker["step"]
    resumed = restored["resumed_from"]
    say(f"train: staged step {staged} at the kill, resumed from step "
        f"{resumed} (restore {restored['restore_s']} s)")
    check(resumed == staged, f"resumed from {resumed}, staged {staged}")
    check(run2[0]["step"] == resumed + 1, "resume skipped or repeated a step")
    check(done["final_step"] == total,
          f"state.step {done['final_step']} != {total}: not one series")
    swing = max(abs(a - b) for a, b in zip(losses, losses[1:])) if len(losses) > 1 else 0
    check(abs(run2[0]["loss"] - run1[-1]["loss"]) <= max(0.5, 2 * swing),
          "the first loss after resume does not continue the series")
    say(f"train: kill to first resumed step: "
        f"{run2[0]['t'] - t_kill:.1f} s (smoke output)")
    say(f"train: first call {run1[0]['step_s']} s (compile + step), "
        f"steady step {done['steady_step_s']} s after warm-up; restarted "
        f"worker's first call {run2[0]['step_s']} s (smoke output)")
    say(f"train: first start read {run1[-1]['cache_hits']} programs from "
        f"the compile cache and compiled {run1[-1]['cache_misses']}")
    hit = run2[0]["cache_hits"] > 0
    say(f"train: second start hit the compile cache: {hit} "
        f"(hits {done['cache_hits']}, misses {done['cache_misses']}; "
        f"entries {entries_before} -> {cache_entries()})")
    if not tiny:  # tiny programs compile under the cache's 1 s floor
        check(hit, "the restarted worker recompiled instead of reading the cache")
    launches = sum(v for k, v in done["interposer_metrics"].items()
                   if "launches_total" in k)
    say(f"train: interposer /metrics: {done['interposer_metrics']}")
    if required == "tpu":
        check(second["interposed"], "default profile did not interpose")
        check(launches > 0, "the interposer counted no device executions")
    if done.get("bytes_limit"):
        say(f"train: device memory peak {done['peak_bytes_in_use'] / 2**30:.2f}"
            f" of {done['bytes_limit'] / 2**30:.2f} GiB")
    return {k: first[k] for k in ("platform", "kind", "count")}


# -- serve phase ------------------------------------------------------------

def http(method: str, url: str, body=None, timeout=600.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def phase_serve(required: str, tiny: bool) -> dict:
    port = free_port()
    widths = TINY_WIDTHS if tiny else GPT2_SMALL_WIDTHS
    cmd = [
        sys.executable, "-m", "dlrover_tpu.launcher.serve",
        "--port", str(port), "--config", json.dumps(widths),
        "--batch-size", "16", "--prompt-width", "32",
        "--max-new-tokens", "32", "--temperature", "0.0",
    ]
    if required == "cpu":
        cmd.append("--cpu")
    say(f"$ {' '.join(cmd[1:])}")
    before = shm_segments()
    log = os.path.join(WORK, "serve.log")
    with open(log, "w") as logf:
        proc = subprocess.Popen(
            cmd, cwd=ROOT, env=child_env(), stdout=logf,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
    base = f"http://127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + (180 if tiny else 600)
        while True:
            check(proc.poll() is None,
                  f"server exited rc={proc.returncode}:\n{tail(log)}")
            try:
                status, text = http("GET", base + "/healthz", timeout=5)
                break
            except OSError:
                check(time.monotonic() < deadline, "server never came up")
                time.sleep(0.5)
        health = json.loads(text)
        device = health["device"]
        say(f"serve: pid {proc.pid} sees {device['count']} x "
            f"{device['platform']} ({device['kind']}), {health['slots']} slots")
        check(device["platform"] == required,
              f"server saw {device['platform']!r}, required {required!r}")

        def complete(prompt, n, **kw):
            status, text = http(
                "POST", base + "/v1/completions",
                dict(prompt=prompt, max_tokens=n, **kw),
            )
            check(status == 200, f"completion status {status}")
            return text

        prompt = [5, 9, 2, 7]
        t0 = time.monotonic()
        a = json.loads(complete(prompt, 8))
        say(f"serve: plain completion, 8 tokens in "
            f"{time.monotonic() - t0:.1f} s incl. first compiles "
            f"(smoke output): {a['tokens']}")
        check(len(a["tokens"]) == 8, f"asked 8 tokens, got {len(a['tokens'])}")
        b = json.loads(complete(prompt, 8))
        check(a["tokens"] == b["tokens"], "greedy output differs between asks")
        lines = [json.loads(l) for l in
                 complete(prompt, 8, stream=True).strip().splitlines()]
        check(lines[-1].get("done") and lines[-1]["tokens"] == a["tokens"],
              f"streamed completion differs: {lines[-1]}")
        check(sum(len(l["tokens"]) for l in lines[:-1]) == 8,
              "streamed chunks do not add up to 8 tokens")
        say(f"serve: streamed completion in {len(lines) - 1} chunks, "
            f"equal to the plain one")
        asks = [4, 7, 10, 13, 16, 19]
        got = {}

        def one(n):
            got[n] = json.loads(complete([3, 1, 4, 1, 5, n], n))["tokens"]

        threads = [threading.Thread(target=one, args=(n,)) for n in asks]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        check(sorted(got) == asks and all(len(got[n]) == n for n in asks),
              f"burst: asked {asks}, got {({n: len(v) for n, v in got.items()})}")
        status, text = http("GET", base + "/healthz")
        health = json.loads(text)
        check(status == 200 and health["served"] >= 9,
              f"healthz after traffic: {status}, served {health.get('served')}")
        say(f"serve: burst of {len(asks)} answered with {asks} tokens; "
            f"served {health['served']}, overlap {health.get('overlap')}, "
            f"decode_chunk {health.get('decode_chunk')}")
    except PhaseFailed:
        sys.stderr.write(f"--- {log}\n{tail(log)}\n")
        raise
    finally:
        stop(proc)
    with socket.socket() as s:
        check(s.connect_ex(("127.0.0.1", port)) != 0,
              f"port {port} still open after the server was stopped")
    leaked = shm_segments() - before
    check(not leaked, f"/dev/shm segments left behind: {sorted(leaked)}")
    say(f"serve: stopped pid {proc.pid}; port {port} closed, no /dev/shm "
        f"segment left")
    return device


# -- four-chip phase --------------------------------------------------------

def phase_mesh(required: str, tiny: bool, seed: int) -> dict:
    events = os.path.join(WORK, "mesh_events.jsonl")
    log_dir = os.path.join(WORK, "mesh_logs")
    proc = tpurun(
        "smoke_mesh_worker.py", events, log_dir, required, tiny, seed,
        SMOKE_CKPT_DIR=os.path.join(WORK, "mesh_ckpt"),
        DLROVER_LOCAL_DEVICES=4,
    )
    try:
        rc = proc.wait(300 if tiny else 1500)
        evs = {e["event"]: e for e in read_events(events)}
        if rc != 0 or "done" not in evs:
            dump_logs(log_dir)
            raise PhaseFailed(f"tpurun exited rc={rc}; events {sorted(evs)}")
    except subprocess.TimeoutExpired:
        raise PhaseFailed("four-chip worker timed out")
    finally:
        stop_job(proc)
    device, losses = evs["device"], evs["losses"]
    say(f"mesh: ONE worker pid {device['pid']} sees {device['count']} x "
        f"{device['platform']} ({device['kind']}); mesh {losses['mesh']}")
    say(f"mesh: losses on devices[:1]: {losses['one_device']}")
    say(f"mesh: losses on four:        {losses['four_devices']}")
    say(f"mesh: max |difference| {losses['max_abs_diff']:.5f} within the "
        f"stated bf16 band {losses['band']}; tpu_custom_call in the steps: "
        f"{losses['tpu_custom_call']}")
    if required == "tpu":
        check(all(losses["tpu_custom_call"]), "a step ran without the kernel")
    place = evs["placement"]
    say(f"mesh: shards on four distinct devices: params "
        f"{place['params']['leaves']} leaves ({place['split_params']} "
        f"split, not copied), optimizer {place['opt_state']['leaves']}, "
        f"batch {place['batch']['leaves']}")
    rs = evs["reshard"]
    say(f"mesh: flash save under {rs['from_mesh']} restored under "
        f"{rs['to_mesh']}: {rs['leaves']} leaves equal to the host copy, "
        f"{rs['split_over_tp']} split over tp, {rs['reshard_s']} s "
        f"(smoke output)")
    return {k: device[k] for k in ("platform", "kind", "count")}


# -- main -------------------------------------------------------------------

def keep_records() -> None:
    """The children's events and logs (small) outlive the work dir, so a
    failed run on the chip can be read after the machine is gone."""
    shutil.rmtree(KEEP, ignore_errors=True)
    os.makedirs(KEEP)
    for path in glob.glob(os.path.join(WORK, "*.jsonl")) + glob.glob(
        os.path.join(WORK, "*.log")
    ) + glob.glob(os.path.join(WORK, "*_logs", "*")):
        if os.path.isfile(path) and os.path.getsize(path) < 4 << 20:
            shutil.copy(path, KEEP)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: ONLY the four-chip path and its comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the data")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at a toy size; needs JAX_PLATFORMS=cpu")
    ns = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "dlrover_tpu")):
        sys.stderr.write("chip_smoke: the repo is not around this file\n")
        return EXIT_NO_ACCELERATOR
    if ns.tiny and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.stderr.write("chip_smoke: --tiny is the CPU rehearsal; set "
                         "JAX_PLATFORMS=cpu\n")
        return 2
    required = "cpu" if ns.tiny else "tpu"
    if ns.tiny and ns.chips == 4:
        flag = "--xla_force_host_platform_device_count=4"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag
            ).strip()

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)

    found = probe_device(required)
    if found.get("platform") != required or found.get("count", 0) < ns.chips:
        sys.stderr.write(
            f"chip_smoke: JAX finds {found or 'no device'}; this run needs "
            f"{ns.chips} x {required}. No result.\n"
        )
        return EXIT_NO_ACCELERATOR
    if not ns.tiny:  # the rehearsal loads no plugin into a chip, and
        # the tests beside it share this checkout's native build; a run
        # that finds no chip has returned above and left that build alone
        for rel in NATIVE_PRODUCTS:
            path = os.path.join(ROOT, rel)
            if os.path.exists(path):
                os.unlink(path)
    say(f"probe: {found['count']} x {found['platform']} ({found['kind']})")

    t0 = time.monotonic()
    ok, device = True, found
    try:
        if ns.chips == 4:
            device = phase_mesh(required, ns.tiny, ns.seed)
        else:
            device = phase_train(required, ns.tiny, ns.seed)
            served = phase_serve(required, ns.tiny)
            check(served == device,
                  f"server's device {served} != worker's {device}")
        check(device["count"] == ns.chips,
              f"{device['count']} devices, this run is for {ns.chips}")
    except PhaseFailed as e:
        ok = False
        sys.stderr.write(f"chip_smoke: FAILED: {e}\n")
    finally:
        keep_records()
        shutil.rmtree(WORK, ignore_errors=True)
    say(f"total {time.monotonic() - t0:.0f} s")
    assert "jax" not in sys.modules, "the parent imported JAX"
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else EXIT_PHASE_FAILED


if __name__ == "__main__":
    sys.exit(main())
