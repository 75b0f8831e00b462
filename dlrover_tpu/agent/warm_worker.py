"""Warm-spare worker: a pre-imported interpreter that waits for the
env contract, then becomes the trainer.

Elastic MTTR is dominated by worker boot: every restart pays a fresh
CPython start plus the jax/flax/optax import tax (~3 s) BEFORE any
product code runs. The reference keeps its *agent* warm and cold-starts
trainers (torch-elastic semantics); on TPU a membership change restarts
the worker on EVERY re-mesh, so this runtime keeps one warm spare per
agent: spawned ahead of need with the heavy imports done, blocked on a
single stdin line. When a (re)start happens the agent writes the
dynamic env (rendezvous round's coordinator/rank/world) as one JSON
line; the spare applies it and ``runpy``-runs the user script as
``__main__``.

Safe because nothing here initializes a JAX *backend*: platform
selection and ``jax.distributed`` happen inside the user script (via
``elastic_context``/``force_virtual_cpu``), and jax config stays
mutable until backend init. The spare must therefore never touch
``jax.devices()`` — importing is free, initializing is binding.
"""

import json
import os
import runpy
import sys


def main() -> int:
    # The import tax, paid while the PREVIOUS worker is still training.
    import importlib

    for mod in ("jax", "jax.numpy", "flax", "optax", "numpy"):
        try:
            importlib.import_module(mod)
        except ImportError:
            pass
    # Pre-apply the shared compile cache (safe: config stays mutable
    # until backend init, which the spare never triggers) so even this
    # setup cost is paid before the handoff.
    from dlrover_tpu.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Tell the agent we are ready (it may wait to avoid racing a
    # half-imported spare into a rendezvous round). The marker is a
    # file because stdout is usually redirected into the worker log.
    ready_file = os.environ.get("DLROVER_WARM_READY_FILE")
    if ready_file:
        with open(ready_file, "w") as f:
            f.write(str(os.getpid()))
    print("WARM_WORKER_READY", flush=True)

    line = sys.stdin.readline()
    if not line.strip():
        return 0  # agent closed the pipe: spare no longer needed
    # For the start-up record this process begins now, at the hand-off: the
    # spare's own start and imports were paid while another worker trained.
    from dlrover_tpu.attribution.recovery import restart_startup_clock

    restart_startup_clock()
    contract = json.loads(line)
    os.environ.update({k: str(v) for k, v in contract["env"].items()})
    entrypoint = contract["entrypoint"]
    argv = [entrypoint] + list(contract.get("args", []))
    sys.argv = argv
    if contract.get("run_module"):
        runpy.run_module(entrypoint, run_name="__main__", alter_sys=True)
    else:
        runpy.run_path(entrypoint, run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
