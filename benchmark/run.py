"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name, from
``BENCHMARK.json``: the cell's configuration (``benchmark/configs/``), its
traffic mix (``benchmark/traffic/<traffic>.json``, which names its driver
under ``benchmark/drivers/``) and one reader a metric under
``benchmark/end_to_end/`` and ``benchmark/layer_metrics/``. Nothing here
names a cell, a model or a metric.

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``. With
``--trace 0`` the metrics are the cell's end-to-end ones, with ``--trace 1``
its per-layer ones. A run that does not find the chips its cell asks for
prints no result and exits non-zero; nothing falls back to the CPU. A run
that fails leaves ``FAILED.txt`` (the reason, then the tail of each of its
logs) under ``chiprun_out/benchmark/<cell>.seed<n>.trace<t>/``.

``--rehearse`` (tests only) runs the same path on the host at the tiny size
the configuration and the traffic file carry under ``rehearsal``; its line
has the single key ``cpu_rehearsal`` and no metric.
"""

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
import types

T_START = time.time()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.harness import RunFailed  # noqa: E402

EXIT_FAILED = 1
EXIT_NO_ACCELERATOR = 3


def load_reader(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise RunFailed(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(cell: str, entries: list, kind: str, ctx) -> dict:
    """Each metric of the cell from its own reader. A reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = load_reader(kind, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size: no metric is printed")
    ns = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "dlrover_tpu")):
        sys.stderr.write("benchmark: the system under test is not around this checkout\n")
        return EXIT_NO_ACCELERATOR
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if ns.workload not in cells:
        sys.stderr.write(f"benchmark: no cell {ns.workload!r}; have {sorted(cells)}\n")
        return 2
    cell = cells[ns.workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = harness.load_json(os.path.join(ROOT, conf_entry["file"]))
    traffic = harness.load_json(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    if ns.rehearse:
        config = harness.merge(config, config.get("rehearsal", {}))
        traffic = harness.merge(traffic, traffic.get("rehearsal", {}))
    seconds = ns.seconds if ns.seconds is not None else bench["run_seconds"]

    shutil.rmtree(harness.WORK, ignore_errors=True)
    os.makedirs(harness.WORK)
    run = types.SimpleNamespace(
        cell=cell["name"], chips=cell["chips"], config=config, traffic=traffic,
        seed=ns.seed, seconds=float(seconds), trace=bool(ns.trace),
        platform="cpu" if ns.rehearse else "tpu", rehearse=ns.rehearse,
        work=harness.WORK, t_start=T_START, deadline_s=1100.0,
    )
    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    try:
        result = driver.run(run)
        device = result["device"]
        if device["platform"] != run.platform or device["count"] < run.chips:
            raise RunFailed(f"ran on {device}, the cell asks for {run.chips} x {run.platform}")
        ctx = types.SimpleNamespace(
            run=run, stamps=result["stamps"], config=config, traffic=traffic,
            device=device, setup_s=result["t_open"] - T_START, trace=None,
            peaks=None if ns.rehearse else peaks_for(device["kind"]),
        )
        if run.trace and result.get("trace_dir"):
            from benchmark import reduce_trace

            ctx.trace = reduce_trace.load(result["trace_dir"])
            if ctx.trace is not None:
                device.update(ctx.trace.busy_and_window(run.chips))
                result.setdefault("checks", {})["trace_events"] = ctx.trace.events  # what the stop had to write
        kind = "layer_metrics" if run.trace else "end_to_end"
        metrics = metrics_of(run.cell, bench["per_layer" if run.trace else "end_to_end"], kind, ctx)
        line = dict(correct=bool(result["correct"]), attempted=result["attempted"],
                    failed=result["failed"], metrics=metrics, device=device,
                    checks=result.get("checks"))
        if run.trace and ctx.trace is not None:
            line["breakdown"] = ctx.trace.breakdown(
                result["stamps"].get("gap_spans", []), result["stamps"].get("gap_rest", "host_other"))
        records = list(result.get("records", []))
        if os.environ.get("BENCH_KEEP_TRACE") and result.get("trace_dir"):  # for a look by hand
            from benchmark import reduce_trace

            records.append(reduce_trace.find_xplane(result["trace_dir"]) or "")
        harness.keep(records, f"{run.cell}.seed{run.seed}.trace{ns.trace}")
    except RunFailed as e:
        sys.stderr.write(f"benchmark: {run.cell}: FAILED: {e}\n")
        keep_failure(run, str(e), ns.trace)
        return EXIT_NO_ACCELERATOR if "platform" in str(e) else EXIT_FAILED
    except Exception:
        keep_failure(run, traceback.format_exc(), ns.trace)
        raise
    finally:
        shutil.rmtree(harness.WORK, ignore_errors=True)
    if "jax" in sys.modules and not run.trace:
        raise AssertionError("the parent of an untraced run imported JAX")
    if ns.rehearse:
        print(json.dumps({"cpu_rehearsal": line}), flush=True)
    else:
        print(json.dumps(line), flush=True)
    return 0


def keep_failure(run, reason: str, trace: int) -> None:
    """A failed run prints no result and its work directory goes: its reason
    and its logs' tails stay, where a run's records do."""
    try:
        harness.keep([harness.failure_report(run.work, reason)], f"{run.cell}.seed{run.seed}.trace{trace}")
    except OSError as e:  # the reason is on stderr already
        sys.stderr.write(f"benchmark: {run.cell}: FAILED.txt was not kept: {e}\n")


def peaks_for(device_kind: str) -> dict:
    table = harness.load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise RunFailed(f"no peaks on record for device_kind {device_kind!r}: "
                        f"add it to benchmark/peaks.json with its source")
    return table["devices"][device_kind]


if __name__ == "__main__":
    sys.exit(main())
