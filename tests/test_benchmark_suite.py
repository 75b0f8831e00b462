"""The benchmark's own tests, collected by tier-1.

``benchmark/tests/`` holds the checks every verdict in the ledger rests
on: the trace reduction on the trace recorded on the v5e, the metric
arithmetic, the readers of the program's spans and counters, and a CPU
rehearsal of every cell's driver (the serving one through
``tpurun-serve``'s default flags). Tier-1 collects ``tests/`` only, and
nothing under ``benchmark/`` may change outside a ``benchmark`` PR, so
this module brings their cases in by name.

They stay in ONE module on purpose: tier-1 runs under ``--dist
loadfile``, which keeps a module on one worker, and the rehearsals start
agents, workers and servers that collide when several run at once.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # what benchmark/tests/conftest.py does there
    sys.path.insert(0, ROOT)

pytest.register_assert_rewrite(
    "benchmark.tests.test_metrics",
    "benchmark.tests.test_metrics_mla_moe",
    "benchmark.tests.test_metrics_lfm2_moe",
    "benchmark.tests.test_metrics_granite_hybrid",
    "benchmark.tests.test_metrics_qwen3_next",
    "benchmark.tests.test_program_spans",
    "benchmark.tests.test_reduce_trace",
    "benchmark.tests.test_rehearsal",
    "benchmark.tests.test_metrics_startup",
    "benchmark.tests.test_metrics_mla_flash_calls",
    "benchmark.tests.test_metrics_mellum",
    "benchmark.tests.test_metrics_olmo_hybrid",
    "benchmark.tests.test_metrics_sdar_moe",
    "benchmark.tests.test_trace_scopes",
)

from benchmark.tests.test_metrics import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_mla_moe import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_lfm2_moe import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_granite_hybrid import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_qwen3_next import *  # noqa: E402,F401,F403
from benchmark.tests.test_program_spans import *  # noqa: E402,F401,F403
from benchmark.tests.test_reduce_trace import *  # noqa: E402,F401,F403
from benchmark.tests.test_rehearsal import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_startup import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_mla_flash_calls import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_mellum import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_olmo_hybrid import *  # noqa: E402,F401,F403
from benchmark.tests.test_metrics_sdar_moe import *  # noqa: E402,F401,F403
from benchmark.tests.test_trace_scopes import *  # noqa: E402,F401,F403


def benchmark_as_it_stood_at(bench_file, cell_name):
    """``BENCHMARK.json`` with the entries of later PRs taken out: no cell
    behind ``cell_name``, no configuration behind its own, no metric from the
    first one on that lists only later cells (new entries go at the end of
    their lists, so every metric behind it is a later PR's too, also one that
    reads earlier cells as well: PR 61's eleven), and the later cells' names
    out of the ``workloads`` lists that stay. So this is the file as the PR
    that added ``cell_name`` left it, whatever came after."""
    cells = [w["name"] for w in bench_file["workloads"]]
    later = set(cells[cells.index(cell_name) + 1:])
    out = dict(bench_file, workloads=[w for w in bench_file["workloads"] if w["name"] not in later])
    used = [w["config"] for w in out["workloads"]]
    last_config = max(i for i, c in enumerate(bench_file["configs"]) if c["name"] in used)
    out["configs"] = bench_file["configs"][:last_config + 1]
    for key in ("per_layer", "end_to_end"):
        kept = []
        for m in bench_file[key]:
            if "workloads" in m:
                if later.issuperset(m["workloads"]):
                    break
                m = dict(m, workloads=[c for c in m["workloads"] if c not in later])
            kept.append(m)
        out[key] = kept
    return out


def test_olmo_hybrid_cell_and_metrics_are_in_the_benchmark(tmp_path, monkeypatch):  # noqa: F811
    """PR 56's test of this name reads its cell, its configuration and its
    six readers as the LAST entries of ``BENCHMARK.json``, and new entries go
    at the end, so no file with a later cell can pass it as it reads the file.
    A ``model_config`` PR may edit no file the benchmark has. So the accepted
    test itself runs here, every assertion of it, on the file as PR 56 left
    it (``benchmark_as_it_stood_at``): what later PRs appended is taken out,
    nothing of PR 56's entries is. That later entries did go behind them is
    each later PR's own test (``test_sdar_moe_cell_and_metrics_are_in_the_benchmark``
    finds its entries by name and by place). ``pytest benchmark/tests`` by hand
    still fails on it until a ``benchmark`` PR mends it there (``PERF.md``
    section 7)."""
    import json

    from benchmark.tests import test_metrics_olmo_hybrid as olmo

    bench_file = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    then = benchmark_as_it_stood_at(bench_file, olmo.CELL)
    # what is taken out is what this file's later tests hold in place: whole entries from the end, and names from
    # the end of lists
    assert then["workloads"] == bench_file["workloads"][:9] and then["configs"] == bench_file["configs"][:8]
    assert [m["name"] for m in then["per_layer"]] == [m["name"] for m in bench_file["per_layer"][:62]]
    for m, was in zip(then["per_layer"] + then["end_to_end"], bench_file["per_layer"][:62] + bench_file["end_to_end"]):
        assert m.get("workloads", []) == was.get("workloads", [])[:len(m.get("workloads", []))]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(then))
    monkeypatch.setattr(olmo, "ROOT", str(tmp_path))
    olmo.test_olmo_hybrid_cell_and_metrics_are_in_the_benchmark()
