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
