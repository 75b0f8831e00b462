"""Agent-side profiler metric collector.

Reference: ``xpu_timer_metric_collector.py:28`` — the agent scrapes the
worker's xpu_timer Prometheus endpoint and forwards gauges to the
master's metric context. Here the endpoint is the native tpu_timer HTTP
server inside the JAX process (port published via the ``TPU_TIMER_PORT``
env the trainer sets, or discovered from the worker env contract).
"""

import re
import threading
import urllib.request
from typing import Dict, Optional

from ..common.events import EventEmitter
from ..common.log import logger
from ..observability.metrics import get_registry
from ..rpc.client import MasterClient

_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+([-0-9.eE+]+)$")


def parse_prometheus(text: str) -> Dict[str, float]:
    """Prometheus exposition text → flat ``{key: value}`` map.

    Flattening rule: every sample keeps its FULL exposition key
    (``name{labels}``), and each metric additionally gets a bare-name
    convenience key holding the LAST sample of that family in file
    order — so unlabeled consumers (hang checks reading
    ``tpu_timer_hang``) don't parse label syntax, at the documented
    cost that a multi-labeled family's bare key is whichever series
    the endpoint rendered last. Comment lines, blank lines, and
    malformed samples (bad name, non-numeric value) are skipped.
    """
    gauges: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if not m:
            continue
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        try:
            parsed = float(value)
        except ValueError:
            continue
        gauges[name + labels] = parsed
        if labels:
            gauges[name] = parsed
    return gauges


class ProfilerMetricCollector:
    def __init__(
        self,
        port: int,
        client: Optional[MasterClient] = None,
        interval_s: float = 30.0,
        scrape_timeout_s: float = 5.0,
    ):
        self._url = f"http://127.0.0.1:{port}/metrics"
        self._client = client or MasterClient.singleton()
        self._interval = interval_s
        # Localhost scrape of the in-process profiler endpoint — a
        # short deadline of its own, injectable rather than inline
        # (tpurun-lint rpc-deadline).
        self._scrape_timeout_s = scrape_timeout_s
        self._thread: Optional[threading.Thread] = None
        self._stopped = threading.Event()
        self._evt = EventEmitter("agent")

    def collect_once(self) -> Optional[Dict[str, float]]:
        try:
            with urllib.request.urlopen(
                self._url, timeout=self._scrape_timeout_s
            ) as resp:
                text = resp.read().decode()
        except Exception as e:
            logger.debug("profiler scrape failed: %s", e)
            return None
        gauges = parse_prometheus(text)
        if gauges:
            # Local half of the unified plane: the agent's own /metrics
            # re-serves the worker scrape (keys are already exposition
            # syntax), so operators read one endpoint per host.
            get_registry().ingest(gauges)
            try:
                self._client.report_node_metrics(gauges)
            except Exception as e:
                logger.debug("metric report failed: %s", e)
        return gauges

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stopped.clear()
        self._thread = threading.Thread(
            target=self._loop, name="profiler-metrics", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stopped.wait(self._interval):
            # an incident-side span: with DLROVER_EVENT_DIR set, the
            # tick shows on tpurun-trace's timeline beside the worker's
            # ckpt_save (both carry wall-clock ts). No metric reads it.
            with self._evt.duration("agent_metric_tick") as tick:
                gauges = self.collect_once()
                tick.content["gauges"] = len(gauges or ())

    def stop(self) -> None:
        self._stopped.set()
        self._thread = None
