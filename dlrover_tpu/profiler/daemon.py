"""Cluster profiler daemon — the rank-0 helper service.

Reference: ``xpu_timer/xpu_timer/server/hosting_service_server_client.cc``
— a standalone process next to the job serving Prometheus for the WHOLE
cluster and coordinating cluster-wide diagnostics. TPU shape: each
trainer already serves its own tpu_timer endpoint (scraped by its agent
and forwarded to the master's metric context), so the daemon talks to
ONE place — the master — and re-exports:

- ``GET /metrics``: every node's last gauges as Prometheus text, each
  line labeled ``node="<id>"`` — one scrape target for the whole job.
- ``GET /job``: the master's job status JSON (stage, goodput, steps/s).
- ``POST /dump`` (or GET): queue a stack dump on every running worker
  (the agents SIGUSR2 their trainers); responds with the node ids hit.

Run: ``python -m dlrover_tpu.profiler.daemon --master HOST:PORT
[--port 18889]``.
"""

import argparse
import json
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from ..common.events import EventEmitter
from ..common.log import logger
from ..rpc.client import MasterClient

# gauge names arrive as 'name{label="x"}' or bare 'name'
_NAME = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?$")


def render_cluster_metrics(node_gauges) -> str:
    """{node: {gauge: value}} -> Prometheus text with node labels."""
    lines = []
    for node_id in sorted(node_gauges):
        for name, value in sorted(node_gauges[node_id].items()):
            m = _NAME.match(name)
            if not m:
                continue
            base, _, labels = m.group(1), m.group(2), m.group(3)
            label_parts = [f'node="{node_id}"']
            if labels:
                label_parts.append(labels)
            lines.append(f"{base}{{{','.join(label_parts)}}} {value}")
    return "\n".join(lines) + ("\n" if lines else "")


class ProfilerDaemon:
    def __init__(
        self,
        client: Optional[MasterClient] = None,
        port: int = 0,
        bind: str = "0.0.0.0",
    ):
        self._client = client or MasterClient.singleton()
        self._port = port
        self._bind = bind
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._evt = EventEmitter("agent")

    @property
    def port(self) -> int:
        return self._httpd.server_address[1] if self._httpd else -1

    def _handler(self):
        daemon = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _send(self, code: int, body: str, ctype="text/plain"):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                # Read-only verbs only: /dump is side-effectful (queues
                # SIGUSR2 stack dumps on every trainer) and scrapers /
                # health probers / browser prefetchers issue GETs freely.
                try:
                    if self.path.startswith("/metrics"):
                        # an incident-side span (see tpurun-trace): a
                        # scrape runs in the rank-0 agent, beside the
                        # worker whose saves it may disturb
                        with daemon._evt.duration("agent_profiler_scrape"):
                            resp = daemon._client.get_cluster_metrics()
                            body = render_cluster_metrics(resp.node_gauges)
                        self._send(200, body)
                    elif self.path.startswith("/job"):
                        status = daemon._client.get_job_status()
                        self._send(
                            200,
                            json.dumps(
                                {
                                    "stage": status.stage,
                                    "goodput": status.goodput,
                                    "steps_per_second": status.steps_per_second,
                                    "last_step": status.last_step,
                                }
                            ),
                            ctype="application/json",
                        )
                    elif self.path.startswith("/dump"):
                        self._send(405, "POST /dump to trigger a dump\n")
                    else:
                        self._send(200, "ok\n")
                except Exception as e:  # noqa: BLE001 — keep serving
                    self._send(502, f"master unreachable: {e}\n")

            def do_POST(self):
                try:
                    if self.path.startswith("/dump"):
                        resp = daemon._client.trigger_cluster_dump()
                        self._send(
                            200, json.dumps({"dumped": resp.node_ids}),
                            ctype="application/json",
                        )
                    else:
                        self._send(404, "unknown endpoint\n")
                except Exception as e:  # noqa: BLE001 — keep serving
                    self._send(502, f"master unreachable: {e}\n")

        return Handler

    def start(self) -> int:
        self._httpd = ThreadingHTTPServer(
            (self._bind, self._port), self._handler()
        )
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="profiler-daemon",
            daemon=True,
        )
        self._thread.start()
        logger.info("profiler daemon serving on :%s", self.port)
        return self.port

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="cluster profiler daemon")
    parser.add_argument("--master", required=True, help="master HOST:PORT")
    parser.add_argument("--port", type=int, default=18889)
    parser.add_argument(
        "--bind",
        default="0.0.0.0",
        help="listen address (use 127.0.0.1 to restrict to local scrapers)",
    )
    ns = parser.parse_args(argv)
    daemon = ProfilerDaemon(
        client=MasterClient(master_addr=ns.master, node_id=-1),
        port=ns.port,
        bind=ns.bind,
    )
    daemon.start()
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        daemon.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
