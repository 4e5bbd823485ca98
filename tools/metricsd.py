"""metricsd — expose the hetu_tpu observability registry (ISSUE 10).

The obs registry (``hetu_tpu.obs.registry``) already holds every
counter family, latency histogram and gauge in the process; this tool
turns it into operational surfaces:

* **file export** — :func:`write_json` dumps ``obs.metrics_dump()``
  (atomic rename), :func:`write_prom` the Prometheus text exposition;
  :func:`start_file_export` rewrites both on an interval from a daemon
  thread (crash-safe: the last complete snapshot survives).
* **HTTP endpoint** — :func:`start_http` serves ``/metrics``
  (Prometheus text, scrapeable) and ``/metrics.json`` (the full dump)
  on a tiny stdlib ``http.server`` daemon thread.  Port 0 picks a free
  port; the return value tells you which.

metricsd reads the registry of the process it runs IN — import it from
the training/serving script::

    from tools.metricsd import start_http, start_file_export
    httpd, port = start_http(9109)
    stop = start_file_export("metrics.json", "metrics.prom",
                             interval_s=15)

As a standalone CLI it snapshots whatever the current process recorded
(``--demo`` seeds a few instruments first so the output is non-empty —
useful for eyeballing the exposition format)::

    python tools/metricsd.py --out metrics.json --prom metrics.prom
    python tools/metricsd.py --http 9109 --interval 15
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _dump():
    from hetu_tpu import obs
    return obs.metrics_dump()


def _prom_text():
    from hetu_tpu import obs
    return obs.prometheus_text()


def write_json(path):
    """Write ``obs.metrics_dump()`` to ``path`` (atomic rename)."""
    blob = _dump()
    tmp = str(path) + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(blob, fh, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return blob


def write_prom(path):
    """Write the Prometheus text exposition to ``path`` (atomic)."""
    text = _prom_text()
    tmp = str(path) + ".tmp"
    with open(tmp, "w") as fh:
        fh.write(text)
    os.replace(tmp, path)
    return text


def start_file_export(json_path=None, prom_path=None, interval_s=15.0):
    """Rewrite the export files every ``interval_s`` seconds from a
    daemon thread.  Returns a ``stop()`` callable (writes one final
    snapshot)."""
    if json_path is None and prom_path is None:
        raise ValueError("nothing to export: give json_path or prom_path")
    stop_ev = threading.Event()

    def once():
        if json_path:
            write_json(json_path)
        if prom_path:
            write_prom(prom_path)

    def loop():
        while not stop_ev.wait(interval_s):
            try:
                once()
            except OSError:
                pass    # disk hiccup: keep the exporter alive

    t = threading.Thread(target=loop, daemon=True, name="hetu-metricsd")
    t.start()

    def stop():
        stop_ev.set()
        t.join(interval_s + 5)
        once()
    return stop


def start_http(port=0, host="127.0.0.1"):
    """Serve ``/metrics`` (Prometheus text) and ``/metrics.json`` on a
    daemon thread.  Returns ``(server, port)`` — port 0 in means "the
    OS picked one", read it from the return.  ``server.shutdown()``
    stops it."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):     # noqa: N802 — stdlib handler contract
            if self.path.startswith("/metrics.json"):
                body = json.dumps(_dump(), sort_keys=True).encode()
                ctype = "application/json"
            elif self.path.startswith("/metrics"):
                body = _prom_text().encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            else:
                self.send_error(404, "try /metrics or /metrics.json")
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *args):
            pass    # a scrape per interval must not spam stderr

    srv = ThreadingHTTPServer((host, port), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True,
                         name="hetu-metricsd-http")
    t.start()
    return srv, srv.server_address[1]


def _seed_demo():
    """Record a few instruments so a standalone invocation shows the
    exposition format instead of an empty registry."""
    from hetu_tpu import metrics
    metrics.record_fault("demo_fault")
    metrics.record_rpc("OP_PULL", 210.0, 4096)
    metrics.record_rpc("OP_PUSH", 480.0, 8192)
    metrics.record_serve_latency("queue_wait", 120.0)
    metrics.record_run_gauges("demo", 3.2)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--out", help="write metrics_dump() JSON here")
    p.add_argument("--prom", help="write Prometheus text here")
    p.add_argument("--http", type=int, default=None, metavar="PORT",
                   help="serve /metrics + /metrics.json (0 = any port)")
    p.add_argument("--interval", type=float, default=0.0,
                   help="rewrite the files every N seconds (0 = once)")
    p.add_argument("--demo", action="store_true",
                   help="seed sample metrics first (format eyeballing)")
    args = p.parse_args(argv)
    if args.demo:
        _seed_demo()
    if not (args.out or args.prom or args.http is not None):
        print(json.dumps(_dump(), indent=1, sort_keys=True))
        return 0
    if args.out:
        write_json(args.out)
        print(f"metricsd: wrote {args.out}")
    if args.prom:
        write_prom(args.prom)
        print(f"metricsd: wrote {args.prom}")
    if args.http is not None:
        srv, port = start_http(args.http)
        print(f"metricsd: http://127.0.0.1:{port}/metrics")
    if args.interval > 0 and (args.out or args.prom):
        stop = start_file_export(args.out, args.prom, args.interval)
        try:
            threading.Event().wait()    # foreground until Ctrl-C
        except KeyboardInterrupt:
            stop()
    elif args.http is not None:
        try:
            threading.Event().wait()
        except KeyboardInterrupt:
            srv.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
