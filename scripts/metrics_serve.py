#!/usr/bin/env python
"""Serve the grid metrics plane over HTTP (`make serve-metrics`).

Thin stdlib ``http.server`` front-end over
:mod:`mpi_grid_redistribute_tpu.telemetry.metrics` /
:mod:`...telemetry.aggregate`. Two endpoints:

* ``GET /metrics`` — OpenMetrics text. The registry is rebuilt from the
  journal source on EVERY scrape (the "re-snapshot" contract): counters
  are the recorder's exact all-time counts, gauges/histograms cover the
  retained window at scrape time. No device work happens on this path —
  the journal is host memory (or files), and the metrics/aggregate
  modules never import jax.
* ``GET /healthz`` — JSON health verdict from a ``HealthMonitor`` run
  read-only over the same journal (``evaluate(record=False)`` — a
  poller must observe health, not mutate the journal it is judging).
  HTTP 200 on OK/WARN, 503 on ALERT, so a plain liveness probe can act
  on it without parsing.
* ``GET /incidents`` (with ``--incident-dir``) — JSON listing of the
  flight-recorder bundles under the directory (each entry is the
  bundle's ``index.json``; see ``telemetry/incident.py`` and
  ``scripts/incident.py`` for inspection/export).
* ``GET /query`` — the telemetry query plane
  (:mod:`...telemetry.query`): filter by ``kind``/``step_min``/
  ``step_max``/``trace``/``host``/``pid``/``since``/``until``/
  ``ctx.<field>``, shape with ``agg=<op>`` windowed series or
  ``by=<key>`` grouped counts (grammar in telemetry/SCHEMA.md). Bad
  parameters are HTTP 400 with the parse error in the body.
* ``GET /events`` — cursor-resumable event stream over the same
  source. The cursor is the ``host:pid:seq`` envelope triple (the pod
  merge's total order); pass the previous reply's ``cursor`` back to
  resume exactly where it left off, ``limit`` to bound the page and
  ``timeout_s`` to long-poll until new events arrive (re-snapshots the
  source every 0.2 s while waiting).

Journal sources, combinable:

* ``--journal FILE`` (repeatable) — JSONL shard(s) written by
  ``StepRecorder.to_jsonl``; several shards are pod-merged via
  ``aggregate.merge_journals`` (``--align wall|start``) and re-read on
  every scrape, so a live run appending shards is picked up. Parsed
  shards are cached keyed on ``(path, mtime, size)``: a scrape storm
  against a quiescent journal re-merges nothing, while any shard
  growing (or appearing) invalidates the cache on the next scrape.
* ``--store DIR`` — a durable ``telemetry.store`` journal-store root
  (``MANIFEST.json`` + segments). Re-read when the manifest changes, so
  a live driver draining into the store is tracked scrape to scrape;
  counters stay the manifest's exact all-time counts even after
  retention and compaction.
* ``--demo`` — no artifacts handy: run a small in-process drift loop in
  a background thread and scrape its live recorder.

Examples:

  # serve a run's journal shards pod-wide on :9100
  python scripts/metrics_serve.py --journal shard0.jsonl \\
      --journal shard1.jsonl --port 9100

  # self-contained demo; --once prints one scrape and exits (CI)
  python scripts/metrics_serve.py --demo --once
"""

from __future__ import annotations

import argparse
import http.server
import json
import os
import signal
import sys
import threading
import time

# gridlint: service-path

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

OPENMETRICS_CONTENT_TYPE = (
    "application/openmetrics-text; version=1.0.0; charset=utf-8"
)


def _shard_key(paths):
    """Cache key over the shard files: ``(path, mtime_ns, size)`` per
    shard. Any append, truncation, replacement or late-appearing shard
    changes the key; a quiescent journal keeps it stable."""
    key = []
    for p in paths:
        try:
            st = os.stat(p)
            key.append((p, st.st_mtime_ns, st.st_size))
        except OSError:
            key.append((p, None, None))
    return tuple(key)


def journal_snapshotter(paths, align):
    """``(snapshot, shutdown)`` over JSONL shard files: re-reads and
    re-merges when any shard changed since the last scrape (keyed on
    ``(path, mtime, size)``), so scrapes track a journal that is still
    growing without re-parsing an unchanged one on every poll. Nothing
    to stop — ``shutdown`` is a no-op."""
    from mpi_grid_redistribute_tpu import telemetry

    lock = threading.Lock()
    cache = {"key": None, "rec": None}

    def snapshot():
        # stat outside the lock (cheap, no shared state), compare under
        # it; parse outside the lock on a miss so a slow merge does not
        # serialize concurrent scrapes, then double-check before storing
        key = _shard_key(paths)
        with lock:
            if cache["key"] == key and cache["rec"] is not None:
                return cache["rec"]
        merged = telemetry.merge_journals(paths, align=align)
        rec = merged.to_recorder(pod_steps=len(merged.shards) > 1)
        with lock:
            cache["key"] = key
            cache["rec"] = rec
        return rec

    def shutdown():
        return None

    return snapshot, shutdown


def store_snapshotter(store_dir):
    """``(snapshot, query_snapshot, shutdown)`` over a durable
    ``telemetry.store`` root. ``snapshot`` returns a replayed
    ``StepRecorder`` with its all-time counters pinned to the
    manifest's exact totals (what ``/metrics`` and ``/healthz``
    consume); ``query_snapshot`` returns the ``StoreReader`` itself so
    ``/query`` and ``/events`` see compacted ``store_window`` rows
    first-class (quantiles over summaries stay exact). Both are cached
    keyed on the manifest's ``(mtime_ns, size)`` — the store's writer
    publishes the manifest atomically, so a changed key is a complete
    new store state, never a torn one."""
    from mpi_grid_redistribute_tpu.telemetry import store as store_lib

    manifest_path = os.path.join(store_dir, "MANIFEST.json")
    lock = threading.Lock()
    cache = {"key": None, "reader": None, "rec": None}

    def _key():
        try:
            st = os.stat(manifest_path)
            return (st.st_mtime_ns, st.st_size)
        except OSError:
            return None

    def _refresh():
        key = _key()
        with lock:
            if cache["key"] == key and cache["reader"] is not None:
                return cache["reader"], cache["rec"]
        reader = store_lib.StoreReader(store_dir)
        rec = reader.to_recorder()
        with lock:
            cache["key"] = key
            cache["reader"] = reader
            cache["rec"] = rec
        return reader, rec

    def snapshot():
        return _refresh()[1]

    def query_snapshot():
        return _refresh()[0]

    def shutdown():
        return None

    return snapshot, query_snapshot, shutdown


def demo_snapshotter(steps: int = 200):
    """``(snapshot, shutdown)`` over a small redistribute loop run in a
    background thread; scrapes snapshot its recorder live. Uses the
    numpy backend — the demo is about the metrics surface, not the
    engines. ``shutdown`` sets the stop event and joins the drive
    thread, so every exit path (``--once``, Ctrl-C, SIGTERM, server
    teardown) leaves no thread behind."""
    import numpy as np

    from mpi_grid_redistribute_tpu import api
    from mpi_grid_redistribute_tpu.domain import Domain

    rd = api.GridRedistribute(
        Domain(0.0, 1.0, periodic=True), (2, 2, 2), backend="numpy"
    )
    rng = np.random.default_rng(0)
    stop = threading.Event()

    def drive():  # racecheck: recorder-writer
        # the drive thread is the recorder's declared single writer
        # (T005); the HTTP handlers only snapshot events()/counts()
        n = 4096
        pos = rng.random((n, 3), dtype=np.float32)
        vel = 0.1 * (rng.random((n, 3), dtype=np.float32) - 0.5)
        for _ in range(steps):
            if stop.is_set():
                return
            t0 = time.perf_counter()
            rd.redistribute(pos, vel)
            rd.monitor.note_step_time(time.perf_counter() - t0)
            rd.monitor.evaluate()
            pos = (pos + 0.05 * vel) % 1.0
        stop.set()

    t = threading.Thread(target=drive, daemon=True)
    t.start()

    def snapshot():
        return rd.telemetry

    def shutdown():
        stop.set()
        t.join(timeout=10)

    return snapshot, shutdown


def make_handler(snapshot, incident_dir=None, query_source=None):
    """An HTTPRequestHandler bound to a journal snapshot factory;
    ``incident_dir`` additionally serves the flight-recorder bundle
    listing on ``/incidents`` (pure file reads — no journal state).
    ``query_source`` overrides the source ``/query``/``/events`` read
    (the store mode passes the ``StoreReader`` here so compacted
    summary rows stay visible); defaults to ``snapshot``."""
    import urllib.parse

    from mpi_grid_redistribute_tpu import telemetry
    from mpi_grid_redistribute_tpu.telemetry import incident as incident_lib
    from mpi_grid_redistribute_tpu.telemetry import query as query_lib

    events_source = query_source if query_source is not None else snapshot

    class Handler(http.server.BaseHTTPRequestHandler):
        def _send(self, code, ctype, body: bytes):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _send_json(self, code, doc):
            body = (json.dumps(doc, sort_keys=True) + "\n").encode("utf-8")
            self._send(code, "application/json; charset=utf-8", body)

        def _params(self):
            qs = urllib.parse.urlsplit(self.path).query
            # last value wins, matching the flat-string grammar
            return {
                k: v[-1]
                for k, v in urllib.parse.parse_qs(
                    qs, keep_blank_values=True
                ).items()
            }

        def do_GET(self):  # noqa: N802 (http.server API)
            path = self.path.split("?", 1)[0]
            if path == "/metrics":
                rec = snapshot()
                text = telemetry.from_journal(rec).render_openmetrics()
                self._send(
                    200, OPENMETRICS_CONTENT_TYPE, text.encode("utf-8")
                )
            elif path == "/healthz":
                rec = snapshot()
                monitor = telemetry.HealthMonitor(rec)
                verdict = monitor.evaluate(record=False)
                body = (json.dumps(verdict, sort_keys=True) + "\n").encode(
                    "utf-8"
                )
                code = 503 if verdict["status"] == "ALERT" else 200
                self._send(code, "application/json; charset=utf-8", body)
            elif path == "/incidents" and incident_dir is not None:
                listing = incident_lib.list_bundles(incident_dir)
                body = (
                    json.dumps(
                        {"dir": incident_dir, "incidents": listing},
                        sort_keys=True,
                    )
                    + "\n"
                ).encode("utf-8")
                self._send(200, "application/json; charset=utf-8", body)
            elif path == "/query":
                try:
                    reply = query_lib.run_query(
                        events_source(), self._params()
                    )
                except query_lib.QueryError as e:
                    self._send_json(400, {"error": str(e)})
                    return
                self._send_json(200, reply)
            elif path == "/events":
                params = self._params()
                try:
                    cursor = params.get("cursor") or None
                    limit = int(params.get("limit", "256"))
                    timeout_s = float(params.get("timeout_s", "0"))
                    kind = params.get("kind") or None
                    deadline = time.monotonic() + min(timeout_s, 60.0)
                    while True:
                        rows = query_lib.rows_of(events_source())
                        if kind:
                            rows = query_lib.filter_rows(rows, kind=kind)
                        page = query_lib.events_page(
                            rows, cursor=cursor, limit=limit
                        )
                        if page["events"] or time.monotonic() >= deadline:
                            break
                        # long-poll: re-snapshot until new events land
                        # or the (capped) timeout expires
                        time.sleep(0.2)
                except (query_lib.QueryError, ValueError) as e:
                    self._send_json(400, {"error": str(e)})
                    return
                self._send_json(200, page)
            else:
                self._send(
                    404,
                    "text/plain; charset=utf-8",
                    b"try /metrics, /healthz, /incidents, /query or "
                    b"/events\n",
                )

        def log_message(self, fmt, *args):
            print("  " + fmt % args, file=sys.stderr)

    return Handler


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Serve /metrics (OpenMetrics) + /healthz over a "
        "telemetry journal."
    )
    p.add_argument(
        "--journal",
        action="append",
        default=[],
        metavar="FILE",
        help="JSONL journal shard (repeat for a pod merge); re-read on "
        "every scrape",
    )
    p.add_argument(
        "--align",
        choices=("wall", "start"),
        default="wall",
        help="multi-shard clock alignment (see aggregate.merge_journals)",
    )
    p.add_argument(
        "--store",
        metavar="DIR",
        help="durable journal-store root (telemetry/store.py); re-read "
        "when its MANIFEST.json changes",
    )
    p.add_argument(
        "--demo",
        action="store_true",
        help="serve a live in-process drift-loop journal",
    )
    p.add_argument(
        "--incident-dir",
        metavar="DIR",
        help="flight-recorder bundle root; enables GET /incidents "
        "(see telemetry/incident.py)",
    )
    p.add_argument("--port", type=int, default=9100,
                   help="0 = ephemeral (bound port is printed)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument(
        "--once",
        action="store_true",
        help="print one /metrics scrape + the /healthz verdict to "
        "stdout and exit (no server)",
    )
    args = p.parse_args(argv)

    sources = sum(
        (bool(args.journal), bool(args.store), bool(args.demo))
    )
    if sources == 0:
        p.error("need --journal FILE (repeatable), --store DIR or --demo")
    if sources > 1:
        p.error("--journal, --store and --demo are mutually exclusive")

    from mpi_grid_redistribute_tpu import telemetry

    query_source = None
    if args.journal:
        snapshot, shutdown = journal_snapshotter(args.journal, args.align)
    elif args.store:
        snapshot, query_source, shutdown = store_snapshotter(args.store)
    else:
        snapshot, shutdown = demo_snapshotter()

    if args.once:
        try:
            rec = snapshot()
            sys.stdout.write(
                telemetry.from_journal(rec).render_openmetrics()
            )
            verdict = telemetry.HealthMonitor(rec).evaluate(record=False)
            print("healthz: " + json.dumps(verdict, sort_keys=True))
        finally:
            # --once must not leave the demo drive thread running behind
            # the printed scrape
            shutdown()
        return 0

    server = http.server.ThreadingHTTPServer(
        (args.host, args.port),
        make_handler(
            snapshot,
            incident_dir=args.incident_dir,
            query_source=query_source,
        ),
    )
    host, port = server.server_address[:2]
    extra = " and /incidents" if args.incident_dir else ""
    print(f"serving http://{host}:{port}/metrics, /healthz, /query, "
          f"/events{extra} (Ctrl-C to stop)", flush=True)

    def _on_sigterm(signum, frame):
        # route SIGTERM through the KeyboardInterrupt path below so the
        # server closes and the snapshotter's stop event fires — a
        # killed scrape server must not strand its drive thread
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        print("stopped")
    finally:
        server.server_close()
        shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
