"""Telemetry history plane (telemetry/store.py, query.py) — ISSUE 18 gates.

Six contracts, each tested against hand math, a real corruption, or a
real HTTP exchange:

* exactness — the headline claim: ``metrics.from_journal`` over a
  drained+compacted (and retention-trimmed) store equals the live
  recorder's all-time counts after ring eviction, byte for byte, and
  the manifest's conservation ledger (``counts == retired + segments
  + active + missed``) holds at every stage;
* durability — rotation closes immutable sha256-checksummed segments,
  ``verify()`` catches a single flipped byte, manifest publishes are
  staged-rename atomic (no ``.tmp-`` droppings), and a restarted
  writer resumes from the drain watermark with zero duplicates;
* compaction — non-step events survive verbatim while per-step runs
  collapse into ``store_window`` sketches whose merged quantiles equal
  the live ``Histogram``'s (identical ``STEP_TIME_EDGES`` buckets);
* query plane — filters/group-bys/windowed aggregations against hand
  fixtures, the cursor total order (exact resume, evicted-cursor
  fallback, unknown-shard replay), and the flat-string grammar's
  error surface (unknown param, bad int → ``QueryError``);
* service — ``GET /query``/``GET /events`` over a real store via a
  subprocess ``metrics_serve --store``, cursor-walked to exhaustion;
  the in-process concurrency gate (parallel ``/metrics`` + ``/query``
  + ``/events`` against a LIVE recorder under an armed
  ``ThreadAccessTracer`` — zero unlocked accesses); and the driver
  integration (boundary drains, supervised-restart no-duplication);
* overhead — boundary drains add <= 2% to the config1-style
  steady-state step (the same paired-delta median protocol as the
  recorder+metrics gate in test_metrics.py).

CLI smokes for ``grid_top --once``, ``history`` and ``storecheck``
ride along so ``make check``'s new surfaces stay exercised in tier-1.
"""

import dataclasses
import http.server
import importlib.util
import json
import os
import re
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from mpi_grid_redistribute_tpu.telemetry import (
    StepRecorder,
    ThreadAccessTracer,
    from_journal,
    record_chunk_steps,
)
from mpi_grid_redistribute_tpu.telemetry import metrics as metrics_lib
from mpi_grid_redistribute_tpu.telemetry import query as query_lib
from mpi_grid_redistribute_tpu.telemetry import store as store_lib
from mpi_grid_redistribute_tpu.telemetry.query import (
    QueryError,
    events_page,
    filter_rows,
    group_rows,
    run_query,
    window_aggregate,
)
from mpi_grid_redistribute_tpu.telemetry.store import (
    JournalStore,
    StoreCorruptError,
    StoreReader,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE = os.path.join(REPO_ROOT, "scripts", "metrics_serve.py")


def _journal_counter(reader):
    """The scrape-side counts: ``grid_journal_events_total`` per kind
    from ``from_journal`` over the store."""
    reg = from_journal(reader)
    fam = reg.get("grid_journal_events")
    out = {}
    for values, child in fam.children():
        out[values[0]] = int(child._value)
    return out


def _conservation(man):
    """retired + closed segments + active + missed, per kind."""
    total = dict(man["retired"]["counts"])
    for seg in man["segments"]:
        for k, v in seg["counts"].items():
            total[k] = total.get(k, 0) + v
    if man["active"]:
        for k, v in man["active"]["counts"].items():
            total[k] = total.get(k, 0) + v
    for k, v in man["missed"].items():
        total[k] = total.get(k, 0) + v
    return total


def _drive(root, chunks=16, per_chunk=40, capacity=96, **store_kw):
    """A wrapping-ring run drained at every chunk boundary: enough
    volume to force eviction, rotation and (with the right knobs)
    compaction + retention."""
    kw = dict(
        segment_events=120,
        segment_bytes=1 << 20,
        retain_bytes=1 << 30,
        compact_after=1,
        compact_window=16,
    )
    kw.update(store_kw)
    rec = StepRecorder(capacity=capacity, host="h0", pid=7)
    store = JournalStore(str(root), **kw)
    for c in range(chunks):
        record_chunk_steps(
            rec, c * per_chunk, 0.002 * (1 + (c % 3)), [c % 2] * per_chunk
        )
        if c % 4 == 0:
            rec.record(
                "alert", rule="imbalance_ratio", severity="WARN",
                value=1.0 + c, step=c * per_chunk,
            )
        if c % 7 == 0:
            rec.record(
                "flow_snapshot", imbalance_ratio=1.0 + 0.1 * c,
                total_rows=64, step=c * per_chunk,
            )
        store.drain(rec)
    return rec, store


# ====================================================== exactness


def test_counts_exact_after_eviction_and_compaction(tmp_path):
    """The ISSUE 18 headline: after the ring evicted hundreds of events
    and old raw segments were compacted to sketches, the store's counts
    — manifest-side AND through a full ``from_journal`` scrape — equal
    the live recorder's all-time counts exactly."""
    rec, store = _drive(tmp_path / "store")
    assert rec.evicted > 0, "ring never wrapped — test is vacuous"
    man = store.manifest
    assert any(s["kind"] == "summary" for s in man["segments"]), (
        "nothing compacted — test is vacuous"
    )
    reader = store.reader()
    assert reader.counts() == rec.counts()
    assert _journal_counter(reader) == rec.counts()
    assert _conservation(man) == rec.counts()
    # the live scrape agrees with the store scrape, counter for counter
    assert _journal_counter(reader) == _journal_counter(rec)


def test_counts_exact_after_retention(tmp_path):
    """Retention deletes the oldest segments but folds their per-kind
    counts into the ``retired`` ledger — all-time counts survive the
    disk bound, and closed-segment bytes respect it."""
    bound = 26 << 10
    rec, store = _drive(tmp_path / "store", chunks=20, retain_bytes=bound)
    man = store.manifest
    assert man["retired"]["segments"] >= 1, "nothing retired — vacuous"
    closed = sum(s["bytes"] for s in man["segments"])
    assert closed <= bound
    reader = store.reader()
    assert reader.counts() == rec.counts()
    assert _journal_counter(reader) == rec.counts()
    assert _conservation(man) == rec.counts()
    # retired detail is gone from events() but not from the ledger
    assert man["retired"]["counts"].get("step_latency", 0) > 0


def test_missed_ledger_accounts_for_between_drain_eviction(tmp_path):
    """Events the ring evicts BETWEEN drains are unrecoverable; the
    manifest must say so (``missed``) instead of silently shorting the
    conservation sum."""
    rec = StepRecorder(capacity=8, host="h0", pid=1)
    store = JournalStore(str(tmp_path / "s"), segment_events=1000)
    store.drain(rec)
    # 50 events through an 8-slot ring with no drain in between: most
    # are gone before the next drain can see them
    for i in range(50):
        rec.record("step_time", step=i, seconds=0.001)
    store.drain(rec)
    man = store.manifest
    assert man["missed"].get("step_time", 0) > 0
    assert _conservation(man) == rec.counts()
    assert store.reader().counts() == rec.counts()


# ===================================================== durability


def test_rotation_checksums_and_verify_detects_corruption(tmp_path):
    rec, store = _drive(tmp_path / "store", compact_after=10**6)
    man = store.manifest
    raws = [s for s in man["segments"] if s["kind"] == "raw"]
    assert len(raws) >= 2, "rotation never closed a segment — vacuous"
    # staged-rename publish leaves no droppings behind
    assert not [
        n for n in os.listdir(tmp_path / "store") if ".tmp-" in n
    ]
    reader = StoreReader(str(tmp_path / "store"))
    reader.verify()  # every sha256 matches
    # flip one byte of a closed segment: verify must name the member
    victim = os.path.join(str(tmp_path / "store"), raws[0]["name"])
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(victim, "wb") as f:
        f.write(blob)
    with pytest.raises(StoreCorruptError) as ei:
        StoreReader(str(tmp_path / "store")).verify()
    assert raws[0]["name"] in str(ei.value)


def test_restart_resumes_watermark_no_duplicates(tmp_path):
    """A supervisor restart re-opens the same root: the new writer must
    resume from ``drained_seq``, persisting nothing twice and nothing
    already covered — the exactly-once contract."""
    rec = StepRecorder(capacity=256, host="h0", pid=1)
    store = JournalStore(str(tmp_path / "s"), segment_events=10**6)
    record_chunk_steps(rec, 0, 0.001, [0] * 10)
    store.drain(rec)
    before = len(store.reader().events())

    # "restart": a fresh JournalStore over the same root + recorder
    store2 = JournalStore(str(tmp_path / "s"), segment_events=10**6)
    persisted = store2.drain(rec)
    # the drain journals itself, so exactly the one store_drain row is
    # new — none of the 10 steps re-persist
    assert persisted == 1
    record_chunk_steps(rec, 10, 0.001, [0] * 5)
    store2.drain(rec)
    rows = store2.reader().events()
    seqs = [r["seq"] for r in rows]
    assert len(seqs) == len(set(seqs)), "duplicate seq after restart"
    assert len([r for r in rows if r["kind"] == "step_latency"]) == 15
    assert len(rows) > before
    assert store2.reader().counts() == rec.counts()


def test_drain_rejects_new_recorder_incarnation(tmp_path):
    """A FRESH recorder (seq space restarted) draining into an existing
    store would have every event silently skipped by the watermark and
    then booked as missed. All-time counts are monotone for the real
    writer, so the regression is detectable — drain must refuse loudly
    rather than lose data."""
    rec = StepRecorder(capacity=64, host="h0", pid=1)
    store = JournalStore(str(tmp_path / "s"), segment_events=10**6)
    record_chunk_steps(rec, 0, 0.001, [0] * 20)
    store.drain(rec)

    fresh = StepRecorder(capacity=64, host="h0", pid=1)
    record_chunk_steps(fresh, 0, 0.001, [0] * 5)
    store2 = JournalStore(str(tmp_path / "s"), segment_events=10**6)
    with pytest.raises(ValueError, match="regressed|incarnation"):
        store2.drain(fresh)
    # nothing was persisted or mis-booked by the refused drain
    man = store2.reader().manifest
    assert man["missed"] == {}
    assert man["counts"]["step_latency"] == 20
    # a recorder rebuilt from the store resumes cleanly
    rebuilt = store2.reader().to_recorder()
    n = store2.drain(rebuilt)
    assert n == 1  # just its own store_drain row
    assert store2.reader().counts() == rebuilt.counts()


def test_store_drain_journals_itself(tmp_path):
    rec = StepRecorder(capacity=64, host="h0", pid=1)
    store = JournalStore(str(tmp_path / "s"))
    rec.record("step_time", step=0, seconds=0.001)
    store.drain(rec)
    store.drain(rec)
    rows = store.reader().events("store_drain")
    assert len(rows) == 2
    assert rows[0]["after_seq"] == 0
    assert rows[1]["after_seq"] > 0
    for r in rows:
        assert r["segment"].startswith("seg_")
    assert store.reader().counts()["store_drain"] == 2


def test_close_flushes_and_helpers(tmp_path):
    rec = StepRecorder(capacity=64, host="h0", pid=1)
    root = tmp_path / "runs" / "a" / "store"
    store = JournalStore(str(root))
    rec.record("step_time", step=0, seconds=0.001)
    store.close(rec)  # final drain + rotate: nothing left active
    man = StoreReader(str(root)).manifest
    assert man["active"] is None
    assert store_lib.is_store(str(root))
    assert not store_lib.is_store(str(tmp_path))
    assert store_lib.list_stores(str(tmp_path)) == [str(root)]
    store_lib.wipe(str(root))
    assert not os.path.exists(root)


# ===================================================== compaction


def test_compaction_preserves_non_step_and_quantiles(tmp_path):
    """Every non-step event survives compaction verbatim; the per-step
    stream collapses to ``store_window`` sketches whose merged quantile
    equals the live ``Histogram``'s — same edges, same answer."""
    rec, store = _drive(tmp_path / "store")
    reader = store.reader()
    man = store.manifest
    windows = reader.events("store_window")
    assert windows, "no summary rows — vacuous"
    # alerts recorded inside compacted segments are still there, with
    # their payloads intact
    live_alerts = [e.data for e in rec.events("alert")]
    stored_alerts = reader.events("alert")
    assert len(stored_alerts) == rec.counts()["alert"]
    for row in stored_alerts:
        assert row["rule"] == "imbalance_ratio"
        assert row["severity"] == "WARN"
    # the ring only retains the tail; the store has the full history
    assert len(stored_alerts) >= len(live_alerts)

    # quantile exactness: live histogram over every recorded latency
    live = metrics_lib.Histogram((), metrics_lib.STEP_TIME_EDGES)
    for c in range(16):
        for _ in range(40):
            live.observe(0.002 * (1 + (c % 3)))
    merged = reader.latency_histogram()
    assert merged._bucket_counts == live._bucket_counts
    assert merged.count == live.count
    for q in (0.5, 0.9, 0.99):
        assert merged.quantile(q) == live.quantile(q)
    # window rows carry the exact per-kind counts of their span
    total = {}
    for w in windows:
        for k, v in w["counts"].items():
            total[k] = total.get(k, 0) + v
    summary_counts = {}
    for seg in man["segments"]:
        if seg["kind"] == "summary":
            for k, v in seg["counts"].items():
                if k in store_lib.COMPACT_KINDS:
                    summary_counts[k] = summary_counts.get(k, 0) + v
    assert total == summary_counts


def test_to_recorder_pins_alltime_counts(tmp_path):
    rec, store = _drive(tmp_path / "store")
    replay = store.reader().to_recorder()
    assert replay.counts() == rec.counts()
    # the replayed ring serves the retained tail for health rules
    assert replay.events("step_latency")


# ==================================================== query plane


def _rows(spec):
    """Hand-built envelope rows: (kind, host, pid, seq, time, extra)."""
    out = []
    for kind, host, pid, seq, t, extra in spec:
        row = {"kind": kind, "host": host, "pid": pid, "seq": seq,
               "time": t}
        row.update(extra)
        out.append(row)
    return out


def test_query_filters_and_groups():
    rows = _rows([
        ("step_latency", "a", 1, 1, 10.0, {"step": 5, "seconds": 0.1}),
        ("step_latency", "a", 1, 2, 11.0, {"step": 6, "seconds": 0.2}),
        ("alert", "a", 1, 3, 12.0, {"rule": "x", "ctx_trace": "t1",
                                    "ctx_step": 6}),
        ("migrate_step", "b", 2, 1, 13.0,
         {"step": 7, "sent_per_rank": [3, 0], "received_per_rank": [0, 3]}),
    ])
    assert [r["seq"] for r in filter_rows(rows, kind="alert")] == [3]
    assert len(filter_rows(rows, kind="step_latency,alert")) == 3
    # step bounds match payload step AND ctx_step envelopes
    got = filter_rows(rows, step_min=6, step_max=6)
    assert sorted(r["kind"] for r in got) == ["alert", "step_latency"]
    assert [r["host"] for r in filter_rows(rows, host="b")] == ["b"]
    assert filter_rows(rows, trace="t1")[0]["kind"] == "alert"
    assert filter_rows(rows, ctx={"trace": "t1"})[0]["seq"] == 3
    assert filter_rows(rows, since=12.5)[0]["kind"] == "migrate_step"
    assert filter_rows(rows, until=10.0)[0]["seq"] == 1

    groups = group_rows(rows, "kind")
    assert sorted(groups) == ["alert", "migrate_step", "step_latency"]
    # vrank explodes per-rank vectors into scalar slices
    by_rank = group_rows(rows, "vrank")
    assert sorted(by_rank) == ["0", "1"]
    assert by_rank["0"][0]["sent"] == 3
    assert by_rank["1"][0]["received"] == 3
    with pytest.raises(QueryError):
        group_rows(rows, "nope")


def test_query_window_aggregate_ops():
    rows = _rows([
        ("step_latency", "a", 1, i, float(i), {"step": i,
                                               "seconds": 0.001 * (i + 1)})
        for i in range(10)
    ])
    series = window_aggregate(rows, op="count", window_s=5.0)
    assert [w["n"] for w in series] == [5, 5]
    assert [w["value"] for w in series] == [5.0, 5.0]
    rate = window_aggregate(rows, op="rate", window_s=5.0)
    assert rate[0]["value"] == pytest.approx(1.0)
    mean = window_aggregate(rows, op="mean", window_s=5.0)
    assert mean[0]["value"] == pytest.approx(0.003)
    # hand-checkable EMA: window means are 0.003 and 0.008
    ema = window_aggregate(rows, op="ema", window_s=5.0, ema_alpha=0.5)
    assert ema[0]["value"] == pytest.approx(0.003)
    assert ema[1]["value"] == pytest.approx(0.5 * 0.008 + 0.5 * 0.003)
    # quantiles answer with the Histogram's bucketed upper bound
    h = metrics_lib.Histogram((), metrics_lib.STEP_TIME_EDGES)
    for i in range(10):
        h.observe(0.001 * (i + 1))
    p99 = window_aggregate(rows, op="p99", window_s=100.0)
    assert p99[0]["value"] == h.quantile(0.99)
    with pytest.raises(QueryError):
        window_aggregate(rows, op="p12")
    with pytest.raises(QueryError):
        window_aggregate(rows, op="count", window_s=0.0)


def test_query_quantile_merges_store_sketches(tmp_path):
    """A query spanning raw + compacted history answers the same p99 as
    the all-raw run — sketches are the histogram, not an estimate."""
    rec, store = _drive(tmp_path / "store")
    reader = store.reader()
    reply = run_query(reader, {"agg": "p99", "window_s": "1e9",
                               "kind": "step_latency,store_window"})
    (window,) = reply["series"]
    assert window["value"] == reader.latency_histogram().quantile(0.99)
    assert window["n"] == 16 * 40


def test_query_grammar_errors_and_limit():
    rec = StepRecorder(capacity=32, host="h", pid=1)
    for i in range(8):
        rec.record("step_time", step=i, seconds=0.001)
    with pytest.raises(QueryError, match="unknown query parameter"):
        run_query(rec, {"bogus": "1"})
    with pytest.raises(QueryError, match="bad integer"):
        run_query(rec, {"step_min": "abc"})
    with pytest.raises(QueryError, match="bad number"):
        run_query(rec, {"since": "abc"})
    with pytest.raises(QueryError, match="limit"):
        run_query(rec, {"limit": "0"})
    reply = run_query(rec, {"kind": "step_time", "limit": "3"})
    assert reply["matched"] == 8
    # newest kept under the cap
    assert [r["step"] for r in reply["events"]] == [5, 6, 7]
    by = run_query(rec, {"by": "kind"})
    assert by["groups"] == {"step_time": 8}


def test_query_cursor_semantics():
    rows = _rows([
        ("a", "h", 1, i, float(i), {}) for i in range(1, 7)
    ])
    page = events_page(rows, cursor=None, limit=4)
    assert [r["seq"] for r in page["events"]] == [1, 2, 3, 4]
    assert page["cursor"] == "h:1:4"
    assert page["remaining"] == 2
    page2 = events_page(rows, cursor=page["cursor"], limit=4)
    assert [r["seq"] for r in page2["events"]] == [5, 6]
    assert page2["remaining"] == 0
    # exhausted: the reply echoes the input cursor, never regresses
    page3 = events_page(rows, cursor=page2["cursor"], limit=4)
    assert page3["events"] == [] and page3["cursor"] == page2["cursor"]
    # evicted cursor: rows 1-3 compacted away, resume at seq 4 (no
    # duplicates, no skips of retained rows)
    page4 = events_page(rows[3:], cursor="h:1:2", limit=10)
    assert [r["seq"] for r in page4["events"]] == [4, 5, 6]
    # unknown shard replays everything
    page5 = events_page(rows, cursor="other:9:3", limit=10)
    assert len(page5["events"]) == 6
    with pytest.raises(QueryError, match="bad cursor"):
        events_page(rows, cursor="nocolons")
    with pytest.raises(QueryError, match="limit"):
        events_page(rows, cursor=None, limit=0)


def test_rows_of_sources_agree(tmp_path):
    """One query plane, every source: live recorder, JSONL shard file
    and store reader rows agree on the shared span."""
    rec = StepRecorder(capacity=256, host="h0", pid=1)
    for i in range(6):
        rec.record("step_time", step=i, seconds=0.001)
    store = JournalStore(str(tmp_path / "s"))
    store.drain(rec)
    # shard written after the drain: all three sources cover the same
    # span, store_drain event included
    shard = tmp_path / "shard.jsonl"
    rec.to_jsonl(str(shard))

    live = query_lib.rows_of(rec)
    file_rows = query_lib.rows_of(str(shard))
    stored = query_lib.rows_of(store.reader())
    key = lambda r: (r["seq"], r["kind"])  # noqa: E731
    live_keys = [key(r) for r in live]
    assert "store_drain" in {k[1] for k in live_keys}
    assert [key(r) for r in file_rows] == live_keys
    assert [key(r) for r in stored] == live_keys


# ======================================================== service


def _spawn_serve(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, SERVE] + args + ["--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO_ROOT, env=env,
    )
    watchdog = threading.Timer(120, proc.kill)
    watchdog.start()
    line = proc.stdout.readline()
    m = re.search(r"http://([\d.]+):(\d+)/metrics", line)
    assert m, (line, proc.poll(),
               proc.stderr.read() if proc.poll() is not None else "")
    return proc, watchdog, f"http://{m.group(1)}:{m.group(2)}"


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        assert r.status == 200
        return json.loads(r.read().decode("utf-8"))


def test_http_query_and_events_over_store(tmp_path):
    """The served history plane: a compacted store behind
    ``metrics_serve --store`` answers /query aggregations and a full
    /events cursor walk; the grammar's 400 surface round-trips."""
    rec, store = _drive(tmp_path / "store")
    store.close(rec)
    proc, watchdog, base = _spawn_serve(["--store", str(tmp_path / "store")])
    try:
        by = _get_json(base + "/query?by=kind")
        assert by["groups"]["alert"] == rec.counts()["alert"]
        assert "store_window" in by["groups"]
        p99 = _get_json(
            base + "/query?agg=p99&window_s=1e9"
            "&kind=step_latency,store_window"
        )
        (window,) = p99["series"]
        assert window["value"] == store.reader().latency_histogram(
        ).quantile(0.99)
        # /metrics over the same store scrapes the exact all-time counts
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            text = r.read().decode("utf-8")
        line = [
            ln for ln in text.splitlines()
            if ln.startswith("grid_journal_events_total")
            and 'kind="step_latency"' in ln
        ]
        assert line and float(line[0].rsplit(" ", 1)[1]) == float(
            rec.counts()["step_latency"]
        )
        # cursor walk to exhaustion: every retained row exactly once
        seen, cursor = [], ""
        while True:
            page = _get_json(
                base + f"/events?limit=100&cursor={cursor}"
            )
            seen.extend(page["events"])
            cursor = page["cursor"]
            if page["remaining"] == 0 and not page["events"]:
                break
        keys = [(r["host"], r["pid"], r["seq"]) for r in seen]
        assert len(keys) == len(set(keys)), "cursor walk duplicated rows"
        assert len(seen) == len(store.reader().events())
        # a bad parameter is a 400 with the offending name, not a 500
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/query?bogus=1", timeout=30)
        assert ei.value.code == 400
        assert b"bogus" in ei.value.read()
    finally:
        watchdog.cancel()
        proc.terminate()
        proc.wait(timeout=10)


def test_metrics_serve_concurrency_tracer_clean():
    """The ISSUE 18 concurrency satellite: parallel /metrics + /query +
    /events (cursor-resumed) against a LIVE recorder being written by a
    step thread, with the runtime thread sanitizer armed — every ring
    access must go through the lock (zero violations)."""
    spec = importlib.util.spec_from_file_location("_serve_mod", SERVE)
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)

    rec = StepRecorder(capacity=512, host="h0", pid=1)
    handler = serve.make_handler(lambda: rec)
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    server.daemon_threads = True
    srv_thread = threading.Thread(target=server.serve_forever, daemon=True)
    srv_thread.start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    errors = []

    def writer():
        for i in range(300):
            rec.record("step_time", step=i, seconds=0.001)

    def scraper():
        try:
            for _ in range(10):
                with urllib.request.urlopen(
                    base + "/metrics", timeout=30
                ) as r:
                    assert r.read().decode().rstrip().endswith("# EOF")
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    def querier():
        try:
            for _ in range(10):
                doc = _get_json(base + "/query?agg=count&window_s=60")
                assert "series" in doc
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def streamer():
        try:
            cursor, got = "", 0
            for _ in range(10):
                page = _get_json(
                    base + f"/events?limit=64&cursor={cursor}"
                )
                got += len(page["events"])
                cursor = page["cursor"]
            assert got > 0
        except Exception as e:  # pragma: no cover
            errors.append(e)

    try:
        with ThreadAccessTracer(rec) as tracer:
            threads = [threading.Thread(target=writer, daemon=True)]
            threads += [
                threading.Thread(target=fn, daemon=True)
                for fn in (scraper, scraper, querier, streamer)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not errors, errors
            tracer.assert_clean()
            assert tracer.violations() == []
            assert len(tracer.by_thread()) >= 3, (
                "concurrency never happened — test is vacuous"
            )
    finally:
        server.shutdown()
        server.server_close()
    assert rec.counts()["step_time"] == 300


def test_driver_drains_store_at_boundaries(tmp_path):
    """Service integration: a driver with ``store_dir`` set leaves a
    complete, verified store behind — every step's latency row
    persisted despite the ring, counts byte-equal the live journal."""
    from mpi_grid_redistribute_tpu.service import DriverConfig, ServiceDriver

    cfg = DriverConfig(
        grid_shape=(2, 2, 2),
        n_local=128,
        steps=24,
        seed=3,
        backend="numpy",
        snapshot_every=4,
        snapshot_dir=str(tmp_path / "snaps"),
        store_dir=str(tmp_path / "store"),
        store_segment_events=64,
    )
    rec = StepRecorder(capacity=64, host="h0", pid=1)
    driver = ServiceDriver(cfg, recorder=rec)
    driver.run()
    driver.close()
    reader = StoreReader(str(tmp_path / "store"), verify=True)
    assert reader.counts() == rec.counts()
    latencies = reader.events("step_latency")
    assert len(latencies) == 24, "boundary drains missed steps"
    # driver steps are 1-based (step is incremented before the boundary)
    assert sorted(r["step"] for r in latencies) == list(range(1, 25))
    assert reader.counts()["store_drain"] >= 24 // 4


def test_supervised_restart_store_no_duplicates(tmp_path):
    """The watermark across real restarts: a crash-injected supervised
    run re-opens the same store root; no (host, pid, seq) persists
    twice and the final counts still match the shared journal."""
    from mpi_grid_redistribute_tpu.service import (
        CrashFault,
        DriverConfig,
        FaultPlan,
        RestartPolicy,
        ServiceDriver,
        Supervisor,
    )

    cfg = DriverConfig(
        grid_shape=(2, 2, 2),
        n_local=128,
        steps=24,
        seed=3,
        backend="numpy",
        snapshot_every=4,
        snapshot_dir=str(tmp_path / "snaps"),
        store_dir=str(tmp_path / "store"),
    )
    rec = StepRecorder(capacity=4096, host="h0", pid=1)
    faults = FaultPlan([CrashFault(10)])

    def factory(grid_shape=None):
        c = cfg
        if grid_shape is not None:
            c = dataclasses.replace(c, grid_shape=tuple(grid_shape))
        return ServiceDriver(c, recorder=rec, faults=faults)

    sup = Supervisor(
        factory,
        policy=RestartPolicy(
            max_restarts=3, backoff_base_s=0.01, backoff_cap_s=0.02,
        ),
        recorder=rec,
        sleep_fn=lambda s: None,
    )
    verdict = sup.run()
    assert verdict.ok is True, verdict
    assert rec.counts().get("restart", 0) >= 1, "no restart?"
    reader = StoreReader(str(tmp_path / "store"), verify=True)
    rows = reader.events()
    keys = [(r["host"], r["pid"], r["seq"]) for r in rows]
    assert len(keys) == len(set(keys)), "restart duplicated rows"
    assert reader.counts() == rec.counts()


# ======================================================= overhead


def test_drain_overhead_under_2pct(rng, _devices, tmp_path):
    """Acceptance: boundary drains (journal -> fsync'd segment +
    manifest publish) add <= 2% to the config1-style steady state —
    the same paired-delta median protocol as the recorder+metrics gate
    (test_metrics.py), with the drain as the only difference between
    the legs."""
    import gc
    import time

    import jax

    from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
    from mpi_grid_redistribute_tpu.telemetry import record_migrate_steps

    grid = ProcessGrid((2, 2, 2))
    n_local = 2048
    n = grid.nranks * n_local
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=Domain(0.0, 1.0, periodic=True), grid=grid, dt=0.02,
        capacity=n_local // 4, n_local=n_local,
    )
    # 128 steps per sample for the same reason as the metrics gate: the
    # drain path scales with the journal window, so the ratio is
    # steps-invariant, but the host's scheduler wobble needs the longer
    # loop to stay under a 2% signal
    steps = 128
    loop = nbody.make_migrate_loop(cfg, mesh, steps)
    pos = rng.random((n, 3), dtype=np.float32)
    vel = (0.2 * (rng.random((n, 3), dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    alive = np.ones((n,), bool)
    jax.block_until_ready(loop(pos, vel, alive))  # compile

    store = JournalStore(
        str(tmp_path / "store"), segment_events=4096,
        retain_bytes=8 << 20, compact_after=2,
    )
    base_rec = StepRecorder()
    obs_rec = StepRecorder()

    def sample(observe):
        rec = obs_rec if observe else base_rec
        t0 = time.perf_counter()
        out = loop(pos, vel, alive)
        jax.block_until_ready(out)
        stats_host = jax.tree.map(np.asarray, out[3])
        # both legs journal (that cost is the metrics gate's budget);
        # only the observed leg drains to disk
        record_migrate_steps(rec, stats_host, rank_totals=True)
        if observe:
            store.drain(rec)
        return time.perf_counter() - t0

    def batch_median():
        deltas = []
        gc.collect()
        gc.disable()
        try:
            for k in range(9):
                if k % 2:
                    o = sample(True)
                    b = sample(False)
                else:
                    b = sample(False)
                    o = sample(True)
                deltas.append((o - b) / b)
        finally:
            gc.enable()
        return float(np.median(deltas)), deltas

    overhead, deltas = batch_median()
    if overhead > 0.02:
        # confirm before failing, exactly like the metrics gate: a real
        # regression reproduces, a scheduler excursion does not
        overhead2, deltas2 = batch_median()
        if overhead2 < overhead:
            overhead, deltas = overhead2, deltas2
    assert overhead <= 0.02, (
        f"store drain overhead {overhead:.1%} > 2% (median of "
        f"{len(deltas)} paired samples, {steps}-step loop, best of two "
        f"batches; deltas {[f'{d:.1%}' for d in deltas]})"
    )
    # the drained store is real, not a no-op: every sample persisted
    assert store.reader().counts().get("migrate_step", 0) > 0


# ===================================================== CLI smokes


def _run_cli(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True,
        cwd=REPO_ROOT, env=env, timeout=300, **kw,
    )


def test_storecheck_cli_clean_and_real_store(tmp_path):
    out = _run_cli([os.path.join("scripts", "storecheck.py"), "--check"])
    assert out.returncode == 0, out.stdout + out.stderr
    # point it at a real store root built here
    rec, store = _drive(tmp_path / "store")
    store.close(rec)
    out = _run_cli(
        [os.path.join("scripts", "storecheck.py"), str(tmp_path / "store")]
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_grid_top_once_renders_store(tmp_path):
    rec, store = _drive(tmp_path / "store")
    store.close(rec)
    out = _run_cli([
        os.path.join("scripts", "grid_top.py"),
        "--store", str(tmp_path / "store"), "--once",
    ])
    assert out.returncode == 0, out.stdout + out.stderr
    assert "steps" in out.stdout
    assert "p99" in out.stdout
    # an unreadable store is exit 1, not a stack trace
    bad = _run_cli([
        os.path.join("scripts", "grid_top.py"),
        "--store", str(tmp_path / "nope"), "--once",
    ])
    assert bad.returncode == 1
    assert "Traceback" not in bad.stderr


def test_history_cli_indexes_runs(tmp_path):
    rec, store = _drive(tmp_path / "runs" / "r1" / "store")
    store.close(rec)
    out = _run_cli([
        os.path.join("scripts", "history.py"), "--json",
        "--stores", str(tmp_path / "runs"),
    ])
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    (entry,) = doc["stores"]
    assert entry["events_total"] == sum(rec.counts().values())
    assert entry["steps"] == rec.counts()["step_latency"]
