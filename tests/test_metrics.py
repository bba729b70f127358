"""Metrics plane (telemetry/metrics.py, aggregate.py) — ISSUE 5 gates.

Five contracts, each tested against hand math or a real scrape:

* registry — Counter/Gauge/Histogram semantics (pow2 bucket edges,
  exact-edge placement, label validation, reserved-suffix rejection)
  against hand-computed fixtures;
* exposition — ``render_openmetrics`` output must survive a STRICT
  hand-written OpenMetrics parser (every sample belongs to a declared
  family, counters end ``_total``, buckets are cumulative and
  non-decreasing, ``+Inf`` equals ``_count``, one trailing ``# EOF``),
  and a real HTTP scrape of ``scripts/metrics_serve.py`` must serve it;
* exactness — ``grid_journal_events`` counters equal the recorder's
  all-time counts even after ring eviction, and a merged pod journal's
  ``counts()`` equal the sum of per-shard counts (property-tested on
  random shards);
* purity — the scrape path (metrics.py, aggregate.py) must be loadable
  without jax ever entering ``sys.modules`` (runtime subprocess check;
  gridlint G007 holds the static half in test_gridlint.py);
* gating — the schema-drift gate (journaled kinds vs SCHEMA.md, both
  directions).
"""

import ast
import importlib.util
import json
import math
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
    HealthMonitor,
    MergedJournal,
    MetricsRegistry,
    StepRecorder,
    from_journal,
    merge_journals,
    pow2_edges,
)
from mpi_grid_redistribute_tpu.telemetry import metrics as metrics_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "mpi_grid_redistribute_tpu")
TELEMETRY = os.path.join(PACKAGE, "telemetry")
SERVE = os.path.join(REPO_ROOT, "scripts", "metrics_serve.py")


# ------------------------------------------------------------ hand math


def test_pow2_edges_hand_math():
    assert pow2_edges(0, 3) == (1.0, 2.0, 4.0, 8.0)
    assert pow2_edges(-2, 1) == (0.25, 0.5, 1.0, 2.0)
    edges = pow2_edges(-14, 4)
    assert len(edges) == 19
    assert edges[0] == 2.0 ** -14 and edges[-1] == 16.0


def test_counter_and_gauge_hand_math():
    reg = MetricsRegistry()
    c = reg.counter("hits", "hand-math counter", labelnames=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2.5)
    c.labels(kind="b").inc(0)
    assert c.labels(kind="a").value == 3.5
    assert c.labels(kind="b").value == 0
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)
    g = reg.gauge("depth", "hand-math gauge")
    g.labels().set(7)
    g.labels().inc(3)
    g.labels().dec(2.5)
    assert g.labels().value == 7.5


def test_histogram_bucket_hand_math():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "hand-math histogram", edges=pow2_edges(0, 3))
    child = h.labels()
    # exact edge values land in their own bucket (le is inclusive)
    for v in (0.5, 1.0, 2.0, 3.0, 8.0, 100.0):
        child.observe(v)
    cum = child.cumulative()
    assert [le for le, _ in cum] == [1.0, 2.0, 4.0, 8.0, math.inf]
    assert [n for _, n in cum] == [2, 3, 4, 5, 6]
    assert child.count == 6
    assert child.sum == pytest.approx(114.5)


def test_histogram_quantile_hand_math():
    h = metrics_lib.Histogram((), pow2_edges(0, 3))  # edges 1,2,4,8
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(0.0)
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)
    assert h.quantile(0.99) == 0.0  # empty histogram, not an error
    for v in (0.5, 1.0, 3.0, 8.0):
        h.observe(v)
    # bucketed UPPER bound: smallest edge covering ceil(q * count)
    assert h.quantile(0.5) == 1.0   # target 2 of 4 -> le=1 bucket (2)
    assert h.quantile(0.75) == 4.0  # target 3 -> le=4 bucket
    assert h.quantile(1.0) == 8.0
    h.observe(100.0)  # overflow bucket
    assert h.quantile(1.0) == math.inf
    assert h.quantile(0.8) == 8.0   # target 4 of 5 still inside edges


def test_dropped_edges_zero_bucket_keeps_p99_of_zeros_zero():
    # grid_dropped_rows carries an explicit 0 edge: a loss-free window's
    # p99 must be 0, not 1, or the threshold=0 SLO would always breach
    assert metrics_lib.DROPPED_EDGES[0] == 0.0
    h = metrics_lib.Histogram((), metrics_lib.DROPPED_EDGES)
    for _ in range(100):
        h.observe(0)
    assert h.quantile(0.99) == 0.0
    h.observe(3)  # a single lossy step is visible at the tail
    assert h.quantile(1.0) == 4.0


def test_family_shape_and_label_validation():
    reg = MetricsRegistry()
    c = reg.counter("ops", "ops", labelnames=("kind",))
    # same declaration is idempotent, conflicting shape raises
    assert reg.counter("ops", "ops", labelnames=("kind",)) is c
    with pytest.raises(ValueError):
        reg.counter("ops", "ops", labelnames=("other",))
    with pytest.raises(ValueError):
        reg.gauge("ops", "ops")
    # label set must match the declaration exactly
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.labels()
    # OpenMetrics reserves the suffixes the renderer appends
    for bad in ("x_total", "x_bucket", "x_sum", "x_count", "x_created"):
        with pytest.raises(ValueError):
            reg.counter(bad, "reserved")
    with pytest.raises(ValueError):
        reg.counter("0bad", "bad name")


def _mixed_recorder():
    rec = StepRecorder(host="h0", pid=7)
    rec.record("migrate_step", step=0, sent=5, received=5, backlog=2,
               dropped_recv=0, population=100)
    rec.record("migrate_step", step=1, sent=3, received=3, backlog=1,
               dropped_recv=1, population=100)
    rec.record("step_time", seconds=0.004)
    rec.record("step_time", seconds=0.006)
    rec.record("fast_path", step=0, taken=1, movers=12, movers_max_rank=4)
    rec.record("fast_path", step=1, taken=0, movers=900, movers_max_rank=300)
    rec.record("alert", rule="backlog_growth", severity="warn", reason="x")
    rec.record("capacity_grow", which="send", old=10, new=20, needed=15,
               dropped=0, call=1)
    rec.record("mover_cap_grow", old=64, new=128, peak_movers=90)
    rec.record("flow_snapshot", steps=2, n_ranks=8, moved_rows_total=42,
               imbalance=1.25, population=[20, 12, 10, 8, 2, 0, 28, 20],
               top_pairs=[[0, 1, 30]])
    return rec


def test_from_journal_hand_math():
    rec = _mixed_recorder()
    reg = from_journal(rec)

    def val(name, **labels):
        return reg.get(name).labels(**labels).value

    assert val("grid_journal_events", kind="migrate_step") == 2
    assert val("grid_journal_events", kind="alert") == 1
    assert val("grid_journal_evicted_events") == 0
    assert val("grid_migrate_rows", direction="sent") == 8
    assert val("grid_migrate_rows", direction="received") == 8
    assert val("grid_migrate_rows", direction="backlog") == 3
    assert val("grid_migrate_rows", direction="dropped_recv") == 1
    assert val("grid_population_rows") == 100
    assert val("grid_backlog_rows") == 1          # latest step
    assert val("grid_fast_path_steps", taken="1") == 1
    assert val("grid_fast_path_steps", taken="0") == 1
    assert val("grid_capacity_rows", which="send") == 20
    assert val("grid_capacity_rows", which="mover") == 128
    assert val("grid_alerts", rule="backlog_growth", severity="warn") == 1
    assert val("grid_flow_moved_rows") == 42
    assert val("grid_flow_imbalance") == 1.25
    assert val("grid_rank_population", vrank="0") == 20
    assert val("grid_rank_population", vrank="5") == 0
    assert val("grid_rank_population", vrank="6") == 28
    assert len(reg.get("grid_rank_population").children()) == 8
    st = reg.get("grid_step_time_seconds").labels()
    assert st.count == 2 and st.sum == pytest.approx(0.010)
    mv = reg.get("grid_movers_per_step").labels()
    assert mv.count == 2 and mv.sum == 912
    # 0.004 and 0.006 both exceed 2^-8 s, land in the le=2^-7 s bucket
    cum = dict(st.cumulative())
    assert cum[2.0 ** -8] == 0 and cum[2.0 ** -7] == 2


def test_from_journal_service_slo_families():
    # the ISSUE 8 SLO surface: step_latency events feed both histograms,
    # restore events feed the corrupt-snapshot counter
    rec = StepRecorder(host="h0", pid=7)
    rec.record("step_latency", step=1, seconds=0.004, dropped=0)
    rec.record("step_latency", step=2, seconds=0.006, dropped=5)
    rec.record("restore", what="state", step=4, path="p",
               snapshots_skipped=2)
    rec.record("restore", what="journal", path="p")  # no skip field: +0
    reg = from_journal(rec)

    lat = reg.get("grid_step_latency_seconds").labels()
    assert lat.count == 2 and lat.sum == pytest.approx(0.010)
    drop = reg.get("grid_dropped_rows").labels()
    assert drop.count == 2
    assert dict(drop.cumulative())[0.0] == 1  # loss-free step visible
    assert drop.quantile(1.0) == 8.0          # the 5-row step's bucket
    assert reg.get("grid_snapshot_corrupt").labels().value == 2

    text = reg.render_openmetrics()
    assert 'grid_dropped_rows_bucket{le="0"} 1' in text
    assert "grid_snapshot_corrupt_total 2" in text
    assert "grid_step_latency_seconds_count 2" in text


def test_journal_counters_exact_after_ring_eviction():
    rec = StepRecorder(capacity=4, host="h0", pid=1)
    for s in range(10):
        rec.record("migrate_step", step=s, sent=1, received=1, backlog=0,
                   dropped_recv=0, population=8)
    assert len(rec.events()) == 4
    reg = from_journal(rec)
    fam = reg.get("grid_journal_events")
    # the counter comes from all-time counts(), NOT the retained window
    assert fam.labels(kind="migrate_step").value == 10
    assert reg.get("grid_journal_evicted_events").labels().value == 6
    assert rec.counts() == {"migrate_step": 10}


# ------------------------------------------- strict OpenMetrics parser

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_][a-zA-Z0-9_]*)"
    r"(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\",?)*)\})?"
    r" (\S+)$"
)
_LABEL_RE = re.compile(r"([a-zA-Z_][a-zA-Z0-9_]*)=\"((?:[^\"\\]|\\.)*)\"")


def _parse_openmetrics(text):
    """Strict hand parser: returns {family: (type, {sample_name:
    {labelstr: value}})} and raises AssertionError on any violation."""
    lines = text.splitlines()
    assert lines and lines[-1] == "# EOF", "must terminate with # EOF"
    assert sum(1 for l in lines if l == "# EOF") == 1
    families = {}   # name -> type
    helped = set()
    samples = {}    # family -> {sample name -> {label str -> float}}
    for line in lines[:-1]:
        assert line and not line.isspace(), "no blank lines"
        if line.startswith("# TYPE "):
            _, _, name, mtype = line.split(" ", 3)
            assert name not in families, f"duplicate TYPE for {name}"
            assert mtype in ("counter", "gauge", "histogram"), mtype
            families[name] = mtype
            samples[name] = {}
            continue
        if line.startswith("# HELP "):
            name = line.split(" ", 3)[2]
            assert name in families, f"HELP before TYPE for {name}"
            helped.add(name)
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        sname, labelstr, value = m.group(1), m.group(2) or "", m.group(3)
        fval = float(value)  # raises on malformed values
        fam = None
        for base, mtype in families.items():
            expect = {
                "counter": (base + "_total",),
                "gauge": (base,),
                "histogram": (base + "_bucket", base + "_sum",
                              base + "_count"),
            }[mtype]
            if sname in expect:
                fam = base
        assert fam is not None, f"sample {sname} belongs to no family"
        labels = dict(_LABEL_RE.findall(labelstr))
        key = tuple(sorted(labels.items()))
        assert key not in samples[fam].get(sname, {}), (
            f"duplicate sample {sname}{labels}"
        )
        samples[fam].setdefault(sname, {})[key] = fval
    assert helped == set(families), "every family needs a HELP line"
    # histogram invariants: cumulative non-decreasing, +Inf == _count
    for base, mtype in families.items():
        if mtype != "histogram":
            continue
        buckets = samples[base].get(base + "_bucket", {})
        series = {}
        for key, v in buckets.items():
            rest = tuple((k, x) for k, x in key if k != "le")
            le = dict(key)["le"]
            series.setdefault(rest, []).append((le, v))
        for rest, pts in series.items():
            les = [le for le, _ in pts]
            assert les[-1] == "+Inf", "last bucket must be +Inf"
            nums = [float(le) for le in les[:-1]]
            assert nums == sorted(nums), "le values must ascend"
            vals = [v for _, v in pts]
            assert vals == sorted(vals), "bucket counts must be cumulative"
            count = samples[base][base + "_count"][rest]
            assert vals[-1] == count, "+Inf bucket must equal _count"
    return families, samples


def test_render_openmetrics_passes_strict_parser():
    text = from_journal(_mixed_recorder()).render_openmetrics()
    families, samples = _parse_openmetrics(text)
    assert families["grid_journal_events"] == "counter"
    assert families["grid_step_time_seconds"] == "histogram"
    assert families["grid_population_rows"] == "gauge"
    # counters carry the _total suffix on the wire, not in the family
    key = (("kind", "migrate_step"),)
    assert samples["grid_journal_events"]["grid_journal_events_total"][
        key
    ] == 2
    # unsampled gauges render metadata but no misleading 0 samples
    assert samples["grid_flow_moved_rows"]  # sampled here
    text2 = from_journal(StepRecorder(host="h", pid=1)).render_openmetrics()
    fam2, samp2 = _parse_openmetrics(text2)
    assert samp2["grid_flow_moved_rows"] == {}
    assert samp2["grid_population_rows"] == {}
    assert samp2["grid_rank_population"] == {}


def test_rank_population_latest_snapshot_wins():
    """A later flow_snapshot replaces the per-vrank family outright —
    including DROPPING ghost vranks when the rank count shrinks."""
    rec = StepRecorder(host="h", pid=1)
    rec.record("flow_snapshot", steps=1, n_ranks=4, moved_rows_total=0,
               imbalance=2.0, population=[8, 0, 0, 0], top_pairs=[])
    rec.record("flow_snapshot", steps=2, n_ranks=2, moved_rows_total=3,
               imbalance=1.0, population=[4, 4], top_pairs=[])
    reg = from_journal(rec)
    fam = reg.get("grid_rank_population")
    assert len(fam.children()) == 2
    assert fam.labels(vrank="0").value == 4
    assert fam.labels(vrank="1").value == 4
    # a null population leaf (accumulator never fed one) is skipped,
    # leaving the previous snapshot's family intact
    rec.record("flow_snapshot", steps=3, n_ranks=2, moved_rows_total=3,
               imbalance=1.0, population=None, top_pairs=[])
    reg2 = from_journal(rec)
    assert len(reg2.get("grid_rank_population").children()) == 2


def test_label_value_escaping_round_trips():
    reg = MetricsRegistry()
    c = reg.counter("odd", "escape check", labelnames=("reason",))
    raw = 'a"b\\c\nd'
    c.labels(reason=raw).inc()
    text = reg.render_openmetrics()
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    _, samples = _parse_openmetrics(text)
    (key,) = samples["odd"]["odd_total"]
    assert dict(key)["reason"] == 'a\\"b\\\\c\\nd'  # still escaped on wire


# ------------------------------------------- multi-host merge property


KINDS = ("migrate_step", "step_time", "alert", "flow_snapshot",
         "capacity_grow")


def test_merge_equals_sum_property(rng, tmp_path):
    shards = []
    for i in range(5):
        rec = StepRecorder(host=f"host{i:02d}", pid=1000 + i)
        for s in range(int(rng.integers(0, 40))):
            kind = KINDS[int(rng.integers(0, len(KINDS)))]
            rec.record(kind, step=s, v=int(rng.integers(0, 9)))
        # wall-clock wobble, including backward steps the merge must
        # repair to monotone
        for j, e in enumerate(rec._ring):
            rec._ring[j] = e._replace(
                time=e.time + float(rng.normal(0.0, 0.5))
            )
        shards.append(rec)
    merged = merge_journals(shards)
    assert isinstance(merged, MergedJournal)
    expected = {}
    for rec in shards:
        for k, n in rec.counts().items():
            expected[k] = expected.get(k, 0) + n
    assert merged.counts() == expected
    assert len(merged) == sum(len(r.events()) for r in shards)
    per = merged.per_shard_counts()
    for rec in shards:
        assert per[(rec.host, rec.pid)] == rec.counts()
    # merged order: aligned time non-decreasing, intra-shard seq order
    # preserved exactly
    times = [e["t_aligned"] for e in merged.events()]
    assert times == sorted(times)
    for rec in shards:
        seqs = [e["seq"] for e in merged.events()
                if e["host"] == rec.host]
        assert seqs == sorted(seqs)
    # the same merge through JSONL shard files (the pod artifact path)
    paths = []
    for rec in shards:
        p = tmp_path / f"{rec.host}.{rec.pid}.jsonl"
        rec.to_jsonl(str(p))
        paths.append(str(p))
    refile = merge_journals(paths, align="start")
    assert refile.counts() == expected
    t0 = [e["t_aligned"] for e in refile.events()]
    assert t0 == sorted(t0) and (not t0 or t0[0] == 0.0)


def test_pod_steps_sum_and_concat():
    recs = []
    for i, (sent, pop) in enumerate(((5, 40), (7, 24))):
        rec = StepRecorder(host=f"h{i}", pid=i + 1)
        for s in range(3):
            rec.record("migrate_step", step=s, sent=sent, received=sent,
                       backlog=i, dropped_recv=0, population=pop,
                       sent_per_rank=[sent, 0], received_per_rank=[0, sent],
                       population_per_rank=[pop // 2, pop // 2])
        recs.append(rec)
    merged = merge_journals(recs)
    pod = merged.to_recorder(pod_steps=True)
    assert pod.host == "pod" and pod.counts() == {"migrate_step": 3}
    for e in pod.events("migrate_step"):
        assert e.data["sent"] == 12 and e.data["population"] == 64
        # per-rank vectors concatenate in shard order
        assert e.data["population_per_rank"] == [20, 20, 12, 12]
    stats = merged.pod_stats()
    assert stats.population.shape == (3, 4)
    assert int(stats.sent.sum()) == 3 * 12


# --------------------------------------------------- live HTTP scrape


def test_metrics_serve_scrapes_over_http(tmp_path):
    paths = []
    for i in range(2):
        rec = StepRecorder(host=f"h{i}", pid=i + 1)
        for s in range(4):
            rec.record("migrate_step", step=s, sent=3 - i, received=3 - i,
                       backlog=0, dropped_recv=0, population=64)
        p = tmp_path / f"shard{i}.jsonl"
        rec.to_jsonl(str(p))
        paths.append(str(p))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, SERVE, "--journal", paths[0], "--journal",
         paths[1], "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO_ROOT, env=env,
    )
    watchdog = threading.Timer(120, proc.kill)
    watchdog.start()
    try:
        line = proc.stdout.readline()   # "serving http://host:port/..."
        m = re.search(r"http://([\d.]+):(\d+)/metrics", line)
        assert m, (line, proc.poll(), proc.stderr.read() if proc.poll()
                   is not None else "")
        base = f"http://{m.group(1)}:{m.group(2)}"
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith(
                "application/openmetrics-text"
            )
            text = r.read().decode("utf-8")
        _, samples = _parse_openmetrics(text)
        # two 4-step shards pod-merge into 4 pod steps; row counters sum
        key = (("kind", "migrate_step"),)
        assert samples["grid_journal_events"][
            "grid_journal_events_total"][key] == 4
        dkey = (("direction", "sent"),)
        assert samples["grid_migrate_rows"][
            "grid_migrate_rows_total"][dkey] == 4 * (3 + 2)
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            assert r.status == 200
            verdict = json.loads(r.read().decode("utf-8"))
        assert verdict["status"] in ("OK", "WARN")
        # scraping twice re-snapshots, not accumulates
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            assert r.read().decode("utf-8").splitlines()[-1] == "# EOF"
    finally:
        watchdog.cancel()
        proc.terminate()
        proc.wait(timeout=10)


def test_journal_snapshotter_caches_unchanged_shards(tmp_path):
    """Scrape-storm contract (ISSUE 17): an unchanged shard set must not
    be re-parsed — the snapshotter caches the merged recorder keyed on
    every shard's (path, mtime, size) and invalidates on any growth."""
    spec = importlib.util.spec_from_file_location("_serve_mod", SERVE)
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)

    rec = StepRecorder(host="h", pid=1)
    rec.record("migrate_step", step=0, sent=1, received=1, backlog=0,
               dropped_recv=0, population=8)
    p = tmp_path / "shard.jsonl"
    rec.to_jsonl(str(p))
    snapshot, shutdown = serve.journal_snapshotter([str(p)], "wall")
    a = snapshot()
    assert a.counts() == {"migrate_step": 1}
    assert snapshot() is a          # quiescent journal: cache hit
    # the shard growing (size changes) invalidates on the next scrape
    rec.record("migrate_step", step=1, sent=1, received=1, backlog=0,
               dropped_recv=0, population=8)
    rec.to_jsonl(str(p))
    b = snapshot()
    assert b is not a
    assert b.counts() == {"migrate_step": 2}
    shutdown()


def test_incidents_endpoint_and_healthz_503(tmp_path):
    """The ISSUE 17 HTTP surface: a journal whose health verdict ALERTs
    must 503 on /healthz, and --incident-dir serves the flight-recorder
    bundle listing on /incidents (a 404 names all three endpoints)."""
    from mpi_grid_redistribute_tpu.telemetry import incident as incident_lib

    rec = StepRecorder(host="h", pid=1)
    for s in range(8):
        rec.record("migrate_step", step=s, sent=1, received=1,
                   backlog=100 * (s + 1), dropped_recv=0, population=64)
    bundles = tmp_path / "incidents"
    fr = incident_lib.FlightRecorder(rec, str(bundles), clock=lambda: 123.0)
    assert fr.capture(
        rule="backlog_growth", reason="monotone backlog", trigger="alert"
    ) is not None
    shard = tmp_path / "shard.jsonl"
    rec.to_jsonl(str(shard))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, SERVE, "--journal", str(shard),
         "--incident-dir", str(bundles), "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO_ROOT, env=env,
    )
    watchdog = threading.Timer(120, proc.kill)
    watchdog.start()
    try:
        line = proc.stdout.readline()
        m = re.search(r"http://([\d.]+):(\d+)/metrics", line)
        assert m, (line, proc.poll(), proc.stderr.read() if proc.poll()
                   is not None else "")
        base = f"http://{m.group(1)}:{m.group(2)}"
        with urllib.request.urlopen(base + "/incidents", timeout=30) as r:
            assert r.status == 200
            doc = json.loads(r.read().decode("utf-8"))
        assert [e["id"] for e in doc["incidents"]] == [
            "incident-0001-backlog_growth"
        ]
        entry = doc["incidents"][0]
        assert entry["rule"] == "backlog_growth"
        assert entry["captured_at"] == 123.0
        # the monotone backlog ALERTs: the probe sees 503, not 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz", timeout=30)
        assert ei.value.code == 503
        verdict = json.loads(ei.value.read().decode("utf-8"))
        assert verdict["status"] == "ALERT"
        # /metrics still renders well-formed OpenMetrics alongside
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            assert r.read().decode("utf-8").splitlines()[-1] == "# EOF"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope", timeout=30)
        assert ei.value.code == 404
        assert b"/incidents" in ei.value.read()
    finally:
        watchdog.cancel()
        proc.terminate()
        proc.wait(timeout=10)


def test_healthz_evaluate_is_read_only():
    rec = StepRecorder(host="h", pid=1)
    for s in range(8):
        rec.record("migrate_step", step=s, sent=1, received=1,
                   backlog=100 * (s + 1), dropped_recv=0, population=64)
    mon = HealthMonitor(rec)
    before = (dict(rec.counts()), rec.total_recorded)
    verdict = mon.evaluate(record=False)
    assert verdict["status"] == "ALERT"       # backlog grows monotonically
    assert (dict(rec.counts()), rec.total_recorded) == before
    # the recording evaluate() journals the same finding afterwards —
    # the read-only pass must not have consumed its novelty
    mon.evaluate()
    assert rec.counts().get("alert", 0) >= 1


# ------------------------------------------------- purity + schema gate


def test_scrape_path_loads_without_jax():
    """metrics.py/aggregate.py — the ISSUE 17 capture path (context.py,
    incident.py) and the ISSUE 18 history plane (store.py, query.py) —
    must be importable with jax absent from sys.modules — the runtime
    half of the G007 contract (a scrape, an incident capture or a store
    drain can never stall on device work it cannot even reach)."""
    code = (
        "import importlib.util, os, sys, types\n"
        f"tel = {TELEMETRY!r}\n"
        "pkg = types.ModuleType('scrape_pkg')\n"
        "pkg.__path__ = [tel]\n"
        "sys.modules['scrape_pkg'] = pkg\n"
        "for name in ('context', 'recorder', 'metrics', 'aggregate',\n"
        "             'incident', 'store', 'query'):\n"
        "    spec = importlib.util.spec_from_file_location(\n"
        "        'scrape_pkg.' + name, os.path.join(tel, name + '.py'))\n"
        "    mod = importlib.util.module_from_spec(spec)\n"
        "    sys.modules[spec.name] = mod\n"
        "    spec.loader.exec_module(mod)\n"
        "assert 'jax' not in sys.modules, 'scrape path pulled in jax'\n"
        "print('pure')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO_ROOT, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "pure"
    # static half: no jax import statement in the module sources
    for name in ("metrics.py", "aggregate.py", "context.py", "incident.py",
                 "store.py", "query.py"):
        with open(os.path.join(TELEMETRY, name), encoding="utf-8") as fh:
            src = fh.read()
        assert re.search(r"#\s*gridlint:\s*scrape-path", src), name
        assert not re.search(r"^\s*(?:import|from)\s+jax\b", src,
                             re.MULTILINE), f"{name} imports jax"


def _recorded_kinds():
    """Every literal event kind passed to .record()/.record_at() across
    the package (AST scan — grep would catch strings in comments)."""
    kinds = set()
    for dirpath, _, names in os.walk(PACKAGE):
        for fname in names:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("record", "record_at")
                        and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    continue
                kinds.add(node.args[0].value)
    return kinds


def test_schema_drift_gate():
    """SCHEMA.md and the code must agree on the event-kind set in BOTH
    directions: an undocumented kind and a documented-but-dead kind are
    equally schema drift."""
    with open(os.path.join(TELEMETRY, "SCHEMA.md"), encoding="utf-8") as fh:
        schema = fh.read()
    documented = set()
    for line in schema.splitlines():
        if line.startswith("### "):
            documented.update(re.findall(r"`([a-z_]+)`", line))
    recorded = _recorded_kinds()
    assert recorded, "AST scan found no journaled kinds — scan broken?"
    undocumented = recorded - documented
    dead = documented - recorded
    assert not undocumented, (
        f"journaled kinds missing from SCHEMA.md: {sorted(undocumented)}"
    )
    assert not dead, (
        f"SCHEMA.md documents kinds nothing records: {sorted(dead)}"
    )


# ------------------------------------------------- steady-state overhead


def test_recorder_plus_metrics_overhead_under_2pct(rng, _devices):
    """Acceptance: journaling + health + a full metrics scrape add <= 2%
    to the config1-style steady-state step (min-of-k protocol; the
    scrape is a host-side fold over the ring, so it must be noise
    against ms-scale device steps)."""
    import time

    import jax

    from mpi_grid_redistribute_tpu.domain import Domain, ProcessGrid
    from mpi_grid_redistribute_tpu.models import nbody
    from mpi_grid_redistribute_tpu.parallel import mesh as mesh_lib
    from mpi_grid_redistribute_tpu.telemetry import (
        FlowAccumulator,
        record_flow_snapshot,
        record_migrate_steps,
    )

    grid = ProcessGrid((2, 2, 2))
    n_local = 2048
    n = grid.nranks * n_local
    mesh = mesh_lib.make_mesh(grid)
    cfg = nbody.DriftConfig(
        domain=Domain(0.0, 1.0, periodic=True), grid=grid, dt=0.02,
        capacity=n_local // 4, n_local=n_local,
    )
    # 128 steps per sample: the observe path under test (per-step
    # journaling + the scrape over the journal) scales WITH the loop, so
    # the overhead ratio is steps-invariant — but the host's absolute
    # scheduler wobble is not, and at 32 steps it dominated a 2% gate
    # (paired deltas spread +-15%); the longer loop buys signal, not a
    # different measurement
    steps = 128
    loop = nbody.make_migrate_loop(cfg, mesh, steps)
    pos = rng.random((n, 3), dtype=np.float32)
    vel = (0.2 * (rng.random((n, 3), dtype=np.float32) - 0.5)).astype(
        np.float32
    )
    alive = np.ones((n,), bool)
    jax.block_until_ready(loop(pos, vel, alive))  # compile

    def sample(observe):
        rec = StepRecorder()
        mon = HealthMonitor(rec)
        t0 = time.perf_counter()
        out = loop(pos, vel, alive)
        jax.block_until_ready(out)
        stats_host = jax.tree.map(np.asarray, out[3])
        if observe:
            record_migrate_steps(rec, stats_host, rank_totals=True)
            acc = FlowAccumulator()
            acc.update(stats_host)
            record_flow_snapshot(rec, acc)
            mon.note_step_time((time.perf_counter() - t0) / steps)
            mon.evaluate()
            # the scrape itself: journal -> registry -> OpenMetrics text
            text = from_journal(rec).render_openmetrics()
            assert text.rstrip().endswith("# EOF")
        return time.perf_counter() - t0

    # median of paired base/observed deltas with GC held off, for the
    # same reason as test_flow's overhead gate: the in-suite loop
    # wobbles by several ms, so pairs share the slow drift and the
    # median rejects scheduler spikes a min-of-k difference cannot
    import gc

    def batch_median():
        deltas = []
        gc.collect()
        gc.disable()
        try:
            for k in range(9):
                # alternate which leg runs first: the two legs of a pair
                # share the slow drift, but the SECOND leg systematically
                # pays any residual warm-up/degradation trend —
                # alternating puts that bias on each leg equally often,
                # so the median of the signed deltas cancels it instead
                # of billing it to the observe path
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
        # a real regression reproduces; a scheduler-noise excursion does
        # not — confirm before failing (keeps the gate's false-failure
        # rate at p^2 without loosening the 2% acceptance itself)
        overhead2, deltas2 = batch_median()
        if overhead2 < overhead:
            overhead, deltas = overhead2, deltas2
    assert overhead <= 0.02, (
        f"recorder+metrics overhead {overhead:.1%} > 2% (median of "
        f"{len(deltas)} paired samples, {steps}-step loop, best of two "
        f"batches; deltas {[f'{d:.1%}' for d in deltas]})"
    )
