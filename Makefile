PY ?= python

.PHONY: test lint lint-json baseline observe serve-metrics progcheck \
	progcheck-baseline shardcheck shardcheck-baseline check \
	racecheck racecheck-baseline \
	kernelcheck kernelcheck-baseline incident-demo storecheck \
	grid-top history

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# metrics plane demo: serve /metrics (OpenMetrics) + /healthz for a
# small in-process drift loop on 127.0.0.1:9100. Scrape with
#   curl localhost:9100/metrics
# Point --journal at StepRecorder JSONL shards to serve a real run
# (repeat the flag to pod-merge shards). See telemetry/metrics.py.
serve-metrics:
	JAX_PLATFORMS=cpu $(PY) scripts/metrics_serve.py --demo --port 9100

# grid observatory smoke: drift demo with the health monitor on, three
# legs on 8 virtual CPU devices. Balanced leg must stay OK (unexpected
# ALERT = exit 1) and writes a Perfetto trace; biased leg must ALERT
# (no alert = exit 2); corruption leg NaN-bursts a probed supervised
# service run and must detect -> page -> bundle -> restore pre-
# corruption (any broken link = exit 3). See telemetry/SCHEMA.md.
observe:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) examples/drift_demo.py --n 16384 --steps 20 \
		--trace observe_trace.json
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) examples/drift_demo.py --n 16384 --steps 20 \
		--bias --expect-alert
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) examples/drift_demo.py --n 16384 --steps 20 \
		--corrupt

# every analyzer family in --check text mode, driven off the single
# ANALYZERS registry in scripts/check_all.py (gridlint G, progcheck J,
# shardcheck S, racecheck T, kernelcheck K, incident-demo I,
# storecheck ST) — adding a family is one registry row, not a
# Makefile edit. Exit 0 = clean or
# fully baselined; 1 = new findings or stale baseline entries; 2 =
# usage/parse error. See mpi_grid_redistribute_tpu/analysis/.
lint:
	$(PY) scripts/check_all.py --lint

# one-shot CI umbrella: the same seven analyzers/gates, SARIF runs merged
# into a single analysis_merged.sarif for one code-scanning upload.
# Per-analyzer wall-time is printed so lint growth stays visible;
# `--analyzers NAME[,NAME]` subsets the registry for fast local loops.
check:
	$(PY) scripts/check_all.py

# progcheck alone: trace every registered SPMD program on the virtual
# 8-device CPU mesh and gate J001-J004 plus the static wire/footprint
# profile against analysis/progprofile_baseline.json. No chip, no
# compile — make_jaxpr only.
progcheck:
	$(PY) scripts/progcheck.py --check

# refresh the J004 static-cost baseline after an INTENTIONAL wire or
# footprint change (justify the delta in the commit message)
progcheck-baseline:
	$(PY) scripts/progcheck.py --update-baseline

# shardcheck alone: infer per-mesh-axis vary-sets for every registered
# program and gate S001-S003 plus the S004 per-axis ICI/DCN wire
# attribution against progprofile_baseline.json's wire_attribution
# section. Same trace-only machinery as progcheck.
shardcheck:
	$(PY) scripts/shardcheck.py --check

# refresh the S004 wire-attribution baseline after an INTENTIONAL
# re-routing of collectives across the mesh (justify the delta)
shardcheck-baseline:
	$(PY) scripts/shardcheck.py --update-baseline

# racecheck alone: infer the host-thread topology (Thread targets +
# HTTP handler pools), the cross-thread shared-state matrix, and gate
# T001-T005 against analysis/racecheck_baseline.json. Pure ast — no
# jax, nothing scanned is executed. `--list-threads` dumps the
# inferred topology.
racecheck:
	$(PY) scripts/racecheck.py --check

# regenerate the racecheck baseline (then hand-edit each entry's
# justification — a bare regen is not a justification)
racecheck-baseline:
	$(PY) scripts/racecheck.py --write-baseline

# incident observatory smoke (ISSUE 17, also inside `make check`): a
# fault-injected supervised run on the numpy backend must leave
# flight-recorder bundles behind (alert- AND fault-triggered), every
# index.json must carry the triggering step context's trace id, the
# per-rule debounce must hold across restarts, and the frozen journal
# must export to a Perfetto trace with causal flow arrows. See
# telemetry/incident.py and scripts/incident.py.
incident-demo:
	JAX_PLATFORMS=cpu $(PY) scripts/incident_demo.py --check

# journal-store integrity gate (ISSUE 18, also inside `make check`):
# build a demo store through rotation + compaction + retention on a
# deliberately tiny wrapping recorder ring, then gate ST01-ST07 —
# segment sha256s vs the manifest, the count-conservation ledger, seq
# ordering, rotation/retention bounds, compaction exactness, and the
# headline claim: metrics.from_journal over the drained+compacted
# store equals the live recorder's all-time counts after eviction.
# Point it at a real store root to check a run's artifacts:
#   python scripts/storecheck.py /path/to/store
storecheck:
	JAX_PLATFORMS=cpu $(PY) scripts/storecheck.py --check

# one-shot dashboard snapshot over the storecheck demo store (CI-safe;
# live mode: scripts/grid_top.py --store DIR or --url http://host:port)
grid-top:
	JAX_PLATFORMS=cpu $(PY) scripts/storecheck.py --keep .grid_top_demo \
		> /dev/null && \
	JAX_PLATFORMS=cpu $(PY) scripts/grid_top.py \
		--store .grid_top_demo/store --once; \
	rm -rf .grid_top_demo

# run-index view of the journal-store runs under --stores DIR
# (`make history` alone indexes none; see scripts/history.py)
history:
	JAX_PLATFORMS=cpu $(PY) scripts/history.py

# kernelcheck alone: capture every registered Pallas kernel's
# pallas_call anatomy via jax.eval_shape (no execution) and gate
# K000-K004 (index-map bounds, scatter coverage/overlap, VMEM
# footprint vs analysis/kernelcheck_baseline.json, lane tiling), then
# run the K005 interpret-mode bit-identity backstop on CPU. The
# ROADMAP item-3 megakernel must pass this gate (with a committed
# footprint row) before it is ever compiled on a chip.
kernelcheck:
	$(PY) scripts/kernelcheck.py --check

# refresh the K003 VMEM-footprint table after an INTENTIONAL blocking
# change (justify the footprint delta in the commit message)
kernelcheck-baseline:
	$(PY) scripts/kernelcheck.py --update-baseline

lint-json:
	$(PY) scripts/gridlint.py mpi_grid_redistribute_tpu/ --format=json

# regenerate the grandfathered-findings file (then hand-edit each
# entry's justification — a bare regen is not a justification)
baseline:
	$(PY) scripts/gridlint.py mpi_grid_redistribute_tpu/ --write-baseline
