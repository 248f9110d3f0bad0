PYTHON ?= python

.PHONY: test test-fast test-crash dev-deps bench bench-smoke bench-mesh-smoke bench-compare lint-invariants lint-invariants-selftest

dev-deps:
	$(PYTHON) -m pip install -r requirements-dev.txt

# tier-1 verify (ROADMAP.md)
test:
	PYTHONPATH=src $(PYTHON) -m pytest -x -q

test-fast:
	PYTHONPATH=src $(PYTHON) -m pytest -x -q tests/test_crystal.py \
		tests/test_offload_engine.py tests/test_castore.py \
		tests/test_checkpoint.py tests/test_chunking.py

# invariant lint suite (docs/STATIC_ANALYSIS.md): fails on any finding
# not in the committed baseline; ra-findings.txt is the CI artifact
lint-invariants:
	PYTHONPATH=src $(PYTHON) -m repro.analysis src/repro \
		--baseline analysis-baseline.txt --report ra-findings.txt

# prove the checkers still catch violations: every `# ra-selftest:`
# marker in the fixtures must be reported at exactly its file:line,
# and a raw run over the bad fixtures must exit non-zero
lint-invariants-selftest:
	PYTHONPATH=src $(PYTHON) -m repro.analysis \
		--selftest tests/fixtures/analysis
	@if PYTHONPATH=src $(PYTHON) -m repro.analysis \
		tests/fixtures/analysis --root tests/fixtures/analysis \
		> /dev/null 2>&1; then \
		echo "ERROR: bad fixtures produced a zero exit"; exit 1; \
	else echo "fixture violations exit non-zero: ok"; fi

# durability: WAL framing fuzz + crash/restart fault-injection matrix
test-crash:
	PYTHONPATH=src $(PYTHON) -m pytest -x -q tests/test_wal.py \
		tests/test_crash_recovery.py

bench:
	PYTHONPATH=src:. $(PYTHON) benchmarks/run.py

# tiny-size perf smoke (CI): exercises the engine/pipeline benchmark
# paths, leaves the CSV in bench-smoke.csv and the machine-readable
# summary (rows + engine/gateway counters) in BENCH_smoke.json for the
# artifact uploads
# (redirect, don't pipe: a module failure must fail the make target)
bench-smoke:
	BENCH_SMOKE=1 BENCH_JSON=BENCH_smoke.json PYTHONPATH=src:. \
		$(PYTHON) benchmarks/run.py \
		fig4 fig11 read scrub recovery gateway > bench-smoke.csv
	@cat bench-smoke.csv
	@grep -q '^gateway/latency_p99' bench-smoke.csv
	@grep -q '^recovery/fsync_p95' bench-smoke.csv
	@grep -q '^health/status' bench-smoke.csv
	@grep -q '^health/sampler' bench-smoke.csv
	@grep -q '^health/scrape' bench-smoke.csv
	@test -s obs-health.json
	@$(PYTHON) -c "import json; s = json.load(open('BENCH_smoke.json')); \
		assert s.get('obs'), 'missing obs block in BENCH_smoke.json'"

# perf-regression gate: fresh smoke JSON vs the committed baseline
# (generous cross-machine tolerance bands; ok-flag counters exact —
# see benchmarks/compare.py for the row policy and env overrides)
bench-compare:
	PYTHONPATH=src:. $(PYTHON) benchmarks/compare.py \
		BENCH_baseline.json BENCH_smoke.json

# engine-mesh ablation alone (1 vs 4 forced host devices, static vs
# adaptive fusion), measured in this one process over its 4 devices;
# asserts the mesh rows actually landed in the CSV
bench-mesh-smoke:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	BENCH_SMOKE=1 BENCH_JSON=BENCH_mesh.json PYTHONPATH=src:. \
		$(PYTHON) benchmarks/run.py mesh > bench-mesh.csv
	@cat bench-mesh.csv
	@grep -q '^mesh/whale_1dev,' bench-mesh.csv
	@grep -q '^mesh/whale_4dev_sharded,' bench-mesh.csv
	@grep -q '^mesh/fusion_static,' bench-mesh.csv
	@grep -q '^mesh/fusion_adaptive,' bench-mesh.csv
	@grep -q '^mesh/device_' bench-mesh.csv
	@grep -q '^mesh/digest_ok,0.0,ok=1' bench-mesh.csv
