# Build / test / bench entry points (reference: Makefile targets fmt/clippy/test)

.PHONY: test native bench baselines serve lint jaxlint typecheck smoke-metrics bench-smoke mem-smoke chaos-smoke cluster-smoke clean soak dryruns

test:
	python -m pytest tests/ -x -q

native:
	$(MAKE) -C horaedb_tpu/native

bench:
	python bench.py

baselines:
	python benchmarks/run_baselines.py --quick

serve:
	python -m horaedb_tpu.server.main --config docs/example.toml

# AST lint gate (tools/lint.py): unused imports, star imports, dup dict
# keys, mutable defaults, bare except, style — the clippy/rustfmt analog
# (reference Makefile:37-53); ruff/mypy are not in the image, the linter
# is stdlib. compileall still guards syntax across every file.
lint:
	python -m compileall -q horaedb_tpu tests benchmarks bench.py chip_smoke.py __graft_entry__.py
	python tools/lint.py
	$(MAKE) jaxlint
	$(MAKE) typecheck
	$(MAKE) smoke-metrics
	$(MAKE) mem-smoke
	$(MAKE) bench-smoke
	$(MAKE) chaos-smoke
	$(MAKE) cluster-smoke

# Domain-aware gate (tools/jaxlint/): host-sync on hot paths (J001),
# retrace hazards under jit (J002), dtype drift in engine code (J003),
# lock discipline on the concurrency surface (J004), host timers/spans
# inside jit bodies (J005), ad-hoc aggregation lanes (J006), naked jit
# (J007), blocking flush work on the append path (J008), naked
# object-store construction outside the ResilientStore boundary (J009),
# ad-hoc tombstone/retention filtering off the shared visibility helper
# (J010), server query entries bypassing admission (J011), ad-hoc decode
# of encoded SST lanes outside the sanctioned funnel (J012), serving-tier
# funnel breaches (J013), unaudited invalidation-funnel subscribers
# (J014), per-tenant accounting outside the metering funnel (J015),
# ad-hoc stacking/padding of query result lanes outside the query
# batcher's stacked-execution funnel (J016), cluster-funnel breaches —
# manifest views outside the replica funnel, assignment-record mutation
# outside the fenced CAS API (J017). Whole-program passes over the
# shared call-graph index: event-loop blocking reachable from
# coroutines (J018), lock-order deadlock cycles + await-under-sync-lock
# (J019), deadline-propagation completeness on query-reachable loops
# (J020), suppression hygiene — stale or reason-less disables (J021).
# Findings print as path:line: CODE message.
# Rules + suppression syntax: docs/static-analysis.md
jaxlint:
	python -m tools.jaxlint

# Observability gate: boot the server against the in-process fake S3,
# push one remote-write batch, run one query, and fail if any /metrics
# line violates the Prometheus text exposition format
# (tools/promcheck.py) or an expected family / the trace round-trip is
# missing (tools/smoke_metrics.py).
smoke-metrics:
	JAX_PLATFORMS=cpu python tools/smoke_metrics.py

# Memory gate: pins the config-2 scan path's memtrace event counts
# (allocs/copies/views per stage, cold + cache-hit) against the committed
# benchmarks/mem_baseline.json — ROADMAP item 2's allocation-count
# acceptance criteria as a gate — and measures memtrace's own cost
# (track ns/event + scan-p50 A/B vs HORAEDB_MEMTRACE=off; target <2%).
# Re-pin after an intentional data-plane change:
#   python tools/mem_smoke.py --pin
mem-smoke:
	JAX_PLATFORMS=cpu python tools/mem_smoke.py

# Aggregation-dispatch gate: a <120 s quick-shape bench.py --smoke on CPU
# asserting the calibrated registry picks a valid impl, both A/B dicts are
# non-empty, and the calibration cache round-trips (tools/bench_smoke.py).
bench-smoke:
	JAX_PLATFORMS=cpu python tools/bench_smoke.py

# Fault-tolerance gate: boot the real server over a seeded ChaosStore
# (injected errors, torn writes, listing lag), assert exact query
# results under live faults, breaker-open 503s with Retry-After, the
# horaedb_objstore_* families, and crash recovery (fence re-acquire +
# orphan-SST GC) at smoke scale (tools/chaos_smoke.py).
chaos-smoke:
	JAX_PLATFORMS=cpu python tools/chaos_smoke.py

# Cluster gate: boot one writer + one stateless read replica (two real
# servers, two S3 clients) over one fake-S3 bucket and assert exact
# replica reads after catch-up, the X-Horaedb-Staleness-Ms header, write
# forwarding replica->writer, /api/v1/cluster/status epoch equality, and
# the horaedb_cluster_* families (tools/cluster_smoke.py).
cluster-smoke:
	JAX_PLATFORMS=cpu python tools/cluster_smoke.py

# mypy over the annotated core (config in pyproject.toml [tool.mypy]); the
# dev image has no mypy, so this degrades to a loud skip locally — CI
# (.github/workflows/ci.yml) installs and enforces it.
typecheck:
	@if python -c "import mypy" 2>/dev/null; then \
	  python -m mypy; \
	else \
	  echo "typecheck: mypy not installed in this image; enforced in CI"; \
	fi

soak:
	SOAK_REGIONS=3 SOAK_METRICS=8 SOAK_BUFFER_ROWS=30000 python benchmarks/soak.py 60

dryruns:
	python benchmarks/shared_store_dryrun.py
	python benchmarks/multihost_dryrun.py
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  python -c "import jax; jax.config.update('jax_platforms','cpu'); \
	  import __graft_entry__ as g; g.dryrun_multichip(8)"

clean:
	$(MAKE) -C horaedb_tpu/native clean
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
