PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test lint format bench-smoke bench-smoke-sharded bench-smoke-zipf \
	bench-smoke-reuse bench-smoke-selftune bench-smoke-slo \
	bench-smoke-multitenant bench-runtime bench-compare tune-smoke \
	trace-smoke example-stream example-control example-tune \
	example-selftune example-multitenant

# tier-1 verify (ROADMAP.md)
test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest -x -q

# lint gate (ruff config in pyproject.toml). `ruff check` is repo-wide;
# format parity is enforced on the sharded-runtime layer and grows
# file-by-file as modules get normalized.
lint:
	ruff check .
	ruff format --check src/repro/serve/runtime/shard.py tests/test_shard.py

format:
	ruff format src/repro/serve/runtime/shard.py tests/test_shard.py

# fast perf datapoint: measured zero-loss throughput -> BENCH_runtime.json
bench-smoke:
	$(PYTHON) -m benchmarks.bench_runtime --smoke

# sharded smoke: 4 RSS-steered workers, gated >= 2x the committed 1-shard
# median (acceptance floor; measured speedups land nearer n/imbalance)
bench-smoke-sharded:
	$(PYTHON) -m benchmarks.bench_runtime --smoke --shards 4 \
		--out results/BENCH_runtime_sharded.json \
		--single BENCH_runtime.json --min-speedup 2.0

# zipf skew gate: 4 workers under elephant-flow skew, static RETA vs the
# adaptive control plane measured under one calibration — dynamic must
# report strictly lower load_imbalance and no lower median zero-loss pps
bench-smoke-zipf:
	$(PYTHON) -m benchmarks.bench_runtime --smoke --shards 4 \
		--scenario zipf --skew-gate \
		--out results/BENCH_runtime_zipf.json

# prediction-reuse gate (DESIGN.md §12): zipf 4-shard zero-loss A/B with
# the drift-gated reuse path on vs off, same calibration and stream.
# Fails unless reuse wins by >= 1.5x with zero drops on both arms and
# threshold-0 predictions stay bit-identical to the non-reuse path
bench-smoke-reuse:
	$(PYTHON) -m benchmarks.bench_runtime --smoke --scenario zipf \
		--min-reuse-speedup 1.5

# self-optimizing-fleet gate (DESIGN.md §13): drift-scenario controlled
# replay where a drift-triggered reoptimizer re-tunes and hot-swaps the
# knee autonomously — must fire exactly one audited episode, lose zero
# packets through the swap, beat the frozen knee on post-drift macro-F1,
# and stay silent on a uniform control arm
bench-smoke-selftune:
	$(PYTHON) -m benchmarks.bench_runtime --smoke --scenario drift \
		--selftune

# SLO latency gate (DESIGN.md §14): probe the fleet's replayed latency
# distribution, then controlled replays against self-calibrated met and
# violated targets — per-stage p99 decomposition must be consistent with
# the end-to-end total, breaches must be audited (and only when real),
# and the exporter's Prometheus/JSONL output must validate
bench-smoke-slo:
	$(PYTHON) -m benchmarks.bench_runtime --smoke --scenario zipf --slo

# multi-tenant gate (DESIGN.md §15): one 3-tenant shared fleet (merged
# extraction plan, fused multi-model dispatch) vs 3 independent 1-shard
# fleets at equal total shards, zero-loss bisection each arm — fails
# unless shared wins by >= 1.5x with zero drops on both arms and every
# tenant's predictions stay bit-identical to its solo-served baseline
bench-smoke-multitenant:
	$(PYTHON) -m benchmarks.bench_runtime --smoke --tenants 3 \
		--min-tenant-speedup 1.5

# observability smoke (DESIGN.md §11): one instrumented 4-shard zipf
# replay under the control plane — Chrome trace + stage breakdown +
# bit-matched metrics snapshot + audit log from a single run — then the
# overhead gate: tracing-disabled replay must stay within 5% of the
# untraced baseline on this machine
trace-smoke:
	$(PYTHON) -m benchmarks.bench_runtime --trace results/trace_serving.json
	$(PYTHON) -m benchmarks.trace_smoke --gate 5

# multi-fidelity tuner gate: batched cheap->measured optimization vs the
# sequential loop and every baseline, all through one shared memoized
# evaluator; fails unless CATO-MF's measured-fidelity hypervolume is >=
# every method's at equal measurement budget (DESIGN.md §10.3)
tune-smoke:
	$(PYTHON) -m benchmarks.tune_smoke --gate

# full runtime benchmark (Fig. 5c, measured) — separate output so it never
# clobbers the smoke baseline the bench-compare gate diffs against
bench-runtime:
	$(PYTHON) -m benchmarks.bench_runtime --out results/BENCH_runtime_full.json

# perf gate: fresh smoke run vs committed BENCH_runtime.json
# (fails on >20% median CATO zero_loss_pps regression)
bench-compare:
	$(PYTHON) -m benchmarks.compare_runtime

example-stream:
	$(PYTHON) examples/serve_stream.py

example-control:
	$(PYTHON) examples/serve_control.py

# the closed loop: optimize under zipf -> compile the front -> hot-swap
# the knee point into a live sharded replay (DESIGN.md §10)
example-tune:
	$(PYTHON) examples/tune_serving.py

# the loop closing itself: drift-triggered re-optimization with an
# autonomous hot-swap mid-replay (DESIGN.md §13)
example-selftune:
	$(PYTHON) examples/selftune_fleet.py

# the optimizer seeing the sharing: joint multi-tenant tuning where the
# union-plan extraction discount moves the Pareto front relative to
# independently tuned tenants, then a fused deploy (DESIGN.md §15.5)
example-multitenant:
	$(PYTHON) examples/tune_multitenant.py
