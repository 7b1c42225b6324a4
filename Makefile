PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: verify tier1 smoke-serve smoke-paged smoke-prefill smoke-specdec \
	smoke-quantkv smoke-async smoke-telemetry smoke-chaos smoke-sharding \
	smoke-disagg bench-serving bench-kvcache bench-prefill bench-specdec \
	bench-quantkv bench-telemetry bench-overload bench-sharding \
	bench-disagg bench-check bench examples

# The full gate: tier-1 tests + a CPU smoke of the serving stack.
verify: tier1 smoke-serve smoke-paged smoke-prefill smoke-specdec \
	smoke-quantkv smoke-async smoke-telemetry smoke-chaos smoke-sharding \
	smoke-disagg

# Tier-1 (ROADMAP.md): the repo's own test suite.
tier1:
	$(PY) -m pytest -x -q

# CPU smoke: the traffic-driven serving loop, both engines, small stream.
smoke-serve:
	$(PY) -m repro.launch.serve --smoke --requests 12 --rate 200 \
		--tokens-mean 5 --max-len 32 --engine both

# CPU smoke: the paged KV engine on a shared-prefix stream.
smoke-paged:
	$(PY) -m repro.launch.serve --smoke --requests 12 --rate 200 \
		--tokens-mean 5 --max-len 32 --engine paged \
		--page-size 8 --num-pages 20 --prefix-len 8

# CPU smoke: chunked prefill on long distinct prompts (DESIGN.md §10).
smoke-prefill:
	$(PY) -m repro.launch.serve --smoke --requests 8 --rate 200 \
		--tokens-mean 4 --max-len 96 --engine paged \
		--page-size 16 --num-pages 28 --prompt-len 48 --prefill-chunk 16

# CPU smoke: speculative decoding through the draft/verify lanes
# (DESIGN.md §11) on the paged engine.
smoke-specdec:
	$(PY) -m repro.launch.serve --smoke --requests 8 --rate 200 \
		--tokens-mean 6 --max-len 64 --engine paged \
		--page-size 8 --num-pages 36 --prompt-len 16 --prefill-chunk 16 \
		--spec-k 2 --sample-frac 0

# CPU smoke: quantised int8 KV pages (DESIGN.md §12) on the paged engine.
smoke-quantkv:
	$(PY) -m repro.launch.serve --smoke --requests 8 --rate 200 \
		--tokens-mean 4 --max-len 64 --engine paged \
		--page-size 8 --num-pages 28 --prompt-len 16 --prefill-chunk 16 \
		--kv-dtype int8 --sample-frac 0

# CPU smoke: the async step pipeline (DESIGN.md §13) on both continuous
# engines — greedy streams bitwise identical to the synchronous loop.
smoke-async:
	$(PY) -m repro.launch.serve --smoke --requests 12 --rate 200 \
		--tokens-mean 5 --max-len 32 --engine continuous --async-steps
	$(PY) -m repro.launch.serve --smoke --requests 12 --rate 200 \
		--tokens-mean 5 --max-len 32 --engine paged \
		--page-size 8 --num-pages 20 --prefix-len 8 --async-steps

# CPU smoke: the flight recorder + metrics registry (DESIGN.md §14) —
# capture a trace and a Prometheus snapshot from the full paged stack and
# validate both (Chrome-trace schema, event-type diversity, per-lane
# latency histograms).
smoke-telemetry:
	$(PY) -m repro.launch.serve --smoke --requests 12 --rate 200 \
		--tokens-mean 5 --max-len 32 --engine paged \
		--page-size 8 --num-pages 20 --prefix-len 8 \
		--trace-out artifacts/trace_smoke.json \
		--metrics-out artifacts/metrics_smoke.prom
	$(PY) scripts/check_trace.py artifacts/trace_smoke.json \
		artifacts/metrics_smoke.prom

# CPU smoke: overload hardening + chaos (DESIGN.md §15) — bounded
# admission, deadlines, the degradation ladder, and a seeded fault plan
# across {sync,async} x {spec on,off}; the dense arms of the chaos matrix
# run in tier-1 via tests/test_faults.py.
smoke-chaos:
	for async_flag in "" "--async-steps"; do \
		for speck in 0 2; do \
			$(PY) -m repro.launch.serve --smoke --requests 10 --rate 500 \
				--tokens-mean 5 --max-len 64 --engine overload \
				--page-size 8 --num-pages 28 --spec-k $$speck --sample-frac 0 \
				--capacity 12 --shed-policy drop-oldest --deadline 2.0 \
				--degrade --chaos-seed 0 $$async_flag || exit 1; \
		done; \
	done

# CPU smoke: sharded serving (DESIGN.md §16) — two fake host devices,
# active 1x2 (model-parallel) with the 1x1 standby warmed, paged engine;
# the report must show mesh=1x2 and zero post-warmup compiles.
smoke-sharding:
	XLA_FLAGS="--xla_force_host_platform_device_count=2 $$XLA_FLAGS" \
		$(PY) -m repro.launch.serve --smoke --requests 8 --rate 200 \
		--tokens-mean 4 --max-len 32 --engine paged \
		--page-size 8 --num-pages 20 --prefix-len 8 \
		--mesh 1x2 --meshes "1x1"

# CPU smoke: disaggregated prefill/decode (DESIGN.md §17) — two fake host
# devices, prefill lanes pinned to the warmed "1x1@1" slice, KV pages
# live-migrating decode-ward at each flip; the report must show migrations
# and zero post-warmup compiles.
smoke-disagg:
	XLA_FLAGS="--xla_force_host_platform_device_count=2 $$XLA_FLAGS" \
		$(PY) -m repro.launch.serve --smoke --requests 8 --rate 200 \
		--tokens-mean 4 --max-len 64 --engine paged \
		--page-size 8 --num-pages 28 --prompt-len 24 --prefill-chunk 8 \
		--meshes "1x1@1" --disagg

# Serving perf trajectory: writes BENCH_serving.json (per-burst vs
# continuous-batching throughput/latency/cold-path counters, plus the
# sync-vs-async step-pipeline pair on the saturated stream).
bench-serving:
	$(PY) -m benchmarks.run --only serving --fast

# Paged KV-cache scenario: writes BENCH_kvcache.json (shared-prefix
# workload: pages in use, share ratio, preemptions, rebinds, percentiles).
bench-kvcache:
	$(PY) -m benchmarks.run --only kvcache --fast

# Chunked-prefill scenario: writes BENCH_prefill.json (long-prompt TTFT,
# chunked vs token-by-token ingestion, zero post-warmup compiles).
bench-prefill:
	$(PY) -m benchmarks.run --only prefill --fast

# Speculative-decoding scenario: writes BENCH_specdec.json (accepted
# tokens/step, acceptance percentiles, spec vs plain latency, zero
# post-warmup compiles across k-bucket crossings).
bench-specdec:
	$(PY) -m benchmarks.run --only specdec --fast

# Quantised-KV scenario: writes BENCH_quantkv.json (int8 vs fp32 pools at
# matched memory: seating ratio, logit drift, zero-compile dtype crossing).
bench-quantkv:
	$(PY) -m benchmarks.run --only quantkv --fast

# Telemetry overhead: writes BENCH_telemetry.json (tracing off vs on
# tok/s, disabled-path overhead estimate, capture validity — DESIGN.md §14).
bench-telemetry:
	$(PY) -m benchmarks.run --only telemetry --fast

# Overload hardening: writes BENCH_overload.json (goodput vs the
# unbounded baseline at >=2x capacity, bounded admitted p95, ladder
# down+up, chaos containment, bitwise-inert identity — DESIGN.md §15).
bench-overload:
	$(PY) -m benchmarks.run --only overload --fast

# Sharded multi-device serving: writes BENCH_sharding.json (mesh-ladder
# throughput, mid-stream scale-out + failover-shrink rebinds at zero
# compiles, 1x1 bitwise identity, collectives microcosts — DESIGN.md §16).
bench-sharding:
	$(PY) -m benchmarks.run --only sharding --fast

# Disaggregated prefill/decode: writes BENCH_disagg.json (shared vs
# pinned-slice TTFT/throughput on the mixed stream, live KV-page
# migration counts, split/collapse rebinds at zero compiles, bitwise
# identity — DESIGN.md §17).
bench-disagg:
	$(PY) -m benchmarks.run --only disagg --fast

# Regression gate over freshly written BENCH_*.json (CI runs this).
bench-check:
	$(PY) scripts/bench_check.py BENCH_serving.json BENCH_kvcache.json \
		BENCH_prefill.json BENCH_specdec.json BENCH_quantkv.json \
		BENCH_telemetry.json BENCH_overload.json BENCH_sharding.json \
		BENCH_disagg.json

bench:
	$(PY) -m benchmarks.run --fast

examples:
	$(PY) examples/serve_modes.py
	$(PY) examples/failover_demo.py
