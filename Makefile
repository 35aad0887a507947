# Developer entry points. The sandbox CI has no package egress, so the
# three real-pyspark `local[4]` tests importorskip there; the docker
# image installs pyspark at build time (network available), and
# `make docker-test` is where they run for real — 0 pyspark skips.

IMAGE ?= analytics-zoo-tpu

.PHONY: test docker-build docker-test docker-test-spark dist docs \
    lint obs-smoke flops-audit serving-smoke \
    bench-serving bench-serving-fleet trace-smoke trace-report \
    slo-smoke perf-sentinel fleet-smoke generate-smoke \
    bench-generate chaos-smoke autotune autotune-smoke \
    dashboard-smoke

# unit tests plus the end-to-end telemetry smokes (metrics
# exposition, tracing, SLO control loop), so `make test` proves the
# observability stack, not just the library; the perf sentinel runs
# advisory here so every test run prints the bench trajectory
test:
	python -m pytest tests/ -x -q
	$(MAKE) obs-smoke
	$(MAKE) trace-smoke
	$(MAKE) slo-smoke
	$(MAKE) fleet-smoke
	$(MAKE) generate-smoke
	$(MAKE) chaos-smoke
	$(MAKE) autotune-smoke
	$(MAKE) dashboard-smoke
	python scripts/perf_sentinel.py --advisory

# telemetry end-to-end: 2 train steps + 1 served request, then assert
# the /metrics exposition carries every layer (docs/observability.md)
obs-smoke:
	JAX_PLATFORMS=cpu python scripts/obs_smoke.py

# tracing end-to-end: 3 train steps + 1 traced request (X-Zoo-Trace-Id
# echo, /debug/traces, chrome-trace export) — docs/observability.md
trace-smoke:
	JAX_PLATFORMS=cpu python scripts/trace_smoke.py

# SLO control loop end-to-end: shipped serving objectives on
# /debug/slo, a driven error burst trips the error-rate breach and
# the breach/anomaly counters increment (docs/slo.md)
slo-smoke:
	JAX_PLATFORMS=cpu python scripts/slo_smoke.py

# perf-regression sentinel over the committed BENCH_serving*.json /
# BENCH_generate.json (and any BENCH_r<NN>.json wrappers in --dir):
# trajectory table + exit 1 when the newest round regressed >10%
# vs the best comparable (same-lineage) prior value (docs/slo.md)
perf-sentinel:
	python scripts/perf_sentinel.py

# offline report over a ZOO_TPU_EVENT_LOG JSONL: per-step timeline,
# top-N slowest requests, anomaly digest, optional Perfetto export
EVENTS ?= /tmp/zoo_tpu_trace_smoke.events.jsonl
trace-report:
	python scripts/trace_report.py --events $(EVENTS)

# executed-FLOPs audit of the ResNet-50 train step (lowering only —
# CPU-safe, no chip; docs/perf_flags.md)
flops-audit:
	JAX_PLATFORMS=cpu python scripts/flops_audit.py --image 96

# dynamic-batching end-to-end: batched server (default front-end),
# mixed-size concurrent requests, exact outputs, warmed buckets,
# queue metrics on /metrics (docs/serving.md)
serving-smoke:
	JAX_PLATFORMS=cpu python scripts/serving_smoke.py

# batched-vs-unbatched serving throughput on the host CPU backend
# (the chip headline stays null; see bench_serving.py)
bench-serving:
	JAX_PLATFORMS=cpu python bench_serving.py --cpu-fallback

# fleet A/B sweep: 1 replica vs N replicas behind the router, writes
# BENCH_serving_fleet.json (its own perf-sentinel lineage — never
# compared against single-process serving rows)
bench-serving-fleet:
	JAX_PLATFORMS=cpu python bench_serving.py --cpu-fallback \
	    --replicas 4

# decode fast path end-to-end: compiled generate loop must EXACTLY
# match a naive uncached re-forward reference, then mixed concurrent
# /generate requests through the continuous batcher (docs/serving.md)
generate-smoke:
	JAX_PLATFORMS=cpu python scripts/generate_smoke.py

# continuous batching vs sequential per-request decode on the host
# CPU backend; writes BENCH_generate.json (its own perf-sentinel
# lineage — decode tokens/s is never compared against predict rows/s).
# Capacity levers on: chunked prefill (chunk sized to ~one decode
# iteration's compute on this backend) + speculative decoding, so the
# artifact carries the TTFT short/long probe and acceptance-rate
# fields the PR 17 gate reads.
bench-generate:
	JAX_PLATFORMS=cpu python bench_generate.py --cpu-fallback \
	    --prefill-chunk 64 --spec-k 2

# chaos end-to-end: injected kill/straggler/queue-wedge faults under
# concurrent load (zero lost acked requests), then a canary rollout
# auto-rolled-back by an injected error burst and a clean re-roll
# promoted, all observable on /debug/rollout (docs/robustness.md)
chaos-smoke:
	JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

# replicated-fleet end-to-end: 2-replica CPU fleet, mixed concurrent
# load with exact outputs, one replica killed mid-load (zero lost
# acked requests), ejected, healed, re-admitted (docs/serving.md)
fleet-smoke:
	JAX_PLATFORMS=cpu python scripts/fleet_smoke.py

# populate the persistent autotune cache for the bench shapes
# (ZOO_TPU_AUTOTUNE=1 sweeps on first sight; docs/autotune.md), then
# print the decision table.
autotune:
	ZOO_TPU_AUTOTUNE=1 python scripts/autotune_report.py --sweep

# autotuner lifecycle end-to-end on CPU: sweep two shapes
# (interpret-guarded), persist, reload in a FRESH process as pure
# cache hits (zero sweeps, counter-asserted), report renders
autotune-smoke:
	JAX_PLATFORMS=cpu python scripts/autotune_smoke.py

# metric-history plane end-to-end: MetricHistory sampling cost under
# a byte cap, capacity_forecast firing with a finite KV-page ETA
# BEFORE saturation, /debug/metrics/history + /debug/dashboard on
# both HTTP front-ends, fleet-merged series (docs/observability.md)
dashboard-smoke:
	JAX_PLATFORMS=cpu python scripts/dashboard_smoke.py

docker-build:
	docker build -t $(IMAGE) -f docker/Dockerfile .

# full suite inside the image (CPU mesh; includes the pyspark tier)
docker-test: docker-build
	docker run --rm -e JAX_PLATFORMS=cpu \
	    -e XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(IMAGE) python -m pytest tests -q

# just the three environment-bound pyspark tests, verbose — proves
# the suite runs with 0 pyspark skips where pyspark is installable
docker-test-spark: docker-build
	docker run --rm -e JAX_PLATFORMS=cpu \
	    -e XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    $(IMAGE) python -m pytest tests/test_spark_ingest.py \
	    tests/test_nnframes.py -q -rs

docs:
	JAX_PLATFORMS=cpu python scripts/gen_api_docs.py

dist:
	bash scripts/make-dist.sh

lint:
	python scripts/lint.py
