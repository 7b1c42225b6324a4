#!/usr/bin/env bash
# Tier-1 verify + CPU smoke of the serving stack (same as `make verify`,
# for environments without make).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== serving smoke (CPU) =="
python -m repro.launch.serve --smoke --requests 12 --rate 200 \
  --tokens-mean 5 --max-len 32 --engine both

echo "== paged kvcache smoke (CPU) =="
python -m repro.launch.serve --smoke --requests 12 --rate 200 \
  --tokens-mean 5 --max-len 32 --engine paged \
  --page-size 8 --num-pages 20 --prefix-len 8

echo "== chunked prefill smoke (CPU) =="
python -m repro.launch.serve --smoke --requests 8 --rate 200 \
  --tokens-mean 4 --max-len 96 --engine paged \
  --page-size 16 --num-pages 28 --prompt-len 48 --prefill-chunk 16

echo "== speculative decoding smoke (CPU) =="
python -m repro.launch.serve --smoke --requests 8 --rate 200 \
  --tokens-mean 6 --max-len 64 --engine paged \
  --page-size 8 --num-pages 36 --prompt-len 16 --prefill-chunk 16 \
  --spec-k 2 --sample-frac 0

echo "== quantised int8 KV pages smoke (CPU) =="
python -m repro.launch.serve --smoke --requests 8 --rate 200 \
  --tokens-mean 4 --max-len 64 --engine paged \
  --page-size 8 --num-pages 28 --prompt-len 16 --prefill-chunk 16 \
  --kv-dtype int8 --sample-frac 0

echo "== async step pipeline smoke (CPU) =="
python -m repro.launch.serve --smoke --requests 12 --rate 200 \
  --tokens-mean 5 --max-len 32 --engine continuous --async-steps
python -m repro.launch.serve --smoke --requests 12 --rate 200 \
  --tokens-mean 5 --max-len 32 --engine paged \
  --page-size 8 --num-pages 20 --prefix-len 8 --async-steps

echo "== telemetry smoke (CPU): flight recorder + metrics registry =="
python -m repro.launch.serve --smoke --requests 12 --rate 200 \
  --tokens-mean 5 --max-len 32 --engine paged \
  --page-size 8 --num-pages 20 --prefix-len 8 \
  --trace-out artifacts/trace_smoke.json \
  --metrics-out artifacts/metrics_smoke.prom
python scripts/check_trace.py artifacts/trace_smoke.json \
  artifacts/metrics_smoke.prom

echo "== sharded serving smoke (CPU, 2 fake devices) =="
# Active 1x2 (model-parallel) with the 1x1 standby warmed (DESIGN.md §16):
# the mesh is a dispatch coordinate, so serving at 1x2 must report zero
# post-warmup compiles like any other lane.
XLA_FLAGS="--xla_force_host_platform_device_count=2 ${XLA_FLAGS:-}" \
python -m repro.launch.serve --smoke --requests 8 --rate 200 \
  --tokens-mean 4 --max-len 32 --engine paged \
  --page-size 8 --num-pages 20 --prefix-len 8 \
  --mesh 1x2 --meshes "1x1"

echo "== disaggregated prefill/decode smoke (CPU, 2 fake devices) =="
# Prefill lanes pinned to the warmed "1x1@1" slice, decode on "1x1"; KV
# pages live-migrate decode-ward at each flip (DESIGN.md §17) — zero
# post-warmup compiles like any other semi-static coordinate.
XLA_FLAGS="--xla_force_host_platform_device_count=2 ${XLA_FLAGS:-}" \
python -m repro.launch.serve --smoke --requests 8 --rate 200 \
  --tokens-mean 4 --max-len 64 --engine paged \
  --page-size 8 --num-pages 28 --prompt-len 24 --prefill-chunk 8 \
  --meshes "1x1@1" --disagg

echo "== overload hardening + chaos smoke matrix (CPU) =="
# {sync,async} x {spec on,off} through the hardened driver with bounded
# admission, deadlines, the degradation ladder, and a seeded fault plan
# (DESIGN.md §15). The dense arms of the chaos matrix run in tier-1 via
# tests/test_faults.py.
for async_flag in "" "--async-steps"; do
  for speck in 0 2; do
    python -m repro.launch.serve --smoke --requests 10 --rate 500 \
      --tokens-mean 5 --max-len 64 --engine overload \
      --page-size 8 --num-pages 28 --spec-k "$speck" --sample-frac 0 \
      --capacity 12 --shed-policy drop-oldest --deadline 2.0 --degrade \
      --chaos-seed 0 $async_flag
  done
done
