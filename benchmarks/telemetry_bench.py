"""Telemetry overhead benchmark (DESIGN.md §14) — the observability tax.

The flight recorder's contract is "compiled out unless enabled": with
recording disabled every instrumented call site pays one ``is None`` check
(plus the always-on metrics registry's counter add / histogram bisect).
This bench measures that tax at two levels and writes
``BENCH_telemetry.json`` for ``scripts/bench_check.py`` to gate:

* **micro**: the per-call-site cost of the disabled path (guard + counter
  + histogram observe) and of a phase span nothing records, scaled by the
  instrumentation density of one serving step and compared against the
  measured step time — the disabled-path overhead estimate must stay
  under 1%.
* **macro**: the same saturated greedy/sample stream BENCH_serving.json
  drives, run tracing-off and tracing-on, sync and async. Tracing-on must
  hold >= 95% of tracing-off throughput, greedy token streams must be
  bitwise identical across the pair, and post-warmup compiles must stay
  zero everywhere (telemetry adds no dispatch keys).

The tracing-on run's capture is validated in-memory (Chrome-trace schema,
event-type diversity, Prometheus exposition) so the artifact contract is
exercised on every bench run, not only in the smoke.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro import models
from repro.configs import get_config
from repro.core import reset_entry_points
from repro.core.telemetry import Telemetry
from repro.runtime.serve import Engine, EngineConfig

# Instrumented call sites on one decode step's hot path (lane tick, token
# note, finish check, d2h pull, async bookkeeping) — a deliberate
# overestimate so the micro gate errs strict.
SITES_PER_STEP = 8
# Phase spans one dense decode step opens while nothing records them: the
# step, the executable call and its three pulls.
SPANS_PER_STEP = 5


def disabled_site_ns(reps: int = 200_000) -> float:
    """Median cost of one disabled-path call site: the recorder guard plus
    the always-on registry counter + histogram observation."""
    tel = Telemetry()  # recording disabled (the production default)
    rec = tel.trace_or_none()
    assert rec is None
    reg = tel.registry
    c = reg.counter("lane_calls_total", lane="cb")
    h = reg.histogram("lane_step_ms", lane="cb")
    samples = []
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(reps):
            if rec is not None:  # the flight-recorder guard
                pass
            c.inc()
            h.observe(0.123)
        samples.append((time.perf_counter_ns() - t0) / reps)
    return float(np.median(samples))


def disabled_span_ns(reps: int = 200_000) -> float:
    """Median cost of one phase span with a note while neither the recorder
    nor the profiler records it (the shared no-op span)."""
    span = Telemetry().span
    samples = []
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(reps):
            with span("serve.dispatch.pull") as sp:
                sp.note(bytes=8)
        samples.append((time.perf_counter_ns() - t0) / reps)
    return float(np.median(samples))


def telemetry_comparison(
    n_requests: int = 16,
    *,
    slots: int = 4,
    tokens_mean: float = 16.0,
    max_len: int = 64,
    seed: int = 0,
    repeats: int = 2,
) -> dict:
    from repro.runtime.scheduler import poisson_arrivals
    from repro.runtime.serve import run_continuous_stream
    from repro.runtime.tracing import chrome_trace, validate_trace

    reset_entry_points()
    cfg = get_config("olmo-1b").smoke()
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_len=max_len, batch_quantum=2, max_batch=slots)

    sat_rate = 100.0 * n_requests  # all due ~immediately: decode-bound

    def traffic():
        return poisson_arrivals(
            n_requests,
            sat_rate,
            seed=seed,
            tokens_mean=tokens_mean,
            tokens_max=max_len - 1,
            sample_frac=0.5,
            vocab=cfg.vocab_size,
        )

    def greedy_tokens(reqs):
        return {r.rid: list(r.tokens) for r in reqs if r.greedy}

    def run_arm(enabled: bool, async_steps: bool) -> dict:
        """Best-of-``repeats`` streams on one warmed engine."""
        tel = Telemetry(enabled=enabled)
        eng = Engine(cfg, params, ecfg, telemetry=tel)
        best = None
        tokens = None
        for _ in range(repeats):
            reqs = traffic()
            rep = run_continuous_stream(
                eng, reqs, slots=slots, async_steps=async_steps
            )
            tokens = greedy_tokens(reqs)
            if best is None or rep.get("tok_per_s", 0.0) > best.get(
                "tok_per_s", 0.0
            ):
                best = rep
        eng.close()
        best["greedy_tokens"] = tokens
        best["telemetry"] = tel
        return best

    arms = {}
    for mode, async_steps in (("sync", False), ("async", True)):
        arms[f"off_{mode}"] = run_arm(False, async_steps)
        arms[f"on_{mode}"] = run_arm(True, async_steps)

    # In-memory artifact validation on the tracing-on sync capture.
    tel_on = arms["on_sync"].pop("telemetry")
    trace = chrome_trace(tel_on.recorder)
    trace_problems = validate_trace(trace)
    event_types = sorted(
        {e["name"] for e in trace["traceEvents"] if e["ph"] != "M"}
    )
    prom = tel_on.registry.to_prometheus()
    prom_ok = (
        "# TYPE lane_step_ms histogram" in prom
        and 'lane_step_ms_bucket{lane="' in prom
        and "queue_wait_ms_count" in prom
    )

    # Micro: disabled-path tax per step vs the measured step time.
    site_ns = disabled_site_ns(20_000)
    phase_ns = disabled_span_ns(20_000)
    off = arms["off_sync"]
    steps = max(1, off.get("steps", 1))
    span_ns = off.get("span_s", 0.0) * 1e9
    step_ns = span_ns / steps if span_ns else float("inf")
    off_overhead_frac = (
        site_ns * SITES_PER_STEP + phase_ns * SPANS_PER_STEP
    ) / step_ns

    ratios = {}
    identical = {}
    for mode in ("sync", "async"):
        o, n = arms[f"off_{mode}"], arms[f"on_{mode}"]
        ratios[mode] = (
            n.get("tok_per_s", 0.0) / o.get("tok_per_s", 1.0)
            if o.get("tok_per_s")
            else 0.0
        )
        identical[mode] = (
            o.pop("greedy_tokens", None) == n.pop("greedy_tokens", None)
        )
    for arm in arms.values():  # strip non-JSON fields
        arm.pop("greedy_tokens", None)
        arm.pop("telemetry", None)

    caw_zero = all(
        arms[a].get("compiles_after_warmup") == 0 for a in arms
    )
    acceptance = {
        "tracing_off_overhead_frac": round(off_overhead_frac, 5),
        "tracing_off_ok": off_overhead_frac <= 0.01,
        "tracing_on_ratio_sync": round(ratios["sync"], 4),
        "tracing_on_ratio_async": round(ratios["async"], 4),
        "tracing_on_ok": min(ratios.values()) >= 0.95,
        "greedy_bitwise_identical": all(identical.values()),
        "zero_post_warmup_compiles": caw_zero,
        "trace_valid": not trace_problems,
        "trace_event_types": event_types,
        "prometheus_valid": prom_ok,
    }
    return {
        "meta": {
            "arch": cfg.name,
            "n_requests": n_requests,
            "slots": slots,
            "tokens_mean": tokens_mean,
            "max_len": max_len,
            "seed": seed,
            "repeats": repeats,
            "sites_per_step": SITES_PER_STEP,
            "disabled_site_ns": round(site_ns, 1),
            "spans_per_step": SPANS_PER_STEP,
            "disabled_span_ns": round(phase_ns, 1),
            "step_ns": round(step_ns, 1),
        },
        **{k: v for k, v in arms.items()},
        "acceptance": acceptance,
    }
