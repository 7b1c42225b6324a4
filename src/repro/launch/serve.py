"""Serving driver: traffic-driven server loop over the semi-static engine.

Synthesises an open-loop Poisson request stream (mixed greedy/sample, random
lengths) and drives it through the serving runtime, reporting per-request
latency percentiles, throughput, and cold-path activity (compiles, rebinds).

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --smoke \
      --requests 24 --rate 100 --tokens-mean 8 --engine both
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax

from repro import models
from repro.configs import get_config
from repro.core.faults import FaultPlan
from repro.core.telemetry import Telemetry
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.admission import SHED_POLICIES
from repro.runtime.scheduler import (
    attach_distinct_prompts,
    poisson_arrivals,
    shared_prefix_arrivals,
)
from repro.runtime.serve import (
    Engine,
    EngineConfig,
    run_burst_stream,
    run_continuous_stream,
    run_overload_stream,
    run_paged_stream,
)
from repro.runtime.tracing import write_trace


def _print_report(rep: dict) -> None:
    head = (
        f"[serve/{rep['engine']}] {rep.get('finished', 0)} requests, "
        f"{rep.get('tokens', 0)} tokens"
    )
    if "p50_ms" in rep:
        head += (
            f" | latency p50 {rep['p50_ms']:.1f}ms p95 {rep['p95_ms']:.1f}ms "
            f"p99 {rep['p99_ms']:.1f}ms | {rep['tok_per_s']:.0f} tok/s"
        )
    if "ttft_p95_ms" in rep:
        head += (
            f" | ttft p50 {rep['ttft_p50_ms']:.1f}ms "
            f"p95 {rep['ttft_p95_ms']:.1f}ms"
        )
    print(head, flush=True)
    cold = {
        k: rep[k]
        for k in (
            "compiles_total",
            "compiles_after_warmup",
            "rebinds",
            "mode_switches",
            "slots",
            "steps",
            "occupancy",
            "prefill_chunk",
            "prefill_chunks",
            "chunk_bucket_crossings",
            "h2d_uploads",
            "mesh",
            "pool_shards",
        )
        if k in rep
    }
    print(f"[serve/{rep['engine']}] cold path: {cold}", flush=True)
    if "lane_steps" in rep:  # multi-lane pipeline telemetry (DESIGN.md §11)
        lanes = {"lane_steps": rep["lane_steps"]}
        if "tokens_per_target_step" in rep:
            lanes["tok_per_target_step"] = rep["tokens_per_target_step"]
        print(f"[serve/{rep['engine']}] lanes: {lanes}", flush=True)
    if rep.get("pipeline"):  # async step pipeline telemetry (DESIGN.md §13)
        pl = rep["pipeline"]
        print(
            f"[serve/{rep['engine']}] pipeline: "
            f"async={pl['async_steps']} "
            f"host_plan {pl['host_plan_ms']:.1f}ms / "
            f"device_wait {pl['device_wait_ms']:.1f}ms "
            f"(overlap {pl['overlap_ratio']:.2f}) "
            f"inflight_depth={pl['inflight_depth']} "
            f"d2h_transfers={pl['d2h_transfers']}",
            flush=True,
        )
    if rep.get("spec"):
        sp = rep["spec"]
        print(
            f"[serve/{rep['engine']}] specdec: k={sp['k']} "
            f"accept={sp['acceptance_rate']:.3f} "
            f"(p50 {sp.get('acceptance_p50', 0.0):.2f} "
            f"p95 {sp.get('acceptance_p95', 0.0):.2f}) "
            f"accepted={sp['accepted_tokens']}/{sp['drafted_tokens']} "
            f"k_crossings={sp['k_bucket_crossings']}",
            flush=True,
        )
    if rep.get("engine") == "paged":
        paged = {
            k: rep[k]
            for k in (
                "kv_dtype",
                "pool_pages",
                "pages_in_use_peak",
                "peak_concurrent",
                "share_ratio",
                "overcommit_ratio",
                "preemptions",
                "bucket_crossings",
                "cow_copies",
            )
            if k in rep
        }
        print(f"[serve/paged] kvcache: {paged}", flush=True)
    if rep.get("disagg"):  # prefill/decode split surfaces (DESIGN.md §17)
        print(
            f"[serve/paged] disagg: prefill_slice={rep['disagg']} "
            f"migrations={rep.get('migrations', 0)} "
            f"migrated_pages={rep.get('migrated_pages', 0)} "
            f"rebinds={rep.get('disagg_rebinds', 0)}",
            flush=True,
        )
    if rep.get("engine") == "overload":  # hardening surfaces (DESIGN.md §15)
        hard = {
            k: rep[k]
            for k in (
                "capacity",
                "shed_policy",
                "shed",
                "cancelled",
                "failed",
                "deadline_missed",
                "stragglers",
                "preemptions",
                "unserved",
                "degrade_rung",
            )
            if rep.get(k) is not None
        }
        print(f"[serve/overload] hardening: {hard}", flush=True)
        if rep.get("degrade_transitions"):
            print(
                f"[serve/overload] ladder: {rep['degrade_transitions']}",
                flush=True,
            )
        if rep.get("faults"):
            print(f"[serve/overload] faults: {rep['faults']}", flush=True)
    if rep.get("robustness"):  # registry-derived accounting (DESIGN.md §15)
        print(
            f"[serve/{rep['engine']}] robustness: {rep['robustness']}",
            flush=True,
        )


def main(argv: list[str] | None = None) -> dict:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--tokens-mean", type=float, default=8.0,
                    help="mean decode length (geometric)")
    ap.add_argument("--sample-frac", type=float, default=0.5,
                    help="fraction of requests that sample (vs greedy)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--slots", type=int, default=0,
                    help="continuous-batching slots (0 = engine max_batch)")
    ap.add_argument("--engine",
                    choices=("continuous", "burst", "paged", "overload",
                             "both", "all"),
                    default="both")
    ap.add_argument("--page-size", type=int, default=8,
                    help="paged engine: tokens per KV page")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="paged engine: pool pages (0 = dense-equivalent)")
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="paged engine: shared prompt prefix length")
    ap.add_argument("--num-prefixes", type=int, default=3,
                    help="paged engine: number of distinct shared prefixes")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: max prompt tokens ingested per "
                         "step (0 = token-by-token teacher forcing)")
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="attach a distinct random prompt of this length to "
                         "every request (continuous/paged engines)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: max draft depth per target "
                         "step (0 = off; k-buckets {1,2,...,K} are "
                         "AOT-warmed draft/verify dispatch keys)")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="speculative decoding: layer-periods of the target "
                         "retained in the truncated-layer draft view")
    ap.add_argument("--kv-dtype", choices=("fp32", "int8"), default="fp32",
                    help="paged engine: KV page storage dtype (DESIGN.md "
                         "§12). int8 pages carry per-page scales and cost "
                         "~1/4 the bytes; the dtype is a warmed dispatch "
                         "coordinate, so serving either pool never "
                         "compiles mid-stream")
    ap.add_argument("--mesh", default="1x1",
                    help="serving device mesh 'DPxMP' — data x model "
                         "parallel (also accepts 'dp,mp'). Meshes over one "
                         "device need that many JAX devices (on CPU: "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N). The mesh is an AOT-warmed dispatch "
                         "coordinate (DESIGN.md §16)")
    ap.add_argument("--meshes", default="",
                    help="space-separated standby mesh names to AOT-warm "
                         "alongside --mesh (e.g. '1x2 2x2'): a mid-stream "
                         "rebind onto any of them — scale-out or failover "
                         "shrink — is a hot-slot flip, never a compile")
    ap.add_argument("--async-steps", action="store_true",
                    help="software-pipelined step loop (DESIGN.md §13): "
                         "host plans step N+1 while step N's outputs stay "
                         "on device; d2h syncs land at token-emit "
                         "boundaries only. Greedy streams are bitwise "
                         "identical to the synchronous loop")
    ap.add_argument("--async-depth", type=int, default=2,
                    help="async step pipeline: in-flight queue depth "
                         "(issued-but-uncommitted steps; 2 = classic "
                         "one-ahead, deeper queues suit accelerators "
                         "whose enqueue is truly asynchronous)")
    ap.add_argument("--disagg", nargs="?", const=True, default=None,
                    metavar="SLICE",
                    help="disaggregated prefill/decode (DESIGN.md §17): "
                         "pin the prefill lanes to a mesh slice "
                         "('DPxMP@OFF', e.g. '1x1@1') while decode stays "
                         "on --mesh; with no value the canonical slice "
                         "right after the decode slice's devices is "
                         "derived. The slice must be listed in --meshes "
                         "so its lane cells are AOT-warmed; needs "
                         "--engine paged and --prefill-chunk > 0")
    ap.add_argument("--capacity", type=int, default=0,
                    help="overload engine: bounded admission-queue "
                         "capacity (0 = unbounded; DESIGN.md §15)")
    ap.add_argument("--shed-policy", choices=SHED_POLICIES,
                    default="reject-new",
                    help="overload engine: what to drop when the bounded "
                         "queue is full")
    ap.add_argument("--queue-ttl", type=float, default=0.0,
                    help="overload engine: shed requests that waited in "
                         "queue longer than this many seconds (0 = off)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="overload engine: per-request SLO in seconds — "
                         "bounds queue wait (ttl) and sets the absolute "
                         "decode deadline past which a seated request is "
                         "cancelled (0 = off)")
    ap.add_argument("--degrade", action="store_true",
                    help="overload engine: enable the semi-static "
                         "degradation ladder (spec off -> chunk-min -> "
                         "budget-trim -> int8 pool), hysteresis-guarded "
                         "rebinds over warmed keys, never a compile")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="overload engine: arm FaultPlan.random(SEED) — "
                         "deterministic fault injection across the five "
                         "sites, with detection/containment accounting "
                         "in the report")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="emit the reports as one JSON object on stdout")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable the flight recorder (DESIGN.md §14) and "
                         "write a Chrome trace-event JSON file, openable "
                         "in ui.perfetto.dev — one track per lane plus "
                         "dispatcher / scheduler / page-pool tracks")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot after the "
                         "run: Prometheus text exposition if PATH ends in "
                         ".prom, JSON otherwise")
    ap.add_argument("--compile-report", default=None, metavar="PATH",
                    help="write a per-DispatchKey compile report (build "
                         "ms + HLO FLOPs/bytes estimate) as JSON")
    args = ap.parse_args(argv)
    if args.rate <= 0:
        ap.error(f"--rate must be > 0 requests/s, got {args.rate}")
    if args.requests < 1:
        ap.error(f"--requests must be >= 1, got {args.requests}")
    if args.prompt_len > 0 and args.engine in ("burst", "both", "all"):
        # the per-burst driver seeds first_token only and never ingests
        # prompts; a side-by-side report would compare different workloads
        ap.error(
            "--prompt-len requires --engine continuous or paged "
            "(the burst driver does not ingest prompts)"
        )
    if args.spec_k > 0 and args.engine in ("burst", "both", "all"):
        ap.error(
            "--spec-k requires --engine continuous or paged "
            "(the burst driver has no draft/verify lanes)"
        )
    if args.kv_dtype != "fp32" and args.engine not in ("paged", "overload"):
        ap.error(
            "--kv-dtype requires --engine paged or overload (the dense "
            "cache has no page pool to quantise)"
        )
    if args.engine != "overload" and (
        args.capacity or args.queue_ttl or args.deadline or args.degrade
        or args.chaos_seed is not None
    ):
        ap.error(
            "--capacity/--queue-ttl/--deadline/--degrade/--chaos-seed "
            "require --engine overload (the hardened serving loop)"
        )
    if args.async_steps and args.engine in ("burst", "both", "all"):
        ap.error(
            "--async-steps requires --engine continuous or paged (the "
            "per-burst driver has no step pipeline to overlap)"
        )
    if args.async_depth < 1:
        ap.error(f"--async-depth must be >= 1, got {args.async_depth}")
    if args.disagg is not None and args.engine != "paged":
        ap.error(
            "--disagg requires --engine paged (prefill/decode "
            "disaggregation pins the paged lanes to mesh slices)"
        )
    if args.disagg is not None and args.prefill_chunk <= 0:
        ap.error(
            "--disagg requires --prefill-chunk > 0 (without the chunked "
            "prefill lane there is nothing to pin to a prefill slice)"
        )

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if cfg.input_kind != "tokens":
        raise SystemExit(
            f"{cfg.name} has a stub modality frontend; the serving loop "
            f"feeds sampled ids back and needs a token-input arch "
            f"(e.g. olmo-1b)."
        )
    params = models.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(
        max_len=args.max_len,
        batch_quantum=2,
        max_batch=8,
        page_size=args.page_size,
        num_pages=args.num_pages,
        prefill_chunk=args.prefill_chunk,
        spec_k=args.spec_k,
        draft_layers=args.draft_layers,
        kv_dtype=args.kv_dtype,
        mesh=args.mesh,
        meshes=tuple(args.meshes.split()),
    )

    def traffic(seed: int):
        reqs = poisson_arrivals(
            args.requests,
            args.rate,
            seed=seed,
            tokens_mean=args.tokens_mean,
            tokens_max=max(1, args.max_len - max(args.prompt_len, 1) + 1),
            sample_frac=args.sample_frac,
            vocab=cfg.vocab_size,
        )
        if args.prompt_len > 0:  # distinct long prompts (DESIGN.md §10)
            attach_distinct_prompts(
                reqs, args.prompt_len, vocab=cfg.vocab_size, seed=seed + 1
            )
        return reqs

    def prefix_traffic(seed: int):
        return shared_prefix_arrivals(
            args.requests,
            args.rate,
            seed=seed,
            num_prefixes=args.num_prefixes,
            prefix_len=args.prefix_len,
            tokens_mean=args.tokens_mean,
            total_max=args.max_len,
            sample_frac=args.sample_frac,
            vocab=cfg.vocab_size,
        )

    # One Telemetry shared by every engine (DESIGN.md §14): the flight
    # recorder is enabled only when a trace is requested (otherwise call
    # sites pay a single None-check), compile analysis only when the
    # compile report is requested (as_text + parse per built executable).
    telemetry = Telemetry(
        enabled=args.trace_out is not None,
        compile_analysis=args.compile_report is not None,
    )

    # Every engine run is close-guarded and the whole sweep is
    # interrupt-guarded: a Ctrl-C mid-stream keeps the reports of every
    # completed engine and still flushes the telemetry artifacts
    # (--trace-out/--metrics-out/--compile-report) on the way out, then
    # exits 130: an interrupted run never reports success.
    reports = {}
    interrupted = False
    try:
        if args.engine in ("continuous", "both", "all"):
            eng = Engine(cfg, params, ecfg, telemetry=telemetry)
            try:
                reports["continuous"] = run_continuous_stream(
                    eng,
                    traffic(args.seed),
                    slots=args.slots or None,
                    async_steps=args.async_steps,
                    async_depth=args.async_depth,
                )
            finally:
                eng.close()
        if args.engine in ("burst", "both", "all"):
            eng = Engine(cfg, params, ecfg, telemetry=telemetry)
            try:
                reports["burst"] = run_burst_stream(eng, traffic(args.seed))
            finally:
                eng.close()
        if args.engine in ("paged", "all"):
            eng = Engine(cfg, params, ecfg, telemetry=telemetry)
            try:
                # --prompt-len switches the paged stream from the
                # shared-prefix workload (DESIGN.md §9) to long distinct
                # prompts (DESIGN.md §10)
                paged_reqs = (
                    traffic(args.seed) if args.prompt_len > 0
                    else prefix_traffic(args.seed)
                )
                reports["paged"] = run_paged_stream(
                    eng,
                    paged_reqs,
                    slots=args.slots or None,
                    async_steps=args.async_steps,
                    async_depth=args.async_depth,
                    disagg=args.disagg,
                )
            finally:
                eng.close()
        if args.engine == "overload":
            over_cfg = ecfg
            if args.degrade and "int8" not in (
                ecfg.kv_dtype, *ecfg.kv_dtypes
            ):
                # warm the int8 standby pool so the ladder's bottom rung
                # (admission-routed pool flip) is expressible
                over_cfg = dataclasses.replace(
                    ecfg, kv_dtypes=(*ecfg.kv_dtypes, "int8")
                )
            eng = Engine(cfg, params, over_cfg, telemetry=telemetry)
            try:
                reqs = traffic(args.seed)
                if args.deadline > 0:
                    for r in reqs:
                        r.ttl_s = args.deadline
                        r.deadline_s = r.arrival_s + args.deadline
                plan = (
                    FaultPlan.random(args.chaos_seed)
                    if args.chaos_seed is not None else None
                )
                reports["overload"] = run_overload_stream(
                    eng,
                    reqs,
                    slots=args.slots or None,
                    async_steps=args.async_steps,
                    kv_dtype=args.kv_dtype,
                    capacity=args.capacity or None,
                    shed_policy=args.shed_policy,
                    queue_ttl_s=args.queue_ttl or None,
                    degrade=args.degrade,
                    faults=plan,
                )
            finally:
                eng.close()
    except KeyboardInterrupt:
        interrupted = True
        print(
            "[serve] interrupted — engines drained; writing telemetry "
            "artifacts before exit",
            flush=True,
        )
    finally:
        for path in (args.trace_out, args.metrics_out, args.compile_report):
            if path and os.path.dirname(path):
                os.makedirs(os.path.dirname(path), exist_ok=True)
        if args.trace_out:
            trace = write_trace(args.trace_out, telemetry.recorder)
            print(
                f"[serve] trace: {args.trace_out} "
                f"({len(trace['traceEvents'])} events, "
                f"{telemetry.recorder.dropped} dropped) — open in "
                f"ui.perfetto.dev",
                flush=True,
            )
        if args.metrics_out:
            with open(args.metrics_out, "w") as fh:
                if args.metrics_out.endswith(".prom"):
                    fh.write(telemetry.registry.to_prometheus())
                else:
                    fh.write(telemetry.metrics_json())
            print(f"[serve] metrics: {args.metrics_out}", flush=True)
        if args.compile_report:
            with open(args.compile_report, "w") as fh:
                json.dump(telemetry.compile_reports, fh, indent=2)
            print(
                f"[serve] compile report: {args.compile_report} "
                f"({len(telemetry.compile_reports)} keys)",
                flush=True,
            )

    if interrupted:
        print(
            f"[serve] partial results: {sorted(reports)} completed",
            flush=True,
        )
    if args.json:
        print(json.dumps(reports, indent=2))
    else:
        for rep in reports.values():
            _print_report(rep)
        if len(reports) == 2 and all(
            "tok_per_s" in r for r in reports.values()
        ):
            c, b = reports["continuous"], reports["burst"]
            print(
                f"[serve] continuous vs burst: "
                f"{c['tok_per_s']:.0f} vs {b['tok_per_s']:.0f} tok/s, "
                f"p99 {c['p99_ms']:.1f} vs {b['p99_ms']:.1f} ms, "
                f"compiles after warmup {c['compiles_after_warmup']} vs "
                f"{b['compiles_after_warmup']}",
                flush=True,
            )
    if interrupted:
        raise SystemExit(130)  # a cut-short run is not a success
    return reports


if __name__ == "__main__":
    main()
