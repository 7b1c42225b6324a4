"""Chip smoke: the paged serving engine on a TPU at olmo-1b's published widths.

Serves a seeded stream of 16 greedy requests (distinct 512-token prompts,
geometric output lengths with mean 32) through ``Engine.paged_continuous``,
driven by ``run_paged_stream`` exactly as ``python -m repro.launch.serve
--engine paged`` drives it. The model is olmo-1b at its published widths
(16 layers, d_model 2048, 16 heads x 128, d_ff 8192, vocab 50304, bf16) with
random weights from a seed. What comes out is checked against
``models.forward`` on the same weights.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # the 2x2 mesh and the prefill/decode
                                     # split, each beside the 1x1 run

The script needs a TPU and has no CPU path: it exits non-zero, and prints no
``"ok"`` line, when JAX finds no TPU or any phase fails. Its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The throughput it prints is a smoke reading, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path
from typing import Callable

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import models  # noqa: E402
from repro.configs import ArchConfig, get_config  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.runtime.scheduler import (  # noqa: E402
    Request,
    attach_distinct_prompts,
    poisson_arrivals,
)
from repro.runtime.serve import Engine, EngineConfig, run_paged_stream  # noqa: E402

ARCH = "olmo-1b"
SEED = 0
# Sized from the v5e compile rehearsal (tests/test_tpu_compile.py): the
# parameters (2.35 GB) and the dense-equivalent pool of 1024 pages
# (2.15 GB) stay resident, and the largest step, the 256-token prefill
# chunk, adds 2.82 GB of temp: 7.3 GB of the chip's 16 GB.
SLOTS = 8
MAX_LEN = 2048
PAGE_SIZE = 16
PREFILL_CHUNK = 256
REQUESTS = 16
PROMPT_LEN = 512
TOKENS_MEAN = 32
RATE_HZ = 1000.0  # every request is due within the first few steps
CHECKED = 4
# Reference check: at every emitted position, the engine's token must have
# a reference logit within DELTA_SIGMA standard deviations (of that
# position's reference logits) of the position's maximum. Both sides run
# in bf16 and differ only in rounding: the head rounds logits to bf16
# (spacing 2**-6 near the maximum of a random-weight model), and the hidden
# state drifts by a few bf16 steps over 16 layers, which moves a logit by a
# few hundredths of a standard deviation. Random weights make near-ties,
# so exact token equality would flip on rounding alone. A wrong path (a
# wrong page, position or layer) emits tokens that are ordinary draws
# against the reference, about four deviations below the maximum of 50304
# logits; 0.5 deviations admits only the top ten or so of them.
DELTA_SIGMA = 0.5


def say(key: str, value) -> None:
    print(f"chip_smoke: {key}: {value}", flush=True)


def require_tpu() -> dict:
    """The device as JAX reports it; refuses anything but a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r}"
        )
    return device_info()


def device_info() -> dict:
    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def engine_config(**overrides) -> EngineConfig:
    ecfg = EngineConfig(
        max_len=MAX_LEN,
        max_batch=SLOTS,
        page_size=PAGE_SIZE,
        prefill_chunk=PREFILL_CHUNK,
        spec_k=0,
        kv_dtype="fp32",  # the model dtype: bf16 pages at these widths
        mesh="1x1",
    )
    return dataclasses.replace(ecfg, **overrides)


def make_stream(
    cfg: ArchConfig,
    *,
    requests: int = REQUESTS,
    prompt_len: int = PROMPT_LEN,
    tokens_mean: float = TOKENS_MEAN,
    max_len: int = MAX_LEN,
    seed: int = SEED,
) -> list[Request]:
    reqs = poisson_arrivals(
        requests,
        RATE_HZ,
        seed=seed,
        tokens_mean=tokens_mean,
        tokens_max=max_len - prompt_len,
        sample_frac=0.0,
        vocab=cfg.vocab_size,
    )
    return attach_distinct_prompts(
        reqs, prompt_len, vocab=cfg.vocab_size, seed=seed + 1
    )


def serve(
    cfg: ArchConfig,
    params,
    ecfg: EngineConfig,
    reqs: list[Request],
    *,
    disagg: str | None = None,
) -> dict:
    """One stream through the paged engine; the report of
    ``run_paged_stream`` plus the wall-clock split of warmup and serving."""
    t0 = time.perf_counter()
    with Engine(cfg, params, ecfg) as eng:
        report = run_paged_stream(eng, reqs, disagg=disagg)
        report["compile_s"] = eng._decode.stats.compile_seconds
    report["wall_s"] = time.perf_counter() - t0
    report["warmup_s"] = report["wall_s"] - report.get("span_s", 0.0)
    return report


def check_stream(cfg: ArchConfig, reqs: list[Request], report: dict) -> None:
    """Every request finished, nothing compiled after warmup, every token
    is a vocabulary id."""
    if report.get("finished") != len(reqs) or report.get("unserved"):
        raise AssertionError(
            f"{report.get('finished')}/{len(reqs)} requests finished "
            f"({report.get('unserved')} unserved)"
        )
    short = [r.rid for r in reqs if len(r.tokens) != r.new_tokens]
    if short:
        raise AssertionError(f"requests {short} emitted the wrong count")
    if report["compiles_after_warmup"] != 0:
        raise AssertionError(
            f"{report['compiles_after_warmup']} compiles after warmup"
        )
    toks = np.concatenate([np.asarray(r.tokens, np.int64) for r in reqs])
    if toks.min() < 0 or toks.max() >= cfg.vocab_size:
        raise AssertionError(
            f"token ids span [{toks.min()}, {toks.max()}], outside "
            f"[0, {cfg.vocab_size})"
        )


@functools.partial(jax.jit, static_argnums=0)
def _reference_rows(cfg, params, seqs, prompt_len, emitted):
    """Reference logits at the emitting positions: ``seqs`` [B, L] holds
    prompt + emitted tokens (zero-padded at the end, which causal attention
    never lets earlier rows see); ``emitted[b, i]`` was chosen from row
    ``prompt_len[b] - 1 + i``. Returns per position the maximum, the
    emitted token's logit, the standard deviation and the argmax, plus
    whether every logit of the forward pass is finite."""
    logits, _ = models.forward(cfg, params, seqs, impl="naive")
    t = emitted.shape[1]
    rows_at = prompt_len[:, None] - 1 + jnp.arange(t)[None, :]
    rows_at = jnp.clip(rows_at, 0, seqs.shape[1] - 1)
    rows = jnp.take_along_axis(logits, rows_at[..., None], axis=1)  # [B,T,V]
    chosen = jnp.take_along_axis(
        rows, jnp.maximum(emitted, 0)[..., None], axis=2
    )[..., 0]
    return (
        rows.max(-1),
        chosen,
        rows.std(-1),
        jnp.argmax(rows, -1),
        jnp.isfinite(logits).all(),
    )


def reference_check(
    cfg: ArchConfig,
    params,
    reqs: list[Request],
    *,
    n: int = CHECKED,
    delta_sigma: float = DELTA_SIGMA,
) -> dict:
    """``models.forward`` on prompt + emitted for ``n`` requests; raises
    unless every emitted token is within ``delta_sigma`` deviations of
    its position's reference maximum and no reference logit is NaN."""
    picked = sorted(reqs, key=lambda r: r.rid)[:n]
    length = max(len(r.prompt) + len(r.tokens) for r in picked)
    width = max(len(r.tokens) for r in picked)
    seqs = np.zeros((len(picked), length), np.int32)
    emitted = np.full((len(picked), width), -1, np.int32)
    for b, r in enumerate(picked):
        full = list(r.prompt) + list(r.tokens)
        seqs[b, : len(full)] = full
        emitted[b, : len(r.tokens)] = r.tokens
    prompt_len = np.array([len(r.prompt) for r in picked], np.int32)
    top, chosen, sigma, argmax, finite = jax.device_get(
        _reference_rows(
            cfg, params, jnp.asarray(seqs), jnp.asarray(prompt_len),
            jnp.asarray(emitted),
        )
    )
    if not finite:
        raise AssertionError("the reference forward produced a NaN or inf")
    real = emitted >= 0
    margin = (top - chosen) / np.maximum(sigma, 1e-30)
    worst = float(margin[real].max())
    result = {
        "requests": [r.rid for r in picked],
        "positions": int(real.sum()),
        "exact": int((argmax == emitted)[real].sum()),
        "worst_margin_sigma": worst,
        "delta_sigma": delta_sigma,
    }
    if worst > delta_sigma:
        b, i = np.argwhere(real & (margin == worst))[0]
        raise AssertionError(
            f"request {picked[b].rid} token {i}: the engine emitted "
            f"{emitted[b, i]}, {worst:.3f} deviations below the reference "
            f"maximum (token {argmax[b, i]}); the limit is {delta_sigma}"
        )
    return result


def run_arm(
    name: str,
    cfg: ArchConfig,
    params,
    ecfg: EngineConfig,
    stream: Callable[[], list[Request]],
    *,
    disagg: str | None = None,
) -> dict:
    """Serve a fresh copy of the seeded stream, check it, check it against
    the reference, and print what the arm measured."""
    reqs = stream()
    report = serve(cfg, params, ecfg, reqs, disagg=disagg)
    check_stream(cfg, reqs, report)
    ref = reference_check(cfg, params, reqs)
    say(f"{name} requests finished", f"{report['finished']}/{len(reqs)}")
    say(f"{name} compiles_after_warmup", report["compiles_after_warmup"])
    say(f"{name} compiles during warmup", report["compiles_total"])
    say(f"{name} warmup seconds", report["warmup_s"])
    say(f"{name} compile seconds", report["compile_s"])
    say(
        f"{name} tok/s over the served span (smoke reading, not a "
        f"benchmark)",
        report["tok_per_s"],
    )
    say(f"{name} tokens", report["tokens"])
    say(f"{name} steps", report["steps"])
    say(f"{name} prefill chunks", report["prefill_chunks"])
    say(f"{name} mesh", report["mesh"])
    if disagg:
        say(f"{name} migrations", report["migrations"])
    say(f"{name} reference check passed", ref)
    report["reference"] = ref
    return report


def check_mesh_plans(cfg: ArchConfig, ecfg: EngineConfig) -> None:
    """The four-chip arms' placements span the devices they name: the 2x2
    mesh holds four distinct devices and gives each a quarter of the page
    pool (pages over 'data', heads over 'model'), and the prefill slice
    1x1@1 sits on device 1, not device 0."""
    devs = jax.devices()
    plan = shd.MeshPlan("2x2")
    # make_mesh orders the devices along the chips' links, not by id
    on_mesh = sorted(d.id for d in plan.mesh.devices.flat)
    if on_mesh != [d.id for d in devs[:4]]:
        raise AssertionError(f"2x2 mesh holds devices {on_mesh}, not 0-3")
    with Engine(cfg, params_shape(cfg), ecfg) as eng:
        pages = eng.pool_physical_pages
    pool = jax.eval_shape(
        lambda: models.init_paged_cache(cfg, pages, ecfg.page_size)
    )
    k = pool[0]["k"]
    sh = plan.paged_cache_shardings(pool)[0]["k"]
    want = (k.shape[0], pages // 2, k.shape[2], k.shape[3] // 2, k.shape[4])
    if len(sh.device_set) != 4 or sh.shard_shape(k.shape) != want:
        raise AssertionError(
            f"2x2 pool shard {sh.shard_shape(k.shape)} over "
            f"{len(sh.device_set)} devices, want {want} over 4"
        )
    solo = shd.MeshPlan("1x1@1")
    if solo.device != devs[1]:
        raise AssertionError(f"prefill slice 1x1@1 sits on {solo.device}")


def params_shape(cfg: ArchConfig):
    return jax.eval_shape(
        lambda: models.init_params(cfg, jax.random.PRNGKey(SEED))
    )


def main(argv: list[str] | None = None) -> int:
    cache_dir = enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run the 2x2 mesh and the 1x1 + 1x1@1 prefill/decode split, "
             "each beside the 1x1 run on device 0 (needs four chips)",
    )
    args = ap.parse_args(argv)

    device = require_tpu()
    say("device_kind", device["kind"])
    say("device count", device["count"])
    say("compile cache", cache_dir)
    if args.four_chips and device["count"] < 4:
        raise SystemExit(
            f"chip_smoke: --four-chips needs 4 devices, found "
            f"{device['count']}"
        )

    cfg = get_config(ARCH)
    say(
        "model",
        f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads}x{cfg.head_dim} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.dtype}",
    )
    t0 = time.perf_counter()
    # one compiled program: eager initialisation dispatches each op alone
    params = jax.block_until_ready(
        jax.jit(models.init_params, static_argnums=0)(
            cfg, jax.random.PRNGKey(SEED)
        )
    )
    say("params init seconds", time.perf_counter() - t0)

    stream = functools.partial(make_stream, cfg)
    run_arm("1x1", cfg, params, engine_config(), stream)
    if args.four_chips:
        check_mesh_plans(cfg, engine_config(mesh="2x2"))
        say("mesh plans", "2x2 spans devices 0-3; 1x1@1 sits on device 1")
        run_arm("2x2", cfg, params, engine_config(mesh="2x2"), stream)
        run_arm(
            "disagg", cfg, params, engine_config(meshes=("1x1@1",)), stream,
            disagg="1x1@1",
        )
    stats = jax.devices()[0].memory_stats() or {}
    say("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
