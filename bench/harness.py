"""One run of one benchmark cell.

``BENCHMARK.json`` names the cell; the cell names a configuration file
(``configs/<config>.json``: the model's sizes, its serving setup, its plain
reference and the limits of the output check) and a traffic mix
(``traffic/<mix>.json``: lengths, prefix sharing and the arrival process,
read by ``loadgen``). Per-layer metrics are readers
in ``metrics/<name>.py`` (or ``metrics/<name before the first dot>.py``), each
a ``read(run)`` that returns a number or None. Nothing here names a cell, a
configuration, a mix or a metric: a new one is new files and entries.

A run: check the chip, make the weights from the seed on the device, build
and warm the engine (``Engine.paged_continuous``), serve the mix in real
time through a lead-in, measure a window of ``--seconds``, keep serving until
every request due in the window has its first token, then free the engine
and check a seeded sample of the served requests against the reference.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field, fields
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import counts  # noqa: E402
import loadgen  # noqa: E402
import phases  # noqa: E402
import weights  # noqa: E402
import devtrace as tracing  # noqa: E402

DRAIN_S = 60.0  # how long past the window a due request may wait for its first token
DECODE_MODULE = "paged_slot_step"  # the cbp lane's jitted step
PREFILL_MODULE = "paged_prefill_step"  # the pf lane's jitted step


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ----------------------------------------------------------------- the spec
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    spec: dict
    bench: Path

    @property
    def model(self) -> dict:
        return self.config["model"]

    def end_to_end(self) -> list[dict]:
        return [
            m for m in self.spec["end_to_end"]
            if self.name in m.get("workloads", [self.name])
        ]

    def per_layer(self) -> list[dict]:
        return [m for m in self.spec["per_layer"] if self.name in m["workloads"]]


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, name: str, root: Path = ROOT, bench: Path = BENCH) -> Cell:
    """The cell ``name`` with its configuration file and mix loaded."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    mix = loadgen.load_mix(bench / "traffic", w["traffic"])
    return Cell(name, int(w["chips"]), config, mix, spec, bench)


def load_reader(bench: Path, metric: str):
    """``metrics/<metric>.py``, else ``metrics/<metric before '.'>.py``."""
    for stem in (metric, metric.split(".")[0]):
        path = bench / "metrics" / f"{stem}.py"
        if path.exists():
            mod_spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path
            )
            mod = importlib.util.module_from_spec(mod_spec)
            mod_spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def load_reference(bench: Path, name: str):
    path = bench / "reference" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------- the device
def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(
            f"bench: needs a TPU, JAX found {devs[0].platform!r}; no result"
        )
    if len(devs) < chips:
        raise NoChip(
            f"bench: the cell needs {chips} chips, JAX found {len(devs)}"
        )
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def memory_peak(chips: list[int]) -> int:
    import jax

    devs = jax.devices()
    peaks = [
        (devs[c].memory_stats() or {}).get("peak_bytes_in_use", 0)
        for c in chips
    ]
    return int(max(peaks))


# ---------------------------------------------------------- serving records
@dataclass
class Served:
    """One request as its client sees it."""

    rid: int
    due: float  # perf_counter seconds
    prompt: np.ndarray
    new_tokens: int
    req: object = None
    admitted: float | None = None
    stamps: list = field(default_factory=list)  # token arrival times
    seen: int = 0  # tokens of the current attempt already accounted
    finished: float | None = None
    preempted: int = 0


@dataclass
class StepRecord:
    decode_keys: list  # per decode row, positions its token attends
    prefill: list  # (start, count, ends_prompt) per request in the pf call


@dataclass
class Counters:
    steps: int
    host_plan_ms: float
    prompt_tokens: int
    shared_tokens: int
    preemptions: int
    compiles: int
    lane_ms: dict  # lane -> (sum ms, count) of the lane_step_ms histogram

    @classmethod
    def read(cls, cb, eng) -> "Counters":
        reg = eng.telemetry.registry
        lane_ms = {}
        for lane in ("cbp", "pf"):
            labels = {"lane": lane}
            if cb.mesh != "1x1":
                labels["mesh"] = cb.mesh
            h = reg.histogram("lane_step_ms", **labels)
            lane_ms[lane] = (h.sum, h.count)
        st = cb.stats
        return cls(
            steps=st.steps, host_plan_ms=st.host_plan_ms,
            prompt_tokens=st.prompt_tokens, shared_tokens=st.shared_tokens,
            preemptions=st.preemptions,
            compiles=eng.post_warmup_compiles, lane_ms=lane_ms,
        )


def live_pages(cb) -> int:
    """Pages held by the requests in the batcher's slots (the pool also
    keeps finished prompts' pages for the prefix cache until it needs them;
    those are not counted)."""
    return sum(t.num_pages for t in cb.live_tables())


def _prefix_hit(cb, req) -> int:
    """Prompt tokens a just-seated request took from the prefix cache: the
    cursor its slot starts prefill from."""
    slot = next(i for i, r in enumerate(cb._slots) if r is req)
    return int(cb._cursor[slot])


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Driver:
    """Serves a mix through a paged batcher in real time and keeps the
    client-side record: due, admit and token-arrival times, and per step
    the work the step's calls required."""

    def __init__(self, cb, eng, mix: dict, model: dict, seed: int):
        self.cb, self.eng, self.mix = cb, eng, mix
        self.arrivals = loadgen.Arrivals(
            mix,
            loadgen.stream(mix, context=model["context"],
                           vocab=model["vocab_size"], seed=seed),
            cb.num_slots,
        )
        self.all: list[Served] = []
        self.waiting: deque[Served] = deque()
        self.inflight: dict[int, Served] = {}
        self.pf_queue: deque[list] = deque()  # [served, cursor], admit order
        self.prompt_tokens = 0
        self.record_steps = False
        self.steps: list[StepRecord] = []
        self.pages_peak = 0
        self.stop_arrivals = False

    # ---- arrivals
    def _new(self, due: float, draw) -> Served:
        from repro.runtime.scheduler import Request

        s = Served(rid=draw.index, due=due, prompt=draw.prompt,
                   new_tokens=draw.new_tokens)
        s.req = Request(
            rid=s.rid, new_tokens=s.new_tokens, greedy=True,
            arrival_s=due, prompt=tuple(int(t) for t in s.prompt),
        )
        self.all.append(s)
        self.waiting.append(s)
        return s

    def start(self, t0: float) -> None:
        for due, draw in self.arrivals.start(t0):
            self._new(due, draw)

    # ---- one iteration of the serving loop
    def tick(self) -> None:
        cb = self.cb
        now = time.perf_counter()
        if not self.stop_arrivals:
            for due, draw in self.arrivals.due(now):
                self._new(due, draw)
        if self.waiting and cb.free_slots > 0:
            self._admit(now)
        if cb.has_work:
            with _annotate("bench.step"):
                t0 = time.perf_counter()
                finished = cb.step(now=t0)
                t1 = time.perf_counter()
            self._account(t0, t1, finished)
            return
        wake = self.arrivals.next_due
        if wake is None or self.stop_arrivals:
            wake = now + 0.001
        with _annotate("bench.wait"):
            time.sleep(max(0.0, min(wake - now, 0.05)))

    def _admit(self, now: float) -> None:
        cb = self.cb
        batch = [self.waiting.popleft()
                 for _ in range(min(cb.free_slots, len(self.waiting)))]
        with _annotate("bench.admit"):
            deferred = cb.admit([s.req for s in batch], now=now)
        back = {r.rid for r in deferred}
        seated = [s for s in batch if s.rid not in back]
        for s in reversed([s for s in batch if s.rid in back]):
            self.waiting.appendleft(s)
        for s in seated:
            if s.admitted is None:
                s.admitted = now
            self.inflight[s.rid] = s
            # the prefill stream starts after the prompt pages the prefix
            # cache lent the request; a prompt left with its last token
            # only goes straight to decode
            skip = _prefix_hit(cb, s.req)
            if len(s.prompt) - skip > 1:
                self.pf_queue.append([s, skip])

    def _account(self, t0: float, t1: float, finished) -> None:
        cb = self.cb
        pt = cb.stats.prompt_tokens
        segs = self._prefill_segments(pt - self.prompt_tokens)
        self.prompt_tokens = pt
        keys = []
        for s in list(self.inflight.values()):
            n = len(s.req.tokens)
            if n <= s.seen:
                continue
            for j in range(max(s.seen, 1), n):  # token 0 comes from prefill
                keys.append(len(s.prompt) + j)
            for _ in range(len(s.stamps), n):
                s.stamps.append(t1)
            s.seen = n
        for r in finished:
            s = self.inflight.pop(r.rid)
            s.finished = t1
            if not self.stop_arrivals:
                for due, draw in self.arrivals.finished(t1):
                    self._new(due, draw)
        for r in cb.preempted:
            s = self.inflight.pop(r.rid)
            s.seen = 0
            s.preempted += 1
            self.pf_queue = deque(e for e in self.pf_queue if e[0] is not s)
            self.waiting.appendleft(s)
        cb.preempted.clear()
        self.pages_peak = max(self.pages_peak, live_pages(cb))
        if self.record_steps:
            self.steps.append(StepRecord(keys, segs))

    def _prefill_segments(self, tokens: int) -> list:
        """The prompt positions a step's prefill covered. The batcher feeds
        prefilling requests first-admitted first, so the prefill stream is
        the admitted prompts end to end and a step takes its next
        ``tokens`` positions."""
        segs = []
        while tokens > 0 and self.pf_queue:
            entry = self.pf_queue[0]
            s, cursor = entry
            take = min(tokens, len(s.prompt) - cursor)
            ends = cursor + take >= len(s.prompt)
            segs.append((cursor, take, ends))
            tokens -= take
            entry[1] = cursor + take
            if ends:
                self.pf_queue.popleft()
        return segs


# ------------------------------------------------------------ the whole run
@dataclass
class Run:
    """What a run measured, as the per-layer readers see it."""

    cell: Cell
    shapes: counts.Shapes
    peak: dict
    chips_used: list
    window: tuple
    c0: Counters
    c1: Counters
    served: list
    pages_peak: int
    pool_pages: int
    slice: dict | None = None  # the traced slice, see _reduce_trace


def _percentile(xs: list, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q)) if xs else float("nan")


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file: a dense SiLU
    decoder, overlaid with every key of ``model`` that names one of its
    fields (JSON lists as tuples). ``context`` is the engine's; any other
    key is refused by name, so a misspelt one cannot serve a dense model."""
    from repro.configs import ArchConfig

    known = {f.name for f in fields(ArchConfig)}
    model = dict(config["model"])
    model.pop("context")
    unknown = sorted(set(model) - known)
    if unknown:
        raise ValueError(
            f"{config['name']}: model keys {unknown} name no ArchConfig field"
        )
    overlay = {k: tuple(v) if isinstance(v, list) else v for k, v in model.items()}
    return ArchConfig(
        **{"name": config["name"], "family": "dense", "act": "silu", **overlay}
    ).validate()


def engine_config(config: dict):
    """The program's ``EngineConfig`` for a configuration file's context
    and ``serving`` block (``moe_policy`` where it names one)."""
    from repro.runtime.serve import EngineConfig

    sv = config["serving"]
    disagg = sv.get("disagg")
    policy = {"moe_policy": sv["moe_policy"]} if "moe_policy" in sv else {}
    return EngineConfig(
        max_len=config["model"]["context"], max_batch=sv["slots"],
        page_size=sv["page_size"], num_pages=sv["num_pages"],
        prefill_chunk=sv["prefill_chunk"],
        token_budget=sv.get("token_budget", 0), spec_k=0,
        kv_dtype="fp32", mesh="1x1", meshes=(disagg,) if disagg else (),
        **policy,
    )


def build_engine(cell: Cell, params):
    """The program's config and engine for a cell, and its disagg slice."""
    from repro.runtime.serve import Engine

    arch = arch_config(cell.config)
    disagg = cell.config["serving"].get("disagg")
    return arch, Engine(arch, params, engine_config(cell.config)), disagg


def serve_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
    *, require_tpu: bool = True, fault=None, control: str | None = None,
) -> dict:
    """The whole run; returns the result line's object."""
    import jax

    from repro import models
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = device_info(cell.chips, require_tpu)
    peak = counts.peaks(device["kind"]) if require_tpu else None
    params = weights.make_params(cell.model, seed)
    arch, eng, disagg = build_engine(cell, params)
    weights.check_layout(params, jax.eval_shape(
        lambda: models.init_params(arch, jax.random.PRNGKey(0))
    ))
    chips_used = [0, 1] if disagg else [0]
    cb = eng.paged_continuous(seed=seed % 2**32, disagg=disagg)
    if fault is not None:
        fault(cb)
    drv = Driver(cb, eng, cell.mix, cell.model, seed)
    t_origin = time.perf_counter()
    drv.start(t_origin)
    lead_end = t_origin + float(cell.mix["lead_in_s"])
    while time.perf_counter() < lead_end:
        drv.tick()

    slice_s = float(cell.mix.get("trace_slice_s", 4.0))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    t_w0 = time.perf_counter()
    c0 = Counters.read(cb, eng)
    drv.pages_peak = live_pages(cb)
    t_trace = t_w0 + max(seconds - slice_s, 0.0)
    tracing_on = False
    with _annotate("bench.window"):
        while True:
            now = time.perf_counter()
            if now >= t_w0 + seconds:
                break
            if trace and not tracing_on and now >= t_trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing_on = True
                drv.record_steps = True
            drv.tick()
    t_w1 = time.perf_counter()
    c1 = Counters.read(cb, eng)
    pages_peak = drv.pages_peak
    if tracing_on:
        jax.profiler.stop_trace()
        drv.record_steps = False

    # drain: every request due in the window gets its first token
    due = [s for s in drv.all if t_w0 <= s.due < t_w1]
    drv.stop_arrivals = True
    drain_end = t_w1 + DRAIN_S
    while any(not s.stamps for s in due) and time.perf_counter() < drain_end:
        drv.tick()
    t_end = time.perf_counter()

    mem = memory_peak(chips_used)
    pool_pages = cb.pool.num_pages
    run = Run(
        cell=cell, shapes=counts.Shapes.of(cell.model), peak=peak,
        chips_used=chips_used, window=(t_w0, t_w1), c0=c0, c1=c1,
        served=drv.all, pages_peak=pages_peak, pool_pages=pool_pages,
    )
    steps = drv.steps
    # free the program's state before the reference runs
    del cb, drv
    eng.close()
    del eng
    gc.collect()

    failed = [s for s in due if not s.stamps]
    window_s = t_w1 - t_w0
    ttft = [
        (s.stamps[0] if s.stamps else t_end) - s.due for s in due
    ]
    itl = [
        b - a
        for s in run.served
        for a, b in zip(s.stamps, s.stamps[1:])
        if t_w0 < b <= t_w1
    ]
    out_tokens = sum(
        1 for s in run.served for t in s.stamps if t_w0 < t <= t_w1
    )
    e2e = {
        "ttft_p90_ms": (_percentile(ttft, 90) * 1e3, "ms"),
        "itl_p95_ms": (_percentile(itl, 95) * 1e3, "ms"),
        "output_tok_s": (out_tokens / window_s, "tokens/s"),
        "prompt_tok_s": ((c1.prompt_tokens - c0.prompt_tokens) / window_s,
                         "tokens/s"),
        "setup_s": (t_w0 - t_start, "s"),
    }
    info = {
        "requests_due": len(due),
        "ttft_samples": len(ttft),
        "itl_samples": len(itl),
        "compiles_in_window": c1.compiles - c0.compiles,
        "preemptions_in_window": c1.preemptions - c0.preemptions,
        "prefix_hit_tokens_in_window": c1.shared_tokens - c0.shared_tokens,
        "steps_in_window": c1.steps - c0.steps,
        "drain_s": t_end - t_w1,
    }

    # the output check, on a seeded sample of what the window finished
    ref = load_reference(cell.bench, cell.config["reference"])
    done = [s for s in run.served if s.finished and s.finished >= t_w0]
    verdict = check.compare(
        cell.model, params, done, seed, cell.mix.get("check", {}),
        cell.config["limits"], ref, control=control,
    )
    del params
    gc.collect()
    info.update(
        checked_requests=verdict["requests"],
        checked_positions=verdict["positions"],
        checked_exact=verdict["exact"],
    )
    if control is not None:
        info["control"] = control
        info["program_gap"] = verdict["program_gap"]

    result = {
        "correct": verdict["correct"],
        "attempted": len(due),
        "failed": len(failed),
        "device": {**device, "memory_peak_bytes": mem},
    }
    if trace:
        run.slice = _reduce_trace(trace_dir, steps, chips_used, run)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in cell.per_layer():
            value = load_reader(cell.bench, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        if run.slice is not None:
            info["program_idle"] = run.slice["program_idle"]
            result["device"]["busy_s"] = run.slice["busy_s"]
            result["device"]["window_s"] = run.slice["window_s"]
            result["breakdown"] = run.slice["breakdown"]
    else:
        want = {m["name"] for m in cell.end_to_end()}
        result["metrics"] = {
            k: {"value": float(v), "unit": u} for k, (v, u) in e2e.items()
            if k in want
        }
    result["info"] = info
    result["checks"] = verdict["checks"]
    return result


def _reduce_trace(trace_dir, steps, chips_used, run) -> dict | None:
    """Match the host's step records to the trace's ``bench.step`` spans
    (both end with the window's last step), take each lane's calls that
    started inside a matched step, and total their device time and the
    work the step's record says they required. The chips' idle time in the
    matched steps goes to the benchmark's own annotations (``breakdown``)
    and to the program's layers (``program_idle``, see ``phases``)."""
    path = tracing.find_xplane(trace_dir)
    ev = tracing.read_xplane(path)
    host_steps = [e for e in ev.host if e[0] == "bench.step"]
    n = min(len(host_steps), len(steps))
    if n == 0:
        return None
    pairs = list(zip(host_steps[-n:], steps[-n:]))
    lo, hi = pairs[0][0][1], pairs[-1][0][2]
    lanes = {
        "decode": {"device_s": 0.0, "calls": 0, "work": counts.ZERO},
        "prefill": {"device_s": 0.0, "calls": 0, "work": counts.ZERO},
    }
    mods = {c: ev.modules.get(c, []) for c in chips_used}
    for (_, s0, s1), rec in pairs:
        for lane, pattern, work in (
            ("decode", DECODE_MODULE,
             counts.decode_call(run.shapes, rec.decode_keys)),
            ("prefill", PREFILL_MODULE,
             counts.prefill_call(run.shapes, rec.prefill)),
        ):
            calls = [
                e for c in chips_used
                for e in tracing.module_calls(mods[c], pattern)
                if s0 <= e[1] < s1
            ]
            if not calls:
                continue
            lanes[lane]["device_s"] += sum(e[2] - e[1] for e in calls)
            lanes[lane]["calls"] += len(calls)
            lanes[lane]["work"] = lanes[lane]["work"] + work
    busy = [tracing.busy_seconds(ev.ops.get(c, []), lo, hi) for c in chips_used]
    gaps: dict[str, float] = {}
    for c in chips_used:
        for k, v in tracing.attribute_gaps(
            tracing.idle_gaps(ev.ops.get(c, []), lo, hi), ev.host
        ).items():
            gaps[k] = gaps.get(k, 0.0) + v / len(chips_used)
    clipped = ev.clip(lo, hi)
    return {
        "lo": lo, "hi": hi, "steps": n,
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "lanes": lanes,
        "program_idle": phases.program_idle(
            ev.ops, phases.read_program(path), chips_used, lo, hi
        ),
        "breakdown": {
            "device_ops": tracing.top_ops(clipped.ops, chips_used),
            "idle_gaps": [
                [k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])
            ][:10],
        },
    }
