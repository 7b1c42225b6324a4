"""Request scheduler + continuous batching for the serving runtime.

This is the admission layer the paper's cold/hot split demands at serving
scale (DESIGN.md §4). The semi-static hot loop must run uninterrupted; this
module owns everything that happens *around* it:

* ``Request`` / ``RequestQueue`` — arrival-stamped requests with a
  Poisson-friendly API (``poisson_arrivals`` synthesises open-loop traffic,
  ``pop_due`` admits whatever has arrived by the scheduler's clock).
* ``form_bursts`` — the per-burst baseline's batch former: group by sampling
  mode, chunk, bucket. Each burst costs a ``set_mode`` (dispatch + possible
  compile + rebind) before its hot loop.
* ``ContinuousBatcher`` — slot-based continuous batching over the unified
  decode executable (``runtime.steps.make_slot_decode_fn``): a fixed bucket
  of S slots, per-slot active masks, per-slot positions, and per-slot packed
  sampling params *as data*. Requests join free slots and leave on
  completion without the hot loop ever recompiling, rebinding, or branching
  on mode — the cold path is touched exactly once per bucket size, at
  warmup.
* ``PagedContinuousBatcher`` — the same slot machinery against a paged KV
  pool (``runtime.kvcache``, DESIGN.md §9): block tables instead of dense
  per-slot caches, prefix sharing, preemption on pool exhaustion, and the
  capacity bucket as a semi-static dispatch key.

Both batchers drive a **multi-lane step pipeline** (DESIGN.md §10/§11):
every per-step activity is a named *lane* — ``prefill`` (chunked prompt
ingestion), ``decode`` (one token per slot), and the speculative pair
``draft``/``verify`` — and each lane is a semi-static dispatch key with its
own bucket axis (chunk buckets for prefill, capacity buckets for paged
decode, k-buckets for draft/verify), AOT-compiled and dummy-run at warmup.
The per-step token budget is split across lanes by a ``LanePolicy`` instead
of a hard-coded rule; which lanes run in a step is decided on the cold path
from slot state, never by a hot-loop conditional.

* Prefill lane: seated requests sit in a PREFILL state and the plan's chunk
  budget funds C-token chunks (C from the log-sized bucket set
  {8, 16, 32, ...}); the dense engine batches chunks for several prefilling
  requests into one ``("pfd", slots, chunk_bucket)`` call. Without the
  lane, prompts fall back to token-by-token teacher forcing at decode
  speed — the baseline ``benchmarks/prefill_bench.py`` measures against.
* Draft/verify lanes (speculative decoding, DESIGN.md §11): a truncated-
  layer draft view emits K candidates per slot through ``("dr", slots,
  k_bucket)``, the target scores all K+1 positions in one chunk-path pass
  through ``("vf"/"vfd", slots, k_bucket)``, and acceptance/rollback is
  pure *data* — a per-slot accepted-length that rewinds positions (dense)
  or ``BlockTable``s (paged). Greedy speculative streams are bit-for-bit
  the plain greedy streams.

The batcher is model-agnostic: it drives abstract lane callables and leaves
compilation to the engine's ``Dispatcher`` (core/dispatch.py).
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bucket_multiple, bucket_pow2
from repro.core.telemetry import MetricsRegistry, Telemetry
from repro.runtime.steps import pack_step_d2h, pack_verify_d2h, pull_host

GREEDY, SAMPLE = 0, 1

# Smallest chunked-prefill bucket: chunk sizes are drawn from the log-sized
# set {8, 16, 32, ..., prefill_chunk} (DESIGN.md §10).
CHUNK_BUCKET_MIN = 8

# The lane names of the step pipeline (DESIGN.md §11). Order documents the
# in-step execution order; membership is fixed — a lane that has no work
# this step simply isn't dispatched (a cold-path decision, not a hot-loop
# branch).
LANES = ("prefill", "draft", "verify", "decode")


@dataclass(frozen=True)
class StepPlan:
    """One step's lane allocation, produced by ``LanePolicy.plan``.

    ``chunk_budget`` — prompt tokens the prefill lane may ingest this step.
    ``k``            — draft depth (the k-bucket) for the draft/verify
                       lanes; 0 routes decoding slots through the plain
                       decode lane instead.
    """

    chunk_budget: int
    k: int


class LanePolicy:
    """Per-step token-budget split across lanes (DESIGN.md §11).

    Generalises the old one-chunk-plus-decode rule: each decoding slot
    consumes ``1 + k`` budget tokens (its verify window), and whatever
    remains funds the prefill lane's chunks. The draft depth ``k`` is drawn
    from the log-sized k-bucket set {1, 2, 4, ..., spec_k} and clamped by
    the longest useful window (``max_remaining - 1`` — drafting past a
    request's last token is pure waste), so k shrinks near stream tails and
    the crossing is a cold-path rebind, never a compile (the buckets are
    AOT-warmed) and never a hot-loop branch.

    ``decoupled`` (DESIGN.md §17): under disaggregated prefill/decode the
    two lanes run on disjoint mesh slices, so decode slots no longer eat
    into the prefill budget — the chunk budget is the full token budget.
    """

    def __init__(
        self, *, token_budget: int, prefill_chunk: int, spec_k: int = 0,
        decoupled: bool = False,
    ):
        self.token_budget = token_budget
        self.prefill_chunk = prefill_chunk
        self.spec_k = spec_k
        self.decoupled = decoupled

    def plan(self, *, n_decode: int, max_remaining: int = 0) -> StepPlan:
        """``n_decode`` decoding slots this step; ``max_remaining`` is the
        largest remaining emission count over draft-eligible slots (0 when
        speculation is off or nothing is eligible)."""
        k = 0
        if self.spec_k > 0 and n_decode > 0 and max_remaining > 1:
            k = bucket_pow2(
                min(self.spec_k, max_remaining - 1), 1, self.spec_k
            )
        budget = (
            self.token_budget
            if self.decoupled
            else self.token_budget - n_decode * (1 + k)
        )
        return StepPlan(chunk_budget=budget, k=k)


# ------------------------------------------------------------------ requests
@dataclass
class Request:
    """One decode request: ``new_tokens`` tokens from ``first_token`` on.

    ``prompt`` (optional) is a token prefix that is teacher-forced before
    generation starts — the paged engine dedupes common prompt prefixes
    through the ``kvcache.PrefixCache`` (DESIGN.md §9). Empty prompt means
    the classic single-seed-token request (``first_token``). ``priority``
    orders preemption under pool pressure: lower values are evicted first.
    """

    rid: int
    new_tokens: int
    greedy: bool = True
    temperature: float = 1.0
    first_token: int = 0
    arrival_s: float = 0.0
    prompt: tuple = ()
    priority: int = 0
    # Deadlines (DESIGN.md §15). ``ttl_s`` bounds *queue wait*: a request
    # still queued ttl_s after arrival is shed before admission
    # (runtime.admission). ``deadline_s`` is the absolute virtual-clock
    # completion deadline: a seated request past it is cancelled mid-stream,
    # its slot/pages released. None = no deadline (the default keeps
    # un-hardened streams byte-identical to the pre-§15 engine).
    ttl_s: float | None = None
    deadline_s: float | None = None
    # Filled by the runtime:
    tokens: list = field(default_factory=list)
    t_admit: float | None = None
    t_first: float | None = None  # first emitted token (TTFT anchor)
    t_last: float | None = None  # last emit (inter-token histogram anchor)
    t_done: float | None = None
    preemptions: int = 0
    shed_reason: str | None = None  # why admission dropped it (§15)
    cancelled: bool = False  # cancelled mid-stream (deadline or explicit)
    error: str | None = None  # failed after fault containment gave up
    faults: int = 0  # times this request's slot was quarantined

    def __post_init__(self) -> None:
        if self.prompt:
            self.first_token = int(self.prompt[0])

    @property
    def effective_prompt(self) -> tuple:
        return self.prompt if self.prompt else (self.first_token,)

    @property
    def total_tokens(self) -> int:
        """Logical KV length at completion: prompt + generated tokens."""
        return len(self.effective_prompt) + self.new_tokens

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.new_tokens

    @property
    def latency_s(self) -> float | None:
        """Arrival-to-last-token latency (the serving SLO metric)."""
        if self.t_done is None:
            return None
        return self.t_done - self.arrival_s


def poisson_arrivals(
    n: int,
    rate_hz: float,
    *,
    seed: int = 0,
    tokens_mean: float = 16.0,
    tokens_max: int | None = None,
    sample_frac: float = 0.5,
    temperature: float = 1.0,
    vocab: int | None = None,
) -> list[Request]:
    """Open-loop Poisson traffic: exponential inter-arrivals, geometric
    lengths, a Bernoulli greedy/sample mix. The 'realistic data' antidote to
    the too-predictable synthetic switch patterns the paper warns about."""
    if rate_hz <= 0:
        raise ValueError(f"rate_hz must be > 0, got {rate_hz}")
    rng = np.random.default_rng(seed)
    reqs = []
    t = 0.0
    for rid in range(n):
        t += float(rng.exponential(1.0 / rate_hz))
        # geometric already has support {1,2,...} with mean tokens_mean
        nt = int(rng.geometric(min(1.0, 1.0 / max(tokens_mean, 1.0))))
        if tokens_max is not None:
            nt = min(nt, tokens_max)
        reqs.append(
            Request(
                rid=rid,
                new_tokens=nt,
                greedy=bool(rng.random() >= sample_frac),
                temperature=temperature,
                first_token=int(rng.integers(vocab)) if vocab else 0,
                arrival_s=t,
            )
        )
    return reqs


def shared_prefix_arrivals(
    n: int,
    rate_hz: float,
    *,
    seed: int = 0,
    num_prefixes: int = 4,
    prefix_len: int = 32,
    suffix_len_mean: float = 4.0,
    tokens_mean: float = 8.0,
    tokens_max: int | None = None,
    total_max: int | None = None,
    heavy_frac: float = 0.2,
    heavy_mult: float = 6.0,
    sample_frac: float = 0.5,
    temperature: float = 1.0,
    vocab: int = 256,
    priorities: Sequence[int] = (0, 1),
) -> list[Request]:
    """Shared-prefix Poisson traffic with long-tail decode lengths.

    The paged-KV scenario family (DESIGN.md §9): every request's prompt is
    one of ``num_prefixes`` common prefixes (system prompts / few-shot
    headers) plus a short private suffix, and decode lengths mix a geometric
    body with a heavy tail (``heavy_frac`` of requests draw from a
    ``heavy_mult``× longer geometric). Dense caches must provision
    ``slots × max_len`` for this; paged caches share the prefix pages and
    only the tail pays for its length.
    """
    if rate_hz <= 0:
        raise ValueError(f"rate_hz must be > 0, got {rate_hz}")
    if prefix_len < 1:
        raise ValueError(f"prefix_len must be >= 1, got {prefix_len}")
    if total_max is not None and prefix_len > total_max - 2:
        raise ValueError(
            f"prefix_len={prefix_len} leaves no room for generation under "
            f"total_max={total_max}"
        )
    rng = np.random.default_rng(seed)
    prefixes = [
        tuple(int(t) for t in rng.integers(0, vocab, size=prefix_len))
        for _ in range(num_prefixes)
    ]
    reqs = []
    t = 0.0
    for rid in range(n):
        t += float(rng.exponential(1.0 / rate_hz))
        mean = tokens_mean * (
            heavy_mult if rng.random() < heavy_frac else 1.0
        )
        nt = int(rng.geometric(min(1.0, 1.0 / max(mean, 1.0))))
        if tokens_max is not None:
            nt = min(nt, tokens_max)
        ns = int(rng.geometric(min(1.0, 1.0 / max(suffix_len_mean, 1.0))))
        if total_max is not None:
            # keep prompt + generation inside a request's capacity cap
            nt = max(1, min(nt, total_max - prefix_len - 1))
            ns = max(0, min(ns, total_max - prefix_len - nt))
        suffix = tuple(int(x) for x in rng.integers(0, vocab, size=ns))
        reqs.append(
            Request(
                rid=rid,
                new_tokens=nt,
                greedy=bool(rng.random() >= sample_frac),
                temperature=temperature,
                arrival_s=t,
                prompt=prefixes[int(rng.integers(num_prefixes))] + suffix,
                priority=int(priorities[int(rng.integers(len(priorities)))]),
            )
        )
    return reqs


def attach_distinct_prompts(
    requests: Sequence[Request],
    prompt_len: int,
    *,
    vocab: int,
    seed: int = 0,
) -> list[Request]:
    """Give every request its own random ``prompt_len``-token prompt.

    The chunked-prefill scenario family (DESIGN.md §10): distinct prompts
    defeat the prefix cache, so every prompt token must actually be
    ingested — TTFT gains are earned by the chunk lane, not by sharing.
    One source of truth for the launcher and the prefill benchmark.
    """
    rng = np.random.default_rng(seed)
    for r in requests:
        r.prompt = tuple(
            int(x) for x in rng.integers(0, vocab, size=prompt_len)
        )
        r.first_token = int(r.prompt[0])
    return list(requests)


class RequestQueue:
    """Thread-safe arrival queue ordered by (arrival_s, rid)."""

    def __init__(self, requests: Iterable[Request] = ()):  # noqa: B008
        self._heap: list[tuple[float, int, Request]] = []
        self._tie = itertools.count()
        self._lock = threading.Lock()
        self.extend(requests)

    def submit(self, req: Request) -> None:
        with self._lock:
            heapq.heappush(self._heap, (req.arrival_s, next(self._tie), req))

    def extend(self, requests: Iterable[Request]) -> None:
        for r in requests:
            self.submit(r)

    def __len__(self) -> int:
        with self._lock:
            return len(self._heap)

    def __bool__(self) -> bool:
        return len(self) > 0

    def next_arrival(self) -> float | None:
        """Arrival time of the earliest queued request (None if empty)."""
        with self._lock:
            return self._heap[0][0] if self._heap else None

    def pop_due(self, now: float, limit: int | None = None) -> list[Request]:
        """Admit: pop every request with ``arrival_s <= now`` (up to limit)."""
        out: list[Request] = []
        with self._lock:
            while self._heap and self._heap[0][0] <= now:
                if limit is not None and len(out) >= limit:
                    break
                out.append(heapq.heappop(self._heap)[2])
        return out


# ------------------------------------------------------------ burst batching
def form_bursts(
    requests: Sequence[Request], *, quantum: int, max_batch: int
) -> list[tuple[int, bool, list[Request]]]:
    """Per-burst baseline batch forming: (bucket, greedy, requests) groups.

    Requests are split by sampling mode (a burst has one mode — the mode is
    baked into the per-burst executable), chunked to ``max_batch``, and the
    chunk size is rounded up to a compile bucket. Every returned burst costs
    one ``Engine.set_mode`` before its hot loop.
    """
    bursts = []
    for greedy in (True, False):
        group = [r for r in requests if r.greedy == greedy]
        for i in range(0, len(group), max_batch):
            chunk = group[i : i + max_batch]
            if chunk:
                bucket = bucket_multiple(len(chunk), quantum, max_batch)
                bursts.append((bucket, greedy, chunk))
    return bursts


# ---------------------------------------------------------------- the clock
class Clock:
    """Wall clock with virtual fast-forward.

    Serving latencies are measured against this clock: it advances with real
    time while work is in flight, and jumps over idle gaps (no due arrivals,
    no active slots) so a low arrival rate doesn't stall a benchmark run.
    """

    def __init__(self) -> None:
        self._t0 = time.perf_counter()
        self._offset = 0.0

    def now(self) -> float:
        return time.perf_counter() - self._t0 + self._offset

    def jump_to(self, t: float) -> None:
        """Fast-forward to virtual time ``t`` (no-op if already past it)."""
        gap = t - self.now()
        if gap > 0:
            self._offset += gap


# ------------------------------------------------------- continuous batching
@dataclass
class BatcherStats:
    steps: int = 0
    admitted: int = 0
    finished: int = 0
    tokens: int = 0
    active_slot_steps: int = 0
    idle_slot_steps: int = 0
    prompt_tokens: int = 0  # teacher-forced (not emitted) tokens
    prefill_chunks: int = 0  # chunks ingested (rows; batched calls carry >1)
    prefill_calls: int = 0  # prefill-lane executable calls
    # Rows of those calls (DESIGN.md §10): the chunks they carried, and
    # the rows they were sized at (a row bucket, or every slot); the ratio
    # is the prefill call's row fill.
    prefill_rows: int = 0
    prefill_rows_padded: int = 0
    chunk_bucket_crossings: int = 0
    h2d_uploads: int = 0  # host->device coordinate uploads (see _DeviceMirror)
    h2d_overlapped: int = 0  # uploads issued while a step was in flight
    # Step-pipeline telemetry (DESIGN.md §13): host-side planning/bookkeeping
    # time vs time spent blocked on device pulls, the peak number of issued-
    # but-uncommitted steps, and how many d2h transfers actually happened
    # (the packed-pull satellite shrinks this per step; async defers it).
    host_plan_ms: float = 0.0
    device_wait_ms: float = 0.0
    inflight_depth: int = 0
    d2h_transfers: int = 0
    # Per-lane step counts (DESIGN.md §11): executable calls per lane.
    decode_steps: int = 0
    draft_steps: int = 0
    verify_steps: int = 0
    # Speculative decoding accounting: candidates offered vs accepted, and
    # tokens emitted through the verify lane (incl. the correction token).
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    spec_tokens: int = 0
    k_bucket_crossings: int = 0
    # Robustness accounting (DESIGN.md §15): mid-stream cancellations (by
    # deadline or explicit cancel), step-time stragglers flagged by an
    # attached watchdog, and fault-containment outcomes (quarantined slots
    # whose requests were re-admitted vs failed).
    cancelled: int = 0
    deadline_missed: int = 0
    stragglers: int = 0
    faults_detected: int = 0
    faults_contained: int = 0
    faults_failed: int = 0
    # The metrics registry (core.telemetry, DESIGN.md §14) this batcher's
    # per-lane counters and latency histograms live in. ``lane_calls`` is
    # *derived* from it — the registry's lane-label namespace ("cb"/"cbp"/
    # "pf"/"pfd"/"dr"/"drp"/"vf"/"vfd"/"burst") is the dispatch-key
    # namespace, so per-lane telemetry, the Prometheus snapshot, the trace,
    # and the dispatch keys can never drift apart.
    registry: MetricsRegistry = field(
        default_factory=MetricsRegistry, repr=False, compare=False
    )

    def note_lane(self, spec_name: str) -> None:
        self.registry.inc("lane_calls_total", lane=spec_name)

    @property
    def lane_calls(self) -> dict:
        """Executable calls grouped by lane spec name (DESIGN.md §12),
        read straight out of the registry."""
        return self.registry.labeled_values("lane_calls_total", "lane")

    @property
    def occupancy(self) -> float:
        total = self.active_slot_steps + self.idle_slot_steps
        return self.active_slot_steps / total if total else 0.0

    @property
    def target_steps(self) -> int:
        """Target-model decode-lane calls: the denominator of the
        accepted-tokens-per-step speculation metric."""
        return self.decode_steps + self.verify_steps

    @property
    def lane_steps(self) -> dict:
        """Executable calls per lane — one unit across all four lanes
        (``prefill_chunks`` separately counts ingested chunk *rows*, which
        batched dense prefill packs several of into one call)."""
        return {
            "prefill": self.prefill_calls,
            "draft": self.draft_steps,
            "verify": self.verify_steps,
            "decode": self.decode_steps,
        }


class _DeviceMirror:
    """Host->device upload dedup for the hot loop's coordinate arrays.

    The per-slot arrays (tok/pos/active/temps/greedy/keys/block tables)
    change rarely — admits, finishes, prefill flips — relative to how often
    the step executes. Re-uploading all of them with ``jnp.asarray`` every
    step is the data-movement analogue of re-evaluating a branch the paper
    moved off the hot path. The mirror keeps one device-resident copy per
    name: ``get`` uploads only when the host copy was ``touch``ed since the
    last step, and ``put`` adopts device arrays the step itself returned
    (positions, keys, next tokens) so steady-state decode re-uploads
    nothing. ``stats.h2d_uploads`` counts actual uploads.
    """

    def __init__(self, stats: BatcherStats):
        self._dev: dict[str, Any] = {}
        self._stats = stats

    def touch(self, *names: str) -> None:
        """Host mutated these arrays: the next ``get`` re-uploads."""
        for n in names:
            self._dev.pop(n, None)

    def get(self, name: str, host: Any) -> Any:
        if name not in self._dev:
            self._dev[name] = jnp.asarray(host)
            self._stats.h2d_uploads += 1
        return self._dev[name]

    def put(self, name: str, dev: Any) -> None:
        """Adopt a device array the step returned (no upload needed)."""
        self._dev[name] = dev

    def preload(self, name: str, host: Any) -> None:
        """Double-buffered upload (DESIGN.md §13): stage a touched array
        while the device is busy with an in-flight step, so the copy
        overlaps device execution and the next ``get`` is a hit instead
        of an issue-time stall. A later ``touch`` still invalidates the
        staged copy, so correctness never depends on the overlap — on an
        inline CPU backend this degrades to an early (but still counted)
        upload and nothing else changes."""
        if name not in self._dev:
            self._dev[name] = jnp.asarray(host)
            self._stats.h2d_uploads += 1
            self._stats.h2d_overlapped += 1

    def invalidate(self) -> None:
        """Drop every device-resident copy. A mesh rebind moved the
        serving state's placement (DESIGN.md §16): arrays committed to
        the old mesh's devices would be rejected by the new plan's
        executables, so the next ``get`` of each name re-uploads from
        the (authoritative, just-committed) host copies."""
        self._dev.clear()


@dataclass
class _InflightStep:
    """One issued-but-uncommitted device step (DESIGN.md §13).

    ``packed`` is the step's single host-bound device array — for a decode
    step the executable's own bundle output (``steps._step_bundle``,
    ``[next_tok | new_pos | keys]``), for a non-flip prefill chunk the
    packed sample/keys array (``steps.pack_step_d2h`` — only the keys are
    ever read back; the chunk's bookkeeping already ran at issue), for a
    spec step the host-packed verify rows (``steps.pack_verify_d2h``) —
    the *only* d2h sync the step ever costs, deferred to its token-emit
    boundary. A spec step keeps the draft candidates and verify-window
    lengths so accept/rollback can be *replayed* one step late against the
    pulled verify rows.
    """

    kind: str  # "decode" | "prefill" | "spec"
    packed: Any  # device [S, W] int32, pulled once at commit
    chainable: bool = False  # a second decode may issue on top of this one
    drafts: np.ndarray | None = None  # spec: host [S, K] candidates
    lengths: np.ndarray | None = None  # spec: per-slot verify-window lengths
    k: int = 0  # spec: the step's k-bucket


class _MultiLaneMixin:
    """The multi-lane step core shared by both batchers (DESIGN.md §10/§11):
    the per-step ``LanePolicy`` plan, FIFO chunk allocation, chunk/k bucket
    accounting, flip-time first-token priming, the draft lane, and the
    accept/rollback arithmetic of the verify lane. The engines differ only
    in storage bookkeeping (dense rows vs pages) and executable signatures.

    The ``_*_lane`` class attributes name each engine's lane *specs* in the
    ``core.lanes`` registry (DESIGN.md §12) so ``stats.lane_calls`` groups
    executable calls under the same names the dispatch keys carry.
    """

    _decode_lane = "cb"
    _prefill_lane = "pfd"
    _verify_lane = "vfd"
    # Active device-mesh coordinate (DESIGN.md §16); constructors override
    # with the engine's launch mesh and ``set_mesh`` moves it mid-stream.
    mesh = "1x1"
    _mesh_ctl = None  # engine-wired topology-flip closure (serve.py)
    # Disaggregated prefill/decode (DESIGN.md §17): True while the prefill
    # lane runs on its pinned mesh slice. The dense engine never sets it.
    disagg = False

    def _init_telemetry(self, telemetry: Telemetry | None) -> None:
        """Telemetry wiring shared by both constructors (DESIGN.md §14).

        Runs before ``stats`` is built so the batcher's counters land in
        the engine's registry. ``_trace`` is None unless the flight
        recorder is enabled — every hot-path emit site guards on that one
        compare, which is the whole disabled-path overhead. ``_span`` opens
        the step's ``serve.<layer>.<phase>`` spans (DESIGN.md §14). The
        request-phase histograms are cached as plain attributes (one bisect
        per observation, no registry lookup per token)."""
        self.telemetry = telemetry or Telemetry()
        self._trace = self.telemetry.trace_or_none()
        self._span = self.telemetry.span  # phase spans (core.telemetry)
        reg = self.telemetry.registry
        self._h_qwait = reg.histogram("queue_wait_ms")
        self._h_ttft = reg.histogram("ttft_ms")
        self._h_itl = reg.histogram("inter_token_ms")
        self._h_e2e = reg.histogram("request_latency_ms")
        self._lane_hist: dict[str, Any] = {}

    def _issue(self, lane: str, t0_ns: int, exe: Callable, *args) -> Any:
        """Call a lane's executable under its ``serve.dispatch.issue`` span,
        then observe the lane's call latency from ``t0_ns``."""
        with self._span("serve.dispatch.issue", lane=lane):
            out = exe(*args)
        self._lane_tick(lane, t0_ns)
        return out

    def _lane_tick(self, lane: str, t0_ns: int) -> None:
        """Per-lane executable-call latency into the always-on
        ``lane_step_ms{lane=...}`` histogram (the per-lane Prometheus
        surface), anchored at ``t0_ns``: taken just before the prefill
        call, and before the decode call's mirror uploads."""
        dt_ns = time.perf_counter_ns() - t0_ns
        h = self._lane_hist.get(lane)
        if h is None:
            # sharded serving labels the per-lane surface with the active
            # mesh (DESIGN.md §16) so a rebind's latency shift is visible;
            # the classic single-device topology keeps the historical
            # label set (handles refresh on ``set_mesh``)
            labels = {"lane": lane}
            if self.mesh != "1x1":
                labels["mesh"] = self.mesh
            h = self._lane_hist[lane] = self.telemetry.registry.histogram(
                "lane_step_ms", **labels
            )
        h.observe(dt_ns / 1e6)

    def _note_admit(self, req: Request, now: float) -> None:
        """Queue-wait histogram + admission lifecycle event."""
        self._h_qwait.observe(max(now - req.arrival_s, 0.0) * 1e3)
        tr = self._trace
        if tr is not None:
            tr.emit("admit", "scheduler", args={"rid": req.rid})

    def _note_tokens(self, req: Request, now: float) -> None:
        """Request-phase emit accounting: TTFT on the first emitted token,
        inter-token gap after it. Virtual-clock milliseconds — the same
        basis as ``latency_report``'s percentiles."""
        if req.t_first is None:
            req.t_first = now
            self._h_ttft.observe(max(now - req.arrival_s, 0.0) * 1e3)
        elif req.t_last is not None and now > req.t_last:
            self._h_itl.observe((now - req.t_last) * 1e3)
        req.t_last = now

    def _note_finish(self, req: Request, now: float) -> None:
        """End-to-end latency histogram + finish lifecycle event."""
        self._h_e2e.observe(max(now - req.arrival_s, 0.0) * 1e3)
        tr = self._trace
        if tr is not None:
            tr.emit("finish", "scheduler",
                    args={"rid": req.rid, "tokens": len(req.tokens)})

    def _init_lanes(
        self,
        *,
        draft_dispatch: Callable[[int], Callable] | None,
        verify_dispatch: Callable[[int], Callable] | None,
        draft_prefill_dispatch: Callable[[int], Callable] | None,
        draft_cache: Any,
        spec_k: int,
        async_steps: bool = False,
        async_depth: int = 2,
    ) -> None:
        """Lane wiring shared by both constructors. Speculation is active
        only when the engine supplied both spec lanes. ``async_depth`` is
        the pipeline's issued-step capacity (DESIGN.md §13): at most
        ``async_depth - 1`` steps stay parked after a ``step()`` returns,
        so 2 (the default) reproduces the classic one-deep overlap and 1
        degrades async mode to the synchronous commit."""
        self.async_steps = async_steps
        self.async_depth = max(1, int(async_depth))
        self._inflight: deque[_InflightStep] = deque()  # issued, uncommitted
        self._backlog: list[Request] = []  # finished off the step path
        self._draft_dispatch = draft_dispatch
        self._verify_dispatch = verify_dispatch
        self._draft_prefill_dispatch = draft_prefill_dispatch
        self._draft_cache = draft_cache
        self.spec_k = (
            spec_k if (draft_dispatch and verify_dispatch) else 0
        )
        self._lane_policy = LanePolicy(
            token_budget=self.token_budget,
            prefill_chunk=self.prefill_chunk,
            spec_k=self.spec_k,
            decoupled=self.disagg,
        )
        self._k_bucket: int | None = None  # unset until the first spec step
        self._chunk_slots: set[int] = set()
        self._flip_slots: set[int] = set()
        # Overload/fault hardening (DESIGN.md §15). All inert by default:
        # un-attached, each costs one None-check (or, for deadlines, one
        # bool check) per step — clean streams stay bitwise identical.
        self._watchdog = None  # ft.failover.StepTimeWatchdog
        self._on_straggler: Callable[[float], None] | None = None
        self._faults = None  # core.faults.FaultPlan
        self._stall_pending = False  # an injected d2h stall awaits detection
        self._has_deadlines = False  # any seated request carries deadline_s
        self._fault_retry_limit = 1  # re-admissions before a request fails
        self.cancelled_requests: list[Request] = []
        self.failed_requests: list[Request] = []
        self.requeued: list[Request] = []  # quarantined, to be re-admitted
        # Launch-time knob ceilings: ``set_knobs`` (the degradation ladder's
        # actuation surface) may move spec_k/prefill_chunk/token_budget only
        # within the ranges whose dispatch keys warmup actually compiled.
        self._spec_max = self.spec_k
        self._chunk_max = self.prefill_chunk
        self._budget_max = self.token_budget
        # per-slot, per-verify a/k acceptance samples; bounded so a long
        # serving loop doesn't grow host memory (recent window is what the
        # report's percentiles should reflect anyway)
        self.accept_samples: deque[float] = deque(maxlen=4096)

    @property
    def _spec_on(self) -> bool:
        return self.spec_k > 0

    @property
    def _pending(self) -> _InflightStep | None:
        """Newest issued-but-uncommitted step (None when the pipeline is
        drained) — the one-deep pipeline's single record, kept as a
        read-only view now that ``_inflight`` holds a configurable-depth
        queue."""
        return self._inflight[-1] if self._inflight else None

    # ------------------------------------------------- step pipeline (§13)
    def _pull(self, dev) -> np.ndarray:
        """The emit-boundary d2h sync: every host read of a device array
        goes through ``steps.pull_host`` so ``device_wait_ms`` measures
        exactly how long the host sat blocked on the device,
        ``d2h_transfers`` counts every transfer the step loop actually
        paid for, and each pull is a ``serve.dispatch.pull`` span."""
        if self._faults is not None:
            f = self._faults.fire("d2h_stall")
            if f is not None:
                time.sleep(f.stall_s)  # simulated interconnect stall
                self._stall_pending = True  # the watchdog should flag it
        out, dt_ns = pull_host(dev, self._trace)
        self.stats.device_wait_ms += dt_ns / 1e6
        self.stats.d2h_transfers += 1
        return out

    def step(self, now: float = 0.0) -> list[Request]:
        """One scheduler step; returns requests that finished.

        The software-pipelined wrapper around the engines' ``_step_impl``
        (DESIGN.md §13). Synchronous mode is a pass-through. Async mode
        keeps up to ``async_depth - 1`` issued-but-uncommitted device
        steps: when every parked step is one whose outcome cannot change
        what the host would plan next (``chainable`` decodes, non-flip
        prefill chunks), the *next* step is issued first and the oldest
        parked steps' tokens are emitted while the device runs it — host
        bookkeeping for step N overlaps device execution of step N+1. Any
        step the host must read before planning (spec accept/rollback,
        prefill flips, finishes, teacher forcing) commits first, so the
        device-visible call sequence — and therefore every token stream —
        is identical to the synchronous loop.
        """
        with self._span("serve.batcher.step", step=self.stats.steps):
            t0 = time.perf_counter()
            dw0 = self.stats.device_wait_ms
            if self._has_deadlines:
                self._cancel_overdue(now)
            finished = self._backlog
            self._backlog = []
            ran_ahead = False
            if self.async_steps and self._inflight:
                if self._can_run_ahead():
                    finished.extend(self._run_ahead(now))
                    ran_ahead = True
                else:
                    finished.extend(self._commit_pending(now))
            if not ran_ahead:
                finished.extend(self._step_impl(now))
            # depth limit: commit oldest-first until the queue fits the
            # configured pipeline capacity (0 in synchronous mode — the
            # lanes never park there, so this loop is a no-op)
            limit = self.async_depth - 1 if self.async_steps else 0
            while len(self._inflight) > limit:
                finished.extend(self._commit_oldest(now))
            self.stats.host_plan_ms += (
                (time.perf_counter() - t0) * 1e3
                - (self.stats.device_wait_ms - dw0)
            )
            if self._watchdog is not None:
                self._watchdog_tick(time.perf_counter() - t0)
        return finished

    def flush(self, now: float = 0.0) -> list[Request]:
        """Drain the pipeline: commit every parked step (if any) and return
        every finished request not yet handed out. Call after the last
        ``step`` of a stream; a no-op in synchronous mode."""
        finished = self._backlog
        self._backlog = []
        if self._inflight:
            finished.extend(self._commit_pending(now))
        return finished

    def _can_run_ahead(self) -> bool:
        """Issue-before-commit is legal only when the newest parked step
        cannot change the next step's plan: a chainable decode or a parked
        non-flip prefill chunk. Prefilling slots are compatible with
        run-ahead only when this step's own chunk plan cannot flip one of
        them (a flip edits the decoding mask the parked steps were issued
        under) and no spec step is planned; disaggregated prefill always
        commits first (its chunks are eager — they bridge two pools)."""
        if not self._inflight:
            return False
        rec = self._inflight[-1]
        if rec.kind == "spec" or not rec.chainable:
            return False
        if not (self._prefilling & self._active).any():
            return True
        if self.prefill_chunk <= 0 or self.disagg or self._spec_on:
            return False
        plan = self._plan_step()
        if plan.k > 0:
            return False
        return not self._flip_planned(plan.chunk_budget)

    def _flip_planned(self, budget: int) -> bool:
        """Would this step's chunk plan flip some slot PREFILL->DECODE?
        Pure planning (``_plan_chunks`` has no side effects); exact because
        the planner already shrinks a final chunk that must defer its
        flip-token past a dry budget."""
        for s, cursor, chunk in self._plan_chunks(budget):
            if cursor + chunk >= len(self._slots[s].effective_prompt):
                return True
        return False

    def _run_ahead(self, now: float) -> list[Request]:
        """The overlap step: issue step N+1 against the mirror's chained
        device arrays (step N's outputs are already its inputs — no host
        round-trip), *then* pull and emit the oldest parked steps' tokens
        while the device works on N+1 (the depth-limit drain in ``step``).
        Prefill chunks ride the same pipeline (DESIGN.md §13/§17): a
        planned non-flip chunk issues and parks just like a decode."""
        tr = self._trace
        if tr is not None:
            tr.emit("async_issue", "scheduler")
        self._pre_issue_fast()
        finished: list[Request] = []
        self._chunk_slots = set()
        self._flip_slots = set()
        prefilling = (
            self.prefill_chunk > 0
            and bool((self._prefilling & self._active).any())
        )
        if prefilling:
            # upkeep preemptions may have re-shaped the plan into a flip
            # (or freed the whole decode set): re-validate, else fall back
            # to the synchronous path on a drained pipeline
            plan = self._plan_step()
            if plan.k > 0 or self._flip_planned(plan.chunk_budget):
                finished.extend(self._commit_pending(now))
                finished.extend(self._step_impl(now))
                return finished
        decoding = self._active & ~self._prefilling
        if not decoding.any() and not prefilling:
            # _pre_issue_fast may have preempted every slot
            finished.extend(self._commit_pending(now))
            return finished
        # the parked steps are still in flight: stage any upkeep-touched
        # coordinate arrays now so their uploads ride their execution
        with self._span("serve.dispatch.upload", lane="preload"):
            self._preload_step_inputs()
        if prefilling:
            finished.extend(self._prefill_step(now, plan.chunk_budget))
            decoding = self._active & ~self._prefilling
        if decoding.any():
            finished.extend(self._decode_lane_step(now, decoding))
        else:
            self.stats.steps += 1  # prefill-only step
            self._count_slot_steps(decoding)
        return finished

    def _pre_issue_fast(self) -> None:
        """Cold-path upkeep that must precede an issued decode even on the
        run-ahead path (paged storage overrides with page upkeep)."""

    def _preload_step_inputs(self) -> None:
        """Double-buffered coordinate uploads (DESIGN.md §13): re-stage any
        per-slot array whose device copy was invalidated, *off* the
        executable-issue path — at admission time and under run-ahead while
        the parked step still occupies the device — so the next issue pays
        no upload stall. Steady-state decode stages nothing (every input is
        chained via ``put``); a later host mutation still ``touch``es the
        staged copy away, so this is a pure prefetch (the paged engine adds
        its packed block table)."""
        m = self._mirror
        m.preload("tok", self._tok)
        m.preload("pos", self._pos)
        m.preload("active", self._active & ~self._prefilling)
        m.preload("temps", self._temps)
        m.preload("greedy", self._greedy)
        m.preload("keys", self._keys)

    def _decode_chainable(self, decoding) -> bool:
        """True when the *next* step's plan is independent of this decode's
        outputs for every decoding slot: past teacher forcing (the next
        input token is the step's own output, already chained on device),
        not finishing (the emit loop would free the slot), and not about
        to enter the draft/verify lanes (their plan reads host state)."""
        for s, req in enumerate(self._slots):
            if req is None or not decoding[s]:
                continue
            if self._cursor[s] + 1 < len(req.effective_prompt):
                return False
            rem_after = req.new_tokens - len(req.tokens) - 1
            if rem_after < 1:
                return False
            if self._spec_on and req.greedy and rem_after > 1:
                return False
        return True

    def _queue_decode(self, packed, decoding) -> None:
        """Park a just-issued decode instead of syncing on it. ``packed``
        is the executable's own bundle output (``steps._step_bundle``) —
        queuing costs no dispatch at all. Positions advance *predictively*
        — the device computes ``pos + active`` and the host mirrors that
        arithmetic, so ``self._pos`` stays current for the next step's
        planning without a d2h pull (commit re-reads the device's own
        ``new_pos`` from the packed array)."""
        new_pos = np.array(self._pos, np.int32)
        new_pos[decoding] += 1
        self._pos = new_pos
        rec = _InflightStep(
            kind="decode",
            packed=packed,
            chainable=self._decode_chainable(decoding),
        )
        self._park(rec)

    def _queue_prefill(self, packed) -> None:
        """Park a just-issued non-flip prefill chunk (DESIGN.md §13): all
        of its bookkeeping (cursors, positions, stats) already ran at
        issue — commit only reads back the split keys. Always chainable:
        a chunk that cannot flip leaves the decoding mask, the teacher-
        forcing cursors and every emitted stream untouched."""
        self._park(_InflightStep(kind="prefill", packed=packed,
                                 chainable=True))

    def _park(self, rec: _InflightStep) -> None:
        self._inflight.append(rec)
        self.stats.inflight_depth = max(
            self.stats.inflight_depth, len(self._inflight)
        )
        tr = self._trace
        if tr is not None:
            tr.emit("async_park", "scheduler",
                    args={"kind": rec.kind, "chainable": rec.chainable})

    def _commit_oldest(self, now: float) -> list[Request]:
        return self._commit_rec(self._inflight.popleft(), now)

    def _commit_pending(self, now: float) -> list[Request]:
        """Drain every parked step, oldest first (FIFO = issue order, so
        host state converges to the device's)."""
        out: list[Request] = []
        while self._inflight:
            out.extend(self._commit_oldest(now))
        return out

    def _commit_rec(self, rec: _InflightStep, now: float) -> list[Request]:
        """The emit boundary: one packed pull, then exactly the bookkeeping
        the synchronous loop runs after its step call."""
        tr = self._trace
        if tr is not None:
            tr.emit("async_commit", "scheduler", args={"kind": rec.kind})
        if rec.kind == "spec":
            return self._commit_spec(rec, now)
        if rec.kind == "prefill":
            # [S,3]: sample | keys-as-int32. The sample is only meaningful
            # at a flip (never parked); idle rows' keys pass through the
            # chunk executable unsplit (length-0 mask, see steps.py), so
            # wholesale adoption is exact for every slot.
            p = self._pull(rec.packed)
            self._keys = p[:, 1:3].astype(np.uint32)
            return []
        p = self._pull(rec.packed)  # [S,4]: nxt | new_pos | keys-as-int32
        self._keys = p[:, 2:4].astype(np.uint32)  # bit-exact (see steps.py)
        return self._emit_decode(p[:, 0], p[:, 1], now)

    def _commit_spec(self, rec: _InflightStep, now: float) -> list[Request]:
        """Replay accept/rollback one step late: the pulled verify rows and
        the parked draft candidates reproduce the exact accept-length
        arithmetic the synchronous loop ran immediately, so the committed
        stream — including every rollback — is bitwise identical."""
        p = self._pull(rec.packed)
        k = rec.k
        rows = p[:, : k + 1]
        nxt0 = p[:, k + 1]
        self._keys = p[:, k + 2 : k + 4].astype(np.uint32)
        return self._apply_verify(now, rows, nxt0, rec.drafts, rec.lengths)

    # ------------------------------------------------------------- planning
    def _plan_step(self) -> StepPlan:
        """Ask the lane policy for this step's budget split. Draft
        eligibility (greedy, past teacher forcing, >= 2 tokens still to
        emit) is computed here on the cold path; per-slot verify windows
        are clamped later as data."""
        with self._span("serve.batcher.plan") as sp:
            decoding = self._active & ~self._prefilling
            max_rem = 0
            if self._spec_on:
                for s, req in enumerate(self._slots):
                    if req is None or not decoding[s] or not req.greedy:
                        continue
                    if self._cursor[s] + 1 < len(req.effective_prompt):
                        continue  # still teacher-forcing prompt tokens
                    max_rem = max(max_rem, req.new_tokens - len(req.tokens))
            plan = self._lane_policy.plan(
                n_decode=int(decoding.sum()), max_remaining=max_rem
            )
            sp.note(budget=plan.chunk_budget, k=plan.k)
        return plan

    def _plan_chunks(
        self, budget_left: int, *, limit: int | None = None
    ) -> list[tuple[int, int, int]]:
        """FIFO chunk allocation for the prefill lane: earliest-admitted
        prefilling slots first, each chunk clamped to [1, prefill_chunk] —
        the head slot always progresses even on a dry budget; later slots
        (dense batched prefill) only while budget remains. A slot whose
        chunk reaches its prompt end also decodes its first token this
        step, so the final chunk shrinks to keep that token inside the
        budget. Pure planning, no side effects: a chunk aborted by
        preemption records nothing. Returns [(slot, cursor, chunk), ...].
        """
        with self._span("serve.batcher.plan", budget=budget_left) as sp:
            order = sorted(
                (
                    s for s in range(self.num_slots)
                    if self._prefilling[s] and self._active[s]
                ),
                key=lambda s: (self._slots[s].t_admit or 0.0, s),
            )
            out: list[tuple[int, int, int]] = []
            for s in order:
                if out and (
                    budget_left < 1 or (limit and len(out) >= limit)
                ):
                    break
                req = self._slots[s]
                prompt = req.effective_prompt
                cursor = int(self._cursor[s])
                remaining = len(prompt) - cursor
                chunk = max(
                    1, min(remaining, budget_left, self.prefill_chunk)
                )
                if (
                    chunk == remaining
                    and chunk + 1 > budget_left
                    and remaining > 1
                ):
                    # a flipping slot also decodes its first token this
                    # step; shrink the final chunk so that token stays in
                    # budget
                    chunk -= 1
                out.append((s, cursor, chunk))
                budget_left -= chunk + (1 if chunk == remaining else 0)
            sp.note(slots=len(out))
        return out

    def _note_chunk_bucket(self, bucket: int) -> None:
        """Crossing accounting, called only for chunks that actually run."""
        if bucket != self._chunk_bucket:
            self.stats.chunk_bucket_crossings += 1
            self._chunk_bucket = bucket

    def _note_k_bucket(self, k: int) -> None:
        """k-axis crossing accounting (DESIGN.md §11): a different draft
        depth re-dispatches the draft/verify executables — a cold-path
        rebind over AOT-warmed buckets, never a compile. The first spec
        step *binds* rather than crosses (counting it would let a run
        whose k never moves satisfy the crossings gate vacuously)."""
        if self._k_bucket is not None and k != self._k_bucket:
            self.stats.k_bucket_crossings += 1
        self._k_bucket = k

    # ----------------------------------------------------------- spec lanes
    def _verify_len(self, s: int, k: int) -> int:
        """Slot ``s``'s verify-window length (0 = not in the lane). Window
        arithmetic keeps every write inside the capacity admission
        reserved: 1 + min(k, remaining - 1) for draft-eligible slots;
        sampling slots, teacher-forcing slots, and slots that flipped this
        step (their first token is already budgeted) ride with length 1 —
        a verify of length 1 *is* a decode step."""
        req = self._slots[s]
        if req is None or not self._active[s] or self._prefilling[s]:
            return 0
        if (
            not req.greedy
            or s in self._flip_slots
            or self._cursor[s] + 1 < len(req.effective_prompt)
        ):
            return 1
        return 1 + min(k, max(req.new_tokens - len(req.tokens) - 1, 0))

    def _run_draft(self, k: int, decoding) -> Any:
        """Draft lane: K greedy candidates per slot in one executable call.
        The draft stack writes its own KV for the fed token at ``pos`` —
        which is exactly how its cache tracks the committed stream, even
        for slots the verify lane later rejects everything for (rejected
        tails are overwritten once ``pos`` is rewound). Returns the host
        [S, K] candidate array.

        Every input rides the ``_DeviceMirror``: tok/pos re-upload only
        when the host actually moved them (they do, each spec step — the
        mirror counts those honestly), the all-ones greedy vector uploads
        exactly once (forced greedy keeps candidate streams deterministic),
        and the split keys the draft returns are discarded so sampling
        streams are untouched."""
        step = self._draft_dispatch(k)  # cold: slot-hit unless k moved
        t0_ns = time.perf_counter_ns()
        drafts, self._draft_cache, _, _ = self._issue(
            "dr",
            t0_ns,
            step,
            self._draft_cache,
            self._mirror.get("tok", self._tok),
            self._mirror.get("pos", self._pos),
            self._mirror.get("active", decoding),
            self._mirror.get("temps", self._temps),
            self._mirror.get("spec_greedy", np.ones(self.num_slots, bool)),
            self._mirror.get("keys", self._keys),
        )
        self.stats.draft_steps += 1
        self.stats.note_lane("dr")
        # an inherent sync point: the host packs the verify windows from
        # the candidates, so the draft pull cannot be deferred
        return self._pull(drafts)

    @staticmethod
    def _accepted_prefix(drafts_row, rows_row, k_s: int) -> int:
        """Greedy acceptance: longest prefix where the draft's candidate
        equals the target's own greedy continuation. Host-side data — the
        executables never branch on it."""
        a = 0
        while a < k_s and int(drafts_row[a]) == int(rows_row[a]):
            a += 1
        return a

    def _pack_verify_tok(self, drafts, lengths: np.ndarray, k: int):
        """[S, K+1] verify window: the committed token then the accepted
        candidates; columns >= length are bucket padding."""
        tok = np.zeros((self.num_slots, k + 1), np.int32)
        tok[:, 0] = self._tok[:, 0]
        for s in range(self.num_slots):
            if lengths[s] > 1:
                tok[s, 1 : lengths[s]] = drafts[s, : lengths[s] - 1]
        return tok

    def _spec_step(self, now: float, k: int, decoding) -> list[Request]:
        """Speculative decode for the decoding slots (DESIGN.md §11): the
        draft lane proposes K candidates per slot, the verify lane scores
        all K+1 positions in one target pass through the chunked path, and
        acceptance rewinds per-slot positions (and, paged, block tables) as
        data. Greedy slots emit ``accepted + 1`` tokens; sampling and
        draft-ineligible slots ride the same executables with a length-1
        window whose row 0 *is* a decode step (same logits, same per-step
        key split). Storage-specific pieces — the verify executable's
        signature and the table bookkeeping — live in the engines'
        ``_verify_call`` / ``_before_emit`` / ``_after_commit`` /
        ``_release_spec_slot`` hooks."""
        self._note_k_bucket(k)
        drafts = self._run_draft(k, decoding)
        lengths = np.array(
            [self._verify_len(s, k) for s in range(self.num_slots)], np.int32
        )
        tok = self._pack_verify_tok(drafts, lengths, k)
        t0_ns = time.perf_counter_ns()
        # the verify hook packs its tables and uploads its windows inside
        # the issue span
        rows, nxt0, keys = self._issue(
            self._verify_lane, t0_ns, self._verify_call, k, tok, lengths
        )
        self.stats.verify_steps += 1
        self.stats.note_lane(self._verify_lane)
        self._mirror.put("keys", keys)
        rec = _InflightStep(
            kind="spec",
            packed=pack_verify_d2h(rows, nxt0, keys),
            drafts=drafts,
            lengths=lengths,
            k=k,
        )
        if self.async_steps:
            # accept/rollback lags one step: the next step() commits it by
            # replaying the decision against the parked drafts — the verify
            # plan never needs the outcome, so nothing is guessed
            self._park(rec)
            return []
        return self._commit_spec(rec, now)

    def _apply_verify(
        self, now, rows, nxt0, drafts, lengths
    ) -> list[Request]:
        """Accept/rollback as data: commit the accepted prefix plus the
        target's correction token, rewind ``pos`` past it, and feed the
        correction token next. Rejected-tail KV sits beyond the rewound
        position — masked out by per-row attention and overwritten by the
        next committed write (paged storage additionally trims pages the
        shrinking tail can no longer reach), never branched on."""
        finished: list[Request] = []
        if self._faults is not None:
            nxt0, rows = self._inject_step_output(nxt0, rows)
        for s, req in enumerate(self._slots):
            if req is None or not self._active[s]:
                self.stats.idle_slot_steps += 1
                continue
            if self._prefilling[s]:
                continue  # chunk lane owns this slot (ticked elsewhere)
            self.stats.active_slot_steps += 1
            ln = int(lengths[s])
            if ln == 0:
                continue
            prompt = req.effective_prompt
            if self._cursor[s] + 1 < len(prompt):
                # token-by-token fallback: row 0 wrote this prompt token's
                # KV; feed the next prompt token, drop the sample
                self._pos[s] += 1
                self._cursor[s] += 1
                self._tok[s, 0] = prompt[self._cursor[s]]
                self._after_commit(s, req)
                self.stats.prompt_tokens += 1
                continue
            self._before_emit(s, req)
            if ln == 1:
                emitted = [int(nxt0[s])]
            else:
                k_s = ln - 1
                a = self._accepted_prefix(drafts[s], rows[s], k_s)
                emitted = [int(t) for t in rows[s, : a + 1]]
                self.stats.drafted_tokens += k_s
                self.stats.accepted_tokens += a
                self.accept_samples.append(a / k_s)
                tr = self._trace
                if tr is not None:
                    # a < k_s means the target rejected a draft suffix:
                    # the rollback is the interesting trace event
                    tr.emit(
                        "spec_rollback" if a < k_s else "spec_accept",
                        "lane:" + self._verify_lane,
                        args={"slot": s, "accepted": a, "k": k_s},
                    )
            if min(emitted) < 0:
                # NaN guard (§15): a poisoned sample surfaced as an invalid
                # token id. Quarantine exactly this slot — pos is not
                # advanced, nothing is appended, co-batched rows untouched.
                self._quarantine_slot(s, now)
                continue
            self._pos[s] += len(emitted)
            self._tok[s, 0] = emitted[-1]
            req.tokens.extend(emitted)
            self._after_commit(s, req)
            self._note_tokens(req, now)
            self.stats.tokens += len(emitted)
            self.stats.spec_tokens += len(emitted)
            if req.done:
                req.t_done = now
                self._note_finish(req, now)
                finished.append(req)
                self._release_spec_slot(s)
                self._mirror.touch("active")
                self.stats.finished += 1
        self._mirror.touch("tok", "pos")
        return finished

    # Storage hooks with dense defaults; the paged engine overrides them.
    def _before_emit(self, s: int, req: Request) -> None:
        """Pre-emission bookkeeping for a slot past teacher forcing."""

    def _after_commit(self, s: int, req: Request) -> None:
        """The slot's ``pos`` just advanced; sync storage to it."""

    def _release_spec_slot(self, s: int) -> None:
        """The slot's request finished inside the verify lane."""
        self._slots[s] = None
        self._active[s] = False

    # ------------------------------------- robustness surface (DESIGN.md §15)
    def _release_slot(self, s: int) -> None:
        """Storage-release hook for cancel/quarantine: clear the slot's
        host state so the next step masks it out (paged storage overrides
        to release the block table's pages too). The slot's input token is
        zeroed so a poisoned id never feeds a later gather."""
        self._slots[s] = None
        self._active[s] = False
        self._prefilling[s] = False
        self._tok[s, 0] = 0
        self._mirror.touch("tok", "active")

    def attach_watchdog(self, watchdog, on_straggler=None) -> None:
        """Wire a ``ft.failover.StepTimeWatchdog`` into the step loop:
        every ``step()`` observes its wall time; a flagged straggler emits
        a flight-recorder event, counts in the registry, and calls
        ``on_straggler(dt_s)`` (the degradation controller's hook)."""
        self._watchdog = watchdog
        self._on_straggler = on_straggler

    def attach_faults(self, plan) -> None:
        """Arm a ``core.faults.FaultPlan`` at this batcher's injection
        sites (``step_output`` at the commit boundaries, ``d2h_stall`` in
        ``_pull``). Detection/containment report back through the plan."""
        self._faults = plan

    def _watchdog_tick(self, dt_s: float) -> None:
        wd = self._watchdog
        straggler = wd.observe(self.stats.steps, dt_s)
        if straggler:
            self.stats.stragglers += 1
            self.telemetry.registry.inc("step_stragglers_total")
            tr = self._trace
            if tr is not None:
                tr.emit(
                    "straggler", "scheduler",
                    args={"step": self.stats.steps,
                          "ms": round(dt_s * 1e3, 3)},
                )
            if self._stall_pending and self._faults is not None:
                # an injected d2h stall was caught by the watchdog: the
                # detection mechanism worked; containment is that the step
                # still committed (a latency fault kills no request)
                self._faults.note_detected("d2h_stall")
                self._faults.note_contained("d2h_stall")
            if self._on_straggler is not None:
                self._on_straggler(dt_s)
        self._stall_pending = False

    def set_knobs(
        self,
        *,
        spec_k: int | None = None,
        prefill_chunk: int | None = None,
        token_budget: int | None = None,
    ) -> dict:
        """Cold-path actuation surface for the degradation ladder (§15).

        Every knob is pure host data consumed by the per-step plan: the
        next step simply dispatches different *already-warmed* keys
        (smaller chunk buckets, smaller or no k-buckets), so an actuation
        is at most a hysteresis-guarded rebind — never a compile. Values
        are clamped into the launch-time ranges warmup actually compiled;
        restoring the launch values is the symmetric recovery path."""
        if spec_k is not None:
            k = int(spec_k)
            k = 0 if k <= 0 else min(k, self._spec_max)
            if self._draft_dispatch is None or self._verify_dispatch is None:
                k = 0
            self.spec_k = k
            self._lane_policy.spec_k = k
        if prefill_chunk is not None and self._chunk_max > 0:
            c = bucket_pow2(
                max(CHUNK_BUCKET_MIN,
                    min(int(prefill_chunk), self._chunk_max)),
                CHUNK_BUCKET_MIN,
                self._chunk_max,
            )
            self.prefill_chunk = c
            self._lane_policy.prefill_chunk = c
        if token_budget is not None:
            b = min(self._budget_max,
                    max(int(token_budget), self.num_slots + 1))
            self.token_budget = b
            self._lane_policy.token_budget = b
        return {
            "spec_k": self.spec_k,
            "prefill_chunk": self.prefill_chunk,
            "token_budget": self.token_budget,
        }

    def set_mesh(self, name: str, now: float = 0.0) -> str:
        """Cold-path topology rebind (DESIGN.md §16): move the live serving
        state onto a different *warmed* device mesh and flip the decode hot
        slot to that mesh's executables — ``set_knobs``'s twin on the mesh
        axis of the dispatch key. The engine's ``mesh_ctl`` validates the
        name against the AOT-warmed ladder, ``device_put``s the caches onto
        the new plan (pure data movement), mutates the shared mesh binding
        every dispatch closure reads, and force-rebinds the dispatcher — by
        construction a rebind, never a compile. The in-flight step commits
        first so the state being moved is current; the device mirror drops
        its copies (they were committed to the old placement) and the
        mesh-labelled lane histograms refresh. Returns the canonical name
        of the mesh now active. Flipping to the current mesh is a no-op."""
        if self._mesh_ctl is None:
            raise RuntimeError(
                "this batcher has no mesh control surface; construct it "
                "through Engine.continuous/paged_continuous with the "
                "target topology in EngineConfig.mesh/meshes."
            )
        if self._inflight:
            self._backlog.extend(self._commit_pending(now))
        nm, self._cache, self._draft_cache = self._mesh_ctl(
            name, self._cache, self._draft_cache, **self._mesh_hot()
        )
        if nm != self.mesh:
            self.mesh = nm
            self._mirror.invalidate()
            self._lane_hist = {}  # new handles carry the new mesh label
            self._rebind_step()
        return nm

    def _mesh_hot(self) -> dict:
        """Engine hook: the batcher's current bucket state, forwarded to
        ``mesh_ctl``'s ``hot_key`` (the paged engine adds its pages
        bucket; the dense decode key has no bucket axis beyond slots)."""
        return {}

    def _rebind_step(self) -> None:
        """Engine hook: re-fetch the bound hot-loop step under the new
        mesh binding (the paged engine dispatches per step off its bucket
        and needs no stored rebind)."""

    def cancel(self, rid: int, now: float = 0.0,
               reason: str = "cancel") -> bool:
        """First-class mid-stream cancellation: release the request's slot
        (and, paged, its pages), mark it cancelled, and account it. A
        parked in-flight step commits first and its outcome is honoured —
        a request that finished inside that commit is *not* cancelled
        (commit-then-discard). Returns True when a seated request with
        ``rid`` was actually cancelled."""
        for s, req in enumerate(self._slots):
            if req is not None and req.rid == rid:
                return self._cancel_slot(s, now, reason) is not None
        return False

    def _cancel_slot(self, s: int, now: float, reason: str):
        target = self._slots[s]
        if self._inflight:
            # a parked step may be about to emit into this slot: commit
            # them all, then discard whatever landed (commit-then-discard)
            self._backlog.extend(self._commit_pending(now))
        req = self._slots[s]
        if req is None or req is not target:
            return None  # the committed step finished (or replaced) it
        req.cancelled = True
        req.shed_reason = reason
        self._release_slot(s)
        self.cancelled_requests.append(req)
        self.stats.cancelled += 1
        if reason == "deadline":
            self.stats.deadline_missed += 1
        self.telemetry.registry.inc(
            "requests_cancelled_total", reason=reason
        )
        tr = self._trace
        if tr is not None:
            tr.emit("cancel", "scheduler",
                    args={"rid": req.rid, "slot": s, "reason": reason})
        return req

    def _cancel_overdue(self, now: float) -> None:
        """Deadline enforcement: cancel every seated request whose
        ``deadline_s`` has passed (runs once per step, only while some
        seated request actually carries a deadline)."""
        for s in range(self.num_slots):
            req = self._slots[s]
            if (
                req is not None
                and req.deadline_s is not None
                and now > req.deadline_s
            ):
                self._cancel_slot(s, now, "deadline")

    def _quarantine_slot(self, s: int, now: float,
                         site: str = "step_output") -> None:
        """Fault containment (§15): a poisoned emission was detected on
        slot ``s``. Quarantine exactly that slot — release it (paged
        storage frees its pages) and either re-admit its request from
        scratch (first offence) or fail it (retry limit reached). The
        co-batched slots' state is untouched: per-row masking means the
        released row simply stops participating."""
        req = self._slots[s]
        self._release_slot(s)
        req.faults += 1
        self.stats.faults_detected += 1
        plan = self._faults
        if plan is not None:
            plan.note_detected(site)
        tr = self._trace
        if tr is not None:
            tr.emit("quarantine", "scheduler",
                    args={"rid": req.rid, "slot": s, "site": site})
        if req.faults <= self._fault_retry_limit:
            # restart from scratch: poisoned progress is discarded, like a
            # preemption (the driver re-submits ``requeued``)
            req.tokens = []
            req.t_admit = None
            req.t_first = None
            req.t_last = None
            self.requeued.append(req)
            self.stats.faults_contained += 1
            if plan is not None:
                plan.note_contained(site)
        else:
            req.error = site
            self.failed_requests.append(req)
            self.stats.faults_failed += 1
            self.telemetry.registry.inc("requests_failed_total", site=site)

    def _inject_step_output(self, nxt_host, rows=None):
        """``step_output`` fault site: one commit boundary. When armed,
        replace the victim slot's emission with ``POISON_TOKEN`` (the
        int32 image of a NaN-poisoned sample). The arrays are copied —
        the device-side step outputs are never mutated."""
        f = self._faults.fire("step_output")
        if f is None:
            return nxt_host, rows
        cands = [
            s for s, r in enumerate(self._slots)
            if r is not None and self._active[s] and not self._prefilling[s]
        ]
        if not cands:
            return nxt_host, rows
        from repro.core.faults import POISON_TOKEN

        s = cands[f.slot % len(cands)]
        nxt_host = np.array(nxt_host)
        nxt_host[s] = POISON_TOKEN
        if rows is not None:
            rows = np.array(rows)
            rows[s, :] = POISON_TOKEN
        tr = self._trace
        if tr is not None:
            tr.emit("fault_inject", "scheduler",
                    args={"site": "step_output", "slot": s})
        return nxt_host, rows

    # ------------------------------------------------------------ occupancy
    def _count_prefilling_slot_steps(self) -> None:
        """One occupancy tick per prefilling slot: active only for slots
        that received one of this step's chunks."""
        for s in range(self.num_slots):
            if self._slots[s] is None or not self._prefilling[s]:
                continue
            if s in self._chunk_slots:
                self.stats.active_slot_steps += 1
            else:
                self.stats.idle_slot_steps += 1

    def _count_slot_steps(self, decoding) -> None:
        """Occupancy accounting for prefill-only steps (the decode lane was
        skipped, so the per-slot loop never ran)."""
        self._count_prefilling_slot_steps()
        for s in range(self.num_slots):
            if self._slots[s] is None or self._prefilling[s]:
                if self._slots[s] is None:
                    self.stats.idle_slot_steps += 1
                continue
            # seated but excluded from a skipped decode call
            self.stats.idle_slot_steps += 1

    def _prime_first_token(
        self, s: int, req: Request, token: int, now: float
    ) -> None:
        """Flip tail: PREFILL -> DECODE, the chunk's last-row sample becomes
        the request's first emitted token (its TTFT anchor)."""
        self._prefilling[s] = False
        self._flip_slots.add(s)  # spec lanes treat it as plain decode today
        self._mirror.touch("active")  # the decoding mask just changed
        req.tokens.append(token)
        self._note_tokens(req, now)
        self.stats.tokens += 1
        self._tok[s, 0] = token
        self._mirror.touch("tok")


class ContinuousBatcher(_MultiLaneMixin):
    """Slot-based continuous batching over one fixed-bucket executable.

    ``step(cache, tok, pos, active, temps, greedy, keys)`` is the compiled
    hot-loop step (params already bound by the engine); see
    ``runtime.steps.make_slot_decode_fn`` for the contract. The batcher owns
    the S slots' host-side state and the device cache; ``admit`` (cold path)
    seats requests in free slots, ``step`` (hot path) advances every slot
    with a single direct executable call.

    Join/leave never touches the cold path: a join resets the slot's
    position to 0 (per-row attention masking makes the previous occupant's
    cache rows invisible — see ``attention.decode_attention``), a leave just
    clears the active mask. GREEDY vs SAMPLE is per-slot *data*.

    Prompts (``Request.prompt``) are teacher-forced before generation. With
    ``prefill_dispatch``/``prefill_chunk`` set (DESIGN.md §10) a seated
    prompt is ingested C tokens per step through the chunked-prefill
    executable (slots sit in a PREFILL state until their cursor reaches the
    prompt end, then flip to DECODE); otherwise prompts fall back to
    token-by-token forcing through the decode step — one full decode step
    per prompt token, the baseline the chunked path is benchmarked against.
    """

    def __init__(
        self,
        *,
        step: Callable,
        num_slots: int,
        max_len: int,
        cache: Any,
        seed: int = 0,
        prefill_dispatch: Callable[[int], Callable] | None = None,
        prefill_chunk: int = 0,
        token_budget: int = 0,
        draft_dispatch: Callable[[int], Callable] | None = None,
        verify_dispatch: Callable[[int], Callable] | None = None,
        draft_prefill_dispatch: Callable[[int], Callable] | None = None,
        draft_cache: Any = None,
        spec_k: int = 0,
        async_steps: bool = False,
        async_depth: int = 2,
        telemetry: Telemetry | None = None,
        mesh: str = "1x1",
        mesh_ctl: Callable | None = None,
        step_dispatch: Callable[[], Callable] | None = None,
    ):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.mesh = mesh  # before telemetry: lane histograms carry the label
        self._mesh_ctl = mesh_ctl
        self._step_dispatch = step_dispatch
        self._init_telemetry(telemetry)
        self._step = step
        self.num_slots = num_slots
        self.max_len = max_len
        self._cache = cache  # device-side KV cache, donated through steps
        self._rng = np.random.default_rng(seed)
        self._slots: list[Request | None] = [None] * num_slots
        self._tok = np.zeros((num_slots, 1), np.int32)
        self._pos = np.zeros(num_slots, np.int32)
        self._active = np.zeros(num_slots, bool)
        self._temps = np.ones(num_slots, np.float32)
        self._greedy = np.ones(num_slots, bool)
        self._keys = self._rng.integers(
            0, 2**32, size=(num_slots, 2), dtype=np.uint32
        )
        # chunked prefill (DESIGN.md §10): PREFILL/DECODE state per slot
        self._prefill_dispatch = prefill_dispatch
        self.prefill_chunk = prefill_chunk if prefill_dispatch else 0
        self.token_budget = token_budget or (num_slots + self.prefill_chunk)
        self._chunk_bucket = 0
        self._cursor = np.zeros(num_slots, np.int64)  # next prompt index fed
        self._prefilling = np.zeros(num_slots, bool)
        self.stats = BatcherStats(registry=self.telemetry.registry)
        self._mirror = _DeviceMirror(self.stats)
        self._init_lanes(
            draft_dispatch=draft_dispatch,
            verify_dispatch=verify_dispatch,
            draft_prefill_dispatch=draft_prefill_dispatch,
            draft_cache=draft_cache,
            spec_k=spec_k,
            async_steps=async_steps,
            async_depth=async_depth,
        )

    # ------------------------------------------------------------ properties
    @property
    def active_count(self) -> int:
        return int(self._active.sum())

    @property
    def free_slots(self) -> int:
        return self.num_slots - self.active_count

    @property
    def has_work(self) -> bool:
        return bool(self._active.any()) or bool(self._inflight)

    def _rebind_step(self) -> None:
        """The dense batcher holds its decode executable bound; after a
        mesh flip the engine's dispatch closure re-fetches the hot slot
        (``set_direction`` in ``mesh_ctl`` already flipped it — this is a
        table read, never a compile)."""
        if self._step_dispatch is not None:
            self._step = self._step_dispatch()

    # ------------------------------------------------------------- cold path
    def admit(self, requests: Iterable[Request], now: float = 0.0) -> int:
        """Seat requests in free slots. Returns the number admitted."""
        requests = list(requests)
        if requests and self._inflight:
            # admission edits the full per-slot state and re-uploads it; the
            # in-flight step must land first so those arrays are current
            self._backlog.extend(self._commit_pending(now))
        admitted = 0
        free = [i for i, r in enumerate(self._slots) if r is None]
        for req in requests:
            if not free:
                raise RuntimeError(
                    "ContinuousBatcher.admit called with no free slot; "
                    "gate admissions on .free_slots."
                )
            prompt = req.effective_prompt
            if len(prompt) + req.new_tokens - 1 > self.max_len:
                raise ValueError(
                    f"request {req.rid} wants {len(prompt)} prompt + "
                    f"{req.new_tokens} new tokens but the bucket's cache "
                    f"holds max_len={self.max_len}."
                )
            s = free.pop(0)  # seat in ascending slot order (deterministic)
            self._slots[s] = req
            self._tok[s, 0] = prompt[0]
            self._pos[s] = 0
            self._cursor[s] = 0
            self._active[s] = True
            # PREFILL when there is a prompt to ingest and a chunked lane to
            # ingest it with; single-seed requests decode straight away.
            self._prefilling[s] = self.prefill_chunk > 0 and len(prompt) > 1
            self._temps[s] = req.temperature
            self._greedy[s] = req.greedy
            self._keys[s] = self._rng.integers(
                0, 2**32, size=2, dtype=np.uint32
            )
            req.t_admit = now
            if req.deadline_s is not None:
                self._has_deadlines = True
            self._note_admit(req, now)
            admitted += 1
        if admitted:
            self._mirror.touch(
                "tok", "pos", "active", "temps", "greedy", "keys"
            )
            # double-buffered uploads (DESIGN.md §13): stage the edited
            # arrays on the admission cold path, not the next issue
            self._preload_step_inputs()
        self.stats.admitted += admitted
        return admitted

    # ------------------------------------------------------- prefill lane
    def _prefill_step(self, now: float, budget: int) -> list[Request]:
        """Ingest chunks for prefilling requests (DESIGN.md §10): plan and
        flip semantics live in ``_MultiLaneMixin``; this body is the dense
        storage half — each chunk writes straight into its slot's private
        cache rows (length 0 = idle row). *Batched* dense prefill: the
        ``("pfd", slots, chunk_bucket)`` executable already takes per-row
        chunk windows, so every prefilling slot the budget covers gets a
        chunk in the same call — bitwise-equal to running the chunks one
        slot at a time (rows are independent)."""
        plan = self._plan_chunks(budget)
        if not plan:
            return []
        bucket = bucket_pow2(
            max(c for _, _, c in plan), CHUNK_BUCKET_MIN, self.prefill_chunk
        )
        self._note_chunk_bucket(bucket)
        step = self._prefill_dispatch(bucket)  # cold: slot-hit usually
        tok = np.zeros((self.num_slots, bucket), np.int32)
        length = np.zeros(self.num_slots, np.int32)
        for s, cursor, chunk in plan:
            prompt = self._slots[s].effective_prompt
            tok[s, :chunk] = prompt[cursor : cursor + chunk]
            length[s] = chunk
        self.stats.prefill_rows += len(plan)
        self.stats.prefill_rows_padded += self.num_slots
        # a chunk that cannot flip any slot this step leaves every plan
        # input untouched: under async it issues and parks like a chainable
        # decode (DESIGN.md §13) — its keys must then chain through the
        # mirror, because a parked predecessor's key split only exists on
        # device until its commit
        park = (
            self.async_steps
            and not self._spec_on
            and not self.disagg
            and not any(
                cursor + chunk >= len(self._slots[s].effective_prompt)
                for s, cursor, chunk in plan
            )
        )
        # chunk-lane inputs are genuinely per-chunk data (tokens, cursor,
        # length, split keys) — uploaded raw once, counted honestly, and
        # the device arrays are shared with the draft mirror below
        self.stats.h2d_uploads += 3 if park else 4
        self.stats.prefill_calls += 1
        self.stats.note_lane(self._prefill_lane)
        tok_dev = jnp.asarray(tok)
        start_dev = jnp.asarray(np.array(self._pos, np.int32))  # == cursor
        length_dev = jnp.asarray(length)
        keys_dev = (
            self._mirror.get("keys", self._keys)
            if park
            else jnp.asarray(self._keys)
        )
        t0_ns = time.perf_counter_ns()
        nxt, self._cache, new_keys = self._issue(
            self._prefill_lane,
            t0_ns,
            step,
            self._cache,
            tok_dev,
            start_dev,
            length_dev,
            self._mirror.get("temps", self._temps),
            self._mirror.get("greedy", self._greedy),
            keys_dev,
        )
        # draft mirror (DESIGN.md §11): the draft stack must ingest the
        # same prompt windows so its KV tracks the committed stream before
        # the draft lane runs; the inputs are the target call's device
        # arrays (no second upload), and the sampling params are inert
        # (the sampled head output and split keys are discarded).
        if self._spec_on and self._draft_prefill_dispatch is not None:
            dstep = self._draft_prefill_dispatch(bucket)
            self.stats.note_lane("drp")
            t0_ns = time.perf_counter_ns()
            _, self._draft_cache, _ = self._issue(
                "drp",
                t0_ns,
                dstep,
                self._draft_cache,
                tok_dev,
                start_dev,
                length_dev,
                self._mirror.get("temps", self._temps),
                self._mirror.get("greedy", self._greedy),
                keys_dev,
            )
        if park:
            # no host read: bookkeeping runs now (the chunk plan is final),
            # the split keys chain on device, and the packed pull parks
            # until the pipeline's next emit boundary
            self._mirror.put("keys", new_keys)
            for s, cursor, chunk in plan:
                self._chunk_slots.add(s)
                cursor += chunk
                self._cursor[s] = cursor
                self._pos[s] = cursor
                self.stats.prompt_tokens += chunk
                self.stats.prefill_chunks += 1
            self._mirror.touch("pos")
            self._queue_prefill(pack_step_d2h(nxt, new_keys))
            return []
        # one packed transfer for the chunk's host-bound outputs (§13)
        p = self._pull(pack_step_d2h(nxt, new_keys))
        nxt_host = p[:, 0]
        nk = p[:, 1:3].astype(np.uint32)
        finished: list[Request] = []
        for s, cursor, chunk in plan:
            req = self._slots[s]
            prompt = req.effective_prompt
            self._keys[s] = nk[s]
            self._chunk_slots.add(s)
            cursor += chunk
            self._cursor[s] = cursor
            self._pos[s] = cursor
            self.stats.prompt_tokens += chunk
            self.stats.prefill_chunks += 1
            if cursor >= len(prompt):  # flip: prompt done, prime generation
                self._prime_first_token(s, req, int(nxt_host[s]), now)
                if req.done:
                    req.t_done = now
                    self._note_finish(req, now)
                    finished.append(req)
                    self._slots[s] = None
                    self._active[s] = False
                    self.stats.finished += 1
        self._mirror.touch("pos", "keys")
        return finished

    # -------------------------------------------------------------- hot path
    def _step_impl(self, now: float = 0.0) -> list[Request]:
        """One multi-lane step for all slots (entered through the mixin's
        pipelined ``step`` wrapper); returns finished requests.

        Lane order (DESIGN.md §11): prefill chunks first, then either the
        draft/verify pair (speculation planned this step) or the plain
        decode executable for the decoding slots — every lane a single
        direct call of a pre-compiled executable, no tracing, no cache
        hashing, no mode conditionals, regardless of the request mix.
        """
        if not self._active.any():
            return []
        finished: list[Request] = []
        self._chunk_slots = set()
        self._flip_slots = set()
        plan = self._plan_step()
        if self.prefill_chunk > 0 and (self._prefilling & self._active).any():
            finished.extend(self._prefill_step(now, plan.chunk_budget))
        decoding = self._active & ~self._prefilling
        if not decoding.any():
            self.stats.steps += 1  # prefill-only step
            self._count_slot_steps(decoding)
            return finished
        if plan.k > 0:  # draft/verify lanes replace the decode lane
            finished.extend(self._spec_step(now, plan.k, decoding))
            self.stats.steps += 1
            self._count_prefilling_slot_steps()
            return finished
        finished.extend(self._decode_lane_step(now, decoding))
        return finished

    def _decode_lane_step(self, now: float, decoding) -> list[Request]:
        """Dense decode lane: one direct executable call. Synchronous mode
        pulls and emits immediately; async adopts the executable's bundle
        outputs (chained input + packed d2h array, ``steps._step_bundle``)
        and parks the step for the pipeline to commit at the next emit
        boundary (DESIGN.md §13). A legacy 4-output step fn (tests inject
        them) degrades async to the synchronous commit."""
        t0_ns = time.perf_counter_ns()
        out = self._issue(
            self._decode_lane,
            t0_ns,
            self._step,
            self._cache,
            self._mirror.get("tok", self._tok),
            self._mirror.get("pos", self._pos),
            self._mirror.get("active", decoding),
            self._mirror.get("temps", self._temps),
            self._mirror.get("greedy", self._greedy),
            self._mirror.get("keys", self._keys),
        )
        nxt, self._cache, pos, keys = out[:4]
        self.stats.decode_steps += 1
        self.stats.note_lane(self._decode_lane)
        self.stats.steps += 1
        self._mirror.put("pos", pos)
        self._mirror.put("keys", keys)
        if self.async_steps and len(out) == 6:
            self._mirror.put("tok", out[4])  # bundle-staged chained input
            self._queue_decode(out[5], decoding)
            return []
        self._mirror.put("tok", nxt[:, None])  # device reshape, no upload
        nxt_host = self._pull(nxt)  # blocks until the device step is done
        # copies: the host mutates these on join (device views are read-only)
        self._pos = np.array(self._pull(pos), np.int32)
        self._keys = np.array(self._pull(keys), np.uint32)
        return self._emit_decode(nxt_host, self._pos, now)

    def _emit_decode(
        self, nxt_host, pos_host, now: float
    ) -> list[Request]:
        """The decode step's emit boundary: pure host bookkeeping against
        already-pulled outputs (``pos_host`` is unused here — dense slots
        carry no storage that tracks positions; the paged twin needs it)."""
        finished: list[Request] = []
        if self._faults is not None:
            nxt_host, _ = self._inject_step_output(nxt_host)
        self._tok = np.asarray(nxt_host)[:, None].astype(np.int32)
        self._count_prefilling_slot_steps()
        for s, req in enumerate(self._slots):
            if req is None or not self._active[s]:
                self.stats.idle_slot_steps += 1
                continue
            if self._prefilling[s]:
                continue  # chunked lane owns this slot (ticked above)
            self.stats.active_slot_steps += 1
            prompt = req.effective_prompt
            if self._cursor[s] + 1 < len(prompt):
                # token-by-token fallback (prefill_chunk == 0): feed the
                # next prompt token, drop the sample
                self._cursor[s] += 1
                self._tok[s, 0] = prompt[self._cursor[s]]
                self._mirror.touch("tok")
                self.stats.prompt_tokens += 1
                continue
            if int(nxt_host[s]) < 0:
                # NaN guard (§15): invalid token id — quarantine this slot
                self._quarantine_slot(s, now)
                continue
            req.tokens.append(int(nxt_host[s]))
            self._note_tokens(req, now)
            self.stats.tokens += 1
            if req.done:
                req.t_done = now
                self._note_finish(req, now)
                finished.append(req)
                self._slots[s] = None
                self._active[s] = False
                self._mirror.touch("active")
                self.stats.finished += 1
        return finished

    # ---------------------------------------------------- draft/verify lanes
    def _verify_call(self, k: int, tok, lengths):
        """Dense verify executable ``("vfd", slots, k)``: the shared
        ``_spec_step``/``_apply_verify`` core lives in ``_MultiLaneMixin``;
        only the signature (no block tables) is engine-specific."""
        step = self._verify_dispatch(k)  # cold: slot-hit unless k moved
        self.stats.h2d_uploads += 2  # per-step window data (tok pack, len)
        rows, nxt0, self._cache, keys = step(
            self._cache,
            jnp.asarray(tok),
            self._mirror.get("pos", self._pos),
            jnp.asarray(lengths),
            self._mirror.get("temps", self._temps),
            self._mirror.get("greedy", self._greedy),
            self._mirror.get("keys", self._keys),
        )
        return rows, nxt0, keys


# ------------------------------------------------- paged continuous batching
@dataclass
class PagedBatcherStats(BatcherStats):
    preemptions: int = 0
    bucket_crossings: int = 0
    starved_admissions: int = 0  # distinct requests deferred for pages
    rejected_oversize: int = 0  # requests that can never fit the page cap
    shared_tokens: int = 0  # prompt tokens skipped via the prefix cache
    # Disaggregated prefill/decode (DESIGN.md §17): PREFILL->DECODE flips
    # that moved pages across pools, pages moved, and prefill-slice shadow
    # pages allocated (adopted-prefix mirrors + split-time copies).
    migrations: int = 0
    migrated_pages: int = 0
    pf_shadow_pages: int = 0


class PagedContinuousBatcher(_MultiLaneMixin):
    """Continuous batching against a paged KV pool (DESIGN.md §9).

    The slot-state machinery mirrors ``ContinuousBatcher``; what changes is
    capacity. Slots no longer own ``[max_len]`` cache rows — each active
    request owns a ``kvcache.BlockTable`` over the shared ``PagePool``, and
    the hot-loop executable is keyed by ``("cbp", slots, pages_bucket,
    kv_dtype, mesh)``
    where ``pages_bucket`` is the (bucketed) widest block table currently
    active. The bucket moves rarely — once per ``page_size × bucket`` tokens
    — so the capacity check lives entirely on the cold path: ``dispatch_fn``
    (the engine's Dispatcher) returns the bucket's executable and the hot
    loop calls it directly.

    Admission walks the ``PrefixCache``: prompt pages already populated by an
    earlier request are adopted by reference (ref++), the teacher-forcing
    cursor starts after them, and completed prompts insert their full pages
    back into the trie. On pool exhaustion the batcher first evicts idle
    cached pages, then preempts the lowest-priority active request (its
    pages recycle; the request re-queues and restarts) — admission never
    hard-rejects.
    """

    _decode_lane = "cbp"
    _prefill_lane = "pf"
    _verify_lane = "vf"

    def __init__(
        self,
        *,
        dispatch_fn: Callable[[int], Callable],
        pool,
        prefix_cache,
        cache: Any,
        num_slots: int,
        max_pages_per_req: int,
        cache_copy: Callable | None = None,
        seed: int = 0,
        prefill_dispatch: Callable[[int], Callable] | None = None,
        prefill_chunk: int = 0,
        token_budget: int = 0,
        prefill_rows_dispatch: Callable[[int], Callable] | None = None,
        draft_dispatch: Callable[[int], Callable] | None = None,
        verify_dispatch: Callable[[int], Callable] | None = None,
        draft_prefill_dispatch: Callable[[int], Callable] | None = None,
        draft_cache: Any = None,
        spec_k: int = 0,
        async_steps: bool = False,
        async_depth: int = 2,
        telemetry: Telemetry | None = None,
        mesh: str = "1x1",
        mesh_ctl: Callable | None = None,
        pf_pool=None,
        pf_cache: Any = None,
        transport: Callable | None = None,
        pf_put: Callable | None = None,
        disagg_ctl: Callable | None = None,
        disagg: bool = False,
    ):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if pool.shards > num_slots:
            raise ValueError(
                f"pool has {pool.shards} shards but only {num_slots} slots; "
                f"every shard needs at least one slot to serve its pages."
            )
        self.mesh = mesh  # before telemetry: lane histograms carry the label
        self._mesh_ctl = mesh_ctl
        self._init_telemetry(telemetry)
        self._dispatch = dispatch_fn
        self.pool = pool
        self.prefix = prefix_cache
        self._cache = cache  # pooled device pages, donated through steps
        self.num_slots = num_slots
        self.max_pages_per_req = max_pages_per_req
        # device half of COW: cache_copy(cache, src, dst) -> cache (e.g. a
        # jitted models.copy_cache_pages); None skips the data move.
        self._cache_copy = cache_copy
        self._rng = np.random.default_rng(seed)
        self._slots: list[Request | None] = [None] * num_slots
        self._tables: list[Any] = [None] * num_slots
        self._cursor = np.zeros(num_slots, np.int64)  # next prompt index fed
        self._tok = np.zeros((num_slots, 1), np.int32)
        self._pos = np.zeros(num_slots, np.int32)
        self._active = np.zeros(num_slots, bool)
        self._temps = np.ones(num_slots, np.float32)
        self._greedy = np.ones(num_slots, bool)
        self._keys = self._rng.integers(
            0, 2**32, size=(num_slots, 2), dtype=np.uint32
        )
        self._prompt_cached = np.zeros(num_slots, bool)
        self._pages_bucket = 1
        # Data-parallel slot partitioning (DESIGN.md §16): slot ``s`` is
        # pinned to pool shard ``s * shards // num_slots`` — contiguous
        # slot groups per shard, so its block table allocates, adopts and
        # prefix-matches only shard-local pages and the device gather
        # never crosses the mesh's data axis. One shard (the default)
        # makes every entry 0, i.e. the classic unsharded layout.
        self._slot_shard = [
            s * pool.shards // num_slots for s in range(num_slots)
        ]
        # Packed-table padding rows: each slot pads with *its* shard's
        # null page (all zeros when shards == 1 — the historical fill).
        self._null_fill = np.array(
            [pool.null_page(sh) for sh in self._slot_shard], np.int32
        )
        self._bt_host: np.ndarray | None = None
        # chunked prefill (DESIGN.md §10): PREFILL/DECODE state per slot
        self._prefill_dispatch = prefill_dispatch
        self.prefill_chunk = prefill_chunk if prefill_dispatch else 0
        # row-bucketed prefill (DESIGN.md §10): rows -> the executable of
        # the ("pfr", slots, rows, ...) key; None keeps every call at the
        # slot count (spec lanes on, sharded pool, or no engine)
        self._prefill_rows_dispatch = (
            prefill_rows_dispatch if prefill_dispatch else None
        )
        self.token_budget = token_budget or (num_slots + self.prefill_chunk)
        self._chunk_bucket = 0
        self._prefilling = np.zeros(num_slots, bool)
        self.preempted: list[Request] = []
        self.rejected: list[Request] = []  # oversized: can never be seated
        self._starved_rids: set[int] = set()
        # Disaggregated prefill/decode (DESIGN.md §17): the prefill slice's
        # own page pool + device cache, the cross-slice page transport, and
        # the split/collapse rebind closure (all engine-wired; None when
        # the batcher was built without ``disagg``). ``self.disagg`` is the
        # *current* mode — ``set_disagg`` flips it mid-stream. Per-slot
        # prefill-side state: the shadow block table a prefilling slot
        # writes on the prefill slice, and how many of its leading pages
        # are pure copies of decode-resident prefix pages (never written
        # on the prefill slice, so they are dropped — not migrated — at
        # the PREFILL->DECODE flip).
        self.pf_pool = pf_pool
        self._pf_cache = pf_cache
        self._transport = transport
        # single-hop host->prefill-slice upload (falls back to the default
        # device when the engine passes none); the staging dict memoises
        # slow-moving per-slot arrays (temps/greedy/keys) on the slice so
        # steady-state chunk steps re-upload nothing
        self._pf_put = (
            pf_put
            if pf_put is not None
            else (lambda host: jax.tree.map(jnp.asarray, host))
        )
        self._pf_staged: dict[str, tuple[np.ndarray, Any]] = {}
        self._disagg_ctl = disagg_ctl
        self.disagg = bool(disagg) and disagg_ctl is not None
        self._pf_tables: dict[int, Any] = {}
        self._pf_keep: dict[int, int] = {}
        self.stats = PagedBatcherStats(registry=self.telemetry.registry)
        self._mirror = _DeviceMirror(self.stats)
        self._bt_dirty = True  # host block-table array needs a rebuild
        # full-width packed table for the verify lane (pinned at the
        # per-request page cap, like the prefill lane's — k is the only
        # verify bucket axis)
        self._bt_full_dirty = True
        self._bt_full: np.ndarray | None = None
        self._init_lanes(
            draft_dispatch=draft_dispatch,
            verify_dispatch=verify_dispatch,
            draft_prefill_dispatch=draft_prefill_dispatch,
            draft_cache=draft_cache,
            spec_k=spec_k,
            async_steps=async_steps,
            async_depth=async_depth,
        )

    def _tables_changed(self) -> None:
        """Some block table changed shape or contents (growth, COW, trim,
        admit, release): both packed host arrays need a rebuild."""
        self._bt_dirty = True
        self._bt_full_dirty = True

    # ------------------------------------------------------------ properties
    @property
    def active_count(self) -> int:
        return int(self._active.sum())

    @property
    def free_slots(self) -> int:
        return self.num_slots - self.active_count

    @property
    def has_work(self) -> bool:
        return bool(self._active.any()) or bool(self._inflight)

    @property
    def pages_bucket(self) -> int:
        return self._pages_bucket

    @property
    def kv_dtype(self) -> str:
        """The pool's page storage dtype (DESIGN.md §12) — fixed per
        batcher; the engine warmed every configured dtype's lanes, so a
        new batcher on the other dtype rebinds without compiling."""
        return self.pool.kv_dtype

    def live_tables(self):
        return [t for t in self._tables if t is not None]

    def _mesh_hot(self) -> dict:
        """The paged decode key carries the pages bucket; forward the
        current one so ``mesh_ctl`` flips the hot slot to the same bucket
        under the new mesh coordinate."""
        return {"pages_bucket": self._pages_bucket}

    def _preload_step_inputs(self) -> None:
        super()._preload_step_inputs()
        if not self._bt_dirty and self._bt_host is not None:
            self._mirror.preload("bt", self._bt_host)

    # ------------------------------------------------------------- cold path
    def _reclaim_pages(
        self, want: int, requester_priority: int, shard: int = 0
    ) -> bool:
        """Free >= ``want`` pages *on ``shard``*: evict idle prefix pages
        from that shard's trie, then preempt strictly-lower-priority
        requests seated on the same shard (a victim elsewhere would free
        pages the requester cannot use). False if pressure can't be met.
        Single-shard pools reproduce the historical global sweep."""
        if self.pool.pages_free_in(shard) >= want:
            return True
        self.prefix.evict(want - self.pool.pages_free_in(shard), shard)
        while self.pool.pages_free_in(shard) < want:
            victim = self._pick_victim(requester_priority, shard)
            if victim is None:
                return False
            self._preempt_slot(victim)
            self.prefix.evict(want - self.pool.pages_free_in(shard), shard)
        return True

    def _pick_victim(
        self, requester_priority: int, shard: int = 0
    ) -> int | None:
        """Lowest-priority active slot strictly below the requester *on the
        requester's shard*; ties break toward the most recently admitted
        (least sunk work)."""
        best, best_key = None, None
        for s, req in enumerate(self._slots):
            if req is None or not self._active[s]:
                continue
            if self._slot_shard[s] != shard:
                continue
            if req.priority >= requester_priority:
                continue
            key = (req.priority, -(req.t_admit or 0.0))
            if best_key is None or key < best_key:
                best, best_key = s, key
        return best

    def _preempt_slot(self, s: int) -> None:
        req = self._slots[s]
        assert req is not None
        self._drop_pf_state(s)
        self._tables[s].release()
        self._tables[s] = None
        self._slots[s] = None
        self._active[s] = False
        self._prefilling[s] = False
        self._mirror.touch("active")
        self._tables_changed()
        req.tokens = []
        req.t_admit = None
        req.t_first = None  # restart: earlier progress is discarded
        req.preemptions += 1
        self.stats.preemptions += 1
        self.preempted.append(req)
        tr = self._trace
        if tr is not None:
            tr.emit("preempt", "scheduler",
                    args={"rid": req.rid, "slot": s})

    def admit(self, requests: Iterable[Request], now: float = 0.0) -> list:
        """Seat requests in free slots; returns the requests deferred for
        lack of pages (callers re-queue them — admission never rejects)."""
        requests = list(requests)
        with self._span("serve.batcher.admit") as sp:
            deferred = self._admit(requests, now)
            sp.note(requests=len(requests), deferred=len(deferred))
        return deferred

    def _admit(self, requests: list, now: float) -> list:
        from repro.runtime.kvcache import BlockTable

        if requests and self._inflight:
            # admission edits the full per-slot state and re-uploads it; the
            # in-flight step must land first so those arrays are current
            self._backlog.extend(self._commit_pending(now))
        deferred: list[Request] = []
        seated = False
        free = [i for i, r in enumerate(self._slots) if r is None]
        for req in requests:
            if not free:
                raise RuntimeError(
                    "PagedContinuousBatcher.admit called with no free slot; "
                    "gate admissions on .free_slots."
                )
            prompt = req.effective_prompt
            # the last generated token is emitted but never written to KV,
            # so capacity is total_tokens - 1 positions (mirrors the dense
            # admission check)
            need_pages = -(
                -max(req.total_tokens - 1, 1) // self.pool.page_size
            )
            if need_pages > self.max_pages_per_req:
                # can never fit, at any load: reject this one request rather
                # than crash the stream (deferring would loop forever)
                self.stats.rejected_oversize += 1
                self.rejected.append(req)
                tr = self._trace
                if tr is not None:
                    tr.emit("admission_rejected", "scheduler",
                            args={"rid": req.rid,
                                  "need_pages": need_pages})
                continue
            # The request seats in the head free slot, and the slot pins
            # the pool shard (DESIGN.md §16) — so the shard is decided
            # *before* the prefix walk: only pages physically resident on
            # that shard may be adopted, and reclaim pressure lands there.
            s = free[0]
            shard = self._slot_shard[s]
            # Prefix-cache walk: adopt already-populated full prompt pages,
            # but never the page holding the last prompt token — that token
            # is re-fed to prime generation, and keeping its page private
            # makes prompt-path writes COW-free (shared pages stay read-only
            # by construction).
            with self._span("serve.cache.match") as sp:
                pages, matched = self.prefix.match(prompt, shard)
                usable = min(
                    len(pages), (len(prompt) - 1) // self.pool.page_size
                )
                for pid in pages[usable:]:
                    self.pool.decref(pid)
                pages = pages[:usable]
                matched = usable * self.pool.page_size
                sp.note(pages=usable)
            table = BlockTable(pool=self.pool, pages=pages,
                               num_tokens=matched, shard=shard)
            # first private page: the one the re-fed prompt token writes into
            with self._span("serve.cache.reserve", pages=1):
                starved = not self._reclaim_pages(1, req.priority, shard) or (
                    not table.ensure_capacity(matched)
                )
            # PREFILL when more than the re-fed last token remains to ingest
            # and a chunked lane exists; otherwise straight to DECODE
            # (token-by-token forcing handles any prompt remainder there).
            will_prefill = (
                self.prefill_chunk > 0 and len(prompt) - matched > 1
            )
            if not starved and self.disagg and will_prefill:
                # disaggregated chunk steps run on the prefill slice
                # (DESIGN.md §17): mirror the adopted prefix pages there,
                # and drop the just-ensured private decode page — every
                # page the prefill lane writes lands in the decode pool
                # via migration at the flip instead
                if self._make_pf_shadow(s, table):
                    table.trim(len(pages))
                else:
                    starved = True
            if starved:
                table.release()
                if req.rid not in self._starved_rids:  # count requests once
                    self._starved_rids.add(req.rid)
                    self.stats.starved_admissions += 1
                deferred.append(req)
                tr = self._trace
                if tr is not None:
                    tr.emit("admission_deferred", "scheduler",
                            args={"rid": req.rid})
                continue
            free.pop(0)  # == s, peeked above
            self._slots[s] = req
            self._tables[s] = table
            self._cursor[s] = matched
            self._tok[s, 0] = prompt[matched]
            self._pos[s] = matched
            self._active[s] = True
            self._prefilling[s] = will_prefill
            self._temps[s] = req.temperature
            self._greedy[s] = req.greedy
            self._keys[s] = self._rng.integers(
                0, 2**32, size=2, dtype=np.uint32
            )
            self._prompt_cached[s] = False
            req.t_admit = now
            if req.deadline_s is not None:
                self._has_deadlines = True
            self._note_admit(req, now)
            self._mirror.touch(
                "tok", "pos", "active", "temps", "greedy", "keys"
            )
            self._tables_changed()
            self.stats.admitted += 1
            self.stats.shared_tokens += matched
            seated = True
        if seated:
            # double-buffered uploads (DESIGN.md §13): stage the edited
            # arrays on the admission cold path, not the next issue
            with self._span("serve.dispatch.upload", lane="preload"):
                self._preload_step_inputs()
        return deferred

    def _page_upkeep(self, k: int = 0) -> None:
        """Pre-step cold path: every decoding slot must own writable pages
        for its whole write window this step — just the current position
        for the decode lane, positions ``[pos, pos + len - 1]`` for a
        verify window of ``len`` (DESIGN.md §11). Growth/COW happens here,
        never in-loop. Prefilling slots are skipped — the prefill lane
        reserves its own chunk's pages before each chunk step."""
        with self._span("serve.cache.upkeep") as sp:
            st = self.pool.stats
            allocs0, cows0 = st.allocs, st.cow_copies
            ps = self.pool.page_size
            for s, req in enumerate(self._slots):
                if req is None or not self._active[s] or self._prefilling[s]:
                    continue
                table = self._tables[s]
                pos = int(self._pos[s])
                top = pos
                if k > 0:
                    top += max(self._verify_len(s, k) - 1, 0)
                need = table.page_index(top) + 1 - table.num_pages
                if need > 0:
                    self._tables_changed()
                    if not self._reclaim_pages(
                        need, req.priority, table.shard
                    ) or not table.ensure_capacity(top):
                        # can't grow: preempt the requester itself
                        self._preempt_slot(s)
                        continue
                ok = True
                first, last = table.page_index(pos), table.page_index(top)
                for pi in range(first, last + 1):
                    if not table.ensure_writable(
                        max(pos, pi * ps), self._device_copy_page
                    ):
                        ok = False
                        break
                if not ok:
                    self._preempt_slot(s)
            sp.note(allocs=st.allocs - allocs0, cow=st.cow_copies - cows0)

    def _device_copy_page(self, src: int, dst: int) -> None:
        self._tables_changed()  # COW swapped a page id in some table
        if self._cache_copy is not None:
            self._cache = self._cache_copy(self._cache, src, dst)

    # ----------------------------- disaggregated prefill/decode (§17)
    def _pf_stage(self, name: str, host) -> Any:
        """Upload ``host`` to the prefill slice, memoised by content: the
        per-slot sampling state changes rarely between chunk steps, so the
        steady state re-uploads nothing (DESIGN.md §17)."""
        host = np.asarray(host)
        hit = self._pf_staged.get(name)
        if hit is not None and np.array_equal(hit[0], host):
            return hit[1]
        dev = self._pf_put(host)
        self._pf_staged[name] = (host.copy(), dev)
        self.stats.h2d_uploads += 1
        return dev

    def _make_pf_shadow(
        self, s: int, table, n: int | None = None, keep: int | None = None
    ) -> bool:
        """Give slot ``s`` a prefill-slice shadow of the first ``n`` pages
        of its decode-side ``table`` (default: the adopted full prefix
        pages): allocate twins in the prefill pool and copy the contents
        across, so every chunk step attends the shared prefix without
        touching the decode slice. ``keep`` of the leading shadow pages
        are never *written* on the prefill slice — they are dropped, not
        migrated, at the flip (default: all of ``n``; a mid-prefill split
        passes fewer, because its partially-written boundary page keeps
        being written over there). False = the prefill pool is dry; the
        caller defers or preempts."""
        from repro.runtime.kvcache import BlockTable

        if n is None:
            n = table.num_tokens // self.pool.page_size
        if keep is None:
            keep = n
        shard = table.shard
        pages: list[int] = []
        for _ in range(n):
            pid = self.pf_pool.alloc(shard)
            if pid is None:
                for p in pages:
                    self.pf_pool.decref(p)
                return False
            pages.append(pid)
        if pages:
            with self._span("serve.cache.migrate", pages=n):
                self._pf_cache = self._transport(
                    self._cache, self._pf_cache, table.pages[:n], pages,
                    to_prefill=True,
                )
        self._pf_tables[s] = BlockTable(
            pool=self.pf_pool, pages=pages, num_tokens=table.num_tokens,
            shard=shard,
        )
        self._pf_keep[s] = keep
        self.stats.pf_shadow_pages += n
        return True

    def _migrate_back(self, s: int) -> bool:
        """Land slot ``s``'s freshly written prefill-slice pages in the
        decode pool/cache and fold them into its base table — the KV
        handoff of the PREFILL->DECODE flip (and of a mid-prefill
        collapse). Bookkeeping rides ``kvcache.migrate_pages`` (export/
        import conserves refcounts); contents ride the engine's batched
        gather/``device_put``/scatter transport. The leading ``_pf_keep``
        shadow pages are copies of pages the base table still holds —
        dropped in place; a partially-written boundary page (mid-prefill
        split) migrates back and *replaces* its stale decode twin
        (``trim``). False = the decode pool could not fund the landing
        and the slot was preempted."""
        from repro.runtime.kvcache import migrate_pages

        pf_t = self._pf_tables.pop(s)
        keep = self._pf_keep.pop(s, 0)
        base = self._tables[s]
        req = self._slots[s]
        fresh = pf_t.pages[keep:]
        with self._span("serve.cache.migrate", pages=len(fresh)):
            if fresh:
                shard = base.shard
                if not self._reclaim_pages(len(fresh), req.priority, shard):
                    pf_t.release()
                    self._preempt_slot(s)
                    return False
                mapping = migrate_pages(self.pf_pool, self.pool, fresh, shard)
                dst = [mapping[p] for p in fresh]
                self._cache = self._transport(
                    self._pf_cache, self._cache, fresh, dst
                )
                base.trim(keep)  # stale twin of the boundary page, if any
                base.pages.extend(dst)  # import carried the refcounts over
                self.stats.migrations += 1
                self.stats.migrated_pages += len(fresh)
                # the exported ids already left the prefill pool: drop them
                # without decref so release() only returns the keep shadows
                del pf_t.pages[keep:]
            base.num_tokens = int(self._cursor[s])
            pf_t.release()
            self._tables_changed()
        return True

    def _drop_pf_state(self, s: int) -> None:
        """Slot ``s`` is leaving (preempt/cancel/finish): return its
        prefill-slice shadow pages, if it still holds any."""
        pf_t = self._pf_tables.pop(s, None)
        if pf_t is not None:
            pf_t.release()
        self._pf_keep.pop(s, None)

    def set_disagg(self, on: bool, now: float = 0.0) -> bool:
        """Cold-path split/collapse of the serving topology (DESIGN.md
        §17): flip between disaggregated prefill/decode and shared-mesh
        serving mid-stream. Both prefill bindings sit in the AOT-warmed
        ladder, so — like ``set_mesh`` / ``set_knobs`` — this is a
        semi-static rebind, never a compile; the decode lane's binding
        never moves. The pipeline drains first (parked steps were issued
        under the old routing). Splitting gives every mid-prefill slot a
        full shadow of its written pages on the prefill slice (its next
        chunk runs there); collapsing migrates fresh prefill-slice pages
        back early and lets prefill continue on the decode mesh. Returns
        the mode now active."""
        if self._disagg_ctl is None:
            raise RuntimeError(
                "this batcher has no disaggregation surface; construct it "
                "through Engine.paged_continuous(disagg=...)."
            )
        on = bool(on)
        if on == self.disagg:
            return on
        if self._inflight:
            self._backlog.extend(self._commit_pending(now))
        ps = self.pool.page_size
        if on:
            for s, req in enumerate(self._slots):
                if (
                    req is None
                    or not self._active[s]
                    or not self._prefilling[s]
                ):
                    continue
                table = self._tables[s]
                if not self._make_pf_shadow(
                    s, table,
                    n=table.num_pages,
                    keep=table.num_tokens // ps,
                ):
                    self._preempt_slot(s)  # prefill slice can't hold it
        else:
            for s in list(self._pf_tables):
                if s not in self._pf_tables:
                    continue  # a reclaim preempted it mid-collapse
                self._migrate_back(s)
        self.disagg = on
        self._lane_policy.decoupled = on
        self._disagg_ctl(on)
        tr = self._trace
        if tr is not None:
            tr.emit("disagg_flip", "scheduler", args={"on": on})
        return on

    # ------------------------------------------------------- prefill lane
    def _prefill_step(self, now: float, budget: int) -> list[Request]:
        """Ingest chunks for prefilling requests, *batched* (DESIGN.md
        §10/§12): plan and flip semantics live in ``_MultiLaneMixin``; this
        body is the paged storage half. Every planned chunk's pages are
        reserved up front (reclaim -> preempt-self on OOM, exactly like
        decode growth), then every surviving slot rides one call — per-row
        chunk windows through per-row block tables, length 0 = idle row,
        padded columns writing only the null page. The call is sized by the
        chunks it carries: when they fit a power-of-two row bucket below the
        slot count, they pack into its rows at the top chunk bucket, one
        ``("pfr", slots, rows, kv_dtype, mesh)`` call; otherwise row ``s``
        is slot ``s`` in a ``("pf", slots, chunk_bucket, kv_dtype, mesh)``
        call. Rows are independent (each writes its own private pages), so
        the batched call is equal to running the chunks one slot at a time;
        the flip publishes each prompt's full pages to the prefix cache."""
        plan = self._plan_chunks(budget)
        if not plan:
            return []
        kept = self._reserve_chunks(plan)
        if not kept:
            return []
        with self._span("serve.batcher.pack", lane=self._prefill_lane) as sp:
            n_rows = bucket_pow2(len(kept), 1, self.num_slots)
            packed_rows = (
                n_rows < self.num_slots
                and self._prefill_rows_dispatch is not None
            )
            if packed_rows:
                # the row executables exist at the top chunk bucket only
                bucket = self._chunk_max
                step = self._prefill_rows_dispatch(n_rows)
            else:
                n_rows = self.num_slots
                bucket = bucket_pow2(
                    max(c for _, _, c in kept), CHUNK_BUCKET_MIN,
                    self.prefill_chunk,
                )
                step = self._prefill_dispatch(bucket)  # cold: slot-hit usually
            self._note_chunk_bucket(bucket)
            tok = np.zeros((n_rows, bucket), np.int32)
            length = np.zeros(n_rows, np.int32)
            # idle rows pad with each slot's own shard-null page so padded
            # writes stay shard-local under a data-parallel mesh (§16); a
            # packed call only runs on an unsharded pool, one null page
            bt = np.repeat(
                self._null_fill[:n_rows, None], self.max_pages_per_req, 1
            )
            rows = np.full(n_rows, self.num_slots, np.int32)  # pad: dropped
            for i, (s, cursor, chunk) in enumerate(kept):
                r = i if packed_rows else s
                prompt = self._slots[s].effective_prompt
                tok[r, :chunk] = prompt[cursor : cursor + chunk]
                length[r] = chunk
                rows[r] = s
                table = self._pf_tables[s] if self.disagg else self._tables[s]
                bt[r, : table.num_pages] = table.pages
            self.stats.prefill_rows += len(kept)
            self.stats.prefill_rows_padded += n_rows
            sp.note(bucket=bucket, rows=n_rows)
        # a chunk that cannot flip any slot this step leaves every plan
        # input untouched: under async it issues and parks like a chainable
        # decode (DESIGN.md §13) — its keys must then chain through the
        # mirror, because a parked predecessor's key split only exists on
        # device until its commit. Disaggregated chunks never park: their
        # flip bridges two pools and is committed eagerly.
        park = (
            self.async_steps
            and not self._spec_on
            and not self.disagg
            and not any(
                cursor + chunk >= len(self._slots[s].effective_prompt)
                for s, cursor, chunk in kept
            )
        )
        # chunk-lane inputs are per-chunk data (tokens, cursors, packed
        # tables, lengths, split keys) — uploaded raw, counted honestly;
        # idle rows carry length 0 + null tables (writes hit the null page)
        self.stats.prefill_calls += 1
        self.stats.note_lane(self._prefill_lane)
        with self._span("serve.dispatch.upload", lane=self._prefill_lane):
            if self.disagg:
                # chunk-plan inputs go host->prefill-slice in ONE hop (the
                # mirror's device arrays are committed to the decode slice,
                # and a plain upload would land on the default device and
                # bounce); slow-moving per-slot sampling state is staged on
                # the slice and re-uploaded only when its host value
                # changes (§17)
                host = (tok, np.array(self._pos, np.int32), length, bt,
                        self._keys) + ((rows,) if packed_rows else ())
                self.stats.h2d_uploads += len(host)
                tok_dev, start_dev, length_dev, bt_dev, keys_dev, *rows_dev = (
                    self._pf_put(host)
                )
                temps_dev = self._pf_stage("temps", self._temps)
                greedy_dev = self._pf_stage("greedy", self._greedy)
            else:
                self.stats.h2d_uploads += (4 if park else 5) + packed_rows
                tok_dev = jnp.asarray(tok)
                start_dev = jnp.asarray(np.array(self._pos, np.int32))
                length_dev = jnp.asarray(length)
                bt_dev = jnp.asarray(bt)
                rows_dev = [jnp.asarray(rows)] if packed_rows else []
                temps_dev = self._mirror.get("temps", self._temps)
                greedy_dev = self._mirror.get("greedy", self._greedy)
                keys_dev = (
                    self._mirror.get("keys", self._keys)
                    if park
                    else jnp.asarray(self._keys)
                )
        cache_in = self._pf_cache if self.disagg else self._cache
        t0_ns = time.perf_counter_ns()
        nxt, cache_out, new_keys = self._issue(
            self._prefill_lane, t0_ns, step, cache_in, tok_dev, start_dev,
            bt_dev, length_dev, temps_dev, greedy_dev, keys_dev, *rows_dev,
        )
        if self.disagg:
            self._pf_cache = cache_out
        else:
            self._cache = cache_out
        # draft mirror (DESIGN.md §11): the draft stack ingests the same
        # chunk windows into its dense per-slot cache so its KV tracks the
        # committed stream before the draft lane runs; the inputs are the
        # target call's device arrays (no second upload). Prefix-cache-
        # adopted prompt pages never pass through here, so the draft's view
        # of a shared prefix stays cold — acceptance degrades on those
        # requests, correctness never does (the verify lane guards every
        # token).
        if self._spec_on and self._draft_prefill_dispatch is not None:
            dstep = self._draft_prefill_dispatch(bucket)
            self.stats.note_lane("drp")
            t0_ns = time.perf_counter_ns()
            _, self._draft_cache, _ = self._issue(
                "drp", t0_ns, dstep, self._draft_cache, tok_dev, start_dev,
                length_dev, temps_dev, greedy_dev, keys_dev,
            )
        with self._span("serve.dispatch.issue", lane="pack"):
            packed = pack_step_d2h(nxt, new_keys)
        if park:
            # no host read: bookkeeping runs now (the chunk plan is final),
            # the split keys chain on device, and the packed pull parks
            # until the pipeline's next emit boundary
            with self._span("serve.batcher.commit", flips=0):
                self._mirror.put("keys", new_keys)
                for s, cursor, chunk in kept:
                    self._chunk_slots.add(s)
                    cursor += chunk
                    self._cursor[s] = cursor
                    self._pos[s] = cursor
                    self._tables[s].num_tokens = cursor
                    self.stats.prompt_tokens += chunk
                    self.stats.prefill_chunks += 1
                self._mirror.touch("pos")
                self._queue_prefill(packed)
            return []
        # one packed transfer for the chunk's host-bound outputs (§13)
        p = self._pull(packed)
        with self._span("serve.batcher.commit") as sp:
            finished = self._commit_chunks(kept, p, now)
            sp.note(flips=len(self._flip_slots))
        return finished

    def _reserve_chunks(self, plan: list) -> list:
        """Reserve every planned chunk's pages before the batched call
        (reclaim -> preempt-self on OOM); returns the planned chunks whose
        slots survived. ``_reclaim_pages`` may preempt *other* slots —
        including ones planned earlier in this loop — so the survivors are
        re-validated afterwards."""
        with self._span("serve.cache.reserve") as sp:
            ev0 = self.pool.stats.prefix_evictions
            pre0 = self.stats.preemptions
            needed = 0
            for s, cursor, chunk in plan:
                req = self._slots[s]
                if (
                    req is None
                    or not self._active[s]
                    or not self._prefilling[s]
                ):
                    continue  # a victim of an earlier reservation's preemption
                table = self._pf_tables[s] if self.disagg else self._tables[s]
                need = (
                    table.page_index(cursor + chunk - 1) + 1 - table.num_pages
                )
                if need <= 0:
                    continue
                needed += need
                if self.disagg:
                    # prefill-slice growth draws on the prefill pool alone
                    # (§17): it has no trie to evict and no decode tenants
                    # to preempt, so a dry pool preempts the requester
                    if not table.ensure_capacity(cursor + chunk - 1):
                        self._preempt_slot(s)
                    continue
                self._tables_changed()
                if not self._reclaim_pages(
                    need, req.priority, table.shard
                ) or not table.ensure_capacity(cursor + chunk - 1):
                    self._preempt_slot(s)  # can't grow: preempt the requester
            kept = [
                (s, cursor, chunk)
                for s, cursor, chunk in plan
                if self._slots[s] is not None
                and self._active[s]
                and self._prefilling[s]
            ]
            sp.note(
                pages=needed,
                evicted=self.pool.stats.prefix_evictions - ev0,
                preempted=self.stats.preemptions - pre0,
            )
        return kept

    def _commit_chunks(self, kept: list, p, now: float) -> list[Request]:
        """Prefill bookkeeping after the chunk call's pull: cursors, split
        keys, and at each flip the migration (split topology), the prefix
        publish of the prompt's full pages and the first token."""
        nxt_host = p[:, 0]
        nk = p[:, 1:3].astype(np.uint32)
        finished: list[Request] = []
        for s, cursor, chunk in kept:
            req = self._slots[s]
            prompt = req.effective_prompt
            table = self._tables[s]
            self._keys[s] = nk[s]
            self._chunk_slots.add(s)
            cursor += chunk
            self._cursor[s] = cursor
            self._pos[s] = cursor
            if self.disagg:
                # the prefill-slice shadow tracks the written frontier; the
                # decode-side base table catches up at the flip's migration
                self._pf_tables[s].num_tokens = cursor
            else:
                table.num_tokens = cursor
            self.stats.prompt_tokens += chunk
            self.stats.prefill_chunks += 1
            if cursor >= len(prompt):  # flip: prompt done, prime generation
                if self.disagg and not self._migrate_back(s):
                    continue  # the decode pool balked: slot was preempted
                # the packed decode table zeroed this slot's row while it
                # was prefilling; it must carry the real pages from the
                # next step on
                self._tables_changed()
                # publish the prompt's full pages for sharing at the flip
                self._publish(prompt, table)
                self._prompt_cached[s] = True
                self._prime_first_token(s, req, int(nxt_host[s]), now)
                if req.done:  # new_tokens == 1: the primed token was last
                    req.t_done = now
                    self._note_finish(req, now)
                    table.release()
                    self._tables[s] = None
                    self._slots[s] = None
                    self._active[s] = False
                    self._tables_changed()
                    self.stats.finished += 1
                    finished.append(req)
        self._mirror.touch("pos", "keys")
        return finished

    def _publish(self, prompt, table) -> None:
        """Insert a written prompt's full pages into the prefix cache."""
        full = len(prompt) // self.pool.page_size
        if full > 0:
            with self._span("serve.cache.publish", pages=full):
                self.prefix.insert(prompt, table.pages[:full])

    # -------------------------------------------------------------- hot path
    def _step_impl(self, now: float = 0.0) -> list[Request]:
        """One multi-lane step for all slots (entered through the mixin's
        pipelined ``step`` wrapper); returns finished requests.

        Cold path first (the lane plan, one prefill chunk, page upkeep,
        bucket dispatch — mostly no-ops on the vast majority of steps),
        then the step's decode-side lane: either the draft/verify pair
        (speculation planned) or a single direct decode-executable call.
        """
        if not self._active.any():
            return []
        finished: list[Request] = []
        self._chunk_slots = set()
        self._flip_slots = set()
        plan = self._plan_step()
        if self.prefill_chunk > 0 and (self._prefilling & self._active).any():
            finished.extend(self._prefill_step(now, plan.chunk_budget))
        self._page_upkeep(plan.k)
        decoding = self._active & ~self._prefilling
        if not decoding.any():
            self.stats.steps += 1  # prefill-only step
            self._count_slot_steps(decoding)
            return finished
        if plan.k > 0:  # draft/verify lanes replace the decode lane
            finished.extend(self._spec_step(now, plan.k, decoding))
            self.stats.steps += 1
            self._count_prefilling_slot_steps()
            return finished
        finished.extend(self._decode_lane_step(now, decoding))
        return finished

    def _pre_issue_fast(self) -> None:
        """Run-ahead cold path: decode write windows still need writable
        pages (growth/COW) before the next step issues. ``self._pos`` is
        the predictive frontier, which is exactly the position the issued
        step writes; a preemption here discards a pending token the
        restarted request would discard anyway."""
        self._page_upkeep(0)

    def _decode_lane_step(self, now: float, decoding) -> list[Request]:
        """Paged decode lane: capacity-bucket dispatch, packed block
        tables, one direct executable call. Synchronous mode pulls and
        emits immediately; async parks the step (DESIGN.md §13)."""
        with self._span("serve.batcher.pack", lane=self._decode_lane) as sp:
            bucket = bucket_pow2(
                max(
                    [t.num_pages for s, t in enumerate(self._tables)
                     if t is not None and decoding[s]] or [1]
                ) or 1,
                1,
                self.max_pages_per_req,
            )
            if bucket != self._pages_bucket:
                self.stats.bucket_crossings += 1
                self._pages_bucket = bucket
                self._tables_changed()  # table width changed
            step = self._dispatch(bucket)  # cold: slot-hit unless it moved
            if self._bt_dirty:
                # pad with each slot's shard-null page (all zeros on a
                # single-shard pool — the historical NULL_PAGE fill)
                bt = np.repeat(self._null_fill[:, None], bucket, 1)
                for s, table in enumerate(self._tables):
                    if table is not None and decoding[s]:
                        bt[s, : table.num_pages] = table.pages
                self._bt_host = bt
                self._bt_dirty = False
                self._mirror.touch("bt")
            sp.note(bucket=bucket)
        t0_ns = time.perf_counter_ns()
        with self._span("serve.dispatch.upload", lane=self._decode_lane) as sp:
            m = self._mirror
            u0 = self.stats.h2d_uploads
            args = (
                m.get("tok", self._tok),
                m.get("pos", self._pos),
                m.get("bt", self._bt_host),
                m.get("active", decoding),
                m.get("temps", self._temps),
                m.get("greedy", self._greedy),
                m.get("keys", self._keys),
            )
            sp.note(uploads=self.stats.h2d_uploads - u0)
        out = self._issue(self._decode_lane, t0_ns, step, self._cache, *args)
        nxt, self._cache, pos, keys = out[:4]
        self.stats.decode_steps += 1
        self.stats.note_lane(self._decode_lane)
        self.stats.steps += 1
        self._mirror.put("pos", pos)
        self._mirror.put("keys", keys)
        if self.async_steps and len(out) == 6:
            self._mirror.put("tok", out[4])  # bundle-staged chained input
            self._queue_decode(out[5], decoding)
            return []
        self._mirror.put("tok", nxt[:, None])  # device reshape, no upload
        nxt_host = self._pull(nxt)  # blocks until the device step is done
        self._pos = np.array(self._pull(pos), np.int32)
        self._keys = np.array(self._pull(keys), np.uint32)
        return self._emit_decode(nxt_host, self._pos, now)

    def _emit_decode(
        self, nxt_host, pos_host, now: float
    ) -> list[Request]:
        """The paged decode step's emit boundary. ``pos_host`` is the
        committing step's position frontier — under run-ahead the live
        ``self._pos`` is already one step further, so tables sync to the
        record's own positions, never the live array."""
        with self._span("serve.batcher.emit") as sp:
            t0 = self.stats.tokens
            finished = self._emit_tokens(nxt_host, pos_host, now)
            sp.note(tokens=self.stats.tokens - t0, finished=len(finished))
        return finished

    def _emit_tokens(
        self, nxt_host, pos_host, now: float
    ) -> list[Request]:
        finished: list[Request] = []
        if self._faults is not None:
            nxt_host, _ = self._inject_step_output(nxt_host)
        self._tok = np.asarray(nxt_host)[:, None].astype(np.int32)
        self._count_prefilling_slot_steps()
        for s, req in enumerate(self._slots):
            if req is None or not self._active[s]:
                self.stats.idle_slot_steps += 1
                continue
            if self._prefilling[s]:
                continue  # chunked lane owns this slot (ticked above)
            self.stats.active_slot_steps += 1
            table = self._tables[s]
            table.num_tokens = int(pos_host[s])
            prompt = req.effective_prompt
            if self._cursor[s] + 1 < len(prompt):
                # token-by-token fallback (prefill_chunk == 0): feed the
                # next prompt token, drop the sample
                self._cursor[s] += 1
                self._tok[s, 0] = prompt[self._cursor[s]]
                self._mirror.touch("tok")
                self.stats.prompt_tokens += 1
                continue
            if not self._prompt_cached[s]:
                # prompt fully written: publish its full pages for sharing
                self._publish(prompt, table)
                self._prompt_cached[s] = True
            if int(nxt_host[s]) < 0:
                # NaN guard (§15): invalid token id — quarantine this slot
                self._quarantine_slot(s, now)
                continue
            req.tokens.append(int(nxt_host[s]))
            self._note_tokens(req, now)
            self.stats.tokens += 1
            if req.done:
                req.t_done = now
                self._note_finish(req, now)
                finished.append(req)
                table.release()
                self._tables[s] = None
                self._slots[s] = None
                self._active[s] = False
                self._mirror.touch("active")
                self._tables_changed()
                self.stats.finished += 1
        return finished

    # ---------------------------------------------------- draft/verify lanes
    def _verify_call(self, k: int, tok, lengths):
        """Paged verify executable ``("vf", slots, k)``: the shared
        ``_spec_step``/``_apply_verify`` core lives in ``_MultiLaneMixin``;
        this hook adds the full-width packed block tables (rebuilt only
        when some table changed — ``_bt_full_dirty``). ``_page_upkeep(k)``
        already reserved and COW'd every page in the verify windows; the
        draft keeps a dense cache (the truncated stack is cheap enough not
        to page)."""
        if self._bt_full_dirty:  # full-width packed tables (all live slots)
            # per-slot shard-null padding (zeros on a single-shard pool)
            bt = np.repeat(
                self._null_fill[:, None], self.max_pages_per_req, 1
            )
            for s, table in enumerate(self._tables):
                if table is not None:
                    bt[s, : table.num_pages] = table.pages
            self._bt_full = bt
            self._bt_full_dirty = False
            self._mirror.touch("bt_full")
        step = self._verify_dispatch(k)  # cold: slot-hit unless k moved
        self.stats.h2d_uploads += 2  # per-step window data (tok pack, len)
        rows, nxt0, self._cache, keys = step(
            self._cache,
            jnp.asarray(tok),
            self._mirror.get("pos", self._pos),
            self._mirror.get("bt_full", self._bt_full),
            jnp.asarray(lengths),
            self._mirror.get("temps", self._temps),
            self._mirror.get("greedy", self._greedy),
            self._mirror.get("keys", self._keys),
        )
        return rows, nxt0, keys

    def _before_emit(self, s: int, req: Request) -> None:
        """Prompt fully written: publish its full pages for sharing (the
        verify-lane twin of the decode lane's flip-less publication)."""
        if not self._prompt_cached[s]:
            self._publish(req.effective_prompt, self._tables[s])
            self._prompt_cached[s] = True

    def _after_commit(self, s: int, req: Request) -> None:
        """Rollback as data, without churn: sync the table to the new
        frontier and release only pages the *next* verify window can no
        longer reach (``pos .. pos + min(spec_k, remaining - 1)``) — in
        steady state that window covers everything this step wrote, so
        trim fires as the tail drains rather than thrashing alloc/free and
        packed-table rebuilds every boundary-crossing step. Rejected-tail
        KV inside the kept pages is overwritten by the next committed
        write; no code ever branches on it."""
        table = self._tables[s]
        pos = int(self._pos[s])
        table.num_tokens = pos
        horizon = pos + min(
            self.spec_k, max(req.new_tokens - len(req.tokens) - 1, 0)
        )
        if table.trim(table.page_index(horizon) + 1):
            self._tables_changed()

    def _release_spec_slot(self, s: int) -> None:
        self._drop_pf_state(s)
        self._tables[s].release()
        self._tables[s] = None
        self._slots[s] = None
        self._active[s] = False
        self._tables_changed()

    def _release_slot(self, s: int) -> None:
        """Cancel/quarantine release for paged storage: the slot's block
        table returns its pages to the pool before the host state clears
        (the §15 'release pages, trim block tables' contract)."""
        self._drop_pf_state(s)
        if self._tables[s] is not None:
            self._tables[s].release()
            self._tables[s] = None
        super()._release_slot(s)
        self._tables_changed()


# ------------------------------------------------------------------ reports
def latency_report(
    requests: Sequence[Request], batcher=None, registry=None
) -> dict:
    """p50/p95/p99 latency + TTFT + throughput over finished requests.

    With a ``batcher``, the report also carries the multi-lane telemetry
    (DESIGN.md §11): per-lane step counts, accepted-tokens-per-target-step,
    and acceptance-rate percentiles over the per-slot verify samples — the
    numbers ``launch/serve.py`` prints for any engine.

    ``registry`` (a :class:`~repro.core.telemetry.MetricsRegistry`) covers the
    batcher-less burst path: per-lane call counts are derived from the same
    ``lane_calls_total`` family the batchers feed, so burst and continuous
    engines report through one namespace (DESIGN.md §14)."""
    done = [r for r in requests if r.t_done is not None]
    lanes: dict = {}
    if batcher is None and registry is not None:
        calls = registry.labeled_values("lane_calls_total", "lane")
        if calls:
            lanes["lane_calls"] = calls
    if batcher is not None:
        st = batcher.stats
        lanes["lane_steps"] = st.lane_steps
        # per-spec-name executable calls (DESIGN.md §12): grouped under the
        # registry's lane names, so reports and dispatch keys share one
        # namespace ("cbp" and "cb" are different lanes, and read as such)
        lanes["lane_calls"] = dict(st.lane_calls)
        # step-pipeline telemetry (DESIGN.md §13): how much host work ran
        # concurrently with (rather than serialised against) the device
        busy = st.host_plan_ms + st.device_wait_ms
        lanes["pipeline"] = {
            "async_steps": bool(getattr(batcher, "async_steps", False)),
            "host_plan_ms": round(st.host_plan_ms, 3),
            "device_wait_ms": round(st.device_wait_ms, 3),
            "overlap_ratio": round(st.host_plan_ms / busy, 4) if busy else 0.0,
            "inflight_depth": st.inflight_depth,
            "d2h_transfers": st.d2h_transfers,
        }
        if st.target_steps:
            lanes["tokens_per_target_step"] = round(
                st.tokens / st.target_steps, 3
            )
        if st.drafted_tokens:
            lanes["spec"] = {
                "k": batcher.spec_k,
                "drafted_tokens": st.drafted_tokens,
                "accepted_tokens": st.accepted_tokens,
                "acceptance_rate": round(
                    st.accepted_tokens / st.drafted_tokens, 4
                ),
                "k_bucket_crossings": st.k_bucket_crossings,
            }
            acc = np.array(batcher.accept_samples)
            if len(acc):
                lanes["spec"]["acceptance_p50"] = float(
                    np.percentile(acc, 50)
                )
                lanes["spec"]["acceptance_p95"] = float(
                    np.percentile(acc, 95)
                )
                lanes["spec"]["acceptance_p99"] = float(
                    np.percentile(acc, 99)
                )
    # Robustness block (DESIGN.md §15): shed/cancel/fault/degradation
    # accounting, derived from the registry the hardened loop feeds. A
    # clean un-hardened run has every family empty, so the block is
    # omitted and pre-§15 reports are byte-identical.
    reg = registry
    if reg is None and batcher is not None:
        reg = batcher.telemetry.registry
    if reg is not None:
        robust: dict = {}
        for key, family, label in (
            ("shed", "admission_shed_total", "reason"),
            ("cancelled", "requests_cancelled_total", "reason"),
            ("failed", "requests_failed_total", "site"),
            ("faults_injected", "faults_injected_total", "site"),
            ("faults_detected", "faults_detected_total", "site"),
            ("faults_contained", "faults_contained_total", "site"),
            ("rung_dwell_s", "degrade_rung_dwell_s", "rung"),
            ("degrade_transitions", "degrade_transitions_total",
             "direction"),
        ):
            vals = reg.labeled_values(family, label)
            if vals:
                robust[key] = (
                    {k: round(v, 3) for k, v in vals.items()}
                    if key == "rung_dwell_s" else
                    {k: int(v) for k, v in vals.items()}
                )
        stragglers = reg.value("step_stragglers_total")
        if stragglers:
            robust["stragglers"] = int(stragglers)
        if batcher is not None and batcher.stats.deadline_missed:
            robust["deadline_missed"] = batcher.stats.deadline_missed
        if robust:
            lanes["robustness"] = robust
    if not done:
        return {"finished": 0, **lanes}
    lat = np.array([r.latency_s for r in done])
    toks = sum(len(r.tokens) for r in done)
    span = max(r.t_done for r in done) - min(r.arrival_s for r in done)
    report = {
        "finished": len(done),
        "tokens": toks,
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p95_ms": float(np.percentile(lat, 95) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "mean_ms": float(lat.mean() * 1e3),
        "tok_per_s": toks / span if span > 0 else float("inf"),
        "span_s": float(span),
        **lanes,
    }
    ttft = np.array(
        [r.t_first - r.arrival_s for r in done if r.t_first is not None]
    )
    if len(ttft):  # time-to-first-token: the prompt-ingestion SLO metric
        report["ttft_p50_ms"] = float(np.percentile(ttft, 50) * 1e3)
        report["ttft_p95_ms"] = float(np.percentile(ttft, 95) * 1e3)
        report["ttft_p99_ms"] = float(np.percentile(ttft, 99) * 1e3)
        report["ttft_mean_ms"] = float(ttft.mean() * 1e3)
    return report
