"""Request batching over the exported decode artifact (VERDICT r4 #7).

Reference: paddle/fluid/inference/api/analysis_predictor.cc — the
reference's inference engine exists to serve under concurrency
(zero-copy tensors, predictor pools, thread-safe clone). The rebuild's
deployment artifact is the StableHLO decode program from
`models.gpt2.export_generator` (fixed [B, prompt_len] batch); this
module adds the piece that turns the measured W8A16/int8-KV decode wins
into served throughput: a thread-safe request queue and a batcher loop
that assembles dynamic batches, pads the tail, runs the program, and
fans results back out to per-request futures with latency accounting.

    server = GenerationServer(jit.load(prefix), pad_token_id=0)
    server.start()
    fut = server.submit([12, 53, 99])        # any length <= prompt_len
    tokens = fut.result()                    # [prompt_len + new] int32
    print(server.stats())                    # throughput + p50/p99
    server.stop()

Batching policy: wait for the first request, then gather more until the
program's batch size B is full or `max_wait_ms` has elapsed; pad the
remainder by repeating the first row (a full-size program run costs the
same regardless — decode time is batch-invariant at fixed B).
"""
from __future__ import annotations

import itertools
import math
from collections import deque
import os
import statistics
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass

import numpy as np

from ..observability import compile_tracker as _compile_tracker
from ..observability import flight_recorder as _flight
from ..observability import gc_tracker as _gc_tracker
from ..observability import log as _obs_log
from ..observability import metrics as _metrics
from ..observability import tracing as _tracing
from ..observability.attribution import (ResourceLedger,
                                         disabled_attribution_stats)
from ..observability.capacity import PressureSignals
from ..observability.slo import SLO, SLOEngine
from ..observability.trace_context import TraceContext
from ..reliability import (AdmissionShed, QuarantinedRequest,
                           RecoveryPolicy, RequestTimeout,
                           SessionJournal, resolve_fault_plan)
from ..sampling import SamplingParams
from .kv_cache import BlockPoolExhausted
from .kv_tier import payload_nbytes as _payload_nbytes

_logger = _obs_log.get_logger(__name__)

ENV_METRICS_PORT = "PADDLE_TPU_METRICS_PORT"

# Shared serving telemetry (ISSUE 2): near-zero cost while
# PADDLE_TPU_TELEMETRY is off — every update is one bool check.
_m_queue_depth = _metrics.gauge(
    "serving_queue_depth", "requests waiting for a batch/slot",
    labelnames=("server",))
_m_slots_busy = _metrics.gauge(
    "serving_slots_busy", "occupied decode slots (paged) / in-flight "
    "batch rows (dense)", labelnames=("server",))
_m_requests_done = _metrics.counter(
    "serving_requests_total", "requests completed",
    labelnames=("server",))
_m_request_latency = _metrics.histogram(
    "serving_request_latency_seconds", "submit -> future resolved",
    labelnames=("server",))
_m_ttft = _metrics.histogram(
    "serving_ttft_seconds", "submit -> first generated token (paged)")
_m_slot_releases = _metrics.counter(
    "serving_slot_releases_total", "paged slots freed, by why the "
    "request finished", labelnames=("reason",))
_m_slot_refills = _metrics.counter(
    "serving_slot_refills_total",
    "idle paged slots refilled from the queue mid-flight")
_m_itl = _metrics.histogram(
    "paddle_tpu_serving_itl_seconds",
    "inter-token latency per generated token (decode-dispatch gap "
    "amortized over the tokens it emitted, paged) — the metric the "
    "prefill_chunk_tokens knob is tuned against")
_m_prefill_dispatches = _metrics.counter(
    "serving_prefill_dispatches_total",
    "packed ragged prefill chunk dispatches (paged); an admission "
    "burst of N requests costs O(1) of these per decode round, not N")
_m_decode_stall = _metrics.histogram(
    "serving_decode_stall_seconds",
    "time in-flight decode slots stalled while a packed prefill chunk "
    "dispatch ran (bounded by the chunk token budget)")
_m_stop_reason = _metrics.counter(
    "serving_stop_reason_total",
    "finished requests by why generation stopped "
    "(eos | stop_token | stop_string | budget)",
    labelnames=("server", "reason"))
_m_sampling_fast = _metrics.counter(
    "serving_sampling_fast_path_dispatches_total",
    "decode dispatches that took the all-greedy fast path (no resident "
    "request samples: bare argmax, no sort/PRNG cost)")
_m_sampling_sampled = _metrics.counter(
    "serving_sampling_sampled_dispatches_total",
    "decode dispatches through the full vectorized sampling pipeline "
    "(>= 1 resident sampled request)")
# Speculative decoding (round 11): proposal/acceptance accounting.
_m_spec_proposed = _metrics.counter(
    "serving_spec_proposed_tokens_total",
    "draft tokens proposed by the drafter across all slots/rounds")
_m_spec_accepted = _metrics.counter(
    "serving_spec_accepted_tokens_total",
    "proposed draft tokens the packed verification accepted")
_m_spec_rolled_back = _metrics.counter(
    "serving_spec_rolled_back_tokens_total",
    "rejected draft positions rolled back out of the paged cache "
    "(PagedKVCache.truncate_seq)")
_m_spec_verify = _metrics.counter(
    "serving_spec_verify_dispatches_total",
    "packed verification dispatches (one per round scores every "
    "speculating slot's drafts)")
_m_spec_accept_rate = _metrics.histogram(
    "serving_spec_acceptance_rate",
    "per-slot per-round accepted/proposed draft fraction",
    buckets=(0.1, 0.25, 0.5, 0.75, 0.9, 1.0))
# Front door (round 12): preemption, SLO lanes, multi-tenant queueing.
_m_preemptions = _metrics.counter(
    "serving_preemptions_total",
    "slots evicted mid-flight to make room for a higher-priority "
    "admission (the victim's live K/V is published through the "
    "prefix-cache index when caching is on, then the request requeues)",
    labelnames=("reason",))
_m_resumes = _metrics.counter(
    "serving_preempt_resumes_total",
    "preempted requests re-admitted (resume = re-prefill of "
    "prompt + generated-so-far, served from the prefix cache when the "
    "swapped-out blocks survived retention)")
_m_preempt_cached = _metrics.counter(
    "serving_preempt_cached_tokens_total",
    "tokens of victim K/V published into the prefix-cache index at "
    "swap-out (the work preemption preserves instead of recomputing)")
_m_deadline_miss = _metrics.counter(
    "serving_deadline_misses_total",
    "requests whose first token landed after their TTFT deadline",
    labelnames=("lane",))
_m_deadline_overage = _metrics.histogram(
    "serving_deadline_overage_seconds",
    "by how much a missed TTFT deadline was missed (first token time "
    "minus deadline; only observed on misses)",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0))
# Operations plane (ISSUE 10): goodput + health accounting.
_m_decoded = _metrics.counter(
    "serving_tokens_decoded_total",
    "generated-token positions computed on device (decode steps, "
    "verify positions, prefill token-0 samples, and preempt-resume "
    "re-prefill of already-generated tokens)")
_m_replayed = _metrics.counter(
    "serving_tokens_replayed_total",
    "decoded-token positions whose work was wasted: multi-step "
    "post-stop discards, verify positions truncated by a stop, and "
    "preempt-resume re-prefill of already-generated tokens")
_m_goodput = _metrics.gauge(
    "serving_goodput_ratio",
    "emitted tokens / decoded-token positions for the current stats "
    "window (1.0 = every device token reached a client; speculation "
    "rollback, multi-step overrun and preemption replay lower it)")
_m_engine_exc = _metrics.counter(
    "serving_engine_exceptions_total",
    "engine dispatch exceptions fanned out to request futures, by "
    "dispatch kind", labelnames=("where",))
# One-kernel round (r16): dispatch-per-round + async overlap accounting.
_m_round_dispatches = _metrics.histogram(
    "serving_dispatches_per_round",
    "attention dispatches one scheduler round issued (split path: "
    "chunk prefill, decode and verify can each fire; unified round: "
    "always 1)", buckets=(1.0, 2.0, 3.0, 4.0))
_m_round_overlap = _metrics.histogram(
    "serving_round_overlap_seconds",
    "host plan+dispatch time of round N+1 hidden behind round N's "
    "device execution (async double-buffered engine loop; only "
    "observed while a round was in flight)",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.5))
# Reliability (r17): fault injection, recovery ladder, quarantine,
# per-request timeouts.
_m_fault_injected = _metrics.counter(
    "serving_fault_injected_total",
    "deterministic FaultPlan faults fired at an engine seam "
    "(injection is opt-in: ctor fault_plan= or PADDLE_TPU_FAULT_PLAN)",
    labelnames=("seam",))
_m_dispatch_retries = _metrics.counter(
    "serving_dispatch_retries_total",
    "failing dispatches absorbed by the recovery ladder: implicated "
    "requests were snapshotted and requeued for retry instead of "
    "having their futures failed")
_m_quarantined = _metrics.counter(
    "serving_requests_quarantined_total",
    "requests failed by the recovery ladder after implicating "
    "themselves in quarantine_after consecutive dispatch failures "
    "(co-resident requests resume token-identically)")
_m_recoveries = _metrics.counter(
    "serving_recoveries_total",
    "clean recoveries: first successful dispatch after >= 1 dispatch "
    "failure — health returns degraded -> ok")
_m_timeouts = _metrics.counter(
    "serving_request_timeouts_total",
    "requests cancelled by their per-request timeout_s (queued or "
    "resident; the slot and its blocks are freed, the stream "
    "terminates with reason='timeout')")
# Memory-flat long-context round: sequence-parallel attention byte
# accounting + KV-tier prefetch-ahead.
_m_sp_peak_bytes = _metrics.gauge(
    "serving_sp_attention_bytes_peak",
    "peak per-shard cross-shard fresh-K/V bytes any packed-prefill "
    "dispatch of this server materialized (analytic accounting from "
    "serving_dist.sp_attention — linear in chunk length for "
    "'allgather', flat O(block) for 'ring'/'ulysses'; 0 when sp<=1)")
_m_prefetch_issued = _metrics.counter(
    "kv_tier_prefetch_issued_total",
    "host-tier blocks promoted AHEAD of admission by the prefetch "
    "loop, overlapped with the in-flight round's device execution")
_m_prefetch_hit = _metrics.counter(
    "kv_tier_prefetch_hit_total",
    "prefetched tier blocks still device-resident when their request "
    "was admitted — promotion wall time the admission path never paid")
_m_prefetch_wasted = _metrics.counter(
    "kv_tier_prefetch_wasted_total",
    "prefetched tier blocks whose request left the queue unadmitted "
    "(timeout/stop) or that pool pressure reclaimed before admission")
_m_promote_overlap = _metrics.histogram(
    "kv_tier_promote_overlap_seconds",
    "wall time of overlapped (prefetch-ahead) tier promote batches — "
    "host copy time hidden behind device execution instead of being "
    "charged to the admission path",
    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
             0.5))
_req_ids = itertools.count()

STOP_REASONS = ("eos", "stop_token", "stop_string", "budget")

HEALTH_CODES = {"ok": 0.0, "degraded": 1.0, "stalled": 2.0}

#: dispatches whose expert counters `stats()["experts"]["dispatches"]` keeps
EXPERT_RING = 4096
#: what the default loop issues, as `stats()["dispatch_ahead"]` splits
#: `found_idle` and the rounds' `kind` names them
DISPATCH_KINDS = ("decode", "prefill", "verify")
#: rounds whose lengths `stats()["round_phases"]["round_ms"]` is read from
#: (96 s of the fastest cell's 170 rounds a second)
ROUND_RING = 16384
#: the longest rounds that keep their phases (`round_ms["slowest"]`)
SLOWEST_KEPT = 8
# A round stood still, and is logged at WARNING, when it is longer than
# both SLOW_ROUND_MS and SLOW_ROUND_X_MEDIAN times the median of the
# newest SLOW_ROUND_MEDIAN_OF rounds (at least SLOW_ROUND_AFTER of them
# since the reset); at most one line every SLOW_ROUND_LOG_EVERY_S.
SLOW_ROUND_MS = 50.0
SLOW_ROUND_X_MEDIAN = 8
SLOW_ROUND_MEDIAN_OF = 256
SLOW_ROUND_AFTER = 8
SLOW_ROUND_LOG_EVERY_S = 1.0

# What the paged engine's thread does in a round, one vocabulary for
# every loop (split, unified, unified async):
#   admit      taking the lock at the round boundary, host ops,
#              timeouts, admission
#   idle_wait  waiting on the lock with no slot occupied: no work
#   plan       the host arrays of a dispatch: slot scan, chunk packing,
#              sampling args, table growth, the table matrix
#   dispatch   uploading the inputs and the jitted call
#   read_back  np.asarray of the outputs: the host blocked on the device
#   emit       pool swap, per-token callbacks, futures, slot release,
#              latency bookkeeping
# and "other": whatever of the thread's time no phase covers.
ROUND_PHASES = ("admit", "idle_wait", "plan", "dispatch", "read_back",
                "emit")


class _RoundPhases:
    """Where the engine thread's time goes: stats()["round_phases"].

    The engine thread is the only writer. Every phase boundary moves
    the time since the previous boundary to the phase that was open
    (the innermost; "other" when none was), so the phases tile the
    thread's wall time exactly; `dispatches` is counted at the same
    boundary (a `dispatch` phase left without an exception). A round is
    one iteration of the engine's loop that dispatched something;
    `round` numbers them for the life of the engine (the dispatch
    spans' `round` attribute). Every round's `(at_s, ms, kind)` goes to
    a ring of the newest ROUND_RING (`round_ms`: percentiles, by kind);
    the SLOWEST_KEPT longest keep their phases, the seconds the
    collector ran inside them and the programs compiled inside them
    (`round_ms["slowest"]`; its head is `longest_round`); a round in
    which a dispatch landed on a device that had run dry (`starved`)
    adds its phases to `starved_seconds`. Readers and `reset` come from
    other threads: the
    clock is read under the lock, so that a boundary never lies before
    the reset it follows."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stack = []   # open phases, innermost last
        self._t = None     # the last boundary; None: no thread running
        self.round = 0
        self._warned_at = -SLOW_ROUND_LOG_EVERY_S
        self.reset()

    def reset(self):
        with self._lock:
            now = time.perf_counter()
            self._seconds = dict.fromkeys(ROUND_PHASES + ("other",), 0.0)
            self._starved_seconds = dict(self._seconds)
            self._starved_rounds = 0
            self._dispatches = 0
            self._ring = deque(maxlen=ROUND_RING)
            self._slowest = []   # longest first, at most SLOWEST_KEPT
            self._compile_mark = _compile_tracker.mark()
            self._t_reset = now
            self._open_round(now)
            if self._t is not None:
                self._t = now

    def _open_round(self, now):
        self._round = dict.fromkeys(self._seconds, 0.0)
        self._round_t0 = now
        self._kinds = []
        self._starved = False
        self._gc0 = _gc_tracker.seconds()

    def _boundary(self):
        """Caller holds the lock. Returns the boundary's instant."""
        now = time.perf_counter()
        if self._t is not None:
            cur = self._stack[-1] if self._stack else "other"
            self._seconds[cur] += now - self._t
            self._round[cur] += now - self._t
            self._t = now
        return now

    def thread_started(self):
        with self._lock:
            self._t = time.perf_counter()
            self._open_round(self._t)

    def thread_stopped(self):
        with self._lock:
            self._boundary()
            self._t = None
            del self._stack[:]

    def phase(self, name):
        return _Phase(self, name)

    def kind(self, kind):
        """The round in progress holds a dispatch of this kind."""
        with self._lock:
            if kind not in self._kinds:
                self._kinds.append(kind)

    def starved(self):
        """A dispatch of the round in progress landed on a device that
        had run dry."""
        with self._lock:
            self._starved = True

    def close_round(self):
        """Between two iterations of the engine's loop: if the one that
        ended dispatched anything it was a round: its length goes to the
        ring, its phases to `starved_seconds` if it starved the device
        and to `slowest` if it is among the longest; one that stood
        still is logged."""
        slow = None
        with self._lock:
            now = self._boundary()
            if self._kinds:
                ms = (now - self._round_t0) * 1e3
                kind = "+".join(self._kinds)
                if self._starved:
                    self._starved_rounds += 1
                    for k, v in self._round.items():
                        self._starved_seconds[k] += v
                slowest = self._slowest
                among = (len(slowest) < SLOWEST_KEPT
                         or ms > slowest[-1]["ms"])
                stood = (ms > SLOW_ROUND_MS
                         and len(self._ring) >= SLOW_ROUND_AFTER
                         and now - self._warned_at >= SLOW_ROUND_LOG_EVERY_S
                         and ms > SLOW_ROUND_X_MEDIAN * statistics.median(
                             e[1] for e in itertools.islice(
                                 reversed(self._ring), SLOW_ROUND_MEDIAN_OF)))
                if among or stood:
                    rec = {
                        "ms": ms, "at_s": self._round_t0 - self._t_reset,
                        "kind": kind, "round": self.round,
                        "phases_ms": {k: v * 1e3
                                      for k, v in self._round.items()},
                        "gc_ms": (_gc_tracker.seconds() - self._gc0) * 1e3,
                        # read from the compile log only here, for the
                        # few rounds that are kept: an event's `ts` is
                        # the instant its compile ended
                        "compiles": sum(
                            ev["ts"] >= self._round_t0 for ev in
                            _compile_tracker.events_since(
                                self._compile_mark))}
                    if among:
                        slowest.append(rec)
                        slowest.sort(key=lambda r: -r["ms"])
                        del slowest[SLOWEST_KEPT:]
                    if stood:
                        self._warned_at, slow = now, rec
                self._ring.append((self._round_t0 - self._t_reset, ms, kind))
                self.round += 1
            self._open_round(now)
        if slow is not None:
            top = sorted(slow["phases_ms"].items(), key=lambda kv: -kv[1])
            _logger.warning(
                "[slow round] %.1f ms (%s) round %d: %s, gc %.1f, "
                "compiles %d", slow["ms"], slow["kind"], slow["round"],
                ", ".join(f"{k} {v:.1f}" for k, v in top[:2]),
                slow["gc_ms"], slow["compiles"])

    def snapshot(self):
        with self._lock:
            seconds = dict(self._seconds)
            if self._t is not None:  # the phase open right now
                cur = self._stack[-1] if self._stack else "other"
                seconds[cur] += time.perf_counter() - self._t
            slowest = [dict(r, phases_ms=dict(r["phases_ms"]))
                       for r in self._slowest]
            ring = list(self._ring)
            out = {"seconds": seconds, "dispatches": self._dispatches,
                   "starved_seconds": dict(self._starved_seconds),
                   "starved_rounds": self._starved_rounds}
        # the ring is sorted outside the lock: the engine thread takes
        # it at every boundary
        by_kind = {}
        for _at, ms, kind in ring:
            by_kind.setdefault(kind, []).append(ms)
        out["round_ms"] = dict(
            _percentiles([e[1] for e in ring]),
            by_kind={k: _percentiles(v) for k, v in sorted(by_kind.items())},
            slowest=slowest)
        out["longest_round"] = dict(slowest[0]) if slowest else {
            "ms": 0.0, "at_s": 0.0, "kind": "", "round": None,
            "phases_ms": dict.fromkeys(seconds, 0.0), "gc_ms": 0.0,
            "compiles": 0}
        return out


def _percentiles(ms):
    """`{"count", "p50_ms", "p99_ms"}` of a list of round lengths, by
    the rank `stats()` reads its latencies at."""
    ms = sorted(ms)
    n = len(ms)
    return {"count": n,
            "p50_ms": ms[min(n - 1, int(0.50 * n))] if n else 0.0,
            "p99_ms": ms[min(n - 1, int(0.99 * n))] if n else 0.0}


class _Phase:
    """One open phase: the `pt:<phase>` span plus the accounting."""

    __slots__ = ("_clock", "_name", "_span")

    def __init__(self, clock, name):
        self._clock, self._name = clock, name

    def __enter__(self):
        clock = self._clock
        with clock._lock:
            clock._boundary()
            clock._stack.append(self._name)
        # the span opens inside the time it names, and closes inside it
        self._span = _tracing.span(self._name)
        self._span.__enter__()

    def __exit__(self, exc_type, exc, tb):
        self._span.__exit__(exc_type, exc, tb)
        clock = self._clock
        with clock._lock:
            clock._boundary()
            if clock._stack:
                clock._stack.pop()
            if exc_type is None and self._name == "dispatch":
                clock._dispatches += 1
        return False


@dataclass
class RequestMeta:
    """Scheduling metadata the front-door scheduler reads (round 12).

    lane: SLO lane name ("interactive" = TTFT-sensitive, "batch" =
        throughput). The engine itself is lane-agnostic — lanes only
        mean something to the installed scheduler policy.
    tenant: fair-share / rate-limit accounting bucket.
    deadline_s: relative TTFT deadline in SECONDS from submit; the
        engine counts (never enforces) misses at first-token time.
    cost: tokens the tenant's rate bucket is charged at admission
        (conventionally prompt_len + token budget)."""
    lane: str = "interactive"
    tenant: str = "default"
    deadline_s: float | None = None
    cost: int = 0


@dataclass
class _Req:
    ids: np.ndarray
    future: Future
    t_submit: float
    padded: bool = False
    rid: str = ""
    ttft: float | None = None
    sampling: SamplingParams | None = None
    seed: int = 0
    # front door (round 12): scheduling metadata, streaming callback,
    # and preemption resume state. gen0 = tokens generated before the
    # last preemption (the slot's token list is re-seeded with them so
    # position/PRNG-step/budget arithmetic is residency-invariant);
    # resume_ids = ids ++ gen0, the prompt the resume re-prefills.
    meta: "RequestMeta | None" = None
    on_token: object = None
    on_routing: object = None
    gen0: tuple = ()
    resume_ids: np.ndarray | None = None
    preempts: int = 0  # times this request has been swapped out
    # reliability (r17): per-request wall-clock cancellation deadline
    # (seconds from submit; None = never)
    timeout_s: float | None = None
    # causal tracing (ISSUE 14): the TraceContext stamped onto every
    # event/span/ring entry/journal record this request touches; hop
    # bumps on retry requeue (engine) and failover/migration (router)
    trace: TraceContext | None = None


class GenerationServer:
    """Dynamic-batching server over one compiled decode program.

    program: a TranslatedLayer from `paddle.jit.load(prefix)` of an
        `export_generator` artifact, or any callable
        (ids[B, P] int32, seed, temperature, eos, top_p, pad) -> [B, T].
    batch_size: the program's static B (inferred from the artifact's
        input spec when available).
    prompt_len: the program's static P (inferred likewise). Shorter
        prompts are LEFT-padded with pad_token_id (the program masks
        pads from attention and the output keeps the pad prefix).

    Pad caveat: the decode program detects padding by VALUE equality, so
    pad masking is only engaged for batches that contain a padded row;
    in such a mixed batch, a full-length prompt that legitimately
    contains pad_token_id gets those positions masked too — pick a pad
    id outside the prompt alphabet if prompts mix lengths. submit()
    GUARDS this case (ADVICE r5): a full-length prompt containing
    pad_token_id logs a warning naming the positions, or raises when
    the server is built with strict_pad_check=True. (The paged server
    masks by length and has no such caveat.)
    """

    def __init__(self, program, batch_size=None, prompt_len=None,
                 pad_token_id=0, max_wait_ms=5.0, temperature=0.0,
                 seed=0, eos_token_id=-1, top_p=1.0,
                 strict_pad_check=False, attribution=False):
        self._program = program
        # export_generator artifacts record prompt_len and batch_size
        # (batch_size None = batch-polymorphic: the server picks its own)
        meta = getattr(program, "_meta", {}) or {}
        prompt_len = prompt_len or meta.get("prompt_len")
        batch_size = batch_size or meta.get("batch_size")
        if not batch_size and prompt_len and meta.get("batch_size", 0) \
                is None:
            batch_size = 8  # polymorphic artifact: serving default
        if not batch_size or not prompt_len:
            raise ValueError(
                "batch_size/prompt_len not given and not recorded in the "
                "artifact meta (re-export with models.gpt2."
                "export_generator, or pass them explicitly)")
        self.batch_size = int(batch_size)
        self.prompt_len = int(prompt_len)
        # quantization block (schema-congruent with the paged server):
        # the dense program's quantization is baked into the exported
        # artifact — report what its meta records (scale buffers live
        # inside the program's params, so scale bytes read 0 here)
        wq = meta.get("weight_quant")
        kq = meta.get("kv_quant")
        self._quant_stats = {
            "enabled": bool(wq or kq),
            "mode": "w8a16" if wq == "int8" else "none",
            "kv_dtype": kq or "native",
            "kv_scale_bytes": 0,
            "kv_pool_bytes_total": 0,
        }
        self.pad_token_id = int(pad_token_id)
        self.strict_pad_check = bool(strict_pad_check)
        self.max_wait_ms = float(max_wait_ms)
        self._defaults = (np.uint32(seed), np.float32(temperature),
                          np.int32(eos_token_id), np.float32(top_p),
                          np.int32(pad_token_id))
        self._lock = threading.Condition()
        self._queue: list[_Req] = []
        self._stop = False
        self._thread = None
        # stats
        self._lat = []
        self._tokens_out = 0
        self._batches = 0
        self._batches_at_reset = 0
        self._rows = 0
        self._stop_reasons = dict.fromkeys(STOP_REASONS, 0)
        self._t0 = None
        # attribution (ISSUE 17): same ledger class as the paged
        # server — the dense batcher charges whole-batch device time
        # apportioned evenly over its rows (rows cost the same at
        # fixed B by construction)
        self._ledger = ResourceLedger() if attribution else None

    def _req_sig(self, sampling):
        """Program-level parameter signature a batch must share: the
        dense decode program takes ONE (temperature, top_p, seed, eos)
        per dispatch, so the batcher only groups requests whose
        signatures match. None = server defaults (rolling batch seed).
        Returns (temp, top_p, seed|None, eos, from_stop_ids)."""
        seed0, temp0, eos0, top_p0, _ = self._defaults
        if sampling is None:
            return (float(temp0), float(top_p0), None, int(eos0), False)
        s = sampling
        # the dense program has no per-slot param buffers: fields that
        # need them are rejected EAGERLY, naming the field (the paged
        # server supports all of them)
        for field_name, bad in (
                ("top_k", s.top_k != 0),
                ("min_p", s.min_p != 0.0),
                ("repetition_penalty", s.repetition_penalty != 1.0),
                ("presence_penalty", s.presence_penalty != 0.0),
                ("frequency_penalty", s.frequency_penalty != 0.0),
                ("stop_strings", bool(s.stop_strings)),
                ("max_new_tokens", s.max_new_tokens is not None)):
            if bad:
                raise ValueError(
                    f"GenerationServer (dense) does not support "
                    f"SamplingParams.{field_name}="
                    f"{getattr(s, field_name)!r}; use "
                    f"PagedGenerationServer")
        if len(s.stop_token_ids) > 1:
            raise ValueError(
                "GenerationServer (dense) supports at most one stop "
                f"token id (the program's eos), got "
                f"{s.stop_token_ids!r}; use PagedGenerationServer")
        eos = (int(s.stop_token_ids[0]) if s.stop_token_ids
               else int(eos0))
        return (s.temperature, s.top_p, s.seed, eos,
                bool(s.stop_token_ids))

    # ---- client API ----------------------------------------------------
    def submit(self, ids, sampling=None, tenant="default"):
        """Enqueue one prompt (list/array of ints, length <= prompt_len).
        Returns a Future resolving to the [prompt_len + new] int32 row.

        sampling: optional SamplingParams. The dense program runs one
        (temperature, top_p, seed, eos) per dispatch, so requests are
        batched with same-signature peers; per-slot fields (top_k,
        min_p, penalties, stop strings, per-request budgets) raise
        eagerly — the paged server supports them.
        tenant: attribution account the request's device time is
        charged to when the server was built with attribution=True."""
        if sampling is not None and not isinstance(sampling,
                                                   SamplingParams):
            raise TypeError(f"sampling must be a SamplingParams, "
                            f"got {type(sampling).__name__}")
        ids = np.asarray(ids, np.int32).reshape(-1)
        if ids.size == 0 or ids.size > self.prompt_len:
            raise ValueError(
                f"prompt length {ids.size} not in [1, {self.prompt_len}]")
        if ids.size == self.prompt_len and (ids == self.pad_token_id).any():
            # the documented value-masking corruption case (ADVICE r5):
            # this prompt needs no padding itself, but batched with ANY
            # padded row the program masks its pad-valued positions too
            at = np.flatnonzero(ids == self.pad_token_id).tolist()
            msg = (f"full-length prompt contains pad_token_id="
                   f"{self.pad_token_id} at positions {at}: batched "
                   f"with padded rows those positions would be masked "
                   f"(value-equality padding); use "
                   f"PagedGenerationServer (length masking) or a pad "
                   f"id outside the prompt alphabet")
            if self.strict_pad_check:
                raise ValueError(msg)
            _logger.warning("GenerationServer.submit: %s", msg)
        sig = self._req_sig(sampling)  # eager validation
        row = np.full((self.prompt_len,), self.pad_token_id, np.int32)
        row[self.prompt_len - ids.size:] = ids  # LEFT padding
        req = _Req(ids=row, future=Future(), t_submit=time.perf_counter(),
                   padded=ids.size < self.prompt_len,
                   rid=f"d{next(_req_ids)}", sampling=sampling,
                   meta=RequestMeta(tenant=str(tenant)))
        req.sig = sig
        if self._ledger is not None:
            self._ledger.request_begin(req.rid, str(tenant))
        with self._lock:
            if self._stop:
                raise RuntimeError("server stopped")
            self._queue.append(req)
            _m_queue_depth.labels(server="dense").set(len(self._queue))
            self._lock.notify()
        _tracing.event("request_submitted", request_id=req.rid,
                       prompt_len=int(ids.size))
        return req.future

    def start(self):
        if self._thread is not None:
            return self
        if self._stop:
            raise RuntimeError(
                "server was stopped; build a new GenerationServer")
        self._t0 = time.perf_counter()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        with self._lock:
            self._stop = True
            self._lock.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=60)
            self._thread = None
        with self._lock:
            for req in self._queue:  # fail, don't strand, late arrivals
                req.future.set_exception(RuntimeError("server stopped"))
            self._queue.clear()

    def reset_stats(self):
        """Zero the latency/throughput counters (benchmark windows); the
        batch counter keeps advancing so sampling seeds never repeat."""
        with self._lock:
            self._lat.clear()
            self._tokens_out = 0
            self._rows = 0
            self._batches_at_reset = self._batches
            self._stop_reasons = dict.fromkeys(STOP_REASONS, 0)
            self._t0 = time.perf_counter()
        if self._ledger is not None:
            self._ledger.reset()

    def stats(self):
        """Throughput and latency of the current measurement WINDOW —
        everything since start() or the last reset_stats() call.
        `stop_reasons` carries the same four-key breakdown as the paged
        server's stats (the dense program only ever produces eos /
        stop_token / budget — stop_string stays 0)."""
        with self._lock:
            lat = sorted(self._lat)
            dt = (time.perf_counter() - self._t0) if self._t0 else 0.0
            n = len(lat)
            nb = self._batches - self._batches_at_reset
            pct = (lambda p: lat[min(n - 1, int(p * n))] if n else 0.0)
            return {
                "requests": n,
                "batches": nb,
                "batch_fill": self._rows / ((nb or 1) * self.batch_size),
                "new_tokens": self._tokens_out,
                "tokens_per_sec": self._tokens_out / dt if dt else 0.0,
                "p50_ms": pct(0.50) * 1e3,
                "p90_ms": pct(0.90) * 1e3,
                "p99_ms": pct(0.99) * 1e3,
                "stop_reasons": dict(self._stop_reasons),
                "quantization": dict(self._quant_stats),
                # attribution (ISSUE 17): same schema as the paged
                # server — zeroed when the ledger is off
                "attribution": (self._ledger.stats()
                                if self._ledger is not None
                                else disabled_attribution_stats()),
                "wall_s": dt,
            }

    def cost_report(self):
        """`CostReport` billing export for the current window (ISSUE
        17); None when the server was built without attribution."""
        return self._ledger.report() if self._ledger is not None else None

    # ---- batcher loop --------------------------------------------------
    def _take_batch(self):
        """Block for the first request, then gather until full batch or
        the max_wait deadline; only requests sharing the head-of-line
        request's program signature (temperature/top_p/seed/eos) join —
        mismatched requests keep their queue order for a later batch.
        Returns [] on stop."""
        with self._lock:
            while not self._queue and not self._stop:
                self._lock.wait(timeout=0.1)
            if self._stop and not self._queue:
                return []
            deadline = time.perf_counter() + self.max_wait_ms * 1e-3
            while len(self._queue) < self.batch_size and not self._stop:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._lock.wait(timeout=remaining)
            sig = self._queue[0].sig
            batch = []
            for r in self._queue:
                if len(batch) == self.batch_size:
                    break
                if r.sig == sig:
                    batch.append(r)
            for r in batch:
                self._queue.remove(r)
            _m_queue_depth.labels(server="dense").set(len(self._queue))
            return batch

    def _loop(self):
        while True:
            batch = self._take_batch()
            if not batch:
                return
            for r in batch:
                _tracing.event("request_admitted", request_id=r.rid)
            _m_slots_busy.labels(server="dense").set(len(batch))
            rows = [r.ids for r in batch]
            while len(rows) < self.batch_size:  # pad: same device cost
                rows.append(rows[0])
            ids = np.stack(rows)
            # pad masking is VALUE-equality in the decode program: only
            # engage it when some row is actually padded, so full-length
            # prompts that legitimately contain pad_token_id aren't
            # masked at those positions
            temp, top_p, seed, eos, _from_stop = batch[0].sig
            defaults = [np.uint32(0), np.float32(temp), np.int32(eos),
                        np.float32(top_p), self._defaults[-1]]
            if not any(r.padded for r in batch):
                defaults[-1] = np.int32(-1)
            if seed is not None:
                # explicit per-request seed (SamplingParams.seed): part
                # of the batch signature, so every row asked for it —
                # reproducible by construction
                defaults[0] = np.uint32(seed)
            else:
                # per-batch seed: with temperature > 0 a FIXED seed
                # would draw identical sampling noise for every batch
                # (identical prompts -> identical completions, forever)
                defaults[0] = np.uint32(
                    (int(self._defaults[0]) + self._batches) & 0xFFFFFFFF)
            t_disp = time.perf_counter()
            try:
                with _tracing.span("decode_dispatch",
                                   request_ids=[r.rid for r in batch],
                                   batch=len(batch)):
                    out = self._program(ids, *defaults)
                    out = np.asarray(getattr(out, "numpy", lambda: out)())
            except Exception as e:  # noqa: BLE001 — fan the error out
                for r in batch:
                    r.future.set_exception(e)
                continue
            t_done = time.perf_counter()
            if self._ledger is not None:
                self._ledger.charge_device(
                    int((t_done - t_disp) * 1e9),
                    [(r.meta.tenant, r.rid, 1) for r in batch])
            new_tokens = out.shape[1] - self.prompt_len
            # stop accounting (schema-congruent with the paged server):
            # the program keeps emitting eos after a hit, so "did any
            # generated token match the batch's eos id" is exact
            reasons = []
            for i, r in enumerate(batch):
                gen = out[i, self.prompt_len:]
                if eos >= 0 and (gen == eos).any():
                    reasons.append("stop_token" if _from_stop else "eos")
                else:
                    reasons.append("budget")
            with self._lock:
                self._batches += 1
                self._rows += len(batch)
                self._tokens_out += new_tokens * len(batch)
                for i, r in enumerate(batch):
                    self._lat.append(t_done - r.t_submit)
                    self._stop_reasons[reasons[i]] += 1
            _m_slots_busy.labels(server="dense").set(0)
            for i, r in enumerate(batch):
                cost = (self._ledger.request_done(r.rid, new_tokens)
                        if self._ledger is not None else None)
                _tracing.event("request_done", request_id=r.rid,
                               new_tokens=int(new_tokens), cost=cost)
                _m_requests_done.labels(server="dense").inc()
                _m_stop_reason.labels(server="dense",
                                      reason=reasons[i]).inc()
                _m_request_latency.labels(server="dense").observe(
                    t_done - r.t_submit)
                r.future.set_result(out[i])


class PagedGenerationServer:
    """Continuous-batching server over the paged KV cache.

    Where `GenerationServer` pads every request to one global prompt_len
    and holds its slot for the full max_new even after EOS, this server
    runs the PagedDecoder engine directly against a `PagedKVCache`:

      * per-slot sequence lengths — a 70-token prompt costs 70 cache
        positions, not prompt_len;
      * every decode step, finished slots (EOS or the request's token
        budget) resolve their futures, free their blocks, and are
        REFILLED from the queue before the next step — new requests join
        mid-flight instead of waiting for the whole batch to drain;
      * masking is by length, so a prompt that legitimately contains
        pad_token_id can never be corrupted (the dense server's
        value-equality caveat does not exist here).

    Admission is reservation-based: a request is admitted only when the
    pool can cover its worst case (ceil((len + max_new)/block_size)
    blocks) on top of every active slot's outstanding worst case, so
    mid-flight block exhaustion is impossible. Blocks are still
    allocated lazily (`cache.append`) as sequences grow — the
    reservation is accounting, not allocation.

    model: a GPT2 (or same-layout) module; its params are snapshotted at
    construction (weight_quant="int8" serves W8A16).

    Prefill is PACKED and CHUNKED (Ragged Paged Attention direction,
    arXiv:2604.15464; Sarathi-style chunk budget): every loop round, up
    to `prefill_chunk_tokens` prompt tokens across ALL slots still
    feeding their prompts are concatenated into one token-packed stream
    and run as ONE packed ragged prefill dispatch — an admission burst
    of N requests costs O(1) prefill dispatches per decode round
    instead of N sequential B=1 dispatches (each paying the
    per-dispatch cost). Prompts longer than the chunk budget are
    split across rounds, the partial K/V state living in the paged
    cache (which supports it natively), so in-flight decode slots see
    at most one chunk-budget prefill between decode dispatches and
    inter-token latency stays bounded during admission churn. The
    packed stream is bucketed to a power of two, so compile count is
    logarithmic in the packed token budget rather than per
    prompt-length bucket.

    ONE DISPATCH IN FLIGHT (the default loop): decode step N+1 is
    queued on the device before step N is read back, its continuing
    rows taking their input token from step N's result on the device
    (`decode_step`'s `prev`), and a prefill is queued behind the step
    in flight and read after the next one is queued — the device holds
    its next program while the host reads, emits, admits and plans.
    A finish by length costs nothing; a stop only the read reveals
    costs one row of the step already queued (dropped, counted as
    replay). Where the host must be authoritative (stop, host ops,
    swap-out, timeouts, a failed dispatch, idle) the engine reads what
    is in flight first; an engine with a drafter or
    steps_per_dispatch > 1 reads every dispatch where it issues it.
    Tokens are those of the synchronous order. `_round_split`,
    docs/SERVING.md "The round's order", stats()["dispatch_ahead"].

    prefill_chunk_tokens: max REAL prompt tokens per packed prefill
        dispatch (default 512). Smaller bounds decode ITL tighter
        during bursts; larger finishes prefills (TTFT) sooner.
    pack_align: each prompt chunk's packed region is aligned to this
        many tokens (default: 128 on TPU — the Pallas ragged-prefill
        kernel's query-tile contract — else 8). Alignment padding is
        routed to the trash block.

    steps_per_dispatch > 1 turns on multi-step scheduling: that many
    decode tokens run as ONE jitted lax.scan dispatch, amortizing the
    per-dispatch cost that would otherwise bound a token-per-dispatch
    loop (not yet measured on the local chip, PERF.md). The cost is
    granularity: EOS/budget is only observed every k tokens, so up to
    k-1 tokens per request are decoded and discarded, and slot refill
    waits for the scan to return. k=1 is exact continuous batching.

    enable_prefix_cache=True turns on block-level PREFIX CACHING
    (round 9): on admission the request's prompt is matched against
    the pool's content index (`PagedKVCache.attach_prefix`) and the
    longest cached block chain is attached by table-entry copy — those
    tokens are marked already-fed and the packed ragged prefill starts
    at the first uncached token (the PR 3 chunk path already resumes
    mid-sequence, so no engine change is needed). A fully cached
    prompt prefills exactly ONE token: the last prompt token is always
    recomputed to sample token 0. Completed prompts are published back
    to the index; freed blocks with indexed content park in the
    cache's LRU retention list and are reclaimed only under pool
    pressure. Admission reserves one extra block per request for the
    (at most one) copy-on-write a mid-block shared tail can force.
    Default OFF: a disabled server takes the exact pre-cache
    allocation path (no lookups, no publishes, no spare block).

    kv_tier (long-context round) adds a HOST-RAM TIER below the device
    pool (True for the default `kv_tier.HostKVTier`, or an instance
    for explicit capacity/watermark; requires enable_prefix_cache).
    Cold retained prefix blocks demote to pinned host memory as int8
    codes+scales instead of being dropped under pool pressure, and a
    later prompt/resume whose prefix chain continues into the tier
    promotes them back before the attach (prefetch-on-attach) — so
    preempted sessions and shared system prompts survive pool churn
    without recompute. kv_tier=None keeps the exact pre-tier engine.

    QUANTIZED SERVING (this round): `quantization="w8a16"` packs the
    decoder weights to int8 ONCE at construction
    (`model.quantize_weights()`, the shared PTQ implementation) and
    every dispatch — decode step, packed chunked prefill, speculative
    verify — streams half the weight bytes with a fused rescale
    epilogue. `kv_dtype="int8"` additionally quantizes the KV POOL:
    blocks hold int8 codes + per-vector scales
    (`PagedKVCache(kv_dtype="int8")`), appends quantize on write,
    attention dequantizes inside the kernel, and prefix-cache
    publish/attach, CoW, swap-out and truncate all carry the scale
    buffer with the block — so sharing and preemption keep working
    quantized, at ~2x resident tokens per pool byte. Both knobs
    default OFF (the exact pre-round bf16 path); `stats()` reports a
    schema-stable "quantization" block either way. See docs/SERVING.md
    "Quantized serving" for the parity-tolerance policy and when NOT
    to enable.

    sharding=ShardedEngineConfig(tp, dp) (or True for a 1-device mesh)
    turns on SHARDED SERVING (serving_dist round): the snapshotted
    (and optionally quantized) weights are placed on a
    `jax.sharding.Mesh` per the training TP plan (column/row-split
    attention + MLP, vocab-parallel head), the KV pool's head axis
    shards per-device behind the unchanged block-table API (+ the
    block axis over dp), and every decode program is jitted with
    explicit in/out shardings — XLA inserts the two TP collectives.
    The engine loop, prefix cache, speculation, sampling and the
    front door run unmodified (token parity tested across mesh
    sizes); a 1-device mesh is bitwise the unsharded engine, and the
    default None never imports serving_dist. See docs/SERVING.md
    "Sharded serving".

    OPERATIONS PLANE (ISSUE 10): `expose_port=` (or the
    PADDLE_TPU_METRICS_PORT env var; 0 = ephemeral, tests) starts a
    stdlib http.server daemon thread serving `/metrics` (Prometheus
    text from the process registry), `/statusz` (live JSON engine
    state — the `statusz()` method), and `/healthz`
    (ok | degraded | stalled; stalled answers 503). It also enables
    the per-server FLIGHT RECORDER — a bounded ring of structured
    engine events (admission, chunk plans, dispatch shapes,
    preempt/resume, pool levels, XLA compiles, exceptions) — and the
    STALL WATCHDOG, which flips health to "stalled" and auto-dumps the
    ring when work is pending with no dispatch progress past
    `stall_timeout_s` (an engine dispatch exception also dumps).
    XLA compiles at every decode jit boundary are tracked process-wide
    regardless (`observability.compile_tracker`) and windowed into
    `stats()["compiles"]`; `stats()["goodput"]` accounts decoded
    device tokens vs. emitted / speculation-rolled-back / replayed.
    Default OFF: no port, no threads, and every recorder hook is one
    bool check — the exact pre-round engine.

    ONE-KERNEL ROUND (r16): `unified_round=True` fuses each scheduler
    round's up-to-three attention dispatches — packed chunk prefill,
    plain decode, speculative verify — into ONE
    `nn.decode.unified_round` dispatch over a single packed stream
    (prefill chunks, decode rows and verify regions are all just
    ragged segments under the same segment-causal mask; see
    docs/SERVING.md "One-kernel round"). `async_rounds=True` (implies
    unified) additionally DOUBLE-BUFFERS the loop: round N+1 is
    planned on host and dispatched while round N executes on device,
    with round N's sampled tokens feeding round N+1's decode rows
    through a slot-indexed device carry — the only host<->device sync
    point is the detokenize/stop-check boundary, one round behind the
    device. Stop flags are device-computed either way; host-side stop
    checks (stop strings, budgets) drain one round late and the
    overshoot round is discarded, so output is TOKEN-IDENTICAL to the
    split path across the whole composed stack (prefix cache,
    speculation, quantization, sharding, preemption — parity-tested).
    Requires steps_per_dispatch=1. Both default OFF: the exact split
    scheduler path.

    RELIABILITY (r17, docs/RELIABILITY.md): the engine runs a RECOVERY
    LADDER by default — a dispatch exception no longer fans out to
    every in-flight future. Implicated requests are snapshotted
    through the preemption swap-out machinery (tokens-so-far + resume
    prompt; live K/V published into the prefix index when caching is
    on), requeued at the front of their queue, and retried with
    capped exponential backoff; a request implicated in
    `RecoveryPolicy.quarantine_after` consecutive failures is
    QUARANTINED (its future fails with `QuarantinedRequest` naming
    the fault seam) while every co-resident request completes
    token-identically. `recovery=False` restores the legacy
    fail-everything path. `/healthz` is degraded only while
    UNRECOVERED: the first successful dispatch after a failure counts
    a recovery and returns health to ok. `fault_plan=` (or
    PADDLE_TPU_FAULT_PLAN) installs a deterministic `FaultPlan` —
    fixed-seed faults by seam x occurrence at the engine's hazard
    seams (dispatch raise, pool exhaustion, watchdog-visible slow
    dispatch, detokenize error, stream-consumer death) — one bool
    check per seam when off. `journal=` (path or `SessionJournal`)
    records every accepted request + emitted token append-only;
    `recover_from_journal()` on a fresh server re-admits whatever a
    crash (`kill()` in tests) interrupted, token-identically. Per-
    request `submit(timeout_s=)` cancels overdue requests slot-
    freeingly; `shed_queue_depth=` refuses admissions past a queue
    depth with an `AdmissionShed.retry_after_s` hint.

    OBSERVABILITY, FLEET-GRADE (ISSUE 14): every request carries a
    `TraceContext` (minted at submit or passed by a router via
    `submit(trace_ctx=)`) whose trace_id / hop / cause stamp every
    trace event, span, flight-recorder entry and journal record the
    request touches — `observability.assemble_causal_traces` stitches
    a request's whole fleet lifetime (retries, failover, migration)
    into one causal tree. `slos=` (list of `observability.SLO`, or
    True for `default_slos()`) attaches an SLO burn-rate engine fed
    from the TTFT/ITL/availability/goodput hot paths: multi-window
    ok|warn|page states, `slo_*` gauges, a `/slo` ops endpoint, and a
    `stats()["slo"]` block (schema-stable zeros when off).
    `export_timeline(path)` writes the Chrome/Perfetto timeline of
    the span sink + flight-recorder ring.

    speculation=SpecConfig(...) (or True for defaults) turns on
    SPECULATIVE DECODING (round 11): each round, eligible decode-phase
    slots ask the drafter (default: the self-drafting n-gram /
    prompt-lookup drafter — no second model) for up to K draft tokens,
    and ONE packed verification dispatch (`nn.decode.packed_verify`,
    the PR 3 packed-prefill kernel shape with per-row sample indices)
    scores every slot's drafts against the target model. Because the
    per-request PRNG is counter-based, the target's token at every
    position is deterministic, so rejection sampling reduces to exact
    match and fixed-seed output — greedy or sampled, penalties
    included — is token-identical to non-speculative decode no matter
    how many drafts were accepted. Accepted tokens plus the bonus
    token emit in one round (1..K+1 tokens per slot per dispatch);
    rejected speculative K/V positions roll back via
    `PagedKVCache.truncate_seq`. Slots with no proposal this round
    take the plain decode dispatch, interleaved as before. Requires
    steps_per_dispatch=1; admission reserves a K-token overrun per
    request. Default OFF: the scheduler round is the exact
    pre-speculation path.
    """

    def __init__(self, model, *, max_slots=4, block_size=16,
                 max_prompt_len=None, max_new_tokens=32, num_blocks=None,
                 eos_token_id=None, temperature=0.0, seed=0,
                 weight_quant=None, quantization=None, kv_dtype=None,
                 steps_per_dispatch=1,
                 prefill_chunk_tokens=512, pack_align=None,
                 enable_prefix_cache=False, kv_tier=None,
                 detokenize=None,
                 stop_tail_tokens=16, speculation=None, sharding=None,
                 unified_round=False, async_rounds=False,
                 expose_port=None, flight_recorder=None,
                 stall_timeout_s=30.0, fault_plan=None, recovery=True,
                 journal=None, shed_queue_depth=None, slos=None,
                 attribution=None, tier_prefetch=None):
        import jax
        import jax.numpy as jnp

        from ..sampling import SlotParamStore
        from ..nn.decode import PagedDecoder
        from .kv_cache import PagedKVCache, blocks_for

        self._jnp, self._jax = jnp, jax
        cfg = model.cfg
        # a model of latent, recurrent or grouped-head convolutional
        # layers hands over its own description (`nn.decode_blocks`); a
        # model without one is the GPT-2 layout its cfg spells out.  A
        # description serves through
        # the default loop alone, over dense device-resident caches:
        # every option that would need its state shared, rolled back,
        # moved, quantized or sharded is refused here, by name.
        self._desc = (model.decoder_description()
                      if hasattr(model, "decoder_description") else None)
        if self._desc is not None:
            for name, value, ok in (
                    ("enable_prefix_cache", bool(enable_prefix_cache),
                     False),
                    ("speculation", speculation, None),
                    ("kv_dtype", kv_dtype, None),
                    ("quantization", quantization, None),
                    ("weight_quant", weight_quant, None),
                    ("unified_round", bool(unified_round), False),
                    ("async_rounds", bool(async_rounds), False),
                    ("steps_per_dispatch", int(steps_per_dispatch), 1),
                    ("sharding", sharding, None),
                    ("kv_tier", kv_tier or None, None),
                    ("tier_prefetch", tier_prefetch or None, None)):
                if value != ok:
                    raise ValueError(
                        f"PagedGenerationServer({name}={value!r}) has no "
                        f"meaning yet beside a slot-indexed store (recurrent "
                        f"state, conv tails) or a latent or grouped-head "
                        f"pool: this model serves through the default "
                        f"loop (packed_prefill + decode_step) with "
                        f"{name}={ok!r}")
        self.max_new = int(max_new_tokens)
        self.steps_per_dispatch = max(1, int(steps_per_dispatch))
        # speculation (round 11): True -> default SpecConfig; a
        # SpecConfig configures the drafter and the K budget. None
        # keeps the EXACT pre-speculation scheduler path.
        if speculation is True:
            from ..spec_decode import SpecConfig

            speculation = SpecConfig()
        elif speculation is not None:
            from ..spec_decode import SpecConfig

            if not isinstance(speculation, SpecConfig):
                raise TypeError(
                    f"speculation must be a SpecConfig, True or None, "
                    f"got {type(speculation).__name__}")
        self.speculation = speculation
        # sharded serving: normalize (True -> defaults) and validate
        # the mesh config EAGERLY — tp must divide the head count
        # before the pool layout is fixed below. The disabled path
        # never imports serving_dist.
        if sharding is not None:
            from ..serving_dist import normalize_sharding

            sharding = normalize_sharding(sharding, cfg.num_heads)
        # sequence-parallel prefill (long-context round): sp multiplies
        # the packed chunk budget — the sp-sharded program prefills
        # sp * prefill_chunk_tokens prompt tokens per dispatch at the
        # same per-shard token load, so one huge prompt stops
        # serializing through a single replica's budget. sp=1 (or
        # unsharded) keeps the exact pre-round budget and programs.
        self._sp_degree = sharding.sp if sharding is not None else 1
        # sp attention strategy (memory-flat long-context round): how
        # the sp>1 packed-prefill trunk attends across shards —
        # "allgather" (exact r21 seam, linear peak bytes) or the
        # memory-flat "ring"/"ulysses" modes (config-validated and
        # sp=1-normalized by ShardedEngineConfig itself)
        self._sp_attention = (sharding.sp_attention
                              if sharding is not None else "allgather")
        self._spec_k = (speculation.max_draft_tokens
                        if speculation is not None else 0)
        self._drafter = (speculation.make_drafter()
                         if speculation is not None else None)
        if speculation is not None and self.steps_per_dispatch > 1:
            raise ValueError(
                "speculation requires steps_per_dispatch=1 (the verify "
                "dispatch already amortizes the per-dispatch floor over "
                "up to K+1 tokens; fusing verify rounds into a scan "
                "would need host drafting mid-scan)")
        # one-kernel round (r16): unified_round=True fuses the whole
        # scheduler round — chunk prefill rows, decode rows, verify
        # regions — into ONE attention dispatch; async_rounds=True
        # additionally double-buffers the loop (plan round N+1 on host
        # while round N runs on device, tokens chained via the device
        # carry). async implies unified. Default OFF: the exact
        # split-path scheduler.
        self._async = bool(async_rounds)
        self._unified = bool(unified_round) or self._async
        if self._unified and self.steps_per_dispatch > 1:
            raise ValueError(
                "unified_round/async_rounds require steps_per_dispatch"
                "=1 (the fused round already amortizes the dispatch "
                "floor over the whole round)")
        if self._unified and self._sp_degree > 1:
            raise ValueError(
                "sequence-parallel prefill (ShardedEngineConfig.sp > 1) "
                "requires the split scheduler path — the unified round "
                "packs decode/verify rows into the same stream the sp "
                "program would shard, and decode stays TP by design "
                "(set unified_round/async_rounds False)")
        self._uk1 = self._spec_k + 1  # pinned unified readout width
        # overrun horizon past the budget: a multi-step scan may write
        # up to k-1 discarded tokens, and a verify dispatch up to K
        # speculative positions past the last emitted token (rolled
        # back on rejection, but the blocks must be reservable). The
        # async loop adds ONE round of optimistic overshoot: the host
        # learns about stops a round late, so the device may write up
        # to 1 + K extra positions past where the split engine stops.
        slack = max(self.steps_per_dispatch - 1, self._spec_k)
        if self._async:
            slack += 1 + self._spec_k
        self._overrun = slack
        self.max_prompt_len = int(
            max_prompt_len or cfg.max_position - self.max_new - slack)
        if self.max_prompt_len + self.max_new + slack > cfg.max_position:
            raise ValueError(
                f"max_prompt_len ({self.max_prompt_len}) + max_new_tokens "
                f"({self.max_new}) + overrun slack ({slack}, "
                f"steps_per_dispatch/speculation) "
                f"exceeds max_position ({cfg.max_position})")
        self.max_slots = int(max_slots)
        self.block_size = int(block_size)
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        if self.prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1")
        if pack_align is None:  # Pallas kernel query-tile contract on TPU
            pack_align = 128 if jax.default_backend() not in ("cpu",) else 8
        if self._desc is not None:
            # a recurrent chunk and a latent tile are wholly one
            # sequence's: a packed region starts on their boundary
            pack_align = math.lcm(int(pack_align),
                                  self._desc.pack_multiple)
        self._pack_align = int(pack_align)
        # a program of a description with experts, a latent pool and a
        # state store costs tens of seconds to compile and next to
        # nothing for plan rows it does not use (the head reads its
        # weights whatever the rows; the latent walk stops at the deepest
        # token): its packed prefill is bucketed by packed length alone,
        # with every slot's row and the full table width
        self._one_plan_shape = self._desc is not None
        if self._one_plan_shape \
                and self.prefill_chunk_tokens % self._pack_align:
            raise ValueError(
                f"prefill_chunk_tokens ({self.prefill_chunk_tokens}) must "
                f"be a multiple of the packed regions' alignment "
                f"({self._pack_align}) for this model: its packed prefill "
                f"is bucketed by packed length alone")
        # verify regions only need alignment where the Pallas kernel
        # runs; the XLA fallback takes any packing, and a verify
        # dispatch fires every round — off TPU, padding each K+1-token
        # region to the prefill alignment would be pure wasted compute
        self._verify_align = (self._pack_align
                              if jax.default_backend() not in ("cpu",)
                              else 1)
        self.eos = -1 if eos_token_id is None else int(eos_token_id)
        self.temperature = float(temperature)
        # quantized serving hot path: `quantization="w8a16"` packs the
        # decoder weights ONCE here (model.quantize_weights — the shared
        # PTQ implementation) and every dispatch — decode, chunked
        # ragged prefill, speculative verify — runs int8 dots with the
        # fused rescale epilogue; `weight_quant="int8"` is the pre-round
        # alias. `kv_dtype="int8"` quantizes the KV POOL itself (int8
        # codes + per-block-row scales, dequant inside the kernels).
        # Both default OFF: the disabled path is the exact pre-round
        # bf16 program.
        if quantization not in (None, "w8a16"):
            raise ValueError(f"unknown quantization {quantization!r} "
                             "(supported: None, 'w8a16')")
        if weight_quant == "int8":
            quantization = "w8a16"
        elif weight_quant is not None:
            raise ValueError(f"unknown weight_quant {weight_quant!r} "
                             "(supported: 'int8')")
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r} "
                             "(supported: None, 'int8')")
        self.quantization = quantization
        self.kv_dtype = kv_dtype
        params, _ = model.functional_state()
        if quantization == "w8a16":
            params = model.quantize_weights(params)
        self._params = params
        dt = params["ln_f.weight" if self._desc is None
                    else self._desc.final_norm].dtype
        self.enable_prefix_cache = bool(enable_prefix_cache)
        # a description none of whose layers pages anything has no pool:
        # its sequences take no block and a table row is [state slot]
        # alone, so admission goes by state slots
        pooled = self._desc is None or self._desc.pooled
        self._m_width = blocks_for(
            self.max_prompt_len + self.max_new + slack,
            self.block_size) if pooled else 0
        if num_blocks is None:  # worst case: every slot at full horizon
            # (+1 CoW spare per slot when prefix caching is on, so the
            # default pool still fits max_slots worst-case requests; the
            # trash block and one more where there is no pool)
            spare = 1 if self.enable_prefix_cache else 0
            num_blocks = max(
                2, self.max_slots * (self._m_width + spare) + 1)
        if sharding is not None and sharding.dp > 1:
            # the pool's block axis shards over dp: round the array dim
            # up so the explicit placement divides evenly (the extra
            # blocks are just capacity)
            num_blocks = -(-int(num_blocks) // sharding.dp) * sharding.dp
        # host-RAM KV tier (long-context round): True -> default
        # HostKVTier, or an instance for explicit capacity/watermark.
        # Needs the prefix cache — tiering demotes/promotes INDEXED
        # retained content, which only exists when publishing is on.
        if kv_tier is not None and kv_tier is not False \
                and not self.enable_prefix_cache:
            raise ValueError(
                "kv_tier requires enable_prefix_cache=True (the tier "
                "holds demoted prefix-index content)")
        # tier prefetch-ahead (memory-flat long-context round): promote
        # a QUEUED request's cold tier blocks into the device pool
        # WHILE the current round computes, so admission's
        # attach_prefix finds the chain device-resident and pays no
        # promotion wall time. True -> lookahead 2 queued requests; an
        # int sets the lookahead depth. None/False = OFF (the exact
        # synchronous promote-on-attach path).
        if tier_prefetch is not None and tier_prefetch is not False:
            if kv_tier is None or kv_tier is False:
                raise ValueError(
                    "tier_prefetch requires kv_tier (prefetch-ahead "
                    "promotes host-tier content ahead of admission; "
                    "without a tier there is nothing to promote)")
            look = 2 if tier_prefetch is True else int(tier_prefetch)
            if look < 1:
                raise ValueError(
                    f"tier_prefetch={tier_prefetch!r} must be True or "
                    f"a positive lookahead depth (queued requests "
                    f"scanned per round)")
        else:
            look = 0
        self._prefetch_look = look
        self._prefetched: dict = {}    # rid -> set of prefetched hashes
        self._prefetch_done: set = set()  # rids whose walk went dry
        self._prefetch_issued = 0
        self._prefetch_hits = 0
        self._prefetch_wasted = 0
        self._prefetch_overlap_s = 0.0
        self._promote_ctx = None  # rid the in-progress attach serves
        if self._desc is None:
            self.cache = PagedKVCache(
                cfg.num_layers, cfg.num_heads,
                cfg.hidden_size // cfg.num_heads,
                block_size=self.block_size, num_blocks=int(num_blocks),
                dtype=dt, kv_dtype=kv_dtype, tier=kv_tier)
        else:
            # built from the description: a latent pool under the same
            # allocator, a state slot a server slot beside it
            self.cache = PagedKVCache.for_description(
                self._desc, block_size=self.block_size,
                num_blocks=int(num_blocks), dtype=dt,
                max_slots=self.max_slots)
        # sharded serving (serving_dist round): a ShardedEngineConfig
        # (or True for defaults) places the snapshotted/quantized
        # weights and the pool arrays on the mesh and hands the decoder
        # an explicit-shardings bundle. None = the exact pre-round
        # single-device path — serving_dist is never even imported.
        self.sharding = None
        self._mesh = None
        decode_shardings = None
        collective_quant = None
        if sharding is not None:
            from ..serving_dist import (apply_sharding,
                                        build_collective_quant)

            decode_shardings = apply_sharding(self, sharding)
            # quantized collectives (this round): int8/int4-group wire
            # for the mp-axis decode collectives — None (or tp=1, no
            # wire) keeps the exact r16 sharded programs
            collective_quant = build_collective_quant(sharding,
                                                      self._mesh)
        # the decoder's kv_dtype MUST match the cache's — PagedDecoder
        # re-checks the pairing eagerly on every dispatch
        self._decoder = PagedDecoder.for_model(
            model, self.block_size, kv_dtype=kv_dtype,
            shardings=decode_shardings,
            collective_quant=collective_quant,
            sp_attention=self._sp_attention)
        # analytic per-dispatch sp-attention byte accounting (host-side
        # arithmetic — the r20 dispatch_wire_bytes discipline): the
        # high-water mark feeds the serving_sp_attention_bytes_peak
        # gauge and, for ring/ulysses, every dispatch is asserted
        # under the chunk-length-independent flat bound
        self._sp_peak_bytes = 0
        heads = cfg.num_heads if self._desc is None \
            else self._desc.query_heads
        self._sp_bytes_kw = dict(
            sp=self._sp_degree,
            tp=(sharding.tp if sharding is not None else 1),
            num_heads=heads, head_dim=cfg.hidden_size // heads,
            kv_quant=kv_dtype == "int8",
            itemsize=jnp.dtype(dt).itemsize)
        # the decode attention kernel's (name, fan, launches a step)
        # where this engine's decode steps run one: stats()["decode_kernel"]
        self._dk_plan = self._decode_kernel_plan(
            heads, sharding.tp if sharding is not None else 1)
        # per-slot sampling state (round 10): struct-of-arrays param
        # buffers + the [slots, V] penalty count buffer, scattered on
        # admit/refill. Constructor temperature is the DEFAULT for
        # requests submitted without SamplingParams (validated here).
        self._sp_store = SlotParamStore(self.max_slots, cfg.vocab_size)
        self._default_sampling = SamplingParams(
            temperature=self.temperature)
        self._detok = detokenize
        self.stop_tail_tokens = int(stop_tail_tokens)
        if self.stop_tail_tokens < 1:
            raise ValueError("stop_tail_tokens must be >= 1")
        self._seed0 = int(seed) & 0xFFFFFFFF
        self._auto_seeds = itertools.count()
        # slot state: None (idle) or dict(seq, req, toks, pos, budget)
        self._slots = [None] * self.max_slots
        self._worst: dict[int, int] = {}  # seq -> worst-case block count
        self._seq_counter = 0
        self._lock = threading.Condition()
        self._queue: list[_Req] = []
        self._stop = False
        self._thread = None
        # stats window
        self._lat = []
        self._ttft = []
        self._itl = []
        self._tokens_out = 0
        self._requests_done = 0
        self._steps = 0
        self._prefills = 0
        self._prefill_dispatches = 0
        self._active_integral = 0
        self._fill_integral = 0.0
        self._stop_reasons = dict.fromkeys(STOP_REASONS, 0)
        self._fastpath_dispatches = 0
        self._sampled_dispatches = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_rolled_back = 0
        self._spec_dispatches = 0
        self._spec_rounds_per_slot = 0
        # goodput accounting (ISSUE 10): generated-token positions
        # computed on device vs. the ones that reached a client —
        # decoded = goodput + spec-rolled-back + replayed, by
        # construction at every dispatch site
        self._decoded_tokens = 0
        self._replayed_tokens = 0
        # the expert layers' counters (always on, reset with the window):
        # running sums over the dispatches that routed a token, and the
        # newest entries themselves in a bounded ring: (perf_counter,
        # tokens, held picks, experts touched, max load, expert layers),
        # the counts summed over the expert layers of a dispatch and the
        # max taken over them
        self._expert_sums = [0, 0, 0, 0, 0]   # the entry's fields 1..5
        self._expert_ring = deque(maxlen=EXPERT_RING)
        # one-kernel round (r16): per-round dispatch accounting (both
        # engine paths — the split path reports its 1-3 dispatches per
        # round here too, so the fusion win is measurable), async
        # overlap, and the double-buffer state (the in-flight round +
        # the slot-indexed device carry; both live outside the stats
        # window and never reset)
        self._rounds = 0
        self._round_dispatch_count = 0
        self._mixed_rounds = 0
        self._overlap_s = 0.0
        # the engine thread's time by phase (always on, reset with the
        # window): every loop runs its rounds through `self._phase`
        self._phases = _RoundPhases()
        self._phase = self._phases.phase
        self._pending = None
        self._carry = None
        self._zero_carry = None
        # one dispatch in flight (the default loop; `_pending` is the
        # decode step issued and not yet read): decode dispatches
        # issued, those issued while the one before was unread, the
        # reads with nothing queued behind them by the seam that asked
        # (both loops that keep one in flight), the rows a finish left
        # in a dispatch already queued; and the instant up to which
        # dispatch time is charged to the residents (`_charge_read`)
        self._decode_issued = 0
        self._decode_ahead = 0
        self._dk_counts = [0, 0, 0]   # launches, live blocks, grid steps
        self._drains: dict[str, int] = {}
        self._late_rows = 0
        self._charged_to = 0.0
        # did the device run dry (`_probe_device`): one output array of
        # the newest dispatch issued and not yet read (None: everything
        # issued was read); the dispatches that landed with one in
        # flight and those of them that found it finished, by the kind
        # issued
        self._newest_out = None
        self._probed = dict.fromkeys(DISPATCH_KINDS, 0)
        self._found_idle = dict.fromkeys(DISPATCH_KINDS, 0)
        # steady-state device-argument reuse (async window rounds): the
        # whole plan argument set is round-invariant per (slots, seqs,
        # drafts) signature — caching the uploaded arrays is most of
        # "hide the host planner behind the device"
        self._args_cache = None
        self._tables_cache = None
        # front door (round 12): pluggable scheduler + preemption /
        # deadline window counters (zero + unused when no scheduler is
        # installed — the legacy submit/drain path is bit-identical)
        self._sched = None
        self._preemptions = 0
        self._resumes = 0
        self._preempt_cached_tokens = 0
        self._deadline_requests: dict[str, int] = {}
        self._deadline_misses: dict[str, int] = {}
        self._lane_ttft: dict[str, list] = {}
        self._lane_itl: dict[str, list] = {}
        self._t0 = None
        # ---- reliability (r17) ---------------------------------------
        # fault_plan: deterministic seam x occurrence injection (None +
        # unset PADDLE_TPU_FAULT_PLAN = no plan — every seam check is
        # one `is None` branch, the r15 recorder discipline).
        self._faults = resolve_fault_plan(fault_plan)
        # recovery: True (default) runs the recovery ladder — a
        # dispatch exception snapshots + requeues the implicated
        # requests instead of failing every in-flight future; False
        # restores the legacy fail-everything blast radius.
        if recovery is True:
            recovery = RecoveryPolicy()
        elif recovery is False or recovery is None:
            recovery = None
        elif not isinstance(recovery, RecoveryPolicy):
            raise TypeError(f"recovery must be a RecoveryPolicy or a "
                            f"bool, got {type(recovery).__name__}")
        self._recovery = recovery
        # journal: crash-consistent session journal (path or
        # SessionJournal); every accepted request and emitted token is
        # recorded, recover_from_journal() re-admits the interrupted.
        if isinstance(journal, (str, os.PathLike)):
            journal = SessionJournal(journal)
        elif journal is not None and not isinstance(journal,
                                                    SessionJournal):
            raise TypeError(f"journal must be a SessionJournal or a "
                            f"path, got {type(journal).__name__}")
        self._journal = journal
        # shed_queue_depth: admission shedding — a submit arriving
        # while >= this many requests are queued raises AdmissionShed
        # with a retry-after hint (None = never shed).
        if shed_queue_depth is not None and int(shed_queue_depth) < 1:
            raise ValueError(f"shed_queue_depth must be >= 1, "
                             f"got {shed_queue_depth}")
        self._shed_depth = (None if shed_queue_depth is None
                            else int(shed_queue_depth))
        self._fault_streak: dict[str, int] = {}  # rid -> consecutive
        self._consec_failures = 0                # failing dispatches
        self._any_timeouts = False  # set once a timed request is seen
        # SLO engine (ISSUE 14): declarative objectives over
        # TTFT/ITL/availability/goodput evaluated from sliding-window
        # reservoirs with multi-window burn rates; None (default) =
        # every feed site is one `is None` branch, the telemetry
        # discipline. True = observability.slo.default_slos().
        if slos is None or slos is False:
            self._slo = None
        elif isinstance(slos, SLOEngine):
            self._slo = slos
        elif slos is True:
            self._slo = SLOEngine(True)
        else:
            self._slo = SLOEngine(slos)
        # goodput-delta marks for the per-round SLO feed
        self._slo_good_mark = (0, 0)  # (tokens_out, decoded)
        # replica name a fleet wrapper sets (fleet.Replica) — stamps
        # trace events/spans so cross-replica assembly can tell the
        # in-process engines apart
        self.trace_name = None
        self._last_recovery = None  # {"ts","recovered_from","failures"}
        self._last_error_info = None  # structured degraded_reason
        # fleet round (r18): host ops the ENGINE THREAD executes at the
        # next round boundary (device state is only ever touched from
        # that thread — migration imports/exports queue here), and the
        # drain flag readiness() reports (live but not accepting new
        # placements).
        self._host_ops: list = []
        self._draining = False
        # elastic fleet (ISSUE 20): proof that warm_buckets() completed
        # before start() — the router's add_replica readiness gate
        # reads it, so a fresh replica never compiles inside a request
        # window
        self._warm_ran = False
        # window counters (reset_stats-coherent)
        self._faults_injected = 0
        self._dispatch_retries = 0
        self._recoveries = 0
        self._quarantined = 0
        self._timeouts = 0
        self._sheds = 0
        # ---- operations plane (ISSUE 10) -----------------------------
        # expose_port: None + PADDLE_TPU_METRICS_PORT unset = no ops
        # plane (the exact pre-round path: a disabled flight recorder
        # is one bool check per hook, no threads, no sockets).
        # expose_port=0 binds an ephemeral port (tests); the env var is
        # the production switch that needs no code change.
        if expose_port is None:
            env_port = os.environ.get(ENV_METRICS_PORT, "")
            expose_port = int(env_port) if env_port else None
        self._ops_progress = 0  # bumped on every dispatch/admission;
        self._last_error = None  # the stall watchdog samples it
        if isinstance(flight_recorder, _flight.FlightRecorder):
            self._recorder = flight_recorder
        else:
            self._recorder = _flight.FlightRecorder(
                enabled=bool(flight_recorder)
                or expose_port is not None)
        self.stall_timeout_s = float(stall_timeout_s)
        self._watchdog = None
        self.exporter = None
        # ---- attribution + capacity (ISSUE 17) -----------------------
        # attribution: None auto-enables with the ops plane or a live
        # metrics registry (the cost plane rides the telemetry
        # opt-in); True/False force. The ledger attaches to the cache
        # BEFORE any allocation, so block ownership is complete from
        # block one and the conservation invariants hold exactly.
        if attribution is None:
            attribution = expose_port is not None or _metrics.enabled()
        self._ledger = ResourceLedger() if attribution else None
        self.cache.ledger = self._ledger
        self._attr_parts = None  # parts of the dispatch in flight
        self._wire_mark = None   # decoder wire-byte level before it
        # deterministic pressure-signal bus: always constructed (one
        # sample is cheap and pull-only); auto-sampled at round
        # boundaries only when the telemetry plane is on, and always
        # sampled fresh by capacity_snapshot() / the /capacity
        # endpoint. Schema is the ROADMAP-3 Autoscaler contract.
        self._capacity = PressureSignals({
            "pool": self._cap_pool,
            "tier": self._cap_tier,
            "queues": self._cap_queues,
            "admission": self._cap_admission,
            "slo": self._cap_slo,
        })
        self._cap_auto = (self._recorder.enabled
                          or expose_port is not None)
        # tier telemetry: demote/promote land in the flight recorder
        # ring and the trace stream (kv_tier_demote / kv_tier_promote)
        if self.cache.tier is not None:
            self.cache.on_tier_event = self._on_tier_event
        # process-wide compile accounting: this engine answers "am I
        # serving live work" for the in-flight label, mirrors compile
        # events into its flight recorder, and windows the counter for
        # stats()["compiles"] (weakrefs — no unregister needed)
        _compile_tracker.register_in_flight_probe(self._ops_in_flight)
        _compile_tracker.add_listener(self._on_compile_event)
        self._compile_mark = _compile_tracker.mark()
        _gc_tracker.install()   # a round reads the collector's seconds
        if expose_port is not None:
            # asking for a scrape endpoint IS opting into metrics — a
            # /metrics page of zeros because the registry gate stayed
            # closed would be the least debuggable outcome of all
            _metrics.REGISTRY.enable()
            self._watchdog = _flight.StallWatchdog(
                lambda: self._ops_progress, self._ops_in_flight,
                timeout=self.stall_timeout_s,
                on_stall=self._on_stall).start()
            from ..observability.exporter import OpsEndpoint

            self.exporter = OpsEndpoint(
                statusz_fn=self.statusz,
                healthz_fn=self.health,
                livez_fn=self.liveness,
                readyz_fn=self.readiness,
                slo_fn=(self.slo_report if self._slo is not None
                        else None),
                capacity_fn=self.capacity_snapshot).start(
                    port=expose_port)
            # pull-time health gauge; like the watchdog heartbeat
            # gauge, it follows the most recently built ops-plane
            # server when several are live
            _metrics.REGISTRY.gauge_fn(
                "serving_health_state",
                "engine health (0 ok, 1 degraded, 2 stalled) of the "
                "most recent ops-plane server",
                lambda: HEALTH_CODES[self.health()[0]])

    # ---- operations plane (ISSUE 10) -----------------------------------
    def _ops_in_flight(self):
        """True while the engine has live work: busy slots or queued
        requests. Read lock-free from watchdog/compile-tracker threads
        (GIL-atomic loads; staleness only delays detection one poll)."""
        if self._stop:
            # a stopped/killed engine can never dispatch again — a
            # kill() leaves its slots occupied by design (futures
            # unresolved for journal takeover), and reporting that as
            # "in flight" forever would poison the process-wide
            # compile tracker's in_flight label for every later server
            return False
        if any(s is not None for s in self._slots):
            return True
        if self._queue:
            return True
        if self._sched is not None:
            try:
                return self._sched.depth() > 0
            except Exception:  # noqa: BLE001 — a torn-down scheduler
                return False  # must not break health checks
        return False

    def _on_compile_event(self, ev):
        # a finished compile IS progress — without this, the dispatch
        # that just compiled reads as a stall to the watchdog (a
        # compile that itself exceeds the stall threshold still trips,
        # which is exactly the incident compile tracking exists for)
        self._ops_progress += 1
        self._recorder.record(
            "compile", program=ev["program"],
            dur_s=round(ev["dur_s"], 4), in_flight=ev["in_flight"],
            shard=ev["shard"])
        # attribution: an in-window compile is charged to the requests
        # the triggering dispatch computed for (compile wall time is
        # INSIDE the measured dispatch time — a parallel annotation,
        # like the trace assembler's compile_overlap_ms, not a
        # subtraction from it)
        if self._ledger is not None and self._attr_parts:
            self._ledger.charge_compile(int(ev["dur_s"] * 1e9),
                                        self._attr_parts)

    def _on_stall(self):
        self._recorder.record("stall", progress=self._ops_progress,
                              free_blocks=self.cache.
                              available_block_count)
        if self._recorder.enabled:
            self._recorder.dump(trigger="stall")

    def _on_tier_event(self, kind, **fields):
        """Cache tier callback -> flight recorder ring + trace event
        (literal names so the metric/span docs checker sees them)."""
        if kind == "demote":
            self._recorder.record("kv_tier_demote", **fields)
            _tracing.event("kv_tier_demote", **fields)
        elif kind == "tier_promote":
            # one aggregated promote BATCH (the whole tier-chain walk
            # of an attach or a prefetch tick): its wall time is split
            # OUT of the admission span into this dedicated event, so
            # the phase-tiling invariant holds — admission no longer
            # absorbs promotion time it didn't spend. Overlapped
            # batches (prefetch-ahead) also feed the overlap histogram:
            # copy time hidden behind device execution.
            if fields.get("overlapped"):
                dur = float(fields.get("dur_s", 0.0))
                _m_promote_overlap.observe(dur)
                with self._lock:
                    self._prefetch_overlap_s += dur
            if self._promote_ctx is not None:
                fields = dict(fields, request_id=self._promote_ctx)
            self._recorder.record("tier_promote", **fields)
            _tracing.event("tier_promote", **fields)
        else:
            self._recorder.record("kv_tier_promote", **fields)
            _tracing.event("kv_tier_promote", **fields)

    # ---- tier prefetch-ahead (memory-flat long-context round) -----------
    def _tier_prefetch_tick(self):
        """Promote the next queued requests' cold tier blocks into the
        device pool — called right after a round's dispatch is issued,
        so the host-side tier decodes overlap the device execution
        (pure host work: no device state is read or written). MOVE
        semantics are untouched — `prefetch_promote` runs the same
        promote walk an attach would, just earlier; a prefetched block
        that is reclaimed before admission simply re-promotes (or
        re-computes) on attach, token-identically. Budgeted by the
        FREE list only: prefetch fills idle capacity and never
        reclaims retained content from live traffic."""
        if not self._prefetch_look or self.cache.tier is None:
            return
        with self._lock:
            if self._sched is not None:
                # front-door lanes reorder admission: ask the
                # scheduler for its likely-next candidates
                # (LaneScheduler.peek — advisory order, no pops, no
                # rate charges). A scheduler without a peek hook
                # keeps the old skip behavior.
                peek = getattr(self._sched, "peek", None)
                if peek is None:
                    return
                heads = [r for r in peek(time.perf_counter(),
                                         self._prefetch_look)
                         if r.rid not in self._prefetch_done]
            else:
                heads = [r for r in self._queue[:self._prefetch_look]
                         if r.rid not in self._prefetch_done]
        budget = self.cache.free_block_count
        for r in heads:
            if budget <= 0:
                break
            prompt = (r.resume_ids if r.resume_ids is not None
                      else r.ids)
            hashes, _tokens, _nbytes = self.cache.prefetch_promote(
                prompt, limit_blocks=budget)
            if hashes:
                budget -= len(hashes)
                _m_prefetch_issued.inc(len(hashes))
                with self._lock:
                    self._prefetch_issued += len(hashes)
                    self._prefetched.setdefault(
                        r.rid, set()).update(hashes)
            else:
                # dry walk: nothing tiered (left) along this chain —
                # skip the rid until settlement, so an idle queue
                # doesn't re-hash long prompts every round
                with self._lock:
                    self._prefetch_done.add(r.rid)

    def _settle_prefetch_locked(self, rid):
        """Admission settlement: prefetched blocks still device-
        resident are HITS (their promotion wall time was hidden);
        blocks pool pressure reclaimed meanwhile are wasted. Caller
        holds the lock."""
        self._prefetch_done.discard(rid)
        pref = self._prefetched.pop(rid, None)
        if not pref:
            return
        hit = self.cache.device_resident_count(pref)
        wasted = len(pref) - hit
        self._prefetch_hits += hit
        self._prefetch_wasted += wasted
        if hit:
            _m_prefetch_hit.inc(hit)
        if wasted:
            _m_prefetch_wasted.inc(wasted)

    def _abandon_prefetch_locked(self, rid):
        """A queued request left without admission (timeout, stop) —
        everything prefetched for it is wasted. The blocks themselves
        stay parked in prefix-index retention and age out like any
        other published content. Caller holds the lock."""
        self._prefetch_done.discard(rid)
        pref = self._prefetched.pop(rid, None)
        if pref:
            self._prefetch_wasted += len(pref)
            _m_prefetch_wasted.inc(len(pref))

    # ---- capacity signals (ISSUE 17) ------------------------------------
    def _cap_pool(self):
        return self.cache.headroom()

    def _cap_tier(self):
        return self.cache._tier_stats()

    def _cap_queues(self):
        out = {"queue_depth": len(self._queue),
               "busy_slots": sum(1 for s in self._slots if s is not None),
               "max_slots": self.max_slots,
               "lanes": {}, "tenants": {}}
        sched = self._sched
        if sched is not None:
            try:
                out["queue_depth"] = sched.depth()
                out["lanes"] = sched.lane_depths()
                out["tenants"] = sched.tenant_depths()
            except Exception:  # noqa: BLE001 — a torn-down scheduler
                pass           # must not poison the snapshot
        return out

    def _cap_admission(self):
        info = self._last_error_info
        return {
            "sheds": self._sheds,
            "shed_queue_depth": self._shed_depth,
            "draining": self._draining,
            # structured BlockPoolExhausted pressure (r18): how short
            # the last failed allocation fell — zeroed when healthy
            "exhaustion_needed": (info or {}).get("needed", 0),
            "exhaustion_available": (info or {}).get("available", 0),
        }

    def _cap_slo(self):
        if self._slo is None:
            return {"enabled": False, "slos": []}
        rep = self._slo.report()
        return {"enabled": True, "worst": rep["worst"],
                "slos": [{"name": s["name"], "state": s["state"],
                          "burn_fast": s["burn_fast"],
                          "burn_slow": s["burn_slow"],
                          "budget_remaining": s["budget_remaining"]}
                         for s in rep["slos"]]}

    def capacity_snapshot(self):
        """One fresh `PressureSignals` snapshot — the `/capacity`
        endpoint payload and the fleet router's per-replica feed
        (schema_version 1; the ROADMAP-3 Autoscaler input)."""
        return self._capacity.sample()

    def _maybe_sample_capacity(self):
        """Round-boundary auto-sample (telemetry plane on only): a
        min-interval-gated snapshot recorded into the flight-recorder
        ring, so stall/exception dumps carry the pressure history."""
        if not self._cap_auto:
            return
        snap = self._capacity.maybe_sample()
        if snap is None:
            return
        pool = snap.get("pool", {})
        fc = snap.get("forecast", {})
        self._recorder.record(
            "capacity_sample",
            free_blocks=pool.get("free_blocks"),
            available_blocks=pool.get("available_blocks"),
            queue_depth=snap.get("queues", {}).get("queue_depth"),
            exhaustion_eta_s=fc.get("exhaustion_eta_s"))

    # ---- attribution (ISSUE 17) -----------------------------------------
    def _charge_dispatch(self, dur_s, parts):
        """Charge one dispatch's wall time to its resident requests
        and reconcile the collective-wire delta (sharded decode). The
        same `parts` drove any in-window compile charge — see
        `_on_compile_event`."""
        led = self._ledger
        if led is None or not parts:
            return
        led.charge_device(int(dur_s * 1e9), parts)
        if self._wire_mark is not None:
            total = self._decoder.wire_stats()["bytes_total"]
            delta = total - self._wire_mark
            self._wire_mark = total
            if delta > 0:
                led.charge_wire(delta, parts, kind="collective")

    def _attr_begin(self, parts):
        """Note the dispatch about to run (compile-charge target) and,
        once, the decoder's wire-byte level: from there on every byte
        is charged at the next read (`_charge_dispatch` moves the
        level), also those of a dispatch issued before that read. The
        caller has a ledger: without one no `parts` are built."""
        self._attr_parts = parts
        if self._decoder.tp_degree > 1 and self._wire_mark is None:
            self._wire_mark = self._decoder.wire_stats()["bytes_total"]

    @staticmethod
    def _cost_parts(pairs):
        """Apportionment rows [(tenant, rid, weight)] from (req,
        weight) pairs — weight is the request's share of the dispatch
        (tokens fed / tokens decoded / drafts verified)."""
        return [(r.meta.tenant if r.meta is not None else "default",
                 r.rid, int(w)) for r, w in pairs]

    # ---- causal tracing + SLOs (ISSUE 14) -------------------------------
    def _tr(self, req):
        """The trace-stamping attrs (trace_id / hop / cause / replica)
        one request's events, spans, and flight-recorder entries
        carry."""
        t = req.trace
        if t is None:
            return {}
        return t.attrs(replica=self.trace_name)

    def _rattr(self):
        """Replica attr for batch dispatch spans — lets the timeline
        exporter and cross-replica assembly tell in-process engines
        apart (empty off-fleet: no noise on a bare server)."""
        return ({"replica": self.trace_name}
                if self.trace_name is not None else {})

    def _slo_latency(self, kind, value_s, req, n=1):
        """Feed one ttft/itl observation (caller checked _slo)."""
        meta = req.meta
        self._slo.observe(kind, value_s=value_s, n=n,
                          lane=meta.lane if meta is not None else None,
                          tenant=(meta.tenant if meta is not None
                                  else None),
                          replica=self.trace_name)

    def _slo_avail(self, req, ok):
        """Feed one availability outcome (request finished vs failed
        terminally: quarantine / timeout / legacy dispatch failure)."""
        if self._slo is None:
            return
        meta = req.meta
        self._slo.observe("availability", good=ok,
                          lane=meta.lane if meta is not None else None,
                          tenant=(meta.tenant if meta is not None
                                  else None),
                          replica=self.trace_name)

    def _slo_goodput_round(self):
        """Per-round goodput feed: deltas of emitted vs decoded tokens
        since the last round (caller holds the lock and checked
        _slo)."""
        good0, dec0 = self._slo_good_mark
        good = max(0, self._tokens_out - good0)
        waste = max(0, (self._decoded_tokens - dec0)
                    - (self._tokens_out - good0))
        self._slo_good_mark = (self._tokens_out, self._decoded_tokens)
        if good or waste:
            self._slo.observe_counts("goodput", good, waste,
                                     replica=self.trace_name)

    def slo_report(self):
        """The /slo endpoint payload (`SLOEngine.report()` shape); the
        empty all-ok shape when the server runs without SLOs."""
        if self._slo is None:
            return {"slos": [], "worst": "ok", "paging": []}
        return self._slo.report()

    def export_timeline(self, path):
        """Write this engine's Chrome/Perfetto trace-event timeline
        (span sink + flight-recorder ring) to `path`; returns the
        event count. Fleet-wide timelines come from
        `FleetRouter.export_timeline`, which lays every replica out as
        its own process track."""
        from ..observability import timeline as _timeline

        name = self.trace_name or "engine"
        return _timeline.write_chrome_trace(
            path, recorders={name: self._recorder.events()},
            default_name=name)

    def health(self):
        """(status, detail) for /healthz: "stalled" while the watchdog
        sees pending work with no dispatch progress (503 — drain me),
        "degraded" after an engine dispatch exception — sticky only
        while UNRECOVERED: a clean recovery (first successful dispatch
        after the failure) or reset_stats() returns it to "ok", and
        the detail then carries the degradation reason it recovered
        from plus the recovery timestamp (r17)."""
        detail = {
            "engine_running": self._thread is not None,
            "progress": self._ops_progress,
            "stalls": self._watchdog.stalls if self._watchdog else 0,
        }
        if self._last_recovery is not None:
            detail["last_recovery"] = dict(self._last_recovery)
        if self._watchdog is not None and self._watchdog.stalled:
            detail["stall_timeout_s"] = self.stall_timeout_s
            return "stalled", detail
        if self._last_error is not None:
            detail["last_error"] = self._last_error
            detail["degraded_reason"] = self._last_error
            if self._last_error_info is not None:
                # machine-readable degradation (r18 satellite): the
                # seam, type, and — for pool exhaustion — the
                # structured needed/available shortfall
                detail["last_error_info"] = dict(self._last_error_info)
            return "degraded", detail
        return "ok", detail

    def liveness(self):
        """(live, detail) for /healthz/live — the ENGINE LOOP is alive
        (started, not stopped, thread running). Degraded or stalled is
        still live; dead is the fleet router's FAIL-OVER signal (its
        resident sessions re-admit elsewhere), where not-ready is
        merely its stop-routing signal. Split-health satellite, r18."""
        alive = (not self._stop and self._thread is not None
                 and self._thread.is_alive())
        return alive, {"engine_running": alive,
                       "stopped": self._stop,
                       "progress": self._ops_progress}

    def readiness(self):
        """(ready, detail) for /healthz/ready — alive AND accepting
        admissions: not draining (`set_draining`), not stalled. A
        router keeps sessions ON a not-ready replica (they finish or
        drain) but places no new ones — "drain, don't route" vs the
        liveness signal's "dead, fail over"."""
        alive, detail = self.liveness()
        stalled = self._watchdog is not None and self._watchdog.stalled
        ready = alive and not stalled and not self._draining
        detail = dict(detail, stalled=stalled, draining=self._draining,
                      warmed=self._warm_ran,
                      queue_depth=(self._sched.depth()
                                   if self._sched is not None
                                   else len(self._queue)))
        return ready, detail

    def set_draining(self, draining=True):
        """Mark the engine drain-only: /healthz/ready answers 503 (a
        router stops placing NEW sessions here) while residents keep
        decoding to completion. Liveness and the legacy /healthz are
        untouched. Returns self."""
        self._draining = bool(draining)
        self._recorder.record("draining", draining=self._draining)
        return self

    def statusz(self):
        """Live JSON engine state for /statusz: per-slot residency plus
        the full stats() blocks (pool, prefix cache, quantization,
        sharding, speculation, goodput, lanes/tenants when a front
        door is installed) and the flight-recorder/compile summaries."""
        with self._lock:
            slots = []
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                meta = s["req"].meta
                slots.append({
                    "slot": i, "request_id": s["req"].rid,
                    "seq": s["seq"], "prompt_len": int(s["prompt"].size),
                    "fed": int(s["fed"]), "tokens": len(s["toks"]),
                    "budget": s["budget"],
                    "phase": ("decode" if s["fed"] >= s["prompt"].size
                              else "prefill"),
                    "lane": meta.lane if meta else None,
                    "tenant": meta.tenant if meta else None,
                })
        status, detail = self.health()
        live, live_detail = self.liveness()
        ready, ready_detail = self.readiness()
        return {
            "server": "paged",
            "health": {"status": status, **detail},
            # split health semantics (r18): what /healthz/live and
            # /healthz/ready answer, inlined for one-stop debugging
            "liveness": {"live": live, **live_detail},
            "readiness": {"ready": ready, **ready_detail},
            "slots": slots,
            "max_slots": self.max_slots,
            "engine": self.stats(),
            "flight_recorder": self._recorder.stats(),
            "last_dump": self._recorder.last_dump,
        }

    def dump_flight_recorder(self):
        """Manual flight-recorder dump (also triggered automatically by
        a stall or an engine exception)."""
        return self._recorder.dump(trigger="manual")

    def _engine_exception(self, where, e, request_ids=()):
        """Shared dispatch-exception bookkeeping: health goes degraded
        (sticky until reset_stats), the exception counts per dispatch
        kind, and the flight recorder auto-dumps — the post-hoc record
        of the rounds that led here."""
        self._last_error = f"{where}: {type(e).__name__}: {e}"
        # structured twin of the string (r18 satellite): /statusz and
        # /healthz carry machine-readable fields — a router's passive
        # health signal parses these, not the message. Pool exhaustion
        # additionally carries its needed/available pressure fields.
        info = {"where": where, "error_type": type(e).__name__,
                "message": str(e)}
        if isinstance(e, BlockPoolExhausted):
            info["needed"] = e.needed
            info["available"] = e.available
        self._last_error_info = info
        _m_engine_exc.labels(where=where).inc()
        self._recorder.record("engine_exception", where=where,
                              error=self._last_error,
                              request_ids=list(request_ids))
        if self._recorder.enabled:
            self._recorder.dump(trigger="engine_exception")

    # ---- reliability (r17) ---------------------------------------------
    def _maybe_fault(self, seam):
        """Deterministic fault-injection point: one `is None` check
        when no plan is installed; otherwise poll the plan's seam x
        occurrence schedule and turn a scheduled fault into its effect
        (raise / simulated pool exhaustion / watchdog-visible sleep)."""
        plan = self._faults
        if plan is None:
            return
        f = plan.poll(seam)
        if f is None:
            return
        with self._lock:
            self._faults_injected += 1
        _m_fault_injected.labels(seam=seam).inc()
        self._recorder.record("fault_injected", seam=seam, kind=f.kind,
                              occurrence=f.index)
        _tracing.event("fault_injected", seam=seam, kind=f.kind,
                       occurrence=f.index)
        if f.kind == "slow":
            time.sleep(f.delay_s)
            return
        if f.kind == "exhausted":
            raise BlockPoolExhausted(
                f"injected fault at seam '{seam}' (occurrence "
                f"{f.index}): simulated pool exhaustion")
        raise plan.make_fault(f)

    def _recover_slot(self, i, where):
        """Snapshot one implicated slot for retry (the recovery
        ladder's requeue step): roll the sequence back to its DURABLE
        length (K/V provably written by completed dispatches — the
        failing dispatch may not have written what `ensure_many`
        already grew room for), publish the live prefix through the
        swap-out machinery when prefix caching is on, free the slot,
        and hand back the request with its resume state (generated
        tokens + resume prompt), exactly the preemption shape the r12
        parity suite proves token-identical. Returns None when the
        slot already emptied (the drain completed its request)."""
        s = self._slots[i]
        if s is None:
            return None
        seq, req = s["seq"], s["req"]
        toks = s["toks"]
        in_decode = s["fed"] >= s["prompt"].size
        durable = (s["pos"] + len(toks) - 1 if in_decode and toks
                   else int(s["fed"]))
        known = (np.concatenate([req.ids, np.asarray(toks, np.int32)])
                 if toks else req.ids)
        if self.cache.has_seq(seq):
            live = self.cache.seq_len(seq)
            durable = max(0, min(live, durable))
            if durable < live:
                self.cache.truncate_seq(seq, durable)
            if self.enable_prefix_cache and durable > 0:
                self.cache.swap_out_seq(seq, known[:durable])
            else:
                self.cache.free(seq)
        self._worst.pop(seq, None)
        self._slots[i] = None
        self._sp_store.clear_slot(i)
        req.gen0 = tuple(toks)
        req.resume_ids = known
        if req.trace is not None:
            # causal tracing: a fault-retry requeue starts a new hop —
            # the next residency's events carry hop+1 / cause "retry"
            req.trace = req.trace.child("retry")
        self._recorder.record(
            "recover_requeue", request_id=req.rid, slot=i, seq=seq,
            where=where, tokens_done=len(toks), durable=int(durable),
            **self._tr(req))
        _tracing.event("recover_requeue", request_id=req.rid, slot=i,
                       seq=seq, where=where, **self._tr(req))
        return req

    def _quarantine_slot(self, i, where, e, failures):
        """Give up on ONE request: fail its future with a diagnostic
        naming the fault seam, free its slot and blocks, and count it.
        Everything co-resident is untouched."""
        s = self._slots[i]
        seq, req = s["seq"], s["req"]
        if self.cache.has_seq(seq):
            self.cache.free(seq)
        self._worst.pop(seq, None)
        self._slots[i] = None
        self._sp_store.clear_slot(i)
        err = QuarantinedRequest(req.rid, where, failures, e)
        with self._lock:
            self._quarantined += 1
        _m_quarantined.inc()
        if self._journal is not None:
            self._journal.record_done(req.rid, "quarantined")
        self._recorder.record("quarantine", request_id=req.rid, slot=i,
                              seq=seq, seam=where, failures=failures,
                              error=f"{type(e).__name__}: {e}",
                              **self._tr(req))
        cost = (self._ledger.request_done(req.rid)
                if self._ledger is not None else None)
        _tracing.event("quarantined", request_id=req.rid, slot=i,
                       seam=where, failures=failures, cost=cost,
                       **self._tr(req))
        self._slo_avail(req, False)
        _logger.error("quarantined request %s after %d consecutive "
                      "failure(s) at seam %s: %s", req.rid, failures,
                      where, e)
        req.future.set_exception(err)

    def _dispatch_failure(self, where, e, slot_idx):
        """The engine's dispatch-exception path. With recovery OFF,
        the legacy blast radius: every request in the failing dispatch
        fails. With the recovery ladder ON (default): snapshot every
        implicated request through the swap-out machinery and requeue
        it at the FRONT of its queue, quarantine at most ONE request
        whose consecutive-failure streak crossed the policy threshold
        (highest streak, lowest slot on ties), rebuild the dispatch
        state (async chain, device-arg caches), and back off capped-
        exponentially before the loop retries."""
        rids = [self._slots[i]["req"].rid for i in slot_idx
                if self._slots[i] is not None]
        self._engine_exception(where, e, rids)
        self._newest_out = None   # what was in flight is read or given up
        if self._recovery is None:
            for i in slot_idx:
                s = self._slots[i]
                if s is None:
                    continue
                if self.cache.has_seq(s["seq"]):
                    self.cache.free(s["seq"])
                self._worst.pop(s["seq"], None)
                self._slo_avail(s["req"], False)
                if self._ledger is not None:
                    self._ledger.request_done(s["req"].rid)
                s["req"].future.set_exception(e)
                self._slots[i] = None
                self._sp_store.clear_slot(i)
            return
        pol = self._recovery
        # async: resolve the round already in flight FIRST, so the
        # resume snapshots include its tokens (it dispatched before
        # the failure and its outputs are real)
        self._drain_pending("failure")
        with self._lock:
            self._dispatch_retries += 1
            self._consec_failures += 1
            consec = self._consec_failures
        _m_dispatch_retries.inc()
        live = [i for i in slot_idx if self._slots[i] is not None]
        for i in live:
            rid = self._slots[i]["req"].rid
            self._fault_streak[rid] = self._fault_streak.get(rid, 0) + 1
        suspects = [i for i in live
                    if self._fault_streak[self._slots[i]["req"].rid]
                    >= pol.quarantine_after]
        if suspects:
            victim = max(suspects, key=lambda i: (
                self._fault_streak[self._slots[i]["req"].rid], -i))
            streak = self._fault_streak.pop(
                self._slots[victim]["req"].rid)
            self._quarantine_slot(victim, where, e, streak)
            live.remove(victim)
        requeued = []
        for i in live:
            req = self._recover_slot(i, where)
            if req is not None:
                requeued.append(req)
        with self._lock:
            if self._sched is not None:
                now = time.perf_counter()
                # requeue() prepends: reversed keeps original order
                for req in reversed(requeued):
                    self._sched.requeue(req, now)
            else:
                for req in reversed(requeued):
                    self._queue.insert(0, req)
                _m_queue_depth.labels(server="paged").set(
                    len(self._queue))
            self._lock.notify()
        # rebuild dispatch state: the double-buffer chain and the
        # steady-state device-argument caches may name freed slots
        self._pending = None
        self._carry = None
        self._args_cache = None
        self._tables_cache = None
        delay = pol.backoff_s(consec)
        if delay > 0:
            with self._lock:
                if not self._stop:
                    self._lock.wait(timeout=delay)

    def _dispatch_ok(self, rids):
        """Success bookkeeping of the recovery ladder: reset the
        dispatched requests' failure streaks, and if this is the first
        success after >= 1 failure, record a CLEAN RECOVERY — health
        returns degraded -> ok, timestamped for /statusz."""
        if self._recovery is None or (self._consec_failures == 0
                                      and not self._fault_streak):
            return
        for rid in rids:
            self._fault_streak.pop(rid, None)
        if self._consec_failures:
            with self._lock:
                self._last_recovery = {
                    "ts": time.time(),
                    "recovered_from": self._last_error,
                    "failures": self._consec_failures,
                }
                self._consec_failures = 0
                self._recoveries += 1
                self._last_error = None  # degraded -> ok
                self._last_error_info = None
            _m_recoveries.inc()
            self._recorder.record(
                "recovered",
                failures=self._last_recovery["failures"],
                recovered_from=self._last_recovery["recovered_from"])
            _tracing.event("recovered",
                           failures=self._last_recovery["failures"])
            _logger.warning(
                "engine recovered after %d failed dispatch(es): %s",
                self._last_recovery["failures"],
                self._last_recovery["recovered_from"])

    def _fail_timeout_req(self, req, now):
        """Fail one expired request (already detached from any queue
        or slot). Caller holds the lock."""
        self._abandon_prefetch_locked(req.rid)
        self._timeouts += 1
        _m_timeouts.inc()
        if self._journal is not None:
            self._journal.record_done(req.rid, "timeout")
        self._recorder.record("request_timeout", request_id=req.rid,
                              waited_s=round(now - req.t_submit, 4),
                              timeout_s=req.timeout_s, **self._tr(req))
        cost = (self._ledger.request_done(req.rid)
                if self._ledger is not None else None)
        _tracing.event("request_timeout", request_id=req.rid,
                       waited_s=now - req.t_submit, cost=cost,
                       **self._tr(req))
        self._slo_avail(req, False)
        req.future.set_exception(RequestTimeout(
            req.rid, now - req.t_submit, req.timeout_s))

    def _expire_timeouts_locked(self, now):
        """Cancel every queued or resident request past its
        per-request timeout_s — SLOT-FREEING: a resident victim's
        blocks return to the pool immediately. Caller holds the
        lock."""
        def dead(r):
            return (r.timeout_s is not None
                    and now - r.t_submit > r.timeout_s)

        expired = [r for r in self._queue if dead(r)]
        if expired:
            for r in expired:
                self._queue.remove(r)
            _m_queue_depth.labels(server="paged").set(len(self._queue))
        if self._sched is not None:
            exp = getattr(self._sched, "expire", None)
            if exp is not None:
                expired.extend(exp(now, dead))
        for r in expired:
            self._fail_timeout_req(r, now)
        if any(s is not None and dead(s["req"]) for s in self._slots):
            self._drain_pending("timeout")  # host state authoritative
            for i, s in enumerate(self._slots):
                if s is None or not dead(s["req"]):
                    continue
                seq, req = s["seq"], s["req"]
                if self.cache.has_seq(seq):
                    self.cache.free(seq)
                self._worst.pop(seq, None)
                self._slots[i] = None
                self._sp_store.clear_slot(i)
                self._fail_timeout_req(req, now)

    def _retry_after_hint_locked(self, depth):
        """Estimated seconds until the queue drains one admission
        slot's worth of work — the AdmissionShed retry hint."""
        lat = sorted(self._lat)
        p50 = lat[len(lat) // 2] if lat else 0.25
        waves = -(-int(depth) // max(1, self.max_slots))
        return max(0.05, p50) * max(1, waves)

    def kill(self):
        """Hard-stop the engine WITHOUT resolving in-flight futures —
        the crash-simulation half of the journal recovery story: after
        kill(), a fresh server built over the same journal re-admits
        every accepted-but-unfinished request via
        `recover_from_journal`. (Graceful shutdown is `stop()`, which
        fails queued futures so no client hangs.)"""
        with self._lock:
            self._stop = True
            self._lock.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=120)
            self._thread = None
        if self._watchdog is not None:
            self._watchdog.stop()
        if self.exporter is not None:
            self.exporter.stop()
        if self._journal is not None:
            self._journal.flush()

    def recover_from_journal(self, journal=None):
        """Re-admit every accepted-but-unfinished request recorded in
        `journal` (default: the server's own). Each re-admission
        resumes from its recorded prompt + emitted tokens with its
        ORIGINAL seed, budget and sampling params, so — the decode
        stack being deterministic — the completed output is
        token-identical to a run that never crashed. Requests whose
        recorded state already satisfies a stop condition (budget
        reached, EOS/stop token emitted) resolve immediately.

        Returns {rid: Future}. Call before or after start()."""
        j = journal if journal is not None else self._journal
        if j is None:
            raise ValueError("no journal: pass one or build the "
                             "server with journal=")
        out = {}
        for ent in j.interrupted():
            if ent.get("trace"):
                # causal tracing: a crash-restart re-admission is a
                # new hop of the SAME trace (cause "retry")
                ent = dict(ent)
                ent["trace"] = TraceContext.from_dict(
                    ent["trace"]).child("retry").to_dict()
            out[ent["rid"]] = self.admit_journal_entry(ent)
        return out

    def admit_journal_entry(self, ent, on_token=None):
        """Re-admit ONE journal-shape session entry (the dict
        `SessionJournal.entry_for`/`interrupted()` produce: rid, ids,
        gen0, budget, seed, sampling, timeout_s, meta?) and return its
        Future — the replica-facing takeover hook (fleet round): a
        router re-places a dead or drained replica's session here with
        the ROUTER-journaled tokens folded into gen0, and the decode
        stack's determinism (counter-based PRNG resuming at step
        len(gen0), residency-invariant positions) makes the completed
        output token-identical to the run that was never interrupted.
        An entry whose recorded tokens already satisfy a stop
        condition resolves immediately. `on_token` streams the
        REMAINING tokens (the re-admission generates from len(gen0)
        on, so nothing already delivered is replayed to the client)."""
        req = self._build_resume_req(ent)
        req.on_token = on_token
        done = self._journal_terminal_reason(req)
        if done is not None:
            # the interruption lost only the terminal record: the
            # request is already complete — resolve without admitting
            if self._journal is not None:
                self._journal.record_done(req.rid, done)
            req.future.set_result(np.concatenate(
                [req.ids, np.asarray(req.gen0, np.int32)])
                if req.gen0 else req.ids.copy())
            return req.future
        with self._lock:
            if self._stop:
                raise RuntimeError("server stopped")
            if self._sched is not None:
                self._sched.on_submit(req, time.perf_counter())
            else:
                self._queue.append(req)
                _m_queue_depth.labels(server="paged").set(
                    len(self._queue))
            if self._journal is not None:
                # re-accept (under the lock, before the loop can
                # admit) with gen0 folded, so a second crash
                # resumes from here, not from the original prompt
                self._journal.record_accept(req)
            self._lock.notify()
        self._recorder.record("journal_readmit", request_id=req.rid,
                              tokens_done=len(req.gen0),
                              **self._tr(req))
        _tracing.event("journal_readmit", request_id=req.rid,
                       tokens_done=len(req.gen0), **self._tr(req))
        return req.future

    # ---- fleet host ops (r18) ------------------------------------------
    def _run_host_ops_locked(self):
        """Execute queued host ops on the engine thread (caller holds
        the lock, the in-flight round is drained): each op may touch
        the cache device arrays safely because nothing else ever does
        between round boundaries."""
        ops, self._host_ops = self._host_ops, []
        for fn, fut in ops:
            try:
                fut.set_result(fn())
            except BaseException as e:  # noqa: BLE001 — the op's
                fut.set_exception(e)    # error belongs to its caller

    def _fail_host_ops_locked(self, exc):
        ops, self._host_ops = self._host_ops, []
        for _fn, fut in ops:
            if not fut.done():
                fut.set_exception(exc)

    def run_host_op(self, fn, timeout=None):
        """Run `fn()` on the ENGINE thread at the next round boundary
        (under the engine lock, with any async round drained) and
        return its result — the safe way for another thread to touch
        the paged cache's device arrays (migration import/export). On
        a not-yet-started server the op runs inline. Never call from
        an engine callback (on_token/scheduler) — that deadlocks."""
        with self._lock:
            if self._stop:
                raise RuntimeError("server stopped")
            if self._thread is None:
                return fn()
            f = Future()
            self._host_ops.append((fn, f))
            self._lock.notify()
        return f.result(timeout=timeout)

    def export_session(self, rid, include_kv=True):
        """Planned-migration SOURCE hook (fleet round): atomically
        detach one request — resident (preempt-style swap-out, its
        live K/V published through the prefix index when caching is
        on) or still queued — and return `(entry, kv_payload)`.
        `entry` is the journal-shape resume state
        `admit_journal_entry` re-admits on the target replica;
        `kv_payload` is `PagedKVCache.export_prefix` of the swapped-
        out chain (None when caching is off, the request never
        prefilled, or include_kv=False) — imported on the target, the
        session resumes with ZERO prefill recompute. The request's
        future on THIS server is abandoned (the router owns the
        client-facing future) and its journal entry closes with
        reason "migrated". Raises KeyError for an unknown or already-
        finished rid."""
        def op():
            for i, s in enumerate(self._slots):
                if s is not None and s["req"].rid == rid:
                    req = self._preempt_slot_locked(i, why="migration")
                    if req is None:
                        break  # the drain completed it: fall through
                    ent = SessionJournal.entry_for(req)
                    payload = None
                    if include_kv and self.enable_prefix_cache:
                        payload = self.cache.export_prefix(
                            req.resume_ids)
                    if payload is not None and self._ledger is not None:
                        # migration wire bytes, charged export-side to
                        # the departing session's tenant
                        nbytes = (_payload_nbytes(payload["k"])
                                  + _payload_nbytes(payload["v"]))
                        self._ledger.charge_wire(
                            nbytes, self._cost_parts([(req, 1)]),
                            kind="migration")
                    if self._journal is not None:
                        self._journal.record_done(rid, "migrated")
                    if self._ledger is not None:
                        # the session leaves this replica — close its
                        # per-request view (tenant window totals stay)
                        self._ledger.request_done(rid)
                    self._recorder.record(
                        "migrate_out", request_id=rid,
                        tokens_done=len(req.gen0),
                        kv_tokens=(len(payload["tokens"])
                                   if payload else 0), **self._tr(req))
                    _tracing.event("migrate_out", request_id=rid,
                                   tokens_done=len(req.gen0),
                                   **self._tr(req))
                    return ent, payload
            req = None
            if self._sched is not None:
                exp = getattr(self._sched, "expire", None)
                if exp is not None:
                    hits = exp(time.perf_counter(),
                               lambda r: r.rid == rid)
                    req = hits[0] if hits else None
            else:
                req = next((q for q in self._queue if q.rid == rid),
                           None)
                if req is not None:
                    self._queue.remove(req)
                    _m_queue_depth.labels(server="paged").set(
                        len(self._queue))
            if req is None:
                raise KeyError(
                    f"unknown or already-finished request {rid!r} in "
                    f"export_session()")
            ent = SessionJournal.entry_for(req)
            if self._journal is not None:
                self._journal.record_done(rid, "migrated")
            if self._ledger is not None:
                self._ledger.request_done(rid)
            self._recorder.record("migrate_out", request_id=rid,
                                  tokens_done=len(req.gen0),
                                  kv_tokens=0, **self._tr(req))
            return ent, None
        return self.run_host_op(op)

    def import_kv_payload(self, payload, owner=None):
        """Planned-migration TARGET hook: install an `export_prefix`
        payload into this server's pool (on the engine thread — see
        `run_host_op`) so the follow-up `admit_journal_entry` attaches
        it instead of re-prefilling. Returns tokens imported; raises
        BlockPoolExhausted when the pool cannot hold the chain (the
        router then falls back to plain journal replay). `owner` is
        the attribution (tenant, rid) the imported blocks' residency
        charges to on THIS replica."""
        return self.run_host_op(
            lambda: self.cache.import_prefix(payload, owner=owner))

    def _build_resume_req(self, ent):
        """One journal entry -> a resume-state `_Req` (bypasses
        submit(): the recorded seed must win over auto-derivation)."""
        sampling = (SamplingParams(**{k: tuple(v) if isinstance(v, list)
                                      else v
                                      for k, v in ent["sampling"].items()})
                    if ent.get("sampling") else self._default_sampling)
        if sampling.stop_strings and self._detok is None:
            raise ValueError(
                f"journal request {ent['rid']!r} uses stop_strings but "
                f"this server has no detokenizer (pass detokenize=)")
        meta = None
        if ent.get("meta"):
            m = ent["meta"]
            meta = RequestMeta(lane=m.get("lane", "interactive"),
                               tenant=m.get("tenant", "default"),
                               deadline_s=m.get("deadline_s"),
                               cost=int(m.get("cost", 0)))
        req = _Req(ids=np.asarray(ent["ids"], np.int32),
                   future=Future(), t_submit=time.perf_counter(),
                   rid=ent["rid"], sampling=sampling, meta=meta,
                   timeout_s=ent.get("timeout_s"))
        req.seed = int(ent["seed"])
        req.budget = int(ent["budget"])
        # causal tracing: a journal-shape entry carries the trace
        # context across restarts / replicas / migrations; without one
        # (pre-r19 journal) the resumed request starts a fresh trace
        req.trace = (TraceContext.from_dict(ent["trace"])
                     if ent.get("trace") else TraceContext.mint())
        gen0 = [int(t) for t in ent.get("gen0", [])]
        if gen0:
            req.gen0 = tuple(gen0)
            req.resume_ids = np.concatenate(
                [req.ids, np.asarray(gen0, np.int32)])
        if req.timeout_s is not None:
            self._any_timeouts = True
        return req

    def _journal_terminal_reason(self, req):
        """Whether a journal-recovered request's recorded tokens
        already satisfy a stop condition (the crash lost only the
        terminal record): returns the stop reason or None."""
        if not req.gen0:
            return None
        if len(req.gen0) >= req.budget:
            return "budget"
        last = int(req.gen0[-1])
        sp = req.sampling
        if self.eos >= 0 and last == self.eos:
            return "eos"
        if sp is not None and last in getattr(sp, "stop_token_ids", ()):
            return "stop_token"
        if sp is not None and sp.stop_strings and self._detok is not None:
            tail = self._detok(list(req.gen0)[-self.stop_tail_tokens:])
            if any(s in tail for s in sp.stop_strings):
                return "stop_string"
        return None

    def set_scheduler(self, sched):
        """Install a front-door scheduler (round 12) — an object owning
        the request queues and the admission/preemption policy. The
        engine consults it instead of its FIFO queue for: submission
        routing (`on_submit`, which may raise to REJECT), candidate
        selection (`next_request`/`pop`), victim selection for
        preemption (`victims`), requeue of preempted requests
        (`requeue`), packed-prefill ordering and per-slot chunk caps
        (`prefill_plan`), and queue-depth reporting (`lane_depths`/
        `tenant_depths`/`depth`). None uninstalls; with no scheduler
        the engine runs the exact legacy reservation-FIFO path.
        Install before start() — the loop reads it unlocked."""
        if self._thread is not None:
            raise RuntimeError("install the scheduler before start()")
        self._sched = sched
        return self

    def warm_buckets(self, modes=((False, False),)):
        """Pre-compile every reachable packed-prefill jit bucket
        (round 12) so live traffic never pays an XLA compile
        mid-request. The packed chunk path specializes per
        (packed length T, plan rows P, table width) triple — all
        power-of-two bucketed, so the space is small — but WHICH
        buckets a serving window hits depends on admission/preemption
        timing (share-capped chunks, one-token cache-hit resumes,
        churn-sized plans), so a warm-traffic drive cannot enumerate
        them deterministically. Production front ends compile their
        shape buckets before taking traffic; this is that switch.

        Each bucket is compiled by ONE synthetic dispatch whose
        positions are all packing pad (-1), so every write lands in
        the pool's reserved trash block and no sequence, sampling, or
        cache state changes. `modes`: the (any_sampled, any_penalties)
        static pairs to compile (default: the all-greedy fast path;
        pass `[(False, False), (True, False)]` etc. for sampled
        traffic). Call before `start()` — the loop owns the cache
        arrays once it runs. Returns the number of variants compiled."""
        if self._thread is not None:
            raise RuntimeError(
                "warm_buckets must run before start() (the engine loop "
                "owns the cache arrays once it is running)")
        if self._unified:
            # the unified loop never dispatches packed_prefill — its
            # bucket space is the combined-round (T, P) family
            n = self._warm_unified_buckets(modes)
            self._warm_ran = True
            return n
        jnp = self._jnp
        align = self._pack_align
        # sp-sharded prefill reaches sp x the replica budget per
        # dispatch (the _prefill_packed plan), so the reachable (T, P)
        # bucket family scales with it
        budget = self.prefill_chunk_tokens * self._sp_degree
        pairs = set()
        if self._one_plan_shape:
            # one program a packed length: rows and table width are fixed
            T = align
            while T <= budget:
                pairs.add((T, self._plan_rows(1)))
                T *= 2
            rows_range = ()
        else:
            rows_range = range(1, min(self.max_slots, budget) + 1)
        for rows in rows_range:
            P = 1
            while P < rows:
                P *= 2
            # packed length range for a plan of `rows` chunks: each
            # region is align*ceil(n_i/align) with n_i >= 1 and
            # sum(n_i) <= budget, so off spans [rows*align, the
            # one-fat-chunk worst case]
            off_max = (rows - 1) * align + align * (
                -(-(budget - rows + 1) // align))
            T = align
            while T < rows * align:
                T *= 2
            while True:
                pairs.add((T, P))
                if T >= off_max:
                    break
                T *= 2
        widths = []
        w = 1
        while w < self._m_width and not self._one_plan_shape:
            widths.append(w)
            w *= 2
        widths.append(self._m_width)  # the min(pow2, m_width) cap
        n = 0
        for mode in modes:
            for T, P in sorted(pairs):
                for mcap in widths:
                    # fresh args per dispatch: in penalty mode the
                    # count buffer is donated on accelerators, so a
                    # reused dict would hand back an invalidated array
                    sp = self._sp_store.warm_args(P, mode)
                    _tok, _stopped, *rest = \
                        self._decoder.packed_prefill(
                            self._params, jnp.zeros((T,), jnp.int32),
                            jnp.zeros((T,), jnp.int32),
                            jnp.full((T,), -1, jnp.int32),
                            jnp.asarray(self.cache.table_array(
                                [None] * P, mcap)),
                            jnp.zeros((P,), jnp.int32),
                            self.cache.k_blocks, self.cache.v_blocks,
                            sp, mode, state=self.cache.state)
                    # reinstall the round-tripped arrays (donated on
                    # accelerators); only trash-block rows were written
                    self._chain(rest)
                    n += 1
        _logger.info("warm_buckets: compiled %d packed-prefill "
                     "variants (%d shape pairs x %d widths x %d modes)",
                     n, len(pairs), len(widths), len(modes))
        self._warm_ran = True
        return n

    def _warm_unified_buckets(self, modes):
        """Pre-compile the unified-round bucket space (r16): every
        reachable (packed length T, plan rows P) pair at the pinned
        table width, per sampling mode. The combined stream packs up
        to max_slots chunk/decode/verify regions, so T's worst case is
        the chunk half's worst packing plus max_slots pinned
        decode/verify regions; both axes bucket to powers of two, so
        the space stays small. Each bucket compiles via ONE synthetic
        all-pad dispatch (positions -1 route every write to the trash
        block; no sequence, sampling, carry or cache state changes)."""
        jnp = self._jnp
        align = self._pack_align
        dalign = self._verify_align
        K1 = self._uk1
        W = -(-K1 // dalign) * dalign
        budget = self.prefill_chunk_tokens
        chunk_hi = 0
        for rows in range(1, min(self.max_slots, budget) + 1):
            chunk_hi = max(chunk_hi, (rows - 1) * align + align * (
                -(-(budget - rows + 1) // align)))
        off_hi = chunk_hi + W * self.max_slots
        ts = []
        t = align
        while True:
            ts.append(t)
            if t >= off_hi:
                break
            t *= 2
        ps = []
        p = 1
        while True:
            ps.append(p)
            if p >= self.max_slots:
                break
            p *= 2
        zc = self._zero_carry_arrays()
        n = 0

        def one(T, P, mode, window):
            sp = self._sp_store.warm_unified_args(P, mode)
            (_vt, _ac, _st, kc, vc, counts, _ct, _cp,
             _cs) = self._decoder.unified_round(
                self._params, jnp.zeros((T,), jnp.int32),
                jnp.zeros((T,), jnp.int32),
                jnp.full((T,), -1, jnp.int32),
                jnp.zeros((P, self._m_width), jnp.int32),
                jnp.zeros((P, K1), jnp.int32),
                jnp.full((P,), -1, jnp.int32),
                jnp.full((P,), -1, jnp.int32),
                jnp.full((T,), -1, jnp.int32),
                jnp.full((T,), -1, jnp.int32),
                jnp.full((P,), -1, jnp.int32),
                *zc, self.cache.k_blocks, self.cache.v_blocks,
                sp, mode, window=window)
            self._sp_store.swap_counts(counts)
            self.cache.swap_arrays(kc, vc)

        for mode in modes:
            for P in ps:  # chunk-free WINDOW rounds: T pinned = P * W
                one(P * W, P, mode, True)
                n += 1
            for T in ts:  # mixed rounds: the packed (T, P) family
                for P in ps:
                    one(T, P, mode, False)
                    n += 1
        _logger.info("warm_buckets: compiled %d unified-round variants "
                     "(%d window + %d T x %d P packed, %d modes)",
                     n, len(ps), len(ts), len(ps), len(modes))
        return n

    # ---- client API ----------------------------------------------------
    def submit(self, ids, max_new_tokens=None, sampling=None, *,
               meta=None, on_token=None, timeout_s=None, rid=None,
               trace_ctx=None, on_routing=None):
        """Enqueue one prompt (any length <= max_prompt_len; NO padding
        needed). Returns a Future resolving to the UNPADDED
        [len + generated] int32 sequence (generation stops at EOS, a
        stop condition, or the token budget).

        sampling: optional `SamplingParams` — per-request temperature /
        top-k / top-p / min-p, penalties, PRNG seed, stop token ids /
        stop strings, and token budget. Validation is EAGER (here), so
        a bad value fails the submit, not a later jitted dispatch.
        `max_new_tokens` (arg) overrides `sampling.max_new_tokens`
        overrides the server default. Stop strings require the server
        to be built with a `detokenize` callable; matching runs against
        the detokenized last `stop_tail_tokens` tokens.

        meta: optional `RequestMeta` (round 12) — lane / tenant /
        TTFT deadline / rate cost for the installed front-door
        scheduler. When a scheduler is installed the request routes
        into it (its `on_submit` may raise to reject — bounded
        queues); without one, `meta` rides along inert and the legacy
        FIFO path runs unchanged.
        on_token: optional callable `(token:int, reason:str|None)`
        invoked from the engine thread for every generated token
        (reason is None mid-stream, the stop reason on the final
        token). It must be fast and non-blocking; exceptions are
        logged and dropped, never propagated into the engine loop.
        timeout_s: per-request wall-clock deadline (r17) — a request
        still queued or resident past this many seconds after submit
        is CANCELLED: its slot and blocks are freed and its future
        fails with `RequestTimeout` (streams see reason="timeout").
        Enforced by the engine loop, so it needs a started server.
        rid: caller-pinned request id (fleet round) — a router names
        the session once and every replica-facing hook
        (`export_session`, journal records, quarantine diagnostics)
        speaks the same id. Default: auto-assigned "pN".
        trace_ctx: caller-minted `TraceContext` (ISSUE 14) — the fleet
        router/front door mints once at ITS submit so the request's
        whole fleet lifetime shares one trace_id; a bare engine mints
        its own hop-0 context here. Every event, span, flight-recorder
        entry and journal record the request touches is stamped with
        trace_id / hop / cause (+ the replica name on a fleet).

        on_routing: optional callable `(position, picks, state_slot)`
        for a model served from its own description, invoked from the
        engine thread after every dispatch that fed this request's
        tokens: `picks` [expert layers, n, k] int32 are the experts the
        routers chose for the n tokens from `position` on (None for a
        model without routed experts; a preempted request's positions
        come again), `state_slot` the slot of the recurrent-
        state store the sequence holds (0 without one); the slot keeps
        the sequence's last state until another sequence takes it (one
        step past it where a stop token or stop string ended the
        request: the step queued behind the one that revealed the stop
        carried its row).  Called when the dispatch is read back, a
        round after it was issued, with the positions it was issued
        with.  As fast and as harmless as `on_token` must be.

        When the server was built with `shed_queue_depth=`, a submit
        arriving at a queue already that deep raises `AdmissionShed`
        (nothing enqueued) carrying a `retry_after_s` hint."""
        if sampling is None:
            sampling = self._default_sampling
        elif not isinstance(sampling, SamplingParams):
            raise TypeError(f"sampling must be a SamplingParams, "
                            f"got {type(sampling).__name__}")
        if sampling.stop_strings and self._detok is None:
            raise ValueError(
                "stop_strings given but the server has no detokenizer "
                "(pass detokenize= to the PagedGenerationServer "
                "constructor)")
        ids = np.asarray(ids, np.int32).reshape(-1)
        if ids.size == 0 or ids.size > self.max_prompt_len:
            raise ValueError(f"prompt length {ids.size} not in "
                             f"[1, {self.max_prompt_len}]")
        budget = (max_new_tokens if max_new_tokens is not None
                  else sampling.max_new_tokens)
        budget = self.max_new if budget is None else int(budget)
        if not 1 <= budget <= self.max_new:
            raise ValueError(f"max_new_tokens {budget} not in "
                             f"[1, {self.max_new}]")
        if meta is not None and not isinstance(meta, RequestMeta):
            raise TypeError(f"meta must be a RequestMeta, "
                            f"got {type(meta).__name__}")
        if timeout_s is not None:
            timeout_s = float(timeout_s)
            if timeout_s <= 0:
                raise ValueError(f"timeout_s must be > 0, "
                                 f"got {timeout_s}")
            self._any_timeouts = True
        if trace_ctx is not None and not isinstance(trace_ctx,
                                                    TraceContext):
            raise TypeError(f"trace_ctx must be a TraceContext, "
                            f"got {type(trace_ctx).__name__}")
        req = _Req(ids=ids, future=Future(),
                   t_submit=time.perf_counter(),
                   rid=(str(rid) if rid is not None
                        else f"p{next(_req_ids)}"), sampling=sampling,
                   meta=meta, on_token=on_token, on_routing=on_routing,
                   timeout_s=timeout_s,
                   trace=(trace_ctx if trace_ctx is not None
                          else TraceContext.mint()))
        # per-request PRNG stream seed: explicit seeds reproduce tokens
        # regardless of batch composition; auto seeds derive from the
        # server seed + a submission counter (distinct streams per
        # request, deterministic given arrival order)
        req.seed = (sampling.seed if sampling.seed is not None else
                    (self._seed0 + 0x9E3779B9 * (1 + next(
                        self._auto_seeds))) & 0xFFFFFFFF)
        req.budget = budget
        with self._lock:
            if self._stop:
                raise RuntimeError("server stopped")
            if self._shed_depth is not None:
                # admission shedding (r17): refuse — with a retry
                # hint — instead of queueing past the shed depth
                depth = (self._sched.depth() if self._sched is not None
                         else len(self._queue))
                if depth >= self._shed_depth:
                    self._sheds += 1
                    hint = self._retry_after_hint_locked(depth)
                    self._recorder.record(
                        "shed", request_id=req.rid, depth=depth,
                        retry_after_s=round(hint, 3))
                    raise AdmissionShed(depth, self._shed_depth, hint)
            if self._sched is not None:
                # scheduler-owned queues: on_submit may raise (bounded
                # queue rejection) — nothing is enqueued in that case
                try:
                    self._sched.on_submit(req, time.perf_counter())
                except Exception as e:
                    self._recorder.record(
                        "reject", request_id=req.rid,
                        error=f"{type(e).__name__}: {e}")
                    raise
            else:
                self._queue.append(req)
                _m_queue_depth.labels(server="paged").set(
                    len(self._queue))
            if self._journal is not None:
                # under the lock: the engine loop admits under this
                # lock too, so the accept record always precedes the
                # request's first token record
                self._journal.record_accept(req)
            self._lock.notify()
        if self._ledger is not None:
            # only ADMITTED requests enter the cost ledger (a shed or
            # bounded-queue reject raised above, nothing enqueued)
            self._ledger.request_begin(
                req.rid, meta.tenant if meta is not None else "default")
        self._recorder.record(
            "submit", request_id=req.rid, prompt_len=int(ids.size),
            budget=budget,
            lane=meta.lane if meta is not None else None,
            tenant=meta.tenant if meta is not None else None,
            **self._tr(req))
        # stamped with the instant the request's own clocks start from
        # (TTFT, latency): the engine thread may hold the lock above for
        # a round, and a first token must not precede its submit
        _tracing.event("request_submitted", request_id=req.rid,
                       ts=req.t_submit, prompt_len=int(ids.size),
                       budget=budget, **self._tr(req))
        return req.future

    def start(self):
        if self._thread is not None:
            return self
        if self._stop:
            raise RuntimeError(
                "server was stopped; build a new PagedGenerationServer")
        self._t0 = time.perf_counter()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        with self._lock:
            self._stop = True
            self._lock.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=120)
            self._thread = None
            self._log_rounds()
        with self._lock:
            pending = list(self._queue)
            self._queue.clear()
            if self._sched is not None:
                pending.extend(self._sched.drain())
            for req in pending:
                self._abandon_prefetch_locked(req.rid)
                req.future.set_exception(RuntimeError("server stopped"))
        # ops plane teardown: release the port and the watchdog thread
        if self._watchdog is not None:
            self._watchdog.stop()
        if self.exporter is not None:
            self.exporter.stop()
        if self._journal is not None:
            # queued requests failed above stay journal-live on
            # purpose: a restarted server may still re-admit them
            self._journal.flush()

    def _log_rounds(self):
        """INFO lines as the engine stops: the rounds since the last reset
        by kind, how often a dispatch found the device idle, and the
        host's phases in the rounds that starved it beside all rounds;
        the paged decode kernel's blocks a grid step where it ran. How a
        run with no profiler and no reader of `stats()` (a `--trace 0`
        benchmark run) leaves the counters behind."""
        rp = self._phases.snapshot()
        rounds = rp["round_ms"]
        if not rounds["count"]:
            return
        with self._lock:
            probed, found = dict(self._probed), dict(self._found_idle)

        def a_round(seconds, n):
            return ", ".join(f"{k} {1e3 * v / n:.2f}"
                             for k, v in seconds.items() if v)

        kinds = "; ".join(
            f"{k} {v['count']}: {v['p50_ms']:.1f} / {v['p99_ms']:.1f}"
            for k, v in rounds["by_kind"].items())
        idle = ", ".join(f"{k} {found[k]} of {probed[k]}"
                         for k in DISPATCH_KINDS if probed[k])
        line = (f"[rounds] {rounds['count']} rounds since the reset, ms "
                f"p50 / p99 {rounds['p50_ms']:.1f} / "
                f"{rounds['p99_ms']:.1f} ({kinds}); dispatches that found "
                f"the device idle when they landed: {sum(found.values())} "
                f"of {sum(probed.values())} issued with one in flight"
                + (f" ({idle})" if idle else ""))
        if rp["starved_rounds"]:
            line += (f"; ms a round in the {rp['starved_rounds']} rounds "
                     f"that starved it: "
                     f"{a_round(rp['starved_seconds'], rp['starved_rounds'])}"
                     f"; in all: {a_round(rp['seconds'], rounds['count'])}")
        _logger.info("%s", line)
        launches, live, steps = self._dk_counts
        if steps:
            _logger.info("[decode kernel] %s fan %d: %d live blocks in %d "
                         "grid steps of %d launches, %.3f blocks a step",
                         *self._dk_plan[:2], live, steps, launches,
                         live / steps)

    def reset_stats(self):
        """Zero the measurement window — latency AND the TTFT samples
        the window's ttft percentiles derive from, so a post-reset
        stats() can never mix epochs."""
        with self._lock:
            self._lat.clear()
            self._ttft.clear()
            self._itl.clear()
            self._tokens_out = 0
            self._requests_done = 0
            self._steps = 0
            self._prefills = 0
            self._prefill_dispatches = 0
            self._active_integral = 0
            self._fill_integral = 0.0
            self._stop_reasons = dict.fromkeys(STOP_REASONS, 0)
            self._fastpath_dispatches = 0
            self._sampled_dispatches = 0
            self._spec_proposed = 0
            self._spec_accepted = 0
            self._spec_rolled_back = 0
            self._spec_dispatches = 0
            self._spec_rounds_per_slot = 0
            self._decoded_tokens = 0
            self._replayed_tokens = 0
            self._expert_sums = [0, 0, 0, 0, 0]
            self._expert_ring.clear()
            self.cache.reset_state_peak()
            self._rounds = 0
            self._round_dispatch_count = 0
            self._mixed_rounds = 0
            self._overlap_s = 0.0
            self._decode_issued = 0
            self._decode_ahead = 0
            self._dk_counts = [0, 0, 0]
            self._drains = {}
            self._late_rows = 0
            self._probed = dict.fromkeys(DISPATCH_KINDS, 0)
            self._found_idle = dict.fromkeys(DISPATCH_KINDS, 0)
            self._phases.reset()
            self._compile_mark = _compile_tracker.mark()
            self._last_error = None  # a fresh window is healthy again
            self._last_error_info = None
            self._consec_failures = 0
            self._faults_injected = 0
            self._dispatch_retries = 0
            self._recoveries = 0
            self._quarantined = 0
            self._timeouts = 0
            self._sheds = 0
            self._preemptions = 0
            self._resumes = 0
            self._preempt_cached_tokens = 0
            self._prefetch_issued = 0
            self._prefetch_hits = 0
            self._prefetch_wasted = 0
            self._prefetch_overlap_s = 0.0
            self._sp_peak_bytes = 0
            self._deadline_requests = {}
            self._deadline_misses = {}
            self._lane_ttft = {}
            self._lane_itl = {}
            self._slo_good_mark = (0, 0)
            if self._sched is not None:
                self._sched.reset_window()
            self._decoder.reset_wire_stats()
            self._t0 = time.perf_counter()
        if self._ledger is not None:
            # window accounts zero; occupancy LEVELS carry forward so
            # both sides of each conservation equation restart at zero
            self._ledger.reset()
            self._wire_mark = (None if self._wire_mark is None else 0)

    def stats(self):
        """Window stats. ITL (inter-token latency) is per GENERATED
        token: each decode dispatch's host-visible gap since the slot's
        previous emission, amortized over the tokens it emitted (with
        multi-step scheduling, k tokens land per dispatch) — the metric
        the prefill_chunk_tokens knob trades against TTFT."""
        with self._lock:
            lat = sorted(self._lat)
            ttft = sorted(self._ttft)
            itl = sorted(self._itl)
            dt = (time.perf_counter() - self._t0) if self._t0 else 0.0
            n = len(lat)
            nt = len(ttft)
            ni = len(itl)
            pct = (lambda p: lat[min(n - 1, int(p * n))] if n else 0.0)
            tpct = (lambda p: ttft[min(nt - 1, int(p * nt))] if nt
                    else 0.0)
            ipct = (lambda p: itl[min(ni - 1, int(p * ni))] if ni
                    else 0.0)
            out = {
                "requests": n,
                "new_tokens": self._tokens_out,
                "tokens_per_sec": self._tokens_out / dt if dt else 0.0,
                "p50_ms": pct(0.50) * 1e3,
                "p90_ms": pct(0.90) * 1e3,
                "p99_ms": pct(0.99) * 1e3,
                "ttft_p50_ms": tpct(0.50) * 1e3,
                "ttft_p99_ms": tpct(0.99) * 1e3,
                "itl_p50_ms": ipct(0.50) * 1e3,
                "itl_p99_ms": ipct(0.99) * 1e3,
                "decode_steps": self._steps,
                "prefills": self._prefills,
                "prefill_dispatches": self._prefill_dispatches,
                # finished requests by why generation stopped, plus the
                # sampling pipeline's dispatch-mode split (fast path =
                # no resident sampled request: bare argmax)
                "stop_reasons": dict(self._stop_reasons),
                "sampling_fast_path_dispatches":
                    self._fastpath_dispatches,
                "sampling_sampled_dispatches": self._sampled_dispatches,
                # mean busy slots per decode step: the continuous-batching
                # analogue of the dense server's batch_fill
                "slot_fill": (self._active_integral
                              / ((self._steps or 1) * self.max_slots)),
                # mean internal fragmentation of ALLOCATED blocks while
                # decoding (sampled per dispatch; end-of-window cache
                # stats read 0 once everything is freed)
                "kv_block_fill": (self._fill_integral
                                  / (self._steps or 1)),
                # speculation accounting (round 11): zeros when the
                # server runs without a SpecConfig — schema-stable so
                # bench records and dashboards need no gating
                "speculation": {
                    "enabled": self.speculation is not None,
                    "proposed_tokens": self._spec_proposed,
                    "accepted_tokens": self._spec_accepted,
                    "rolled_back_tokens": self._spec_rolled_back,
                    "verify_dispatches": self._spec_dispatches,
                    "slot_rounds": self._spec_rounds_per_slot,
                    # fraction of proposed draft tokens accepted
                    "acceptance_rate": (self._spec_accepted
                                        / (self._spec_proposed or 1)),
                },
                # quantized serving (this round): config + byte
                # accounting, schema-stable (zeroed-but-present when
                # disabled — the speculation/frontdoor convention)
                "quantization": {
                    "enabled": (self.quantization is not None
                                or self.kv_dtype is not None),
                    "mode": self.quantization or "none",
                    "kv_dtype": self.cache.stats_kv_dtype(),
                    "kv_scale_bytes": self.cache.scale_bytes,
                    "kv_pool_bytes_total": self.cache.pool_bytes_total,
                },
                # sharded serving (serving_dist round): mesh config the
                # engine runs on — schema-stable (zeroed when disabled,
                # trivially reset-coherent: it is construction config,
                # not a window counter)
                "sharding": self._sharding_stats(),
                # tier prefetch-ahead (memory-flat long-context round):
                # blocks promoted ahead of admission and how they
                # settled — zeroed-when-disabled congruent schema,
                # reset-coherent window counters
                "tier_prefetch": {
                    "enabled": bool(self._prefetch_look),
                    "lookahead": self._prefetch_look,
                    "issued_blocks": self._prefetch_issued,
                    "hit_blocks": self._prefetch_hits,
                    "wasted_blocks": self._prefetch_wasted,
                    "hit_rate": (self._prefetch_hits
                                 / (self._prefetch_issued or 1)),
                    "overlap_promote_s": self._prefetch_overlap_s,
                },
                # quantized collectives (this round): analytic wire-byte
                # accounting of the sharded decode collectives this
                # window — bytes_total is the dispatched path,
                # bytes_baseline what bf16 would have shipped (equal
                # when quantization is off; all-zero schema for
                # unsharded / tp=1 servers), reset-coherent via
                # reset_stats -> decoder.reset_wire_stats
                "collectives": self._collectives_stats(),
                # goodput accounting (ISSUE 10): decoded device tokens
                # = emitted + speculation-rolled-back + replayed
                # (multi-step overrun discards, stop-truncated verify
                # positions, preempt-resume re-prefill of generated
                # tokens) — conservation holds per window by
                # construction at every dispatch site
                "goodput": {
                    "decoded_tokens": self._decoded_tokens,
                    "goodput_tokens": self._tokens_out,
                    "rolled_back_tokens": self._spec_rolled_back,
                    "replayed_tokens": self._replayed_tokens,
                    "goodput_ratio": (self._tokens_out
                                      / (self._decoded_tokens or 1)),
                },
                # one-kernel round (r16): dispatches-per-round on BOTH
                # engine paths (split: up to chunk-prefill + decode +
                # verify per round; unified: 1) plus the async loop's
                # hidden host-plan time — zeroed-when-disabled schema,
                # reset-coherent (mixed_rounds = rounds that contained
                # prefill AND decode/verify work, the rounds the fusion
                # actually collapses)
                "rounds": {
                    "unified": self._unified,
                    "async": self._async,
                    "rounds": self._rounds,
                    "attention_dispatches": self._round_dispatch_count,
                    "dispatches_per_round": (self._round_dispatch_count
                                             / (self._rounds or 1)),
                    "mixed_rounds": self._mixed_rounds,
                    "overlap_seconds": self._overlap_s,
                    "overlap_fraction": (self._overlap_s / dt
                                         if dt else 0.0),
                },
                # the engine thread's time by round phase (ROUND_PHASES
                # + "other"; the seconds tile its wall time since the
                # reset), the dispatches issued, and the window's
                # longest round with its own phases — what the host
                # costs per dispatch and where a round that stood
                # still spent it; reset-coherent
                "round_phases": self._phases.snapshot(),
                # one dispatch in flight (the default loop): decode
                # dispatches issued and how many of them were queued
                # before the one before was read, the reads that had
                # nothing queued behind them by the seam that asked,
                # and the rows a late finish (a stop only the read
                # revealed) left in a dispatch already queued —
                # reset-coherent; a drafter or steps_per_dispatch > 1
                # issues none ahead
                "dispatch_ahead": {
                    "decode_dispatches": self._decode_issued,
                    "issued_ahead": self._decode_ahead,
                    "ahead_share": (self._decode_ahead
                                    / (self._decode_issued or 1)),
                    "drains": dict(self._drains),
                    "dropped_rows": self._late_rows,
                    # did the device run dry (`_probe_device`):
                    # dispatches that landed while another was unread,
                    # and those of them that found that one finished,
                    # by the kind issued; zeros where every dispatch is
                    # read where it is issued
                    "probed": sum(self._probed.values()),
                    "probed_by_kind": dict(self._probed),
                    "found_idle": dict(self._found_idle),
                    "found_idle_share": (
                        sum(self._found_idle.values())
                        / (sum(self._probed.values()) or 1)),
                },
                # the decode attention kernel over the window's decode
                # dispatches, counted on the host from the contexts the
                # plan holds: which (`kernel`: "paged_attn_decode",
                # "mla_decode", None where decode runs no kernel), blocks
                # of a row a grid step (`fan`; 0 without a kernel),
                # launches (one a pool layer), the rows' live blocks and
                # the grid steps they took — live_blocks / steps is how
                # often a step takes several, and how few are dead
                "decode_kernel": {
                    "kernel": (self._dk_plan or (None,))[0],
                    "fan": (self._dk_plan or (None, 0))[1],
                    "launches": self._dk_counts[0],
                    "live_blocks": self._dk_counts[1],
                    "steps": self._dk_counts[2],
                },
                # the expert layers' counters (zeros for a model with
                # none), summed over the layers of every dispatch that
                # routed a token; `dispatches` is the newest EXPERT_RING
                # of them: [perf_counter, tokens, held_picks,
                # experts_touched, max_load, expert layers] each
                "experts": self._expert_stats_locked(),
                # reliability (r17): fault injection + recovery ladder
                # + timeout/shed window counters — schema-stable
                # (zeros when nothing ever failed), reset-coherent
                "reliability": {
                    "recovery_enabled": self._recovery is not None,
                    "fault_plan": (self._faults.describe()
                                   if self._faults is not None
                                   else None),
                    "faults_injected": self._faults_injected,
                    "dispatch_retries": self._dispatch_retries,
                    "recoveries": self._recoveries,
                    "quarantined": self._quarantined,
                    "timeouts": self._timeouts,
                    "shed": self._sheds,
                    "consecutive_failures": self._consec_failures,
                    "last_recovery": (dict(self._last_recovery)
                                      if self._last_recovery else None),
                    "journal": (self._journal.stats()
                                if self._journal is not None else None),
                },
                # XLA compiles inside THIS stats window (the process-
                # wide compile tracker, windowed at reset_stats):
                # in_flight > 0 means a compile landed on live
                # requests — the bench's compile-clean assertion
                "compiles": {
                    "window_total": _compile_tracker.count_since(
                        self._compile_mark),
                    "window_in_flight": _compile_tracker.count_since(
                        self._compile_mark, in_flight=True),
                },
                # ops plane state (schema-stable when disabled)
                "ops": {
                    "exporter_port": (self.exporter.port
                                      if self.exporter else None),
                    "health": ("ok" if self._watchdog is None
                               and self._last_error is None
                               else self.health()[0]),
                    "stalls": (self._watchdog.stalls
                               if self._watchdog else 0),
                    "flight_recorder": self._recorder.stats(),
                },
                # admission headroom RIGHT NOW: free + LRU-reclaimable
                # blocks — the number the reservation check reasons
                # about (instantaneous, not a window counter)
                "available_blocks": self.cache.available_block_count,
                # queue depths (instantaneous): the FIFO queue without
                # a scheduler, the scheduler's lane/tenant queues with
                # one — schema-stable either way (empty dicts when no
                # front door is installed)
                "queue_depth": (len(self._queue) if self._sched is None
                                else self._sched.depth()),
                "lane_queue_depth": ({} if self._sched is None
                                     else self._sched.lane_depths()),
                "tenant_queue_depth": ({} if self._sched is None
                                       else self._sched.tenant_depths()),
                # front-door window counters (round 12): zeros when no
                # scheduler is installed — congruent schema so bench
                # records and dashboards need no gating (PR 5
                # convention), reset coherently by reset_stats()
                "frontdoor": self._frontdoor_stats_locked(),
                "wall_s": dt,
            }
            out["kv_cache"] = self.cache.stats()
            # the recurrent-state store beside the pool (zeros without),
            # and the bytes a slot holds, by array
            out["state"] = {
                k: out["kv_cache"]["state"][k]
                for k in ("slots", "peak_used_slots", "bytes_per_slot",
                          "entries")}
        # per-tenant cost attribution (ISSUE 17): evaluated OUTSIDE
        # the engine lock (the ledger has its own) — zeroed congruent
        # schema when attribution is off, reset-coherent
        out["attribution"] = (self._ledger.stats()
                              if self._ledger is not None
                              else disabled_attribution_stats())
        # SLO burn-rate block (ISSUE 14): evaluated OUTSIDE the engine
        # lock (the SLO engine has its own) — schema-stable zeroed
        # shape when the server runs without SLOs
        out["slo"] = {
            "enabled": self._slo is not None,
            "slos": (self._slo.evaluate()
                     if self._slo is not None else []),
        }
        return out

    def cost_report(self):
        """Frozen per-tenant billing export for the current window
        (`CostReport`, ISSUE 17); None when attribution is off."""
        return self._ledger.report() if self._ledger is not None else None

    def _sharding_stats(self):
        """The stats()["sharding"] block: the ShardedEngineConfig's
        shape when sharding is on, the zeroed congruent schema when
        off (without importing serving_dist on the disabled path)."""
        if self.sharding is None:
            return {"enabled": False, "mesh_shape": {}, "tp_degree": 0,
                    "dp_degree": 0, "sp_degree": 0,
                    "collective_quant": "none",
                    "sp_attention": "none",
                    "sp_attention_bytes_peak": 0}
        out = self.sharding.stats_block()
        out["sp_attention_bytes_peak"] = self._sp_peak_bytes
        return out

    def _note_sp_peak(self, packed_tokens):
        """Analytic per-dispatch sp-attention byte accounting (memory-
        flat long-context round): compute the cross-shard fresh-K/V
        bytes THIS packed dispatch materializes per shard, keep the
        high-water mark (gauge + stats), and — for the memory-flat
        modes — assert the dispatch stays under the chunk-length-
        independent flat bound, every dispatch, on every backend (the
        invariant ring/ulysses exist to hold)."""
        from ..serving_dist.sp_attention import (sp_attention_flat_bound,
                                                 sp_attention_peak_bytes)

        mode = self._sp_attention
        peak = sp_attention_peak_bytes(mode, int(packed_tokens),
                                       **self._sp_bytes_kw)
        if mode != "allgather":
            kw = dict(self._sp_bytes_kw)
            kw.pop("sp")
            bound = sp_attention_flat_bound(mode, **kw)
            if peak > bound:
                raise AssertionError(
                    f"sp_attention={mode!r}: dispatch peak {peak} B "
                    f"exceeds the chunk-length-independent flat bound "
                    f"{bound} B — the O(block) memory invariant broke")
        if peak > self._sp_peak_bytes:
            self._sp_peak_bytes = peak
            _m_sp_peak_bytes.set(float(peak))

    def _collectives_stats(self):
        """The stats()["collectives"] block: the decoder's window wire
        bytes + the quantization config — zeroed congruent schema when
        sharding is off or tp=1 (no inter-chip wire)."""
        cq = getattr(self._decoder, "_cq", None)
        wire = self._decoder.wire_stats()
        return {
            "enabled": cq is not None,
            "mode": cq.mode if cq is not None else "none",
            "tp": self._decoder._tp,
            "bytes_total": wire["bytes_total"],
            "bytes_baseline": wire["bytes_baseline"],
            "by_collective": wire["by_collective"],
        }

    def _frontdoor_stats_locked(self):
        """The stats()["frontdoor"] block; caller holds the lock."""
        def pcts(samples):
            s = sorted(samples)
            n = len(s)
            return {
                "p50_ms": (s[min(n - 1, int(0.50 * n))] * 1e3
                           if n else 0.0),
                "p99_ms": (s[min(n - 1, int(0.99 * n))] * 1e3
                           if n else 0.0),
                "n": n,
            }

        lanes = {}
        for lane in sorted(set(self._lane_ttft) | set(self._lane_itl)):
            lanes[lane] = {
                "ttft": pcts(self._lane_ttft.get(lane, ())),
                "itl": pcts(self._lane_itl.get(lane, ())),
            }
        d_req = sum(self._deadline_requests.values())
        d_miss = sum(self._deadline_misses.values())
        out = {
            "enabled": self._sched is not None,
            "preemptions": self._preemptions,
            "resumes": self._resumes,
            "preempt_cached_tokens": self._preempt_cached_tokens,
            "deadline_requests": dict(self._deadline_requests),
            "deadline_misses": dict(self._deadline_misses),
            "deadline_miss_rate": d_miss / (d_req or 1),
            "lanes": lanes,
            "rejected": 0,
            "rate_throttled_skips": 0,
        }
        if self._sched is not None:
            out.update(self._sched.window_stats())
        return out

    # ---- engine --------------------------------------------------------
    def _outstanding_blocks(self):
        """Blocks the active slots may still demand in the worst case."""
        total = 0
        for slot in self._slots:
            if slot is not None:  # a just-picked slot holds 0 until its
                held = self.cache.blocks_held(slot["seq"])  # prefill runs
                total += max(0, self._worst[slot["seq"]] - held)
        return total

    def _expert_stats_locked(self):
        tokens, picks, touched, max_load, layers = self._expert_sums
        held = self._desc.held if self._desc is not None else 0
        return {
            "dispatches": [list(e) for e in self._expert_ring],
            "tokens": tokens, "held_picks": picks,
            "experts_touched": touched, "max_load": max_load,
            # picks a held expert of one layer got, on average
            "mean_load": picks / (layers * held) if layers and held
            else 0.0,
        }

    def _chain(self, rest):
        """Install what a program returned after (token, stopped) for the
        next dispatch, read or not: its caches in the cache's place
        (GPT-2's programs return (kc, vc), a description's (kc, state) or,
        with a pool of K and V rows, (kc, vc, state)) and the sampler's
        counts; what is left, a description's `routed`, is returned."""
        if self._desc is None:
            caches, rest = rest[:2], rest[2:]
        elif self.cache.v_blocks is None:
            caches, rest = (rest[0], None, rest[1]), rest[2:]
        else:
            caches, rest = rest[:3], rest[3:]
        self._sp_store.swap_counts(rest[0])
        self.cache.swap_arrays(*caches)
        return rest[1:]

    @staticmethod
    def _read_routed(rest):
        """What a description's program returned after GPT-2's five
        (`routed`, see `nn.decode_blocks`), its counters read back; None
        for GPT-2's programs and for a description without experts."""
        if not rest or rest[0] is None:
            return None
        return dict(rest[0], counts=np.asarray(rest[0]["counts"]))

    def _plan_rows(self, n):
        """Rows of a packed-prefill plan's tables for `n` chunks: the
        power-of-two bucket, or every slot when the plan has one shape."""
        if self._one_plan_shape:
            n = self.max_slots
        rows = 1
        while rows < n:
            rows *= 2
        return rows

    def _state_slot_free(self):
        """Whether one more sequence can take a slot of the recurrent-
        state store (always, for a cache without one): the free slots
        less those that admitted requests will take with their first
        block."""
        if not self.cache.state_slots:
            return True
        waiting = sum(1 for s in self._slots if s is not None
                      and not self.cache.has_seq(s["seq"]))
        return self.cache.free_state_slots - waiting >= 1

    def _note_routed(self, counts):
        """Add one dispatch's expert counters (`routed["counts"]` read
        back: [expert layers, 4]) to the sums and the ring."""
        entry = (time.perf_counter(), int(counts[:, 0].sum()),
                 int(counts[:, 1].sum()), int(counts[:, 2].sum()),
                 int(counts[:, 3].max()), int(counts.shape[0]))
        if not entry[1]:
            return
        with self._lock:
            sums = self._expert_sums
            for j in (0, 1, 2, 4):
                sums[j] += entry[j + 1]
            sums[3] = max(sums[3], entry[4])
            self._expert_ring.append(entry)

    def _tell_routing(self, rows, routed):
        """Hand every request that asked (`submit(on_routing=)`) what its
        rows' routers chose in this dispatch: `rows` is [(slot index,
        first position, first row of `picks`, rows)]."""
        asked = [(self._slots[i], p0, r0, n) for i, p0, r0, n in rows
                 if self._slots[i] is not None
                 and self._slots[i]["req"].on_routing is not None]
        if not asked:
            return
        # [expert layers, rows, k]; None without expert layers
        picks = None if routed is None else np.asarray(routed["picks"])
        for s, p0, r0, n in asked:
            try:
                s["req"].on_routing(int(p0), None if picks is None
                                    else picks[:, r0:r0 + n],
                                    self.cache.state_slot(s["seq"]))
            except Exception:  # noqa: BLE001 — a callback never stops the loop
                _logger.exception("on_routing callback raised")

    def _worst_blocks(self, req):
        """Worst-case block reservation for `req`: the overrun slack
        covers a multi-step scan's up-to-k-1 discarded tokens and a
        verify dispatch's up-to-K speculative positions, plus one spare
        block for the (at most one) copy-on-write a prefix-cache
        attach ending mid-block can force. For a PREEMPTED request the
        resume prompt (ids + generated-so-far) replaces the prompt and
        the already-generated tokens come off the budget — the total is
        identical to the original reservation."""
        prompt = req.resume_ids if req.resume_ids is not None else req.ids
        remaining = req.budget - len(req.gen0)
        return self.cache.blocks_for(
            prompt.size + remaining + self._overrun) \
            + (1 if self.enable_prefix_cache else 0)

    def _install_slot_locked(self, i, req, worst):
        """Shared admission body: bind `req` to slot `i` (reservation
        already checked by the caller). A resumed request's slot is
        re-seeded with its pre-preemption tokens and its resume prompt,
        so every position/PRNG-step/budget formula downstream is
        residency-invariant."""
        seq = self._seq_counter
        self._seq_counter += 1
        self._worst[seq] = worst
        tenant = (req.meta.tenant if req.meta is not None
                  else "default")
        if self._ledger is not None:
            # tag the sequence BEFORE any block is taken: every
            # _take_blocks under this seq charges this (tenant, rid)
            self.cache.set_seq_owner(seq, tenant, req.rid)
        prompt = req.resume_ids if req.resume_ids is not None else req.ids
        # prefix caching: attach the longest cached block chain and
        # mark those tokens already-fed — the packed prefill below
        # starts at the first uncached token. A warm resume attaches
        # the blocks its own swap-out published (near-zero recompute).
        cached = 0
        if self.enable_prefix_cache:
            # prefetch settlement FIRST (hit = still device-resident at
            # this instant — the attach below would re-publish walked
            # hashes and make every block read as a hit), then stamp
            # the request id onto any tier_promote the attach fires
            self._settle_prefetch_locked(req.rid)
            self._promote_ctx = req.rid
            try:
                cached = self.cache.attach_prefix(seq, prompt)
            finally:
                self._promote_ctx = None
            if cached and self._ledger is not None:
                # attacher's saved recompute, credited at the measured
                # per-token prefill cost (publisher keeps paying the
                # blocks' residency — single-owner model)
                self._ledger.credit_prefix(tenant, req.rid, cached)
        # WARM RESUME fast path (round 12): when every context
        # position but the last attached from the cache and at least
        # one token was emitted before the preemption, the slot is
        # structurally a decode slot already — its last emitted token
        # is the decode input, position size-1 is the one position to
        # recompute, and the PRNG step counter is len(gen0). Marking
        # the prompt fully fed lets it rejoin the next DECODE dispatch
        # directly: a warm resume costs zero prefill dispatches.
        warm = (req.resume_ids is not None and bool(req.gen0)
                and cached >= prompt.size - 1)
        if warm:
            # the write block may still be shared with the prefix the
            # swap-out published — privatize it now (the same CoW
            # guard the chunked-prefill path runs per chunk)
            self.cache.prepare_write(seq, prompt.size - 1)
        # fed: prompt tokens already written to the paged cache —
        # a slot is in the PREFILL phase until fed == prompt length,
        # then decodes; t_pre0/t_last anchor the per-request prefill
        # trace span and the ITL clock
        self._slots[i] = {"seq": seq, "req": req,
                          "toks": list(req.gen0), "prompt": prompt,
                          "pos": req.ids.size, "budget": req.budget,
                          "fed": prompt.size if warm else cached,
                          "cached": cached,
                          "chunks": 0, "t_pre0": None,
                          "t_last": None}
        # scatter the request's sampling params into its slot row
        # (one device row-reset only when the request uses
        # penalties); the server-level EOS joins its stop-id set —
        # penalty counts seed from the RESUME prompt, which equals
        # prompt counts + generated counts, exactly the uninterrupted
        # run's buffer state
        self._sp_store.set_slot(i, req.sampling, req.seed,
                                eos=self.eos, prompt_ids=prompt)
        if req.resume_ids is not None:
            self._resumes += 1
            _m_resumes.inc()
            _tracing.event("resumed", request_id=req.rid, slot=i,
                           seq=seq, cached_tokens=cached,
                           tokens_done=len(req.gen0), warm=warm,
                           **self._tr(req))
        if warm and self._async:
            # the slot joins the next decode dispatch directly, so its
            # device-carry entry must hold its host-known state (no
            # unified round ever set it for this residency)
            self._seed_carry_slot(i)
        _m_slot_refills.inc()
        self._ops_progress += 1
        self._recorder.record(
            "admit", request_id=req.rid, slot=i, seq=seq,
            cached_tokens=cached, resume=req.resume_ids is not None,
            free_blocks=self.cache.available_block_count,
            **self._tr(req))
        _tracing.event("request_admitted", request_id=req.rid,
                       slot=i, seq=seq, cached_tokens=cached,
                       **self._tr(req))
        return seq

    def _preempt_slot_locked(self, i, why="pressure"):
        """Evict slot `i` mid-flight (round 12): publish its live K/V
        through the prefix-cache index (when caching is on — the
        swapped-out blocks park in LRU retention, so a prompt resume
        re-prefills ~one token unless pool pressure reclaimed them),
        release its blocks, and hand the request back for requeueing
        with its generated-so-far tokens saved as resume state. Called
        between dispatches only (no in-flight device work touches the
        victim) — in async mode the in-flight round is DRAINED first,
        so the victim's token list and published K/V are
        authoritative (the drain may complete the victim's request —
        then there is nothing to evict and this returns None)."""
        self._drain_pending("preempt")
        if self._slots[i] is None:
            return None
        s = self._slots[i]
        seq, req = s["seq"], s["req"]
        known = (np.concatenate([req.ids,
                                 np.asarray(s["toks"], np.int32)])
                 if s["toks"] else req.ids)
        cached = 0
        if self.cache.has_seq(seq):  # a never-prefilled slot owns none
            if self.enable_prefix_cache:
                cached = self.cache.swap_out_seq(seq, known)
            else:
                self.cache.free(seq)
        del self._worst[seq]
        self._slots[i] = None
        self._sp_store.clear_slot(i)
        req.gen0 = tuple(s["toks"])
        req.resume_ids = known
        req.preempts += 1
        self._preemptions += 1
        self._preempt_cached_tokens += cached
        _m_preemptions.labels(reason=why).inc()
        _m_preempt_cached.inc(cached)
        self._recorder.record(
            "preempt", request_id=req.rid, slot=i, seq=seq,
            tokens_done=len(s["toks"]), cached_tokens=cached,
            reason=why, **self._tr(req))
        _tracing.event("preempted", request_id=req.rid, slot=i, seq=seq,
                       tokens_done=len(s["toks"]), cached_tokens=cached,
                       reason=why, **self._tr(req))
        return req

    def _admit_locked(self):
        """Fill idle slots while the pool can cover each request's worst
        case; runs prefill OUTSIDE the lock? No — prefill here is called
        with the lock released by the loop; this method only picks
        (slot, req) pairs. Without a scheduler this is the legacy
        reservation-FIFO path, bit-identical to pre-round-12; with one,
        the scheduler orders candidates across lanes/tenants and may
        preempt victims to make room."""
        if self._sched is not None:
            return self._admit_sched_locked()
        picked = []
        for i in range(self.max_slots):
            if not self._slot_free(i) or not self._queue:
                continue
            req = self._queue[0]
            worst = self._worst_blocks(req)
            # available counts LRU-retained prefix blocks: alloc paths
            # reclaim them before raising, so they back reservations
            if self.cache.available_block_count \
                    - self._outstanding_blocks() < worst \
                    or not self._state_slot_free():
                break  # head-of-line: keep arrival order under pressure
            self._queue.pop(0)
            seq = self._install_slot_locked(i, req, worst)
            picked.append((i, req, seq))
        if picked:
            _m_queue_depth.labels(server="paged").set(len(self._queue))
        return picked

    def _admit_sched_locked(self):
        """Scheduler-driven admission (round 12): ask the scheduler for
        candidates in policy order (lane weights, EDF, tenant fair
        share, rate limits); a candidate blocked on resources may name
        preemption victims — each victim is swapped out and requeued,
        then the reservation is rechecked. A lane whose candidate stays
        blocked is set aside for this pass (no cross-lane head-of-line
        blocking) and the other lanes keep admitting."""
        picked = []
        blocked: set = set()
        while True:
            now = time.perf_counter()
            req = self._sched.next_request(now, blocked)
            if req is None:
                break
            worst = self._worst_blocks(req)
            free_i = next((i for i in range(self.max_slots)
                           if self._slot_free(i)), None)

            def short():
                return (self.cache.available_block_count
                        - self._outstanding_blocks()) < worst

            if free_i is None or short():
                # (slot, resident, remaining tokens): the remaining
                # budget feeds the policy's drain-wait hysteresis
                occupied = [(j, self._slots[j]["req"],
                             self._slots[j]["budget"]
                             - len(self._slots[j]["toks"]))
                            for j in range(self.max_slots)
                            if self._slots[j] is not None]
                for j in self._sched.victims(req, occupied, now):
                    victim = self._preempt_slot_locked(j)
                    if victim is not None:
                        self._sched.requeue(victim, now)
                    free_i = next((i for i in range(self.max_slots)
                                   if self._slot_free(i)), None)
                    if free_i is not None and not short():
                        break
                if free_i is None or short():
                    blocked.add(getattr(req.meta, "lane", None))
                    continue
            self._sched.pop(req, now)
            seq = self._install_slot_locked(free_i, req, worst)
            picked.append((free_i, req, seq))
        return picked

    def _prefill_packed(self, pre_idx):
        """ONE packed ragged prefill dispatch: take up to
        prefill_chunk_tokens prompt tokens across the slots still
        feeding their prompts (head-of-line slot order), concatenate
        the chunks into a token-packed stream (each chunk's region
        aligned to _pack_align, the packed length bucketed to a power
        of two), bulk-grow the chunk's block tables, and run the
        packed_prefill program — K/V lands directly in each sequence's
        paged blocks. Slots whose FINAL chunk is in this dispatch
        sample their first token here (that is their TTFT). The
        dispatch is ISSUED here, behind whatever decode step is in
        flight (the donated pool orders them on the device), and read
        by `_read_prefill`: returns what that needs, or None when there
        was nothing to feed or the dispatch failed."""
        with self._phase("plan"):
            jnp = self._jnp
            align = self._pack_align
            # sp multiplies the per-dispatch chunk budget: the sp-sharded
            # packed program runs T/sp tokens per shard, so sp chunks'
            # worth of prompt tokens cost one replica-budget dispatch
            budget = self.prefill_chunk_tokens * self._sp_degree
            # chunk-budget sharing (round 12): the scheduler orders the
            # feeding slots (interactive/EDF first) and may cap each slot's
            # share of this chunk so one lane cannot monopolize the budget;
            # without a scheduler the order is slot order, uncapped
            if self._sched is not None:
                entries = self._sched.prefill_plan(
                    [(i, self._slots[i]) for i in pre_idx], budget)
            else:
                entries = [(i, None) for i in pre_idx]
            plan = []  # (slot_idx, start, n, packed_offset)
            off = 0
            for i, cap in entries:
                if budget <= 0:
                    break
                s = self._slots[i]
                n = min(s["prompt"].size - s["fed"], budget)
                if cap is not None:
                    n = min(n, int(cap))
                if n <= 0:
                    continue
                plan.append((i, s["fed"], n, off))
                off += -(-n // align) * align
                # one plan shape: the budget is the packed length itself
                # (regions as aligned), so no stream outgrows its bucket
                budget -= -(-n // align) * align \
                    if self._one_plan_shape else n
            if not plan:
                return
            T = align  # power-of-two bucket: compile count is logarithmic
            while T < off:  # in the packed budget, not per prompt length
                T *= 2
            # COMPACT segment rows: the dispatch carries tables only for the
            # plan's slots (row count bucketed to a power of two), so a
            # one-request churn round pays for one row's cache, not
            # max_slots of them
            P = self._plan_rows(len(plan))
            toks = np.zeros((T,), np.int32)
            seg = np.zeros((T,), np.int32)
            pos = np.full((T,), -1, np.int32)  # -1 marks packing pad
            sample_idx = np.zeros((P,), np.int32)
            done_rows = []  # (slot_idx, compact_row)
            for r, (i, start, n, o) in enumerate(plan):
                s = self._slots[i]
                toks[o:o + n] = s["prompt"][start:start + n]
                seg[o:o + n] = r
                pos[o:o + n] = np.arange(start, start + n, dtype=np.int32)
                if s["t_pre0"] is None:
                    s["t_pre0"] = time.perf_counter()
                if start + n == s["prompt"].size:
                    sample_idx[r] = o + n - 1
                    done_rows.append((i, r))
            # decode-phase slots stall while this dispatch runs — the stall
            # the chunk budget exists to bound
            in_plan = {p[0] for p in plan}
            decoding = any(s is not None and j not in in_plan
                           and s["fed"] >= s["prompt"].size
                           for j, s in enumerate(self._slots))
            if self._recorder.enabled:
                self._recorder.record(
                    "prefill_chunk", packed=int(T), rows=len(plan),
                    tokens=int(sum(p[2] for p in plan)),
                    free_blocks=self.cache.available_block_count)
            if self._sp_degree > 1:
                self._note_sp_peak(T)
            parts = None
            if self._ledger is not None:
                parts = self._cost_parts(
                    [(self._slots[i]["req"], n)
                     for i, _start, n, _o in plan])
                self._attr_begin(parts)
        self._phases.kind("prefill")
        t0 = time.perf_counter()
        try:
            span = _tracing.span(
                "prefill_chunk", packed=T, segments=len(plan),
                tokens=int(sum(p[2] for p in plan)),
                round=self._phases.round,
                request_ids=[self._slots[i]["req"].rid for i, *_ in plan]
                if _tracing.enabled() else (), **self._rattr())
            with span:
                with self._phase("plan"):
                    self._maybe_fault("slow_dispatch")
                    self._maybe_fault("ensure_many")
                    # bulk multi-sequence allocation: the whole chunk plan's
                    # tables grow atomically (reservation-backed, so this
                    # cannot exhaust the pool mid-plan)
                    self.cache.ensure_many(
                        [(self._slots[i]["seq"], start + n)
                         for i, start, n, _ in plan])
                    if self.enable_prefix_cache:
                        # copy-on-write guard: a chunk starting mid-block in
                        # an attached (shared or index-claimed) block gets a
                        # private copy before the dispatch writes into it
                        for i, start, _n, _o in plan:
                            self.cache.prepare_write(
                                self._slots[i]["seq"], start)
                    # cap the table width at a power-of-two bucket of the
                    # plan's deepest chunk end: early chunks of long
                    # prompts attend (and the fallback gathers) only the
                    # cache they can reach, and the jit re-specializes per
                    # (T, width) pair — still logarithmically many
                    mcap = 1
                    need = max(self.cache.blocks_for(start + n)
                               for _, start, n, _ in plan)
                    while mcap < need:
                        mcap *= 2
                    mcap = min(mcap, self._m_width)
                    if self._one_plan_shape:
                        mcap = self._m_width
                    tables = self.cache.table_array(
                        [self._slots[plan[r][0]]["seq"]
                         if r < len(plan) else None for r in range(P)],
                        mcap)
                    # per-slot sampling buffers gathered to compact plan
                    # rows; token-0 sampling (PRNG step 0) runs the same
                    # vectorized pipeline as decode
                    done_set = {r for _, r in done_rows}
                    # per-row PRNG base step: 0 for a fresh prompt; a
                    # resumed request samples its next token at step
                    # len(generated so far), the exact counter position an
                    # uninterrupted decode would have used
                    base_steps = np.array(
                        [len(self._slots[plan[r][0]]["toks"])
                         if r < len(plan) else 0 for r in range(P)],
                        np.int32)
                    sp_args, sp_mode = self._sp_store.packed_args(
                        [plan[r][0] if r < len(plan) else None
                         for r in range(P)],
                        [r in done_set for r in range(P)], base_steps)
                with self._phase("dispatch"):
                    self._maybe_fault("prefill")
                    tok, stopped, *rest = \
                        self._decoder.packed_prefill(
                            self._params, jnp.asarray(toks),
                            jnp.asarray(seg), jnp.asarray(pos),
                            jnp.asarray(tables),
                            jnp.asarray(sample_idx), self.cache.k_blocks,
                            self.cache.v_blocks, sp_args, sp_mode,
                            state=self.cache.state)
                    self._probe_device("prefill", span)
                    # the pool, the store and the sampler's counts chain
                    # from program to program on the device
                    routed = self._chain(rest)
                    self._newest_out = tok
        except Exception as e:  # noqa: BLE001 — the recovery ladder
            # (or, with recovery off, the legacy fail-the-chunk path)
            self._dispatch_failure("prefill", e,
                                   [i for i, *_ in plan])
            return None
        return {"where": "prefill", "t0": t0, "parts": parts,
                "rows": {i: self._slots[i]["seq"] for i, *_ in plan},
                "plan": plan, "done_rows": done_rows, "decoding": decoding,
                "out": (tok, stopped, routed)}

    def _read_prefill(self, rec):
        """Read one packed prefill dispatch back and emit its first
        tokens: the rows whose prompt it completed join the NEXT decode
        step. The rows of a slot that no longer holds the planned
        sequence are dropped."""
        try:
            with self._phase("read_back"):
                tok, stopped, routed = rec["out"]
                tok_h = np.asarray(tok)
                stopped_h = np.asarray(stopped)
                routed = self._read_routed(routed)
                self._was_read(rec)
        except Exception as e:  # noqa: BLE001 — the recovery ladder
            self._read_failure(rec, e)
            return
        with self._phase("emit"):
            plan = [p for p in rec["plan"]
                    if self._holds(p[0], rec["rows"][p[0]])]
            if routed is not None:
                self._note_routed(routed["counts"])
            if self._desc is not None:
                self._tell_routing([(i, start, o, n)
                                    for i, start, n, o in plan], routed)
            self._dispatch_ok([self._slots[i]["req"].rid
                               for i, *_ in plan])
            t_now = time.perf_counter()
            t0 = rec["t0"]
            self._charge_read(rec, t_now)
            if self._ledger is not None:
                # feed the measured prefill unit cost (EMA) — the rate the
                # prefix-cache savings credit is priced at
                self._ledger.note_prefill_cost(
                    int((t_now - t0) * 1e9),
                    int(sum(p[2] for p in rec["plan"])))
            self._ops_progress += 1
            if rec["decoding"]:
                _m_decode_stall.observe(t_now - t0)
            _m_prefill_dispatches.inc()
            # goodput: a resumed request's chunk re-feeds already-generated
            # tokens (positions past its ORIGINAL prompt) — decoded work
            # that emits nothing, accounted as preempt replay
            replay = 0
            for i, start, n, _o in plan:
                req = self._slots[i]["req"]
                if req.resume_ids is not None:
                    replay += max(0, start + n - max(start, req.ids.size))
            with self._lock:
                self._prefill_dispatches += 1
                if replay:
                    self._decoded_tokens += replay
                    self._replayed_tokens += replay
            if replay:
                _m_decoded.inc(replay)
                _m_replayed.inc(replay)
            for i, start, n, o in plan:
                s = self._slots[i]
                s["fed"] = start + n
                s["chunks"] += 1
            for i, r in rec["done_rows"]:
                if not self._holds(i, rec["rows"][i]):
                    continue
                s = self._slots[i]
                req = s["req"]
                if req.ttft is None:
                    # first token of the request's LIFETIME — a resumed
                    # request keeps the TTFT of its first residency
                    req.ttft = t_now - req.t_submit
                    _m_ttft.observe(req.ttft)
                    if self._slo is not None:
                        self._slo_latency("ttft", req.ttft, req)
                    with self._lock:
                        self._ttft.append(req.ttft)
                        if req.meta is not None:
                            lane = req.meta.lane
                            self._lane_ttft.setdefault(lane, []).append(
                                req.ttft)
                            if req.meta.deadline_s is not None:
                                self._deadline_requests[lane] = \
                                    self._deadline_requests.get(lane, 0) + 1
                                if req.ttft > req.meta.deadline_s:
                                    self._deadline_misses[lane] = \
                                        self._deadline_misses.get(lane,
                                                                  0) + 1
                                    _m_deadline_miss.labels(lane=lane).inc()
                                    _m_deadline_overage.observe(
                                        req.ttft - req.meta.deadline_s)
                if self.enable_prefix_cache:
                    # every prompt K/V position is now written: index the
                    # blocks so later requests can attach this prefix (a
                    # resumed request publishes its resume prompt —
                    # original prompt + generated-so-far)
                    self.cache.publish_prefix(s["seq"], s["prompt"])
                # per-request prefill phase for the trace assembler: starts
                # at the request's FIRST chunk dispatch, ends now (its end
                # timestamp IS the request's first-token time)
                _tracing.event("prefill", request_id=req.rid,
                               ts=s["t_pre0"], dur=t_now - s["t_pre0"],
                               prompt_len=int(s["prompt"].size),
                               seq=s["seq"], chunks=s["chunks"],
                               cached_tokens=s["cached"], **self._tr(req))
                with self._lock:
                    self._prefills += 1
                    self._decoded_tokens += 1  # the token-0 sample
                _m_decoded.inc()
                s["t_last"] = t_now
                self._slot_token(i, int(tok_h[r]),
                                 device_stopped=bool(stopped_h[r]))

    def _read_failure(self, rec, e):
        """A dispatch failed where it was read: what was queued behind
        it took its pool and its tokens, so that goes with it, unread,
        and the recovery ladder takes the rows of both."""
        behind, self._pending = self._pending, None
        rows = dict(behind["rows"]) if behind is not None else {}
        rows.update(rec["rows"])
        self._dispatch_failure(
            rec["where"], e,
            [i for i, seq in rows.items() if self._holds(i, seq)])

    # ---- did the device run dry (ISSUE 34) -------------------------------
    @staticmethod
    def _is_ready(out):
        """Whether the program that writes `out` has finished, without
        waiting for it."""
        try:
            return bool(out.is_ready())
        except Exception:  # noqa: BLE001 — a program that failed is not
            return True    # running; the read that follows reports it

    def _probe_device(self, kind, span):
        """In a `dispatch` phase of the default loop, when the jit call
        has returned and the new program is queued: has the newest
        dispatch issued before it, and not yet read, finished? Programs
        run in order on the chip, so if it has, everything queued before
        this dispatch has, and the device stood idle until this one
        landed: counted in `stats()["dispatch_ahead"]` by the `kind`
        issued, the round marked as one that starved the device
        (`round_phases.starved_seconds`), `found_idle=0|1` on the
        dispatch span. One `is_ready()` a dispatch, and it is asked here
        and not at the top of the phase because that is where the device
        runs dry: during the host's uploads and call (at the top the
        chip of PR 34 was busy in all but 2-7 of ~9,900 dispatches). The
        flag turns true some tenths of a millisecond after the device's
        last op ends, so the count is a floor. With nothing unread
        (after a drain; a drafter or k steps a dispatch, which read what
        they issue at once) there is nothing to ask and nothing is
        counted: the device is idle by construction, and the span says
        so all the same (`found_idle=1`)."""
        out = self._newest_out
        idle = out is None or self._is_ready(out)
        if out is not None:
            with self._lock:
                self._probed[kind] += 1
                if idle:
                    self._found_idle[kind] += 1
            if idle:
                self._phases.starved()
        span.set(found_idle=int(idle))

    def _was_read(self, rec):
        """The dispatch `rec` was read back: if it was the newest one
        issued, nothing is in flight."""
        if rec["out"][0] is self._newest_out:
            self._newest_out = None

    def _charge_read(self, rec, t_now):
        """Charge a dispatch's residents the wall time from its issue
        to this read, less what the dispatch read before it was
        charged already: with one in flight the intervals overlap, and
        a second of the device is billed once."""
        self._charge_dispatch(
            t_now - max(rec["t0"], self._charged_to), rec["parts"])
        self._charged_to = t_now

    def _slot_token(self, i, tok, device_stopped=False):
        """Record one generated token for slot i; completes the request
        when generation stopped (slot freed for refill). Stop sources,
        in precedence order:
          * device_stopped — the dispatch's per-slot stop-token matrix
            matched (server EOS or a request stop_token_id);
          * stop strings — host-side: the request's stop strings
            searched in the detokenized last `stop_tail_tokens` tokens
            (the emitted tokens stay in the output);
          * budget — the request's token budget is exhausted."""
        slot = self._slots[i]
        slot["toks"].append(tok)
        if self._journal is not None:
            self._journal.record_token(slot["req"].rid, tok)
        sp = slot["req"].sampling
        reason = None
        if device_stopped:
            reason = ("eos" if self.eos >= 0 and tok == self.eos
                      else "stop_token")
        elif sp is not None and sp.stop_strings:
            # the token list spans preemption boundaries (a resumed
            # slot is re-seeded with its prior tokens), so a stop
            # string straddling a swap-out still matches
            try:
                if self._faults is not None:
                    self._maybe_fault("detokenize")
                tail = self._detok(slot["toks"][-self.stop_tail_tokens:])
            except Exception as e:  # noqa: BLE001 — a broken
                # detokenizer implicates exactly ONE request: fail it
                # with the seam named and keep every co-resident alive
                # (before r17 this killed the whole engine thread)
                self._quarantine_slot(i, "detokenize", e, 1)
                return
            if any(s in tail for s in sp.stop_strings):
                reason = "stop_string"
        if reason is None and len(slot["toks"]) >= slot["budget"]:
            reason = "budget"
        cb = slot["req"].on_token
        if cb is not None:
            # streaming (round 12): deliver from the engine thread —
            # the consumer side (frontend.stream) is bounded and
            # non-blocking; a broken callback must not kill the loop
            try:
                if self._faults is not None:
                    self._maybe_fault("stream_consumer")
                cb(tok, reason)
            except Exception:  # noqa: BLE001 — stream is best-effort
                _logger.exception(
                    "on_token callback failed for request %s "
                    "(stream dropped; generation continues)",
                    slot["req"].rid)
                slot["req"].on_token = None
        if reason is not None:
            seq, req = slot["seq"], slot["req"]
            self._ops_progress += 1
            self._fault_streak.pop(req.rid, None)
            if self._journal is not None:
                self._journal.record_done(req.rid, reason)
            self._recorder.record("request_done", request_id=req.rid,
                                  slot=i, new_tokens=len(slot["toks"]),
                                  reason=reason, **self._tr(req))
            cost = (self._ledger.request_done(req.rid,
                                              len(slot["toks"]))
                    if self._ledger is not None else None)
            _tracing.event("request_done", request_id=req.rid,
                           new_tokens=len(slot["toks"]),
                           ttft_s=req.ttft, reason=reason, cost=cost,
                           **self._tr(req))
            self._slo_avail(req, True)
            with _tracing.span("detokenize", request_id=req.rid,
                               **self._tr(req)):
                out = np.concatenate([req.ids,
                                      np.asarray(slot["toks"], np.int32)])
                self.cache.free(seq)
                del self._worst[seq]
                self._slots[i] = None
                self._sp_store.clear_slot(i)
                t_done = time.perf_counter()
                with self._lock:
                    self._lat.append(t_done - req.t_submit)
                    self._tokens_out += len(slot["toks"])
                    self._requests_done += 1
                    self._stop_reasons[reason] += 1
                _m_slot_releases.labels(reason=reason).inc()
                _m_stop_reason.labels(server="paged",
                                      reason=reason).inc()
                _m_requests_done.labels(server="paged").inc()
                _m_request_latency.labels(server="paged").observe(
                    t_done - req.t_submit)
                req.future.set_result(out)

    def _loop(self):
        self._phases.thread_started()
        try:
            self._loop_body()
        except Exception as e:  # noqa: BLE001 — an unhandled engine
            # bug (outside the per-dispatch except paths) must leave a
            # post-hoc record before the thread dies: health goes
            # degraded and the flight recorder dumps
            self._engine_exception("engine_loop", e)
            raise
        finally:
            self._phases.thread_stopped()

    def _loop_body(self):
        while True:
            self._phases.close_round()
            with self._phase("admit"), self._lock:
                if self._stop:
                    # async: resolve the in-flight round so no future
                    # is stranded mid-stream
                    self._drain_pending("stop")
                    self._fail_host_ops_locked(
                        RuntimeError("server stopped"))
                    return
                if self._host_ops:
                    # fleet host ops (r18): run queued migration
                    # exports/imports on THIS thread at the round
                    # boundary — the in-flight round is drained first
                    # so its write-back cannot overwrite an import
                    self._drain_pending("host_op")
                    self._run_host_ops_locked()
                if self._any_timeouts:
                    self._expire_timeouts_locked(time.perf_counter())
                self._admit_locked()
                if all(s is None for s in self._slots):
                    if self._pending is not None:
                        # only rows of requests that finished are left in
                        # it: read it, and admit into the slots it held
                        self._drain_pending("idle")
                        continue
                    with self._phase("idle_wait"):
                        self._lock.wait(timeout=0.1)
                    continue
            if self._unified:
                self._round_unified()
            else:
                self._round_split()

    def _note_round(self, n_dispatches, mixed):
        """Per-round dispatch accounting (r16), shared by both engine
        paths: `mixed` marks a round that carried prefill AND
        decode/verify work — the rounds the unified kernel collapses
        from up to 3 dispatches to 1."""
        with self._phase("emit"):
            with self._lock:
                self._rounds += 1
                self._round_dispatch_count += n_dispatches
                if mixed:
                    self._mixed_rounds += 1
                if self._slo is not None:
                    self._slo_goodput_round()
            _m_round_dispatches.observe(float(n_dispatches))
            # capacity auto-sampling (ISSUE 17): min-interval gated, so
            # this is a near-free no-op on almost every round
            self._maybe_sample_capacity()

    def _round_split(self):
        """One scheduler round of the SPLIT path (the default loop): at
        most one packed chunk-prefill dispatch, then one verify and/or
        one plain decode dispatch, with ONE DISPATCH KEPT IN FLIGHT.
        The decode step of this round is queued on the device before
        the step of the last round is read back, and the prefill's
        first tokens are read after that, so the device holds its next
        program while the host reads, emits, admits and plans
        (docs/SERVING.md "The round's order"). An engine that needs the
        host's tokens before it can dispatch again (a drafter, a scan
        of k steps) reads what it issues at once: `_keeps_in_flight`."""
        d0 = (self._prefill_dispatches + self._steps
              + self._spec_dispatches)
        flying = self._pending
        # ---- packed/chunked prefill: at most ONE chunk dispatch
        # per round, interleaved with the decode dispatch below, so
        # in-flight decode never stalls longer than one chunk budget
        with self._phase("plan"):
            pre_idx = [i for i, s in enumerate(self._slots)
                       if s is not None
                       and s["fed"] < s["prompt"].size]
        pre = self._prefill_packed(pre_idx) if pre_idx else None
        if pre is not None and not self._keeps_in_flight:
            self._read_prefill(pre)
            pre = None
        with self._phase("plan"):
            _m_slots_busy.labels(server="paged").set(
                sum(s is not None for s in self._slots))
            # decode phase: prompt fully fed (first token sampled). A
            # slot whose last chunk is in flight is not there yet: its
            # rows join the decode one step later
            active_idx = [i for i, s in enumerate(self._slots)
                          if s is not None
                          and s["fed"] >= s["prompt"].size]
        if active_idx:
            # speculative decoding (round 11): eligible slots propose
            # drafts and take ONE packed verification dispatch instead
            # of a decode step; the rest decode plainly below. With
            # speculation off this is a no-op and the round is the
            # exact pre-speculation path.
            spec_slots = ()
            if self._drafter is not None:
                spec_slots = self._speculate(active_idx)
            plain_idx = [i for i in active_idx
                         if i not in spec_slots
                         and self._slots[i] is not None]
            if plain_idx:
                self._decode_plain(plain_idx)
        if flying is not None and self._pending is flying:
            # the step in flight got no successor (its rows end with it)
            self._drain_pending("no_successor")
        if pre is not None:
            self._read_prefill(pre)
        # tier prefetch-ahead: promote the NEXT queued requests' cold
        # blocks now, before the coming round boundary's admission
        # pass runs attach_prefix (one `look` check when disabled)
        with self._phase("admit"):
            self._tier_prefetch_tick()
        d1 = (self._prefill_dispatches + self._steps
              + self._spec_dispatches)
        if d1 > d0:
            self._note_round(d1 - d0,
                             mixed=bool(pre_idx) and bool(active_idx))

    @property
    def _keeps_in_flight(self):
        """Whether the default loop may queue a dispatch before it has
        read the last one: not with a drafter (a draft is made from the
        host's tokens) and not with a scan of k steps (its successor's
        positions wait for how many of the k were kept)."""
        return self._drafter is None and self.steps_per_dispatch == 1

    def _holds(self, i, seq):
        """Whether slot `i` still holds the sequence a dispatch was
        issued for (a finish, a timeout or a failure may have taken it
        before the dispatch is read)."""
        s = self._slots[i]
        return s is not None and s["seq"] == seq

    def _slot_free(self, i):
        """Whether slot `i` can take a request: empty, and in no
        dispatch still in flight (a row that finished by a stop only
        the read revealed keeps its slot until its last dispatch is
        read)."""
        return self._slots[i] is None and (
            self._pending is None or self._unified
            or i not in self._pending["rows"])

    # ---- one-kernel round (r16) -----------------------------------------

    def _round_unified(self):
        """One scheduler round of the UNIFIED path: build the combined
        plan (chunk prefill rows + decode rows + verify regions), run
        it as ONE dispatch, and process the results.

        Synchronous mode processes the round immediately. ASYNC mode
        double-buffers: the round dispatched here runs on device while
        the NEXT loop iteration plans and dispatches its successor
        (inputs chained through the device carry), and only then syncs
        this round's outputs — so the host plan+dispatch work is
        hidden behind device execution, measured as overlap."""
        t0 = time.perf_counter()
        with self._phase("plan"):
            plan = self._plan_round()
        outs = self._dispatch_round(plan) if plan is not None else None
        t1 = time.perf_counter()
        # tier prefetch-ahead: the dispatch above is in flight on
        # device — promote the next queued requests' cold tier blocks
        # through this host-side window (the r16 async seam: the
        # overlapped work is pure host state, outside the overlap
        # measurement so the planner metric stays comparable)
        with self._phase("admit"):
            self._tier_prefetch_tick()
        if not self._async:
            if outs is not None:
                self._process_round(plan, outs)
            return
        pending, self._pending = self._pending, None
        if pending is not None:
            # everything since the previous iteration's sync point ran
            # while the pending round executed on device
            overlap = t1 - t0
            with self._lock:
                self._overlap_s += overlap
            _m_round_overlap.observe(overlap)
            self._process_round(*pending)
        if outs is not None:
            self._pending = (plan, outs)
        else:
            self._carry = None  # chain broken: reseed from host state

    def _drain_pending(self, why):
        """Resolve the dispatch in flight NOW so host state is
        authoritative (preemption swap-out, host ops, timeouts, a
        failed dispatch, engine stop, idle); `why` names the seam, for
        `stats()["dispatch_ahead"]["drains"]`. The async unified loop
        breaks its device chain here (the carry reseeds from host state
        at the next plan). No-op when nothing is in flight."""
        pending, self._pending = self._pending, None
        if pending is None:
            return
        with self._lock:
            self._drains[why] = self._drains.get(why, 0) + 1
        if self._unified:
            self._carry = None
            self._process_round(*pending)
        else:
            self._read_decode(pending)

    def _seed_carry(self):
        """(Re)build the slot-indexed device carry from host state —
        the async chain's starting point after a start/drain. Only
        decode-phase slots have meaningful carry entries; everything
        else is written by its own round before being read."""
        jnp = self._jnp
        S = self.max_slots
        tok = np.zeros((S,), np.int32)
        posn = np.zeros((S,), np.int32)
        st = np.zeros((S,), np.int32)
        for i, s in enumerate(self._slots):
            if s is not None and s["toks"] \
                    and s["fed"] >= s["prompt"].size:
                tok[i] = s["toks"][-1]
                posn[i] = s["pos"] + len(s["toks"]) - 1
                st[i] = len(s["toks"])
        self._carry = (jnp.asarray(tok), jnp.asarray(posn),
                       jnp.asarray(st))

    def _seed_carry_slot(self, i):
        """Install one slot's host-known decode state into the live
        device carry — needed when a slot enters the decode phase
        without a unified dispatch having set its carry entry (the
        warm preempt-resume fast path joins the next decode dispatch
        directly)."""
        if self._carry is None:
            return
        s = self._slots[i]
        ct, cp, cs = self._carry
        self._carry = (ct.at[i].set(int(s["toks"][-1])),
                       cp.at[i].set(int(s["pos"] + len(s["toks"]) - 1)),
                       cs.at[i].set(len(s["toks"])))

    def _plan_round(self):
        """Build ONE combined round plan: prefill chunk rows (the
        exact `_prefill_packed` budget/ordering policy), plain decode
        rows, and speculative verify regions — each plan row is one
        ragged segment of a single packed stream, host-deterministic
        even in async mode (decode inputs are carry REFERENCES, not
        values). Returns None when no slot has work."""
        align = self._pack_align
        dalign = self._verify_align
        K1 = self._uk1
        # pinned decode/verify region width: one compiled T per round
        # composition, not per draft-count combination
        W = -(-K1 // dalign) * dalign
        rows = []
        # ---- chunk half (the _prefill_packed policy)
        pre_idx = [i for i, s in enumerate(self._slots)
                   if s is not None and s["fed"] < s["prompt"].size]
        budget = self.prefill_chunk_tokens
        if self._sched is not None and pre_idx:
            entries = self._sched.prefill_plan(
                [(i, self._slots[i]) for i in pre_idx], budget)
        else:
            entries = [(i, None) for i in pre_idx]
        for i, cap in entries:
            if budget <= 0:
                break
            s = self._slots[i]
            n = min(s["prompt"].size - s["fed"], budget)
            if cap is not None:
                n = min(n, int(cap))
            if n <= 0:
                continue
            rows.append({"kind": "chunk", "slot": i, "seq": s["seq"],
                         "start": s["fed"], "n": n,
                         "width": -(-n // align) * align,
                         "done": s["fed"] + n == s["prompt"].size})
            budget -= n
        # ---- decode / verify half: every decode-phase slot rides the
        # same dispatch (draft-free slots as dlen=0 rows)
        for i, s in enumerate(self._slots):
            if s is None or s["fed"] < s["prompt"].size:
                continue
            drafts = np.empty((0,), np.int32)
            if self._drafter is not None:
                # async note: the context is the host-KNOWN tokens —
                # up to one round stale. Stale drafts only lower the
                # acceptance rate; the verify math emits the target's
                # tokens regardless, so output is unchanged.
                remaining = s["budget"] - len(s["toks"])
                kcap = min(self._spec_k, remaining - 1)
                if kcap >= 1:
                    ctx = np.concatenate(
                        [s["req"].ids, np.asarray(s["toks"], np.int32)])
                    drafts = np.asarray(
                        self._drafter.propose(ctx, kcap),
                        np.int32).reshape(-1)[:kcap]
            rows.append({"kind": "step", "slot": i, "seq": s["seq"],
                         "drafts": drafts, "width": W,
                         "steps": len(s["toks"]),
                         "wpos": s["pos"] + len(s["toks"]) - 1})
        if not rows:
            return None
        if self._async and self._carry is None:
            self._seed_carry()
        P = 1
        while P < len(rows):
            P *= 2
        # chunk-free rounds (steady-state decode/verify — the common
        # case) take the WINDOW layout: T = P * W exactly, one pinned
        # region per row, so the dispatch runs the dense verify-window
        # trunk instead of paying the mixed-round packed geometry
        window = all(row["kind"] == "step" for row in rows)
        if window and self._async:
            # steady-state fast path: in async mode the whole device
            # argument set depends only on (slot, seq, drafts) — when
            # the signature matches the args cache, skip building the
            # plan arrays altogether (the host planner's inner loop
            # disappears from the round)
            akey = (P * W, P, tuple((row["slot"], row["seq"],
                                     row["drafts"].tobytes())
                                    for row in rows))
            if self._args_cache is not None \
                    and self._args_cache[0] == akey:
                return {"rows": rows, "T": P * W, "P": P,
                        "window": True, "akey": akey, "cached": True,
                        "n_chunk": 0, "n_step": len(rows),
                        "n_drafts": sum(int(r["drafts"].size)
                                        for r in rows)}
        if window:
            offsets = [r * W for r in range(len(rows))]
            T = P * W
        else:
            off = 0
            offsets = []
            for row in rows:
                offsets.append(off)
                off += row["width"]
            T = align  # power-of-two bucket, the chunk-path policy
            while T < off:
                T *= 2
        toks = np.zeros((T,), np.int32)
        seg = np.zeros((T,), np.int32)
        pos = np.full((T,), -1, np.int32)
        carry_map = np.full((T,), -1, np.int32)
        pos_map = np.full((T,), -1, np.int32)
        sample_idx = np.zeros((P, K1), np.int32)
        dlen = np.full((P,), -1, np.int32)
        row_slot = np.full((P,), -1, np.int32)
        steps_map = np.full((P,), -1, np.int32)
        steps = np.zeros((P,), np.int32)
        emit_rows = [False] * P
        n_chunk = n_step = n_drafts = 0
        for r, (row, o) in enumerate(zip(rows, offsets)):
            i = row["slot"]
            s = self._slots[i]
            if row["kind"] == "chunk":
                n_chunk += 1
                n = row["n"]
                start = row["start"]
                toks[o:o + n] = s["prompt"][start:start + n]
                seg[o:o + n] = r
                pos[o:o + n] = np.arange(start, start + n,
                                         dtype=np.int32)
                if s["t_pre0"] is None:
                    s["t_pre0"] = time.perf_counter()
                sample_idx[r] = o + n - 1  # every readout clamps there
                if row["done"]:
                    # token-0 samples HERE: a dlen=0 row at the
                    # resume-aware base step (0 for a fresh prompt)
                    dlen[r] = 0
                    row_slot[r] = i
                    steps[r] = len(s["toks"])
                    emit_rows[r] = True
            else:
                n_step += 1
                drafts = row["drafts"]
                k = int(drafts.size)
                n_drafts += k
                seg[o:o + 1 + k] = r
                toks[o + 1:o + 1 + k] = drafts
                if self._async:
                    # decode input token / positions / PRNG base step
                    # resolve from the device carry: round N's sample
                    # feeds round N+1 without a host sync
                    carry_map[o] = i
                    pos[o:o + 1 + k] = np.arange(0, 1 + k,
                                                 dtype=np.int32)
                    pos_map[o:o + 1 + k] = i
                    steps_map[r] = i
                else:
                    toks[o] = s["toks"][-1]
                    pos[o:o + 1 + k] = np.arange(
                        row["wpos"], row["wpos"] + 1 + k,
                        dtype=np.int32)
                    steps[r] = row["steps"]
                sample_idx[r] = o + np.minimum(np.arange(K1), k)
                dlen[r] = k
                row_slot[r] = i
                emit_rows[r] = True
        return {"rows": rows, "T": T, "P": P, "window": window,
                "toks": toks, "seg": seg,
                "pos": pos, "carry_map": carry_map, "pos_map": pos_map,
                "sample_idx": sample_idx, "dlen": dlen,
                "row_slot": row_slot, "steps_map": steps_map,
                "steps": steps, "emit_rows": emit_rows,
                "n_chunk": n_chunk, "n_step": n_step,
                "n_drafts": n_drafts}

    def _zero_carry_arrays(self):
        jnp = self._jnp
        if self._zero_carry is None:
            z = jnp.zeros((self.max_slots,), jnp.int32)
            self._zero_carry = (z, z, z)
        return self._zero_carry

    def _dispatch_round(self, plan):
        """Run one unified-round dispatch. Host-deterministic slot
        bookkeeping (fed positions, dispatch counters, proposal
        accounting) happens here; emissions wait for
        `_process_round`. Returns the device output triple (vtok,
        accepted, stopped) or None after a dispatch failure (the
        plan's slots are failed and freed)."""
        with self._phase("plan"):
            jnp = self._jnp
            rows = plan["rows"]
            # grow every row's table in one atomic call. Async step rows
            # grow to the host UPPER BOUND on the device write horizon
            # (the carry may be up to one emitted round ahead), capped by
            # the admission reservation.
            updates = []
            for row in rows:
                s = self._slots[row["slot"]]
                if row["kind"] == "chunk":
                    updates.append((row["seq"], row["start"] + row["n"]))
                else:
                    k = int(row["drafts"].size)
                    # the last known token writes at wpos, drafts at
                    # wpos+1..wpos+k (the split verify's horizon). Async:
                    # the device write front may be one emitted round
                    # ahead of wpos — grow by that bound too, capped at
                    # the admission reservation.
                    need = row["wpos"] + k + 1
                    if self._async:
                        cap = s["pos"] + s["budget"] + self._overrun
                        need = min(need + 1 + self._spec_k, cap)
                    updates.append((row["seq"], need))
            if self._recorder.enabled:
                self._recorder.record(
                    "round", packed=plan["T"], rows=len(rows),
                    chunk_rows=plan["n_chunk"], step_rows=plan["n_step"],
                    proposed=plan["n_drafts"],
                    free_blocks=self.cache.available_block_count)
            # chunk rows weigh their fed tokens, step rows their verify
            # positions (drafts + the step token) — the same work split
            # the packed program computes
            parts = None
            if self._ledger is not None:
                parts = self._cost_parts(
                    [(self._slots[row["slot"]]["req"],
                      row["n"] if row["kind"] == "chunk"
                      else row["drafts"].size + 1) for row in rows])
                self._attr_begin(parts)
            plan["cost_parts"] = parts  # _process_round charges its sync
            # wait to the same rows
        self._phases.kind("unified")
        t0 = time.perf_counter()
        try:
            with _tracing.span(
                    "round", packed=plan["T"], segments=len(rows),
                    chunk_rows=plan["n_chunk"],
                    step_rows=plan["n_step"], round=self._phases.round,
                    request_ids=[self._slots[row["slot"]]["req"].rid
                                 for row in rows]
                    if _tracing.enabled() else (), **self._rattr()):
                with self._phase("plan"):
                    self._maybe_fault("slow_dispatch")
                    self._maybe_fault("ensure_many")
                    self.cache.ensure_many(updates)
                    if self.enable_prefix_cache and plan["n_chunk"]:
                        # CoW guard: a chunk starting mid-block in an
                        # attached (shared or index-claimed) block gets a
                        # private copy before the dispatch writes into it.
                        # A copy SWAPS a block id without changing the
                        # row's block count, so the table cache below
                        # cannot key on it — drop it for CoW-risk rounds.
                        for row in rows:
                            if row["kind"] == "chunk":
                                self.cache.prepare_write(row["seq"],
                                                         row["start"])
                        self._tables_cache = None
                    P = plan["P"]
                    seqs = tuple(rows[r]["seq"] if r < len(rows) else None
                                 for r in range(P))
                    # device-argument reuse: the table matrix changes only
                    # when a row's block count grows, and in ASYNC window
                    # rounds (steady-state decode — no chunk rows, inputs
                    # ride the carry) the ENTIRE plan argument set is
                    # invariant per (slot, seq, drafts) signature — most
                    # rounds then re-dispatch already-uploaded arrays and
                    # the host planner all but vanishes from the round
                    tkey = (seqs, tuple(self.cache.blocks_held(s)
                                        if s is not None else 0
                                        for s in seqs))
                    tables = tables_h = None
                    if self._tables_cache is not None \
                            and self._tables_cache[0] == tkey:
                        tables = self._tables_cache[1]
                    else:
                        tables_h = self.cache.table_array(
                            list(seqs), self._m_width)
                    dev = akey = None
                    if plan.get("cached"):
                        dev = self._args_cache[1]
                    elif self._async and plan["window"]:
                        akey = (plan["T"], P, tuple(
                            (row["slot"], row["seq"],
                             row["drafts"].tobytes()) for row in rows))
                        if self._args_cache is not None \
                                and self._args_cache[0] == akey:
                            dev = self._args_cache[1]
                    if dev is None:
                        slot_rows = [rows[r]["slot"] if r < len(rows)
                                     else None for r in range(P)]
                        sp_args, sp_mode = self._sp_store.unified_args(
                            slot_rows, plan["emit_rows"], plan["steps"])
                with self._phase("dispatch"):
                    if tables is None:
                        tables = jnp.asarray(tables_h)
                        self._tables_cache = (tkey, tables)
                    if dev is None:
                        dev = {
                            "toks": jnp.asarray(plan["toks"]),
                            "seg": jnp.asarray(plan["seg"]),
                            "pos": jnp.asarray(plan["pos"]),
                            "sample_idx": jnp.asarray(plan["sample_idx"]),
                            "dlen": jnp.asarray(plan["dlen"]),
                            "row_slot": jnp.asarray(plan["row_slot"]),
                            "carry_map": jnp.asarray(plan["carry_map"]),
                            "pos_map": jnp.asarray(plan["pos_map"]),
                            "steps_map": jnp.asarray(plan["steps_map"]),
                            "sp": sp_args, "mode": sp_mode,
                        }
                        if akey is not None:
                            self._args_cache = (akey, dev)
                    sp_args, sp_mode = dev["sp"], dev["mode"]
                    if sp_mode[1]:
                        # the penalty count buffer round-trips through
                        # the dispatch — refresh that one leaf per round
                        sp_args = dict(sp_args,
                                       counts=self._sp_store.counts)
                    if self._async:
                        ct, cp, cs = self._carry
                    else:
                        ct, cp, cs = self._zero_carry_arrays()
                    self._maybe_fault("unified_round")
                    (vtok, accepted, stopped, kc, vc, counts, nct, ncp,
                     ncs) = self._decoder.unified_round(
                        self._params, dev["toks"], dev["seg"], dev["pos"],
                        tables, dev["sample_idx"], dev["dlen"],
                        dev["row_slot"], dev["carry_map"],
                        dev["pos_map"], dev["steps_map"], ct, cp, cs,
                        self.cache.k_blocks, self.cache.v_blocks,
                        sp_args, sp_mode, window=plan["window"])
        except Exception as e:  # noqa: BLE001 — the recovery ladder
            # (or, with recovery off, the legacy fail-all path)
            self._carry = None
            self._dispatch_failure("unified_round", e,
                                   [row["slot"] for row in rows])
            return None
        with self._phase("emit"):
            self._sp_store.swap_counts(counts)
            self.cache.swap_arrays(kc, vc)
            self._dispatch_ok([self._slots[row["slot"]]["req"].rid
                               for row in rows
                               if self._slots[row["slot"]] is not None])
            if self._async:
                self._carry = (nct, ncp, ncs)
            self._charge_dispatch(time.perf_counter() - t0, parts)
            if self._ledger is not None and plan["n_chunk"]:
                chunk_toks = sum(row["n"] for row in rows
                                 if row["kind"] == "chunk")
                self._ledger.note_prefill_cost(
                    int((time.perf_counter() - t0) * 1e9), chunk_toks)
            self._ops_progress += 1
            # host-deterministic bookkeeping (valid before any sync): fed
            # positions advance, dispatch/mode counters, spec proposals
            replay = 0
            for row in rows:
                if row["kind"] != "chunk":
                    continue
                s = self._slots[row["slot"]]
                s["fed"] = row["start"] + row["n"]
                s["chunks"] += 1
                req = s["req"]
                if req.resume_ids is not None:
                    # a resumed request's chunk re-feeds already-generated
                    # tokens — decoded work that emits nothing
                    replay += max(0, row["start"] + row["n"]
                                  - max(row["start"], req.ids.size))
            sampled = bool(sp_mode[0])
            with self._lock:
                if plan["n_chunk"]:
                    self._prefill_dispatches += 1
                if plan["n_step"]:
                    self._steps += 1
                    self._active_integral += plan["n_step"]
                    self._fill_integral += self.cache.block_fill()
                if sampled:
                    self._sampled_dispatches += 1
                else:
                    self._fastpath_dispatches += 1
                if plan["n_drafts"]:
                    self._spec_dispatches += 1
                    self._spec_proposed += plan["n_drafts"]
                    self._spec_rounds_per_slot += sum(
                        1 for row in rows if row["kind"] == "step"
                        and row["drafts"].size)
                if replay:
                    self._decoded_tokens += replay
                    self._replayed_tokens += replay
            if plan["n_chunk"]:
                _m_prefill_dispatches.inc()
            if plan["n_drafts"]:
                _m_spec_verify.inc()
                _m_spec_proposed.inc(plan["n_drafts"])
            (_m_sampling_sampled if sampled else _m_sampling_fast).inc()
            if replay:
                _m_decoded.inc(replay)
                _m_replayed.inc(replay)
            _m_slots_busy.labels(server="paged").set(
                sum(s is not None for s in self._slots))
            self._note_round(1, mixed=bool(plan["n_chunk"]
                                           and plan["n_step"]))
        return (vtok, accepted, stopped)

    def _process_round(self, plan, outs):
        """Sync one unified round's outputs and emit its tokens — the
        ONLY host<->device sync point of the unified loop (async: runs
        one round late, while the successor executes). Rows whose slot
        was freed since planning (async overshoot past a stop the host
        had not yet seen) are discarded as replay, token-identically
        to the split path."""
        self._phases.kind("unified")
        t_sync0 = time.perf_counter()
        with self._phase("read_back"):
            vtok_h = np.asarray(outs[0])
            acc_h = np.asarray(outs[1])
            stop_h = np.asarray(outs[2])
        with self._phase("emit"):
            t_now = time.perf_counter()
            # async: the asarray above is where the host actually waits on
            # the device — busy time the dispatch-site charge missed
            self._charge_dispatch(t_now - t_sync0,
                                  plan.get("cost_parts") or ())
            self._ops_progress += 1
            decoded = 0
            discarded = 0
            rolled = 0
            accepted_n = 0
            itl_updates = []
            for r, row in enumerate(plan["rows"]):
                i = row["slot"]
                s = self._slots[i]
                live = self._holds(i, row["seq"])
                if row["kind"] == "chunk":
                    if not row["done"]:
                        continue
                    decoded += 1
                    if not live:
                        discarded += 1
                        continue
                    req = s["req"]
                    if req.ttft is None:
                        # first token of the request's LIFETIME — a resumed
                        # request keeps the TTFT of its first residency
                        req.ttft = t_now - req.t_submit
                        _m_ttft.observe(req.ttft)
                        if self._slo is not None:
                            self._slo_latency("ttft", req.ttft, req)
                        with self._lock:
                            self._ttft.append(req.ttft)
                            if req.meta is not None:
                                lane = req.meta.lane
                                self._lane_ttft.setdefault(
                                    lane, []).append(req.ttft)
                                if req.meta.deadline_s is not None:
                                    self._deadline_requests[lane] = \
                                        self._deadline_requests.get(
                                            lane, 0) + 1
                                    if req.ttft > req.meta.deadline_s:
                                        self._deadline_misses[lane] = \
                                            self._deadline_misses.get(
                                                lane, 0) + 1
                                        _m_deadline_miss.labels(
                                            lane=lane).inc()
                                        _m_deadline_overage.observe(
                                            req.ttft - req.meta.deadline_s)
                    if self.enable_prefix_cache:
                        self.cache.publish_prefix(s["seq"], s["prompt"])
                    _tracing.event("prefill", request_id=req.rid,
                                   ts=s["t_pre0"],
                                   dur=t_now - s["t_pre0"],
                                   prompt_len=int(s["prompt"].size),
                                   seq=s["seq"], chunks=s["chunks"],
                                   cached_tokens=s["cached"])
                    with self._lock:
                        self._prefills += 1
                    s["t_last"] = t_now
                    self._slot_token(i, int(vtok_h[r, 0]),
                                     device_stopped=bool(stop_h[r, 0]))
                    continue
                # decode / verify row
                a = int(acc_h[r])
                k_r = int(row["drafts"].size)
                decoded += k_r + 1
                if not live:
                    # async overshoot: the device ran one extra round for a
                    # slot the host has since stopped — pure replay, plus
                    # its drafts count as rolled back (conservation:
                    # proposed == accepted + rolled_back)
                    rolled += k_r
                    discarded += 1
                    continue
                if k_r and not self._async:
                    # rollback FIRST (while the sequence still exists); the
                    # async chain instead overwrites rejected positions at
                    # the next rounds' write front (see docs/SERVING.md)
                    self.cache.truncate_seq(s["seq"],
                                            row["wpos"] + a + 1)
                if k_r:
                    rolled += k_r - a
                    accepted_n += a
                    _m_spec_accepted.inc(a)
                    _m_spec_accept_rate.observe(a / k_r)
                    _tracing.event("spec_round", request_id=s["req"].rid,
                                   proposed=k_r, accepted=a,
                                   rolled_back=k_r - a)
                t_prev = s["t_last"] if s["t_last"] is not None else t_now
                consumed = 0
                for jj in range(a + 1):
                    consumed += 1
                    self._slot_token(i, int(vtok_h[r, jj]),
                                     device_stopped=bool(stop_h[r, jj]))
                    if self._slots[i] is None:  # stopped mid-prefix
                        break
                discarded += (a + 1) - consumed
                if self._slots[i] is not None:
                    self._slots[i]["t_last"] = t_now
                per = max(t_now - t_prev, 0.0) / consumed
                lane = (s["req"].meta.lane if s["req"].meta is not None
                        else None)
                itl_updates.append((per, consumed, lane))
                if self._slo is not None:
                    self._slo_latency("itl", per, s["req"], n=consumed)
                for _ in range(consumed):
                    _m_itl.observe(per)
            with self._lock:
                for per, consumed, lane in itl_updates:
                    self._itl.extend([per] * consumed)
                    if lane is not None:
                        self._lane_itl.setdefault(lane, []).extend(
                            [per] * consumed)
                self._decoded_tokens += decoded
                self._spec_accepted += accepted_n
                self._spec_rolled_back += rolled
                if discarded:
                    self._replayed_tokens += discarded
            _m_decoded.inc(decoded)
            if rolled:
                _m_spec_rolled_back.inc(rolled)
            if discarded:
                _m_replayed.inc(discarded)
            _m_goodput.set(self._tokens_out / (self._decoded_tokens or 1))

    def _decode_plain(self, active_idx):
        """One plain decode dispatch (k tokens per slot with multi-step
        scheduling) for the given decode-phase slots, ISSUED BEFORE THE
        ONE IN FLIGHT IS READ: a row that is in the step in flight
        takes its input token from that step's result on the device
        (`decode_step`'s `prev`), its position and PRNG step counter
        one further than the host's, and is left out if its budget ends
        with the step in flight. Then the step in flight is read and
        emitted, and the new one stays in flight for the next round
        (`_keeps_in_flight`; else it is read at once)."""
        rec = self._issue_decode(active_idx)
        if rec is None:
            return   # the round reads what is in flight (`_round_split`)
        flying, self._pending = self._pending, rec
        if flying is not None:
            self._read_decode(flying)
        if not self._keeps_in_flight:
            self._pending = None
            self._read_decode(rec)

    def _decode_kernel_plan(self, heads, tp):
        """(kernel, fan, launches a step) of the decode attention kernel
        where this engine's decode steps run one, else None: a latent pool
        runs `mla_decode` on the chip (its fan fixed, a launch an "mla"
        layer), a K/V pool the paged decode kernel (its fan
        `pool_decode_fan`'s, heads split over tp), a description without
        a pool no attention at all, and shapes the kernel's gate refuses
        (or a host without the chip) the XLA gather."""
        from ..ops.attention import _on_tpu, paged_attention_path
        from ..ops.pallas.unified_attention import pool_decode_fan

        c = self.cache
        if self._desc is not None and self._desc.count("mla"):
            from ..ops.pallas.mla_decode import FAN

            return ("mla_decode", FAN, c.num_layers) if _on_tpu() else None
        if self._desc is not None and not self._desc.count("cca"):
            return None
        if paged_attention_path(c.head_dim, c.block_size, heads,
                                mesh=self._mesh,
                                kv_heads=c.num_heads) == "xla":
            return None
        return ("paged_attn_decode",
                pool_decode_fan(c.k_blocks, c.v_blocks, self._m_width,
                                layer=0, shards=tp), c.num_layers)

    def _count_decode_kernel(self, pos, act, k):
        """Add one decode dispatch of `k` steps to stats()["decode_kernel"]:
        a launch a pool layer and step, each over every row of the table,
        its blocks and grid steps as the kernel lays them out (an idle
        row's context is 0).  Both kernels step over the launch's work
        list, a row's blocks alone (`decode_steps`: an idle row one block
        and one step).  Under the lock."""
        from ..ops.pallas.unified_attention import decode_steps

        if self._dk_plan is None:
            return
        _, fan, layers = self._dk_plan
        for j in range(k):
            ctx = np.where(act, pos + 1 + j, 0)
            n, steps = decode_steps(ctx, self.block_size, self._m_width, fan)
            self._dk_counts[0] += layers
            self._dk_counts[1] += layers * int(n.sum())
            self._dk_counts[2] += layers * int(steps.sum())

    def _issue_decode(self, active_idx):
        """Plan and dispatch one decode step; returns what
        `_read_decode` needs, or None when no row goes on or the
        dispatch failed (the recovery ladder has then read the step in
        flight)."""
        with self._phase("plan"):
            jnp = self._jnp
            k = self.steps_per_dispatch
            flying = self._pending
            tok = np.zeros((self.max_slots,), np.int32)
            pos = np.zeros((self.max_slots,), np.int32)
            act = np.zeros((self.max_slots,), bool)
            steps = np.zeros((self.max_slots,), np.int32)
            rows = {}
            for i in active_idx:
                s = self._slots[i]
                n = len(s["toks"])
                if flying is not None and flying["rows"].get(i) == s["seq"]:
                    # one token of this row is on the device, unread
                    n += 1
                    if n >= s["budget"]:
                        continue   # it ends with the step in flight
                    tok[i] = -1    # decode_step takes `prev` there
                else:
                    tok[i] = s["toks"][-1]
                pos[i] = s["pos"] + n - 1
                act[i] = True
                steps[i] = n  # PRNG step counter
                rows[i] = s["seq"]
            if not rows:
                return None
            # per-slot sampling buffers + the static dispatch mode: ONE
            # jitted dispatch serves the whole mixed batch; all-greedy
            # residents take the argmax fast path
            sp_args, sp_mode = self._sp_store.step_args(steps)
            if sp_mode[0]:
                _m_sampling_sampled.inc()
            else:
                _m_sampling_fast.inc()
            with self._lock:
                if sp_mode[0]:
                    self._sampled_dispatches += 1
                else:
                    self._fastpath_dispatches += 1
            if self._recorder.enabled:
                self._recorder.record(
                    "decode_dispatch", slots=len(rows), k=k,
                    sampled=bool(sp_mode[0]),
                    free_blocks=self.cache.available_block_count)
            parts = None
            if self._ledger is not None:
                parts = self._cost_parts(
                    [(self._slots[i]["req"], k) for i in rows])
                self._attr_begin(parts)
        self._phases.kind("decode")
        t0 = time.perf_counter()
        try:
            span = _tracing.span(
                "decode_dispatch", k=k, round=self._phases.round,
                request_ids=[self._slots[i]["req"].rid for i in rows]
                if _tracing.enabled() else (), **self._rattr())
            with span:
                with self._phase("plan"):
                    self._maybe_fault("slow_dispatch")
                    self._maybe_fault("ensure_many")
                    # grow tables for the incoming token(s) BEFORE the
                    # step writes them (k tokens starting at the feed
                    # position) — inside the try so a pool error takes
                    # the recovery path instead of killing the engine
                    # thread
                    self.cache.ensure_many(
                        [(seq, int(pos[i]) + k) for i, seq in rows.items()])
                    tables = self.cache.table_array(
                        [s["seq"] if s is not None else None
                         for s in self._slots], self._m_width)
                with self._phase("dispatch"):
                    self._maybe_fault("decode")
                    tables = jnp.asarray(tables)
                    if k == 1:
                        toks, stopped, *rest = \
                            self._decoder.step(
                                self._params, jnp.asarray(tok),
                                jnp.asarray(pos), jnp.asarray(act),
                                tables, self.cache.k_blocks,
                                self.cache.v_blocks, sp_args, sp_mode,
                                state=self.cache.state,
                                prev=None if flying is None
                                else flying["out"][0])
                    else:
                        toks, stopped, *rest = \
                            self._decoder.multistep(k, sp_mode)(
                                self._params, jnp.asarray(tok),
                                jnp.asarray(pos), jnp.asarray(act),
                                tables, self.cache.k_blocks,
                                self.cache.v_blocks, sp_args)
                    self._probe_device("decode", span)
                    # the pool, the store and the sampler's counts chain
                    # from program to program on the device
                    routed = self._chain(rest)
                    self._newest_out = toks
        except Exception as e:  # noqa: BLE001 — the recovery ladder
            # (or, with recovery off, the legacy fail-all path)
            self._dispatch_failure("decode", e, list(rows))
            return None
        with self._lock:
            self._decode_issued += 1
            if flying is not None:
                self._decode_ahead += 1
            self._count_decode_kernel(pos, act, k)
        self._ops_progress += 1
        return {"where": "decode", "t0": t0, "parts": parts, "rows": rows,
                "pos": pos, "out": (toks, stopped, routed)}

    def _read_decode(self, rec):
        """Read one decode dispatch back and emit its tokens. A row
        whose slot no longer holds the sequence it was issued for (a
        stop token, a stop string or a timeout that only the read of
        the step before revealed) is dropped, and counted as replay."""
        try:
            with self._phase("read_back"):
                toks, stopped, routed = rec["out"]
                toks = np.asarray(toks)        # [S], or [k, S]
                stops = np.asarray(stopped)
                routed = self._read_routed(routed)
                self._was_read(rec)
                if toks.ndim == 1:
                    toks, stops = toks[None], stops[None]  # [1, S]
        except Exception as e:  # noqa: BLE001 — the recovery ladder
            self._read_failure(rec, e)
            return
        with self._phase("emit"):
            rows = rec["rows"]
            live = [i for i, seq in rows.items() if self._holds(i, seq)]
            if routed is not None:
                self._note_routed(routed["counts"])
            if self._desc is not None:
                self._tell_routing([(i, rec["pos"][i], i, 1)
                                    for i in live], routed)
            self._dispatch_ok([self._slots[i]["req"].rid for i in live])
            t_now = time.perf_counter()
            self._charge_read(rec, t_now)
            self._ops_progress += 1
            decoded = toks.shape[0] * len(rows)
            late = len(rows) - len(live)
            discarded = toks.shape[0] * late
            with self._lock:
                self._steps += 1
                self._active_integral += len(rows)
                self._fill_integral += self.cache.block_fill()
                self._decoded_tokens += decoded
                self._late_rows += late
            _m_decoded.inc(decoded)
            for i in live:
                s = self._slots[i]
                t_prev = s["t_last"] if s["t_last"] is not None else t_now
                consumed = 0
                for j in range(toks.shape[0]):
                    consumed += 1
                    self._slot_token(i, int(toks[j, i]),
                                     device_stopped=bool(stops[j, i]))
                    if self._slots[i] is None:  # finished mid-scan: the
                        break  # remaining scan tokens are discarded
                discarded += toks.shape[0] - consumed  # multi-step overrun
                if self._slots[i] is not None:
                    self._slots[i]["t_last"] = t_now
                # ITL: the dispatch's host-visible gap amortized over
                # the tokens it emitted for this slot
                per = max(t_now - t_prev, 0.0) / consumed
                with self._lock:
                    self._itl.extend([per] * consumed)
                    if s["req"].meta is not None:
                        self._lane_itl.setdefault(
                            s["req"].meta.lane, []).extend([per] * consumed)
                if self._slo is not None:
                    self._slo_latency("itl", per, s["req"], n=consumed)
                for _ in range(consumed):
                    _m_itl.observe(per)
            if discarded:
                with self._lock:
                    self._replayed_tokens += discarded
                _m_replayed.inc(discarded)
            _m_goodput.set(self._tokens_out / (self._decoded_tokens or 1))

    def _speculate(self, active_idx):
        """Propose drafts for every eligible decode-phase slot; when
        any slot got a proposal, run ONE packed verification dispatch
        covering ALL decode-phase slots — draft-free slots ride along
        as k=0 rows whose single verify position IS their decode step,
        so a round never pays a verify AND a plain decode dispatch.
        Rounds where nobody proposes return () untouched and the loop
        takes the plain decode dispatch (the exact pre-speculation
        path, also what a disabled server always runs).

        Draft eligibility: the slot must be able to emit at least 2
        tokens (remaining budget >= 2 — with 1 left there is nothing a
        draft could add), and the drafter must propose at least one
        token for its context."""
        from ..spec_decode import build_verify_plan

        with self._phase("plan"):
            entries = []
            any_drafts = False
            empty = np.empty((0,), np.int32)
            for i in active_idx:
                s = self._slots[i]
                remaining = s["budget"] - len(s["toks"])
                kcap = min(self._spec_k, remaining - 1)
                drafts = empty
                if kcap >= 1:
                    ctx = np.concatenate(
                        [s["req"].ids, np.asarray(s["toks"], np.int32)])
                    drafts = np.asarray(self._drafter.propose(ctx, kcap),
                                        np.int32).reshape(-1)[:kcap]
                if drafts.size:
                    any_drafts = True
                wpos = s["pos"] + len(s["toks"]) - 1
                entries.append((i, s["toks"][-1], wpos, len(s["toks"]),
                                drafts))
            if not any_drafts:
                return ()
            plan = build_verify_plan(entries, self._spec_k,
                                     self._verify_align,
                                     min_rows=self.max_slots)
        self._verify_packed(plan)
        return set(plan.slots)

    def _verify_packed(self, plan):
        """ONE packed verification dispatch for the plan's slots, then
        accept/rollback: each row's drafts were speculatively written at
        positions wpos+1..wpos+k; the dispatch returns the target's
        deterministic token per position, the accepted prefix length,
        and per-position stop flags. Accepted tokens (plus the bonus
        token) feed the normal `_slot_token` path; rejected tail
        positions roll the paged cache back via
        `PagedKVCache.truncate_seq`."""
        with self._phase("plan"):
            jnp = self._jnp
            proposed = int(sum(d.size for d in plan.drafts))
            with self._lock:
                self._spec_proposed += proposed
                self._spec_rounds_per_slot += sum(
                    1 for d in plan.drafts if d.size)
            _m_spec_proposed.inc(proposed)
            if self._recorder.enabled:
                self._recorder.record(
                    "verify_dispatch", rows=plan.rows, proposed=proposed,
                    free_blocks=self.cache.available_block_count)
            P = plan.dlen.shape[0]
            parts = None
            if self._ledger is not None:
                parts = self._cost_parts(
                    [(self._slots[i]["req"], plan.drafts[r].size + 1)
                     for r, i in enumerate(plan.slots)])
                self._attr_begin(parts)
        self._phases.kind("verify")
        t0 = time.perf_counter()
        try:
            span = _tracing.span(
                "verify_dispatch", segments=plan.rows,
                proposed=proposed, round=self._phases.round,
                request_ids=[self._slots[i]["req"].rid
                             for i in plan.slots]
                if _tracing.enabled() else (), **self._rattr())
            with span:
                with self._phase("plan"):
                    self._maybe_fault("slow_dispatch")
                    self._maybe_fault("ensure_many")
                    # grow every row's table to its speculative write
                    # horizon in one atomic call (reservation-backed: the
                    # admission worst case includes the K-token overrun)
                    self.cache.ensure_many(
                        plan.grow_updates([self._slots[i]["seq"]
                                           for i in plan.slots]))
                    # FIXED table width (the decode-dispatch width, not the
                    # prefill path's pow2 bucketing): verify runs every
                    # round, so its jit shape must be pinned — one compiled
                    # variant per sampling mode
                    tables = self.cache.table_array(
                        [self._slots[plan.slots[r]]["seq"]
                         if r < plan.rows else None for r in range(P)],
                        self._m_width)
                    sp_args, sp_mode = self._sp_store.verify_args(
                        [plan.slots[r] if r < plan.rows else None
                         for r in range(P)], plan.steps)
                with self._phase("dispatch"):
                    self._maybe_fault("verify")
                    vtok, accepted, stopped, kc, vc, counts = \
                        self._decoder.packed_verify(
                            self._params, jnp.asarray(plan.toks),
                            jnp.asarray(plan.seg), jnp.asarray(plan.pos),
                            jnp.asarray(tables),
                            jnp.asarray(plan.sample_idx),
                            jnp.asarray(plan.dlen), self.cache.k_blocks,
                            self.cache.v_blocks, sp_args, sp_mode)
                    self._probe_device("verify", span)
                with self._phase("read_back"):
                    vtok_h = np.asarray(vtok)
                    acc_h = np.asarray(accepted)
                    stop_h = np.asarray(stopped)
        except Exception as e:  # noqa: BLE001 — the recovery ladder
            # (or, with recovery off, the legacy fail-all path)
            self._dispatch_failure("verify", e, list(plan.slots))
            return
        with self._phase("emit"):
            self._sp_store.swap_counts(counts)
            self.cache.swap_arrays(kc, vc)
            self._dispatch_ok([self._slots[i]["req"].rid
                               for i in plan.slots
                               if self._slots[i] is not None])
            _m_spec_verify.inc()
            t_now = time.perf_counter()
            self._charge_dispatch(t_now - t0, parts)
            self._ops_progress += 1
            verify_discarded = 0
            with self._lock:
                self._spec_dispatches += 1
            for r, i in enumerate(plan.slots):
                s = self._slots[i]
                a = int(acc_h[r])
                k_r = int(plan.drafts[r].size)
                # rollback FIRST (while the sequence still exists): the
                # kept prefix is the last emitted token plus the accepted
                # drafts; rejected speculative positions leave the cache
                self.cache.truncate_seq(s["seq"], plan.write_pos[r] + a + 1)
                rolled = k_r - a
                if k_r:  # draft-free ride-along rows have nothing to score
                    with self._lock:
                        self._spec_accepted += a
                        self._spec_rolled_back += rolled
                    _m_spec_accepted.inc(a)
                    _m_spec_rolled_back.inc(rolled)
                    _m_spec_accept_rate.observe(a / k_r)
                    _tracing.event("spec_round", request_id=s["req"].rid,
                                   proposed=k_r, accepted=a,
                                   rolled_back=rolled)
                t_prev = s["t_last"] if s["t_last"] is not None else t_now
                consumed = 0
                for j in range(a + 1):
                    consumed += 1
                    self._slot_token(i, int(vtok_h[r, j]),
                                     device_stopped=bool(stop_h[r, j]))
                    if self._slots[i] is None:  # stopped mid-prefix: the
                        break  # remaining accepted tokens are discarded
                # goodput: the row computed k_r+1 verify positions — a+1
                # candidate emissions (stop-truncated remainder is replay)
                # plus k_r-a rejected drafts (rolled back above)
                with self._lock:
                    self._decoded_tokens += k_r + 1
                _m_decoded.inc(k_r + 1)
                verify_discarded += (a + 1) - consumed
                if self._slots[i] is not None:
                    self._slots[i]["t_last"] = t_now
                per = max(t_now - t_prev, 0.0) / consumed
                with self._lock:
                    self._itl.extend([per] * consumed)
                    if s["req"].meta is not None:
                        self._lane_itl.setdefault(
                            s["req"].meta.lane, []).extend([per] * consumed)
                if self._slo is not None:
                    self._slo_latency("itl", per, s["req"], n=consumed)
                for _ in range(consumed):
                    _m_itl.observe(per)
            if verify_discarded:
                with self._lock:
                    self._replayed_tokens += verify_discarded
                _m_replayed.inc(verify_discarded)
            _m_goodput.set(self._tokens_out / (self._decoded_tokens or 1))


def measure_offered_load(server, prompts, offered_rps, duration_s):
    """Drive `server` at a target request rate for `duration_s`; returns
    the server stats plus achieved rate. `prompts`: pool of int lists,
    cycled."""
    futs = []
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < duration_s:
        target = t0 + i / offered_rps
        now = time.perf_counter()
        if now < target:
            time.sleep(target - now)
        futs.append(server.submit(prompts[i % len(prompts)]))
        i += 1
    t_submit_end = time.perf_counter()  # the OFFER window ends here —
    # draining the queue below must not dilute the achieved rate
    for f in futs:
        f.result(timeout=600)
    out = server.stats()
    out["offered_rps"] = offered_rps
    out["achieved_rps"] = i / (t_submit_end - t0)
    return out


def measure_poisson_load(server, prompts, offered_rps, n_requests,
                         seed=0, timeout=600, max_new_tokens=None):
    """Open-loop arrival drive: submit `n_requests` prompts (cycled from
    the pool) at FIXED-SEED Poisson arrivals — exponential inter-arrival
    gaps with mean 1/offered_rps — then wait for all of them. Unlike the
    closed-loop all-upfront drain, this exercises steady-state admission
    CHURN: requests arrive while others are mid-decode, which is where
    prefill stalls live. Returns the server's stats() for the window
    plus offered/achieved rates. max_new_tokens caps each request's
    budget (the shared-prefix TTFT axis keeps decode short)."""
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / max(offered_rps, 1e-9),
                           size=int(n_requests))
    kw = {} if max_new_tokens is None \
        else {"max_new_tokens": int(max_new_tokens)}
    futs = []
    t0 = time.perf_counter()
    arrival = 0.0
    for i in range(int(n_requests)):
        arrival += gaps[i]
        now = time.perf_counter() - t0
        if now < arrival:
            time.sleep(arrival - now)
        futs.append(server.submit(prompts[i % len(prompts)], **kw))
    t_submit_end = time.perf_counter()  # offer window ends here
    for f in futs:
        f.result(timeout=timeout)
    out = server.stats()
    out["offered_rps"] = offered_rps
    out["achieved_rps"] = int(n_requests) / (t_submit_end - t0)
    return out
