"""XLA compile tracking at the jit boundaries (ISSUE 10 tentpole,
part b).

Two measurement rounds were silently poisoned by untracked in-window
XLA compiles (r12/r13: one fresh packed-prefill bucket costs ~0.7s and
lands on whatever requests are in flight). This module makes every
compile a first-class, attributable event:

  * `wrap(program, jit_fn)` returns a call-through wrapper that detects
    a compile EXACTLY — jax's jitted callables expose `_cache_size()`,
    so "the executable cache grew across this call" is the compile,
    not a heuristic over argument shapes (it also catches recompiles
    after a cache drop, e.g. the tier-1 map-count guard);
  * each compile records `serving_xla_compiles_total{program,in_flight,
    shard}` + a `serving_xla_compile_seconds{program,shard}` histogram
    observation, emits a `compile` trace event (ts/dur — the PR 2
    request assembler uses it to attribute TTFT/ITL outliers to
    compiles instead of queue/prefill time), notifies registered
    listeners (the per-server flight recorders), and lands in a
    bounded in-process event log;
  * `in_flight` comes from registered probes (each serving engine
    registers "do I have busy slots or queued work" via a weakref, so
    dead servers fall away) — `warm_buckets()` compiles before start()
    therefore label `in_flight="false"`, and a compile-clean
    measurement window is `count_since(mark, in_flight=True) == 0`.

The tracker is ALWAYS on: compiles are rare, the per-dispatch cost of
detection is one C-level `_cache_size()` call, and a tracker that only
counts while telemetry is enabled would misreport pre-enable buckets
as fresh compiles. Metric emission still goes through the registry's
enabled gate like everything else; the event log and `count_since()`
window API work regardless, which is what lets a benchmark cell
(`benchmark/metrics/compiles_in_window.serve.py`) prove a window
compile-clean without enabling the full telemetry stack.
"""
from __future__ import annotations

import collections
import threading
import time
import weakref

from . import metrics as _metrics
from . import tracing as _tracing

# compile durations are big (0.1s..minutes) — the default latency
# buckets top out at 10s and would crush everything into +Inf
COMPILE_BUCKETS = (.05, .1, .25, .5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                   120.0)

_m_compiles = _metrics.counter(
    "serving_xla_compiles_total",
    "XLA compiles observed at the decode jit boundaries, by program "
    "(prefill | decode_step | packed_prefill | packed_verify | "
    "multistep), whether requests were in flight, and mesh shard "
    "label ('none' unsharded)",
    labelnames=("program", "in_flight", "shard"))
_m_compile_s = _metrics.histogram(
    "serving_xla_compile_seconds",
    "wall duration of the dispatch that compiled (trace + compile + "
    "first run — the latency that lands on in-flight requests)",
    labelnames=("program", "shard"), buckets=COMPILE_BUCKETS)

EVENT_LOG_CAPACITY = 4096


class CompileTracker:
    """Process-wide compile event log + in-flight probe registry.
    Instantiable for tests; `TRACKER` is the default instance the
    decode wrappers use."""

    def __init__(self, capacity=EVENT_LOG_CAPACITY):
        self._lock = threading.Lock()
        self._events = collections.deque(maxlen=capacity)
        self._total = 0
        self._total_in_flight = 0
        self._probes = []     # weakref.WeakMethod / weakref.ref
        self._listeners = []  # same, called with each event dict

    # -- probes / listeners ----------------------------------------------
    def _weak(self, fn):
        try:
            return weakref.WeakMethod(fn)
        except TypeError:
            return weakref.ref(fn)

    def register_in_flight_probe(self, fn):
        """Register a zero-arg callable answering "does your engine
        have live work right now". Held by weakref (bound methods via
        WeakMethod) so a garbage-collected server needs no unregister."""
        with self._lock:
            self._probes.append(self._weak(fn))

    def add_listener(self, fn):
        """Register a callable(event_dict) notified on every compile —
        the per-server flight recorders. Weakly held, like probes."""
        with self._lock:
            self._listeners.append(self._weak(fn))

    def _live(self, refs):
        out, dead = [], False
        for r in refs:
            fn = r()
            if fn is None:
                dead = True
            else:
                out.append((r, fn))
        if dead:
            refs[:] = [r for r, _ in out]
        return [fn for _, fn in out]

    def in_flight(self):
        with self._lock:
            probes = self._live(self._probes)
        for p in probes:
            try:
                if p():
                    return True
            except Exception:  # noqa: BLE001 — a dying server's probe
                continue  # must not break compile accounting
        return False

    # -- recording -------------------------------------------------------
    def record(self, program, dur_s, shard="none", in_flight=None,
               lower=None):
        """`lower`: zero-arg callable that re-lowers the program that
        just compiled, from its argument SHAPES, to a
        `jax.stages.Lowered` — how a chip run proves which kernels are IN
        a dispatched program (count `tpu_custom_call` in
        `ev["lower"]().compile().as_text()`) instead of inferring it from
        a shape gate."""
        if in_flight is None:
            in_flight = self.in_flight()
        ev = {"program": program, "dur_s": float(dur_s),
              "in_flight": bool(in_flight), "shard": shard,
              "ts": time.perf_counter(), "lower": lower}
        with self._lock:
            self._total += 1
            if ev["in_flight"]:
                self._total_in_flight += 1
            self._events.append(ev)
            listeners = self._live(self._listeners)
        flag = "true" if ev["in_flight"] else "false"
        _m_compiles.labels(program=program, in_flight=flag,
                           shard=shard).inc()
        _m_compile_s.labels(program=program, shard=shard).observe(dur_s)
        # the trace event carries the dispatch START ts so the request
        # assembler can overlap it with request windows
        _tracing.event("compile", ts=ev["ts"] - ev["dur_s"],
                       dur=ev["dur_s"], program=program,
                       in_flight=ev["in_flight"], shard=shard)
        for fn in listeners:
            try:
                fn(ev)
            except Exception:  # noqa: BLE001
                pass
        return ev

    # -- window API ------------------------------------------------------
    def mark(self):
        """Opaque window mark: pass back to count_since/events_since."""
        with self._lock:
            return self._total

    def count_since(self, mark, in_flight=None):
        """Compiles since `mark`, optionally only those with the given
        in-flight flag — a cell's compile-clean-window assertion."""
        evs = self.events_since(mark)
        if in_flight is None:
            return len(evs)
        return sum(1 for e in evs if e["in_flight"] == bool(in_flight))

    def events_since(self, mark):
        with self._lock:
            n = self._total - int(mark)
            if n <= 0:
                return []
            return list(self._events)[-min(n, len(self._events)):]

    def stats(self):
        with self._lock:
            return {"total": self._total,
                    "total_in_flight": self._total_in_flight}

    # -- the jit-boundary wrapper ----------------------------------------
    def wrap(self, program, fn, shard="none"):
        """Wrap a jitted callable: every call whose executable cache
        grew is recorded as a compile of `program`. Falls through
        untouched (no detection) when `fn` has no `_cache_size` —
        non-jit callables in tests."""
        cache_size = getattr(fn, "_cache_size", None)
        if cache_size is None:
            return fn
        tracker = self

        def wrapped(*args, **kw):
            n0 = cache_size()
            t0 = time.perf_counter()
            out = fn(*args, **kw)
            if cache_size() > n0:
                tracker.record(program, time.perf_counter() - t0, shard,
                               lower=_lowerer(fn, args, kw))
            return out

        wrapped.__name__ = getattr(fn, "__name__", program)
        wrapped.__wrapped__ = fn
        return wrapped


def _lowerer(fn, args, kw):
    """() -> Lowered for `fn` at the shapes (and shardings) of this
    call.  Holds ShapeDtypeStructs, never the buffers: the call may
    have donated them."""
    import functools

    import jax

    def spec(a):
        if isinstance(a, jax.Array):
            # an uncommitted array's placement is the default one; naming
            # it would change the module and miss the cache entry the
            # dispatch just wrote
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if a.committed else None)
        return a

    args, kw = jax.tree.map(spec, (args, kw))
    return functools.partial(fn.lower, *args, **kw)


# ---- process-wide default tracker ---------------------------------------
TRACKER = CompileTracker()


def wrap(program, fn, shard="none"):
    return TRACKER.wrap(program, fn, shard)


def register_in_flight_probe(fn):
    TRACKER.register_in_flight_probe(fn)


def add_listener(fn):
    TRACKER.add_listener(fn)


def mark():
    return TRACKER.mark()


def count_since(m, in_flight=None):
    return TRACKER.count_since(m, in_flight)


def events_since(m):
    return TRACKER.events_since(m)

