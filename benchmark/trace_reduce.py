"""From a profiler trace to numbers: the reduction every PR is measured by.

What one real trace of this system on the v5e looks like (looked at by hand,
PR 23, GPT-2-medium train step; PERF.md section 3 has the notes):

  plane "/device:TPU:0"      one per chip
    line "Steps"             one event per program run
    line "XLA Modules"       one event per program run, named "jit_step(<id>)"
    line "XLA Ops"           every op the TensorCore ran, back to back, never
                             overlapping; the event name is the op's whole
                             HLO text: "%fusion.9 = bf16[...] fusion(...)".
                             A Pallas kernel is a custom-call whose text ends
                             in custom_call_target="tpu_custom_call"; today
                             the kernels carry no name of their own and read
                             "%jvp__.24" (flash forward) or
                             "%transpose_jvp___.7" (delta, fused backward).
    line "Async XLA Ops"     copies in flight, overlapping the line above:
                             NOT counted as busy time
  plane "/host:CPU"          one line per host thread, "<name>/<tid>"; the
                             main thread's line is named after the process
                             ("python3").  jax.profiler.TraceAnnotation spans
                             land on the line of the thread that opened them.
  Host and device events share one clock (nanoseconds from trace start).

A trace is held here as plain data, so that a recorded one can be kept as
JSON beside the tests:
  {"planes": [{"name": str, "lines": [{"name": str,
                                       "events": [[name, start_ns, dur_ns]]}]}]}
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import shutil

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
SPAN_PREFIX = "bench:"          # the benchmark's own TraceAnnotation spans
SLICE_SPAN = "bench:slice"      # marks a traced slice; explains no gap
NO_SPAN = "(no span)"           # a gap no benchmark span covers
# what a kind reports of a trace with no device plane (a CPU rehearsal):
# the readers of trace metrics then find nothing to read
NOTHING_TRACED = {"trace": None, "busy_s": None, "trace_window_s": None,
                  "breakdown": None}


def start(root, cell_name):
    """Start the profiler (Python tracer off: its events are not read and
    slow the host) into <root>/.bench_cache/trace/<cell>, emptied first.
    Returns the directory for `finish`."""
    import jax

    trace_dir = os.path.join(root, ".bench_cache", "trace", cell_name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return trace_dir


def finish(trace_dir, read=True):
    """Stop the profiler; the trace as plain data (None if not `read`: a
    CPU rehearsal has no device plane).  The files are removed."""
    import jax

    jax.profiler.stop_trace()
    try:
        return load_xplane(trace_dir) if read else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def load_xplane(trace_dir):
    """The newest .xplane.pb under `trace_dir` as plain data.  Host lines
    keep only the benchmark's own spans: the rest (thousands of runtime
    events) is not read by any reduction here."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def clip(trace, t0_ns, t1_ns):
    """The events that lie wholly inside [t0, t1]."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"],
             "events": [e for e in ln["events"]
                        if e[1] >= t0_ns and e[1] + e[2] <= t1_ns]}
            for ln in p["lines"]]}
        for p in trace["planes"]]}


def device_planes(trace):
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PLANE_PREFIX)]


def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def op_events(plane):
    return _line(plane, OPS_LINE)


def module_events(plane):
    return _line(plane, MODULES_LINE)


def short_name(event_name):
    """"%fusion.9 = bf16[...] fusion(...)" -> "fusion.9"."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_family(event_name):
    """"%multiply_subtract_fusion.12 = ..." -> "multiply_subtract_fusion":
    the name with its instance number dropped, which is how a breakdown
    groups the thousands of ops of a step."""
    head = short_name(event_name)
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


def is_kernel(event_name):
    return KERNEL_MARK in event_name


def union_intervals(events):
    """Merged [start, end] intervals of `events`, sorted."""
    out = []
    for start, end in sorted((e[1], e[1] + e[2]) for e in events):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def busy_ns(events):
    return sum(e - s for s, e in union_intervals(events))


def window_of(trace):
    """(t0, t1) ns: first start to last end over the device op lines."""
    starts, ends = [], []
    for p in device_planes(trace):
        ev = op_events(p)
        if ev:
            starts.append(min(e[1] for e in ev))
            ends.append(max(e[1] + e[2] for e in ev))
    if not starts:
        raise ValueError("no device op ran in the trace")
    return min(starts), max(ends)


def busy_and_window_s(trace, window=None):
    """(busy_s, window_s): seconds in which an op ran on the device,
    averaged over the device planes that ran any, and the window's
    length.  `window` (t0_ns, t1_ns) defaults to window_of(trace)."""
    t0, t1 = window or window_of(trace)
    busy = [busy_ns(op_events(p)) for p in device_planes(trace)
            if op_events(p)]
    return sum(busy) / len(busy) / 1e9, (t1 - t0) / 1e9


def idle_share(trace, window=None):
    busy, win = busy_and_window_s(trace, window)
    return 1.0 - busy / win


def time_by(trace, key=op_family, only=None):
    """{key(name): seconds} of device op time summed over device planes,
    for the events `only(name)` admits."""
    out = {}
    for p in device_planes(trace):
        for name, _start, dur in op_events(p):
            if only is None or only(name):
                k = key(name)
                out[k] = out.get(k, 0.0) + dur / 1e9
    return out


def kernel_time_s(trace):
    return sum(time_by(trace, only=is_kernel).values())


def kernel_share(trace):
    """Device time of Pallas kernels over device busy time."""
    busy = sum(busy_ns(op_events(p)) for p in device_planes(trace)) / 1e9
    return kernel_time_s(trace) / busy


def top_ops(trace, k=10):
    """[[family, seconds], ...] — the k op families with most device time,
    kernels shown as "pallas:<family>"."""
    by = time_by(trace, key=lambda n: ("pallas:" if is_kernel(n) else "")
                 + op_family(n))
    return [[n, s] for n, s in sorted(by.items(), key=lambda x: -x[1])[:k]]


def host_spans(trace):
    """The benchmark's own spans on every host thread: [name, start, dur]."""
    out = []
    for p in trace["planes"]:
        if p["name"] == HOST_PLANE:
            for ln in p["lines"]:
                out.extend(e for e in ln["events"]
                           if e[0].startswith(SPAN_PREFIX))
    return out


def idle_gaps(trace, window=None):
    """[[start_ns, end_ns], ...]: stretches of the window in which no op
    ran on the first device plane that ran any."""
    t0, t1 = window or window_of(trace)
    plane = next(p for p in device_planes(trace) if op_events(p))
    gaps, cur = [], t0
    for s, e in union_intervals(op_events(plane)):
        if s > cur:
            gaps.append([cur, min(s, t1)])
        cur = max(cur, e)
    if cur < t1:
        gaps.append([cur, t1])
    return gaps


def attribute_gaps(trace, window=None, k=10, no_span=NO_SPAN):
    """[[span name, idle seconds], ...], longest first: every idle
    nanosecond goes to the benchmark span that covers it (the innermost,
    i.e. the latest started, where spans nest), or to `no_span`."""
    spans = sorted((e for e in host_spans(trace) if e[0] != SLICE_SPAN),
                   key=lambda e: e[1])
    out = {}
    for g0, g1 in idle_gaps(trace, window):
        cuts = sorted({g0, g1} | {t for _n, s, d in spans
                                  for t in (s, s + d) if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            name = no_span
            for n, s, d in spans:  # sorted by start: the last hit is inner
                if s <= mid < s + d:
                    name = n[len(SPAN_PREFIX):]
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return [[n, s] for n, s in sorted(out.items(), key=lambda x: -x[1])[:k]]


def breakdown(trace, window=None, no_span=NO_SPAN):
    return {"device_ops": top_ops(trace),
            "idle_gaps": attribute_gaps(trace, window, no_span=no_span)}
