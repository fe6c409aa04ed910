"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The JAX profiler writes one plane per device (``/device:TPU:<n>``) with a
line of XLA operations and a line of XLA modules (one event per executable
run), and host planes whose ``python`` line holds the benchmark's own
``TraceAnnotation`` spans (named ``bench.*``).  Device and host events share
one clock in nanoseconds.  :func:`load` keeps only those three kinds of
event, so the reduction below can be checked on a small recorded fixture.
"""

from __future__ import annotations

import dataclasses

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, duration_ns): ``ops`` and ``modules`` per
    device, ``spans`` of the host.  A TPU op is named by its HLO text
    (``%fusion.3 = bf16[16,8192]{...} fusion(...)``), which is what
    :func:`is_kernel` and :func:`result_shape` read."""

    ops: list            # [device][(name, start, dur)]
    modules: list        # [device][(name, start, dur)]
    spans: list          # [(name, start, dur)]

    @classmethod
    def from_json(cls, d):
        tup = lambda evs: [tuple(e) for e in evs]
        return cls([tup(x) for x in d["ops"]], [tup(x) for x in d["modules"]],
                   tup(d["spans"]))


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, modules, spans = [], [], []
    names = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            o, m = [], []
            for line in plane.lines:
                dst = {OPS_LINE: o, MODULES_LINE: m}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    name = names.setdefault(ev.name, ev.name)
                    dst.append((name, int(ev.start_ns), int(ev.duration_ns)))
            if o or m:
                ops.append(o)
                modules.append(m)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)))
    return Trace(ops, modules, spans)


def _merge(intervals):
    """Union of [start, end) intervals, sorted and merged."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_s(trace: Trace, window) -> float:
    """Seconds in which some operation ran, per device, averaged over the
    devices; ``window`` is (start_ns, end_ns)."""
    if not trace.ops:
        return 0.0
    lo, hi = window
    tot = 0
    for dev in trace.ops:
        iv = _merge(_clip([(s, s + d) for _, s, d in dev], lo, hi))
        tot += sum(e - s for s, e in iv)
    return tot / len(trace.ops) / 1e9


def module_time(trace: Trace, prefix: str):
    """(seconds, runs) of the executables whose name starts with
    ``prefix`` (e.g. ``jit_tick``), summed over devices and averaged."""
    if not trace.modules:
        return 0.0, 0
    secs, runs = 0, 0
    for dev in trace.modules:
        for name, _, d in dev:
            if name.startswith(prefix):
                secs += d
                runs += 1
    n = len(trace.modules)
    return secs / n / 1e9, runs // n


def is_kernel(name: str) -> bool:
    """A Pallas (Mosaic) kernel: the TPU names the op by its HLO text,
    which records the custom call's target."""
    return 'custom_call_target="tpu_custom_call"' in name


def result_shape(name: str) -> str:
    """The result type in an op's HLO text: ``f32[2048,11]`` of
    ``%x.1 = f32[2048,11]{1,0:T(8,128)} custom-call(...)``."""
    rhs = name.split(" = ", 1)[-1]
    return rhs.split("{", 1)[0].split(" ", 1)[0]


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[16,8192] fusion`` from an op's HLO text."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:80]
    op = rhs.split("}", 1)[-1].strip().split("(", 1)[0].strip()
    return f"{lhs} = {result_shape(name)} {op}"[:120]


def op_time(trace: Trace, match):
    """(seconds, calls) of the device operations whose HLO text satisfies
    ``match`` (a predicate, or a string it must hold), averaged over
    devices."""
    if isinstance(match, str):
        needle = match
        match = lambda name: needle in name  # noqa: E731
    if not trace.ops:
        return 0.0, 0
    secs, calls = 0, 0
    for dev in trace.ops:
        for name, _, d in dev:
            if match(name):
                secs += d
                calls += 1
    n = len(trace.ops)
    return secs / n / 1e9, calls // n


def leaf_ops(ops):
    """The ops of one device that hold no other op: the XLA Ops line nests
    a loop's body ops inside the loop's own event."""
    ev = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is None or nxt[1] >= e[1] + e[2]:
            out.append(e)
    return out


def top_ops(trace: Trace, n=10):
    """The ``n`` innermost operations of device 0 that took most time,
    summed by HLO instruction: [[name, s]]."""
    agg = {}
    for name, _, d in leaf_ops(trace.ops[0]) if trace.ops else []:
        k = short_name(name)
        agg[k] = agg.get(k, 0) + d
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(trace: Trace, window, n=10):
    """The ``n`` longest gaps in which device 0 ran nothing, each named by
    the host span that overlaps it most: [[span, s]]."""
    if not trace.ops:
        return []
    lo, hi = window
    busy = _merge(_clip([(s, s + d) for _, s, d in trace.ops[0]], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        best, name = 0, "no host span"
        for sp, ss, sd in trace.spans:
            ov = min(e, ss + sd) - max(s, ss)
            if ov > best:
                best, name = ov, sp
        out.append([name, (e - s) / 1e9])
    return out
