"""Reduction of the program's own marks in a profiler trace: the serving
engine's host spans and the named scopes of the device's operations.

:mod:`bench.trace` keeps the device's ops and modules and the harness's own
``bench.*`` spans.  The program adds two kinds of mark, on the same clock:

- host spans named ``serve.*`` (``repro.serve.engine``), with integer args
  such as an admission's ``rows``, ``bucket`` and ``npp``;
- ``jax.named_scope`` regions (``p2_student``, ``attention``, ...), which
  reach the compiled HLO as each instruction's ``metadata={op_name=...}``.
  A TPU trace names an op by its HLO text without that metadata, so
  :func:`hlo_scopes` maps instruction names to scope paths from the
  executable's compiled text (``jitted.lower(...).compile().as_text()``).

:func:`idle_by_span` puts each idle stretch of the device down to the
innermost host span that covers it; :func:`time_by_scope` sums leaf-op time
of one executable by scope.
"""

from __future__ import annotations

import re

from bench import trace as T

SERVE_PREFIX = "serve."
NO_SPAN = "no span"
UNSCOPED = ""


def load_spans(path):
    """The engine's ``serve.*`` host spans in the trace file ``path``, as
    (name, start_ns, duration_ns, args) sorted by start; args maps each
    span argument to an int (the program passes only ints)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SERVE_PREFIX):
                    args = {k: int(v) for k, v in ev.stats}
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.duration_ns), args))
    out.sort(key=lambda s: s[1])
    return out


# -- named scopes ------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)")
_CALLEES = re.compile(r"\b(?:branch_computations|called_computations)="
                      r"\{([^}]*)\}")


def hlo_scopes(text):
    """{instruction name: op_name path} of a compiled HLO module's text.

    An instruction without metadata (a copy or a buffer the compiler put
    in) takes the path of the instruction that calls its computation, so a
    copy inside the student's backward loop counts as the student's."""
    own, comp_of, caller = {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        comp_of[name] = comp
        md = _OP_NAME.search(line)
        if md:
            own[name] = md.group(1)
        callees = _CALLEE.findall(line)
        for group in _CALLEES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        for c in callees:
            caller.setdefault(c, name)

    def path(name, seen=()):
        if name in own:
            return own[name]
        up = caller.get(comp_of.get(name))
        if up is None or up in seen:
            return UNSCOPED
        return path(up, seen + (name,))

    return {name: path(name) for name in comp_of}


def instruction(op_text):
    """``fusion.3`` of an op named ``%fusion.3 = bf16[16,8192]{...} ...``."""
    lhs = op_text.split(" = ", 1)[0]
    return lhs[1:] if lhs.startswith("%") else lhs


def in_scope(path, scope):
    """True if ``scope`` is one of the path's named scopes, as such or
    wrapped by a transformation (``transpose(jvp(p2_student))``)."""
    return scope in re.split(r"[/()]", path)


def module_ops(ops, modules, prefix):
    """The ops of one device that ran inside an executable whose name
    starts with ``prefix``: [(name, start, dur)]."""
    runs = sorted((s, s + d) for n, s, d in modules if n.startswith(prefix))
    out, i = [], 0
    for op in sorted(ops, key=lambda e: e[1]):
        while i < len(runs) and runs[i][1] <= op[1]:
            i += 1
        if i < len(runs) and runs[i][0] <= op[1]:
            out.append(op)
    return out


def time_by_scope(leaves, scopes, names):
    """Seconds of ``leaves`` (leaf ops of one executable, see
    :func:`bench.trace.leaf_ops`) under each of ``names``, the first that
    matches in order, with the rest under :data:`UNSCOPED`.  ``scopes`` is
    :func:`hlo_scopes` of the executable."""
    out = dict.fromkeys(list(names) + [UNSCOPED], 0)
    for op, _, d in leaves:
        path = scopes.get(instruction(op), UNSCOPED)
        key = next((n for n in names if in_scope(path, n)), UNSCOPED)
        out[key] += d
    return {k: v / 1e9 for k, v in out.items()}


# -- idle time by host span ---------------------------------------------------

def _segments(spans, lo, hi):
    """The window cut into disjoint [start, end) segments, each labelled
    by the innermost (latest started) span covering it, or NO_SPAN."""
    bounds = sorted({lo, hi} | {t for _, s, d, *_ in spans
                                for t in (s, s + d) if lo < t < hi})
    ev = sorted(spans, key=lambda s: s[1])
    segs, active, j = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(ev) and ev[j][1] <= a:
            active.append(ev[j])
            j += 1
        active = [s for s in active if s[1] + s[2] > a]
        label = max(active, key=lambda s: s[1])[0] if active else NO_SPAN
        if segs and segs[-1][2] == label and segs[-1][1] == a:
            segs[-1][1] = b
        else:
            segs.append([a, b, label])
    return segs


def idle_gaps(ops, window):
    """The stretches of the window in which the device ran no op."""
    lo, hi = window
    busy = T._merge(T._clip([(s, s + d) for _, s, d in ops], lo, hi))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def idle_by_span(ops, spans, window):
    """Seconds in which the device ran nothing, by the innermost host span
    covering each moment (:data:`NO_SPAN` where none does).  ``ops`` are
    one device's (name, start, dur); ``spans`` (name, start, dur, ...) of
    the host thread that drives the device."""
    segs = _segments(spans, *window)
    out, i = {}, 0
    for gs, ge in idle_gaps(ops, window):
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        k = i
        while k < len(segs) and segs[k][0] < ge:
            s, e, label = segs[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[label] = out.get(label, 0) + ov
            k += 1
    return {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


# -- the numbers the program's marks give ------------------------------------

def serve_idle_s(idle):
    """Idle seconds spent inside the engine's own spans, from
    :func:`idle_by_span`."""
    return sum(v for k, v in idle.items() if k.startswith(SERVE_PREFIX))


def slot_occupancy(before, after, slots):
    """Active rows over slot-ticks decoded between two readings of
    ``ServeEngine.counters()``, in %, or None if nothing decoded."""
    ticks = after["decode_ticks"] - before["decode_ticks"]
    if not ticks:
        return None
    return 100.0 * (after["decode_rows"] - before["decode_rows"]) / (
        ticks * slots)


def queue_waits(requests, due, t_end):
    """Seconds from each request's due time to the dispatch of its
    admission (``Request.t_admit``), or to ``t_end`` for one not admitted
    by then; ``due`` maps rid to due time on the host clock."""
    return [(r.t_admit if 0 < r.t_admit <= t_end else t_end) - due[r.rid]
            for r in requests]
