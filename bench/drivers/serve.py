"""Driver for serving mixes: ``repro.serve.ServeEngine`` with the paged
pool, fed by the harness on the traffic generator's schedule, checked
against the plain reference on a seeded sample of the requests it served.

Mix keys: engine {slots, max_len, page_size}, the traffic keys of
``bench/traffic.py``, check {max_logit_gap, min_tokens, max_requests,
drain_s}.

The harness drives the engine through ``begin``/``tick`` and reads only
``Request`` fields.  Open loop: between two ticks it hands the engine every
request whose due time has passed (``begin`` with the ones still waiting
plus the new ones, all eligible at once).  Times are the host's: a
request's first token is the engine's ``t_first`` stamp, each later token
the moment the tick that emitted it returned.

Set-up admits each system prompt once (it fills the prefix cache the
traffic hits), then one request at every (prefix depth, length bucket)
the run's requests can be admitted at, so nothing compiles in the window.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import counts, harness, program, reference, traffic
from bench.weights import Dims, make_weights

TAG_WEIGHTS = 1
WARM_OUT = 2          # a warm-up request: its admission and one tick
NO_READING = 1e30     # the gap reported when nothing finished to compare


def bucket(n, max_len, minimum=8):
    """The engine's admission length bucket for a suffix of ``n`` tokens."""
    b = minimum
    while b < n:
        b *= 2
    return min(b, max_len)


def shapes(plan, mix):
    """(prefix pages, bucket) of every admission the planned requests can
    cause, with the system prompts already cached."""
    eng = mix["engine"]
    ps, ml = eng["page_size"], eng["max_len"]
    out = set()
    for r in plan:
        npp = 0
        if r.system >= 0:
            n = len(r.prompt)
            npp = min(mix["shared_prefix"]["tokens"] // ps,
                      min(n - 1, n // ps * ps) // ps)
        out.add((npp, bucket(len(r.prompt) - npp * ps, ml)))
    return sorted(out)


def make_request(rid, prompt, max_new):
    from repro.serve.streams import Request
    return Request(rid=rid, arrival=0, prompt=np.asarray(prompt, np.int32),
                   max_new=int(max_new))


def serve_until_idle(engine, reqs):
    engine.begin(reqs)
    while engine.pending():
        engine.tick()


def warm(engine, plan, mix, seed, vocab):
    """Fill the prefix cache with every system prompt, then admit one
    request at each admission shape of the run, one per tick."""
    rng = np.random.default_rng([seed, 99])
    systems = traffic.system_prompts(mix, seed, vocab)
    rid = -1
    if systems:
        first = []
        for s in systems:
            first.append(make_request(rid, np.concatenate(
                [s, rng.integers(0, vocab, 1)]), WARM_OUT))
            rid -= 1
        serve_until_idle(engine, first)
    ps = mix["engine"]["page_size"]
    for npp, b in shapes(plan, mix):
        head = systems[0][:npp * ps] if npp else np.zeros(0, np.int32)
        own = rng.integers(0, vocab, b)
        serve_until_idle(engine, [make_request(
            rid, np.concatenate([head, own]), WARM_OUT)])
        rid -= 1


class Ledger:
    """Host-side record of the window: token times per request, the keys
    each decoded row attended, the requests that held a slot in each tick,
    the admissions' prompt and prefix tokens."""

    def __init__(self, page_size):
        self.ps = page_size
        self.times = {}           # rid -> [t of each token]
        self.tick_keys = []       # per tick: keys of each decoded row
        self.holders = []         # per tick: rids of the requests in slots
        self.prompt_tokens = 0
        self.prefix_tokens = 0
        self.late = []            # how late each request was handed over

    def after_tick(self, inflight, t):
        # Before this tick's finished requests leave ``inflight``: each
        # request with a token holds its own slot through the tick.
        self.holders.append([r.rid for r in inflight if r.out])
        keys = []
        for r in inflight:
            seen = self.times.setdefault(r.rid, [])
            new = len(r.out) - len(seen)
            if new <= 0:
                continue
            if not seen:                       # admitted in this tick
                seen.append(r.t_first)
                self.prompt_tokens += len(r.prompt)
                self.prefix_tokens += r.prefix_pages * self.ps
                new -= 1
            seen.extend([t] * new)
            if new:
                keys.append(len(r.prompt) + len(r.out) - 1)
        self.tick_keys.append(keys)

    def busiest(self, rng):
        """The rids in slots at one of the ticks that held the most, drawn
        by ``rng``: one request from every slot that was in use."""
        if not self.holders:
            return []
        most = max(len(h) for h in self.holders)
        ticks = [h for h in self.holders if len(h) == most]
        return ticks[int(rng.integers(len(ticks)))]


def serve_window(engine, plan, seconds, tracer, counter):
    """Run the open loop for ``seconds``; returns (ledger, window start,
    window end, handed requests, finished requests)."""
    reqs = [make_request(p.rid, p.prompt, p.max_new) for p in plan]
    ledger = Ledger(engine.page_size)
    handed, finished, waiting, inflight = 0, [], [], []
    with harness.window(counter), tracer.around():
        t0 = time.perf_counter()
        due = [t0 + p.due for p in plan]
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            new = []
            while handed < len(reqs) and due[handed] <= now:
                new.append(reqs[handed])
                handed += 1
            if new:
                for r in new:
                    ledger.late.append(now - due[r.rid])
                waiting = [r for r in waiting if r.admitted_at < 0] + new
                engine.begin(waiting)
                inflight.extend(new)
            if not engine.pending():
                nxt = due[handed] if handed < len(reqs) else t0 + seconds
                with tracer.span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, t0 + seconds) - now))
                continue
            with tracer.span("bench.tick"):
                done = engine.tick()
            t = time.perf_counter()
            ledger.after_tick(inflight, t)
            if done:
                ids = {r.rid for r in done}
                finished.extend(done)
                inflight = [r for r in inflight if r.rid not in ids]
        t_end = time.perf_counter()
    return ledger, t0, t_end, reqs[:handed], finished, inflight


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def end_to_end(ledger, plan, reqs, t0, t_end, setup_s):
    due = {p.rid: t0 + p.due for p in plan}
    ttft = []
    for r in reqs:
        first = r.t_first if 0 < r.t_first <= t_end else t_end
        ttft.append(first - due[r.rid])
    gaps = [b - a for ts in ledger.times.values()
            for a, b in zip(ts, ts[1:])]
    med = percentile(gaps, 50) if gaps else 0.0
    tokens = sum(len(ts) for ts in ledger.times.values())
    window_s = t_end - t0
    out = {"setup_s": setup_s, "decode_tok_s": tokens / window_s}
    if ttft:
        out["ttft_p90_ms"] = 1e3 * percentile(ttft, 90)
    if gaps:
        out["itl_p99_ms"] = 1e3 * percentile(gaps, 99)
    return out, {"requests_due": len(reqs), "tokens": tokens,
                 "handed_late_max_ms": 1e3 * max(ledger.late, default=0.0),
                 "token_gaps": len(gaps), "window_s": window_s,
                 "ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
                 "itl_p50_ms": 1e3 * med,
                 "itl_p90_ms": 1e3 * percentile(gaps, 90) if gaps else None,
                 "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
                 # gaps over 1.5 ticks: those that span an admission
                 "stalled_gap_share": (sum(g > 1.5 * med for g in gaps)
                                       / len(gaps)) if gaps else None}


def drain(engine, inflight, finished, limit_s):
    """After the window: let the requests in flight finish (no new ones),
    for at most ``limit_s``, so the check has whole answers to compare."""
    engine.begin([])
    t0 = time.perf_counter()
    while engine.pending() and time.perf_counter() - t0 < limit_s:
        finished.extend(engine.tick())


def sample(finished, check, seed, ledger):
    """A seeded sample of the finished requests: every one that held a slot
    in one of the window's busiest ticks (so every slot in use is checked),
    the longest answer, then more drawn from the seed until the sample
    holds ``min_tokens`` served tokens, or ``max_requests`` requests."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 7])
    by_rid = {r.rid: r for r in finished}
    longest = max(finished, key=lambda r: (len(r.out), -r.rid))
    out = [by_rid[i] for i in ledger.busiest(rng) if i in by_rid]
    if longest not in out:
        out.append(longest)
    rest = [r for r in finished if r not in out]
    rng.shuffle(rest)
    n = sum(len(r.out) for r in out)
    for r in rest:
        if n >= check["min_tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(r)
        n += len(r.out)
    return out


def checks(gaps, mix):
    """The numbers compared for ``correct``, as (name, value, limit).  No
    finished request to compare is a failed check, not a pass."""
    return [("max_logit_gap", max(gaps) if gaps else NO_READING,
             mix["check"]["max_logit_gap"])]


def _gaps(dm, fp8, w, tokens, targets, lo, hi):
    import jax.numpy as jnp
    ref = reference.logits(dm, w, tokens)
    pos = jnp.arange(tokens.shape[0])
    if fp8:
        targets = jnp.argmax(reference.logits(dm, w, tokens, fp8=True), -1)
    got = jnp.take_along_axis(ref, targets[:, None], -1)[:, 0]
    gap = jnp.max(ref, -1) - got
    return jnp.max(jnp.where((pos >= lo) & (pos < hi), gap, 0.0))


def logit_gaps(dm, w, reqs, length, fp8=False):
    """Per request, the widest gap by which a served token's reference
    logit lies below the reference's best at its position; with
    ``fp8=True``, the gap of the token the float8 reference puts first
    instead (the control).  Sequences are padded to ``length`` (one
    compile; the padding lies after every position read)."""
    import jax
    gaps = jax.jit(_gaps, static_argnums=(0, 1))
    out = []
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        tok = np.zeros(length, np.int32)
        tok[:len(seq)] = seq
        tgt = np.zeros(length, np.int32)
        tgt[len(r.prompt) - 1:len(seq)] = r.out
        out.append(float(gaps(dm, fp8, w, tok, tgt, len(r.prompt) - 1,
                              len(seq))))
    return out


def build_engine(cell, dm):
    from repro.serve.engine import ServeEngine
    eng = cell.mix["engine"]
    cfg = program.lm_config(dm, cell.config["name"])
    params = program.to_program(make_weights(dm, cell.seed, TAG_WEIGHTS))
    program.check_layout(cfg, params)
    return ServeEngine(cfg, params, slots=eng["slots"],
                       max_len=eng["max_len"], paged=True,
                       page_size=eng["page_size"], sample="greedy")


def run(cell: harness.Cell, t_start):
    mix, dm = cell.mix, Dims.from_config(cell.config)
    counter = harness.CompileCounter()
    tracer = harness.Tracer(cell.trace)
    plan = traffic.plan(mix, cell.seed, cell.seconds, dm.vocab)
    engine = build_engine(cell, dm)
    warm(engine, plan, mix, cell.seed, dm.vocab)
    stats0 = engine.prefix_stats()
    setup_s = time.perf_counter() - t_start

    ledger, t0, t_end, handed, finished, inflight = serve_window(
        engine, plan, cell.seconds, tracer, counter)
    e2e, info = end_to_end(ledger, plan, handed, t0, t_end, setup_s)
    stats = engine.prefix_stats()
    print(f"[serve] {info}; prefix cache {stats} (after set-up {stats0}); "
          f"prefix-hit share of the window's prompt tokens "
          f"{ledger.prefix_tokens}/{ledger.prompt_tokens}", flush=True)
    drain(engine, inflight, finished, mix["check"]["drain_s"])
    mem = harness.memory_peak_bytes(cell.workload["chips"])

    facts = {"memory_peak_bytes": mem,
             "prompt_tokens": ledger.prompt_tokens,
             "prefix_tokens": ledger.prefix_tokens}
    breakdown = None
    if cell.trace:
        tr = tracer.load()
        facts["trace_device"], breakdown = harness.trace_facts(tracer)
        facts.update(trace_counts(tr, dm, ledger, mix, cell))

    checked = sample(finished, mix["check"], cell.seed, ledger)
    del engine
    gc.collect()
    w = make_weights(dm, cell.seed, TAG_WEIGHTS)
    gaps = logit_gaps(dm, w, checked, mix["engine"]["max_len"])
    served = sum(len(r.out) for r in checked)
    most = max(map(len, ledger.holders), default=0)
    print(f"[check] {len(checked)} requests ({most} slots held at the "
          f"busiest ticks), {served} served tokens, widest gap per request "
          f"{gaps}", flush=True)
    return harness.Outcome(e2e=e2e, facts=facts, attempted=len(handed),
                           failed=0, checks=checks(gaps, mix),
                           breakdown=breakdown)


def paged_decode_op(name):
    """The paged_decode kernel in the tick: the one Pallas call whose
    first operand is the scalar-prefetched layer index (s32[1])."""
    from bench import trace as T
    return T.is_kernel(name) and "custom-call(s32[1]" in name


def trace_counts(tr, dm, ledger, mix, cell):
    """What the per-layer readers of a serving cell read from the trace:
    the tick's and the admissions' device time, the tick's operations, and
    the paged-decode kernel's time and least time."""
    from bench import trace as T
    peak, bw = harness.chip_peaks(cell)
    ps = mix["engine"]["page_size"]
    tick_s, ticks = T.module_time(tr, "jit_tick")
    admit_s, admits = T.module_time(tr, "jit_admit")
    kern_s, calls = T.op_time(tr, paged_decode_op)
    least = 0.0
    for keys in ledger.tick_keys:
        if keys:
            f, b = counts.paged_decode_call(dm, keys, ps)
            least += dm.layers * counts.roofline_s(f, b, peak, bw)
    return {"peak_flops": peak,
            "tick_device_s": tick_s, "ticks": ticks,
            "admit_device_s": admit_s, "admits": admits,
            "tick_flops": sum(counts.decode_tick_flops(dm, k)
                              for k in ledger.tick_keys),
            "paged_decode_s": kern_s, "paged_decode_calls": calls,
            "paged_decode_least_s": least}
