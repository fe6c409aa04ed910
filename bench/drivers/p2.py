"""Driver for Phase-2 training mixes: the buffered-KD step of
``repro.launch.steps.make_phase2_step`` run back to back on seeded token
batches, checked against the plain reference over its first three steps.

Mix keys: batch, seq, batches (distinct batches cycled), loss_chunk,
buffer_mode, loss_backend, tau, optimizer {lr, b1, b2, eps, weight_decay},
check {loss_gap, grad_gap, delta_gap} (the limits).

Set-up builds one jitted step with its state, drives it through steps 1-3
on batches 0-2 (all rows differ), and reads the loss of each, AdamW's
first moment after step 1 (its norm per leaf is (1 - b1) times the first
gradient's) and the student after step 3.  The window goes on from step 4
with the same object, dispatching each step without waiting for it: it
waits only on the step two behind, and on the last one at the close.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from bench import counts, harness, program, reference
from bench.weights import Dims, make_tokens, make_weights

TAG_STUDENT, TAG_TEACHER, TAG_TOKENS = 1, 2, 3
CHECKED_STEPS = 3
RUN_AHEAD = 2


def batches(mix, dm: Dims, seed):
    """The mix's distinct token batches: [(tokens, labels)] (B, S) int32."""
    toks = make_tokens(seed, TAG_TOKENS,
                       (mix["batches"], mix["batch"], mix["seq"] + 1),
                       dm.vocab)
    return [(toks[i, :, :-1], toks[i, :, 1:]) for i in range(mix["batches"])]


def hyper(mix, dm):
    o = mix["optimizer"]
    return {"lr": o["lr"], "b1": o["b1"], "b2": o["b2"], "eps": o["eps"],
            "wd": o["weight_decay"], "tau": mix["tau"],
            "loss_rows": mix["loss_chunk"], "param_dtype": dm.dtype}


def build(cfg, mix):
    """The program's jitted step and optimizer, as a user would build
    them (student and optimizer state donated)."""
    import jax
    from repro.launch import steps as St
    from repro.optim import adamw
    o = mix["optimizer"]
    opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    step = St.make_phase2_step(cfg, opt, tau=mix["tau"],
                               buffer_mode=mix["buffer_mode"],
                               loss_backend=mix["loss_backend"],
                               loss_chunk=mix["loss_chunk"])

    def p2_step(student, teacher, buffer, opt_state, batch, i):
        return step(student, teacher, buffer, opt_state, batch, i)

    return jax.jit(p2_step, donate_argnums=(0, 3)), opt


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    flat = program.from_program(tree)
    f = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})
    return {k: float(v) for k, v in f(flat).items()}


def gap_by_leaf(prog, ref, keep):
    """Worst leaf's gap between two norms, against the larger of the
    reference leaf's norm and the median reference leaf's."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def compare(prog, ref, limits):
    """The checks: loss of each step, first gradient and change after the
    checked steps, each by its worst leaf.  Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out of the change."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["losses"],
                                                       ref["losses"]))
    g_ref = ref["grad"]
    med = float(np.median(list(g_ref.values())))
    keep = [k for k in g_ref if g_ref[k] >= 1e-3 * med]
    return [("loss_gap", loss_gap, limits["loss_gap"]),
            ("grad_gap", gap_by_leaf(prog["grad"], g_ref, list(g_ref)),
             limits["grad_gap"]),
            ("delta_gap", gap_by_leaf(prog["delta"], ref["delta"], keep),
             limits["delta_gap"])]


def reference_readings(dm, mix, seed, fp8=False, rows=None):
    """The reference's losses, first gradient and change per leaf over the
    checked steps, from weights and batches made anew from the seed."""
    import jax
    import jax.numpy as jnp
    student = make_weights(dm, seed, TAG_STUDENT)
    teacher = make_weights(dm, seed, TAG_TEACHER)
    data = batches(mix, dm, seed)[:CHECKED_STEPS]
    losses, grad, p = reference.train(dm, hyper(mix, dm), student, teacher,
                                      student, data, fp8=fp8, rows=rows)
    diff = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k] - b[k].astype(jnp.float32)))) for k in a})
    delta = {k: float(v) for k, v in diff(p, student).items()}
    return {"losses": losses, "grad": grad, "delta": delta}


def host_delta(after, before):
    """Per-leaf norm of after - before, on the host in float32."""
    out = {}
    for k, a in after.items():
        d = np.asarray(a, np.float32) - np.asarray(before[k], np.float32)
        out[k] = float(np.sqrt(np.sum(np.square(d, dtype=np.float64))))
    return out


def first_steps(jitted, opt, cfg, dm, mix, seed):
    """Set-up: the program's state made from the seed and driven through
    the checked steps by ``jitted``.  Returns (state, readings): state is
    (student, teacher, buffer, opt_state, batches) ready for the window;
    readings the losses, the first gradient's norm per leaf (from AdamW's
    first moment) and the student after the checked steps, on the host."""
    import jax
    import jax.numpy as jnp
    student = program.to_program(make_weights(dm, seed, TAG_STUDENT))
    teacher = program.to_program(make_weights(dm, seed, TAG_TEACHER))
    program.check_layout(cfg, student)
    buffer = jax.jit(lambda p: jax.tree.map(jnp.copy, p))(student)
    opt_state = jax.jit(opt.init)(student)
    data = [{"tokens": t, "labels": l} for t, l in batches(mix, dm, seed)]
    b1 = mix["optimizer"]["b1"]
    losses, grad = [], None
    for i in range(CHECKED_STEPS):
        student, opt_state, m = jitted(student, teacher, buffer, opt_state,
                                       data[i], jnp.int32(i))
        losses.append(float(m["loss"]))
        if i == 0:
            grad = {k: v / (1 - b1) for k, v in
                    leaf_norms(opt_state["m"]).items()}
    after = jax.device_get(program.from_program(student))
    return ((student, teacher, buffer, opt_state, data),
            {"losses": losses, "grad": grad, "after": after})


def program_readings(first, dm, seed):
    """The program's readings, its change per leaf taken against the
    initial student made anew from the seed (after the state is freed)."""
    import jax
    before = jax.device_get(make_weights(dm, seed, TAG_STUDENT))
    return {"losses": first["losses"], "grad": first["grad"],
            "delta": host_delta(first["after"], before)}


def run(cell: harness.Cell, t_start):
    import jax
    import jax.numpy as jnp
    mix, dm = cell.mix, Dims.from_config(cell.config)
    cfg = program.lm_config(dm, cell.config["name"])
    counter = harness.CompileCounter()
    tracer = harness.Tracer(cell.trace)
    jitted, opt = build(cfg, mix)
    state, first = first_steps(jitted, opt, cfg, dm, mix, cell.seed)
    student, teacher, buffer, opt_state, data = state
    del state
    setup_s = time.perf_counter() - t_start

    step_tokens = mix["batch"] * mix["seq"]
    pending = collections.deque()
    steps = 0
    with harness.window(counter), tracer.around():
        t0 = time.perf_counter()
        i = CHECKED_STEPS
        while time.perf_counter() - t0 < cell.seconds:
            with tracer.span("bench.dispatch"):
                student, opt_state, m = jitted(
                    student, teacher, buffer, opt_state,
                    data[i % len(data)], jnp.int32(i))
            pending.append(m["loss"])
            i += 1
            steps += 1
            if len(pending) > RUN_AHEAD:
                with tracer.span("bench.wait_step"):
                    pending.popleft().block_until_ready()
        with tracer.span("bench.wait_step"):
            jax.block_until_ready((student, opt_state, list(pending)))
        window_s = time.perf_counter() - t0
    mem = harness.memory_peak_bytes(cell.workload["chips"])

    facts = {"memory_peak_bytes": mem, "steps": steps, "window_s": window_s}
    breakdown = None
    if cell.trace:
        tr = tracer.load()
        facts["trace_device"], breakdown = harness.trace_facts(tracer)
        facts.update(trace_counts(tr, dm, mix, cell))

    del student, teacher, buffer, opt_state, data, m, pending
    gc.collect()
    prog = program_readings(first, dm, cell.seed)
    del first
    ref = reference_readings(dm, mix, cell.seed)
    checks = compare(prog, ref, mix["check"])
    e2e = {"setup_s": setup_s, "p2_tokens_s": steps * step_tokens / window_s}
    return harness.Outcome(e2e=e2e, facts=facts, attempted=steps, failed=0,
                           checks=checks, breakdown=breakdown)


def kd_loss_op(name, rows):
    """"fwd" or "bwd" for the fused kd_loss kernels of a ``rows``-row
    chunk (a Pallas call returning the (rows, 11) statistics, or the
    (rows, vocab) student gradient), else None."""
    from bench import trace as T
    if not T.is_kernel(name):
        return None
    shape = T.result_shape(name)
    if shape == f"f32[{rows},11]":
        return "fwd"
    if shape.startswith(f"f32[{rows},"):
        return "bwd"
    return None


def trace_counts(tr, dm, mix, cell):
    """What the per-layer readers of a training cell read from the trace:
    the step's device time and operations, and the kd_loss kernels' time
    and least time."""
    from bench import trace as T
    peak, bw = harness.chip_peaks(cell)
    step_s, runs = T.module_time(tr, "jit_p2_step")
    rows = mix["batch"] * mix["loss_chunk"]
    fwd_s, fwd_n = T.op_time(tr, lambda n: kd_loss_op(n, rows) == "fwd")
    bwd_s, bwd_n = T.op_time(tr, lambda n: kd_loss_op(n, rows) == "bwd")
    f_ops, f_b = counts.kd_loss_fwd_call(dm, rows)
    b_ops, b_b = counts.kd_loss_bwd_call(dm, rows)
    least = (fwd_n * counts.roofline_s(f_ops, f_b, peak, bw)
             + bwd_n * counts.roofline_s(b_ops, b_b, peak, bw))
    return {"peak_flops": peak,
            "step_device_s": step_s, "step_runs": runs,
            "step_flops": counts.p2_step_flops(dm, mix["batch"], mix["seq"]),
            "kd_loss_s": fwd_s + bwd_s, "kd_loss_calls": fwd_n + bwd_n,
            "kd_loss_least_s": least}
