"""Bring-up smoke test: the serving tick and the Phase-2 buffered-KD step
on a TPU, at the published widths of granite-3-2b.

One process takes the chip once, spawns no children, and runs in order:

  device   platform, kind and count as JAX reports them; fails off a TPU
  kernels  paged_decode (at granite's and qwen1.5-4b's head shapes) and
           kd_loss (value and gradient) on the chip at the shapes the
           phases below use, against repro.kernels.ref
  serve    repro.launch.serve --full --paged: all 40 layers, a shared
           system prefix, every request served to its requested length
  phase2   buffered-KD steps (clone buffer, fused Pallas loss) through
           launch.steps.make_phase2_step at granite widths, the depth cut
           to what one chip holds; the loss must be finite and fall

    python3 chip_smoke.py                # one chip, the phases above
    python3 chip_smoke.py --four-chips   # four chips, the sharded step only

``--four-chips`` runs only the sharded Phase-2 phase on a 2x2 (data, model)
mesh: the full 40-layer step for a few steps, the depth-cut steps on one
device and on four compared step by step (loss, and the optimizer moment
that carries the gradient's scale), and the bytes each device holds.  Set-up facts (compile seconds, wall times, peak bytes) go to
earlier lines; the last line of stdout is one JSON object naming the
device, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "granite-3-2b"
SEED = 0                   # weights, data and requests are all made from it
# Serve phase: a few slots, a few dozen requests of a few hundred prompt
# tokens behind one 128-token system prefix.
SLOTS, REQUESTS, MAX_LEN, PAGE_SIZE = 4, 32, 2048, 16
PROMPT_MAX, SHARED_PREFIX, OUT_MAX = 256, 128, 16
# Phase 2: batch x seq tokens per step; the fused loss takes rows of
# batch x LOSS_CHUNK.  4 of 40 layers is the deepest cut whose compiled
# step (student, teacher, buffer, AdamW m/v, plus ~8 GiB of attention
# temporaries at 4 x 2048) stays under one v5e's 16 GiB.
P2_LAYERS, P2_BATCH, P2_SEQ, LOSS_CHUNK = 4, 4, 2048, 512
P2_STEPS, P2_LR = 4, 1e-3
# Tolerances against the float32 references: the paged kernel writes bf16
# (rel. rounding 2^-8 on outputs of magnitude <= ~1); kd_loss is float32.
PAGED_ATOL = 2e-2
KD_RTOL = 1e-4
KD_GRAD_RTOL = 1e-3        # of the largest reference gradient entry
# One device vs four over SHARD_STEPS steps: the same float32 step, its
# matmuls at the TPU's default (bf16-pass) precision, reduced in another
# order.  Dropping one of four row shards in the fused loss moves the
# moment norm by ~15 % at smoke size.
SHARD_STEPS, SHARD_RTOL = 2, 1e-3
# Fail when one device holds this much more than the mean (everything on
# device 0 would give ~4x on a 2x2 mesh).
BYTES_SKEW = 1.25


def log(msg):
    print(msg, flush=True)


def check(ok, msg):
    if not ok:
        raise SystemExit(f"FAILED: {msg}")


def peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(need):
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: JAX found platform {d.platform!r}")
    check(len(devs) >= need, f"need {need} chips, found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def check_paged_decode(cfg, slots, max_len, page_size):
    """paged_decode on the device vs the dense-view reference: ``slots``
    rows from a full table down to a few keys, then a row of a single page
    and an inactive row (``pos = -1``, which must come back zeros)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    n, d = cfg.kv_heads, cfg.head_dim
    g = cfg.num_heads // n
    rows = slots + 2
    pp = max_len // page_size
    pages = rows * pp + 1                        # + the trash page
    kq, kk, kv, kp = jax.random.split(jax.random.key(SEED), 4)
    q = jax.random.normal(kq, (rows, n, g, d), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (pages, page_size, n * d), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (pages, page_size, n * d), jnp.bfloat16)
    pt = (1 + jax.random.permutation(kp, pages - 1)).reshape(rows, pp)
    pt = pt.astype(jnp.int32)
    pos = np.linspace(max_len - 1, 3, slots).astype(np.int32)
    pos = jnp.asarray(np.concatenate([pos, [page_size // 2, -1]]), jnp.int32)
    got = jax.jit(lambda *a: ops.paged_decode_attn(*a, use_pallas=True))(
        q, k_pool, v_pool, pt, pos)
    f32 = lambda a: a.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.paged_decode_ref)(f32(q), f32(k_pool), f32(v_pool),
                                             pt, pos)
    err = float(jnp.max(jnp.abs(f32(got) - want)))
    idle = float(jnp.max(jnp.abs(f32(got[-1]))))
    log(f"[kernels] paged_decode q{tuple(q.shape)} pool{tuple(k_pool.shape)} "
        f"bf16 pos {pos.tolist()}: max |err| {err} (atol {PAGED_ATOL}), "
        f"inactive row max |out| {idle}")
    check(got.shape == q.shape and err <= PAGED_ATOL,
          f"paged_decode off the reference by {err}")
    check(idle == 0.0, f"paged_decode's inactive row is not zeros ({idle})")


def check_kd_loss(rows, vocab, valid):
    """Fused kd_loss value and student gradient vs the jnp reference, on
    padded-vocab logits masked the way the Phase-2 step masks them."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.key(SEED), 4)
    cols = jnp.arange(vocab) < valid
    logits = lambda k: jnp.where(cols, 3.0 * jax.random.normal(
        k, (rows, vocab), jnp.float32), -1e30)
    s, t, b = logits(ks[0]), logits(ks[1]), logits(ks[2])
    y = jax.random.randint(ks[3], (rows,), 0, valid)
    tau = 2.0
    # Operands go in as arguments: closed-over arrays would be baked into
    # the executable as constants (gigabytes at this vocab).
    fused = jax.jit(jax.value_and_grad(
        lambda s_, y_, t_, b_: ops.kd_loss(y_, s_, t_, b_, tau,
                                           use_pallas=True)))
    plain = jax.jit(jax.value_and_grad(
        lambda s_, y_, t_, b_: ref.kd_loss_mean_ref(y_, s_, t_, b_, tau)))
    (lv, g), (lr, gr) = fused(s, y, t, b), plain(s, y, t, b)
    lv, lr = float(lv), float(lr)
    gerr = float(jnp.max(jnp.abs(g - gr)))
    gmax = float(jnp.max(jnp.abs(gr)))
    log(f"[kernels] kd_loss rows={rows} vocab={vocab}: loss {lv} vs {lr}, "
        f"grad max |err| {gerr} of max |g| {gmax}")
    check(abs(lv - lr) <= KD_RTOL * abs(lr), "kd_loss value off the reference")
    check(gerr <= KD_GRAD_RTOL * gmax, "kd_loss gradient off the reference")


def phase_kernels():
    from repro.configs import registry
    cfg = registry.get_config(ARCH)
    t0 = time.perf_counter()
    check_paged_decode(cfg, SLOTS, MAX_LEN, PAGE_SIZE)
    # One query row per kv head (G = 1), as the decode-backlog cell serves.
    check_paged_decode(registry.get_config("qwen1.5-4b"), SLOTS, 1024,
                       PAGE_SIZE)
    check_kd_loss(P2_BATCH * LOSS_CHUNK, cfg.padded_vocab, cfg.vocab_size)
    log(f"[kernels] ok in {time.perf_counter() - t0:.3f}s")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_argv():
    return ["--arch", ARCH, "--full", "--paged", "--stream", "poisson",
            "--slots", str(SLOTS), "--requests", str(REQUESTS),
            "--max-len", str(MAX_LEN), "--page-size", str(PAGE_SIZE),
            "--prompt-max", str(PROMPT_MAX), "--out-max", str(OUT_MAX),
            "--shared-prefix", str(SHARED_PREFIX), "--seed", str(SEED)]


def check_served(finished, engine, n_requests, vocab):
    """Every request served to its requested length with in-vocab tokens,
    and the shared prefix actually hit the page cache."""
    check(len(finished) == n_requests,
          f"served {len(finished)} of {n_requests} requests")
    short = [r.rid for r in finished if len(r.out) != r.max_new]
    check(not short, f"requests {short} stopped short of max_new")
    bad = [r.rid for r in finished if not all(0 <= t < vocab for t in r.out)]
    check(not bad, f"requests {bad} emitted out-of-vocab tokens")
    ps = engine.prefix_stats()
    log(f"[serve] prefix hits {ps['hits']} / misses {ps['misses']}, "
        f"peak pages {ps['peak_pages']}")
    check(ps["hits"] > 0, "the shared system prefix never hit the cache")


def decode_tick_text(engine):
    """Compiled text of the engine's paged decode tick at its live state,
    lowered under the mesh ``serve --full`` serves on: only there does the
    tick wrap its kernel in a ``shard_map`` over the sharded pools."""
    import jax
    from repro.launch.mesh import make_device_mesh
    with jax.set_mesh(make_device_mesh()):
        return engine._tick_fn.lower(
            engine.params, engine.caches, engine.pt, engine.tokens,
            engine.pos, engine.budget, engine.active,
            engine._key).compile().as_text()


def phase_serve():
    import jax
    from repro.launch import serve
    t0 = time.perf_counter()
    finished, engine = serve.main(serve_argv())
    wall = time.perf_counter() - t0
    log(f"[serve] {len(finished)} requests in {wall:.3f}s wall "
        f"(compiles included); peak_bytes_in_use "
        f"{peak_bytes(jax.devices()[0])}")
    check_served(finished, engine, REQUESTS, engine.cfg.padded_vocab)
    check_kernel(decode_tick_text(engine), "serve")


# ---------------------------------------------------------------------------
# phase2
# ---------------------------------------------------------------------------

def run_phase2(cfg, mesh, steps, batch, seq, tag):
    """``steps`` clone-buffer KD steps of ``cfg`` on ``mesh`` over one fixed
    batch; returns (losses, norms of AdamW's first moment after each step,
    live state, compiled step text).  After step 0 that norm is 1 - b1 =
    0.1 of the gradient's, so it shows a gradient of the wrong scale, which
    Adam's normalized update would hide from the loss.  Student,
    teacher and buffer are made on the mesh in their sharded layout, never
    whole on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import specs as S
    from repro.launch import steps as St
    from repro.models.transformer import Transformer
    from repro.optim import adamw

    opt = adamw(P2_LR)
    _, p_sh = S.params_shardings(cfg, mesh)
    o_sh = {"m": p_sh, "v": p_sh}
    rng = np.random.default_rng(SEED)
    toks = rng.integers(0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32)
    host = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    b_sh = S.batch_shardings(
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in host.items()},
        mesh)
    data = {k: jax.device_put(v, b_sh[k]) for k, v in host.items()}
    repl = NamedSharding(mesh, P())

    step = St.make_phase2_step(cfg, opt, buffer_mode="clone",
                               loss_backend="pallas", loss_chunk=LOSS_CHUNK)
    with jax.set_mesh(mesh):
        init = jax.jit(lambda k: Transformer.init(cfg, k)[0],
                       out_shardings=p_sh)
        student = init(jax.random.key(SEED))
        teacher = init(jax.random.key(SEED + 1))
        clone = jax.jit(lambda p: jax.tree.map(jnp.copy, p),
                        out_shardings=p_sh)
        buffer = clone(student)
        opt_state = jax.jit(opt.init, out_shardings=o_sh)(student)
        jit_p2 = jax.jit(step, in_shardings=(p_sh, p_sh, p_sh, o_sh, b_sh,
                                             repl),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 3))
        t0 = time.perf_counter()
        compiled = jit_p2.lower(student, teacher, buffer, opt_state, data,
                                jnp.int32(0)).compile()
        ma = compiled.memory_analysis()
        log(f"[{tag}] compiled in {time.perf_counter() - t0:.3f}s; per "
            f"device: arguments {ma.argument_size_in_bytes} B, temporaries "
            f"{ma.temp_size_in_bytes} B, outputs {ma.output_size_in_bytes} B "
            f"({ma.alias_size_in_bytes} B aliased)")
        norm = jax.jit(lambda t: jnp.sqrt(sum(
            jnp.sum(jnp.square(x)) for x in jax.tree.leaves(t))))
        losses, mnorms = [], []
        for i in range(steps):
            t0 = time.perf_counter()
            student, opt_state, m = compiled(student, teacher, buffer,
                                             opt_state, data, jnp.int32(i))
            losses.append(float(m["loss"]))
            wall = time.perf_counter() - t0
            mnorms.append(float(norm(opt_state["m"])))
            log(f"[{tag}] step {i}: loss {losses[-1]}, |m| {mnorms[-1]} "
                f"({wall:.3f}s)")
    check(all(np.isfinite(losses + mnorms)),
          f"non-finite Phase-2 loss or moment {losses} {mnorms}")
    return (losses, mnorms, (student, teacher, buffer, opt_state, data),
            compiled.as_text())


def check_falls(losses, tag):
    check(losses[-1] < losses[0],
          f"{tag} loss did not fall on a fixed batch: {losses}")


def check_kernel(text, what):
    check("tpu_custom_call" in text, f"the compiled {what} holds no Pallas "
          f"kernel")
    log(f"[{what}] compiled with tpu_custom_call: ok")


def phase2_config(layers=None):
    """granite-3-2b at its published widths; ``layers`` cuts the depth."""
    from repro.configs import registry
    cfg = registry.get_config(ARCH)
    return cfg if layers is None else dataclasses.replace(cfg,
                                                          num_layers=layers)


def phase_phase2():
    import jax
    from repro.launch.mesh import make_device_mesh
    cfg = phase2_config(P2_LAYERS)
    log(f"[phase2] {ARCH} widths, depth cut to {P2_LAYERS} of "
        f"{phase2_config().num_layers} layers; batch {P2_BATCH} x seq "
        f"{P2_SEQ}; clone buffer, pallas loss")
    dev = jax.devices()[0]
    losses, _, state, text = run_phase2(cfg, make_device_mesh([dev]),
                                        P2_STEPS, P2_BATCH, P2_SEQ, "phase2")
    check_kernel(text, "phase2")
    check_falls(losses, "phase2")
    log(f"[phase2] ok; peak_bytes_in_use {peak_bytes(dev)}")
    del state


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def check_bytes_balanced(devs, tag):
    used = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs]
    mean = sum(used) / len(used)
    log(f"[{tag}] bytes_in_use per device {used} (mean {mean})")
    check(mean > 0 and max(used) <= BYTES_SKEW * mean,
          f"device bytes unreported or over {BYTES_SKEW}x the mean: {used}")


def check_shards_agree(one_dev, four_dev):
    """The sharded steps against the one-device steps: the loss of every
    step (after the first, it follows the update's direction) and the
    first-moment norm (the gradient's scale)."""
    off = []
    for name, a, b in zip(("loss", "|m|"), one_dev, four_dev):
        for i, (x, y) in enumerate(zip(a, b)):
            rel = abs(y - x) / abs(x)
            log(f"[four] step {i} {name}: 1 device {x} vs 4 devices {y}: "
                f"rel diff {rel} (rtol {SHARD_RTOL})")
            if not rel <= SHARD_RTOL:
                off.append(f"step {i} {name}")
    check(not off, f"sharded {off} disagree with one device")


def phase_four_chips(devs):
    from repro.launch.mesh import make_device_mesh
    four, one = devs[:4], devs[:1]
    mesh4 = make_device_mesh(four)
    log(f"[four] mesh {dict(mesh4.shape)}")

    # Depth-cut steps: one device against the same steps sharded over four.
    cut = phase2_config(P2_LAYERS)
    one_dev = run_phase2(cut, make_device_mesh(one), SHARD_STEPS, P2_BATCH,
                         P2_SEQ, "four/cut-1dev")
    four_dev = run_phase2(cut, mesh4, SHARD_STEPS, P2_BATCH, P2_SEQ,
                          "four/cut-4dev")
    check_shards_agree(one_dev[:2], four_dev[:2])
    del one_dev, four_dev

    # Full depth, sharded: does not fit one chip.
    losses, _, state, text = run_phase2(phase2_config(), mesh4, P2_STEPS,
                                        P2_BATCH, P2_SEQ, "four/full")
    check_kernel(text, "four/full")
    check_falls(losses, "four/full")
    check_bytes_balanced(four, "four/full")
    log(f"[four] peak_bytes_in_use {[peak_bytes(d) for d in four]}")
    del state


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded Phase-2 phase on four chips")
    args = ap.parse_args(argv)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devs = phase_device(4 if args.four_chips else 1)
    log(f"[device] compile cache: {enable_compile_cache()}")
    if args.four_chips:
        phase_four_chips(devs)
    else:
        phase_kernels()
        phase_serve()
        phase_phase2()
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
