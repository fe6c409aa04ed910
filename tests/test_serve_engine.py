"""Serving-engine lockdown: per-slot decode correctness, boundary semantics,
legacy parity, bounded prefill compiles, stream registry.

The centerpiece regressions:

* ``test_staggered_admission_matches_sequential`` — the shared-``ptick``
  bug: the pre-refactor loop decoded every slot at ``max(pos)``, so a slot
  admitted later produced wrong tokens.  The engine's per-slot ``pos``
  vector must be token-exact against decoding each request alone.
* ``test_engine_parity_vs_legacy`` — the serving analogue of
  ``tests/test_method_parity.py``: on position-homogeneous request sets
  (where the old loop is correct) the engine must be token-exact against
  the frozen ``repro.serve.legacy`` loop, full and ring caches.
* ``test_prefill_compile_count`` — bucketed admission bounds recompiles to
  ``log2(max_prompt) + 1`` executables (jit cache-size inspection).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import Transformer
from repro.serve import (STREAMS, Request, ServeEngine, build_stream,
                         bucket_length)
from repro.serve import legacy as legacy_mod


def _setup(ring=False):
    cfg = registry.get_smoke_config("granite-3-2b")
    if ring:
        cfg = dataclasses.replace(cfg, sliding_window=8, ring_cache=True)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(0))
    return cfg, params, mesh


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size - 1, size=n)


def sequential_decode(cfg, params, prompt, max_new, max_len):
    """Single-request greedy reference: exact-length prefill + scalar-pos
    decode, one token at a time — the ground truth every batching scheme
    must reproduce token-exactly."""
    toks = jnp.asarray(prompt)[None, :]
    lg, cache = Transformer.prefill(cfg, params, {"tokens": toks}, max_len)
    out = [int(jnp.argmax(lg[0, -1]))]
    pos = len(prompt)
    while len(out) < max_new and pos < max_len - 1:
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        lgs, cache = Transformer.decode_step(cfg, params, cache, tok,
                                             jnp.int32(pos))
        # reprolint: disable=R002 (reference decoder syncs per token by design)
        out.append(int(jnp.argmax(lgs[0, -1])))
        pos += 1
    return out


# ---------------------------------------------------------------------------
# The shared-ptick regression (staggered admission).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ring", [False, True])
def test_staggered_admission_matches_sequential(ring):
    """Two requests admitted at different ticks with different prompt
    lengths sit at different positions in the same decode batch; every
    emitted token must equal sequential single-request decoding.  (The
    legacy loop fails this: its scalar ``ptick = max(pos)`` masks the
    lagging slot as if it sat at the batch maximum.)"""
    cfg, params, mesh = _setup(ring=ring)
    rng = np.random.default_rng(3)
    max_len = 48
    reqs = [Request(rid=0, arrival=0, prompt=_prompt(rng, cfg, 6), max_new=10),
            Request(rid=1, arrival=2, prompt=_prompt(rng, cfg, 11), max_new=8)]
    with jax.set_mesh(mesh):
        want = {r.rid: sequential_decode(cfg, params, r.prompt, r.max_new,
                                         max_len) for r in reqs}
        engine = ServeEngine(cfg, params, slots=2, max_len=max_len)
        finished = engine.run(reqs, log=None)
    assert len(finished) == 2
    for r in finished:
        assert r.out == want[r.rid], (
            f"r{r.rid}: engine {r.out} != sequential {want[r.rid]}")


def test_legacy_loop_has_the_shared_ptick_bug():
    """Documented defect pin: under the same staggered admission the frozen
    legacy loop decodes the lagging slot at ``max(pos)`` — its RoPE
    positions and mask are wrong, so its output diverges from sequential
    decoding (if it ever starts matching, the frozen copy was modified)."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(3)
    max_len = 48
    reqs = [Request(rid=0, arrival=0, prompt=_prompt(rng, cfg, 6), max_new=10),
            Request(rid=1, arrival=2, prompt=_prompt(rng, cfg, 11), max_new=8)]
    with jax.set_mesh(mesh):
        want = {r.rid: sequential_decode(cfg, params, r.prompt, r.max_new,
                                         max_len) for r in reqs}
    finished = legacy_mod.simulate(cfg, params, reqs, 2, max_len, mesh,
                                   log=lambda *a: None)
    mismatch = [r.rid for r in finished if r.out != want[r.rid]]
    assert mismatch, "legacy loop unexpectedly token-exact under staggered " \
                     "admission — shared-ptick defect pin no longer holds"


# ---------------------------------------------------------------------------
# max_new / max_len boundary semantics.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("max_new", [1, 2])
def test_max_new_boundary(max_new):
    """A request with ``max_new=k`` emits exactly k tokens.  The legacy
    loop got k=1 wrong (prefill token + one decode tick before the budget
    check = 2 tokens)."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(5)
    prompt = _prompt(rng, cfg, 7)
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=1, max_len=32)
        finished = engine.run([Request(rid=0, arrival=0, prompt=prompt,
                                       max_new=max_new)], log=None)
    assert len(finished) == 1
    assert len(finished[0].out) == max_new
    with jax.set_mesh(mesh):
        want = sequential_decode(cfg, params, prompt, max_new, 32)
    assert finished[0].out == want


def test_legacy_max_new_one_emits_two_tokens():
    """Defect pin on the frozen copy: legacy ``max_new=1`` emits 2."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(5)
    req = Request(rid=0, arrival=0, prompt=_prompt(rng, cfg, 7), max_new=1)
    finished = legacy_mod.simulate(cfg, params, [req], 1, 32, mesh,
                                   log=lambda *a: None)
    assert len(finished[0].out) == 2


def test_max_len_truncation_edge():
    """Decode stops at ``pos == max_len - 1``: a 12-token prompt in a
    16-token budget yields 1 + (16-1-12) = 4 tokens no matter how large
    ``max_new`` is; a prompt already at ``max_len - 1`` yields exactly the
    prefill token."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(7)
    p12, p15 = _prompt(rng, cfg, 12), _prompt(rng, cfg, 15)
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=2, max_len=16)
        finished = engine.run(
            [Request(rid=0, arrival=0, prompt=p12, max_new=50),
             Request(rid=1, arrival=0, prompt=p15, max_new=50)], log=None)
        want = sequential_decode(cfg, params, p12, 50, 16)
    by_rid = {r.rid: r for r in finished}
    assert len(by_rid[0].out) == 4
    assert by_rid[0].out == want
    assert len(by_rid[1].out) == 1


def test_bucket_capped_at_max_len():
    """A prompt whose pow2 bucket overshoots max_len (40 -> 64 > 48) must
    pad to max_len instead of crashing the prefill cache build — and still
    decode token-exactly."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(23)
    prompt = _prompt(rng, cfg, 40)
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=2, max_len=48)
        finished = engine.run([Request(rid=0, arrival=0, prompt=prompt,
                                       max_new=4)], log=None)
        want = sequential_decode(cfg, params, prompt, 4, 48)
    assert finished[0].out == want


def test_prompt_longer_than_max_len_rejected():
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(9)
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=1, max_len=16)
        with pytest.raises(ValueError, match="max_len"):
            engine.run([Request(rid=0, arrival=0,
                                prompt=_prompt(rng, cfg, 16), max_new=4)])


# ---------------------------------------------------------------------------
# Engine vs frozen legacy loop (the serving test_method_parity).
# ---------------------------------------------------------------------------


def _tail_only_setup(ring=False):
    """Smoke config reshaped so the layer stack is unstacked (n_super=0,
    tail-only caches with a leading *batch* axis).  The legacy loop's
    per-slot cache write (``batched.at[slot].set(single[0])``) is only
    correct there — on scanned stacks the leading cache axis is the LAYER
    axis, so the write lands on the wrong axis entirely (see
    ``test_legacy_layered_cache_admission_bug``)."""
    cfg = registry.get_smoke_config("granite-3-2b")
    cfg = dataclasses.replace(cfg, block_pattern=("attn",) * 3)
    assert cfg.n_super == 0 and cfg.n_tail == 2
    if ring:
        cfg = dataclasses.replace(cfg, sliding_window=8, ring_cache=True)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(0))
    return cfg, params, mesh


@pytest.mark.parametrize("ring", [False, True])
def test_engine_parity_vs_legacy(ring):
    """On position-homogeneous request sets — every wave admitted on one
    tick with equal prompt lengths and budgets, so the legacy scalar
    ``ptick`` happens to be each slot's true position — the engine must be
    token-exact per request against the frozen pre-refactor loop.  Two
    waves (6 requests / 3 slots) also exercise slot reuse; the ring variant
    crosses one window wraparound during decode.  Run on the tail-only
    config where the legacy loop's cache write is well-defined."""
    cfg, params, mesh = _tail_only_setup(ring=ring)
    rng = np.random.default_rng(11)
    max_len = 32

    def reqs():
        rng2 = np.random.default_rng(11)
        return [Request(rid=i, arrival=0, prompt=_prompt(rng2, cfg, 10),
                        max_new=5) for i in range(6)]

    legacy_out = legacy_mod.simulate(cfg, params, reqs(), 3, max_len, mesh,
                                     log=lambda *a: None)
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=3, max_len=max_len)
        engine_out = engine.run(reqs(), log=None)
    assert len(legacy_out) == len(engine_out) == 6
    want = {r.rid: r.out for r in legacy_out}
    for r in engine_out:
        assert r.out == want[r.rid], (
            f"r{r.rid}: engine {r.out} != legacy {want[r.rid]}")


def test_legacy_layered_cache_admission_bug():
    """Third documented legacy defect (found while building the parity
    suite): ``prefill_into``'s per-slot cache write indexes the LEADING
    cache axis, which for scanned layer stacks is the layer axis
    (n_super, S, W, N, D) — not the batch axis.  Even one request in one
    slot decodes from a garbled cache on any stacked config.  The engine's
    axis-aware slot merge fixes this (its stacked-config correctness is
    ``test_staggered_admission_matches_sequential``, which runs on the
    n_super=2 smoke config)."""
    cfg, params, mesh = _setup()
    assert cfg.n_super > 1
    rng = np.random.default_rng(5)
    prompt = _prompt(rng, cfg, 8)
    with jax.set_mesh(mesh):
        want = sequential_decode(cfg, params, prompt, 6, 32)
    finished = legacy_mod.simulate(
        cfg, params, [Request(rid=0, arrival=0, prompt=prompt, max_new=6)],
        1, 32, mesh, log=lambda *a: None)
    assert finished[0].out != want, \
        "legacy loop unexpectedly correct on a stacked cache — defect pin " \
        "no longer holds (frozen copy modified?)"


def test_recurrent_arch_exact_length_admission():
    """Recurrent caches carry state, so padded prefill is rejected and the
    engine falls back to exact-length admission — outputs still match
    sequential decoding."""
    cfg = registry.get_smoke_config("mamba2-370m")
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(0))
    rng = np.random.default_rng(13)
    reqs = [Request(rid=0, arrival=0, prompt=_prompt(rng, cfg, 6), max_new=4),
            Request(rid=1, arrival=1, prompt=_prompt(rng, cfg, 9), max_new=4)]
    with jax.set_mesh(mesh):
        want = {r.rid: sequential_decode(cfg, params, r.prompt, r.max_new, 32)
                for r in reqs}
        engine = ServeEngine(cfg, params, slots=2, max_len=32)
        assert not engine._bucketed
        finished = engine.run(reqs, log=None)
    for r in finished:
        assert r.out == want[r.rid]


# ---------------------------------------------------------------------------
# Bounded prefill compiles (bucketing).
# ---------------------------------------------------------------------------


def test_bucket_length():
    assert [bucket_length(n) for n in (1, 8, 9, 16, 17, 48, 64)] == \
        [8, 8, 16, 16, 32, 64, 64]


def test_prefill_compile_count(trace_guard):
    """Admission across many distinct prompt lengths must trace at most
    ``log2(max_prompt) + 1`` prefill executables (one per power-of-two
    bucket) — the legacy loop traced one per distinct length.  The bound is
    enforced live by the sanitizer, then a warm second run must not reach
    the compiler at all (same buckets, same tick shapes)."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(17)
    lengths = [3, 5, 9, 12, 17, 33, 47, 60]
    max_prompt = max(lengths)
    bound = int(np.log2(max_prompt)) + 1
    # staggered arrivals -> one admission per tick, so each request's own
    # bucket is what traces (same-tick arrivals would merge into one
    # max-bucket admission and trace fewer shapes)
    def mk_reqs():
        return [Request(rid=i, arrival=3 * i, prompt=_prompt(rng, cfg, n),
                        max_new=2)
                for i, n in enumerate(lengths)]
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=2, max_len=80)
        with trace_guard(engine._admit_fn, max_compiles=bound):
            engine.run(mk_reqs(), log=None)
        got = engine.prefill_compile_count()
        assert got <= bound, (got, bound)
        # Exactly the buckets the lengths map to: {8, 16, 32, 64}.
        assert got == len({bucket_length(n) for n in lengths})
        # Warm engine: admission and decode tick are both fully compiled —
        # serving the same bucket mix again must trace nothing.
        with trace_guard(engine._admit_fn, engine._tick_fn, max_compiles=0):
            engine.run(mk_reqs(), log=None)


# ---------------------------------------------------------------------------
# Stream registry (arrival-process scenarios).
# ---------------------------------------------------------------------------


def test_stream_registry_names():
    assert set(STREAMS) == {"poisson", "bursty", "diurnal", "heavy_tail"}
    with pytest.raises(ValueError, match="unknown stream"):
        build_stream("sinusoidal", 4, vocab=64)


@pytest.mark.parametrize("name", sorted(STREAMS))
def test_stream_deterministic_and_bounded(name):
    a = build_stream(name, 24, vocab=512, seed=4, prompt_max=40, out_max=12)
    b = build_stream(name, 24, vocab=512, seed=4, prompt_max=40, out_max=12)
    assert len(a) == 24
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(1 <= len(r.prompt) <= 40 for r in a)
    assert all(1 <= r.max_new <= 12 for r in a)
    assert all(r.prompt.max() < 512 for r in a)
    arr = [r.arrival for r in a]
    assert arr == sorted(arr)
    c = build_stream(name, 24, vocab=512, seed=5, prompt_max=40, out_max=12)
    assert [r.arrival for r in a] != [r.arrival for r in c] or \
        any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_bursty_stream_has_bursts():
    reqs = build_stream("bursty", 30, vocab=128, seed=0)
    arrivals = [r.arrival for r in reqs]
    assert len(set(arrivals)) < len(arrivals)  # same-tick groups exist


def test_heavy_tail_prompt_spread():
    reqs = build_stream("heavy_tail", 200, vocab=128, seed=0, prompt_max=64)
    lens = np.array([len(r.prompt) for r in reqs])
    assert lens.min() >= 4 and lens.max() <= 64
    assert np.median(lens) < lens.max() / 2  # most short, a few giants


# ---------------------------------------------------------------------------
# Block-paged engine: token parity, prefix sharing, compile bounds, memory.
# ---------------------------------------------------------------------------


def test_paged_staggered_admission_matches_sequential():
    """The shared-ptick regression on the paged path: staggered admissions
    at different positions, decoding through page-table gathers, must stay
    token-exact against sequential single-request decoding."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(3)
    max_len = 48
    reqs = [Request(rid=0, arrival=0, prompt=_prompt(rng, cfg, 6), max_new=10),
            Request(rid=1, arrival=2, prompt=_prompt(rng, cfg, 11), max_new=8)]
    with jax.set_mesh(mesh):
        want = {r.rid: sequential_decode(cfg, params, r.prompt, r.max_new,
                                         max_len) for r in reqs}
        engine = ServeEngine(cfg, params, slots=2, max_len=max_len,
                             paged=True, page_size=8)
        finished = engine.run(reqs, log=None)
    assert len(finished) == 2
    for r in finished:
        assert r.out == want[r.rid], (
            f"r{r.rid}: paged engine {r.out} != sequential {want[r.rid]}")


@pytest.mark.parametrize("max_new", [1, 2])
def test_paged_max_new_boundary(max_new):
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(5)
    prompt = _prompt(rng, cfg, 7)
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=1, max_len=32,
                             paged=True, page_size=8)
        finished = engine.run([Request(rid=0, arrival=0, prompt=prompt,
                                       max_new=max_new)], log=None)
        want = sequential_decode(cfg, params, prompt, max_new, 32)
    assert len(finished) == 1 and len(finished[0].out) == max_new
    assert finished[0].out == want


def test_paged_max_len_truncation_edge():
    """Same ``pos == max_len - 1`` semantics as the dense engine: a
    12-token prompt in a 16 budget emits 4 tokens; a 15-token prompt emits
    exactly the prefill token — and the page grant is capped at
    ``max_len - 1`` positions, so admission never asks for pages a
    truncated decode cannot reach."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(7)
    p12, p15 = _prompt(rng, cfg, 12), _prompt(rng, cfg, 15)
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=2, max_len=16,
                             paged=True, page_size=8)
        finished = engine.run(
            [Request(rid=0, arrival=0, prompt=p12, max_new=50),
             Request(rid=1, arrival=0, prompt=p15, max_new=50)], log=None)
        want = sequential_decode(cfg, params, p12, 50, 16)
    by_rid = {r.rid: r for r in finished}
    assert len(by_rid[0].out) == 4 and by_rid[0].out == want
    assert len(by_rid[1].out) == 1


def test_paged_matches_dense_on_every_named_stream():
    """Token-exact parity dense vs paged across all four arrival-process
    scenarios — bursts (multi-slot same-tick admission), diurnal clusters,
    heavy-tail giants.  One engine pair reused across streams (reset
    between) keeps the compile bill to one set of executables."""
    cfg, params, mesh = _setup()
    with jax.set_mesh(mesh):
        dense = ServeEngine(cfg, params, slots=3, max_len=64)
        paged = ServeEngine(cfg, params, slots=3, max_len=64,
                            paged=True, page_size=8)
        for name in sorted(STREAMS):
            reqs = lambda: build_stream(name, 8, vocab=cfg.vocab_size,
                                        seed=29, prompt_max=24, out_max=8)
            dense.reset()
            paged.reset()
            want = {r.rid: r.out for r in dense.run(reqs(), log=None)}
            got = {r.rid: r.out for r in paged.run(reqs(), log=None)}
            assert got == want, f"stream {name!r}: paged != dense"


def test_paged_shared_prefix_stream_hits_and_parity():
    """A stream where most requests open with one 20-token system prompt:
    the paged engine must (a) stay token-exact vs dense, (b) serve later
    admissions from the prefix cache (hits > 0, ``prefix_pages`` stamped),
    and (c) skip prefill work for the shared pages."""
    cfg, params, mesh = _setup()
    ps = 8
    reqs = lambda: build_stream("bursty", 10, vocab=cfg.vocab_size, seed=13,
                                prompt_max=20, out_max=6, shared_prefix=20)
    with jax.set_mesh(mesh):
        dense = ServeEngine(cfg, params, slots=3, max_len=96)
        want = {r.rid: r.out for r in dense.run(reqs(), log=None)}
        paged = ServeEngine(cfg, params, slots=3, max_len=96,
                            paged=True, page_size=ps)
        finished = paged.run(reqs(), log=None)
    assert {r.rid: r.out for r in finished} == want
    stats = paged.prefix_stats()
    assert stats["hits"] > 0
    # 20 shared tokens at page_size 8 -> 2 full shared pages; every hit
    # request was admitted with both already resident.
    hit_reqs = [r for r in finished if r.prefix_pages > 0]
    assert len(hit_reqs) == stats["hits"]
    assert all(r.prefix_pages == 20 // ps for r in hit_reqs)


def test_paged_prefill_compile_count(trace_guard):
    """Without shared prefixes every paged admission is an ``npp=0``
    trace, so the dense bucketing bound holds verbatim: at most
    ``log2(max_prompt) + 1`` admission executables, and a warm second run
    traces nothing (admission and tick)."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(17)
    lengths = [3, 5, 9, 12, 17, 33, 47, 60]
    bound = int(np.log2(max(lengths))) + 1

    def mk_reqs():
        return [Request(rid=i, arrival=3 * i, prompt=_prompt(rng, cfg, n),
                        max_new=2)
                for i, n in enumerate(lengths)]
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=2, max_len=80,
                             paged=True, page_size=16)
        with trace_guard(engine._admit_fn, max_compiles=bound):
            engine.run(mk_reqs(), log=None)
        got = engine.prefill_compile_count()
        assert got <= bound, (got, bound)
        assert got == len({bucket_length(n) for n in lengths})
        engine.reset()
        with trace_guard(engine._admit_fn, engine._tick_fn, max_compiles=0):
            engine.run(mk_reqs(), log=None)


def test_paged_undersized_pool_defers_and_stays_exact():
    """With a pool too small for all slots at once the allocator refuses
    mid-stream admissions; the engine requeues them FIFO and serves every
    request token-exactly once pages free up."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(31)
    max_len, ps = 32, 8
    reqs = [Request(rid=i, arrival=0, prompt=_prompt(rng, cfg, 10), max_new=4)
            for i in range(4)]
    with jax.set_mesh(mesh):
        want = {r.rid: sequential_decode(cfg, params, r.prompt, r.max_new,
                                         max_len) for r in reqs}
        # 2 pages/slot needed (10 prompt + 3 decode = 13 positions); grant
        # 5 allocatable pages so at most two slots hold pages at once even
        # though the engine has 4 slots.
        engine = ServeEngine(cfg, params, slots=4, max_len=max_len,
                             paged=True, page_size=ps, num_pages=6)
        finished = engine.run(list(reqs), log=None)
    assert len(finished) == 4
    for r in finished:
        assert r.out == want[r.rid]
    # deferrals really happened: later rids were admitted strictly later
    admits = {r.rid: r.admitted_at for r in finished}
    assert admits[3] > admits[0]


def test_paged_resident_cache_reduction():
    """The memory claim at skewed occupancy: short prompts in a
    long-max_len engine leave dense slots almost empty while the paged
    pool only holds the pages actually written — >= 4x fewer resident
    bytes on this workload (the serve_bench CI gate measures the same
    ratio on the full stream mix)."""
    cfg, params, mesh = _setup()
    rng = np.random.default_rng(41)
    reqs = lambda: [Request(rid=i, arrival=i, prompt=_prompt(rng, cfg, 6),
                            max_new=4) for i in range(6)]
    with jax.set_mesh(mesh):
        dense = ServeEngine(cfg, params, slots=4, max_len=128)
        dense.run(reqs(), log=None)
        paged = ServeEngine(cfg, params, slots=4, max_len=128,
                            paged=True, page_size=16)
        paged.run(reqs(), log=None)
    dense_bytes = dense.resident_cache_bytes()
    paged_bytes = paged.resident_cache_bytes(peak=True)
    assert paged_bytes > 0
    assert dense_bytes >= 4 * paged_bytes, (dense_bytes, paged_bytes)


def test_paged_rejects_unpageable_archs():
    cfg = registry.get_smoke_config("mamba2-370m")
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(0))
        with pytest.raises(ValueError, match="paged"):
            ServeEngine(cfg, params, slots=1, max_len=16, paged=True)


# ---------------------------------------------------------------------------
# Vectorized-pos decode step (the kernel of the per-slot path).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ring", [False, True])
def test_vectorized_pos_decode_matches_scalar(ring):
    """``Transformer.decode_step`` with pos (B,) must equal B scalar-pos
    calls on singleton batches — per-row cache writes, masks, and RoPE."""
    cfg, params, mesh = _setup(ring=ring)
    b, max_len = 3, 24
    positions = [2, 5, 9]
    key = jax.random.key(21)
    toks = jax.random.randint(key, (b, 1), 0, cfg.vocab_size - 1)
    with jax.set_mesh(mesh):
        caches = Transformer.init_cache(cfg, b, max_len)
        # seed caches with random (but shared) content so masks matter
        caches = jax.tree.map(
            lambda c: jax.random.normal(key, c.shape, c.dtype) * 0.1
            if jnp.issubdtype(c.dtype, jnp.floating) else c, caches)
        lg_vec, cache_vec = Transformer.decode_step(
            cfg, params, caches, toks, jnp.asarray(positions, jnp.int32))
        for i, p in enumerate(positions):
            # slice row i out of the batched cache (batch axis differs by subtree)
            def srow(tree, ax):
                return jax.tree.map(lambda c: jax.lax.slice_in_dim(c, i, i + 1,
                                                                   axis=ax), tree)
            row = {k: srow(v, 1 if k == "blocks" else 0)
                   for k, v in caches.items()}
            lg_one, _ = Transformer.decode_step(cfg, params, row,
                                                toks[i:i + 1], jnp.int32(p))
            # batch-1 vs batch-3 XLA fusion differs in the last ulp; the
            # comparison is mask/position correctness, not fusion order
            np.testing.assert_allclose(np.asarray(lg_vec[i]),
                                       np.asarray(lg_one[0]),
                                       rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Host-loop counters and lifecycle stamps.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True])
def test_counters_and_stamps_account_for_every_request(paged):
    """The engine's counters add up to what its requests record: every
    request admitted once, its suffix tokens prefilled, one decode row per
    token after the first, on the paged engine the pages those rows
    attended; ``t_enqueue <= t_admit <= t_first <= t_done``.
    ``prefix_stats`` is the prefix part of ``counters``; ``reset`` zeroes
    them."""
    cfg, params, mesh = _setup()
    ps = 8
    reqs = build_stream("bursty", 8, vocab=cfg.vocab_size, seed=5,
                        prompt_max=20, out_max=6, shared_prefix=16)
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params, slots=3, max_len=64, paged=paged,
                             page_size=ps)
        finished = engine.run(reqs, log=None)
    c = engine.counters()
    assert len(finished) == len(reqs) == c["admitted"]
    assert 1 <= c["admissions"] <= c["admitted"]
    assert c["admit_tokens"] == sum(len(r.prompt) - r.prefix_pages * ps
                                    for r in finished)
    assert c["admit_tokens"] <= c["admit_rows"]
    assert c["decode_rows"] == sum(len(r.out) - 1 for r in finished)
    assert c["decode_rows"] <= c["decode_ticks"] * engine.slots
    # The decode token that makes out m long attends len(prompt) + m - 1
    # keys: ceil of that over the page size pages of K and of V a layer.
    pages = sum(-(-(len(r.prompt) + m - 1) // ps)
                for r in finished for m in range(2, len(r.out) + 1))
    assert c["decode_kv_pages"] == (pages if paged else 0)
    if paged:
        assert c["decode_kv_pages"] <= (c["decode_ticks"] * engine.slots
                                        * engine.pages_per_slot)
    assert c["decode_ticks"] <= engine.ticks
    for r in finished:
        assert 0 < r.t_enqueue <= r.t_admit <= r.t_first <= r.t_done
    assert engine.prefix_stats() == {k: c[k] for k in engine.prefix_stats()}
    assert (c["hits"] > 0) == paged
    engine.reset()
    assert not any(engine.counters().values())
