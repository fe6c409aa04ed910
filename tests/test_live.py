"""Live co-scheduled system lockdown (repro.live + the step-iterator re-cut).

Four contracts from the PR's acceptance criteria:

* **Stepper bit-identity** — a :class:`repro.core.distill_engine.RoundStepper`
  driven in arbitrary microbatch quanta returns exactly what the monolithic
  ``DistillEngine.run`` epoch loop returns, and a quantum-driven
  :class:`repro.live.LiveTrainer` reproduces ``FederatedKD.run`` bit-for-bit
  (state *and* recorded history), withdraw rounds included.
* **Swap atomicity** — a property sweep interleaving ``hot_swap`` at *every*
  tick offset of a serving run: each emitted token must match a versioned
  sequential-decode oracle that picks the params active at that token's tick
  (cache carried across versions) — no torn reads, ever.
* **Warm steady state is zero-compile** — after a warm-up segment, distill
  microbatches, decode ticks, and hot-swaps run under the global
  ``trace_guard(max_compiles=0)`` sanitizer mode: nothing in the process may
  reach the compiler again.
* **Fused checkpoint equivalence** — save mid-round/mid-stream, restore into
  a freshly built system, resume: final core state, history, served tokens,
  clock, and swap log are bit-identical to an uninterrupted run.

Plus the ``ServeEngine.reset()`` regression the swap sweep relies on:
back-to-back sessions on one engine are bit-reproducible, RNG key stream and
swap counters included.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.fl import FederatedKD, FLConfig, mlp_adapter
from repro.data import Dataset, dirichlet_partition, make_synthetic_classification
from repro.launch.mesh import make_test_mesh
from repro.live import LiveSystem, LiveTrainer, lm_adapter, lm_fl_data
from repro.models.transformer import Transformer
from repro.serve import Request, ServeEngine, build_stream


def assert_tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Stepper / LiveTrainer bit-identity (MLP setting, as in test_distill_engine).
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    x, y = make_synthetic_classification(num_classes=6, dim=16, per_class=150,
                                         seed=0)
    xt, yt = x[:200], y[:200]
    xtr, ytr = x[200:], y[200:]
    parts = dirichlet_partition(ytr, 4, alpha=1.0, seed=1)
    core = Dataset(xtr[parts[0]], ytr[parts[0]])
    edges = [Dataset(xtr[p], ytr[p]) for p in parts[1:]]
    return mlp_adapter(16, 32, 6), core, edges, Dataset(xt, yt)


def _mk_fl(setup, **kw):
    adapter, core, edges, test = setup
    base = dict(num_edges=3, rounds=2, method="bkd", core_epochs=2,
                edge_epochs=2, kd_epochs=2, batch_size=64, seed=0)
    cfg = FLConfig(**{**base, **kw})
    return FederatedKD(adapter, cfg, core, edges, test)


@pytest.mark.parametrize("method", ["bkd", "ema"])
def test_round_stepper_chunked_bit_identity(setup, method):
    """Scanning idx[p:p+q] with the carry threaded across calls is one scan
    over the full schedule: any quantum (including one that straddles epoch
    boundaries) must reproduce the monolithic round bit-for-bit — for a
    frozen-buffer method and for one whose scan carry evolves (EMA)."""
    fl = _mk_fl(setup, method=method, rounds=1, kd_epochs=3)
    state = fl.pretrain_core(jax.random.key(0))
    teachers = fl.train_round_edges([state], [0], seed=fl.cfg.seed)
    ref = fl.distill_engine.run(state, teachers, 0)
    for quantum in (1, 2, 5):
        st = fl.distill_engine.stepper(state, teachers, 0)
        total = 0
        while not st.finished:
            total += st.step(quantum)
        assert total == st.steps_done
        assert st.step(quantum) == 0          # finished stepper is inert
        assert_tree_equal(st.result, ref)


@pytest.mark.parametrize("kw", [{}, {"straggler": "alternate",
                                     "withdraw": True}])
def test_live_trainer_matches_monolithic_run(setup, kw):
    """A LiveTrainer driven in small quanta ends bit-identical to
    ``FederatedKD.run`` — same final state, same recorded metrics — with and
    without withdraw (stepper-less) rounds in the stream."""
    fl_ref = _mk_fl(setup, **kw)
    state_ref, hist_ref = fl_ref.run(jax.random.key(0), log=None)
    for quantum in (1, 3):
        fl = _mk_fl(setup, **kw)
        trainer = LiveTrainer(fl, jax.random.key(0), log=None)
        while trainer.pending():
            trainer.step(quantum)
        assert trainer.rounds_done == fl.cfg.rounds
        assert_tree_equal(trainer.state, state_ref)
        assert [h.as_dict() for h in fl.history] == \
            [h.as_dict() for h in hist_ref]


# ---------------------------------------------------------------------------
# Swap atomicity: every tick offset vs a versioned frozen-weights oracle.
# ---------------------------------------------------------------------------


def _tail_only_setup():
    cfg = registry.get_smoke_config("granite-3-2b")
    cfg = dataclasses.replace(cfg, block_pattern=("attn",) * 3)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        params1, _ = Transformer.init(cfg, jax.random.key(0))
        params2, _ = Transformer.init(cfg, jax.random.key(1))
    return cfg, params1, params2, mesh


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size - 1, size=n)


def sequential_decode_versioned(cfg, params_at, ta, prompt, max_new, max_len):
    """Single-request greedy reference under a params *schedule*: token 0
    (prefill) and token 1 (same-iteration decode) use the version active at
    the admission tick ``ta``; token j >= 2 uses the version at tick
    ``ta + j - 1``.  The KV cache is carried across versions — exactly what
    an engine slot that lives through a hot-swap experiences."""
    toks = jax.numpy.asarray(prompt)[None, :]
    lg, cache = Transformer.prefill(cfg, params_at(ta), {"tokens": toks},
                                    max_len)
    out = [int(jax.numpy.argmax(lg[0, -1]))]
    pos, tick = len(prompt), ta
    while len(out) < max_new and pos < max_len - 1:
        tok = jax.numpy.asarray([[out[-1]]], jax.numpy.int32)
        lgs, cache = Transformer.decode_step(cfg, params_at(tick), cache, tok,
                                             jax.numpy.int32(pos))
        # reprolint: disable=R002 (reference decoder syncs per token by design)
        out.append(int(jax.numpy.argmax(lgs[0, -1])))
        pos += 1
        tick += 1
    return out


def test_hot_swap_atomic_at_every_tick_offset():
    """The property sweep: one serving schedule, a hot-swap committed before
    tick ``off`` for every ``off`` in [0, T] (T = swap never fires).  Every
    emitted token must match the versioned oracle — a single mixed-version
    tick anywhere would break token-exactness for its segment."""
    cfg, params1, params2, mesh = _tail_only_setup()
    rng = np.random.default_rng(3)
    p0, p1 = _prompt(rng, cfg, 6), _prompt(rng, cfg, 9)
    max_len = 32

    def mk_reqs():
        return [Request(rid=0, arrival=0, prompt=p0, max_new=6),
                Request(rid=1, arrival=2, prompt=p1, max_new=5)]

    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params1, slots=2, max_len=max_len)
        engine.run(mk_reqs(), log=None)
        total_ticks = engine.ticks          # invariant across offsets: the
        assert total_ticks > 2              # done conditions are budget/pos
        for off in range(total_ticks + 1):
            engine.reset()
            engine.params = params1
            engine.begin(mk_reqs(), log=None)
            while engine.pending():
                if engine.swaps == 0 and engine.ticks == off:
                    engine.hot_swap(params2)
                engine.tick()
            assert engine.swap_log == ([off] if off < total_ticks else [])
            assert len(engine._finished) == 2
            params_at = lambda t: params2 if (off < total_ticks
                                              and t >= off) else params1
            for r in engine._finished:
                want = sequential_decode_versioned(
                    cfg, params_at, r.admitted_at, r.prompt, r.max_new,
                    max_len)
                assert r.out == want, (
                    f"off={off} r{r.rid}: engine {r.out} != oracle {want}")


def test_paged_hot_swap_atomic_at_every_tick_offset():
    """The PR-9 sweep re-run on the paged engine, with the nasty case the
    dense sweep cannot express: the two requests SHARE a prompt prefix, so
    r0's admission registers prefix pages that r1 would hit — and for swap
    offsets landing between the two admissions, those cached pages hold
    OLD-params K/V when r1 arrives under the new params.  ``commit_swap``
    must invalidate the prefix map (epoch bump) or r1's tokens diverge
    from the versioned oracle."""
    cfg, params1, params2, mesh = _tail_only_setup()
    rng = np.random.default_rng(3)
    shared = _prompt(rng, cfg, 10)            # > page_size: 1 full page
    p0 = np.concatenate([shared, _prompt(rng, cfg, 3)])
    p1 = np.concatenate([shared, _prompt(rng, cfg, 5)])
    max_len = 32

    def mk_reqs():
        return [Request(rid=0, arrival=0, prompt=p0, max_new=6),
                Request(rid=1, arrival=2, prompt=p1, max_new=5)]

    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params1, slots=2, max_len=max_len,
                             paged=True, page_size=8)
        engine.run(mk_reqs(), log=None)
        total_ticks = engine.ticks
        assert total_ticks > 2
        assert engine.prefix_stats()["hits"] == 1     # r1 hit r0's page
        for off in range(total_ticks + 1):
            engine.reset()
            engine.params = params1
            engine.begin(mk_reqs(), log=None)
            while engine.pending():
                if engine.swaps == 0 and engine.ticks == off:
                    engine.hot_swap(params2)
                engine.tick()
            assert engine.swap_log == ([off] if off < total_ticks else [])
            assert len(engine._finished) == 2
            params_at = lambda t: params2 if (off < total_ticks
                                              and t >= off) else params1
            for r in engine._finished:
                want = sequential_decode_versioned(
                    cfg, params_at, r.admitted_at, r.prompt, r.max_new,
                    max_len)
                assert r.out == want, (
                    f"off={off} r{r.rid}: paged {r.out} != oracle {want}")
            # A swap committed after r0 registered its prefix page but
            # before r1's admission makes that page stale -> r1 must miss.
            # Outside that window (swap before r0's admission, after r1's,
            # or never) the hit is legitimate and must survive.
            r0 = next(r for r in engine._finished if r.rid == 0)
            r1 = next(r for r in engine._finished if r.rid == 1)
            stale = (off < total_ticks
                     and r0.admitted_at < off <= r1.admitted_at)
            assert r1.prefix_pages == (0 if stale else 1), (
                f"off={off}: prefix_pages {r1.prefix_pages}, stale={stale}")


def test_paged_engine_carry_restore_resume_bit_identical(tmp_path):
    """Engine-level fused-checkpoint equivalence on the paged path: save
    mid-stream (live page tables, allocator books, in-flight admissions),
    restore into a freshly built engine, resume — every served token, the
    clock, and the allocator snapshot must match an uninterrupted run."""
    from repro.checkpoint import io
    cfg, params1, _, mesh = _tail_only_setup()
    path = str(tmp_path / "paged_engine.npz")
    mk_reqs = lambda: build_stream("bursty", 8, vocab=cfg.vocab_size, seed=13,
                                   prompt_max=18, out_max=6, shared_prefix=10)

    def mk_engine():
        return ServeEngine(cfg, params1, slots=2, max_len=64,
                           paged=True, page_size=8)

    with jax.set_mesh(mesh):
        eng_a = mk_engine()
        done_a = eng_a.run(mk_reqs(), log=None)

        eng_b = mk_engine()
        eng_b.begin(mk_reqs(), log=None)
        # advance to a genuinely mid-stream point: in-flight slots (live
        # page tables + admissions) AND requests still queued
        while not (any(r is not None for r in eng_b._host_active)
                   and eng_b._queue):
            eng_b.tick()
            assert eng_b.pending(), "stream drained before a save point"
        tree, meta = eng_b.carry()
        io.save_tree(path, {"engine": tree}, meta)

        eng_c = mk_engine()
        reqs_c = mk_reqs()
        eng_c.restore(path, meta, reqs_c)
        while eng_c.pending():
            eng_c.tick()
    assert {r.rid: r.out for r in eng_c._finished} == \
        {r.rid: r.out for r in done_a}
    assert eng_c.ticks == eng_a.ticks
    assert eng_c._alloc.snapshot() == eng_a._alloc.snapshot()
    assert np.array_equal(eng_c._pt_host, eng_a._pt_host)


def test_commit_swap_requires_stage():
    cfg, params1, _, mesh = _tail_only_setup()
    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params1, slots=1, max_len=16)
        with pytest.raises(RuntimeError, match="stage_params"):
            engine.commit_swap()


def test_engine_reset_bit_reproducible():
    """Back-to-back sessions on one engine — stochastic sampling, a mid-run
    hot-swap — must be bit-reproducible after ``reset()``: RNG key stream,
    clock, and swap counters all restart (the swap sweep above reuses one
    engine per offset on the strength of this)."""
    cfg, params1, params2, mesh = _tail_only_setup()
    rng = np.random.default_rng(7)
    p0, p1 = _prompt(rng, cfg, 5), _prompt(rng, cfg, 8)

    def mk_reqs():
        return [Request(rid=0, arrival=0, prompt=p0, max_new=5),
                Request(rid=1, arrival=1, prompt=p1, max_new=4)]

    with jax.set_mesh(mesh):
        engine = ServeEngine(cfg, params1, slots=2, max_len=32,
                             sample="topk", temperature=0.7, top_k=4, seed=11)

        def session():
            engine.begin(mk_reqs(), log=None)
            while engine.pending():
                if engine.swaps == 0 and engine.ticks == 2:
                    engine.hot_swap(params2)
                engine.tick()
            return ({r.rid: list(r.out) for r in engine._finished},
                    engine.ticks, list(engine.swap_log))

        first = session()
        engine.reset()
        assert engine.ticks == 0 and engine.swaps == 0
        assert engine.swap_log == [] and not engine.pending()
        engine.params = params1
        assert first == session()


# ---------------------------------------------------------------------------
# The co-scheduled live system (LM end-to-end): zero-compile steady state
# and fused-checkpoint equivalence.
# ---------------------------------------------------------------------------


def _lm_setup(rounds):
    cfg = registry.get_smoke_config("granite-3-2b")
    cfg = dataclasses.replace(cfg, block_pattern=("attn",) * 3)
    core, edges, test, _ = lm_fl_data(cfg, num_edges=2, seq_len=8, n_seqs=96,
                                      seed=0)
    flcfg = FLConfig(num_edges=2, rounds=rounds, method="bkd", core_epochs=1,
                     edge_epochs=1, kd_epochs=2, batch_size=8, seed=0)
    return cfg, flcfg, core, edges, test


def _mk_system(cfg, flcfg, core, edges, test):
    fl = FederatedKD(lm_adapter(cfg), flcfg, core, edges, test)
    trainer = LiveTrainer(fl, jax.random.key(0), log=None)
    engine = ServeEngine(cfg, trainer.state, slots=2, max_len=32)
    return LiveSystem(trainer, engine, quantum=1)


def _lm_stream(cfg, seed=3):
    return build_stream("poisson", 5, vocab=cfg.vocab_size, seed=seed,
                        prompt_max=10, out_max=4)


def test_warm_coscheduler_steady_state_zero_compile(trace_guard):
    """After a warm-up segment (two full rounds covering both edges'
    Phase-1 shapes, both chunk shapes of the quantum'd epoch scan, the
    stream's prefill buckets, and a committed hot-swap), the remaining
    rounds + a second identical stream must run without a single backend
    compile — distill microbatch, decode tick, and hot-swap included."""
    cfg, flcfg, core, edges, test = _lm_setup(rounds=4)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        system = _mk_system(cfg, flcfg, core, edges, test)
        eng, trainer = system.engine, system.trainer
        eng.begin(_lm_stream(cfg), log=None)
        while eng.pending() or trainer.rounds_done < 2:
            if eng.pending():
                eng.tick()
            if trainer.pending() and trainer.rounds_done < 2:
                system._train_quantum()
        assert trainer.rounds_done == 2 and trainer.pending()
        assert eng.swaps == 2               # warm-up committed real swaps
        with trace_guard(max_compiles=0):
            eng.begin(_lm_stream(cfg), log=None)    # same stream, rebased
            while eng.pending() or trainer.pending():
                if eng.pending():
                    eng.tick()
                if trainer.pending():
                    system._train_quantum()
        assert trainer.rounds_done == 4
        assert eng.swaps == 4


def test_live_checkpoint_save_restore_resume(tmp_path):
    """Fused-state equivalence: run A straight; run B to a mid-round,
    mid-epoch, mid-stream point and save; restore into a freshly built
    system C and resume.  C must end bit-identical to A — core state,
    history, every served token, the shared clock, and the swap log."""
    cfg, flcfg, core, edges, test = _lm_setup(rounds=2)
    mesh = make_test_mesh()
    path = str(tmp_path / "live.npz")
    with jax.set_mesh(mesh):
        sys_a = _mk_system(cfg, flcfg, core, edges, test)
        done_a = sys_a.run(_lm_stream(cfg), log=None)

        sys_b = _mk_system(cfg, flcfg, core, edges, test)
        eng_b, tr_b = sys_b.engine, sys_b.trainer
        eng_b.begin(_lm_stream(cfg), log=None)
        saved = False
        while eng_b.pending() or tr_b.pending():
            if eng_b.pending():
                eng_b.tick()
            if tr_b.pending():
                sys_b._train_quantum()
            st = tr_b._stepper
            if (tr_b.mid_round and st is not None and st.i > 0
                    and st._idx is not None):
                sys_b.save(path)
                saved = True
                break
        assert saved, "schedule too short to hit a mid-epoch save point"

        sys_c = _mk_system(cfg, flcfg, core, edges, test)
        reqs_c = _lm_stream(cfg)
        sys_c.restore(path, reqs_c)
        done_c = sys_c.run(reqs_c, log=None, resume=True)

    assert_tree_equal(sys_c.trainer.state, sys_a.trainer.state)
    assert [h.as_dict() for h in sys_c.trainer.fl.history] == \
        [h.as_dict() for h in sys_a.trainer.fl.history]
    assert {r.rid: r.out for r in done_c} == {r.rid: r.out for r in done_a}
    assert sys_c.engine.ticks == sys_a.engine.ticks
    assert sys_c.engine.swap_log == sys_a.engine.swap_log
    assert sys_c.swap_records == sys_a.swap_records
    assert sys_c.trainer.fl.distill_engine.uplink_log == \
        sys_a.trainer.fl.distill_engine.uplink_log
