"""Method-as-strategy API — every FL variant behind one pluggable interface.

The paper's contribution ("bkd") is one point in a family of KD-based FL
methods (Wu et al. 2023; Mora & Bellavista 2022 taxonomize dozens).  Before
this module, adding a method meant editing hard-coded ``method ==`` branches
in the orchestrator, the Phase-2 engine, the LLM driver, and the benchmarks,
while FedAvg lived in a disconnected code path the orchestrator couldn't
run.  Here a method is a first-class, registrable object: subclass
:class:`DistillMethod`, decorate with :func:`register_method`, and the whole
stack — ``FederatedKD``, ``DistillEngine``, ``launch/train.py``,
``launch/sweep.py``, the benchmarks and their CLIs — picks it up by name.

Round lifecycle (all hooks optional; see docs/methods.md for the worked
"add your own method in one file" example):

    init_round      build the method-state pytree (and optionally replace
                    the student — FedDF inits from the teacher average)
    on_epoch_start  per-epoch Python-side state refresh (melting's re-clone)
    loss            compose the Eq. 3/4 terms from the engine-provided
                    student/teacher logits (jnp / pallas / topk backends)
    apply_aux_grads transform param grads + update the learned auxiliary
                    (FT's translator SGD) — only when ``learns_aux``
    post_step       traced per-step state update (EMA shadow)
    finalize        end-of-round state swap (EMA weights)
    distill_round   replace the whole gradient phase (FedAvg's averaging)
                    — only when ``full_round``

The method state is a plain dict pytree with three groups the engine treats
differently:

    "frozen"  epoch-constant broadcast inputs (the frozen buffer clone)
    "cache"   per-example arrays gathered with each step's batch indices
              (the ``bkd_cached`` logit cache)
    "step"    carried and updated through the ``lax.scan`` (EMA shadow,
              FT translator)

Built-in methods: the paper's ``kd``/``bkd``/``ema``/``melting``/``ft``,
the beyond-paper ``bkd_cached``, plus ``fedavg`` (parameter averaging run
under the same orchestrator/scheduler/metrics loop) and ``feddf`` (ensemble
distillation, Lin et al. 2020: student inits from the parameter average and
distills A_f with no CE or buffer term).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import distill
from repro.core.aggregation import average_params
from repro.core.buffer import precompute_logits

#: name -> DistillMethod subclass.  Populated by :func:`register_method`.
METHODS: dict = {}


def register_method(cls):
    """Class decorator: register ``cls`` under ``cls.name``.

    Rejects duplicate names — a third-party method that wants to replace a
    built-in must pick a new name (shadowing would silently change results).
    """
    name = cls.name
    if not name or not isinstance(name, str):
        raise ValueError(f"{cls.__name__} must define a non-empty string "
                         f"`name` class attribute")
    if name in METHODS:
        raise ValueError(f"method {name!r} is already registered "
                         f"({METHODS[name].__name__}); duplicate names are "
                         f"rejected — pick a new one")
    METHODS[name] = cls
    return cls


def resolve_method(name: str) -> "DistillMethod":
    """Instantiate the registered method ``name`` (methods are stateless —
    all per-round state lives in the method-state pytree)."""
    if isinstance(name, DistillMethod):
        return name
    try:
        return METHODS[name]()
    except KeyError:
        raise ValueError(f"unknown method {name!r}; registered methods: "
                         f"{method_names()}") from None


def method_names() -> tuple:
    """Sorted registered method names (the CLI ``--method`` choices)."""
    return tuple(sorted(METHODS))


def validate_backend(method: str, backend: str, *, llm: bool = False):
    """Raise ``ValueError`` if ``backend`` can't drive ``method``.

    Used by the CLIs to reject bad ``--method``/``--loss-backend`` combos at
    argparse time instead of deep inside the engine.  ``llm=True`` checks
    the LLM driver's backend set (``launch/train.py``) instead of the
    CPU-scale engine's.
    """
    meth = resolve_method(method)
    allowed = meth.llm_backends if llm else ("auto",) + meth.supported_backends
    if backend not in allowed:
        raise ValueError(
            f"loss_backend {backend!r} is not supported by method "
            f"{method!r} (allowed: {tuple(allowed)})")


def empty_state() -> dict:
    """A method-state pytree with no frozen/cache/step components."""
    return {"frozen": None, "cache": None, "step": None}


@dataclasses.dataclass
class MethodContext:
    """Everything a method hook may need, bundled.

    ``adapter``/``cfg``/``backend`` are always set.  ``core_ds``,
    ``round_idx`` and ``teacher_weights`` (per-teacher shard sizes, for the
    averaging methods) are set for the round-level hooks (``init_round``,
    ``on_epoch_start``, ``finalize``, ``distill_round``) but not inside the
    traced step, where only static trace-time attributes may be read.
    """

    adapter: object
    cfg: object
    backend: str = "jnp"
    core_ds: object = None
    round_idx: int = 0
    teacher_weights: Optional[list] = None


def clip_grads(g, max_norm=5.0):
    """Global-norm clip for the simplified-FT factor loss (can spike through
    near-zero feature norms; FT is a comparison baseline, not the method)."""
    tot = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                       for l in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(tot, 1e-9))
    return jax.tree.map(lambda l: l * scale, g)


def kd_terms(ctx: MethodContext, lg, tls, bl, y):
    """Eq. 3 (+ the Eq. 4 buffer KL when ``bl`` is given), routed through
    the configured loss backend — the composition shared by the KD family."""
    tau = ctx.cfg.tau
    if ctx.backend == "pallas":
        from repro.kernels import ops
        if tls.shape[0] == 1:
            t_eff = tls[0]
        else:
            af = distill.ensemble_probs(tls, tau)
            t_eff = tau * jnp.log(jnp.maximum(af, 1e-30))
        return ops.kd_loss(y, lg, t_eff, bl, tau, use_pallas=True)
    loss = distill.l_kd(lg, tls, y, tau)
    if bl is not None:
        loss = loss + distill.kl_soft(lg, bl, tau)
    return loss


class DistillMethod:
    """Strategy protocol: one FL method's round lifecycle.

    Subclass, set ``name``, override the hooks the method needs, and
    decorate with :func:`register_method`.  Class attributes describe the
    method's capabilities so the engine and the CLIs can wire it without
    per-method branches.
    """

    #: Registry key and CLI ``--method`` choice.
    name: str = ""
    #: One-line description (docs tables, ``--help``).
    description: str = ""
    #: Loss backends the CPU-scale engine can run this method with
    #: ("auto" is always accepted and resolved against this set).
    supported_backends: tuple = ("jnp", "pallas")
    #: The method has a differentiable auxiliary (FT's translator) that is
    #: differentiated jointly with the student params.
    learns_aux: bool = False
    #: The method replaces the whole gradient phase (``distill_round``).
    full_round: bool = False

    # --- LLM-driver (launch/train.py) capability hints -------------------
    #: The distributed driver can run this method.  When False,
    #: ``llm_unsupported_reason`` says why (argparse error text).
    llm_driver: bool = True
    llm_unsupported_reason: str = ""
    #: ``--loss-backend`` choices valid on the LLM driver.
    llm_backends: tuple = ("auto", "jnp", "pallas")
    #: Phase-2 buffer wiring on the LLM driver:
    #: "none" | "clone" (frozen at round start) | "remelt" (re-cloned each
    #: step — the melting ablation at streaming scale).
    llm_buffer: str = "none"
    #: Weight on the CE term of the LLM chunked loss (FedDF: 0 — ensemble
    #: distillation uses no labels).
    llm_ce_weight: float = 1.0
    #: The driver maintains an EMA shadow over Phase-2 steps and swaps it in.
    llm_ema: bool = False
    #: The driver replaces Phase 2 with parameter averaging.
    llm_averaging: bool = False
    #: The driver re-inits the student from the teacher average before
    #: Phase 2 (FedDF).
    llm_init_from_avg: bool = False

    # --- round lifecycle -------------------------------------------------

    def init_round(self, ctx: MethodContext, state, teachers):
        """Start-of-round: return ``(state, method_state)``.  May replace
        ``state`` (FedDF inits the student from the teacher average)."""
        return state, empty_state()

    def on_epoch_start(self, ctx: MethodContext, state, mstate):
        """Python-side per-epoch refresh (melting re-clones its buffer)."""
        return mstate

    def loss(self, ctx: MethodContext, lg, tls, y, *, x, student_state,
             frozen, cache, learned, tstack):
        """Per-step loss from the engine-computed student logits ``lg`` and
        stacked teacher logits ``tls`` ``(R, B, V)``; ``frozen``/``cache``/
        ``learned`` are this method's state slices."""
        raise NotImplementedError

    def learned(self, step_state):
        """The differentiable part of the step state (``learns_aux`` only)."""
        return None

    def wants_aux(self, adapter) -> bool:
        """Whether the joint (params, aux) grad path applies for this
        adapter (trace-time; FT degrades to plain KD without feature taps)."""
        return self.learns_aux

    def apply_aux_grads(self, ctx: MethodContext, grads, aux_grads,
                        step_state):
        """Transform the param grads / update the learned auxiliary from
        its grads (``learns_aux`` only).  Returns ``(grads, step_state)``."""
        return grads, step_state

    def post_step(self, ctx: MethodContext, step_state, new_params):
        """Traced per-step state update after the optimizer step (EMA)."""
        return step_state

    def finalize(self, ctx: MethodContext, state, mstate):
        """End-of-round: final state (EMA swaps in its shadow weights)."""
        return state

    def distill_round(self, ctx: MethodContext, state, teachers):
        """The whole Phase-2 for ``full_round`` methods (FedAvg)."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# The paper's methods + the beyond-paper cached buffer.
# ---------------------------------------------------------------------------


@register_method
class KD(DistillMethod):
    name = "kd"
    description = ("vanilla KD, Eq. 3 (= Lin et al. 2020 at R=1): CE + "
                   "tau^2 KL against the teacher ensemble A_f")
    llm_backends = ("auto", "jnp", "pallas", "topk_cached")

    def loss(self, ctx, lg, tls, y, *, x, student_state, frozen, cache,
             learned, tstack):
        return kd_terms(ctx, lg, tls, None, y)


@register_method
class BKD(DistillMethod):
    name = "bkd"
    description = ("buffered KD, Eq. 4 (the paper's contribution): Eq. 3 + "
                   "tau^2 KL against the frozen start-of-round clone F0")
    llm_buffer = "clone"
    llm_backends = ("auto", "jnp", "pallas", "topk_cached")

    def init_round(self, ctx, state, teachers):
        mstate = empty_state()
        mstate["frozen"] = jax.tree.map(lambda a: a, state)  # Fig. 3 clone
        return state, mstate

    def loss(self, ctx, lg, tls, y, *, x, student_state, frozen, cache,
             learned, tstack):
        bl = ctx.adapter.logits(frozen, x, False)[0]
        return kd_terms(ctx, lg, tls, bl, y)


@register_method
class Melting(BKD):
    name = "melting"
    description = ("ablation (Fig. 4): the buffer is re-cloned every epoch "
                   "— a melting buffer collapses BKD back toward KD")
    llm_buffer = "remelt"
    llm_backends = ("auto", "jnp", "pallas")

    def on_epoch_start(self, ctx, state, mstate):
        return dict(mstate, frozen=jax.tree.map(lambda a: a, state))


@register_method
class EMA(DistillMethod):
    name = "ema"
    description = ("EMA-of-weights baseline (Fig. 4a): plain KD while an "
                   "exponential moving average of the student is tracked "
                   "and swapped in at round end")

    def init_round(self, ctx, state, teachers):
        mstate = empty_state()
        mstate["step"] = ctx.adapter.params(state)
        return state, mstate

    def loss(self, ctx, lg, tls, y, *, x, student_state, frozen, cache,
             learned, tstack):
        return kd_terms(ctx, lg, tls, None, y)

    def post_step(self, ctx, step_state, new_params):
        return distill.ema_update(step_state, new_params, ctx.cfg.ema_decay)

    def finalize(self, ctx, state, mstate):
        return ctx.adapter.with_params(state, mstate["step"])

    llm_ema = True


@register_method
class FT(DistillMethod):
    name = "ft"
    description = ("Factor-Transfer+KD baseline (§4.1): KD plus a linear "
                   "translator trained by SGD inside the step to match "
                   "normalized teacher factors")
    learns_aux = True
    llm_driver = False
    llm_unsupported_reason = ("it needs penultimate-feature taps the "
                              "token-LM path does not expose")

    def init_round(self, ctx, state, teachers):
        mstate = empty_state()
        if ctx.adapter.features is not None:
            f = ctx.adapter.features(state, jnp.asarray(ctx.core_ds.x[:1]))
            mstate["step"] = jnp.eye(f.shape[-1], dtype=jnp.float32)
        return state, mstate

    def learned(self, step_state):
        return step_state

    def wants_aux(self, adapter):
        return adapter.features is not None

    def loss(self, ctx, lg, tls, y, *, x, student_state, frozen, cache,
             learned, tstack):
        loss = kd_terms(ctx, lg, tls, None, y)
        if learned is not None:
            fs = ctx.adapter.features(student_state, x)
            ft = ctx.adapter.features(jax.tree.map(lambda l: l[0], tstack), x)
            loss = loss + ctx.cfg.ft_weight * distill.factor_loss(fs, ft,
                                                                  learned)
        return loss

    def apply_aux_grads(self, ctx, grads, aux_grads, step_state):
        return clip_grads(grads), step_state - 0.01 * clip_grads(aux_grads)


@register_method
class BKDCached(DistillMethod):
    name = "bkd_cached"
    description = ("beyond-paper cached-logit buffer: F0 is frozen and the "
                   "core set static, so its logits are precomputed once — "
                   "mathematically identical to Eq. 4, no third forward")
    supported_backends = ("jnp", "pallas", "topk_cached")
    llm_buffer = "clone"  # LLM batches are resampled; cache lives in the loss
    llm_backends = ("auto", "jnp", "pallas", "topk_cached")

    def init_round(self, ctx, state, teachers):
        topk = ctx.cfg.cache_topk if ctx.backend == "topk_cached" else None
        cache = precompute_logits(ctx.adapter, state, ctx.core_ds, topk=topk)
        mstate = empty_state()
        mstate["cache"] = cache.lookup(slice(None))  # device-resident
        return state, mstate

    def loss(self, ctx, lg, tls, y, *, x, student_state, frozen, cache,
             learned, tstack):
        if ctx.backend == "topk_cached":
            tv, ti, tail = cache
            loss = distill.l_kd(lg, tls, y, ctx.cfg.tau)
            return loss + distill.topk_kl_cached(lg, tv, ti, tail,
                                                 ctx.cfg.tau)
        return kd_terms(ctx, lg, tls, cache, y)


# ---------------------------------------------------------------------------
# The parameter-averaging line, folded into the same loop.
# ---------------------------------------------------------------------------


@register_method
class FedAvgMethod(DistillMethod):
    name = "fedavg"
    description = ("FedAvg (McMahan et al. 2017) under the KD orchestrator: "
                   "the 'distill' phase is a shard-size-weighted parameter "
                   "average of the round's teachers — no gradient epochs")
    full_round = True
    llm_backends = ("auto",)
    llm_averaging = True

    def distill_round(self, ctx, state, teachers):
        params = [ctx.adapter.params(t) for t in teachers]
        avg = average_params(params, ctx.teacher_weights)
        return ctx.adapter.with_params(state, avg)


@register_method
class FedDF(DistillMethod):
    name = "feddf"
    description = ("FedDF ensemble distillation (Lin et al. 2020): student "
                   "inits from the teacher parameter average, then distills "
                   "A_f of the round's teachers — pure KL, no CE or buffer "
                   "term (meaningful at R>1)")
    supported_backends = ("jnp",)  # the fused kernel always includes CE
    # The LLM driver distills R=1 per round, where init-from-average makes
    # FedDF degenerate: KL(teacher || copy-of-teacher) has zero value and
    # zero gradient, so it would silently reproduce fedavg at full Phase-2
    # gradient cost.  Rejected there until that driver grows R>1 rounds.
    llm_driver = False
    llm_unsupported_reason = ("it is only meaningful at R>1 teachers per "
                              "round and the token-LM driver distills R=1 "
                              "(at R=1 it degenerates to fedavg at full "
                              "gradient cost)")
    llm_backends = ("auto", "jnp")
    llm_ce_weight = 0.0
    llm_init_from_avg = True

    def init_round(self, ctx, state, teachers):
        avg = average_params([ctx.adapter.params(t) for t in teachers],
                             ctx.teacher_weights)
        return ctx.adapter.with_params(state, avg), empty_state()

    def loss(self, ctx, lg, tls, y, *, x, student_state, frozen, cache,
             learned, tstack):
        tau = ctx.cfg.tau
        if tls.shape[0] == 1:
            return distill.kl_soft(lg, tls[0], tau)
        af = distill.ensemble_probs(tls, tau)
        return distill.kl_soft_vs_probs(lg, af, tau)
