"""Distributed step functions — the paper's Phase-2 as a pjit workload.

`make_phase2_step` builds the buffered-KD training step: student fwd+bwd,
frozen teacher + frozen buffer forwards, chunked big-vocab loss (Eqs. 3/4).
`buffer_mode`:
    "clone"   faithful paper setup — the frozen clone does a third forward
    "cached"  beyond-paper — precomputed buffer logits enter as an input
              (top-k compressed); exact for a static core set
    "none"    plain KD (the Lin et al. baseline / ablation)
`ce_weight` scales (or, at 0, drops) the CE term — FedDF's label-free
ensemble distillation; the DistillMethod registry's LLM hints
(`llm_buffer` / `llm_ce_weight`) pick these knobs per method.

The step's parts run under named scopes (``jax.named_scope``: HLO
metadata only, the compiled ops are the same), so a device trace can put
each op down to its part: ``p2_student`` (the student's forward, and its
backward and remat recompute, which carry the scope inside
``transpose(jvp(...))``), ``p2_teacher``, ``p2_buffer``, ``p2_kd_loss``
(the chunked loss, logits included) and ``p2_adamw``.

`make_pretrain_step` is Phase 0/1 (plain CE).  The inference steps
(`make_serve_step` / `make_prefill_step`) moved to `repro.serve.engine`
with the serving subsystem; they are re-exported here for the dry-run and
example callers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import distill
from repro.models.transformer import LMConfig, Transformer
from repro.sharding.rules import constrain


def _chunked_bkd_loss(cfg: LMConfig, student, teacher, buffer_params, batch,
                      h_s, h_t, h_b, tau, chunk, cached_buffer_logits=None,
                      topk=None, loss_backend="jnp", ce_weight=1.0,
                      teacher_transform=None):
    """Loss over sequence chunks so the three (B, chunk, V) logit tensors are
    the only full-vocab live values (jnp analogue of the fused Pallas
    kernel's streaming).  ``loss_backend="pallas"`` evaluates each chunk's
    CE + KL (+ clone-buffer KL) with the fused one-pass kernel
    (``repro.kernels.ops.kd_loss``; interpret mode off TPU) — used when the
    chunk has no token mask and no top-k approximation is requested."""
    b, s, d = h_s.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    nc = s // chunk
    labels = batch["labels"]
    mask = batch.get("mask")
    vocab = cfg.vocab_size

    def from_hidden(params, h):
        return Transformer.logits_from_hidden(cfg, params, h)

    def one(idx):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, idx * chunk, chunk, axis=1)
        ls = from_hidden(student, sl(h_s))
        y = sl(labels)
        m = sl(mask).astype(jnp.float32) if mask is not None else None
        lt = jax.lax.stop_gradient(from_hidden(teacher, sl(h_t)))
        if teacher_transform is not None:
            # Uplink transport (repro/transport): the student distills what
            # the wire delivered, not the raw teacher logits.  The transform
            # is a pure jnp value map, so both loss backends see it.
            lt = teacher_transform(lt, ls)
        if loss_backend == "pallas" and m is not None:
            # Trace-time (once per compilation), not per step: the fused
            # kernel has no token-mask support, so masked batches take the
            # jnp path — say so rather than silently mislabeling the run.
            import warnings
            warnings.warn("loss_backend='pallas' ignored for masked batches; "
                          "using the jnp chunked loss")
        if loss_backend == "pallas" and m is None and not topk:
            from repro.kernels import ops
            lb2 = None
            if h_b is not None:
                lb2 = jax.lax.stop_gradient(from_hidden(buffer_params, sl(h_b)))
                lb2 = distill._mask_pad(lb2.reshape(-1, lb2.shape[-1]), vocab)
            flat = lambda a: distill._mask_pad(a.reshape(-1, a.shape[-1]), vocab)
            loss = ops.kd_loss(y.reshape(-1), flat(ls), flat(lt), lb2, tau,
                               use_pallas=True)
            if cached_buffer_logits is not None:
                c = cached_buffer_logits
                loss = loss + distill.topk_kl_cached(
                    ls, sl(c["top_vals"]), sl(c["top_idx"]), sl(c["tail_lse"]),
                    tau, vocab=vocab)
            return loss
        # ce_weight=1 keeps the traced graph unchanged; 0 skips the CE
        # computation entirely at trace time (FedDF's label-free ensemble
        # distillation pays no full-vocab logsumexp for a zeroed term).
        if ce_weight == 1.0:
            loss = distill.ce_loss(ls, y, vocab=vocab, mask=m)
        elif ce_weight:
            loss = ce_weight * distill.ce_loss(ls, y, vocab=vocab, mask=m)
        else:
            loss = jnp.float32(0.0)
        if topk:
            loss = loss + distill.topk_kl(ls, lt, tau, topk, vocab=vocab, mask=m)
        else:
            loss = loss + distill.kl_soft(ls, lt, tau, vocab=vocab, mask=m)
        if h_b is not None:
            lb = jax.lax.stop_gradient(from_hidden(buffer_params, sl(h_b)))
            if topk:
                loss = loss + distill.topk_kl(ls, lb, tau, topk, vocab=vocab, mask=m)
            else:
                loss = loss + distill.kl_soft(ls, lb, tau, vocab=vocab, mask=m)
        elif cached_buffer_logits is not None:
            c = cached_buffer_logits
            loss = loss + distill.topk_kl_cached(
                ls, sl(c["top_vals"]), sl(c["top_idx"]), sl(c["tail_lse"]),
                tau, vocab=vocab, mask=m)
        return loss

    if nc == 1:
        return one(0)
    losses = jax.lax.map(jax.checkpoint(one), jnp.arange(nc))
    return jnp.mean(losses)


def make_phase2_step(cfg: LMConfig, opt, *, tau=2.0, buffer_mode="clone",
                     loss_chunk=512, aux_weight=0.01, topk=None,
                     loss_backend="auto", ce_weight=1.0,
                     teacher_transform=None):
    assert buffer_mode in ("clone", "cached", "none")
    assert loss_backend in ("auto", "jnp", "pallas")
    if loss_backend == "auto":
        from repro.kernels import ops
        loss_backend = "pallas" if ops.default_use_pallas() else "jnp"
    elif loss_backend == "pallas" and topk:
        import warnings
        warnings.warn("loss_backend='pallas' ignored: topk is set, so the "
                      "chunked jnp top-k loss is used instead")
        loss_backend = "jnp"
    if loss_backend == "pallas" and ce_weight != 1.0:
        # The fused kernel computes CE+KL in one pass; a weighted CE term
        # (FedDF's ce_weight=0) needs the chunked jnp composition.
        import warnings
        warnings.warn("loss_backend='pallas' ignored: ce_weight != 1, so "
                      "the chunked jnp loss is used instead")
        loss_backend = "jnp"

    def step(student, teacher, buffer_arg, opt_state, batch, step_idx):
        """buffer_arg: buffer params ("clone"), cached logits (B,S,Vtop?)
        ("cached"), or ignored ("none")."""

        def loss_fn(params):
            with jax.named_scope("p2_student"):
                h_s, aux = Transformer.apply_hidden(cfg, params, batch)
            with jax.named_scope("p2_teacher"):
                h_t, _ = Transformer.apply_hidden(cfg, teacher, batch)
                h_t = jax.lax.stop_gradient(h_t)
            h_b = None
            cached = None
            if buffer_mode == "clone":
                with jax.named_scope("p2_buffer"):
                    h_b, _ = Transformer.apply_hidden(cfg, buffer_arg, batch)
                    h_b = jax.lax.stop_gradient(h_b)
            elif buffer_mode == "cached":
                cached = buffer_arg
            with jax.named_scope("p2_kd_loss"):
                loss = _chunked_bkd_loss(
                    cfg, params, teacher,
                    buffer_arg if buffer_mode == "clone" else None,
                    batch, h_s, h_t, h_b, tau, loss_chunk,
                    cached_buffer_logits=cached, topk=topk,
                    loss_backend=loss_backend, ce_weight=ce_weight,
                    teacher_transform=teacher_transform)
            return loss + aux_weight * aux, loss

        (total, kd_loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(student)
        with jax.named_scope("p2_adamw"):
            new_params, new_opt = opt.update(grads, opt_state, student,
                                             step_idx)
        return new_params, new_opt, {"loss": total, "kd_loss": kd_loss}

    return step


def make_pretrain_step(cfg: LMConfig, opt, *, loss_chunk=512, aux_weight=0.01):
    def step(params, opt_state, batch, step_idx):
        def loss_fn(p):
            h, aux = Transformer.apply_hidden(cfg, p, batch)
            b, s, d = h.shape
            chunk = min(loss_chunk, s)
            while s % chunk:
                chunk -= 1
            nc = s // chunk
            labels = batch["labels"]
            mask = batch.get("mask")

            def one(idx):
                sl = lambda t: jax.lax.dynamic_slice_in_dim(t, idx * chunk, chunk, 1)
                lg = Transformer.logits_from_hidden(cfg, p, sl(h))
                m = sl(mask).astype(jnp.float32) if mask is not None else None
                return distill.ce_loss(lg, sl(labels), vocab=cfg.vocab_size, mask=m)

            if nc == 1:
                loss = one(0)
            else:
                loss = jnp.mean(jax.lax.map(jax.checkpoint(one), jnp.arange(nc)))
            return loss + aux_weight * aux

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt = opt.update(grads, opt_state, params, step_idx)
        return new_params, new_opt, {"loss": loss}

    return step


# Inference steps live with the serving subsystem now (vectorized per-slot
# pos path included); re-exported for the dry-run / example callers.
from repro.serve.engine import make_prefill_step, make_serve_step  # noqa: E402,F401
