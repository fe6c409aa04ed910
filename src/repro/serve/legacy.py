"""Frozen pre-refactor serving loop (verbatim from ``launch/serve.py`` and
``launch/steps.py`` as of commit 0514313) — do not modernize.

This is the parity baseline for the serving engine, the analogue of
``tests/test_method_parity.py``'s ``_RefDistillEngine``: the new engine's
continuous batching must be token-exact against this loop on request sets
where the loop is *correct* (position-homogeneous batches), and
``benchmarks/serve_bench.py`` measures the engine's speedup against it.

Known defects, kept on purpose (they are what the engine fixes and what the
regression tests pin down):

  * shared-``ptick`` decode: every tick attends with ``max(pos)`` across
    slots, so a lagging slot's mask admits cache entries it should not —
    wrong tokens whenever active slots sit at different positions;
  * ``max_new=1`` emits 2 tokens (one decode tick runs before the
    ``budget <= 0`` check);
  * one host round-trip per slot per tick (``int(tokens[s, 0])``) and one
    prefill retrace per distinct prompt length;
  * ``prefill_into``'s per-slot cache write (``batched.at[slot]``) indexes
    the LEADING cache axis — for scanned layer stacks that is the *layer*
    axis (n_super, S, W, N, D), not the batch axis, so on any stacked
    config the admitted cache is garbled and decode diverges from
    sequential decoding even for a single request in a single slot.  The
    loop is only token-correct on unstacked (tail-only) configs; parity
    tests run it there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.transformer import Transformer


def legacy_serve_step(cfg):
    """Verbatim pre-refactor ``make_serve_step``: scalar ``pos`` for the
    whole batch."""

    def step(params, cache, token, pos):
        logits, new_cache = Transformer.decode_step(cfg, params, cache, token, pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        return nxt, new_cache

    return step


def simulate(cfg, params, requests, slots, max_len, mesh, log=print):
    """Slot-based continuous batching: one decode tick per step."""
    serve = jax.jit(legacy_serve_step(cfg))
    active = [None] * slots          # slot -> Request
    pos = [0] * slots                # per-slot decode position
    budget = [0] * slots
    queue = sorted(requests, key=lambda r: r.arrival)
    finished = []
    tokens = jnp.zeros((slots, 1), jnp.int32)
    caches = Transformer.init_cache(cfg, slots, max_len)
    step = 0

    def prefill_into(slot, req):
        """Single-sequence prefill written into the batched cache at `slot`.

        The first generated token comes from the prefill's own last-position
        logits — prefill already runs the full prompt forward, so admission
        costs exactly one prompt-length forward (it used to run a second
        full-prompt `Transformer.apply` just to pick this token: 2x prompt
        FLOPs per admission)."""
        nonlocal caches, tokens
        toks = jnp.asarray(req.prompt)[None, :]
        lg, c1 = Transformer.prefill(cfg, params, {"tokens": toks}, max_len)
        nxt = int(jnp.argmax(lg[0, -1]))

        def put(batched, single):
            return batched.at[slot].set(single[0].astype(batched.dtype))

        caches = jax.tree.map(put, caches, c1)
        tokens = tokens.at[slot, 0].set(nxt)
        req.out.append(nxt)
        return len(req.prompt)

    with jax.set_mesh(mesh):
        while queue or any(a is not None for a in active):
            # admit arrivals into free slots
            for s in range(slots):
                if active[s] is None and queue and queue[0].arrival <= step:
                    req = queue.pop(0)
                    plen = prefill_into(s, req)
                    active[s], pos[s], budget[s] = req, plen, req.max_new - 1
                    log(f"[t={step}] admit r{req.rid} -> slot {s} (prompt {plen})")
            if all(a is None for a in active):
                step += 1
                continue
            # one decode tick for the whole batch
            ptick = max(p if a is not None else 0
                        for p, a in zip(pos, active))
            tokens, caches = serve(params, caches, tokens, jnp.int32(ptick))
            for s in range(slots):
                if active[s] is None:
                    continue
                active[s].out.append(int(tokens[s, 0]))
                pos[s] += 1
                budget[s] -= 1
                if budget[s] <= 0 or pos[s] >= max_len - 1:
                    active[s].done_at = step
                    finished.append(active[s])
                    log(f"[t={step}] finish r{active[s].rid} "
                        f"({len(active[s].out)} tokens)")
                    active[s] = None
            step += 1
    return finished
