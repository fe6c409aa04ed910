"""Production serving engine: per-slot paged decode + bucketed batched prefill.

The pre-refactor loop (frozen in ``repro.serve.legacy``) had three scaling
defects the ROADMAP's "heavy traffic" north star cannot live with:

  1. **Shared decode position.**  It decoded the whole batch with one scalar
     ``ptick = max(pos)``, so a slot admitted later attended with a lagging
     slot's K/V masked *as if it sat at the batch maximum* — wrong tokens
     for every slot whose position trailed the max.  The engine keeps a
     per-slot ``pos: (S,)`` int32 vector ON DEVICE and threads it through
     one jitted decode tick; ``nn/attention.py``/``Transformer.decode_step``
     grew a vectorized-``pos`` path where each row writes its cache and
     computes its (ring) mask at its own position.
  2. **Per-slot host round-trips.**  ``int(tokens[s, 0])`` per slot per tick
     forced a device sync per slot.  The tick is a single jitted call with
     device-side sampling (argmax / temperature / top-k) and done-flag
     computation; the host pulls ``(emitted, done)`` once per tick.
  3. **One prefill trace per prompt length.**  Every distinct prompt length
     retraced the prefill executable.  Admission pads prompts to
     power-of-two length buckets at the full slot batch, bounding compiles
     to ``log2(max_prompt) + 1`` executables for the whole request stream
     (asserted by the compile-count test via jit cache-size inspection).

Off-by-one fixed relative to the legacy loop: a request with ``max_new=1``
emits exactly 1 token (the prefill token) — the legacy loop ran one decode
tick before its budget check and emitted 2.

Slot lifecycle: free -> (bucketed prefill writes cache/token/pos/budget,
first token emitted from the prefill's own last-real-position logits)
-> active decode ticks -> done (budget exhausted or ``pos == max_len - 1``)
-> free.  Inactive slots ride along in the batch with their state frozen
by ``where(active, ...)`` masks — their cache writes are idempotent junk at
a stale position that the next admission overwrites wholesale.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import LMConfig, Transformer
from repro.serve.paged import PageAllocator, Admission, TRASH_PAGE, pages_for
from repro.sharding.rules import constrain


# ---------------------------------------------------------------------------
# Step builders (the former launch/steps.py inference steps live here now).
# ---------------------------------------------------------------------------


def make_serve_step(cfg: LMConfig):
    """One greedy decode step: (params, cache, token, pos) ->
    (next_token, new_cache).  ``pos`` may be scalar (whole batch at one
    position) or (B,) (the engine's per-slot path)."""

    def step(params, cache, token, pos):
        logits, new_cache = Transformer.decode_step(cfg, params, cache, token, pos)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        return nxt, new_cache

    return step


def make_prefill_step(cfg: LMConfig, max_len):
    def step(params, batch):
        logits, cache = Transformer.prefill(cfg, params, batch, max_len)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        return nxt, cache

    return step


def make_sampler(sample: str = "greedy", temperature: float = 1.0,
                 top_k: int = 0):
    """Device-side token sampler over (B, V) logits.  ``greedy`` is exact
    argmax (the parity-tested default); ``topk`` masks to the top-k logits
    and draws categorically at ``temperature``."""
    if sample == "greedy":
        return lambda logits, key: jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if sample != "topk":
        raise ValueError(f"unknown sampler {sample!r}; known: greedy, topk")

    def sampler(logits, key):
        lg = logits / jnp.float32(max(temperature, 1e-6))
        if top_k:
            kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
            lg = jnp.where(lg < kth, -jnp.inf, lg)
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)

    return sampler


def bucket_length(n: int, *, minimum: int = 8) -> int:
    """Smallest power of two >= max(n, minimum) — the prefill length bucket."""
    b = minimum
    while b < n:
        b *= 2
    return b


def _merge_caches(old, new, fill):
    """Per-slot cache replacement: rows of ``new`` where ``fill`` (S,) bool,
    rows of ``old`` elsewhere.  The batch axis is axis 1 under the scanned
    "blocks" subtree (leading layers axis) and axis 0 for tail blocks."""

    def merge(o, n, batch_axis):
        shape = [1] * o.ndim
        shape[batch_axis] = fill.shape[0]
        return jnp.where(fill.reshape(shape), n.astype(o.dtype), o)

    out = {}
    for key in old:
        ax = 1 if key == "blocks" else 0
        out[key] = jax.tree.map(lambda o, n: merge(o, n, ax), old[key], new[key])
    return out


def make_admit_step(cfg: LMConfig, max_len: int, sampler, *, padded=True):
    """Bucketed batched admission: prefill (S, L) right-padded prompts and
    splice the filled slots' state in one jitted call.

    Returns state' = (tokens, caches, pos, budget, active) plus the first
    generated token per slot (from the prefill's own last-real-position
    logits — one prompt-length forward per admission, no second pass) and
    the slots already done at admission (``max_new == 1``, or a prompt that
    already reaches the ``max_len - 1`` truncation edge).

    ``padded=False`` (recurrent archs) admits exact-length groups: every
    filled row's prompt spans the whole (S, L) row, so the prefill needs —
    and recurrent state tolerates — no pad-awareness."""

    def admit(params, caches, tokens, pos, budget, active,
              prompts, lengths, max_news, fill, key):
        logits, new_caches = Transformer.prefill(
            cfg, params, {"tokens": prompts}, max_len,
            lengths=lengths if padded else None)
        rows = jnp.arange(prompts.shape[0])
        last = logits[rows, jnp.maximum(lengths - 1, 0)]         # (S, V)
        first = sampler(last, key)                               # (S,)
        caches = _merge_caches(caches, new_caches, fill)
        tokens = jnp.where(fill, first, tokens[:, 0])[:, None]
        pos = jnp.where(fill, lengths, pos)
        budget = jnp.where(fill, max_news - 1, budget)
        done_now = fill & ((budget <= 0) | (pos >= max_len - 1))
        active = (active | fill) & ~done_now
        return tokens, caches, pos, budget, active, first, done_now

    return admit


def make_init_state(cfg: LMConfig, slots: int, max_len: int):
    """Fresh slot state, built *inside* jit with the same logical-axis
    constraints the admission/tick steps apply, so its shardings match the
    steps' outputs under an active mesh.  (Host-built zeros carry plain
    single-device shardings; feeding them to the jitted steps once and
    their own outputs thereafter would compile every executable twice —
    the compile-count tests pin this.)"""

    def init():
        caches = Transformer.init_cache(cfg, slots, max_len)
        specs = Transformer.cache_specs(cfg)
        is_spec = lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
        caches = jax.tree.map(lambda s, c: constrain(c, s), specs, caches,
                              is_leaf=is_spec)
        tokens = constrain(jnp.zeros((slots, 1), jnp.int32), ("batch", None))
        pos = constrain(jnp.zeros((slots,), jnp.int32), ("batch",))
        budget = constrain(jnp.zeros((slots,), jnp.int32), ("batch",))
        active = constrain(jnp.zeros((slots,), bool), ("batch",))
        return tokens, caches, pos, budget, active

    return init


def make_decode_tick(cfg: LMConfig, max_len: int, sampler):
    """One continuous-batching decode tick over all S slots: vectorized-pos
    decode, device-side sampling, budget/done bookkeeping.  The host needs
    a single pull of (emitted, done) per tick."""

    def tick(params, caches, tokens, pos, budget, active, key):
        logits, caches = Transformer.decode_step(cfg, params, caches, tokens, pos)
        nxt = sampler(logits[:, -1, :], key)                     # (S,)
        act = active.astype(jnp.int32)
        emitted = jnp.where(active, nxt, tokens[:, 0])
        pos = pos + act
        budget = budget - act
        done = active & ((budget <= 0) | (pos >= max_len - 1))
        return emitted[:, None], caches, pos, budget, active & ~done, done

    return tick


def make_paged_admit_step(cfg: LMConfig, max_len: int, sampler, page_size: int):
    """Bucketed admission against the block-paged pool: one call admits a
    group of slots sharing a static prefix-hit depth ``npp`` (pages already
    resident from the prefix cache).  ``prompts`` holds the right-padded
    prompt *suffixes*; the prefill scatters their K/V into the slots'
    private pages and attends [shared prefix pages, suffix] at absolute
    positions.  ``npp == 0`` is the prefix-miss path — bit-identical math
    to :func:`make_admit_step`'s dense prefill."""

    def admit(params, caches, pt, tokens, pos, budget, active,
              prompts, lengths, max_news, fill, key, *, npp):
        b, length = prompts.shape
        start = npp * page_size
        positions = jnp.broadcast_to(
            start + jnp.arange(length, dtype=jnp.int32), (b, length))
        if cfg.rope == "mrope":
            positions = jnp.broadcast_to(positions[:, None, :], (b, 3, length))
        logits, caches = Transformer.paged_prefill(
            cfg, params, {"tokens": prompts, "positions": positions},
            caches, pt, lengths, fill, npp, page_size)
        rows = jnp.arange(b)
        last = logits[rows, jnp.maximum(lengths - 1, 0)]         # (S, V)
        first = sampler(last, key)                               # (S,)
        tokens = jnp.where(fill, first, tokens[:, 0])[:, None]
        pos = jnp.where(fill, start + lengths, pos)
        budget = jnp.where(fill, max_news - 1, budget)
        done_now = fill & ((budget <= 0) | (pos >= max_len - 1))
        active = (active | fill) & ~done_now
        return tokens, caches, pos, budget, active, first, done_now

    return admit


def make_paged_decode_tick(cfg: LMConfig, max_len: int, sampler,
                           page_size: int):
    """Paged twin of :func:`make_decode_tick`: same bookkeeping, with the
    page table threaded through and inactive rows' cache writes redirected
    to the trash page (a freed slot's stale table may alias pages since
    granted to another slot)."""

    def tick(params, caches, pt, tokens, pos, budget, active, key):
        logits, caches = Transformer.paged_decode_step(
            cfg, params, caches, pt, tokens, pos, active,
            page_size=page_size)
        nxt = sampler(logits[:, -1, :], key)                     # (S,)
        act = active.astype(jnp.int32)
        emitted = jnp.where(active, nxt, tokens[:, 0])
        pos = pos + act
        budget = budget - act
        done = active & ((budget <= 0) | (pos >= max_len - 1))
        return emitted[:, None], caches, pos, budget, active & ~done, done

    return tick


def make_paged_steps(cfg: LMConfig, max_len: int, sampler, page_size: int):
    """The paged engine's jitted (admission, decode tick).  The pools are
    donated: both write a few pages and hand the pools back, so XLA updates
    them in place instead of copying every layer's pool per call."""
    admit = jax.jit(make_paged_admit_step(cfg, max_len, sampler, page_size),
                    static_argnames=("npp",), donate_argnums=(1,))
    tick = jax.jit(make_paged_decode_tick(cfg, max_len, sampler, page_size),
                   donate_argnums=(1,))
    return admit, tick


def make_paged_init_state(cfg: LMConfig, slots: int, num_pages: int,
                          page_size: int, pages_per_slot: int):
    """Paged twin of :func:`make_init_state`: pools + page table instead of
    dense per-slot caches, with the same inside-jit sharding discipline."""

    def init():
        caches = Transformer.init_paged_cache(cfg, num_pages, page_size)
        specs = Transformer.paged_cache_specs(cfg)
        is_spec = lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
        caches = jax.tree.map(lambda s, c: constrain(c, s), specs, caches,
                              is_leaf=is_spec)
        pt = constrain(jnp.zeros((slots, pages_per_slot), jnp.int32),
                       ("batch", None))
        tokens = constrain(jnp.zeros((slots, 1), jnp.int32), ("batch", None))
        pos = constrain(jnp.zeros((slots,), jnp.int32), ("batch",))
        budget = constrain(jnp.zeros((slots,), jnp.int32), ("batch",))
        active = constrain(jnp.zeros((slots,), bool), ("batch",))
        return tokens, caches, pt, pos, budget, active

    return init


class ServeEngine:
    """Slot-based continuous batching with device-resident slot state.

    One engine owns S decode slots: per-slot caches, current token, position,
    and remaining budget all live on device; the host loop only (a) groups
    eligible arrivals into length buckets and calls the jitted admission
    step, and (b) calls the jitted decode tick and pulls (emitted, done)
    once.  ``simulate``-style usage::

        engine = ServeEngine(cfg, params, slots=4, max_len=96)
        finished = engine.run(build_stream("poisson", 16, vocab=cfg.vocab_size))
    """

    def __init__(self, cfg: LMConfig, params, *, slots: int, max_len: int,
                 sample: str = "greedy", temperature: float = 1.0,
                 top_k: int = 0, seed: int = 0, min_bucket: int = 8,
                 paged: bool = False, page_size: int = 16,
                 num_pages: int = None):
        if cfg.is_encoder:
            raise ValueError("encoder-only arch has no decode step")
        self.cfg, self.params = cfg, params
        self.slots, self.max_len = slots, max_len
        self.min_bucket = min_bucket
        sampler = make_sampler(sample, temperature, top_k)
        self._stochastic = sample != "greedy"
        self._seed = seed
        # Recurrent blocks need exact-length (unbucketed) prefill: padded
        # prompts would fold pad tokens into the carried state.
        self._bucketed = all(k in ("attn", "local") for k in cfg.block_pattern)
        self.paged = paged
        if paged:
            self.page_size = page_size
            self.pages_per_slot = pages_for(max_len, page_size)
            # +1 for the trash page: with every unreferenced cached prefix
            # evictable, the default pool can always grant what a free slot
            # needs — paged admission then never defers a request the dense
            # engine would admit (the scheduling half of the parity suite).
            self.num_pages = num_pages or slots * self.pages_per_slot + 1
            self._alloc = PageAllocator(self.num_pages, page_size)
            self._admit_fn, self._tick_fn = make_paged_steps(
                cfg, max_len, sampler, page_size)
            self._init_fn = jax.jit(make_paged_init_state(
                cfg, slots, self.num_pages, page_size, self.pages_per_slot))
        else:
            self._admit_fn = jax.jit(
                make_admit_step(cfg, max_len, sampler, padded=self._bucketed))
            self._tick_fn = jax.jit(make_decode_tick(cfg, max_len, sampler))
            self._init_fn = jax.jit(make_init_state(cfg, slots, max_len))
        self.reset()

    def reset(self):
        if self.paged:
            (self.tokens, self.caches, self.pt, self.pos, self.budget,
             self.active) = self._init_fn()
            self._alloc.reset()
            self._pt_host = np.zeros((self.slots, self.pages_per_slot),
                                     np.int32)
            self._slot_adm = [None] * self.slots  # slot -> Admission | None
        else:
            (self.tokens, self.caches, self.pos, self.budget,
             self.active) = self._init_fn()
        self._host_active = [None] * self.slots   # slot -> Request | None
        self.ticks = 0
        # restart the sampling stream too: a reset engine must reproduce a
        # fresh ServeEngine(seed=...) under stochastic sampling
        self._key = jax.random.key(self._seed)
        # hot-swap double buffer + counters: back-to-back runs on one
        # engine must be bit-reproducible (pinned by the reset regression
        # test), so the swap epoch restarts with the key stream.
        self._standby = None
        self.swaps = 0
        self.swap_log = []      # tick index of each committed swap
        # stream state owned by begin()/tick(); cleared so a stale queue
        # from an abandoned run cannot leak into the next one
        self._queue = []
        self._queue_total = 0
        self._finished = []
        self._base = 0
        self._now = 0
        self._log = None

    # -- hot swap ------------------------------------------------------------

    def stage_params(self, params):
        """Load ``params`` into the standby buffer (a ``device_put`` off the
        tick path).  The served params are untouched until
        :meth:`commit_swap` flips the pointer."""
        self._standby = jax.device_put(params)

    def commit_swap(self):
        """Atomically flip the served params to the staged buffer.

        Must be called *between* ticks: :meth:`tick` reads ``self.params``
        exactly once at entry, so every token of a tick — admission prefill
        and decode — sees one params version and no in-flight request ever
        observes a torn update (the swap-atomicity property test sweeps
        every tick offset against a frozen-weights oracle)."""
        if self._standby is None:
            raise RuntimeError("commit_swap() without stage_params()")
        self.params = self._standby
        self._standby = None
        self.swaps += 1
        self.swap_log.append(self.ticks)
        if self.paged:
            # Cached prefix K/V was computed under the old params; a hit
            # after the swap would hand a NEW admission OLD-weights state
            # and break the versioned swap oracle.  Drop the whole map
            # (pages pinned by in-flight slots live on, exactly like a
            # dense slot that decodes across a swap).
            self._alloc.bump_epoch()

    def hot_swap(self, params):
        """``stage_params`` + ``commit_swap`` in one call."""
        self.stage_params(params)
        self.commit_swap()

    def _bucket(self, prompt_len: int) -> int:
        """Pow2 length bucket, capped at max_len: prompts are checked to
        fit max_len, so the cap (at most one extra non-pow2 shape) keeps
        the padded prefill inside the cache budget."""
        return min(bucket_length(prompt_len, minimum=self.min_bucket),
                   self.max_len)

    def prefill_compile_count(self) -> int:
        """Distinct traced admission shapes — one per (length bucket,
        prefix-hit depth), so the compile-count test can assert
        <= log2(max_prompt) + 1 on a stream without shared prefixes."""
        return self._admit_fn._cache_size()

    # -- memory accounting ----------------------------------------------------

    def cache_page_bytes(self) -> int:
        """Bytes one physical page occupies summed over every layer's K and
        V pool (0 on the dense engine)."""
        if not self.paged:
            return 0
        leaves = jax.tree.leaves(self.caches)
        return sum(leaf.size // self.num_pages * leaf.dtype.itemsize
                   for leaf in leaves)

    def resident_cache_bytes(self, peak: bool = True) -> int:
        """KV-cache residency: the dense engine always holds its full
        ``slots x max_len`` allocation; the paged engine holds
        ``pages-in-use x page bytes`` (``peak=True`` reports the high-water
        mark — what a pool provisioned for this workload would need)."""
        if not self.paged:
            return sum(leaf.size * leaf.dtype.itemsize
                       for leaf in jax.tree.leaves(self.caches))
        pages = self._alloc.peak if peak else self._alloc.in_use
        return pages * self.cache_page_bytes()

    def prefix_stats(self) -> dict:
        """Prefix-cache counters (zeros on the dense engine)."""
        if not self.paged:
            return {"hits": 0, "misses": 0, "evictions": 0,
                    "peak_pages": 0, "pages_in_use": 0}
        a = self._alloc
        return {"hits": a.hits, "misses": a.misses, "evictions": a.evictions,
                "peak_pages": a.peak, "pages_in_use": a.in_use}

    def _next_key(self):
        if not self._stochastic:
            return self._key
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- admission ----------------------------------------------------------

    def _admit_group(self, params, group, now, log):
        """One batched admission: prompts right-padded to the group's
        largest length bucket at the full slot batch (exact length, and
        length-homogeneous, on the recurrent path).  ``params`` is the
        tick's single params snapshot — admission and decode within one
        tick always share a version."""
        s = self.slots
        length = (self._bucket(max(len(r.prompt) for _, r in group))
                  if self._bucketed else max(len(r.prompt) for _, r in group))
        prompts = np.zeros((s, length), np.int32)
        lengths = np.ones((s,), np.int32)
        max_news = np.ones((s,), np.int32)
        fill = np.zeros((s,), bool)
        for slot, req in group:
            plen = len(req.prompt)
            prompts[slot, :plen] = req.prompt
            lengths[slot], max_news[slot], fill[slot] = plen, req.max_new, True
        (self.tokens, self.caches, self.pos, self.budget, self.active,
         first, done_now) = self._admit_fn(
            params, self.caches, self.tokens, self.pos, self.budget,
            self.active, jnp.asarray(prompts), jnp.asarray(lengths),
            jnp.asarray(max_news), jnp.asarray(fill), self._next_key())
        self._post_admit(group, first, done_now, now, length, log)

    def _post_admit(self, group, first, done_now, now, length, log):
        """Shared admission epilogue: pull (first, done) once, stamp the
        requests, finish the already-done slots."""
        first_np, done_np = jax.device_get((first, done_now))
        t_wall = time.perf_counter()
        for slot, req in group:
            req.out.append(int(first_np[slot]))
            req.admitted_at = now
            req.t_first = t_wall
            self._host_active[slot] = req
            if log:
                log(f"[t={now}] admit r{req.rid} -> slot {slot} "
                    f"(prompt {len(req.prompt)} pad {length})")
            if done_np[slot]:
                self._finish(slot, now, t_wall, log)

    def _admit_paged(self, params, batch, now, log):
        """Paged admission: grant pages (consulting the prefix cache) per
        request, then run one batched prefill per prefix-hit depth —
        shallower groups first, so a same-tick deeper hit gathers pages a
        shallower admission's scatter just wrote.  If the pool cannot
        grant a request's pages even after eviction (only possible with an
        explicitly undersized pool), it and everything behind it requeue —
        FIFO order is preserved."""
        groups = {}
        requeue = []
        for idx, (slot, req) in enumerate(batch):
            total = min(len(req.prompt) + req.max_new - 1, self.max_len - 1)
            adm = self._alloc.admit(req.prompt, total)
            if adm is None:
                requeue = [r for _, r in batch[idx:]]
                break
            self._slot_adm[slot] = adm
            self._pt_host[slot, :] = TRASH_PAGE
            self._pt_host[slot, :len(adm.pages)] = adm.pages
            req.prefix_pages = adm.shared
            groups.setdefault(adm.shared, []).append((slot, req))
        if requeue:
            self._queue[:0] = requeue
        if not groups:
            return
        self.pt = jnp.asarray(self._pt_host)
        for npp in sorted(groups):
            self._admit_group_paged(params, groups[npp], npp, now, log)

    def _admit_group_paged(self, params, group, npp, now, log):
        """One batched paged admission at prefix-hit depth ``npp``: rows
        carry the prompt *suffixes* (everything past the shared pages),
        right-padded to the suffix length bucket."""
        s = self.slots
        start = npp * self.page_size
        length = self._bucket(max(len(r.prompt) - start for _, r in group))
        prompts = np.zeros((s, length), np.int32)
        lengths = np.ones((s,), np.int32)
        max_news = np.ones((s,), np.int32)
        fill = np.zeros((s,), bool)
        for slot, req in group:
            sl = len(req.prompt) - start
            prompts[slot, :sl] = req.prompt[start:]
            lengths[slot], max_news[slot], fill[slot] = sl, req.max_new, True
        (self.tokens, self.caches, self.pos, self.budget, self.active,
         first, done_now) = self._admit_fn(
            params, self.caches, self.pt, self.tokens, self.pos, self.budget,
            self.active, jnp.asarray(prompts), jnp.asarray(lengths),
            jnp.asarray(max_news), jnp.asarray(fill), self._next_key(),
            npp=npp)
        self._post_admit(group, first, done_now, now, length, log)

    def _finish(self, slot, now, t_wall, log):
        req = self._host_active[slot]
        req.done_at, req.t_done = now, t_wall
        self._host_active[slot] = None
        self._finished.append(req)
        if self.paged:
            # Drop the slot's page references; prefix-cached pages stay
            # resident at refcount zero for future hits.  The device page
            # table is refreshed at the next admission — until then the
            # stale row only backs trash-redirected writes and masked-out
            # reads of this now-inactive slot.
            self._alloc.release(self._slot_adm[slot])
            self._slot_adm[slot] = None
            self._pt_host[slot, :] = TRASH_PAGE
        if log:
            log(f"[t={now}] finish r{req.rid} ({len(req.out)} tokens)")

    # -- the loop -----------------------------------------------------------

    def begin(self, requests, log=None, rebase=True):
        """Open a serving session over ``requests`` without running it: the
        caller owns the outer loop and advances it one :meth:`tick` at a
        time (the live co-scheduler interleaves these with distill steps).

        Arrival ticks are relative to this ``begin`` by default: a warm
        engine (second session without ``reset``) rebases them onto its
        running clock, so the stream's arrival *process* is preserved
        instead of every request looking instantly overdue.
        ``rebase=False`` keeps absolute arrival ticks — the checkpoint
        restore path, where the clock itself is restored."""
        for r in requests:
            if len(r.prompt) >= self.max_len:
                raise ValueError(f"request {r.rid}: prompt length "
                                 f"{len(r.prompt)} >= max_len {self.max_len}")
        self._queue = sorted(requests, key=lambda r: (r.arrival, r.rid))
        self._queue_total = len(self._queue)
        self._finished = []
        self._base = self.ticks if rebase else 0
        self._now = self.ticks
        self._log = log

    def pending(self) -> bool:
        """True while the session begun by :meth:`begin` has queued or
        in-flight requests."""
        return bool(self._queue) or any(r is not None
                                        for r in self._host_active)

    @property
    def queue_cursor(self) -> int:
        """How many of the session's requests left the queue (admitted or
        finished) — the stream cursor the live checkpoint records."""
        return self._queue_total - len(self._queue)

    def tick(self):
        """Exactly one iteration of the serving loop: stamp newly-eligible
        arrivals, admit them into free slots, run one decode tick over all
        slots (skipped while none are active), advance the virtual clock.
        Returns the requests finished during this tick.

        ``self.params`` is read once at entry; :meth:`commit_swap` between
        ticks is therefore atomic — no tick mixes params versions."""
        params = self.params     # the tick's single params-version read
        log, now, base = self._log, self._now, self._base
        queue = self._queue
        n_done = len(self._finished)
        # Stamp queue-eligibility (TTFT clock starts here, not at
        # admission — queueing delay is part of time-to-first-token).
        t_wall = time.perf_counter()
        for r in queue:
            if r.arrival + base <= now and r.t_enqueue < 0:
                r.t_enqueue = t_wall
            elif r.arrival + base > now:
                break
        # Admit eligible arrivals into free slots, grouped by bucket.
        free = [s for s in range(self.slots)
                if self._host_active[s] is None]
        batch = []
        while free and queue and queue[0].arrival + base <= now:
            batch.append((free.pop(0), queue.pop(0)))
        if self.paged and batch:
            self._admit_paged(params, batch, now, log)
        elif self._bucketed and batch:
            # One admission per tick at the largest arrival's bucket:
            # padding is numerically invisible (lengths= masks it), so
            # splitting same-tick arrivals per bucket would only run
            # extra full-slot-batch prefills.
            self._admit_group(params, batch, now, log)
        else:
            # Recurrent (exact-length) admission: rows cannot be
            # padded, so groups must share one exact prompt length.
            groups = {}
            for slot, req in batch:
                groups.setdefault(len(req.prompt), []).append((slot, req))
            for _, group in sorted(groups.items()):
                self._admit_group(params, group, now, log)
        if any(r is not None for r in self._host_active):
            # One decode tick for every slot; one host sync.
            if self.paged:
                (self.tokens, self.caches, self.pos, self.budget,
                 self.active, done) = self._tick_fn(
                    params, self.caches, self.pt, self.tokens, self.pos,
                    self.budget, self.active, self._next_key())
            else:
                (self.tokens, self.caches, self.pos, self.budget,
                 self.active, done) = self._tick_fn(
                    params, self.caches, self.tokens, self.pos, self.budget,
                    self.active, self._next_key())
            # reprolint: disable=R002 (one sync per tick IS the contract)
            emitted_np, done_np = jax.device_get((self.tokens, done))
            t_wall = time.perf_counter()
            for s in range(self.slots):
                req = self._host_active[s]
                if req is None:
                    continue
                req.out.append(int(emitted_np[s, 0]))
                if done_np[s]:
                    self._finish(s, now, t_wall, log)
        self._now = now + 1
        self.ticks = self._now
        return self._finished[n_done:]

    def run(self, requests, log=None):
        """Serve ``requests`` to completion; returns them finished, with
        per-request tick and wall-clock lifecycle stamps filled in.  A thin
        driver over :meth:`begin`/:meth:`tick` — the co-scheduler uses the
        same granular API with its own loop."""
        self.begin(requests, log=log)
        while self.pending():
            self.tick()
        return self._finished

    # -- fused-checkpoint carry (repro.checkpoint.io.save_live_state) -------

    def carry(self):
        """(arrays pytree, JSON meta) capturing the engine between ticks:
        the device-resident slot state plus the sampling key, and the
        session's host bookkeeping — clock, swap epoch, stream cursor, and
        each in-flight/finished request's lifecycle (by rid, so the
        deterministic arrival stream can be re-spliced on restore)."""
        tree = {"tokens": self.tokens, "caches": self.caches,
                "pos": self.pos, "budget": self.budget,
                "active": self.active,
                "key": jax.random.key_data(self._key)}
        if self.paged:
            tree["pt"] = self.pt
        req_meta = lambda r: {"rid": r.rid, "out": [int(t) for t in r.out],
                              "admitted_at": r.admitted_at,
                              "done_at": r.done_at}
        meta = {"ticks": self.ticks, "now": self._now, "base": self._base,
                "swaps": self.swaps, "swap_log": list(self.swap_log),
                "queue_cursor": self.queue_cursor,
                "queue_total": self._queue_total,
                "slots": [None if r is None else req_meta(r)
                          for r in self._host_active],
                "finished": [req_meta(r) for r in self._finished]}
        if self.paged:
            meta["paged"] = {
                "alloc": self._alloc.snapshot(),
                "pt": self._pt_host.tolist(),
                "slot_adm": [None if a is None else a.as_meta()
                             for a in self._slot_adm]}
        return tree, meta

    def restore(self, path, meta, requests):
        """Inverse of :meth:`carry` (in place, from the fused checkpoint at
        ``path``): ``requests`` must be the same arrival stream the saved
        session was begun with — rebuilt deterministically, its Request
        objects are re-spliced into queue/slots/finished by rid."""
        from repro.checkpoint import io
        like = {"engine": {"tokens": self.tokens, "caches": self.caches,
                           "pos": self.pos, "budget": self.budget,
                           "active": self.active,
                           "key": jax.random.key_data(self._key)}}
        if self.paged:
            like["engine"]["pt"] = self.pt
        tree = io.load_tree(path, like)["engine"]
        (self.tokens, self.caches, self.pos, self.budget, self.active) = (
            tree["tokens"], tree["caches"], tree["pos"], tree["budget"],
            tree["active"])
        self._key = jax.random.wrap_key_data(tree["key"])
        if self.paged:
            pm = meta["paged"]
            self.pt = tree["pt"]
            self._pt_host = np.asarray(pm["pt"], np.int32)
            self._alloc = PageAllocator.from_snapshot(pm["alloc"])
            self._slot_adm = [None if a is None else Admission.from_meta(a)
                              for a in pm["slot_adm"]]
        ordered = sorted(requests, key=lambda r: (r.arrival, r.rid))
        by_rid = {r.rid: r for r in ordered}
        if len(ordered) != meta["queue_total"]:
            raise ValueError(
                f"restore stream has {len(ordered)} requests; checkpoint "
                f"was begun with {meta['queue_total']}")

        def splice(m):
            r = by_rid[m["rid"]]
            r.out = list(m["out"])
            r.admitted_at, r.done_at = m["admitted_at"], m["done_at"]
            return r

        self._queue = ordered[meta["queue_cursor"]:]
        self._queue_total = meta["queue_total"]
        self._host_active = [None if m is None else splice(m)
                             for m in meta["slots"]]
        self._finished = [splice(m) for m in meta["finished"]]
        self.ticks, self._now = meta["ticks"], meta["now"]
        self._base = meta["base"]
        self.swaps, self.swap_log = meta["swaps"], list(meta["swap_log"])
        self._standby = None


def simulate(cfg, params, requests, slots, max_len, mesh=None, log=print,
             **engine_kw):
    """Drop-in functional wrapper matching the legacy ``simulate``
    signature: build an engine, serve the request list, return finished."""
    if mesh is None:
        return ServeEngine(cfg, params, slots=slots, max_len=max_len,
                           **engine_kw).run(requests, log=log)
    with jax.set_mesh(mesh):
        # built inside the mesh scope: the jitted state init only matches
        # the step outputs' shardings under the same active mesh
        engine = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                             **engine_kw)
        return engine.run(requests, log=log)
