"""Cached-logit buffer — beyond-paper optimization of buffered KD.

Observation: the buffer F0 is *frozen* for the whole of Phase 2 (that is the
point of the paper's ablation — 'melting' buffers collapse back to KD), and
the core set C is static.  Therefore F0(x_i) is a constant per round: compute
it once, cache it, and drop the third forward pass from every KD step.  The
loss is *mathematically identical* to Eq. 4.

Caveat recorded in DESIGN.md: with stochastic input augmentation (the
paper's CIFAR setup) the cached logits correspond to the un-augmented
inputs, so the CIFAR reproduction defaults to the faithful clone; at LLM
scale (no augmentation) the equivalence is exact.

`topk` compresses the cache: store top-k logits + a tail logsumexp so memory
is O(N*k) instead of O(N*V); the reconstructed distribution lumps the tail
into a single bucket (see distill.topk_kl for the matching loss).

The cache is device-resident (jax arrays), and :meth:`LogitCache.lookup`
gathers with ``jnp.take`` — a scan-carried lookup never bounces through host
numpy.  :func:`core_logits` is the shared batched forward (also used by the
transport codecs, repro/transport): it jits ONE batch-shaped executable and
pads the tail batch up to it instead of re-tracing per tail shape.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distill import tail_logsumexp

#: adapter.logits -> jitted (state, x) -> logits batch forward.  One entry
#: per adapter, so every `core_logits` call over same-shaped batches reuses
#: one compiled executable (pinned by tests/test_buffer.py via trace_guard).
_FWD_CACHE: dict = {}


def _forward_fn(adapter):
    fn = adapter.logits
    if fn not in _FWD_CACHE:
        _FWD_CACHE[fn] = jax.jit(lambda st, x: fn(st, x, False)[0])
    return _FWD_CACHE[fn]


def core_logits(adapter, state, ds, batch=512):
    """Logits of ``state`` over every example of ``ds`` as one device-
    resident (N, V) float32 array.

    All batches run through ONE batch-shaped jitted executable: the tail
    batch is padded up to the batch shape (repeating its last row) and the
    padding rows are sliced off again, so ``len(ds) % batch != 0`` costs a
    few wasted rows instead of a second trace + compile per tail shape.
    """
    n = len(ds)
    b = min(batch, n)
    fwd = _forward_fn(adapter)
    outs = []
    for i in range(0, n, b):
        xb = np.asarray(ds.x[i:i + b])
        pad = b - xb.shape[0]
        if pad:
            xb = np.concatenate(
                [xb, np.broadcast_to(xb[-1:], (pad,) + xb.shape[1:])])
        lg = fwd(state, jnp.asarray(xb))
        outs.append(lg[:b - pad] if pad else lg)
    return jnp.concatenate(outs).astype(jnp.float32)


@dataclasses.dataclass
class LogitCache:
    logits: object = None       # (N, V) exact cache (device-resident)
    top_vals: object = None     # (N, k) compressed cache
    top_idx: object = None      # (N, k)
    tail_lse: object = None     # (N,) logsumexp of non-top entries

    def lookup(self, idx):
        """Gather cache rows on device.  ``idx`` may be a slice (the whole-
        cache view the engine broadcasts into its scan) or an index array
        (gathered with ``jnp.take`` — no host round-trip per lookup)."""
        def take(a):
            if isinstance(idx, slice):
                return a[idx]
            return jnp.take(a, jnp.asarray(idx), axis=0)
        if self.logits is not None:
            return take(self.logits)
        return (take(self.top_vals), take(self.top_idx), take(self.tail_lse))

    @property
    def exact(self):
        return self.logits is not None


def precompute_logits(adapter, state, ds, batch=512, topk=None):
    """Run the frozen buffer once over the core set."""
    logits = core_logits(adapter, state, ds, batch)
    if topk is None:
        return LogitCache(logits=logits)
    if topk < 1:
        raise ValueError(f"topk must be >= 1, got {topk} (k=0 would drop the "
                         "buffer KL term entirely)")
    # Keep at least one tail entry: k = V would make the tail logsumexp
    # log(0) and the compressed form pointless (use the exact cache then).
    topk = min(topk, logits.shape[-1] - 1)
    tv, ti = jax.lax.top_k(logits, topk)
    return LogitCache(top_vals=tv, top_idx=ti,
                      tail_lse=tail_logsumexp(logits, ti))


def reconstruct_logits(cache_entry, vocab):
    """Expand a compressed cache entry back to a (B, V) logit tensor whose
    softmax matches the original on the top-k support (the tail mass is
    spread uniformly over the V-k non-top entries)."""
    tv, ti, tail = cache_entry
    b, k = tv.shape
    n_tail = max(vocab - k, 1)
    fill_val = tail[:, None].astype(jnp.float32) - jnp.log(float(n_tail))
    out = jnp.broadcast_to(fill_val, (b, vocab))
    out = jax.vmap(lambda o, i, v: o.at[i].set(v.astype(jnp.float32)))(out, ti, tv)
    return out
