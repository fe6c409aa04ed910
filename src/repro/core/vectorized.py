"""Vectorized multi-edge Phase-1 engine.

The seed orchestrator trained the R teachers of a round one after another in
a Python loop (R calls to ``_train_on``, each re-jitting its own step).  The
edge computations are embarrassingly parallel — disjoint shards, disjoint
model states — so this module stacks the R edge states into a single
leading-axis pytree and runs the whole round's Phase-1 as ONE jitted
``jax.vmap``-ed ``lax.scan``:

  * per-edge batch schedules come from the same ``data.pipeline.batches``
    stream as the sequential path (same seeds, same permutations), stored
    as ``(R, S, B)`` index arrays into the once-stacked shard data — the
    scan body gathers each step's batch on device;
  * edges with fewer steps than the longest edge are padded with masked
    no-op steps (``jnp.where`` keeps state/optimizer/step-counter), so
    heterogeneous shard sizes vectorize without changing any edge's math;
  * each edge keeps its own LR-decay boundaries (they depend on shard
    size) as a traced per-edge array.

The result is bit-for-bit identical to the sequential path on CPU (the
parity test asserts exact equality) while compiling once per shape instead
of once per edge per round, and executing one batched matmul stream the
backend can fuse — wall-clock becomes sub-linear in R.

When a mesh is active (``jax.set_mesh``), the stacked edge
axis is sharded over the mesh's data axes via the ``repro.sharding`` logical
"batch" rule, so a multi-host mesh splits the edge population across hosts
(``shard_map`` over the edge axis; each shard runs the same vmapped scan).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.data.pipeline import batches
from repro.optim import sgd_momentum, step_decay
from repro.sharding.rules import (DEFAULT_RULES, get_abstract_mesh_or_none,
                                  logical_to_spec)


def stack_trees(trees):
    """Stack a list of identically-structured pytrees along a new axis 0."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


def unstack_tree(tree, n):
    """Inverse of :func:`stack_trees`: split axis 0 back into n pytrees."""
    return [jax.tree.map(lambda l: l[i], tree) for i in range(n)]


@dataclasses.dataclass
class BatchPlan:
    """Stacked batch schedule for one round of edge training.

    The shard data is stored ONCE per edge (padded to the largest shard)
    and the per-step batches are (S, B) index arrays into it — the scan
    body gathers each batch on device, so host/device memory is
    O(data + epochs*indices) rather than epochs copies of every shard.

    x: (R, N, ...) padded shard inputs;  y: (R, N) padded labels;
    idx: (R, S, B) int32 per-step sample indices;
    valid: (R, S) step mask (False = padding step, a masked no-op);
    boundaries: (R, 2) per-edge LR step-decay boundaries.
    """

    x: np.ndarray
    y: np.ndarray
    idx: np.ndarray
    valid: np.ndarray
    boundaries: np.ndarray


def build_batch_plan(edge_dss, batch_size, epochs, seed) -> Optional[BatchPlan]:
    """Build the stacked per-edge batch schedules.

    Index streams come from the exact same ``batches()`` generator (same
    seed, same permutations) as the sequential path, so the vectorized
    engine consumes identical data in identical order.  Returns None when
    the shards are too heterogeneous to stack (different effective batch
    sizes, i.e. some shard is smaller than ``batch_size``) — callers then
    fall back to the sequential path.
    """
    per_edge = []
    for ds in edge_dss:
        if len(ds) == 0:
            return None  # empty shard: defer to the sequential path
        bs = min(batch_size, len(ds))
        steps_per_epoch = max(len(ds) // bs, 1)
        total = steps_per_epoch * epochs
        sels = list(batches(ds, batch_size, seed=seed, epochs=epochs,
                            indices_only=True))
        per_edge.append((bs, total, np.stack(sels).astype(np.int32)))

    if len({bs for bs, _, _ in per_edge}) != 1:
        return None  # heterogeneous batch shapes: sequential fallback
    max_steps = max(idx.shape[0] for _, _, idx in per_edge)
    max_n = max(len(ds) for ds in edge_dss)

    def pad_to(a, n):
        return np.concatenate(
            [a, np.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)]) \
            if a.shape[0] < n else a

    x = np.stack([pad_to(np.asarray(ds.x), max_n) for ds in edge_dss])
    y = np.stack([pad_to(np.asarray(ds.y), max_n) for ds in edge_dss])
    idx = np.stack([pad_to(i, max_steps) for _, _, i in per_edge])
    valid = np.stack([np.arange(max_steps) < i.shape[0]
                      for _, _, i in per_edge])
    boundaries = np.stack([[total // 2, 3 * total // 4]
                           for _, total, _ in per_edge])
    return BatchPlan(x=x, y=y, idx=idx, valid=valid, boundaries=boundaries)


def _select(pred, new, old):
    """Per-leaf ``where`` keeping dtypes — the masked no-op for pad steps."""
    return jax.tree.map(lambda n, o: jnp.where(pred, n, o), new, old)


def make_edge_trainer(adapter, lr, weight_decay, loss_fn=None):
    """Build the vmapped, jitted multi-edge trainer.

    Returns ``train(stacked_states, x, y, valid, boundaries) -> stacked``
    where every argument carries a leading edge axis.  ``loss_fn`` defaults
    to cross-entropy (the paper's L_edge, Eq. 2).
    """
    if loss_fn is None:
        from repro.core import distill

        def loss_fn(lg, y):
            return distill.ce_loss(lg, y)

    def train_one(state, data_x, data_y, idx, valid, bounds):
        opt = sgd_momentum(step_decay(lr, bounds), weight_decay=weight_decay)
        opt_state0 = opt.init(adapter.params(state))

        def objective(params, st, x, y):
            lg, new_st = adapter.logits(adapter.with_params(st, params), x, True)
            return loss_fn(lg, y), new_st

        def body(carry, batch):
            st, opt_st, i = carry
            sel, ok = batch
            x = jnp.take(data_x, sel, axis=0)   # gather this step's batch
            y = jnp.take(data_y, sel, axis=0)
            params = adapter.params(st)
            (loss, new_st), grads = jax.value_and_grad(
                objective, has_aux=True)(params, st, x, y)
            new_params, new_opt = opt.update(grads, opt_st, params, i)
            st = _select(ok, adapter.with_params(new_st, new_params), st)
            opt_st = _select(ok, new_opt, opt_st)
            return (st, opt_st, i + ok.astype(i.dtype)), loss

        (state, _, _), _ = jax.lax.scan(
            body, (state, opt_state0, jnp.asarray(0)), (idx, valid))
        return state

    vmapped = jax.vmap(train_one)
    jit_vmapped = jax.jit(vmapped)
    shard_cache = {}

    def train(stacked_states, x, y, idx, valid, boundaries):
        mesh = get_abstract_mesh_or_none()
        if mesh is not None:
            # Shard the edge axis over the mesh's data axes (logical "batch"
            # rule); within each shard the same vmapped scan runs.  An edge
            # count no data axis divides stays replicated (spec None).
            spec = logical_to_spec(("batch",), (x.shape[0],), mesh,
                                   DEFAULT_RULES)
            if spec[0] is not None:
                # Key on the mesh object itself (Mesh/AbstractMesh are
                # hashable): keeps the executable bound to ITS mesh and
                # avoids id-reuse collisions after garbage collection.
                key = (mesh, spec)
                if key not in shard_cache:
                    in_spec = P(spec[0])
                    shard_cache[key] = jax.jit(jax.shard_map(
                        vmapped, mesh=mesh, in_specs=(in_spec,) * 6,
                        out_specs=in_spec, check_vma=False))
                return shard_cache[key](stacked_states, x, y, idx, valid,
                                        boundaries)
        return jit_vmapped(stacked_states, x, y, idx, valid, boundaries)

    return train


class VectorizedEdgeEngine:
    """Round-level driver: resolve a round's init states, stack, train.

    One engine instance caches its jitted trainer, so repeated rounds with
    the same stacked shapes reuse the compiled executable (the sequential
    path re-traced every edge of every round).
    """

    def __init__(self, adapter, lr, weight_decay):
        self.adapter = adapter
        self._trainer = make_edge_trainer(adapter, lr, weight_decay)

    def train_round(self, init_states, edge_dss, batch_size, epochs, seed):
        """Train all edges of one round as a single vmapped computation.

        init_states: per-edge starting states (already staleness-resolved);
        edge_dss: the matching per-edge shard Datasets.
        Returns the list of trained teacher states, or None if the shards
        cannot be stacked (caller falls back to sequential training).
        """
        plan = build_batch_plan(edge_dss, batch_size, epochs, seed)
        if plan is None:
            return None
        stacked = stack_trees(init_states)
        out = self._trainer(stacked, jnp.asarray(plan.x), jnp.asarray(plan.y),
                            jnp.asarray(plan.idx), jnp.asarray(plan.valid),
                            jnp.asarray(plan.boundaries))
        return unstack_tree(out, len(init_states))
