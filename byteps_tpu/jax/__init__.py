"""JAX/optax framework adapter — the flagship plugin.

The TPU-native counterpart of the reference's framework plugins
(byteps/torch, byteps/tensorflow, byteps/mxnet — SURVEY.md §2.4): a
Horovod-style surface over the push_pull core.

Two modes, mirroring the reference's two integration styles:

- **engine mode** (imperative; like torch ``DistributedOptimizer`` whose
  backward hooks enqueue per-tensor push_pulls, reference
  torch/__init__.py:115-156): pytree leaves become named tensors, each
  partitioned/scheduled/reduced by the background engine with priority =
  declaration order.  Host-driven; works outside jit.
- **fused mode** (in-graph; like the TF custom op path, reference
  tensorflow/ops.cc): :func:`distributed_optimizer` returns a pure optax
  ``GradientTransformation`` whose update psums gradients — call it inside
  your shard_map/jit step and XLA fuses the collectives with the update.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..common import tracing as _tracing
from ..common.handles import TreeHandle
from ..common.metrics import gauges as _gauges
from ..core import api as _api
from ..ops import push_pull_tree as _traced_push_pull_tree

__all__ = [
    "push_pull",
    "push_pull_async",
    "DistributedOptimizer",
    "distributed_optimizer",
    "broadcast_parameters",
    "broadcast_optimizer_state",
    "DistributedGradientTape",
]


def _leaf_names(tree, prefix: str) -> list:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [prefix + jax.tree_util.keystr(path) for path, _ in paths]


def push_pull_async(tree, name_prefix: str = "byteps", op: str = "average"
                    ) -> list:
    """Enqueue every leaf of a rank-stacked pytree; returns handles.

    Each leaf must have leading axis == number of ranks (see
    byteps_tpu.comm.collectives data model).  Leaf names derive from tree
    paths, so declaration order — and therefore communication priority
    (reference tensorflow/ops.cc:158 ``priority=-declared_key``) — is the
    order leaves first appear.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    names = _leaf_names(tree, name_prefix)
    return [_api.push_pull_async(leaf, n, op=op)
            for n, leaf in zip(names, leaves)]


def _enqueue_and_wait(enqueue) -> tuple:
    """``enqueue(engine)`` the tree, then block on its handles: the
    caller thread's two halves of a tree-level push_pull, as the phases
    ``bps.push_pull`` (whole; ``StepStats.push_pull_ms``) and
    ``bps.engine.wait`` (blocked; ``attrib["wait"]``); each feeds its
    wall and, of it, the thread's CPU milliseconds
    (``push_pull_cpu_ms``, ``attrib_cpu["wait"]``).  ``enqueue``
    returns a ``TreeHandle``; returns ``(tree_handle, results)``."""
    eng = _api._require()
    feeds = eng.phase_feeds
    if feeds["push_pull"] is not None:    # telemetry on: a step to open
        eng.step_stats.open_call()
    # once-a-step phases: each reads the thread's CPU clock too
    with _tracing.phase("bps.push_pull", feeds["push_pull"], cpu=True) as ph:
        pushed = enqueue(eng)
        step = eng.step_stats.current_step
        ph.note(step=step)
        with _tracing.phase("bps.engine.wait", feeds["wait"],
                            cpu=True) as ph_wait:
            ph_wait.note(step=step)
            outs = pushed.wait()
    return pushed, outs


def push_pull(tree, name_prefix: str = "byteps", op: str = "average"):
    """Synchronously reduce a rank-stacked pytree; returns the reduced tree
    (leaves lose their leading rank axis).

    The whole tree goes to the engine at once
    (``PushPullEngine.push_pull_tree_async``), which pushes consecutive
    plain float leaves in BUCKETS: a run of leaves of one dtype, up to 16
    partitions' worth of bytes, is packed by one program, partitioned,
    scheduled and reduced as one tensor, and split back into leaves by
    one program -- a few dozen engine tensors for a model's few hundred
    leaves.  Priority is per bucket, in declaration (= flattening)
    order.  A leaf goes by itself, exactly as through
    :func:`push_pull_async`, where only the per-tensor path can serve it:
    an integer leaf (exact ``//``), a leaf at or over the cap, a name
    with a codec declared or the compressor ladder on (codec state is
    per tensor), ``BYTEPS_DEBUG_SAMPLE_TENSOR`` set.  Results, shapes
    and output shardings are those of the per-leaf path (the cross-rank
    summation order may differ within float32 rounding).
    :func:`push_pull_async` and ``bps.push_pull[_async]`` are unchanged:
    one tensor, one handle."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    names = _leaf_names(tree, name_prefix)
    _, outs = _enqueue_and_wait(
        lambda eng: eng.push_pull_tree_async(leaves, names, op=op))
    return jax.tree_util.tree_unflatten(treedef, outs)


def broadcast_parameters(params, root: int = 0):
    """Make every rank's parameters identical to ``root``'s.

    Reference: broadcast_parameters zeroes non-root tensors then sum-reduces
    (torch/__init__.py:259-291).  Input leaves may be rank-stacked
    ([R, ...], per-rank values) or plain (replicated candidates).  Returns
    the root's tree (no rank axis).
    """
    from ..comm.collectives import broadcast as _bcast
    from ..comm.mesh import get_comm
    comm = get_comm()
    r = comm.num_ranks

    def one(leaf):
        leaf = jnp.asarray(leaf)
        if leaf.ndim >= 1 and leaf.shape[0] == r:
            stacked = leaf
        else:
            stacked = jnp.broadcast_to(leaf[None], (r,) + leaf.shape)
        return _bcast(comm, stacked, root=root)

    return jax.tree.map(one, params)


def broadcast_optimizer_state(opt_state, root: int = 0):
    """Broadcast optax optimizer state (reference broadcast_optimizer_state,
    torch/__init__.py:292-411 — there it must walk torch state dicts; optax
    state is already a pytree).  Non-array leaves (step counters etc.) pass
    through untouched."""
    def one(leaf):
        if isinstance(leaf, (int, float, bool)):
            return leaf
        return broadcast_parameters(leaf, root=root)
    return jax.tree.map(one, opt_state)


def distributed_optimizer(tx: optax.GradientTransformation,
                          axis_names=("dcn", "ici"),
                          op: str = "average") -> optax.GradientTransformation:
    """Fused-mode wrapper: an optax transformation that reduces gradients
    across mesh axes before the inner update.  Use inside shard_map.

    The in-graph analog of the reference's _DistributedOptimizer
    ``compute_gradients`` override (tensorflow/__init__.py:186-280).
    """

    def init_fn(params):
        return tx.init(params)

    def update_fn(grads, state, params=None, **extra):
        grads = _traced_push_pull_tree(grads, axis_names, op=op)
        return tx.update(grads, state, params, **extra)

    return optax.GradientTransformation(init_fn, update_fn)


def _donating(tx_update):
    """``tx_update(reduced, state, params)`` as ONE program that donates
    the leaves of ``(reduced, state)`` handed over in ``donated``: every
    output of an optax update has the shape of a gradient or of a state
    leaf, so each is written into a buffer that dies in the call and
    nothing is allocated.  ``donated`` and ``kept`` are the flattened
    pair, each with None where the other holds the leaf.  A
    ``tx_update`` that is jitted already is inlined: one executable, one
    launch.  No ``out_shardings``: outputs follow the inputs."""
    def run(treedef, donated, kept, params):
        leaves = [k if d is None else d for d, k in zip(donated, kept)]
        reduced, state = jax.tree_util.tree_unflatten(treedef, leaves)
        return tx_update(reduced, state, params)
    return jax.jit(run, static_argnums=(0,), donate_argnums=(1,))


class DistributedOptimizer:
    """Engine-mode optimizer wrapper (imperative, host-driven).

    Mirrors the reference torch ``DistributedOptimizer``
    (torch/__init__.py:110-214): gradients are enqueued into the
    background engine (partitioned, priority-scheduled, credit-limited) and
    the optax update runs on the averaged result.  The gradient tree goes
    through :func:`push_pull`: consecutive plain float leaves travel in
    buckets (one engine tensor, priority in declaration order per
    bucket), the rest per leaf -- see there for what falls back and why.
    Supports
    ``backward_passes_per_step`` gradient accumulation: micro-steps
    accumulate locally and only the boundary step communicates
    (reference torch/__init__.py:110-156).

    With ``sharded_update=True`` (default: follow
    ``Config.sharded_update``) the optax state moves INTO the engine
    (ISSUE 20): ``init(params)`` declares one sharded-update slot per
    leaf — flat-shard master/optimizer state resident on the
    reduce-scatter owners, AOT-warmed at declare time — and ``update``
    pushes gradients through the same stacked chunk collectives but
    receives the owner-computed optax UPDATES back (pull leg N/R
    instead of N); every leaf is then its own tensor (a slot is
    per-tensor state), never bucketed.  The returned ``(updates, state)`` contract is
    unchanged, and the trajectory is bit-for-bit that of the optax update
    run op by op on the reduced gradients (tests/test_sharded_update.py;
    the unsharded mode COMPILES its update, below, and follows the same
    trajectory to float32 rounding, as any jitted update does).

    **``update(grads, state, params)`` consumes ``state``** (unsharded
    mode, every backend): the optax update runs as one program of the
    adapter's own in which the reduced gradients and ``state`` are
    DONATED, so the updates and the new state are written into their
    buffers and the step allocates nothing.  Rebind it, as the
    reference's ``step()`` updates its state in place:
    ``updates, state = opt.update(grads, state, params)``; reading the
    old ``state`` afterwards raises JAX's "Array has been deleted".
    ``grads`` and ``params`` stay the caller's.  What stays alive is
    seen in the call itself: a state leaf that IS a leaf of ``params``
    (the same array) or that the state mentions twice, leaves that are
    not ``jax.Array``s, and the whole ``state`` on accumulation
    micro-steps, which return it untouched.  How much of the donation
    the program could use: gauge ``adapter.tx_update_donated_share``.
    """

    def __init__(self, tx: optax.GradientTransformation,
                 name_prefix: str = "grad",
                 op: str = "average",
                 backward_passes_per_step: int = 1,
                 sharded_update: Optional[bool] = None):
        if backward_passes_per_step < 1:
            raise ValueError("backward_passes_per_step must be >= 1")
        self._tx = tx
        self._prefix = name_prefix
        self._op = op
        self._bpps = backward_passes_per_step
        self._accum = None
        self._micro = 0
        self._lock = threading.Lock()
        self._sharded = sharded_update
        self._tx_program = _donating(tx.update)
        self._leaf_meta = None      # [(name, shape, dtype)] once declared
        self._declared_engine = None

    def _sharded_on(self) -> bool:
        if self._sharded is not None:
            return self._sharded
        from ..common.config import get_config
        return get_config().sharded_update

    def _declare_sharded(self, params):
        """Declare one engine slot per leaf.  Re-runs after an elastic
        transition (the engine instance changed): api.declare_update
        consumes the suspend() stash, re-padding each flat shard to the
        new mesh — optimizer state survives the shrink."""
        names = _leaf_names(params, self._prefix)
        leaves = jax.tree_util.tree_leaves(params)
        self._leaf_meta = []
        for name, leaf in zip(names, leaves):
            arr = np.asarray(leaf)
            if self._op != "average":
                raise ValueError(
                    "sharded_update supports op='average' only (the "
                    "fused 1/R scale is baked into the update program)")
            _api.declare_update(name, arr.shape, arr.dtype, tx=self._tx,
                                init_value=arr)
            self._leaf_meta.append((name, arr.shape, arr.dtype))
        self._declared_engine = _api._engine

    def init(self, params):
        if self._sharded_on():
            self._declare_sharded(params)
            # the real state lives in the engine slots; the caller-side
            # state object is a placeholder threaded through update()
            return optax.EmptyState()
        return self._tx.init(params)

    def update(self, grads, state, params=None):
        """grads: rank-stacked pytree ([R, ...] leaves).

        Returns (updates, new_state) and CONSUMES ``state`` (its arrays
        are donated to the update's program: see the class).  On
        accumulation micro-steps the updates are zeros (parameters
        unchanged) and ``state`` comes back as it was, matching the
        reference's deferral of push_pull until the boundary pass.
        """
        # the feeds as _enqueue_and_wait reaches them; with no engine,
        # nothing (push_pull below then says "not initialized")
        eng = _api._engine
        feeds = eng.phase_feeds if eng is not None else {}
        with _tracing.phase("bps.adapter.update", feeds.get("update")) as ph:
            out = self._update(grads, state, params, feeds.get("tx_update"))
            # the engine step the update landed in is known only now
            if _api._engine is not None:
                ph.note(step=_api._engine.step_stats.current_step)
        return out

    def _update(self, grads, state, params, tx_feed=None):
        with self._lock:
            if self._bpps > 1:
                self._accum = grads if self._accum is None else jax.tree.map(
                    jnp.add, self._accum, grads)
                self._micro += 1
                if self._micro < self._bpps:
                    zeros = jax.tree.map(
                        lambda g: jnp.zeros(g.shape[1:], g.dtype), grads)
                    return zeros, state
                grads = self._accum
                if self._op == "average":
                    grads = jax.tree.map(lambda g: g / self._bpps, grads)
                self._accum = None
                self._micro = 0
        if self._sharded_on():
            if self._leaf_meta is None:
                raise RuntimeError(
                    "DistributedOptimizer(sharded_update=True).init("
                    "params) must run before update(): it declares the "
                    "engine-resident optimizer slots")
            if self._declared_engine is not _api._engine:
                # elastic transition: a new engine has no slots yet;
                # re-declare from the suspend() stash (params= reseeds
                # the master only when no stash exists)
                if params is None:
                    raise RuntimeError(
                        "sharded_update re-declare after an elastic "
                        "transition needs params= (slot geometry)")
                self._declare_sharded(params)
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            # one handle per leaf: an engine-resident optimizer slot
            # is per tensor, so no leaf rides a bucket here
            pushed, outs = _enqueue_and_wait(lambda eng: TreeHandle(
                [eng.push_pull_update_async(leaf, name, stacked=True)
                 for (name, _, _), leaf in zip(self._leaf_meta, leaves)],
                [(k, None) for k in range(len(leaves))]))
            eng = _api._require()
            for h in pushed.handles:
                eng.handles.release(h.id)
            return jax.tree_util.tree_unflatten(treedef, outs), state
        reduced = push_pull(grads, self._prefix, op=self._op)
        # the caller's own optax update: lands in the step whose
        # push_pull this was (the NEXT step's first push finalizes it)
        with _tracing.phase("bps.adapter.tx_update", tx_feed, cpu=True) as ph:
            if ph.ann is not None:
                ph.note(step=_api._require().step_stats.current_step)
            return self._tx_update(reduced, state, params)

    def _tx_update(self, reduced, state, params):
        """The optax update over buffers that die in it: ``reduced`` is
        the engine's (``push_pull`` handed over the only reference) and
        ``state`` the caller's to give (class docstring)."""
        leaves, treedef = jax.tree_util.tree_flatten((reduced, state))
        # XLA refuses a buffer that is donated and passed again in the
        # same call: an array ``params`` holds too, or one the state
        # mentions twice, stays the caller's
        mentions = collections.Counter(map(id, leaves))
        mentions.update(map(id, jax.tree_util.tree_leaves(params)))
        give = [isinstance(leaf, jax.Array) and mentions[id(leaf)] == 1
                for leaf in leaves]
        donated = [leaf if g else None for leaf, g in zip(leaves, give)]
        kept = [None if g else leaf for leaf, g in zip(leaves, give)]
        program = self._tx_program
        compiled = program._cache_size()
        out = program(treedef, donated, kept, params)
        if program._cache_size() != compiled:
            # a new signature's first call: what of the donation the
            # program used (a backend without donation, or outputs of
            # other shapes, leave the inputs alive)
            consumed = sum(leaf.is_deleted() for leaf in donated
                           if leaf is not None)
            _gauges.set("adapter.tx_update_donated_share",
                        consumed / max(len(jax.tree_util.tree_leaves(out)), 1))
        return out


class DistributedGradientTape:
    """API parity with the reference's TF DistributedGradientTape
    (tensorflow/__init__.py:343-417): wraps a loss function; ``gradient``
    computes per-rank grads (vmap over the rank axis) and push_pull-averages
    them through the engine."""

    def __init__(self, loss_fn, name_prefix: str = "tape",
                 op: str = "average"):
        self._grad_fn = jax.grad(loss_fn)
        self._prefix = name_prefix
        self._op = op

    def gradient(self, params, *stacked_args):
        """``params``: one parameter tree (shared across ranks);
        ``stacked_args``: rank-stacked per-rank inputs ([R, ...])."""
        grads = jax.vmap(self._grad_fn, in_axes=(None,) + (0,) * len(
            stacked_args))(params, *stacked_args)
        return push_pull(grads, self._prefix, op=self._op)
