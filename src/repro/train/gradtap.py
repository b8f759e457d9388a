"""Gradient taps: each piece of a worker's gradient is tampered, cast to
f32 and summed over the workers at the moment the backward pass makes it.

A tap is a ``jax.custom_vjp`` identity on one use of a parameter.  Its
backward pass takes the cotangent ``ct`` of that use (in the parameter's
dtype) and

- applies the worker's attack: ``t = where(do, a·ct + b, ct)``, with the
  affine ``a, b`` of ``byzantine.AFFINE`` (``b`` on one use of a leaf
  only);
- returns ``t`` as the parameter's cotangent, so the local, tampered
  gradient stays available (the check step sketches it);
- returns ``psum(w · t.astype(f32))`` over the worker axes as the
  cotangent of an f32 *sink* of the parameter's shape.

The loss is differentiated with respect to the sinks as well, and their
cotangents are the reduced gradient.  The sinks are zeros that the
forward pass never reads, so only their cotangents take memory, and those
are the reduced gradient itself.  Each all-reduce then depends on one
piece only, and the compiler may run it while the backward pass of the
layers below computes.

The model places the taps (``models.model.forward`` with ``hook=``):
every layer's parameter slice inside ``transformer.run_stack``, the
head's use of the embedding (or the untied head) in ``unembed``, and the
embedding lookup in ``embed``.  The lookup's gradient is a scatter of the
rows of the worker's tokens: where the rows of all workers are fewer than
the vocabulary, the lookup tap all-gathers the token ids and the f32 rows
and scatter-adds them (the same sum in another order) instead of
all-reducing the whole table.  Leaves that no tap reaches (the norms
outside the layers) are tampered and reduced after the backward pass.

A layer group of at most ``UNROLL_REPEATS`` repeats is unrolled when it
is tapped, so that each layer's reduction stands in the step's own
computation and not at the end of a loop iteration, which no collective
outlives; a longer group keeps its ``lax.scan``, since compile time grows
with every unrolled layer.

Precision is that of a reduction of the whole gradient tree: each piece
is the worker's gradient in the parameter's dtype, summed over the
workers in f32.  The one difference is a tied embedding with a sparse
lookup: its head part and its lookup rows are summed across the workers
in f32 apiece, where a whole-tree reduction first adds them, and the rows
of an id the batch repeats, in the parameter's dtype within each worker.
In bf16 with a few frequent ids that rounding is most of the whole-tree
gradient's distance from a float32 one; the taps avoid it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import byzantine

UNROLL_REPEATS = 8


@dataclasses.dataclass
class _Step:
    """What every tap of one worker's step shares."""
    attack: str
    scale: float
    worker_axes: tuple[str, ...]
    workers: int
    do: Any                  # this worker tampers in this iteration
    weight: Any              # its weight in the sum over workers
    tapped: set = dataclasses.field(default_factory=set)   # parameter paths
    # path -> the table and sink a sparse lookup passed on, for the leaf's
    # next use
    passed: dict = dataclasses.field(default_factory=dict)


def _tap(st: _Step, bias: bool):
    @jax.custom_vjp
    def tap(p, sink, keys, do, w):
        return p

    def fwd(p, sink, keys, do, w):
        return p, (keys, do, w)

    def bwd(res, ct):
        keys, do, w = res
        t = jax.tree.map(
            lambda c, k: jnp.where(
                do, byzantine.affine(c, st.attack, k, st.scale, bias), c),
            ct, keys)
        red = jax.lax.psum(
            jax.tree.map(lambda c: w * c.astype(jnp.float32), t),
            st.worker_axes)
        # one all-reduce a piece: XLA would otherwise fold the pieces of a
        # stacked leaf back into one all-reduce of the whole leaf
        return t, jax.lax.optimization_barrier(red), None, None, None

    tap.defvjp(fwd, bwd)
    return tap


def _sparse_lookup(st: _Step):
    """``table[ids]`` whose backward pass reduces the lookup's gradient
    as gathered rows: ``a·l`` of every worker, summed in f32.

    It also passes the table and its sink through, for the leaf's later
    use (the tied head): the backward pass then scatters the lookup's
    rows into that use's gradient and reduced gradient in place, and no
    second table-sized buffer exists."""
    @jax.custom_vjp
    def lookup(table, sink, ids, do, w):
        return table[ids], table, sink

    def fwd(table, sink, ids, do, w):
        return (table[ids], table, sink), (ids, do, w)

    def bwd(res, cts):
        ids, do, w = res
        ct, d_table, reduced = cts
        t = jnp.where(do, byzantine.affine(ct, st.attack, None, st.scale,
                                           bias=False), ct)
        d_table = d_table.at[ids].add(t.astype(d_table.dtype))
        all_ids = jax.lax.all_gather(ids, st.worker_axes, tiled=False)
        rows = jax.lax.all_gather(w * t.astype(jnp.float32),
                                  st.worker_axes, tiled=False)
        reduced = reduced.at[all_ids.reshape(-1)].add(
            rows.reshape(-1, reduced.shape[-1]))
        return d_table, reduced, None, None, None

    lookup.defvjp(fwd, bwd)
    return lookup


class GradTap:
    """The model's hook: taps on the parameters under ``path``.

    ``sinks`` and ``keys`` mirror the parameters under ``path``: an f32
    sink and an attack key a leaf.  The model calls ``at`` to descend,
    ``__call__`` on a parameter use, ``lookup`` for the embedding, and
    ``unrolls``/``scan``/``layer`` for a layer group.
    """

    def __init__(self, st: _Step, sinks, keys, path: tuple = ()):
        self._st, self._sinks, self._keys, self._path = st, sinks, keys, path

    def at(self, *names) -> "GradTap":
        sinks, keys = self._sinks, self._keys
        for n in names:
            sinks, keys = sinks[n], keys[n]
        return GradTap(self._st, sinks, keys, self._path + names)

    def __call__(self, p, bias: bool = True):
        """``p`` (the parameters under this path), tapped.  ``bias``: this
        use takes the attack's ``b``; exactly one use of a leaf does."""
        st = self._st
        st.tapped.add(self._path)
        p, sinks = st.passed.pop(self._path, (p, self._sinks))
        return _tap(st, bias)(p, sinks, self._keys, st.do, st.weight)

    def lookup(self, table, ids, bias: bool):
        """``table[ids]`` with the lookup's gradient tapped: sparse where
        the rows of all workers are fewer than the table's, and where
        this use takes no ``b`` (a bias reaches every row)."""
        st = self._st
        dense = (st.workers * ids.size >= table.shape[0]
                 or (bias and byzantine.has_bias(st.attack)))
        if dense:
            return self(table, bias=bias)[ids]
        st.tapped.add(self._path)
        rows, table, sinks = _sparse_lookup(st)(table, self._sinks, ids,
                                                st.do, st.weight)
        st.passed[self._path] = table, sinks
        return rows

    @staticmethod
    def unrolls(repeats: int) -> bool:
        """Whether a tapped layer group of ``repeats`` is unrolled."""
        return repeats <= UNROLL_REPEATS

    def scan(self, stacked):
        """The inputs of a layer group's ``lax.scan`` (or of its unrolled
        layers): the group's stacked parameters (one tree a pattern
        position), their sinks and the layer index."""
        reps = jax.tree.leaves(stacked)[0].shape[0]
        return tuple(stacked), tuple(self._sinks), jnp.arange(reps)

    def layer(self, xs, pos: int):
        """The tapped parameters of pattern position ``pos`` from one slice
        ``xs`` of ``scan``'s inputs.  A layer's noise is drawn from its
        leaf's key folded with the layer index."""
        params, sinks, r = xs
        keys = jax.tree.map(lambda k: jax.random.fold_in(k, r),
                            self._keys[pos])
        sub = GradTap(self._st, sinks[pos], keys, self._path + (pos,))
        return sub(params[pos])


def _names(path) -> tuple:
    return tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)


def _split(params, tapped: set) -> list[bool]:
    """For each leaf of ``params``: whether a tap reached it."""
    return [any(_names(path)[:len(t)] == t for t in tapped)
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]]


def _keys(params, key):
    leaves, treedef = jax.tree.flatten(params)
    return treedef.unflatten(list(jax.random.split(key, len(leaves))))


def value_and_reduced_grad(loss: Callable, params, *, do, key, weight,
                           attack: str, scale: float,
                           worker_axes: tuple[str, ...], workers: int):
    """``loss(params, hook) -> (value, aux)`` and its gradient, in one
    worker's shard_map body.

    Returns ``((value, aux), grads, reduced)``: ``grads`` this worker's
    gradient, tampered where ``do`` (leaf i's noise from the i-th key of
    ``split(key, leaves)``, as ``byzantine.apply_attack``), and
    ``reduced`` the f32 sum over ``worker_axes`` of ``weight`` times it.
    """
    st = _Step(attack, scale, tuple(worker_axes), workers, do, weight)
    keys = _keys(params, key)
    sinks = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    (value, aux), (grads, red) = jax.value_and_grad(
        lambda p, s: loss(p, GradTap(st, s, keys)), argnums=(0, 1),
        has_aux=True)(params, sinks)
    g, treedef = jax.tree.flatten(grads)
    red, k = jax.tree.leaves(red), jax.tree.leaves(keys)
    tapped = _split(params, st.tapped)
    rest = [i for i, hit in enumerate(tapped) if not hit]
    # the leaves no tap reached: tampered and reduced after the backward
    for i in rest:
        g[i] = jnp.where(do, byzantine.affine(g[i], attack, k[i], scale),
                         g[i])
    if rest:
        late = jax.lax.psum(
            [weight * g[i].astype(jnp.float32) for i in rest], worker_axes)
        for i, r in zip(rest, late):
            red[i] = r
    return (value, aux), treedef.unflatten(g), treedef.unflatten(red)


def reduced_bytes(loss: Callable, params) -> tuple[int, int]:
    """f32 bytes of the gradient of ``loss(params, hook)`` that the taps
    reduce inside the backward pass, and the bytes left to reduce after
    it.  Traces the forward pass only (``jax.eval_shape``)."""
    st = _Step("none", 1.0, (), 2, False, 1.0)
    keys = jax.tree.map(lambda p: jax.ShapeDtypeStruct((2,), jnp.uint32),
                        params)
    sinks = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
    jax.eval_shape(lambda p, s, k: loss(p, GradTap(st, s, k)), params,
                   sinks, keys)
    sizes = [4 * math.prod(p.shape) for p in jax.tree.leaves(params)]
    tapped = _split(params, st.tapped)
    inside = sum(s for s, hit in zip(sizes, tapped) if hit)
    return inside, sum(sizes) - inside
