"""Public jit'd wrappers over the Pallas kernels.

Off a TPU every entry point runs its kernel in Pallas interpret mode; on
a TPU the same call sites compile to Mosaic.  The choice is made when an
op is called (``interpret=None``), never when this module is imported, so
importing ``repro`` initializes no backend.

The ``batched_*`` ops (leading trial dimension) additionally carry an
``impl`` switch because they sit on the jitted scenario engine's hot
path (repro.core.engine_jax): ``"pallas"`` is the TPU kernel (interpret
mode off-TPU — correct but slow, used by CI to keep the kernel path
alive on CPU runners), ``"xla"`` is the pure-jnp fallback built on the
ref.py definitions.  ``impl=None`` auto-selects: Pallas on TPU, XLA
everywhere else.  ``REPRO_KERNEL_IMPL`` overrides the auto choice.

Sharding: the jitted engine invokes the batched ops inside its own
shard_map over a 1-D ``("trials",)`` mesh, so the kernels always see
per-device local shards and need no GSPMD partitioning rules.  Called
OUTSIDE that context under an ambient trials mesh (``set_mesh``), the
pallas branch self-distributes via ``_shard_batched`` — the XLA branch
is plain jnp, which GSPMD partitions on its own.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import coded_encode as _enc
from repro.kernels import flash_attention as _fa
from repro.kernels import gram as _gm
from repro.kernels import majority_vote as _mv
from repro.kernels import ref as _ref
from repro.kernels import sketch as _sk

# the XLA fallbacks state f32 precision, as the kernels do: a TPU's
# default for f32 operands multiplies in bf16 passes
_HIGHEST = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(interpret: bool | None) -> bool:
    """Interpret mode unless asked otherwise or running on a TPU."""
    return not _on_tpu() if interpret is None else interpret


def _shard_batched(kernel, args, arg_specs, out_spec):
    """Sharding-aware dispatch for batched Pallas kernels.

    ``pallas_call`` has no GSPMD partitioning rules, so under an ambient
    1-D ``("trials",)`` mesh (repro.sharding.trials_mesh installed via
    ``set_mesh``) a batched kernel is wrapped in a shard_map over the
    leading trial axis — each device runs the Mosaic/interpret kernel on
    its local shard.  No-op when there is no trials mesh, when the axis
    is already consumed by an enclosing shard_map (the jitted engine's
    own manual context), or when the batch does not divide across it.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding import ambient_mesh, mesh_axis_size_here, shard_map

    ntr = mesh_axis_size_here("trials")
    if ntr <= 1 or args[0].shape[0] % ntr:
        return kernel(*args)
    specs = tuple(
        P(*(("trials",) + (None,) * (a.ndim - 1))) if sp else P()
        for a, sp in zip(args, arg_specs)
    )
    out = P(*(("trials",) + (None,) * (out_spec - 1)))
    return shard_map(kernel, ambient_mesh(), in_specs=specs,
                     out_specs=out, axis_names={"trials"},
                     check_vma=False)(*args)


_IMPL_CHOICES = ("pallas", "xla")


def resolve_impl(impl: str | None) -> str:
    """Resolve a batched-op impl choice to "pallas" | "xla".

    None -> REPRO_KERNEL_IMPL if set, else Pallas on TPU / XLA off-TPU.
    A typo'd env value raises instead of silently falling through to
    the default impl (an unset or empty variable means "auto").
    Long-lived callers that bake the choice into a jit cache key (the
    jitted engine) resolve ONCE up front so a later env change can't
    produce a half-and-half run; the engine records the resolved value
    as ``ExecutionPlan.kernel_impl`` (repro.core.engineplan.plan), so
    ``result.plan.explain()`` reports which dispatch actually ran.
    """
    if impl is None:
        env = os.environ.get("REPRO_KERNEL_IMPL") or None
        if env is None:
            return "pallas" if _on_tpu() else "xla"
        if env not in _IMPL_CHOICES:
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r} is not a known kernel impl; "
                f"allowed values: {list(_IMPL_CHOICES)} (unset it for "
                f"the auto choice)")
        return env
    if impl not in _IMPL_CHOICES:
        raise ValueError(
            f"unknown kernel impl {impl!r}; allowed values: "
            f"{list(_IMPL_CHOICES)} (or None for the auto choice)")
    return impl


_batched_impl = resolve_impl


def sketch(flat_g, key_scalar, k: int = 256, interpret: bool | None = None):
    return _sk.sketch(
        flat_g, key_scalar, k=k,
        interpret=_interpret(interpret),
    )


def pairwise_relmax(replicas, interpret: bool | None = None):
    return _mv.pairwise_relmax(
        replicas, interpret=_interpret(interpret)
    )


def vote(replicas, tau: float = 1e-5, interpret: bool | None = None):
    """Kernel-backed majority vote: (value, faulty, has_majority).

    Same contract as repro.core.identification.majority_vote, but the
    pairwise comparison streams through the Pallas kernel (no (R,R,d)
    materialization)."""
    R = replicas.shape[0]
    rel = pairwise_relmax(replicas.astype(jnp.float32), interpret=interpret)
    agree = rel <= tau
    counts = agree.sum(axis=1)
    is_major = counts > (R // 2)
    has_majority = is_major.any()
    winner = jnp.argmax(is_major)
    value = replicas[winner]
    faulty = ~agree[winner] & has_majority
    return value, faulty, has_majority


def coded_encode(coeffs, grads, interpret: bool | None = None):
    return _enc.coded_encode(
        coeffs, grads, interpret=_interpret(interpret)
    )


def batched_pairwise_relmax(replicas, *, impl: str | None = None,
                            interpret: bool | None = None):
    """(B, R, d) -> (B, R, R) relative max-difference matrices.

    Pallas: grid (B, d-blocks), (R, R) VMEM accumulator per trial.  XLA:
    d is folded in chunks so the (B, R, R, chunk) broadcast stays
    bounded (~64 MiB) at production gradient sizes."""
    if _batched_impl(impl) == "pallas":
        kern = functools.partial(
            _mv.pairwise_relmax_batched,
            interpret=_interpret(interpret),
        )
        return _shard_batched(kern, (replicas.astype(jnp.float32),),
                              (True,), 3)
    return _relmax_xla(replicas.astype(jnp.float32))


@jax.jit
def _relmax_xla(replicas):
    B, R, d = replicas.shape
    chunk = max(128, (1 << 24) // max(1, B * R * R))
    if d <= chunk:
        return _ref.batched_pairwise_maxdiff_ref(replicas)
    pad = (-d) % chunk
    x = jnp.pad(replicas, ((0, 0), (0, 0), (0, pad)))      # zero-pad: rel 0
    x = x.reshape(B, R, -1, chunk).transpose(2, 0, 1, 3)   # (C, B, R, chunk)

    def body(acc, xc):
        return jnp.maximum(acc, _ref.batched_pairwise_maxdiff_ref(xc)), None

    acc, _ = jax.lax.scan(body, jnp.zeros((B, R, R), jnp.float32), x)
    return acc


def batched_vote(replicas, group_of_worker, tau: float = 1e-5, *,
                 impl: str | None = None, interpret: bool | None = None):
    """Majority votes for all replica groups of all trials at once.

    replicas: (B, n, d) worker gradients; group_of_worker: (B, n) int32
    (-1 = idle).  Every group's members hold (putatively) the same
    shard gradient; the vote picks, per group, the lowest-indexed
    worker agreeing with a strict in-group majority — the same winner
    ``identification.majority_vote_np`` picks on the group's member
    stack in ascending worker order.  Returns (winner_coeff (B, n) f32
    one-hot-per-group, faulty (B, n) bool).  The voted VALUE for group
    g is ``sum_w winner_coeff[w] * replicas[w]`` restricted to g; the
    engine folds the whole mean-over-groups into one coded encode.
    """
    rel = batched_pairwise_relmax(replicas, impl=impl, interpret=interpret)
    valid = group_of_worker >= 0                                  # (B, n)
    same = (group_of_worker[:, :, None] == group_of_worker[:, None, :]) \
        & valid[:, None, :] & valid[:, :, None]                   # (B, n, n)
    agree = (rel <= tau) & same
    counts = agree.sum(axis=2)                                    # (B, n)
    gsize = same.sum(axis=2)
    is_major = valid & (counts > gsize // 2)
    n = replicas.shape[1]
    idx = jnp.arange(n)
    # lowest-indexed majority member of each group
    cand = jnp.where(is_major, idx[None, :], n)
    first = jnp.min(jnp.where(same, cand[:, None, :], n), axis=2)  # (B, n)
    winner_coeff = (valid & (idx[None, :] == first)).astype(jnp.float32)
    is_winner_row = jnp.take_along_axis(
        agree, jnp.minimum(first, n - 1)[:, :, None].astype(jnp.int32),
        axis=2,
    )[:, :, 0]
    faulty = valid & ~is_winner_row & (first < n)
    return winner_coeff, faulty


def batched_regroup(keys, active, repl):
    """Masked replica regroup: the on-device control plane's assignment.

    keys (B, n) uint32 per-worker sort keys (repro.core.rngstream PERM
    stream); active (B, n) bool; repl (B,) int replication factor.
    Each trial's active workers are ordered by (key, worker id) — the
    counter-RNG analogue of ``rng.permutation(act_idx)`` via a stable
    argsort, bit-identical to the host ``CounterPermuter`` — and the
    first m*r of that order form m = n_active // r groups of r
    consecutive workers.  Returns (shard (B, n) i32, group (B, n) i32
    with -1 = idle, m (B,) i32).  Inactive workers and the < r
    leftovers get group -1 / shard 0, matching
    ``engine._grouped_rows``'s layout exactly.
    """
    B, n = active.shape
    wi = jnp.broadcast_to(jnp.arange(n, dtype=jnp.uint32), (B, n))
    inact = (~active).astype(jnp.uint32)
    # primary: active first; secondary: key; tertiary: worker id — the
    # id tie-break reproduces the host's *stable* argsort on key ties
    order = jnp.lexsort((wi, jnp.asarray(keys, jnp.uint32), inact))
    rank = jnp.argsort(order, axis=-1)               # inverse permutation
    r = jnp.maximum(jnp.asarray(repl, jnp.int32), 1)
    m = (active.sum(axis=1).astype(jnp.int32) // r)
    member = active & (rank < (m * r)[:, None])
    gid = (rank // r[:, None]).astype(jnp.int32)
    shard = jnp.where(member, gid, 0).astype(jnp.int32)
    group = jnp.where(member, gid, -1).astype(jnp.int32)
    return shard, group, m


def batched_vote_masked(replicas, keys, active, repl, tau: float = 1e-5, *,
                        gate=None, impl: str | None = None,
                        interpret: bool | None = None):
    """Masked-regroup variant of ``batched_vote``: group each trial's
    active workers by the key permutation, then majority-vote per
    group.  ``gate`` (B,) bool optionally idles whole trials (their
    groups vote as -1).  Returns (winner_coeff, faulty, shard, group,
    m) — the last three are ``batched_regroup``'s layout so callers can
    reuse it for aggregation."""
    shard, group, m = batched_regroup(keys, active, repl)
    gv = group if gate is None else jnp.where(gate[:, None], group, -1)
    wc, faulty = batched_vote(replicas, gv, tau=tau, impl=impl,
                              interpret=interpret)
    return wc, faulty, shard, group, m


def batched_detect_masked(symbols, keys, active, repl, tau: float = 1e-9, *,
                          gate=None):
    """Masked-regroup variant of ``detection.detect_groups_batched``:
    regroup, then flag trials whose replica groups mismatch on their
    detection symbols.  Returns (trial_fault (B,), worker_mismatch
    (B, n), shard, group, m)."""
    from repro.core.detection import detect_groups_batched

    shard, group, m = batched_regroup(keys, active, repl)
    gv = group if gate is None else jnp.where(gate[:, None], group, -1)
    fault, mism = detect_groups_batched(symbols, gv, tau=tau)
    return fault, mism, shard, group, m


def batched_coded_encode(coeffs, grads, *, impl: str | None = None,
                         interpret: bool | None = None):
    """(B, n_sym, m) @ (B, m, d) -> (B, n_sym, d) f32 per-trial encode."""
    if _batched_impl(impl) == "pallas":
        kern = functools.partial(
            _enc.coded_encode_batched,
            interpret=_interpret(interpret),
        )
        return _shard_batched(kern, (coeffs, grads), (True, True), 3)
    return _ref.batched_coded_encode_ref(coeffs, grads)


def batched_sketch(flat_g, key_scalar, k: int = 256, *,
                   impl: str | None = None, interpret: bool | None = None):
    """(B, d) -> (B, k) CountSketches under one shared key."""
    if _batched_impl(impl) == "pallas":
        kern = functools.partial(
            _sk.sketch_batched, k=k,
            interpret=_interpret(interpret),
        )
        return _shard_batched(kern, (flat_g, jnp.asarray(key_scalar)),
                              (True, False), 2)
    return _sketch_xla(flat_g, key_scalar, k)


# VMEM budget for the gram kernel's (T, Ie_p, k) sketch accumulator;
# ops chunks the key axis so each pallas_call stays under it (the rows
# are re-streamed once per chunk — Ie^2*d of redundant Gram work per
# extra chunk, trivial next to the T*Ie*d sketch work itself)
_GRAM_SK_VMEM = 4 << 20


def gram_factors(rows, W0, keys, *, k: int = 256,
                 impl: str | None = None, interpret: bool | None = None):
    """Gram-plane precompute: everything d-sized, in one streaming pass.

    (rows (Ie, d) f32/bf16, W0 (B, d) f32 or None, keys (T,) u32) ->
    (G (Ie, Ie), S0 (B, Ie) or None, SK (T, Ie, k)) with G = rows @
    rows^T, S0 = W0 @ rows^T, SK[t] = CountSketch_k(rows) under
    keys[t] (repro.kernels.gram; oracle: ref.gram_factors_ref).  After
    this call the whole protocol scan runs in coefficient space —
    residual symbols of any iterate W0 - C @ rows are S0 - C @ G.
    ``"pallas"`` streams rows through VMEM in d-blocks, chunking the
    key axis to bound the resident sketch accumulator; ``"xla"`` is a
    jitted fallback that computes all T sketch tables as one bucketed
    einsum over a (T, d) sign table (no (T, Ie, d) intermediate).
    """
    keys = jnp.asarray(keys, jnp.uint32)
    if _batched_impl(impl) == "pallas":
        interp = _interpret(interpret)
        (T,) = keys.shape
        if T == 0:
            G, S0, _ = _gm.gram_factors(rows, W0,
                                        jnp.zeros((1,), jnp.uint32),
                                        k=k, interpret=interp)
            return G, S0, jnp.zeros((0, rows.shape[0], k), jnp.float32)
        Ie_p = -(-rows.shape[0] // 8) * 8
        tc = max(1, _GRAM_SK_VMEM // (Ie_p * k * 4))
        if T <= tc:
            return _gm.gram_factors(rows, W0, keys, k=k, interpret=interp)
        G = S0 = None
        sks = []
        for lo in range(0, T, tc):
            g_c, s_c, sk_c = _gm.gram_factors(
                rows, W0 if lo == 0 else None, keys[lo:lo + tc],
                k=k, interpret=interp)
            if lo == 0:
                G, S0 = g_c, s_c
            sks.append(sk_c)
        return G, S0, jnp.concatenate(sks, axis=0)
    return _gram_factors_xla(rows, W0, keys, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _gram_factors_xla(rows, W0, keys, k):
    rows32 = rows.astype(jnp.float32)
    G = jax.lax.dot_general(rows32, rows32, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    S0 = None if W0 is None else jax.lax.dot_general(
        W0.astype(jnp.float32), rows32, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32)
    Ie, d = rows32.shape
    pad = (-d) % k
    g = jnp.pad(rows32, ((0, 0), (0, pad)))
    idx = jax.lax.iota(jnp.uint32, d + pad)

    if keys.shape[0] == 0:
        SK = jnp.zeros((0, Ie, k), jnp.float32)
    else:
        # All T sketches as ONE batched contraction: bucket b of key t is
        # sum_m g[i, m, b] * signs[t, m, b].  ~10x faster than lax.map
        # over keys (one fused matmul vs T passes over rows) at the cost
        # of a transient (T, d) sign table and a different f32 summation
        # order than the stream plane's per-key sketch (tables agree to
        # ~1e-5 relative; detection margins dwarf that).
        signs = jax.vmap(lambda key: _ref.hash_signs_ref(idx, key))(keys)
        SK = jnp.einsum("imb,tmb->tib", g.reshape(Ie, -1, k),
                        signs.reshape(keys.shape[0], -1, k),
                        precision=_HIGHEST,
                        preferred_element_type=jnp.float32)
    return G, S0, SK


@functools.partial(jax.jit, static_argnames=("k",))
def _sketch_xla(flat_g, key_scalar, k):
    B, d = flat_g.shape
    pad = (-d) % k
    g = jnp.pad(flat_g.astype(jnp.float32), ((0, 0), (0, pad)))
    idx = jax.lax.iota(jnp.uint32, d + pad)
    signed = g * _ref.hash_signs_ref(idx, key_scalar)[None]
    return signed.reshape(B, -1, k).sum(axis=1)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    scale: float | None = None, bq: int = 512, bk: int = 512,
                    interpret: bool | None = None):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, scale=scale, bq=bq, bk=bk,
        interpret=_interpret(interpret),
    )
