"""Multi-device wrapping of the step core: one ``shard_wrap`` builder.

Trials are embarrassingly parallel — the scan body touches one trial's
row everywhere — so the data plane scales out with shard_map over a 1-D
``("trials",)`` mesh and NO cross-device collectives inside the scan:
each device runs the identical jitted scan on its slice of the batch.
The batched Pallas kernels see per-device local shards (manual mode),
so the TPU kernel path needs no sharding rules of its own.

Because :func:`repro.core.engineplan.stepcore.step_core` has ONE
argument layout for every path (unused slots are ``None`` — an empty
pytree, so its in_spec is ``None`` too), the wrapper builds one
in_specs tuple, and only the out_specs depend on the control mode (the
device control plane returns its decision trace).
"""
from __future__ import annotations

import functools

import jax

from repro.core.engineplan.stepcore import step_core
from repro.obs.telemetry import TEL_KEYS


@functools.lru_cache(maxsize=32)
def _build(mesh, gram: bool, control: str, shared: bool,
           has_filter: bool, has_bias: bool, impl: str | None,
           stat_sig: tuple, xs_sig: tuple | None, com_sig: tuple,
           a_ndim: int, telemetry: bool = False):
    """Build (and cache) the shard_map-wrapped, jitted step core.

    The signature tuples carry (key, ndim) pairs so the in_specs trees
    match the dict pytrees exactly; the cache keys on them plus the jit
    statics — deliberately NOT on batch-size-dependent plan fields, so
    re-runs at a different B reuse the wrapped function (and its jit
    cache) instead of recompiling."""
    from repro.sharding import shard_map, trial_partition_spec as ts

    if gram:
        # the gram factors replicate: every device scans its trial shard
        # against the same (Ie, Ie) G and contracts against the same
        # (Ie, d) rows after the scan
        a_spec = {"rows": ts(2, None), "G": ts(2, None)}
        y_spec = ts(1, None)
    else:
        # A: the shared data matrix replicates; per-trial stacks shard
        a_spec = ts(a_ndim, None if shared else 0)
        y_spec = ts(a_ndim - 1, None if shared else 0)
    in_specs = (
        a_spec,
        y_spec,
        ts(2, 0),                                          # W0
        ts(2, 0) if gram else None,                        # cw0
        {k: ts(nd, 0) for k, nd in stat_sig},              # stat
        None if xs_sig is None else
        {k: ts(nd, 1) for k, nd in xs_sig},                # xs (T, B, ..)
        {k: ts(nd, None) for k, nd in com_sig},            # replicated
        None if gram else ts(1, None),                     # noisevec
        None if gram else ts(1, 0),                        # pid
    )
    if control == "device":
        # (W, losses, q, check, det, faulty2): the carry's protocol
        # state and the per-step trace stay in the per-trial shard
        out_specs = (ts(2, 0), ts(2, 1), ts(2, 1), ts(2, 1), ts(2, 1),
                     ts(3, 1))
    else:
        out_specs = (ts(2, 0), ts(2, 1), ts(2, 1))
    if telemetry:
        # the (B,) counters accumulate inside each device's trial shard
        # and stay sharded on the way out — no collective anywhere
        out_specs = out_specs + ({k: ts(1, 0) for k in TEL_KEYS},)
    body = functools.partial(step_core, gram=gram,
                             control=control, shared=shared,
                             has_filter=has_filter, has_bias=has_bias,
                             impl=impl, telemetry=telemetry)
    fn = shard_map(body, mesh, in_specs=in_specs, out_specs=out_specs,
                   axis_names={"trials"}, check_vma=False)
    return jax.jit(fn, donate_argnums=(2, 3, 4, 5)), in_specs


def shard_wrap(plan, mesh, *, stat_sig: tuple, xs_sig: tuple | None,
               com_sig: tuple, a_ndim: int):
    """shard_map-wrap the step core for ``plan`` on ``mesh``.

    Returns ``(fn, in_specs)`` — ``in_specs`` doubles as the
    device_put target layout for the chunk pipeline.  Only the plan's
    path statics key the cache; see :func:`_build`."""
    return _build(mesh, plan.data_plane == "gram",
                  plan.control, plan.shared_problem,
                  plan.has_filter, plan.has_bias, plan.kernel_impl,
                  stat_sig, xs_sig, com_sig, a_ndim,
                  getattr(plan, "telemetry", False))
