"""Chunked H2D/donation pipeline: stream the trial batch through the
step core without ever exceeding the plan's chunk memory bound.

Extracted from the tail of ``run_batch_jax``.  Chunks flow through an
async pipeline of depth 1: dispatch chunk k's scan, start chunk k+1's
H2D while it executes, then drain chunk k-1 before staging k+2 — so at
most two chunks' buffers are ever resident and the ``chunk_trials``
memory bound holds.  The last chunk pads up to a mesh multiple with
inert trials (live=False, weights 0; ``PAD_FILL`` marks idle workers
with -1) and the padding is sliced off the results.

The unified step-core signature (see
:mod:`repro.core.engineplan.stepcore`) means ONE staging function
serves every path — the old per-path argument juggling is gone: unused
slots stage as ``None``.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs import trace as obtrace
from repro.obs.telemetry import TEL_KEYS

# per-array padding fill values: -1 marks idle workers / no-filter rows,
# everything else pads to an inert zero trial (live=False, weights 0)
PAD_FILL = {"group1": -1, "group2": -1, "fcode": -1, "farr": 1}


def pad_rows(arr: np.ndarray, axis: int, pad: int, fill=0) -> np.ndarray:
    """Pad ``arr`` with ``fill`` along ``axis`` (idle-trial padding)."""
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return np.pad(arr, widths, constant_values=fill)


def run_chunks(scan_fn, plan, *, B: int, T: int, d: int, n_max: int,
               mesh, in_specs, A_np, y_np, A_dev, y_dev, com_dev,
               noise_dev, pid_np, stat_np, xs_np):
    """Drive the step core over the batch in plan-sized chunks.

    ``A_dev``/``y_dev`` are the pre-placed chunk-invariant operands
    (the gram plane passes its rows and Gram factors as ``A_dev``);
    non-shared problems upload per-chunk slices of ``A_np``/``y_np``
    instead — a full (B, n_data, d) upfront copy would defeat the chunk
    memory bound.  Returns ``(W, losses, det, extras)`` where
    ``extras`` is ``None`` or a dict holding the device control plane's
    decision trace (q/check/faulty2) and/or the scan's telemetry
    counters under ``"telemetry"``."""
    gram = plan.data_plane == "gram"      # the gram plane stages cw0
    device_mode = plan.control == "device"
    telemetry = getattr(plan, "telemetry", False)
    shared = plan.shared_problem
    ndev = plan.n_devices
    chunk_trials = plan.chunk_trials
    Ie = A_dev["rows"].shape[0] if gram else 0

    if mesh is not None:
        from jax.sharding import NamedSharding

        ns = lambda spec: NamedSharding(mesh, spec)          # noqa: E731

        def dev(x, i):
            if x is None:
                return None
            return jax.device_put(x, jax.tree.map(ns, in_specs[i]))
    else:
        def dev(x, i):
            if x is None:
                return None
            if isinstance(x, dict):
                return {k: jnp.asarray(v) for k, v in x.items()}
            return jnp.asarray(x)

    def _stage(lo: int):
        """H2D-transfer one chunk's per-trial arrays (async)."""
        hi = min(lo + chunk_trials, B)
        with obtrace.span("pipeline.stage", lo=lo, hi=hi) as span_args:
            sl, bs, args, nbytes = _stage_inner(lo, hi)
            span_args["bytes"] = nbytes
        return sl, bs, args

    def _stage_inner(lo: int, hi: int):
        bs = hi - lo
        pad = (-bs) % ndev
        stat_c = {k: pad_rows(v[lo:hi], 0, pad, PAD_FILL.get(k, 0))
                  for k, v in stat_np.items()}
        xs_c = None if xs_np is None else {
            k: pad_rows(v[:, lo:hi], 1, pad, PAD_FILL.get(k, 0))
            for k, v in xs_np.items()}
        W0 = np.zeros((bs + pad, d), np.float32)
        # gram: the slot is S0 = W0 @ rows^T, identically zero because
        # every chunk starts from W0 = 0
        cw0 = np.zeros((bs + pad, Ie), np.float32) if gram else None
        pid_c = None if gram else pad_rows(pid_np[lo:hi], 0, pad)
        host = [W0, cw0, stat_c, xs_c, pid_c]
        if gram or shared:
            A_c, y_c = A_dev, y_dev
        else:
            A_h = pad_rows(A_np[lo:hi], 0, pad)
            y_h = pad_rows(y_np[lo:hi], 0, pad)
            host += [A_h, y_h]
            A_c, y_c = dev(A_h, 0), dev(y_h, 1)
        args = (A_c, y_c, dev(W0, 2), dev(cw0, 3), dev(stat_c, 4),
                dev(xs_c, 5), com_dev, noise_dev, dev(pid_c, 8))
        nbytes = sum(a.nbytes for a in jax.tree.leaves(host))
        return slice(lo, hi), bs, args, nbytes

    W = np.empty((B, d), np.float64)
    losses = np.empty((T, B))
    det = np.empty((T, B), bool)
    if device_mode:
        q_tr = np.empty((T, B), np.float32)
        check_tr = np.empty((T, B), bool)
        faulty2_tr = np.empty((T, B, n_max), bool)
    if telemetry:
        tel_acc = {k: np.zeros(B, np.int64) for k in TEL_KEYS}

    def _drain(sl, bs, out):                     # gathers; blocks
        with obtrace.span("pipeline.drain", lo=sl.start, hi=sl.stop):
            # the host blocked until the chunk's outputs exist: the scan,
            # and before it the end of the chunk's copies to the device,
            # which _stage only enqueues; the conversions below would
            # block on the same outputs anyway
            with obtrace.span("pipeline.wait"):
                jax.block_until_ready(out)
            nbytes = sum(x.nbytes for x in jax.tree.leaves(out))
            with obtrace.span("pipeline.fetch", bytes=nbytes):
                _fetch(sl, bs, out)

    def _fetch(sl, bs, out):
        """D2H of one chunk's outputs into the float64 host results."""
        if telemetry:
            out, telc = out[:-1], out[-1]
            for k in TEL_KEYS:
                tel_acc[k][sl] = np.asarray(telc[k])[:bs]
        if device_mode:
            Wc, lc, qc, cc, dc, fc = out
            q_tr[:, sl] = np.asarray(qc)[:, :bs]
            check_tr[:, sl] = np.asarray(cc)[:, :bs]
            faulty2_tr[:, sl] = np.asarray(fc)[:, :bs]
        else:
            Wc, lc, dc = out
        W[sl] = np.asarray(Wc, np.float64)[:bs]
        losses[:, sl] = np.asarray(lc, np.float64)[:, :bs]
        det[:, sl] = np.asarray(dc)[:, :bs]

    staged = _stage(0)
    inflight = None
    while staged is not None:
        sl, bs, args = staged
        with obtrace.span("pipeline.dispatch", lo=sl.start, hi=sl.stop):
            out = scan_fn(*args)                 # async dispatch
        nxt = sl.stop if sl.stop < B else None
        staged = _stage(nxt) if nxt is not None else None
        if inflight is not None:
            _drain(*inflight)                    # backpressure point
        inflight = (sl, bs, out)
    if inflight is not None:
        _drain(*inflight)

    extras = {}
    if device_mode:
        extras.update(q=q_tr, check=check_tr, faulty2=faulty2_tr)
    if telemetry:
        extras["telemetry"] = tel_acc
    return W, losses, det, extras or None
