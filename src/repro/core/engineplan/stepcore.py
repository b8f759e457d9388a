"""One parameterized scan step for every engine data-plane path.

:func:`step_core` is the protocol loop for both control planes and both
data planes: ``control: "host" | "device"`` and ``gram: bool`` are
jit-static *configuration*, and the shared step-epilogue closures
(``contract`` / ``agg`` / ``symbols`` / ``vote_part``) are built once.

The ``gram: bool`` static selects the gram-domain data plane on top of
either control plane: the scan carry is residual *coefficients* only
(``C_t`` with ``W_t = W_0 - C_t @ rows``), residual symbols come from
the precomputed Gram factors as ``S_0 - C_t @ G`` (``ops.gram_factors``),
and ``d`` is touched exactly once after the scan — the post-scan
contraction materializing ``W_T``.  Per-step cost is O(B·I²) with no
(B, d) traffic at all.  The stream plane (``gram=False``) carries the
(B, d) iterate and pays two d-sized contractions a step.

Unified signature (unused slots are ``None``, an empty pytree under
jit/shard_map, so one argument layout serves every path)::

    step_core(A, y, W0, cw0, stat, xs, com, noisevec, pid, *,
              control, shared, has_filter, has_bias, impl,
              gram=False)

=====  ======================  =========================================
slot   stream                  gram
=====  ======================  =========================================
A      (n_data, d) or          {"rows": (Ie, d), "G": (Ie, Ie)}
       (B, n_data, d) matrix
cw0    None                    (B, Ie) starting symbols S_0
xs     (T, B, ...) host        as stream
       schedule; None under
       device control
com    per-step sketch tables  per-step sketch tables of the rows;
       of the data rows;       device control adds "tix"
       device control adds
       "tix"
=====  ======================  =========================================

Outputs: host control -> ``(W, losses, det)``; device control ->
``(W, losses, q_tr, check_tr, det_tr, faulty2_tr)`` (the decision trace
the host replays exactly via ``engine.replay_control_from_trace``).

The physics of each path (why the folding is exact, the HBM-pass
accounting, the counter-RNG contract) is documented in
docs/architecture.md and docs/performance.md.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import adaptive, rngstream
from repro.core.detection import detect_groups_batched
from repro.obs.telemetry import TEL_KEYS

TAU_VOTE = 1e-9       # matches majority_vote_np(tau=1e-9) in both engines
TAU_DETECT = 1e-9     # matches the engine's absolute replica compare

_PH1 = np.uint32(1 << 16)     # phase-1 counter bit (identify pass)

# Every contraction below states full f32 precision.  On a TPU, XLA's
# default for f32 operands multiplies in bf16 passes; that broke the
# values contract with the float64 numpy engine (max deviation 1e-4)
# on the chip, by up to 0.6 on the gram plane.
HIGHEST = jax.lax.Precision.HIGHEST


def shard_mask(shard, group, m, n_data):
    """(B, n) shard layout -> (B, n, I) f32 row-ownership mask.

    Row i belongs to worker w iff i // rows == shard[w] (contiguous
    shards of rows = I // m rows each; remainder rows dropped), and w is
    a group member.  This is ``shard_batch_indices`` as a dense mask.
    """
    rows = n_data // jnp.maximum(m, 1)                         # (B,)
    i = jnp.arange(n_data, dtype=jnp.int32)
    owner = i[None, :] // jnp.maximum(rows, 1)[:, None]        # (B, I)
    used = i[None, :] < (m * rows)[:, None]
    mask = (owner[:, None, :] == shard[:, :, None]) \
        & used[:, None, :] & (group >= 0)[:, :, None]
    return mask.astype(jnp.float32), rows


def apply_affine(g, tam, alpha, beta, nu, noisevec, has_bias: bool):
    """Masked affine Byzantine attacks on a (B, n, d) gradient stack."""
    tam3 = tam[:, :, None]
    out = jnp.where(tam3, alpha[:, None, None] * g, g)
    if has_bias:
        add = beta[:, None, None] + nu[:, None, None] * noisevec[None, None]
        out = out + jnp.where(tam3, add, 0.0)
    return out


def masked_median(g, act):
    """Coordinate-wise median over each trial's active workers."""
    B = g.shape[0]
    x = jnp.where(act[:, :, None], g, jnp.inf)
    x = jnp.sort(x, axis=1)
    cnt = act.sum(axis=1)
    lo = jnp.maximum((cnt - 1) // 2, 0)
    hi = jnp.maximum(cnt // 2, 0)
    rows = jnp.arange(B)
    return 0.5 * (x[rows, lo] + x[rows, hi])


def masked_krum(g, act, f):
    """KRUM (m=1) over each trial's active workers, inactive rows masked
    out of distances, scores and the argmin — same winner as
    ``filters.krum`` on the active subset (ascending worker order)."""
    B, n, d = g.shape
    diff = g[:, :, None, :] - g[:, None, :, :]
    d2 = (diff * diff).sum(-1)                                  # (B, n, n)
    pair_ok = act[:, :, None] & act[:, None, :]
    d2 = jnp.where(pair_ok, d2, 1e30) + jnp.eye(n) * 1e30
    cnt = act.sum(axis=1)                                       # (B,)
    kth = jnp.clip(cnt - f - 2, 1, n)                           # (B,)
    s = jnp.sort(d2, axis=2)
    csum = jnp.cumsum(s, axis=2)
    rows = jnp.arange(B)
    scores = csum[rows[:, None], jnp.arange(n)[None, :],
                  jnp.minimum(kth - 1, n - 1)[:, None]]         # (B, n)
    scores = jnp.where(act, scores, jnp.inf)
    best = jnp.argmin(scores, axis=1)
    return g[rows, best]


def masked_mean(g, act):
    cnt = jnp.maximum(act.sum(axis=1), 1)
    return (g * act[:, :, None]).sum(axis=1) / cnt[:, None]


def step_core(A, y, W0, cw0, stat, xs, com, noisevec, pid, *,
              control: str, shared: bool, has_filter: bool,
              has_bias: bool, impl: str | None, gram: bool = False,
              telemetry: bool = False):
    """The protocol loop: scan the schedule (or the in-scan control
    plane) over iterations, configured by jit-static flags.

    Every iteration pays only two d-sized contractions on the stream
    plane and none on the gram plane.  Honest replicas are copies and
    attacks are affine, so the whole "shard grads → tamper →
    aggregate/vote" pipeline folds into per-row residual coefficients;
    detection symbols and vote agreement run in the k-dim sketch domain
    by the same linearity.  A replica group's
    symbols are bitwise equal exactly when its full gradients are, so
    symbol-domain winners match the numpy engine's full-vector vote
    outside the detectability floor.  Nothing of shape (B, n, d) is
    ever materialized, except for the genuinely nonlinear
    gradient-filter baselines (compiled only when present).

    ``telemetry=True`` (jit-static) threads a ``{TEL_KEYS: (B,) int32}``
    counters dict through the scan carry — a handful of masked integer
    adds per step, no extra d-sized work, no effect on the primary
    outputs — and appends it to the return tuple."""
    from repro.kernels import ops

    n_data = y.shape[-1]
    B = W0.shape[0]
    lr, alpha, beta, nu = stat["lr"], stat["alpha"], stat["beta"], stat["nu"]
    # the gram plane carries per-row residual coefficients instead of
    # (B, d) update values, through the tuple-valued agg/vote epilogue
    if gram:
        Ie = A["rows"].shape[0]
        Gn = A["G"][:, :n_data]          # symbol columns the scan reads
        S0n = cw0[:, :n_data]

    # ---- shared step epilogue, parameterized by the statics -------------

    def contract(cr):                  # (B, I) row weights -> (B, d)
        if shared:
            return jnp.einsum("bi,id->bd", cr, A, precision=HIGHEST)
        return ops.batched_coded_encode(cr[:, None, :], A, impl=impl)[:, 0]

    def agg(agg_coeff, tam, mask, cr_base):
        """(B, n) aggregation coefficients -> the update, with the
        affine attacks folded in: sum_w coeff_w * attack_w(g_w).
        The stream plane returns the (B, d) update value; the gram plane
        returns the residual-coefficient row (B, I) plus its two bias
        coefficients (the ones-row / noise-row columns of the extended
        contraction) for the gram carry to apply."""
        aeff = jnp.where(tam, alpha[:, None], 1.0) * agg_coeff
        row = jnp.einsum("bw,bwi->bi", aeff, mask,
                         precision=HIGHEST) * cr_base
        if gram:
            tw = agg_coeff * tam
            return row, (tw * beta[:, None]).sum(axis=1), \
                (tw * nu[:, None]).sum(axis=1)
        upd = contract(row)
        if has_bias:
            tw = agg_coeff * tam
            upd = upd + (tw * beta[:, None]).sum(axis=1)[:, None] \
                + (tw * nu[:, None]).sum(axis=1)[:, None] * noisevec[None]
        return upd

    def symbols(mask, cr_base, tam, SA_b, sk_one, sk_noise):
        """Per-worker detection symbols: sketch linearity turns the
        worker's gradient sketch into its coefficient row times the
        pre-sketched data rows; attacks act affinely on symbols too.
        ``SA_b`` is (I, k) on the gram plane (the gram precompute's
        per-step table) and (B, I, k) on the stream plane (per-problem
        tables gathered by ``pid``)."""
        C = mask * cr_base[:, None, :]                       # (B, n, I)
        if gram:
            skw = jnp.einsum("bwi,ik->bwk", C, SA_b, precision=HIGHEST)
        else:
            skw = jnp.einsum("bwi,bik->bwk", C, SA_b, precision=HIGHEST)
        if gram or has_bias:
            add = beta[:, None, None] * sk_one[None, None] \
                + nu[:, None, None] * sk_noise[None, None]
        else:
            add = 0.0
        return jnp.where(tam[:, :, None],
                         alpha[:, None, None] * skw + add, skw)

    def acc(u, v):                     # update accumulation, either plane
        if gram:
            return (u[0] + v[0], u[1] + v[1], u[2] + v[2])
        return u + v

    def upd_zeros():                   # the additive identity of acc()
        if gram:
            return (jnp.zeros((B, n_data)), jnp.zeros(B), jnp.zeros(B))
        return jnp.zeros_like(W0)

    def fold_coeff(upd, live):
        """Gram-plane epilogue: (row, b1, b2) -> the (B, Ie)
        coefficient increment with lr and the live mask folded in (a
        dead trial's row is exactly zero, so its post-scan iterate
        stays bitwise intact)."""
        row_u, b1, b2 = upd
        scale = jnp.where(live, lr, 0.0)
        return jnp.concatenate(
            [row_u, b1[:, None], b2[:, None],
             jnp.zeros((B, Ie - n_data - 2))], axis=1) * scale[:, None]

    # ---- device control plane: decisions made inside the scan ----------

    if control == "device":
        n_max = stat["byz"].shape[1]
        p32 = stat["p"]
        wi_b = jnp.broadcast_to(jnp.arange(n_max, dtype=jnp.uint32),
                                (B, n_max))
        zero_u = jnp.zeros((B,), jnp.uint32)

        def device_step(carry, c):
            # carry[0] is the (B, d) iterate W — or, on the gram plane,
            # the (B, Ie) coefficient matrix C with W = W0 - C @ rows
            if telemetry:
                (W, active, kappa), tel = carry
            else:
                W, active, kappa = carry
            t = c["tix"]
            t32 = t.astype(jnp.uint32)
            live = t < stat["steps"]                          # (B,)
            if gram:
                SA_b = c["SA"]
            else:
                SA_b = c["SA"][pid]
            sk_one, sk_noise = c["sk_one"], c["sk_noise"]

            if gram:
                resid = S0n - jnp.dot(
                    W, Gn, precision=HIGHEST,
                    preferred_element_type=jnp.float32) - y[None, :]
            elif shared:
                resid = jnp.einsum("id,bd->bi", A, W,
                                   precision=HIGHEST) - y[None, :]
            else:
                resid = jnp.einsum("bid,bd->bi", A, W,
                                   precision=HIGHEST) - y
            loss = (resid * resid).mean(axis=1)

            # -- q*_t and the check coin (rngstream DECIDE) ------------
            f_t = jnp.maximum(stat["f0"] - kappa, 0)          # (B,) i32
            lam = adaptive.lam_from_loss_arr(loss, jnp)
            qad = adaptive.q_star_arr(f_t, p32, lam, jnp)
            qvec = jnp.where(stat["qcode"] == 1, jnp.float32(1.0),
                             stat["qfix"])
            qvec = jnp.where(f_t > 0, qvec, 0.0)
            q_t = jnp.where(stat["qcode"] == 3, qad,
                            jnp.where(stat["qcode"] == 0, 0.0, qvec))
            q_t = q_t.astype(jnp.float32)
            db, _ = rngstream.threefry2x32(stat["dk0"], stat["dk1"],
                                           jnp.broadcast_to(t32, (B,)),
                                           zero_u)
            check = live & (rngstream.uniform01(db) < q_t)

            # -- tamper coins, both phases (rngstream TAMPER) ----------
            tb0, _ = rngstream.threefry2x32(stat["tk0"][:, None],
                                            stat["tk1"][:, None], t32, wi_b)
            tb1, _ = rngstream.threefry2x32(stat["tk0"][:, None],
                                            stat["tk1"][:, None], t32,
                                            _PH1 | wi_b)
            elig = stat["byz"] & (live & (t >= stat["onset"]))[:, None]
            tam1 = elig & (rngstream.uniform01(tb0) < p32[:, None])

            # -- phase-1 layout: masked regroup when checking, else fast
            pk0, _ = rngstream.threefry2x32(stat["pk0"][:, None],
                                            stat["pk1"][:, None], t32, wi_b)
            pk1, _ = rngstream.threefry2x32(stat["pk0"][:, None],
                                            stat["pk1"][:, None], t32,
                                            _PH1 | wi_b)
            r1 = jnp.maximum(f_t, 1) + 1
            sh_c, gr_c, m_c = ops.batched_regroup(pk0, active, r1)
            rank = jnp.cumsum(active, axis=1, dtype=jnp.int32) - 1
            n_act = active.sum(axis=1).astype(jnp.int32)
            chk = check[:, None]
            shard1 = jnp.where(chk, sh_c, jnp.where(active, rank, 0))
            group1 = jnp.where(chk, gr_c, jnp.where(active, rank, -1))
            group1 = jnp.where(live[:, None], group1, -1)
            m1 = jnp.where(check, m_c, n_act)
            mask1, rows1 = shard_mask(shard1, group1, m1, n_data)
            cr1 = resid * (2.0 / rows1)[:, None]

            # -- detection verdict on sketch symbols -------------------
            skt1 = symbols(mask1, cr1, tam1, SA_b, sk_one, sk_noise)
            fault, _ = detect_groups_batched(skt1, group1, tau=TAU_DETECT)
            det = check & fault

            # -- aggregation (fast + clean-check; detect trials defer) -
            w_per = 1.0 / jnp.maximum(m1 * jnp.where(check, r1, 1),
                                      1).astype(jnp.float32)
            aggw = jnp.where(group1 >= 0, w_per[:, None], 0.0)
            aggw = jnp.where(det[:, None], 0.0, aggw)
            upd = agg(aggw, tam1, mask1, cr1)

            # -- identify round: regroup at 2 max(f_t,1)+1, vote,
            #    eliminate ---------------------------------------------
            tam2 = det[:, None] & elig \
                & (rngstream.uniform01(tb1) < p32[:, None])
            r2 = 2 * jnp.maximum(f_t, 1) + 1

            def identify(_):
                sh2, gr2, m2 = ops.batched_regroup(pk1, active, r2)
                gr2 = jnp.where(det[:, None], gr2, -1)
                mask2, rows2 = shard_mask(sh2, gr2, m2, n_data)
                cr2 = resid * (2.0 / rows2)[:, None]
                skt2 = symbols(mask2, cr2, tam2, SA_b, sk_one, sk_noise)
                wc, faulty = ops.batched_vote(skt2, gr2, tau=TAU_VOTE,
                                              impl=impl)
                coeff = jnp.where(det[:, None],
                                  wc / jnp.maximum(m2, 1)[:, None], 0.0)
                return agg(coeff, tam2, mask2, cr2), \
                    det[:, None] & faulty & (gr2 >= 0)

            upd2, faulty2 = jax.lax.cond(
                det.any(), identify,
                lambda _: (upd_zeros(), jnp.zeros((B, n_max), bool)),
                None)
            upd = acc(upd, upd2)

            if gram:
                W = W + fold_coeff(upd, live)
            else:
                W = jnp.where(live[:, None], W - lr[:, None] * upd, W)
            act_pre = active
            active = active & ~faulty2
            kappa = kappa + faulty2.sum(axis=1).astype(kappa.dtype)
            new_carry = (W, active, kappa)
            if telemetry:
                # device control has no deterministic vote schedule, so
                # redundant/vote/identify all trace back to the check
                # coin.  Tamper coins fire unconditionally in the scan
                # (counter RNG) — only hits on still-active workers are
                # real injections (the oracle's streams draw for active
                # byz only); byz_active counts post-elimination
                # (recorder timing).
                i32 = jnp.int32
                det32 = det.astype(i32)
                tel = {
                    "steps": tel["steps"] + live.astype(i32),
                    "checks": tel["checks"] + check.astype(i32),
                    "redundant_steps": tel["redundant_steps"]
                    + check.astype(i32),
                    "detects": tel["detects"] + det32,
                    "identify_rounds": tel["identify_rounds"] + det32,
                    "vote_rounds": tel["vote_rounds"] + det32,
                    "eliminations": tel["eliminations"]
                    + faulty2.sum(axis=1).astype(i32),
                    "tamper_events": tel["tamper_events"]
                    + ((tam1 & act_pre).sum(axis=1)
                       + (tam2 & act_pre).sum(axis=1)).astype(i32),
                    "byz_active_steps": tel["byz_active_steps"]
                    + (stat["byz"] & active
                       & live[:, None]).sum(axis=1).astype(i32),
                }
                new_carry = (new_carry, tel)
            return new_carry, (loss, jnp.where(live, q_t, 0.0),
                               check, det, faulty2)

        init = (jnp.zeros_like(cw0) if gram else W0,
                stat["act0"], jnp.zeros(B, jnp.int32))
        if telemetry:
            init = (init, {k: jnp.zeros(B, jnp.int32) for k in TEL_KEYS})
            ((W, _, _), tel), ys = jax.lax.scan(device_step, init, com)
        else:
            (W, _, _), ys = jax.lax.scan(device_step, init, com)
            tel = None
        if gram:
            # the only d-sized work of the whole run: W_T = W0 - C_T @ R
            W = W0 - jnp.dot(W, A["rows"].astype(jnp.float32),
                             precision=HIGHEST,
                             preferred_element_type=jnp.float32)
        losses, q_tr, check_tr, det_tr, faulty2_tr = ys
        if telemetry:
            return W, losses, q_tr, check_tr, det_tr, faulty2_tr, tel
        return W, losses, q_tr, check_tr, det_tr, faulty2_tr

    # ---- host control plane: scan the precomputed schedule -------------

    fcode, farr = stat["fcode"], stat["farr"]

    def host_step(carry, xc):
        if telemetry:
            carry, tel = carry
        if gram:
            # NO d-sized pass at all: symbols of W_t = W0 - C_t @ rows
            # come from the precomputed Gram factors, O(B·I²)
            W = carry                                        # C_t (B, Ie)
            x, c = xc
            resid = S0n - jnp.dot(
                W, Gn, precision=HIGHEST,
                preferred_element_type=jnp.float32) - y[None, :]
            SA_b = c["SA"]
            sk_one, sk_noise = c["sk_one"], c["sk_noise"]
        else:
            W = carry
            x, c = xc
            if shared:
                resid = jnp.einsum("id,bd->bi", A, W,
                                   precision=HIGHEST) - y[None, :]
            else:
                resid = jnp.einsum("bid,bd->bi", A, W,
                                   precision=HIGHEST) - y
            SA_b = c["SA"][pid]
            sk_one, sk_noise = c["sk_one"], c["sk_noise"]
        loss = (resid * resid).mean(axis=1)

        mask1, rows1 = shard_mask(x["shard1"], x["group1"], x["m1"],
                                  n_data)
        cr1 = resid * (2.0 / rows1)[:, None]                 # (B, I)

        # -- weighted aggregation (fast + clean-check trials) ----------
        upd = agg(x["aggw"], x["tam1"], mask1, cr1)

        # -- detection symbols + on-device check verdicts --------------
        skt1 = symbols(mask1, cr1, x["tam1"], SA_b, sk_one, sk_noise)
        fault, _ = detect_groups_batched(skt1, x["group1"], tau=TAU_DETECT)
        det = x["checks"] & fault

        # -- majority votes (draco every step; identify rounds rare) ---
        def vote_part(shard, group, m, tam, gate, skt=None, mask=None,
                      cr=None, count_elim=False):
            def compute(_):
                if skt is None:
                    mask_, rows_ = shard_mask(shard, group, m, n_data)
                    cr_ = resid * (2.0 / rows_)[:, None]
                    skt_ = symbols(mask_, cr_, tam, SA_b, sk_one,
                                   sk_noise)
                else:
                    mask_, cr_, skt_ = mask, cr, skt
                gv = jnp.where(gate[:, None], group, -1)
                wc, faulty = ops.batched_vote(skt_, gv, tau=TAU_VOTE,
                                              impl=impl)
                coeff = jnp.where(gate[:, None],
                                  wc / jnp.maximum(m, 1)[:, None], 0.0)
                out = agg(coeff, tam, mask_, cr_)
                if count_elim:
                    # the vote's outvoted workers are this step's
                    # eliminations (the host schedule applied them when
                    # building later steps; here we just count)
                    elim = (gate[:, None] & faulty
                            & (gv >= 0)).sum(axis=1).astype(jnp.int32)
                    return out, elim
                return out

            def skip(_):
                if count_elim:
                    return upd_zeros(), jnp.zeros(B, jnp.int32)
                return upd_zeros()

            return jax.lax.cond(gate.any(), compute, skip, None)

        upd = acc(upd, vote_part(x["shard1"], x["group1"], x["m1"],
                                 x["tam1"], x["vote1"], skt=skt1,
                                 mask=mask1, cr=cr1))
        if telemetry:
            upd2, elim2 = vote_part(x["shard2"], x["group2"], x["m2"],
                                    x["tam2"], x["identify"],
                                    count_elim=True)
        else:
            upd2 = vote_part(x["shard2"], x["group2"], x["m2"],
                             x["tam2"], x["identify"])
        upd = acc(upd, upd2)

        # -- gradient-filter baselines (genuinely need the stack;
        #    the plan gate keeps them on the stream plane) -------------
        if has_filter:
            C = mask1 * cr1[:, None, :]
            if shared:
                g1 = jnp.einsum("bwi,id->bwd", C, A, precision=HIGHEST)
            else:
                g1 = jnp.einsum("bwi,bid->bwd", C, A, precision=HIGHEST)
            gt1 = apply_affine(g1, x["tam1"], alpha, beta, nu, noisevec,
                               has_bias)
            act = x["active"] & x["live"][:, None]
            fupd = jnp.where((fcode == 1)[:, None],
                             masked_median(gt1, act),
                             masked_mean(gt1, act))
            fupd = jnp.where((fcode == 2)[:, None],
                             masked_krum(gt1, act, farr), fupd)
            upd = jnp.where((fcode >= 0)[:, None], fupd, upd)

        if gram:
            new_carry = W + fold_coeff(upd, x["live"])
        else:
            new_carry = jnp.where(x["live"][:, None],
                                  W - lr[:, None] * upd, W)
        if telemetry:
            # the schedule already masked every event array by liveness,
            # so the counters are straight masked sums of what the host
            # recorder wrote — integer-exact against the numpy oracle
            i32 = jnp.int32
            tel = {
                "steps": tel["steps"] + x["live"].astype(i32),
                "checks": tel["checks"] + x["checks"].astype(i32),
                "redundant_steps": tel["redundant_steps"]
                + (x["checks"] | x["vote1"]).astype(i32),
                "detects": tel["detects"] + det.astype(i32),
                "identify_rounds": tel["identify_rounds"]
                + x["identify"].astype(i32),
                "vote_rounds": tel["vote_rounds"]
                + (x["identify"] | x["vote1"]).astype(i32),
                "eliminations": tel["eliminations"] + elim2,
                "tamper_events": tel["tamper_events"]
                + (x["tam1"].sum(axis=1)
                   + x["tam2"].sum(axis=1)).astype(i32),
                "byz_active_steps": tel["byz_active_steps"]
                + (stat["byz"] & x["active"]
                   & x["live"][:, None]).sum(axis=1).astype(i32),
            }
            return (new_carry, tel), (loss, det)
        return new_carry, (loss, det)

    init = jnp.zeros_like(cw0) if gram else W0
    if telemetry:
        init = (init, {k: jnp.zeros(B, jnp.int32) for k in TEL_KEYS})
        (fin, tel), (losses, det) = jax.lax.scan(host_step, init, (xs, com))
    else:
        fin, (losses, det) = jax.lax.scan(host_step, init, (xs, com))
        tel = None
    if gram:
        # the only d-sized work of the whole run: W_T = W0 - C_T @ R
        W = W0 - jnp.dot(fin, A["rows"].astype(jnp.float32),
                         precision=HIGHEST,
                         preferred_element_type=jnp.float32)
    else:
        W = fin
    if telemetry:
        return W, losses, det, tel
    return W, losses, det


# the single-device entry: one jit whose cache keys on the plan statics.
# Per-chunk buffers (W0, cw0, stat, xs) are freshly uploaded each chunk
# and donated; chunk-invariant operands (A/rows, y, com, noisevec, pid)
# are reused and never donated.
jitted_step_core = functools.partial(
    jax.jit,
    static_argnames=("control", "shared", "has_filter",
                     "has_bias", "impl", "gram", "telemetry"),
    donate_argnames=("W0", "cw0", "stat", "xs"),
)(step_core)
