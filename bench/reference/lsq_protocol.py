"""Plain reference of the randomized reactive redundancy protocol on a
noiseless least-squares problem: one trial, one Python loop, float64.

It follows the paper's scheme step by step (Gupta & Vaidya, arXiv
1912.09528, §4.2-4.3) with the same seeded random streams a user of the
engine states through a trial's ``seed`` and ``problem_seed``:

- the problem: ``default_rng(problem_seed)`` draws A (n_data, d), then
  w* (d,); y = A w*;
- check coins: ``default_rng(SeedSequence([seed, 0x0DEC1DE]))``, one draw
  per step while checks are possible;
- replica-group permutations: ``default_rng(seed)``;
- tamper coins: ``default_rng(seed + 1)``, one draw per Byzantine worker
  per gradient round.

Those are the "host" streams.  The "device" streams are the
counter-indexed ones a control plane inside a jitted scan draws: every
coin and permutation key is one threefry-2x32 block of (seed, stream tag,
step, phase, worker), the coins are the top 24 bits scaled by 2^-24 and
compared in float32.  ``streams`` picks one; a run is judged against the
streams of the control plane it reports.

Workers compute least-squares shard gradients 2/rows * A_s^T (A_s w -
y_s) on contiguous shards of the rows; a check replicates each shard on
f_t + 1 workers and compares replicas; a detected fault triggers the
identify round on 2 f_t + 1 replicas of each shard and a majority vote,
whose winners form the update and whose losers are removed.

``dot`` is the one place where products are formed and ``dtype`` the
one type of every array.  The reference is ``np.matmul`` in float64; its
float32 run measures how far a trial amplifies float32 rounding; the
benchmark's control passes a float32 matmul that rounds like a lower
precision, and nothing else changes.

Nothing here imports the system under test.
"""
from __future__ import annotations

import math

import numpy as np

# per-worker affine tampering g -> alpha * g + beta, the attacks a trial
# may name
ATTACKS = {
    "none": (1.0, 0.0),
    "sign_flip": (-5.0, 0.0),
    "scale": (10.0, 0.0),
    "drift": (1.0, 1.0),
    "zero": (0.0, 0.0),
}
DETECT_TOL = 1e-9          # replicas differ when any |a - b| exceeds it
VOTE_TOL = 1e-9            # vote agreement: |a - b| <= tol * (1 + min|a|,|b|)


class HostStreams:
    def __init__(self, seed: int, steps: int, n: int, p: float):
        self.perm_rng = np.random.default_rng(seed)
        self.coin_rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 0x0DEC1DE]))
        self.tamper_rng = np.random.default_rng(seed + 1)
        self.p = p

    def coin(self, t: int, q: float) -> bool:
        return bool(self.coin_rng.random() < q)

    def permute(self, idx: np.ndarray, t: int, phase: int) -> np.ndarray:
        return self.perm_rng.permutation(idx)

    def tampers(self, w: int, t: int, phase: int) -> bool:
        return bool(self.tamper_rng.random() < self.p)


_DECIDE, _TAMPER, _PERM = 0x0DEC1DE5, 0x7A39B013, 0x9E3779B1
_ROT = (13, 15, 26, 6, 17, 29, 16, 24)


def threefry2x32(k0, k1, c0, c1):
    """The 20-round threefry-2x32 block over uint32 arrays."""
    u = np.uint32
    ks = (k0, k1, k0 ^ k1 ^ u(0x1BD11BDA))
    x0, x1 = c0 + ks[0], c1 + ks[1]
    for r in range(5):
        for rot in _ROT[4 * (r % 2): 4 * (r % 2) + 4]:
            x0 = x0 + x1
            x1 = ((x1 << u(rot)) | (x1 >> u(32 - rot))) ^ x0
        x0 = x0 + ks[(r + 1) % 3]
        x1 = x1 + ks[(r + 2) % 3] + u(r + 1)
    return x0, x1


class DeviceStreams:
    def __init__(self, seed: int, steps: int, n: int, p: float):
        s = int(seed) & 0xFFFFFFFFFFFFFFFF
        lo, hi = np.uint32(s & 0xFFFFFFFF), np.uint32(s >> 32)
        shape = (steps, 2, n)
        t = np.broadcast_to(np.arange(steps, dtype=np.uint32)[:, None, None],
                            shape).copy()
        c1 = np.broadcast_to(
            np.arange(2, dtype=np.uint32)[None, :, None] << np.uint32(16)
            | np.arange(n, dtype=np.uint32)[None, None, :], shape).copy()

        def block(tag, c0, c1):
            return threefry2x32(np.full(c0.shape, lo),
                                np.full(c0.shape, hi ^ np.uint32(tag)),
                                c0, c1)[0]

        steps_ = np.arange(steps, dtype=np.uint32)
        self.u_coin = _uniform(block(_DECIDE, steps_, np.zeros_like(steps_)))
        self.u_tamper = _uniform(block(_TAMPER, t, c1))
        self.perm_keys = block(_PERM, t, c1)
        self.p32 = np.float32(p)

    def coin(self, t: int, q: float) -> bool:
        return bool(self.u_coin[t] < np.float32(q))

    def permute(self, idx: np.ndarray, t: int, phase: int) -> np.ndarray:
        return idx[np.argsort(self.perm_keys[t, phase, idx], kind="stable")]

    def tampers(self, w: int, t: int, phase: int) -> bool:
        return bool(self.u_tamper[t, phase, w] < self.p32)


def _uniform(bits):
    return (bits >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)


STREAMS = {"host": HostStreams, "device": DeviceStreams}


def make_problem(n_data: int, d: int, seed: int):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n_data, d))
    w_true = rng.normal(size=d)
    return A, A @ w_true


def q_star(f_t: int, p: float, loss: float) -> float:
    """Adaptive check probability (paper §4.3, eqs. 4-5), closed form."""
    if f_t <= 0:
        return 0.0
    lam = 1.0 - math.exp(-max(0.0, float(loss)))
    a = 2.0 * f_t / (2.0 * f_t + 1.0)
    b = 1.0 - (1.0 - p) ** f_t
    if b == 0.0:
        return 0.0
    lam = min(max(lam, 0.0), 1.0)
    denom = (1.0 - lam) * a * a + lam * b * b
    if denom == 0.0:
        return 0.0
    return min(1.0, max(0.0, lam * b * b / denom))


def _groups(active: np.ndarray, r: int, permute=None) -> list[np.ndarray]:
    """Active workers, permuted when ``permute`` is given, cut into
    groups of ``r``; leftovers idle."""
    idx = np.flatnonzero(active)
    if permute is not None:
        idx = permute(idx)
    m = len(idx) // r
    if m == 0:
        raise ValueError(f"{len(idx)} active workers, replication {r}")
    return [np.sort(idx[g * r:(g + 1) * r]) for g in range(m)]


def _vote(reps: np.ndarray):
    """Majority vote over (r, d) replicas, compared in float32: the value
    of the first replica a strict majority agrees with, and the replicas
    that disagree with it."""
    reps = np.asarray(reps, np.float32)
    a, b = reps[:, None], reps[None, :]
    agree = (np.abs(a - b)
             <= VOTE_TOL * (1.0 + np.minimum(np.abs(a), np.abs(b)))).all(-1)
    major = agree.sum(1) > reps.shape[0] // 2
    win = int(np.argmax(major))
    return reps[win], ~agree[win] & bool(major.any())


def run_trial(spec: dict, *, streams: str = "host", dot=np.matmul,
              dtype=np.float64, problem=None) -> dict:
    """Run one trial; returns the final iterate, the loss before every
    step, and the control record (check and identify steps, the step each
    worker was identified at, gradients used and computed, q per step).

    ``problem``: (A, y) as ``make_problem`` gives them, to share one draw
    between trials of the same ``problem_seed``."""
    n, f = spec["n"], spec["f"]
    byz = tuple(spec["byz"])
    alpha, beta = ATTACKS[spec["attack"]]
    p, q_fixed, lr = spec["p_tamper"], spec["q"], spec["lr"]
    seed = spec["seed"]
    if problem is None:
        problem = make_problem(spec["n_data"], spec["d"], spec["problem_seed"])
    A, y = (np.asarray(x, dtype) for x in problem)
    n_data, d = A.shape
    rs = STREAMS[streams](seed, spec["steps"], n, p)
    active = np.ones(n, bool)
    identified_at: dict[int, int] = {}
    w = np.zeros(d, dtype)
    out = {"losses": [], "q": [], "checks": [], "detected": [],
           "used": 0, "computed": 0}

    def gradients(groups, resid, t, phase):
        """Every worker's gradient for this layout (idle workers: 0),
        after the Byzantine workers' tamper coins."""
        m = len(groups)
        rows = n_data // m
        grads = np.zeros((n, d), dtype)
        for s, members in enumerate(groups):
            sl = slice(s * rows, (s + 1) * rows)
            g = 2.0 * dot(resid[None, sl], A[sl])[0] / rows
            grads[members] = g
        for b in byz:
            if active[b] and rs.tampers(b, t, phase):
                grads[b] = alpha * grads[b] + beta
        return grads

    def mean_of(groups, grads):
        m = len(groups)
        r = len(groups[0])
        weight = np.zeros(n, np.float32)
        for members in groups:
            weight[members] = np.float32(1.0 / (r * m))
        return dot(weight[None].astype(dtype), grads)[0]

    for t in range(spec["steps"]):
        resid = dot(A, w[:, None])[:, 0] - y
        loss = float((resid.astype(np.float64) ** 2).mean())
        out["losses"].append(loss)
        f_t = max(0, f - len(identified_at))
        if f_t == 0:
            q = 0.0
        elif q_fixed is not None:
            q = float(q_fixed)
        else:
            q = q_star(f_t, p, loss)
        out["q"].append(q)
        checked = rs.coin(t, q)
        detected = False
        if checked:
            groups = _groups(active, max(1, f_t) + 1,
                             lambda i: rs.permute(i, t, 0))
            grads = gradients(groups, resid, t, 0)
            out["used"] += len(groups)
            out["computed"] += sum(len(g) for g in groups)
            detected = any(np.abs(grads[g] - grads[g[0]]).max() > DETECT_TOL
                           for g in groups)
            if detected:
                groups = _groups(active, 2 * max(1, f_t) + 1,
                                 lambda i: rs.permute(i, t, 1))
                grads = gradients(groups, resid, t, 1)
                out["used"] += len(groups)
                out["computed"] += sum(len(g) for g in groups)
                votes, newly = [], set()
                for members in groups:
                    value, faulty = _vote(grads[members])
                    votes.append(value)
                    newly |= {int(x) for x in members[faulty]}
                for b in sorted(newly):
                    identified_at[b] = t
                    active[b] = False
                grad = np.mean(votes, axis=0).astype(dtype)
            else:
                grad = mean_of(groups, grads)
        else:
            groups = _groups(active, 1)
            grads = gradients(groups, resid, t, 0)
            out["used"] += len(groups)
            out["computed"] += len(groups)
            grad = mean_of(groups, grads)
        out["checks"].append(checked)
        out["detected"].append(detected)
        w = w - dtype(lr) * grad.astype(dtype)
    out["w"] = w
    out["identified_at"] = identified_at
    return out
