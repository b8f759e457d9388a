"""The fast and check steps with the gradient reduced in the backward pass
(``repro.train.gradtap``) against the same steps with the whole-tree
reduction, recomputed here: value_and_grad -> maybe_tamper -> psum (run
in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=4).

Tiny float32 models, each with a group of repeated layers and a tail
group: a tied one whose nine repeats keep their ``lax.scan`` and whose
lookup takes the sparse route (the rows of all workers fewer than the
vocabulary), and untied ones whose two repeats are unrolled, with a
vocabulary that sends the lookup to the dense route (``untied``) or to
the sparse one unless the attack adds a bias (``untied512``).  Each case runs one fast and one check
step from the same state by both reductions, worker 3 Byzantine with its
tampering forced on (none in the honest case); the replicas of a check
group share their tokens, so only a tampering worker's group is
faulty.  With ``tied`` also the noise attack's draws and the gauges of
the bytes reduced in and after the backward pass.

In ``bf16`` the model runs in bfloat16 and the two reductions are
compared leaf by leaf, each also against the f32 model's.

Usage: ``gradtap_scenario.py <model> <case> ...`` with cases
``honest`` or an attack kind, or ``gradtap_scenario.py <model> bf16``.
Prints one ``RESULT <json>`` line; the pytest wrapper asserts on it.
"""
import dataclasses
import json
import os
import sys
from concurrent.futures import ThreadPoolExecutor

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get_config, layer_groups  # noqa: E402
from repro.core.randomized import BFTConfig  # noqa: E402
from repro.data.pipeline import global_batch_for_step  # noqa: E402
from repro.obs import metrics  # noqa: E402
from repro.optim import OptConfig, init_opt_state  # noqa: E402
from repro.sharding import make_mesh, shard_map  # noqa: E402
from repro.train import gradtap, steps  # noqa: E402
from repro.train import AttackConfig, StepConfig, Trainer, TrainerConfig  # noqa: E402,E501
from repro.models import model as M  # noqa: E402

N = 4
SEQ = 16
SCALE = 10.0
BASE = dataclasses.replace(
    get_config("qwen3-4b").reduced(), dtype="float32", global_period=2,
    sliding_window=8)
MODELS = {"tied": dataclasses.replace(BASE, vocab_size=512, num_layers=19),
          "untied": dataclasses.replace(BASE, vocab_size=48, num_layers=5,
                                        tie_embeddings=False),
          "untied512": dataclasses.replace(BASE, vocab_size=512, num_layers=5,
                                           tie_embeddings=False)}
OPT = OptConfig(kind="adamw", peak_lr=1e-2, warmup_steps=2, total_steps=20)
SC = StepConfig(worker_axes=("data",))
MESH = make_mesh((N, 1), ("data", "model"))
GROUP_OF_WORKER = jnp.asarray([0, 0, 1, 1], jnp.int32)


def whole_tree(params, tokens, labels, byz, key, w, cfg, attack, waxes, n):
    """The reduction of the whole gradient tree after the backward pass."""
    loss, grads, _ = steps._per_worker_grad(params, tokens, labels, byz, key,
                                            cfg, attack)
    return loss, grads, jax.tree.map(
        lambda g: jax.lax.psum(w * g.astype(jnp.float32), waxes), grads)


def lower(cfg, attack, opt, whole: bool, kinds=("fast", "check")):
    """The fast and check steps, by the taps or by ``whole_tree``,
    lowered (the compile is left to ``compile_all``)."""
    tapped = steps._reduced_grad
    steps._reduced_grad = whole_tree if whole else tapped
    try:
        args = inputs(cfg, 3)
        out = {}
        if "fast" in kinds:
            fast = steps.make_fast_step(cfg, opt, MESH, SC, attack)
            out["fast"] = jax.jit(fast).lower(*args["fast"])
        if "check" in kinds:
            check = steps.make_check_step(cfg, opt, MESH, SC, attack,
                                          num_groups=2)
            out["check"] = jax.jit(check).lower(*args["check"])
    finally:
        steps._reduced_grad = tapped
    return out


def compile_all(lowered: dict) -> dict:
    """Compile every lowered step of ``{name: {kind: lowered}}`` at once,
    a thread each (XLA compiles outside the interpreter lock)."""
    jobs = [(n, k, lo) for n, d in lowered.items() for k, lo in d.items()]
    with ThreadPoolExecutor(len(jobs)) as pool:
        done = list(pool.map(lambda j: j[2].compile(), jobs))
    out: dict = {n: {} for n in lowered}
    for (n, k, _), c in zip(jobs, done):
        out[n][k] = c
    return out


def inputs(cfg, byz_worker):
    params = M.init(cfg, jax.random.PRNGKey(0))
    # a state a few steps in: moments not zero, so both moments compare
    opt_state = init_opt_state(OPT, params)
    opt_state = jax.tree.map(
        lambda m: m + 1e-3 * jnp.abs(jax.random.normal(
            jax.random.PRNGKey(1), m.shape)), opt_state)
    rng = np.random.default_rng(5)

    def batch(tok):
        return {"tokens": jnp.asarray(tok[..., :-1], jnp.int32),
                "labels": jnp.asarray(tok[..., 1:], jnp.int32)}

    fast_tok = rng.integers(0, cfg.vocab_size, (N, 1, SEQ + 1))
    # a check group's replicas compute on the same tokens
    group_tok = rng.integers(0, cfg.vocab_size, (2, 2, SEQ + 1))
    check_tok = group_tok[np.asarray(GROUP_OF_WORKER)]
    w = jnp.full((N,), 1.0 / N, jnp.float32)
    byz = jnp.zeros((N,), bool)
    if byz_worker is not None:
        byz = byz.at[byz_worker].set(True)
    key, step = jax.random.PRNGKey(9), jnp.asarray(3, jnp.int32)
    return {"fast": (params, opt_state, batch(fast_tok), w, byz, key, step),
            "check": (params, opt_state, batch(check_tok), w * 2, byz,
                      GROUP_OF_WORKER, key, step)}


def rel(a, b) -> float:
    """Largest relative deviation over the leaves of two trees (equal
    non-finite entries agree)."""
    out = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        same = (x == y) | (np.isnan(x) & np.isnan(y))
        if same.all():
            continue
        fin = np.isfinite(x) & np.isfinite(y)
        if not fin[~same].all():
            return float("inf")
        d = np.linalg.norm((x - y)[~same & fin])
        out = max(out, float(d / max(np.linalg.norm(y[fin]), 1e-30)))
    return out


def attack_of(case: str) -> AttackConfig:
    return AttackConfig(kind="none" if case == "honest" else case,
                        p_tamper=1.0, scale=SCALE)


def compare(cfg, case: str, new: dict, old: dict) -> dict:
    """One fast and one check step from the same state by both
    reductions (compiled ``new`` and ``old``): the largest relative
    deviation of each output, and whether the compiled fast steps
    all-gather (the sparse route does; nothing else in a fast step
    gathers)."""
    args = inputs(cfg, None if case == "honest" else 3)
    out = {"sparse": "all-gather" in new["fast"].as_text(),
           "old_gathers": "all-gather" in old["fast"].as_text()}
    for step in ("fast", "check"):
        p1, o1, m1 = jax.device_get(new[step](*args[step]))
        p0, o0, m0 = jax.device_get(old[step](*args[step]))
        out[step] = {
            "params": rel(p1, p0), "mu": rel(o1["mu"], o0["mu"]),
            "nu": rel(o1["nu"], o0["nu"]),
            "loss": rel(m1["loss"], m0["loss"]),
            "grad_norm": rel(m1["grad_norm"], m0["grad_norm"]),
            "updated": bool(rel(p1, args[step][0]) > 0),
            **({"any_fault": [bool(m1["any_fault"]), bool(m0["any_fault"])],
                "group_fault": [np.asarray(m1["group_fault"]).tolist(),
                                np.asarray(m0["group_fault"]).tolist()]}
               if step == "check" else {})}
    return out


def noise() -> dict:
    """Worker 3's noise, per leaf of at least 1,000 coordinates: the
    reduced gradient less the honest one, over worker 3's weight (read
    from the first moment with no clipping), and whether two runs of the
    step draw the same."""
    cfg = MODELS["tied"]
    opt = dataclasses.replace(OPT, grad_clip=0.0)   # mu = b1 mu0 + (1-b1) g
    args = inputs(cfg, 3)["fast"]
    mu0 = args[1]["mu"]
    runs = {}
    fast = compile_all({kind: lower(cfg, attack_of(kind), opt, False,
                                    kinds=("fast",))
                        for kind in ("noise", "honest")})
    for kind in ("noise", "honest"):
        outs = [jax.device_get(fast[kind]["fast"](*args)[1]["mu"])
                for _ in range(2)]
        runs[kind] = [jax.tree.map(
            lambda m, m0: (m - opt.beta1 * np.asarray(m0)) / (1 - opt.beta1),
            mu, mu0) for mu in outs]
    diff = jax.tree.map(lambda a, b: (a - b) * N, runs["noise"][0],
                        runs["honest"][0])
    leaves = [(jax.tree_util.keystr(p), d) for p, d in
              jax.tree_util.tree_flatten_with_path(diff)[0] if d.size >= 1000]
    return {
        "std": {p: float(np.std(d)) for p, d in leaves},
        "mean_over_se": {p: float(np.mean(d) / (SCALE / np.sqrt(d.size)))
                         for p, d in leaves},
        "deterministic": all(
            np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(runs["noise"][0]),
                jax.tree.leaves(runs["noise"][1]))),
    }


def gauges() -> dict:
    """The gauges of a Trainer's fast step, and the f32 sizes they split."""
    cfg = MODELS["tied"]
    metrics.reset()
    tr = Trainer(cfg, OPT, BFTConfig(n=N, f=1, mode="none", seed=0), MESH,
                 TrainerConfig(seq_len=SEQ, global_batch=N, log_every=0))
    tr.train_step()
    snap = {k: v["value"] for k, v in metrics.snapshot().items()
            if "grad_reduce" in k}
    sizes = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda p: 4 * p.size, tr.params))[0]
    top = {"embed", "decoder", "encoder"}
    return {"gauges": snap,
            "total": sum(s for _, s in sizes),
            "tree_level": sum(s for p, s in sizes
                              if getattr(p[0], "key", None) not in top)}


def reduced(fn, cfg, attack):
    """The gradient summed over the workers by ``fn`` (``_reduced_grad``
    or ``whole_tree``), jitted over the mesh."""
    def body(params, tokens, labels, w, byz, key):
        return fn(params, tokens[0], labels[0], byz[0], key, w[0], cfg,
                  attack, ("data",), N)[2]
    spec = P("data", None, None)
    return jax.jit(shard_map(
        body, MESH, in_specs=(P(), spec, spec, P("data"), P("data"), P()),
        out_specs=P(), axis_names={"data"}, check_vma=False))


def bf16(model: str) -> dict:
    """The model in bfloat16, honest, on the synthetic token stream: per
    leaf, the tapped reduction against the whole-tree one (its layers
    unrolled as the taps unroll them), and each against the f32 model's
    reduction at the highest precision; for the embedding also its
    looked-up rows and the rest apart."""
    seq = 256       # the tied lookup stays sparse: 4 x 256 rows < 2,048
    cfg = dataclasses.replace(MODELS[model], dtype="bfloat16",
                              vocab_size=4 * MODELS[model].vocab_size)
    groups = [g.repeats for g in layer_groups(cfg)]
    unrolled = dataclasses.replace(
        cfg, unroll_layers=all(gradtap.GradTap.unrolls(r) for r in groups))
    f32 = dataclasses.replace(cfg, dtype="float32")
    b = global_batch_for_step(cfg, global_batch=N, seq_len=seq, step=3,
                              seed=5)
    tok, lab = (jnp.asarray(b[k])[:, None] for k in ("tokens", "labels"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    args = (tok, lab, jnp.full((N,), 1.0 / N, jnp.float32),
            jnp.zeros((N,), bool), jax.random.PRNGKey(9))
    honest = attack_of("honest")
    tap = reduced(steps._reduced_grad, cfg, honest)(params, *args)
    whole = reduced(whole_tree, unrolled, honest)(params, *args)
    with jax.default_matmul_precision("highest"):
        truth = reduced(whole_tree, f32, honest)(
            jax.tree.map(lambda a: a.astype(jnp.float32), params), *args)
    hit = np.zeros(cfg.vocab_size, bool)
    hit[np.unique(b["tokens"])] = True
    out = {}
    for (path, t), w, f in zip(jax.tree_util.tree_flatten_with_path(tap)[0],
                               jax.tree.leaves(whole),
                               jax.tree.leaves(truth)):
        name = jax.tree_util.keystr(path)
        parts = {name: slice(None)}
        if name == "['embed']['tokens']":
            parts.update({name + ".looked_up": hit, name + ".other": ~hit})
        for part, m in parts.items():
            out[part] = {
                "tap~whole": rel(t[m], w[m]), "tap~f32": rel(t[m], f[m]),
                "whole~f32": rel(w[m], f[m])}
    return out


def main(model: str, cases: list[str]) -> None:
    if cases == ["bf16"]:
        print("RESULT " + json.dumps({"bf16": bf16(model)}))
        return
    cfg = MODELS[model]
    progs = compile_all(
        {(c, whole): lower(cfg, attack_of(c), OPT, whole)
         for c in cases for whole in (False, True)})
    out = {"groups": [g.repeats for g in layer_groups(cfg)],
           "cases": {c: compare(cfg, c, progs[c, False], progs[c, True])
                     for c in cases}}
    if model == "tied":
        out.update(noise=noise(), gauges=gauges())
    print("RESULT " + json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:])
