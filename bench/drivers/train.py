"""BFT training: each call is one ``Trainer.train_step()`` of a dense
decoder whose workers are the cell's chips, one whole data-parallel
replica a chip, the way a team trains a model over workers it cannot
fully trust.

The configuration gives the model (``model``, in the key names of a
Hugging Face ``config.json``; ``arch``, the program's registered
architecture it is a depth cut of), the parameters' dtype, the
optimizer and the protocol (``n`` workers tolerating ``f``).  Traffic
parameters (the ``traffic`` block of a workload file):

- ``global_batch``, ``seq_len``: the sequences of one step and their
  length;
- ``mode``, ``q``: the protocol and its fixed check probability;
- ``byzantine``, ``attack``: the Byzantine workers and their tampering
  (none and "none": every worker honest);
- ``kept_steps``: steps of the window kept for the check: its first
  two calls, one fast and one check step.

The run's seed seeds the weights (made on the chips), the token stream
and the protocol.  The check coin is seeded, so the kind of every step
is known in advance: the warm-up trains at least ``MIN_WARM`` steps,
until each kind has run on parameters a step produced (their sharding
differs from the initial one, so each kind compiles for it here, not in
the window) and the next two steps are one fast and one check step.
The parameters and both moments are copied to the host at the end of
the warm-up, and the parameters and first moment after each kept call,
in ``keep``, so that no copy falls inside a timed call.  The second
moment before the second kept step is rebuilt from the first's moments
(``_nu``).

After the window the plain float32 reference
(``bench/reference/qwen3_train.py``) takes each kept step from the
parameters before it, on the step's global batch, and is compared with
what the step produced:

- ``loss_dev``: |loss - loss_ref| / |loss_ref|;
- ``grad_dev``: the aggregated gradient the step applied, read from the
  first moment's change ((mu' - beta1 mu) / (1 - beta1), the clipped
  gradient), against the reference's clipped gradient, as relative L2
  over every parameter;
- ``update_dev``: the parameters after the step against the reference's
  AdamW update from the same parameters and moments, rounded to the
  parameters' dtype, ||theta' - theta_ref|| / ||theta_ref - theta||:
  the error of the update over its size;
- ``protocol_mismatch`` (limit 0): steps that checked where the replayed
  coin says they do not, or the reverse; check steps that found a fault
  (every worker is honest); and 1 where the computation efficiency's
  counts differ from the reference's;
- ``kept_steps``: the kept steps the window reached, at least
  ``kept_steps``.

The copies are large (9 GB at the end of the warm-up, 5.4 GB after
each kept call, at Qwen3-4B's widths), so they are made once each and
early: kept calls later in the window would leave it few calls.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import sys

import numpy as np

from bench.reference import qwen3_train as ref

CHUNK = 1 << 25           # most elements of an array a device pass compares
COIN_STEPS = 4096         # steps of the check coin replayed in advance
MIN_WARM = 8              # fewest warm-up steps


def model_config(config: dict):
    """The program's ``ModelConfig`` of the configuration: the registered
    architecture with the file's sizes."""
    from repro.configs import get_config

    m = config["model"]
    return dataclasses.replace(
        get_config(config["arch"]),
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        tie_embeddings=m["tie_word_embeddings"],
        dtype=config["param_dtype"], remat=config["remat"])


def reference_layout(tree) -> dict:
    """The reference's flat names for a parameter-shaped tree of the
    program (parameters or a moment) of a dense decoder whose layers are
    one scanned group."""
    (layer,), = tree["decoder"]
    mix, ffn = layer["mixer"], layer["ffn"]
    out = {"embed": tree["embed"]["tokens"],
           "final_norm": tree["final_norm"]["scale"],
           "attn_norm": layer["ln1"]["scale"], "mlp_norm": layer["ln2"]["scale"],
           "wq": mix["wq"], "wk": mix["wk"], "wv": mix["wv"], "wo": mix["wo"],
           "q_norm": mix["q_norm"], "k_norm": mix["k_norm"],
           "gate": ffn["gate"], "up": ffn["up"], "down": ffn["down"]}
    import jax

    if len(jax.tree.leaves(tree)) != len(out):
        raise ValueError("the parameter tree has leaves the reference "
                         "does not name")
    return out


def warm_up_steps(checks: np.ndarray, least: int) -> int:
    """Warm-up steps for the coin ``checks`` (one bool a step from step
    0): at least ``least``, each kind of step among them after the
    first, and the two steps after them of different kinds."""
    w = least
    while not ({False, True} <= set(checks[1:w].tolist())
               and checks[w] != checks[w + 1]):
        w += 1
    return w


def _sums(opt: dict, step: int, theta_b, mu_b, nu_b, g, theta_a, mu_a):
    """Over one chunk: sum of squares of the applied gradient's
    deviation and of the reference's, and of the update's deviation and
    of the reference's update, all in float32."""
    import jax.numpy as jnp

    b1 = opt["beta1"]
    g_prog = (mu_a - b1 * mu_b) / (1 - b1)
    theta_r, _, _ = ref.adamw(opt, step, theta_b, mu_b, nu_b, g)
    theta_r = theta_r.astype(theta_b.dtype).astype(jnp.float32)
    ta, tb = theta_a.astype(jnp.float32), theta_b.astype(jnp.float32)
    sq = lambda x: jnp.sum(x * x)                      # noqa: E731
    return jnp.stack([sq(g_prog - g), sq(g), sq(ta - theta_r),
                      sq(theta_r - tb)])


def _after(opt: dict, step: int, theta_b, mu_b, nu_b, g):
    """What a step that applied the clipped gradient ``g`` leaves."""
    theta, mu, _ = ref.adamw(opt, step, theta_b, mu_b, nu_b, g)
    return theta.astype(theta_b.dtype), mu


def _nu(opt: dict, mu_prev, nu_prev, mu):
    """The second moment after a step, from both moments before it and
    the first moment after it: the step applied the clipped gradient
    (mu - beta1 mu_prev) / (1 - beta1)."""
    b1, b2 = opt["beta1"], opt["beta2"]
    g = (mu - b1 * mu_prev) / (1 - b1)
    return b2 * nu_prev + (1 - b2) * g * g


def _chunks(a: np.ndarray, size: int):
    """A flat view of ``a`` in pieces of ``size`` elements, the last
    padded with zeros (which add nothing to any sum)."""
    flat = a.reshape(-1)
    for i in range(0, flat.size, size):
        piece = flat[i:i + size]
        if piece.size < size:
            piece = np.concatenate([piece, np.zeros(size - piece.size,
                                                    piece.dtype)])
        yield piece


class Driver:
    span_name = "train.call"
    items_per_call = 1

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.core.randomized import BFTConfig
        from repro.optim import OptConfig
        from repro.sharding import make_mesh
        from repro.train import AttackConfig, StepConfig, Trainer, TrainerConfig

        proto = config["protocol"]
        self.traffic, self.seed = traffic, seed
        self.model = config["model"]
        self.opt = config["optimizer"]
        self.n, self.f = proto["n"], proto["f"]
        self.devices = list(devices[:self.n])
        byz = np.zeros(self.n, bool)
        byz[list(traffic["byzantine"])] = True
        mesh = make_mesh((self.n, 1), ("data", "model"),
                         devices=self.devices)
        self.trainer = Trainer(
            model_config(config), OptConfig(**self.opt),
            BFTConfig(n=self.n, f=self.f, mode=traffic["mode"],
                      q=traffic["q"], tau=proto["tau"],
                      sketch_k=proto["sketch_k"], seed=seed),
            mesh,
            TrainerConfig(seq_len=traffic["seq_len"],
                          global_batch=traffic["global_batch"], seed=seed,
                          log_every=0),
            attack=AttackConfig(kind=traffic["attack"]),
            sc=StepConfig(worker_axes=("data",), detection=proto["detection"],
                          sketch_k=proto["sketch_k"], tau=proto["tau"]),
            true_byzantine=byz)
        self.states: list[dict] = []     # host copies: warm-up end, calls 1, 2
        self.kept: list[dict] = []       # step and loss of calls 1 and 2
        self.protocol = None

    # -- the timed path -------------------------------------------------
    def warm_up(self) -> None:
        coin = ref.check_coin(self.seed, self.traffic["q"], COIN_STEPS)
        for _ in range(warm_up_steps(coin, MIN_WARM)):
            self.trainer.train_step()
        self._snapshot()

    def call(self, i: int):
        return 1, self.trainer.train_step()

    def keep(self, i: int, rec) -> None:
        if i <= self.traffic["kept_steps"]:
            self._snapshot(nu=False)
            self.kept.append({"step": rec["step"], "loss": rec["loss"]})

    def _snapshot(self, nu: bool = True) -> None:
        """Host copies of the parameters and moments, in the reference's
        names.  Inside the window the second moment is left on the chips:
        the check rebuilds it from the moments before the step."""
        import jax

        tr = self.trainer
        trees = {"theta": tr.params, "mu": tr.opt_state["mu"]}
        if nu:
            trees["nu"] = tr.opt_state["nu"]
        host = jax.device_get(trees)
        self.states.append({k: reference_layout(v) for k, v in host.items()})

    def release(self) -> None:
        """Frees the chips: only the host's record of the protocol is
        kept."""
        import gc

        tr = self.trainer
        self.protocol = {"history": tr.history, "meter": tr.state.meter,
                         "kappa": tr.state.kappa}
        self.trainer = None
        gc.collect()

    # -- the check ------------------------------------------------------
    def protocol_mismatch(self) -> int:
        p = self.protocol
        hist = p["history"]
        coin = ref.check_coin(self.seed, self.traffic["q"], len(hist))
        ran = np.array([h["efficiency"] < 1 for h in hist])
        faults = sum("identified" in h for h in hist) + p["kappa"]
        used, computed = ref.efficiency_counts(coin, self.n, self.f)
        meter = p["meter"]
        return (int((ran != coin).sum()) + faults
                + int((meter.used, meter.computed) != (used, computed)))

    def _grad(self, theta: dict, step: int, control: bool):
        """The reference's loss and clipped gradient (on the host) of the
        global batch of ``step`` at ``theta``."""
        import jax
        import jax.numpy as jnp

        B, S = self.traffic["global_batch"], self.traffic["seq_len"]
        tokens, labels = ref.token_batch(self.seed, step, B, S,
                                         self.model["vocab_size"])
        loss, g = ref.loss_and_grad(theta, tokens, labels, self.model,
                                    control=control, device=self.devices[0])
        norm = float(jnp.sqrt(sum(jnp.sum(v * v) for v in g.values())))
        scale = np.float32(ref.clip_scale(self.opt, norm))
        g = {k: np.asarray(jax.device_get(v)) * scale for k, v in g.items()}
        return loss, g

    def step_numbers(self, before: dict, after: dict, step: int,
                     loss: float, control: bool = False,
                     prev: dict | None = None) -> dict:
        """loss_dev, grad_dev and update_dev of one kept step from the
        states ``before`` and ``after`` it (a state without its second
        moment has it rebuilt from the state ``prev`` before it); with
        ``control`` of the control reference's step in place of the
        program's."""
        import jax

        loss_r, g_r = self._grad(before["theta"], step, False)
        if control:
            loss, g_c = self._grad(before["theta"], step, True)
        sums = jax.jit(functools.partial(_sums, self.opt, step))
        make = jax.jit(functools.partial(_after, self.opt, step))
        nu_of = jax.jit(functools.partial(_nu, self.opt))
        size = min(CHUNK, max(g.size for g in g_r.values()))
        dev = self.devices[0]
        total, pending = np.zeros(4), []
        for name in g_r:
            src = {"tb": before["theta"][name], "mb": before["mu"][name],
                   "g": g_r[name]}
            if "nu" in before:
                src["nb"] = before["nu"][name]
            else:
                src.update(mp=prev["mu"][name], vp=prev["nu"][name])
            if control:
                src["gc"] = g_c[name]
            else:
                src.update(ta=after["theta"][name], ma=after["mu"][name])
            for chunk in zip(*(_chunks(a, size) for a in src.values())):
                c = {k: jax.device_put(a, dev) for k, a in zip(src, chunk)}
                nb = c["nb"] if "nb" in c else nu_of(c["mp"], c["vp"],
                                                     c["mb"])
                ta, ma = (make(c["tb"], c["mb"], nb, c["gc"]) if control
                          else (c["ta"], c["ma"]))
                pending.append(sums(c["tb"], c["mb"], nb, c["g"], ta, ma))
                if len(pending) == 2:         # two chunks in flight at most
                    total += np.sum(pending, axis=0, dtype=np.float64)
                    pending = []
        total += np.sum(pending, axis=0, dtype=np.float64) if pending else 0
        return {"loss_dev": abs(loss - loss_r) / abs(loss_r),
                "grad_dev": float(np.sqrt(total[0] / total[1])),
                "update_dev": float(np.sqrt(total[2] / max(total[3],
                                                           1e-300)))}

    def numbers(self, control: bool = False) -> list[dict]:
        """The three numbers of every kept step that the window reached,
        each also printed on standard error."""
        out = []
        for k, kept in enumerate(self.kept[:len(self.states) - 1]):
            out.append(dict(self.step_numbers(
                self.states[k], self.states[k + 1], kept["step"],
                kept["loss"], control, self.states[k - 1] if k else None),
                step=kept["step"]))
            print(f"kept {'control ' if control else ''}"
                  f"{json.dumps(out[-1])}", file=sys.stderr, flush=True)
        return out

    def verify(self, limits: dict, control: bool = False) -> list[dict]:
        """The checks of the kept steps against ``limits``; with
        ``control`` the control reference's steps are judged in place of
        the program's (the limits must reject them)."""
        got = self.numbers(control)
        want = self.traffic["kept_steps"]
        checks = [("protocol_mismatch", self.protocol_mismatch(), 0)]
        checks += [(name, max((x[name] for x in got), default=np.inf), lim)
                   for name, lim in limits.items()]
        checks.append(("kept_steps", len(got), want))
        return [{"name": n, "value": v, "limit": lim,
                 "ok": bool(v >= lim if n == "kept_steps" else v <= lim)}
                for n, v, lim in checks]
