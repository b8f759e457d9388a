"""Host-side BFT trainer: dispatches the compiled fast / check / identify
steps according to the randomized reactive-redundancy protocol.

Per iteration (paper §4.2):
  1. q_t from the protocol (fixed q, or adaptive closed-form §4.3 using the
     previously observed loss — a real system reuses last iteration's loss
     instead of paying an extra forward pass; documented deviation);
  2. coin < q_t  ->  check iteration: replicated assignment, detection;
       fault detected -> *reactive* identify iteration ON THE SAME BATCH
       (r = 2f_t+1, majority vote), Byzantine workers eliminated, exact
       gradient applied;
     else          ->  fast iteration (plain parallelized SGD);
  3. efficiency accounting (Definition 2), checkpointing, elastic remaps.

Compiled-step caching: step functions are jitted per assignment signature
(mode, num_shards, replication, rows); signatures change only on
elimination / crash events (<= f + #crashes times per run).

Spans and counters (``repro.obs``; tree in docs/observability.md): each
step is a ``train.step`` span holding its global batch (``train.batch``)
and one span per compiled step it dispatches (``train.fast``,
``train.check``, ``train.identify``), each of those the worker batches
sliced and copied to the devices (``train.put``), a step-cache miss with
its first call (``train.compile``) and the host's wait for the outputs
it reads (``train.sync``).  Counters: ``train.steps.<kind>``, ``train.tokens``,
``train.step_cache_misses``, ``train.faults_detected``.  Gauges, set when a
fast or check step is built: ``train.grad_reduce_in_backward_bytes.<kind>``
and ``train.grad_reduce_after_backward_bytes.<kind>``, the f32 bytes of
the gradient reduced inside the backward pass and after it.

Supported BFT modes: randomized (paper), deterministic (paper §4.1), draco
(baseline: permanent 2f+1 voting), filter:<name> (gradient-filter
baselines), none (vanilla parallelized SGD).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.core import filters as filters_mod
from repro.core.assignment import Assignment, group_members
from repro.core.randomized import BFTConfig, ProtocolState
from repro.data import global_batch_for_step, worker_batches
from repro.models import model as M
from repro.obs import metrics as obmetrics
from repro.obs import trace as obtrace
from repro.optim import OptConfig, init_opt_state, opt_update
from repro.sharding import PARAM_RULES, set_mesh, tree_specs
from repro.train.steps import (
    AttackConfig,
    StepConfig,
    grad_reduce_bytes,
    make_check_step,
    make_fast_step,
    make_identify_step,
    num_workers,
)


@dataclasses.dataclass
class TrainerConfig:
    seq_len: int = 128
    global_batch: int = 64
    seed: int = 0
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0
    filter_name: str = "median"       # for mode == "filter"
    log_every: int = 10


def _tp_only_rules():
    rules = dict(PARAM_RULES)
    rules["embed"] = None  # params replicated over worker (data) axes
    return rules


class Trainer:
    def __init__(self, cfg, opt: OptConfig, bft: BFTConfig, mesh,
                 tc: TrainerConfig, attack: AttackConfig | None = None,
                 sc: StepConfig | None = None,
                 true_byzantine: np.ndarray | None = None):
        self.cfg, self.opt, self.bft, self.mesh, self.tc = cfg, opt, bft, mesh, tc
        self.sc = sc or StepConfig()
        self.attack = attack or AttackConfig(kind="none")
        n = num_workers(mesh, self.sc.worker_axes)
        assert n == bft.n, f"mesh gives {n} workers, BFTConfig.n={bft.n}"
        self.state = ProtocolState.create(bft)
        self.true_byz = (
            np.zeros(n, bool) if true_byzantine is None else true_byzantine
        )
        self.rules = _tp_only_rules()
        self._step_cache: dict[Any, Any] = {}
        self.ckpt = (
            CheckpointManager(tc.checkpoint_dir, tc.checkpoint_every)
            if tc.checkpoint_dir
            else None
        )
        self.last_loss: float = 1.0
        self.history: list[dict] = []

        with set_mesh(mesh):
            abstract = M.abstract_params(cfg)
            shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                tree_specs(abstract, mesh, self.rules),
            )
            key = jax.random.PRNGKey(tc.seed)
            self.params = jax.jit(
                lambda k: M.init(cfg, k), out_shardings=shardings
            )(key)
            self.opt_state = init_opt_state(opt, self.params)
        self.key = jax.random.PRNGKey(tc.seed + 1)

    # ------------------------------------------------------------------
    def _get_step(self, mode: str, assignment: Assignment):
        """The compiled step for ``assignment``, and whether it was made
        now (a miss: its first call compiles it)."""
        rows = self.tc.global_batch // assignment.num_shards
        sig = (mode, assignment.num_shards, assignment.replication, rows)
        if sig in self._step_cache:
            return self._step_cache[sig], False
        obmetrics.counter("train.step_cache_misses").inc()
        if mode == "fast":
            fn = make_fast_step(self.cfg, self.opt, self.mesh, self.sc, self.attack)
        elif mode == "check":
            fn = make_check_step(
                self.cfg, self.opt, self.mesh, self.sc, self.attack,
                num_groups=assignment.num_shards,
            )
        elif mode == "identify":
            members = np.stack(group_members(assignment))
            fn = make_identify_step(
                self.cfg, self.opt, self.mesh, self.sc, self.attack, members
            )
        elif mode == "filter":
            from repro.train.steps import make_filter_step

            fn = make_filter_step(
                self.cfg, self.opt, self.mesh, self.sc, self.attack,
                self.tc.filter_name, self.bft.f,
            )
        else:
            raise ValueError(mode)
        if mode in ("fast", "check"):
            inside, after = grad_reduce_bytes(
                self.cfg, self.params,
                num_workers(self.mesh, self.sc.worker_axes))
            obmetrics.gauge(
                f"train.grad_reduce_in_backward_bytes.{mode}").set(inside)
            obmetrics.gauge(
                f"train.grad_reduce_after_backward_bytes.{mode}").set(after)
        fn = jax.jit(fn, donate_argnums=(0, 1))
        self._step_cache[sig] = fn
        return fn, True

    def _dispatch(self, mode: str, assignment: Assignment, batch) -> dict:
        """Run the compiled ``mode`` step on the global ``batch`` and
        return its metrics, on the device."""
        with obtrace.span("train.put"):
            wb = worker_batches(batch, assignment)
            wb = {k: jnp.asarray(v) for k, v in wb.items()}
            weights = jnp.asarray(assignment.weight)
            byz = jnp.asarray(self.true_byz & self.state.active)
            extra = ((jnp.asarray(assignment.group_of_worker),)
                     if mode == "check" else ())
            step = jnp.asarray(self.state.step, jnp.int32)
        step_fn, fresh = self._get_step(mode, assignment)
        args = (self.params, self.opt_state, wb, weights, byz) + extra + (
            self.key, step)
        if fresh:
            with obtrace.span("train.compile"):
                self.params, self.opt_state, metrics = step_fn(*args)
        else:
            self.params, self.opt_state, metrics = step_fn(*args)
        return metrics

    def _run(self, mode: str, assignment: Assignment, batch,
             *keys: str) -> dict:
        """One compiled step on ``batch``, span ``train.<mode>`` from its
        worker batches to its metrics ``loss`` and ``keys`` read on the
        host."""
        with obtrace.span(f"train.{mode}"):
            m = self._dispatch(mode, assignment, batch)
            with obtrace.span("train.sync"):
                out = jax.device_get({k: m[k] for k in ("loss",) + keys})
        obmetrics.counter(f"train.steps.{mode}").inc()
        return out

    # ------------------------------------------------------------------
    def train_step(self) -> dict:
        with obtrace.span("train.step"):
            return self._train_step()

    def _train_step(self) -> dict:
        st = self.state
        with obtrace.span("train.batch"):
            batch = global_batch_for_step(
                self.cfg, global_batch=self.tc.global_batch,
                seq_len=self.tc.seq_len, step=st.step, seed=self.tc.seed,
            )
        record: dict[str, Any] = {"step": st.step}

        mode = self.bft.mode
        with set_mesh(self.mesh):
            if mode in ("deterministic", "randomized") and st.decide_check(
                self.last_loss
            ):
                a = st.assignment_check()
                m = self._run("check", a, batch, "any_fault")
                checked = True
                used = a.num_shards
                computed = a.gradients_computed()
                identified = False
                if bool(m["any_fault"]):
                    obmetrics.counter("train.faults_detected").inc()
                    ai = st.assignment_identify()
                    mi = self._run("identify", ai, batch, "byz")
                    byz = np.asarray(mi["byz"])
                    st.on_identified(np.flatnonzero(byz))
                    self._step_cache.clear()  # assignments changed shape
                    used += ai.num_shards
                    computed += ai.gradients_computed()
                    identified = True
                    record["identified"] = np.flatnonzero(byz).tolist()
                    m, a = mi, ai
                else:
                    st.on_clean_check(np.flatnonzero(a.group_of_worker >= 0))
                eff = st.meter.record(
                    used, computed, checked=True, identified=identified
                )
            elif mode == "draco":
                a = st.assignment_identify()
                m = self._run("identify", a, batch, "byz")
                byz = np.asarray(m["byz"])
                newly = np.flatnonzero(byz & ~st.identified)
                if len(newly):
                    st.on_identified(newly)
                    self._step_cache.clear()
                    record["identified"] = newly.tolist()
                eff = st.meter.record(
                    a.num_shards, a.gradients_computed(), checked=True
                )
            elif mode == "filter":
                a = st.assignment_fast()
                m = self._run("filter", a, batch)
                eff = st.meter.record(a.num_shards, a.gradients_computed())
            else:  # fast path (randomized default / none)
                a = st.assignment_fast()
                m = self._run("fast", a, batch)
                eff = st.meter.record(a.num_shards, a.gradients_computed())

        obmetrics.counter("train.tokens").inc(
            self.tc.global_batch // a.num_shards * a.num_shards
            * self.tc.seq_len)
        self.last_loss = float(m["loss"])
        record.update(
            loss=self.last_loss,
            efficiency=eff,
            q=st.last_q,
            f_t=st.f_t,
            kappa=st.kappa,
        )
        st.step += 1
        if self.ckpt:
            self.ckpt.maybe_save(
                st.step, params=self.params, opt_state=self.opt_state,
                protocol_state=st, extra={"last_loss": self.last_loss},
            )
        self.history.append(record)
        return record

    def run(self, steps: int) -> list[dict]:
        for _ in range(steps):
            rec = self.train_step()
            if self.tc.log_every and rec["step"] % self.tc.log_every == 0:
                print(
                    f"step {rec['step']:5d} loss {rec['loss']:.4f} "
                    f"eff {rec['efficiency']:.3f} q {rec['q']:.3f} "
                    f"κ {rec['kappa']}",
                    flush=True,
                )
        return self.history

    # -- elasticity -----------------------------------------------------
    def inject_crash(self, workers) -> None:
        self.state.on_crash(np.asarray(workers))
        self._step_cache.clear()

    def recover(self, workers) -> None:
        self.state.on_recover(np.asarray(workers))
        self._step_cache.clear()

    # -- restart ----------------------------------------------------------
    def restore_latest(self) -> int | None:
        from repro.checkpoint import latest_step, restore

        if not self.tc.checkpoint_dir:
            return None
        step = latest_step(self.tc.checkpoint_dir)
        if step is None:
            return None
        self.params, self.opt_state, extra = restore(
            self.tc.checkpoint_dir, step,
            params_template=self.params, opt_template=self.opt_state,
            protocol_state=self.state,
        )
        self.last_loss = extra.get("last_loss", 1.0)
        self._step_cache.clear()
        return step
