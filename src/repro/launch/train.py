"""Production training launcher.

Builds a (data, model) mesh over the available devices, instantiates the
BFT trainer for any registered architecture, and runs with checkpointing,
restart, and the randomized reactive-redundancy protocol live.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-4b --reduced --steps 50 --mode randomized --f 1 \
        --ckpt-dir /tmp/run1
    # restart after interruption:
    PYTHONPATH=src python -m repro.launch.train ... --restore

On a real TPU slice the same entry point shards over the physical chips;
`--workers` pins the data-axis (BFT worker) count.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, list_configs
from repro.core.randomized import BFTConfig
from repro.optim import OptConfig
from repro.train import AttackConfig, StepConfig, Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="paper-smalllm", choices=list_configs())
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--mode", default="randomized",
                    choices=["randomized", "deterministic", "draco",
                             "filter", "none"])
    ap.add_argument("--filter", dest="filter_name", default="median")
    ap.add_argument("--f", type=int, default=1)
    ap.add_argument("--q", type=float, default=-1.0,
                    help="fault-check probability; <0 -> adaptive (§4.3)")
    ap.add_argument("--detection", default="sketch", choices=["sketch", "full"])
    ap.add_argument("--selective", action="store_true")
    ap.add_argument("--workers", type=int, default=0,
                    help="data-axis size (0: all devices)")
    ap.add_argument("--byz", default="", help="comma list of Byzantine ranks (simulation)")
    ap.add_argument("--attack", default="sign_flip")
    ap.add_argument("--p-tamper", type=float, default=0.6)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    n_dev = len(jax.devices())
    workers = args.workers or n_dev
    model_par = n_dev // workers
    from repro.sharding import make_mesh

    mesh = make_mesh((workers, model_par), ("data", "model"))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[launch] {cfg.name} on mesh data={workers} x model={model_par}")

    byz = [int(x) for x in args.byz.split(",") if x]
    trainer = Trainer(
        cfg,
        OptConfig(kind="adamw", peak_lr=args.lr, warmup_steps=20,
                  total_steps=max(100, args.steps)),
        BFTConfig(n=workers, f=args.f, mode=args.mode,
                  q=None if args.q < 0 else args.q,
                  p_assumed=args.p_tamper, selective=args.selective,
                  seed=args.seed),
        mesh,
        TrainerConfig(
            seq_len=args.seq_len,
            global_batch=args.global_batch or 4 * workers,
            seed=args.seed,
            checkpoint_dir=args.ckpt_dir or None,
            checkpoint_every=args.ckpt_every if args.ckpt_dir else 0,
            filter_name=args.filter_name,
            log_every=10,
        ),
        attack=AttackConfig(kind=args.attack if byz else "none",
                            p_tamper=args.p_tamper),
        sc=StepConfig(worker_axes=("data",), detection=args.detection),
        true_byzantine=np.isin(np.arange(workers), byz),
    )
    if args.restore:
        step = trainer.restore_latest()
        print(f"[launch] restored step {step}")
    trainer.run(max(0, args.steps - trainer.state.step))
    st = trainer.state
    print(
        f"[launch] done: loss={trainer.history[-1]['loss']:.4f} "
        f"eff={st.meter.overall:.3f} κ={st.kappa} "
        f"identified={sorted(np.flatnonzero(st.identified).tolist())}"
    )


if __name__ == "__main__":
    main()
