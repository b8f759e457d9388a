"""Sharded-engine parity scenario (run in a subprocess with a forced
8-device host platform).

Runs scenario grids through ``run_batch(backend="jax")`` with the trial
batch sharded over the full ("trials",) device mesh and asserts the
documented parity contract against the numpy engine: control quantities
exact, floats at the f32 tolerances.  Also exercises the chunked async
pipeline (chunk smaller than B, non-divisible remainders -> padding)
and prints machine-checkable ``RESULT key=value`` lines for the pytest
wrapper (tests/test_sharded_engine.py).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

import jax
import numpy as np

N_DEV = 8
if len(jax.devices()) < N_DEV:
    print(f"SCENARIO_SKIP need {N_DEV} devices, have {len(jax.devices())}")
    raise SystemExit(0)

from repro.core.engine import SCENARIOS, TrialSpec, run_batch
from repro.sharding import trials_mesh

W_RTOL = W_ATOL = 1e-4
LOSS_RTOL, LOSS_ATOL = 1e-3, 1e-4


def compare(name, npb, jxb):
    ctrl = val = True
    for rn, rj in zip(npb, jxb):
        ctrl &= rn.identify_step == rj.identify_step
        ctrl &= rn.efficiency == rj.efficiency
        ctrl &= rn.q_trace == rj.q_trace
        ctrl &= bool(np.array_equal(rn.state.identified, rj.state.identified))
        sm, jm = rn.state.meter, rj.state.meter
        ctrl &= (sm.used, sm.computed, sm.check_iterations) == \
            (jm.used, jm.computed, jm.check_iterations)
        val &= bool(np.allclose(rj.w, np.asarray(rn.w),
                                rtol=W_RTOL, atol=W_ATOL))
        val &= bool(np.allclose(np.asarray(rj.losses), np.asarray(rn.losses),
                                rtol=LOSS_RTOL, atol=LOSS_ATOL))
    print(f"RESULT {name}_control_parity={ctrl}")
    print(f"RESULT {name}_value_parity={val}")


def main() -> None:
    mesh = trials_mesh()
    print(f"RESULT devices={len(jax.devices())}")
    print(f"RESULT mesh_shape={tuple(int(x) for x in mesh.devices.shape)}")

    # -- the SCENARIOS grid, batch sharded over all 8 devices -------------
    for name, mx in SCENARIOS.items():
        npb = mx.run()
        jxb = mx.run(backend="jax", mesh=mesh)
        compare(name, npb, jxb)

    # -- sharded vs unsharded: different chunk/shard shapes reassociate
    #    f32 reductions by a few ulp, so the cross-configuration contract
    #    is a tight float tolerance (the NUMPY-engine parity above is the
    #    exactness contract for control quantities)
    def close(a, b):
        return bool(np.allclose(np.asarray(a.w), np.asarray(b.w),
                                rtol=1e-5, atol=1e-6))

    specs = [TrialSpec(byz=(2, 5), attack="drift", q=0.3, steps=60, seed=s,
                       label=f"s{s}") for s in range(24)]
    un = run_batch(specs, backend="jax", mesh=None)
    sh = run_batch(specs, backend="jax", mesh=mesh)
    same = all(close(a, b) for a, b in zip(un, sh))
    print(f"RESULT sharded_equals_unsharded={same}")

    # -- gram data plane across the mesh: coefficient-space scan with
    #    the (B, Ie) carry sharded over trials and the gram factors
    #    replicated; detection verdicts must stay bitwise equal to the
    #    stream plane's sharded run (same precomputed sketch tables) -----
    gr = run_batch(specs, backend="jax", mesh=mesh, data_plane="gram")
    same_g = all(close(a, b) for a, b in zip(sh, gr))
    plane_ok = (gr.plan.data_plane == "gram"
                and sh.plan.data_plane == "stream"
                and bool(np.array_equal(gr.detect_flags, sh.detect_flags)))
    print(f"RESULT gram_sharded_parity={same_g and plane_ok}")

    # gram through the chunked pipeline (chunk < B, padded remainder)
    gr_ch = run_batch(specs, backend="jax", mesh=mesh, data_plane="gram",
                      chunk_trials=9)
    same_gch = all(close(a, b) for a, b in zip(gr, gr_ch))
    print(f"RESULT gram_chunk_pipeline_parity={same_gch}")

    # -- chunked async pipeline: several chunks + a padded remainder ------
    ch = run_batch(specs, backend="jax", mesh=mesh, chunk_trials=9)
    same_ch = all(close(a, b) for a, b in zip(un, ch))
    print(f"RESULT chunk_pipeline_parity={same_ch}")

    # -- telemetry across the mesh: protocol counters reduced inside the
    #    per-trial shard (no new collectives); primary outputs must stay
    #    bitwise identical to the telemetry-off sharded run and counters
    #    must equal the numpy oracle's, padding sliced off -------------
    np_tel = run_batch(specs, telemetry=True)
    sh_tel = run_batch(specs, backend="jax", mesh=mesh, telemetry=True)
    tel_bitwise = all(
        bool(np.array_equal(np.asarray(a.w), np.asarray(b.w)))
        for a, b in zip(sh, sh_tel))
    tel_counts = all(
        bool(np.array_equal(np_tel.telemetry.counters[k],
                            sh_tel.telemetry.counters[k]))
        for k in np_tel.telemetry.counters)
    print(f"RESULT telemetry_sharded_bitwise={tel_bitwise}")
    print(f"RESULT telemetry_sharded_counters={tel_counts}")

    # through the chunked pipeline (telemetry accumulated per chunk,
    # padded trials dropped) and on the on-device control plane
    ch_tel = run_batch(specs, backend="jax", mesh=mesh, chunk_trials=9,
                       telemetry=True)
    tel_chunk = all(
        bool(np.array_equal(np_tel.telemetry.counters[k],
                            ch_tel.telemetry.counters[k]))
        for k in np_tel.telemetry.counters)
    print(f"RESULT telemetry_chunk_pipeline_counters={tel_chunk}")

    np_dev = run_batch(specs, rng="device", telemetry=True)
    sh_dev = run_batch(specs, backend="jax", schedule="device", mesh=mesh,
                       telemetry=True)
    tel_dev = all(
        bool(np.array_equal(np_dev.telemetry.counters[k],
                            sh_dev.telemetry.counters[k]))
        for k in np_dev.telemetry.counters)
    print(f"RESULT telemetry_sharded_device_counters={tel_dev}")

    # -- B smaller than the mesh (pure padding) ---------------------------
    tiny = run_batch(specs[:3], backend="jax", mesh=mesh)
    same_tiny = all(close(a, b) for a, b in zip(un[:3], tiny))
    print(f"RESULT small_batch_padding_parity={same_tiny}")

    # -- ops-level sharding-aware Pallas dispatch -------------------------
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.sharding import set_mesh

    x = np.random.default_rng(0).normal(size=(16, 5, 64)).astype(np.float32)
    ref = ops.batched_pairwise_relmax(jnp.asarray(x), impl="xla")
    with set_mesh(mesh):
        rel = ops.batched_pairwise_relmax(jnp.asarray(x), impl="pallas")
    ops_ok = bool(np.allclose(np.asarray(rel), np.asarray(ref),
                              rtol=1e-6, atol=1e-6))
    ops_sharded = "trials" in str(getattr(rel, "sharding", ""))
    print(f"RESULT ops_sharded_pallas={ops_ok and ops_sharded}")

    # -- mixed per-trial problems through the sharded path ----------------
    mixed = [
        TrialSpec(byz=(2, 5), attack="drift", steps=50, q=0.4, seed=1),
        TrialSpec(byz=(2,), attack="noise", steps=30, q=0.3, seed=9,
                  n=6, f=1, problem_seed=3),
        TrialSpec(byz=(), attack="none", steps=45, q=0.5, seed=4,
                  problem_seed=7),
    ]
    npm = run_batch(mixed)
    jxm = run_batch(mixed, backend="jax", mesh=mesh)
    compare("mixed_problems", npm, jxm)

    print("SCENARIO_DONE")


if __name__ == "__main__":
    main()
