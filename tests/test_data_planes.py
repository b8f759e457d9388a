"""The jax backend's two data planes against the numpy engine, off the
shapes the SCENARIOS grid runs.

The stream plane carries the (B, d) iterate; the gram plane carries
(B, I) residual coefficients against the precomputed Gram factors.
Both must reproduce the float64 numpy engine's control quantities and
detection verdicts exactly and its values at the 1e-4 contract, under
either kernel dispatch: the Pallas kernels (interpret mode off a TPU)
and their XLA fallbacks.  The shapes put d on and off the kernels' 512
column blocks and 256 sketch lanes.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.engine import SCENARIOS, TrialSpec, run_batch

W_RTOL, W_ATOL = 1e-4, 1e-4
LOSS_RTOL, LOSS_ATOL = 1e-3, 1e-4

# (B, n_data, d): a singleton batch at tiny d, d one short of a 512
# block (and off the 256 sketch lane), one past it, and two blocks
EDGE_SHAPES = [(1, 3, 8), (2, 10, 511), (3, 7, 513), (2, 8, 1024)]
EDGE_ATTACKS = ("drift", "sign_flip", "noise")


def _edge_specs(B, n_data, d):
    # lr below 1/λmax of every worker's shard Hessian (~2d/rows): the
    # iterates converge, so the value comparison is not vacuous
    lr = min(0.05, 1.0 / d)
    return [TrialSpec(n=3, f=1, byz=(1,), attack=EDGE_ATTACKS[b % 3],
                      q=0.5, steps=10, seed=b + 1, n_data=n_data, d=d,
                      lr=lr, label=f"b{b}")
            for b in range(B)]


def _control_equal(rn, rj):
    return (rn.identify_step == rj.identify_step
            and rn.efficiency == rj.efficiency
            and rn.q_trace == rj.q_trace
            and np.array_equal(rn.state.identified, rj.state.identified)
            and np.array_equal(rn.state.active, rj.state.active))


def _assert_detect_flags_exact(res):
    sched = res.schedule.arrays
    want = sched["checks"] & sched["identify"]
    assert np.array_equal(res.detect_flags, want)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("plane", ["stream", "gram"])
@pytest.mark.parametrize("B,n_data,d", EDGE_SHAPES,
                         ids=[f"B{b}-I{i}-d{d}" for b, i, d in EDGE_SHAPES])
def test_edge_shapes_match_numpy_engine(B, n_data, d, plane, impl):
    specs = _edge_specs(B, n_data, d)
    npb = run_batch(specs)
    jxb = run_batch(specs, backend="jax", data_plane=plane,
                    kernel_impl=impl)
    assert jxb.plan.data_plane == plane
    assert jxb.plan.kernel_impl == impl
    for spec, rn, rj in zip(specs, npb, jxb):
        assert _control_equal(rn, rj), spec.label
        np.testing.assert_allclose(rj.w, np.asarray(rn.w), rtol=W_RTOL,
                                   atol=W_ATOL, err_msg=spec.label)
        np.testing.assert_allclose(np.asarray(rj.losses),
                                   np.asarray(rn.losses), rtol=LOSS_RTOL,
                                   atol=LOSS_ATOL, err_msg=spec.label)
    _assert_detect_flags_exact(jxb)


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_stream_plane_pallas_matches_xla(name):
    """The SCENARIOS grid on the stream plane through the Pallas kernels
    and through their XLA fallbacks: the same control and detection."""
    mx = dataclasses.replace(SCENARIOS[name],
                             steps=min(SCENARIOS[name].steps, 40))
    pal = mx.run(backend="jax", data_plane="stream", kernel_impl="pallas")
    xla = mx.run(backend="jax", data_plane="stream", kernel_impl="xla")
    assert pal.plan.data_plane == xla.plan.data_plane == "stream"
    for spec, rp, rx in zip(pal.specs, pal, xla):
        assert _control_equal(rp, rx), spec.label
        np.testing.assert_allclose(rp.w, rx.w, rtol=W_RTOL, atol=W_ATOL,
                                   err_msg=spec.label)
    assert np.array_equal(pal.detect_flags, xla.detect_flags)
    _assert_detect_flags_exact(pal)


def _sup_dev(a, b) -> float:
    """max|a - b| / (1 + max|b|), the production-d value contract."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (1.0 + np.abs(b).max()))


def test_gram_matches_stream_at_production_d():
    """d = 2^16 with 64 data rows: the auto plan takes gram, which must
    agree with the stream plane (and both with the numpy engine) —
    control exact, values within 1e-4 of the iterate's scale.  lr =
    n_data/d keeps gradient descent contractive at this d."""
    d = 1 << 16
    specs = [TrialSpec(byz=(2, 5), attack="drift", q=0.2, steps=3,
                       seed=s, n_data=64, d=d, lr=64.0 / d)
             for s in range(8)]
    gr = run_batch(specs, backend="jax")
    st = run_batch(specs, backend="jax", data_plane="stream")
    npb = run_batch(specs)
    assert (gr.plan.data_plane, st.plan.data_plane) == ("gram", "stream")
    assert np.array_equal(gr.detect_flags, st.detect_flags)
    for k, v in st.schedule.arrays.items():
        assert np.array_equal(v, gr.schedule.arrays[k]), k
    for rg, rs, rn in zip(gr, st, npb):
        assert _control_equal(rs, rg) and _control_equal(rn, rg)
        assert _sup_dev(rg.w, rs.w) <= 1e-4
        assert _sup_dev(rg.w, rn.w) <= 1e-4
        assert _sup_dev(rs.w, rn.w) <= 1e-4
