"""The plain reference agrees with the engine's float64 numpy pass on
small problems, bit for bit, under both random-stream contracts."""
import numpy as np
import pytest

from bench.reference import lsq_protocol


@pytest.mark.parametrize("streams", ["host", "device"])
@pytest.mark.parametrize("attack,q", [("drift", 0.2), ("sign_flip", None),
                                      ("sign_flip", 0.4)])
def test_reference_matches_numpy_engine(streams, attack, q):
    from repro.core.engine import TrialSpec, run_batch

    seed = 2**31 + 12345
    specs = [TrialSpec(byz=(2, 5), attack=attack, q=q, steps=30,
                       seed=seed + b, problem_seed=seed, n_data=64, d=512,
                       lr=16 / 512) for b in range(3)]
    res = run_batch(specs, rng=streams)
    for s, r in zip(specs, res.results):
        ref = lsq_protocol.run_trial(
            {k: getattr(s, k) for k in (
                "n", "f", "byz", "attack", "p_tamper", "steps", "q", "lr",
                "seed", "problem_seed", "n_data", "d")}, streams=streams)
        assert np.array_equal(r.w, ref["w"])
        assert r.losses == ref["losses"]
        assert np.allclose(r.q_trace, ref["q"], rtol=1e-6, atol=0)
        assert r.identify_step == ref["identified_at"]
        assert (r.state.meter.used, r.state.meter.computed) == (
            ref["used"], ref["computed"])


def test_device_streams_are_counter_indexed():
    a = lsq_protocol.DeviceStreams(7, 10, 8, 0.5)
    b = lsq_protocol.DeviceStreams(7, 20, 8, 0.5)
    assert np.array_equal(a.u_coin, b.u_coin[:10])
    assert np.array_equal(a.perm_keys, b.perm_keys[:10])
    assert ((a.u_tamper >= 0) & (a.u_tamper < 1)).all()
