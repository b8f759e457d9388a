"""The identify step's blocked majority vote (``steps._majority_vote``).

Run on four forced host devices in a child process, with the vote block
cut to 8 elements so a 21-element leaf spans two full blocks and a
5-element tail.  Three of the four workers form one replica group; one
replica is tampered at a single coordinate, in a full block or in the
tail.  The vote must flag exactly that replica and return the honest
gradient.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CASES = {"clean": None, "full_block": (1, 3), "tail": (2, 19)}

_CHILD = """
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.sharding import make_mesh, shard_map
from repro.train import steps

steps._VOTE_BLOCK = 8
mesh = make_mesh((4,), ("data",))
members = np.array([[0, 1, 2]])
base = np.random.default_rng(0).normal(size=(21,)).astype(np.float32)

def body(x):
    widx = jax.lax.axis_index("data")
    value, faulty = steps._majority_vote(x[0], widx, ("data",), members, 1e-5)
    return value[None], faulty[None]

vote = jax.jit(shard_map(body, mesh, in_specs=P("data"),
                         out_specs=(P("data"), P("data")), check_vma=False))
out = {}
for name, where in json.loads(%r).items():
    g = np.tile(base, (4, 1))
    if where is not None:
        g[where[0], where[1]] += 1.0
    value, faulty = vote(jnp.asarray(g))
    out[name] = {"value": np.asarray(value).tolist(),
                 "faulty": np.asarray(faulty).tolist()}
out["base"] = base.tolist()
print("VOTE " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def votes():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    proc = subprocess.run([sys.executable, "-c", _CHILD % json.dumps(CASES)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(s for s in proc.stdout.splitlines() if s.startswith("VOTE "))
    return json.loads(line[len("VOTE "):])


@pytest.mark.parametrize("case", sorted(CASES))
def test_vote_flags_the_tampered_replica(votes, case):
    got = votes[case]
    want = [False, False, False]
    if CASES[case] is not None:
        want[CASES[case][0]] = True
    for worker in range(4):                 # every worker holds the result
        assert got["faulty"][worker] == [want]
        np.testing.assert_array_equal(got["value"][worker], votes["base"])
