"""Every op in repro.kernels.ops vs its repro.kernels.ref oracle, in
interpret mode (CPU validation of the TPU kernels), including
non-multiple-of-block shapes and the batched (leading trial dimension)
variants the jitted engine drives.  For the batched ops, the Pallas
kernel (interpret) and the XLA fallback are asserted against the SAME
reference, so either dispatch choice is interchangeable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, sketch

IMPLS = ("pallas", "xla")


# ---------------------------------------------------------------------------
# single-item ops (interpret=True explicitly)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [8, 255, 256, 257, 70001])
def test_sketch_vs_ref_interpret(d):
    g = jax.random.normal(jax.random.PRNGKey(d), (d,), jnp.float32)
    np.testing.assert_allclose(
        ops.sketch(g, 99, k=256, interpret=True), ref.sketch_ref(g, 99, 256),
        rtol=2e-5, atol=1e-4,
    )


@pytest.mark.parametrize("R,d", [(3, 8), (5, 2047), (5, 2048), (7, 2049)])
def test_pairwise_relmax_vs_ref_interpret(R, d):
    reps = jax.random.normal(jax.random.PRNGKey(R + d), (R, d), jnp.float32)
    np.testing.assert_allclose(
        ops.pairwise_relmax(reps, interpret=True),
        ref.pairwise_maxdiff_ref(reps), rtol=1e-6, atol=1e-6,
    )


@pytest.mark.parametrize("n_sym,m,d", [(2, 3, 8), (3, 3, 2047), (4, 2, 2049)])
def test_coded_encode_vs_ref_interpret(n_sym, m, d):
    key = jax.random.PRNGKey(d)
    C = jax.random.normal(key, (n_sym, m), jnp.float32)
    G = jax.random.normal(key, (m, d), jnp.float32)
    np.testing.assert_allclose(
        ops.coded_encode(C, G, interpret=True), ref.coded_encode_ref(C, G),
        rtol=1e-5, atol=1e-5,
    )


def test_vote_vs_majority_vote_ref():
    honest = jax.random.normal(jax.random.PRNGKey(0), (1000,), jnp.float32)
    reps = jnp.tile(honest[None], (5, 1)).at[1].multiply(-3.0)
    v_k, f_k, ok_k = ops.vote(reps, interpret=True)
    v_r, f_r, ok_r = ref.majority_vote_ref(reps, tau=1e-5)
    np.testing.assert_array_equal(v_k, v_r)
    np.testing.assert_array_equal(f_k, f_r)
    assert bool(ok_k) == bool(ok_r)


@pytest.mark.parametrize("Sq,Sk", [(64, 64), (100, 100), (63, 127)])
def test_flash_attention_vs_ref_interpret(Sq, Sk):
    ks = jax.random.split(jax.random.PRNGKey(Sq + Sk), 3)
    q = jax.random.normal(ks[0], (1, Sq, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, Sk, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, Sk, 2, 32), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32,
                            interpret=True)
    o_ref = ref.mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# batched ops: both impls vs the batched refs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("B,R,d", [(1, 3, 8), (3, 5, 2049), (4, 8, 700)])
def test_batched_pairwise_relmax(impl, B, R, d):
    reps = jax.random.normal(jax.random.PRNGKey(B + d), (B, R, d),
                             jnp.float32)
    np.testing.assert_allclose(
        ops.batched_pairwise_relmax(reps, impl=impl, interpret=True),
        ref.batched_pairwise_maxdiff_ref(reps), rtol=1e-6, atol=1e-6,
    )


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("B,s,m,d", [(1, 1, 8, 8), (3, 2, 4, 2049)])
def test_batched_coded_encode(impl, B, s, m, d):
    key = jax.random.PRNGKey(B + d)
    C = jax.random.normal(key, (B, s, m), jnp.float32)
    G = jax.random.normal(key, (B, m, d), jnp.float32)
    np.testing.assert_allclose(
        ops.batched_coded_encode(C, G, impl=impl, interpret=True),
        ref.batched_coded_encode_ref(C, G), rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("B,d", [(1, 8), (3, 70001), (5, 256)])
def test_batched_sketch(impl, B, d):
    g = jax.random.normal(jax.random.PRNGKey(B + d), (B, d), jnp.float32)
    got = ops.batched_sketch(g, 12345, impl=impl, interpret=True)
    np.testing.assert_allclose(got, ref.batched_sketch_ref(g, 12345, 256),
                               rtol=2e-5, atol=1e-3)
    # row b == the single-item op on row b
    np.testing.assert_allclose(got[0], ref.sketch_ref(g[0], 12345, 256),
                               rtol=2e-5, atol=1e-3)


@pytest.mark.parametrize("d", [8, 255, 1024, 2048, 2049])
def test_sketch_batched_rows_equal_single_kernel_bitwise(d):
    """A row of at most 8 tiles (d <= 2048) takes an 8-row block, where
    the single-vector kernel streams a 512-row one of zeros: the sums
    must not move by a bit (d = 2049 takes the 512-row block too)."""
    g = jax.random.normal(jax.random.PRNGKey(d), (3, d), jnp.float32)
    got = sketch.sketch_batched(g, 4242, interpret=True)
    for b in range(3):
        np.testing.assert_array_equal(
            np.asarray(got[b]),
            np.asarray(sketch.sketch(g[b], 4242, interpret=True)))


def test_relmax_xla_chunking_matches_unchunked():
    """The memory-bounded XLA fallback folds d in chunks; values must
    equal the naive reference regardless of the chunk boundary."""
    B, R = 12, 8                    # forces chunk = (1<<24)//(B*R*R) < d
    d = (1 << 24) // (B * R * R) + 1000
    reps = jax.random.normal(jax.random.PRNGKey(1), (B, R, d), jnp.bfloat16)
    reps = reps.astype(jnp.float32)
    np.testing.assert_array_equal(
        ops.batched_pairwise_relmax(reps, impl="xla"),
        ref.batched_pairwise_maxdiff_ref(reps),
    )


@pytest.mark.parametrize("impl", IMPLS)
def test_batched_vote_matches_majority_vote_np(impl):
    """Winners and faulty masks per replica group vs the host vote on
    each group's member stack (ascending worker order)."""
    from repro.core.identification import majority_vote_np

    rng = np.random.default_rng(7)
    n, d = 8, 64
    group = np.array([[0, 0, 0, 1, 1, 1, -1, -1],
                      [0, 1, 0, 1, 0, 1, 0, -1]], np.int32)
    grads = np.zeros((2, n, d), np.float32)
    for b in range(2):
        vals = rng.normal(size=(2, d))
        for w in range(n):
            if group[b, w] >= 0:
                grads[b, w] = vals[group[b, w]]
    grads[0, 1] *= -4.0
    grads[1, 4] += 2.0
    coeff, faulty = ops.batched_vote(jnp.asarray(grads),
                                     jnp.asarray(group), tau=1e-9,
                                     impl=impl, interpret=True)
    coeff, faulty = np.asarray(coeff), np.asarray(faulty)
    for b in range(2):
        for gid in np.unique(group[b][group[b] >= 0]):
            mem = np.flatnonzero(group[b] == gid)
            val, f_np, ok = majority_vote_np(grads[b][mem], tau=1e-9)
            assert ok
            winner = mem[int(np.argmax(
                np.all(grads[b][mem] == val[None], axis=1)))]
            assert coeff[b, winner] == 1.0
            np.testing.assert_array_equal(faulty[b, mem], f_np)
    # exactly one winner per group, none among idle workers
    assert coeff[0].sum() == 2 and coeff[1].sum() == 2
    assert not coeff[group < 0].any()


# ---------------------------------------------------------------------------
# gram-plane precompute kernel vs the composed single-op oracles
# ---------------------------------------------------------------------------


def _gram_inputs(B, Ie, d, T, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    rows = jax.random.normal(ks[0], (Ie, d), jnp.float32)
    W0 = jax.random.normal(ks[1], (B, d), jnp.float32)
    keys = np.uint32(0x9E3779B9) * (np.arange(T, dtype=np.uint32) + 1)
    return rows, W0, keys


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("B,Ie,d,T", [
    (1, 3, 8, 1),          # B = 1 singleton batch, single key
    (2, 10, 511, 3),       # d off the 512 block AND off the 256 lane
    (3, 7, 513, 2),        # just past one block
    (2, 8, 1024, 4),       # exact block multiple
])
def test_gram_factors_vs_composed_refs(impl, B, Ie, d, T):
    rows, W0, keys = _gram_inputs(B, Ie, d, T, seed=B + Ie + d + T)
    G_k, S0_k, SK_k = ops.gram_factors(rows, W0, keys, impl=impl,
                                       interpret=True)
    G_r, S0_r, SK_r = ref.gram_factors_ref(rows, W0, keys)
    assert SK_k.shape == (T, Ie, 256)
    np.testing.assert_allclose(G_k, G_r, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(S0_k, S0_r, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(SK_k, SK_r, rtol=2e-5, atol=1e-3)


@pytest.mark.parametrize("impl", IMPLS)
def test_gram_factors_no_w0_and_empty_keys(impl):
    rows, W0, keys = _gram_inputs(2, 6, 700, 3, seed=6)
    G_k, S0_k, _ = ops.gram_factors(rows, None, keys, impl=impl,
                                    interpret=True)
    assert S0_k is None
    np.testing.assert_allclose(G_k, ref.gram_factors_ref(rows, None, keys)[0],
                               rtol=1e-5, atol=1e-3)
    G0, S00, SK0 = ops.gram_factors(rows, W0, np.zeros(0, np.uint32),
                                    impl=impl, interpret=True)
    assert SK0.shape == (0, 6, 256)
    np.testing.assert_allclose(G0, G_k, rtol=1e-6, atol=1e-5)


def test_gram_factors_key_chunking_matches_unchunked(monkeypatch):
    """The pallas dispatch bounds the resident (Tc, Ie, k) sketch
    accumulator by chunking the key axis; values must not depend on
    where the chunk boundary lands."""
    rows, W0, keys = _gram_inputs(3, 10, 1024, 5, seed=3)
    full = ops.gram_factors(rows, W0, keys, impl="pallas", interpret=True)
    monkeypatch.setattr(ops, "_GRAM_SK_VMEM", 16 * 256 * 4 * 2)  # 2 keys/call
    chunked = ops.gram_factors(rows, W0, keys, impl="pallas",
                               interpret=True)
    for a, b in zip(full, chunked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gram_factors_xla_tables_match_stream_plane():
    """The gram plane's detection verdicts rest on the per-step sketch
    tables matching the values the stream plane pre-sketches.  The xla
    dispatch computes all T tables as one bucketed einsum, which sums
    each bucket in a different f32 order than the stream plane's
    per-key reshape(-1, k).sum — so the match is tight-tolerance, not
    bitwise (the ~1e-5 relative reassociation noise is orders of
    magnitude below any detection margin; the engine-level tests assert
    verdict equality end to end)."""
    rows, _, keys = _gram_inputs(1, 9, 2049, 4, seed=9)
    _, _, SK = ops.gram_factors(rows, None, keys, impl="xla")
    for t, key in enumerate(keys):
        np.testing.assert_allclose(
            np.asarray(SK[t]),
            np.asarray(ops.batched_sketch(rows, key, impl="xla")),
            rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# impl dispatch: REPRO_KERNEL_IMPL / explicit impl validation
# ---------------------------------------------------------------------------


def test_resolve_impl_rejects_bad_env(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "cuda")
    with pytest.raises(ValueError, match=r"cuda.*pallas.*xla"):
        ops.resolve_impl(None)


def test_resolve_impl_rejects_bad_explicit():
    with pytest.raises(ValueError, match=r"mosaic.*pallas.*xla"):
        ops.resolve_impl("mosaic")


def test_resolve_impl_env_and_auto(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "xla")
    assert ops.resolve_impl(None) == "xla"
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "")      # empty == unset
    assert ops.resolve_impl(None) in ("pallas", "xla")
    monkeypatch.delenv("REPRO_KERNEL_IMPL")
    assert ops.resolve_impl("pallas") == "pallas"
    # the explicit argument wins over the env override
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "cuda")
    assert ops.resolve_impl("xla") == "xla"


# ---------------------------------------------------------------------------
# property-based shape sweeps — hypothesis strategies when installed (the
# CI adaptive-smoke job), seeded sampling from the SAME pools otherwise,
# so the adversarial coverage also runs in the bare tier-1 environment.
# Corners by construction: d off the 256-lane block / chunk boundaries,
# B = 1 singleton batches, groups at the n = 2f+1 minimum quorum, trials
# with zero active workers, and key ties that probe the stable sort.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

D_OFFBLOCK = (1, 7, 255, 257, 511)      # around the k=256 sketch block
B_POOL = (1, 2, 5)
R_POOL = (2, 3, 5)
N_POOL = (2, 3, 5, 8, 9)
_PROP_CASES = 6


def _fallback_pick(case_seed, tag):
    rng = np.random.default_rng((0x5EED, case_seed, tag))
    return lambda seq: (lambda s: s[rng.integers(len(s))])(list(seq))


def _layout_arrays(pick):
    """(keys, active, repl) for a batch, with adversarial actives."""
    B = pick(B_POOL)
    n = pick(N_POOL)
    rng = np.random.default_rng(pick(range(1 << 16)))
    repl = np.array([pick(R_POOL) for _ in range(B)], np.int32)
    tie = pick([True, False])
    hi = 4 if tie else 1 << 32          # ties exercise the stable argsort
    keys = rng.integers(0, hi, size=(B, n), dtype=np.uint32)
    active = np.ones((B, n), bool)
    for b in range(B):
        r = int(repl[b])
        kind = pick(["all", "none", "quorum", "sub", "random"])
        if kind == "none":              # zero active workers
            active[b] = False
        elif kind == "quorum":          # exactly r active -> m = 1
            active[b] = False
            active[b, rng.choice(n, size=min(r, n), replace=False)] = True
        elif kind == "sub":             # fewer than r active -> m = 0
            active[b] = False
            active[b, rng.choice(n, size=min(r, n) - 1, replace=False)] = True
        elif kind == "random":
            active[b] = rng.random(n) < 0.6
    return keys, active, repl


def _prop_sketch(impl, pick):
    B, d = pick(B_POOL), pick(D_OFFBLOCK)
    g = jax.random.normal(jax.random.PRNGKey(pick(range(1 << 16))),
                          (B, d), jnp.float32)
    key = pick(range(1 << 16))
    np.testing.assert_allclose(
        ops.batched_sketch(g, key, impl=impl, interpret=True),
        ref.batched_sketch_ref(g, key, 256), rtol=2e-5, atol=1e-3)


def _prop_relmax(impl, pick):
    B, R, d = pick(B_POOL), pick(R_POOL), pick(D_OFFBLOCK)
    reps = jax.random.normal(jax.random.PRNGKey(pick(range(1 << 16))),
                             (B, R, d), jnp.float32)
    np.testing.assert_allclose(
        ops.batched_pairwise_relmax(reps, impl=impl, interpret=True),
        ref.batched_pairwise_maxdiff_ref(reps), rtol=1e-6, atol=1e-6)


def _prop_coded_encode(impl, pick):
    B, s, m, d = pick(B_POOL), pick((1, 2, 4)), pick((2, 3, 5)), \
        pick(D_OFFBLOCK)
    key = jax.random.PRNGKey(pick(range(1 << 16)))
    C = jax.random.normal(key, (B, s, m), jnp.float32)
    G = jax.random.normal(jax.random.fold_in(key, 1), (B, m, d), jnp.float32)
    np.testing.assert_allclose(
        ops.batched_coded_encode(C, G, impl=impl, interpret=True),
        ref.batched_coded_encode_ref(C, G), rtol=1e-5, atol=1e-5)


def _prop_regroup(pick):
    keys, active, repl = _layout_arrays(pick)
    shard, group, m = ops.batched_regroup(
        jnp.asarray(keys), jnp.asarray(active), jnp.asarray(repl))
    s_ref, g_ref, m_ref = ref.batched_regroup_ref(keys, active, repl)
    np.testing.assert_array_equal(np.asarray(shard), s_ref)
    np.testing.assert_array_equal(np.asarray(group), g_ref)
    np.testing.assert_array_equal(np.asarray(m), m_ref)


def _prop_masked_composites(impl, pick):
    """vote/detect masked composites == regroup_ref layout + the
    unmasked op on that layout, and a False gate idles the trial."""
    from repro.core.detection import detect_groups_batched

    keys, active, repl = _layout_arrays(pick)
    B, n = active.shape
    d = pick(D_OFFBLOCK)
    rng = np.random.default_rng(pick(range(1 << 16)))
    s_ref, g_ref, m_ref = ref.batched_regroup_ref(keys, active, repl)
    grads = np.zeros((B, n, d), np.float32)
    for b in range(B):                  # per-group shared values...
        vals = rng.normal(size=(n, d)).astype(np.float32)
        for w in range(n):
            if g_ref[b, w] >= 0:
                grads[b, w] = vals[g_ref[b, w]]
        mem = np.flatnonzero(g_ref[b] >= 0)
        if mem.size and pick([True, False]):   # ...one corrupted member
            grads[b, rng.choice(mem)] *= -3.0
    gate = np.array([pick([True, False]) for _ in range(B)])
    wc, faulty, shard, group, m = ops.batched_vote_masked(
        jnp.asarray(grads), jnp.asarray(keys), jnp.asarray(active),
        jnp.asarray(repl), tau=1e-6, gate=jnp.asarray(gate), impl=impl,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(shard), s_ref)
    np.testing.assert_array_equal(np.asarray(group), g_ref)
    np.testing.assert_array_equal(np.asarray(m), m_ref)
    gv = np.where(gate[:, None], g_ref, -1)
    wc_u, faulty_u = ops.batched_vote(jnp.asarray(grads), jnp.asarray(gv),
                                      tau=1e-6, impl=impl, interpret=True)
    np.testing.assert_array_equal(np.asarray(wc), np.asarray(wc_u))
    np.testing.assert_array_equal(np.asarray(faulty), np.asarray(faulty_u))
    assert not np.asarray(wc)[~gate].any()

    symbols = np.asarray(ref.batched_sketch_ref(
        jnp.asarray(grads.reshape(B * n, d)), 7, 256)).reshape(B, n, -1)
    fault, mism, shard2, group2, m2 = ops.batched_detect_masked(
        jnp.asarray(symbols), jnp.asarray(keys), jnp.asarray(active),
        jnp.asarray(repl), tau=1e-6, gate=jnp.asarray(gate))
    f_ref, mm_ref = detect_groups_batched(jnp.asarray(symbols),
                                          jnp.asarray(gv), tau=1e-6)
    np.testing.assert_array_equal(np.asarray(group2), g_ref)
    np.testing.assert_array_equal(np.asarray(fault), np.asarray(f_ref))
    np.testing.assert_array_equal(np.asarray(mism), np.asarray(mm_ref))
    assert not np.asarray(fault)[~gate].any()


_PROPS = {
    "sketch": (_prop_sketch, True),
    "relmax": (_prop_relmax, True),
    "coded_encode": (_prop_coded_encode, True),
    "regroup": (_prop_regroup, False),
    "masked_composites": (_prop_masked_composites, True),
}


def _run_prop(name, impl, pick):
    fn, takes_impl = _PROPS[name]
    fn(impl, pick) if takes_impl else fn(pick)


if HAVE_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(data=st.data())
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("name", sorted(_PROPS))
    def test_prop_batched_ops(name, impl, data):
        _run_prop(name, impl,
                  lambda seq: data.draw(st.sampled_from(list(seq))))

else:

    @pytest.mark.parametrize("case_seed", range(_PROP_CASES))
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("name", sorted(_PROPS))
    def test_prop_batched_ops(name, impl, case_seed):
        tag = hash((name, impl)) & 0xFFFF
        _run_prop(name, impl, _fallback_pick(case_seed, tag))
