"""ShufflePlan fast path, composition and ISA equivalence."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.fabric import (PAD, ShufflePlan, apply_plan, apply_plan_np,
                               apply_strided, concat_plans, identity_plan,
                               pad_plan_to_word, strided_form, tile_plan)


def _rand_plan(rng, n_out, n_in, width=16, pad_frac=0.2):
    gi = rng.integers(0, n_in, size=n_out).astype(np.int32)
    gi[rng.random(n_out) < pad_frac] = PAD
    pv = rng.integers(-100, 100, size=n_out)
    return ShufflePlan(gi, pv, width)


def test_identity():
    x = np.arange(10.0)
    p = identity_plan(10)
    np.testing.assert_array_equal(apply_plan_np(x, p), x)


def test_jax_matches_numpy_batched():
    rng = np.random.default_rng(0)
    plan = _rand_plan(rng, 37, 23)
    x = rng.standard_normal((4, 5, 23)).astype(np.float32)
    ref = apply_plan_np(x.copy(), plan)
    got = np.asarray(apply_plan(jnp.asarray(x), plan))
    np.testing.assert_allclose(got, ref)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 31))
def test_composition_property(seed):
    """plan_a.then(plan_b) == apply b after a."""
    rng = np.random.default_rng(seed)
    n0, n1, n2 = 17, 29, 13
    a = _rand_plan(rng, n1, n0)
    b = _rand_plan(rng, n2, n1)
    x = rng.standard_normal(n0)
    two_step = apply_plan_np(apply_plan_np(x.copy(), a), b)
    fused = apply_plan_np(x.copy(), a.then(b))
    np.testing.assert_allclose(fused, two_step)


def test_concat_and_pad_to_word():
    rng = np.random.default_rng(1)
    a = _rand_plan(rng, 5, 8, width=8)
    b = _rand_plan(rng, 6, 8, width=8)
    c = concat_plans(a, b)
    assert c.n_out == 11
    p = pad_plan_to_word(c)
    assert p.n_out % p.elems_per_word() == 0
    x = rng.integers(-100, 100, size=8)
    np.testing.assert_array_equal(apply_plan_np(x, p)[:11],
                                  apply_plan_np(x, c))


# --------------------------------------------------------------------------
# Plan classification + einsum folding helpers (v2 cross-einsum fusion)
# --------------------------------------------------------------------------

def test_is_permutation_classification():
    from repro.core.fabric import fuse_plans, is_permutation, tile_plan

    rng = np.random.default_rng(2)
    perm = ShufflePlan(rng.permutation(16).astype(np.int32),
                       np.zeros(16, np.int64))
    assert is_permutation(perm)
    assert is_permutation(identity_plan(16))
    # tiling a permutation (block-diagonal replication) stays a permutation
    assert is_permutation(tile_plan(perm, 3, 16))
    # composition of permutations is a permutation
    perm2 = ShufflePlan(rng.permutation(16).astype(np.int32),
                        np.zeros(16, np.int64))
    assert is_permutation(fuse_plans(perm, perm2))
    # duplication, padding and selection are NOT permutations
    dup = ShufflePlan(np.array([0, 0, 1, 2], np.int32), np.zeros(4, np.int64))
    assert not is_permutation(dup)
    padded = ShufflePlan(np.array([0, PAD, 1, 2], np.int32),
                         np.zeros(4, np.int64))
    assert not is_permutation(padded)
    select = ShufflePlan(np.array([0, 2, 4, 6], np.int32),
                         np.zeros(4, np.int64))
    assert not is_permutation(select)


def test_block_perm_tile():
    from repro.core.fabric import block_perm_tile, tile_plan

    rng = np.random.default_rng(3)
    inner = ShufflePlan(rng.permutation(8).astype(np.int32),
                        np.zeros(8, np.int64))
    tiled = tile_plan(inner, 4, 8)
    assert block_perm_tile(tiled) == 8          # per-tile window
    assert block_perm_tile(identity_plan(12)) == 1
    # a global rotation has no smaller tile than the whole plan
    rot = ShufflePlan(np.roll(np.arange(8), 1).astype(np.int32),
                      np.zeros(8, np.int64))
    assert block_perm_tile(rot) == 8
    # non-permutations are unclassifiable
    dup = ShufflePlan(np.array([0, 0], np.int32), np.zeros(2, np.int64))
    assert block_perm_tile(dup) is None


def test_compose_into_einsum_matches_two_pass_execution():
    """Folding (plan, diag) into an existing (pre, pre_diag) stream-in
    shuffle must equal running the two scaled gathers back to back."""
    from repro.core.fabric import compose_into_einsum

    rng = np.random.default_rng(4)
    n0, n1, n2 = 12, 10, 14
    g1 = _rand_plan(rng, n1, n0, pad_frac=0.15)
    g2 = _rand_plan(rng, n2, n1, pad_frac=0.15)
    d1 = rng.standard_normal(n1)
    d2 = rng.standard_normal(n2)
    x = rng.standard_normal(n0)

    ref = apply_plan_np(x.copy(), g1) * d1
    ref = apply_plan_np(ref, g2) * d2

    plan, diag = compose_into_einsum(g1, d1, g2, d2)
    got = apply_plan_np(x.copy(), plan) * diag
    np.testing.assert_allclose(got, ref)

    # degenerate case: nothing to fold into
    plan0, diag0 = compose_into_einsum(g1, None, None, None)
    assert plan0 is g1 and diag0 is None
    # identity stream-in with an existing scale must keep the scale
    plan1, diag1 = compose_into_einsum(g1, None, None, d1)
    assert plan1 is g1
    np.testing.assert_allclose(diag1, d1)
    plan2, diag2 = compose_into_einsum(g1, d1, None, d1)
    np.testing.assert_allclose(diag2, d1 * d1)


# --------------------------------------------------------------------------
# strided_form: which plans lower as a reshape/transpose
# --------------------------------------------------------------------------

FFT_FRAMES = 3


def _fft_stage_cases():
    from repro.core.signal_mapping import make_fft_plan
    cases = []
    for n in (8, 64, 1024):
        for i, stage in enumerate(make_fft_plan(n).stages):
            cases.append(pytest.param(n, i, "gather", id=f"fft{n}-s{i}-in"))
            if stage.scatter.n_out:
                cases.append(pytest.param(n, i, "scatter",
                                          id=f"fft{n}-s{i}-out"))
    return cases


def _assert_form_matches_take(idx, n_in, batch=2):
    """A strided form exists for ``idx`` and reproduces the gather
    bit for bit, batch axes leading."""
    form = strided_form(idx, n_in)
    assert form is not None
    assert form[2] == idx.shape
    assert sorted(form[1]) == list(range(len(form[0])))
    x = np.random.default_rng(7).standard_normal(
        (batch, n_in)).astype(np.float32)
    got = np.asarray(apply_strided(jnp.asarray(x), form))
    want = np.asarray(jnp.take(jnp.asarray(x), jnp.asarray(idx), axis=-1))
    np.testing.assert_array_equal(got, want)
    return form


@pytest.mark.parametrize("n,stage,which", _fft_stage_cases())
def test_strided_form_fft_stages(n, stage, which):
    """Every fused FFT stage's stream-in (bit reversal folded into the
    first) and the last stage's stream-out, tiled over frames, is a
    strided permutation."""
    from repro.core.signal_mapping import make_fft_plan
    plan = getattr(make_fft_plan(n).stages[stage], which)
    tiled = tile_plan(plan, FFT_FRAMES, 2 * n)
    form = _assert_form_matches_take(tiled.gather_idx, FFT_FRAMES * 2 * n)
    # merged digits: no two source axes that stay adjacent in the output
    perm = form[1]
    assert all(perm[k + 1] != perm[k] + 1 for k in range(len(perm) - 1))


def test_strided_form_2d_transpose():
    idx = np.arange(6 * 5).reshape(6, 5).T
    form = _assert_form_matches_take(idx, 30)
    assert form[:2] == ((6, 5), (1, 0))
    # the identity is the rank-1 form
    assert strided_form(np.arange(12), 12) == ((12,), (0,), (12,))


@pytest.mark.parametrize("case", ["random", "pad", "framing", "prefix"])
def test_strided_form_rejects(case):
    """Anything but a strided permutation of the whole source gathers."""
    rng = np.random.default_rng(3)
    n_in = 64
    if case == "random":          # a permutation, but not a strided one
        idx = rng.permutation(n_in)
    elif case == "pad":           # pad constants (zero imaginary parts)
        idx = np.arange(n_in)
        idx[1::2] = PAD
    elif case == "framing":       # overlapping frames read sources twice
        idx = (np.arange(4)[:, None] * 8 + np.arange(16)[None, :]).ravel()
    else:                         # a prefix of a longer source
        idx = np.arange(48)
    assert strided_form(idx, n_in) is None


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 31))
def test_strided_form_finds_any_transpose(seed):
    """Any reshape/transpose of a source (axes of length 1 to 5, rank 1
    to 5) is found, and reproduces the gather."""
    rng = np.random.default_rng(seed)
    shape = tuple(int(d) for d in rng.integers(1, 6, rng.integers(1, 6)))
    perm = tuple(int(p) for p in rng.permutation(len(shape)))
    idx = np.arange(int(np.prod(shape))).reshape(shape).transpose(perm)
    _assert_form_matches_take(idx, idx.size)
