"""Pallas kernels (interpret mode) vs pure-jnp oracles — shape/dtype sweeps
plus property tests on the poison semantics (hypothesis when available,
a seeded-random fallback loop otherwise)."""
import random

import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from conftest import dae_test_seed

# fallback sample drawn from the single DAE_TEST_SEED knob (see conftest)
_FALLBACK_SEEDS = sorted(
    random.Random(dae_test_seed()).sample(range(10_000), 15))

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ragged_matmul import ragged_matmul
from repro.kernels.spec_gather import spec_gather
from repro.kernels.spec_scatter import spec_scatter_add

RNG = np.random.default_rng(7)


def _arr(shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


# ---------------------------------------------------------------------------
# spec_gather
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("v,d,n,bd", [(32, 128, 16, 64), (8, 256, 40, 256),
                                      (64, 512, 7, 128), (4, 128, 1, 128)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32, jnp.bfloat16])
def test_spec_gather_sweep(v, d, n, bd, dtype):
    table = _arr((v, d)).astype(dtype)
    idx = jnp.asarray(RNG.integers(-3, v, n).astype(np.int32))
    got = spec_gather(table, idx, block_d=bd)
    np.testing.assert_allclose(got, ref.spec_gather(table, idx), atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spec_gather_exact_bits(dtype):
    """The kernel picks a row out of its fetched row block; the pick must
    return every value bit for bit — signed zeros, infinities and NaN
    included — whatever the other rows of the block hold."""
    special = np.array([-0.0, 0.0, np.inf, -np.inf, np.nan, -1.5, 3.0,
                        np.finfo(np.float32).tiny], np.float32)
    table = jnp.asarray(np.resize(special, (24, 128))
                        * np.arange(1, 25, dtype=np.float32)[:, None]
                        ).astype(dtype)
    table = table.at[::3].set(-0.0)
    idx = jnp.asarray(np.arange(23, -1, -1).astype(np.int32))
    got = np.asarray(spec_gather(table, idx, block_d=128))
    want = np.asarray(table)[np.asarray(idx)]
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(got.view(bits), want.view(bits))


def test_spec_gather_all_poisoned():
    table = _arr((8, 128))
    idx = jnp.full((5,), -1, jnp.int32)
    assert np.all(np.asarray(spec_gather(table, idx)) == 0)


# ---------------------------------------------------------------------------
# spec_scatter_add
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("v,d,n", [(16, 128, 24), (8, 64, 40), (5, 128, 17)])
def test_spec_scatter_sweep(v, d, n):
    table = _arr((v, d))
    idx = jnp.asarray(RNG.integers(-3, v, n).astype(np.int32))
    vals = _arr((n, d))
    got = spec_scatter_add(table, idx, vals, block_d=64)
    np.testing.assert_allclose(got, ref.spec_scatter_add(table, idx, vals),
                               atol=1e-4)


def _check_scatter_poison_never_commits(seed):
    """Paper §3.1: mis-speculated stores are never committed — rows only
    referenced by poisoned requests are bit-identical afterwards."""
    r = np.random.default_rng(seed)
    v, d, n = 12, 64, 20
    table = jnp.asarray(r.normal(size=(v, d)).astype(np.float32))
    idx = r.integers(0, v, n).astype(np.int32)
    poisoned_rows = r.choice(v, 4, replace=False)
    idx = np.where(np.isin(idx, poisoned_rows), -1, idx)
    out = spec_scatter_add(table, jnp.asarray(idx),
                           jnp.asarray(r.normal(size=(n, d)).astype(np.float32)),
                           block_d=64)
    touched = set(int(i) for i in idx if i >= 0)
    for row in range(v):
        if row not in touched:
            np.testing.assert_array_equal(np.asarray(out[row]),
                                          np.asarray(table[row]))


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None, derandomize=True)
    @given(st.integers(0, 10_000))
    def test_spec_scatter_poison_never_commits(seed):
        _check_scatter_poison_never_commits(seed)
else:
    @pytest.mark.parametrize("seed", _FALLBACK_SEEDS)
    def test_spec_scatter_poison_never_commits(seed):
        _check_scatter_poison_never_commits(seed)


# ---------------------------------------------------------------------------
# ragged_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("e,c,d,f,bm,bn,bk", [
    (4, 64, 128, 256, 32, 128, 64),
    (2, 128, 256, 128, 128, 128, 128),
    (8, 32, 64, 64, 32, 64, 64),
])
def test_ragged_matmul_sweep(e, c, d, f, bm, bn, bk):
    x = _arr((e * c, d))
    w = _arr((e, d, f))
    got = ragged_matmul(x, w, capacity=c, bm=bm, bn=bn, bk=bk)
    np.testing.assert_allclose(got, ref.ragged_matmul(x, w, c),
                               atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,t,d,bq,bk", [(2, 3, 256, 64, 64, 64),
                                           (1, 2, 128, 128, 128, 64),
                                           (1, 1, 512, 64, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, t, d, bq, bk, causal):
    q, k, v = _arr((b, h, t, d)), _arr((b, h, t, d)), _arr((b, h, t, d))
    got = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,d,p,page,nmax", [(3, 4, 64, 16, 8, 5),
                                               (1, 8, 128, 8, 16, 3),
                                               (2, 2, 64, 32, 8, 8)])
def test_paged_attention_sweep(b, h, d, p, page, nmax):
    q = _arr((b, h, d))
    kp, vp = _arr((p, page, h, d)), _arr((p, page, h, d))
    pt = jnp.asarray(RNG.integers(0, p, (b, nmax)).astype(np.int32))
    seq = jnp.asarray(RNG.integers(1, page * nmax, b).astype(np.int32))
    # poison pages past each sequence's end (speculative tail fetch)
    used = (np.asarray(seq) + page - 1) // page
    ptn = np.asarray(pt).copy()
    for i in range(b):
        ptn[i, used[i]:] = -1
    pt = jnp.asarray(ptn)
    got = paged_attention(q, kp, vp, pt, seq)
    want = ref.paged_attention(q, kp, vp, pt, seq)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_paged_matches_flash_decode():
    """Paged decode == dense attention over the materialized cache."""
    b, h, d, page = 2, 4, 64, 8
    t = 40
    n_pages = t // page + 1
    q1 = _arr((b, h, 1, d))
    k = _arr((b, h, t, d))
    v = _arr((b, h, t, d))
    want = ref.flash_attention(q1, k, v, causal=False)[:, :, 0]

    # scatter the dense cache into pages
    pool_k = np.zeros((b * n_pages, page, h, d), np.float32)
    pool_v = np.zeros_like(pool_k)
    pt = np.full((b, n_pages), -1, np.int32)
    for i in range(b):
        for pg in range((t + page - 1) // page):
            pid = i * n_pages + pg
            lo, hi = pg * page, min((pg + 1) * page, t)
            pool_k[pid, :hi - lo] = np.asarray(k[i, :, lo:hi]).transpose(1, 0, 2)
            pool_v[pid, :hi - lo] = np.asarray(v[i, :, lo:hi]).transpose(1, 0, 2)
            pt[i, pg] = pid
    got = paged_attention(q1[:, :, 0], jnp.asarray(pool_k),
                          jnp.asarray(pool_v), jnp.asarray(pt),
                          jnp.full((b,), t, jnp.int32))
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)
