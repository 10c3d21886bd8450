"""Pallas Block-COO SDDMM kernel vs pure-jnp oracle (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.formats import BlockCOO
from repro.kernels.sddmm.ops import sddmm_blockcoo
from repro.kernels.sddmm.ref import sddmm_blockcoo_ref
from repro.sparse.paths import sddmm_element_dots


@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (256, 256, 256, 64, 128, 128),
    (128, 256, 512, 64, 64, 256),
    (64, 128, 128, 64, 128, 128),
])
@pytest.mark.parametrize("density", [0.05, 0.5])
def test_sddmm_kernel_matches_ref(rng, m, n, k, bm, bn, bk, density):
    maskd = (rng.random((m, n)) < density).astype(np.float32)
    coo = BlockCOO.from_dense(maskd, bm, bn)
    b = rng.normal(size=(m, k)).astype(np.float32)
    c = rng.normal(size=(k, n)).astype(np.float32)
    ref = sddmm_blockcoo_ref(coo, jnp.asarray(b), jnp.asarray(c))
    out = sddmm_blockcoo(coo, jnp.asarray(b), jnp.asarray(c), bk=bk,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out.blocks),
                               np.asarray(ref.blocks), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref.to_dense(), maskd * (b @ c),
                               rtol=3e-4, atol=3e-4)


def test_sddmm_weighted_mask(rng):
    """A carries values (not just 0/1): Y = A ⊙ (B C)."""
    m = n = k = 128
    a = np.where(rng.random((m, n)) < 0.2, rng.normal(size=(m, n)), 0.0) \
        .astype(np.float32)
    coo = BlockCOO.from_dense(a, 64, 64)
    b = rng.normal(size=(m, k)).astype(np.float32)
    c = rng.normal(size=(k, n)).astype(np.float32)
    out = sddmm_blockcoo(coo, jnp.asarray(b), jnp.asarray(c), interpret=True)
    np.testing.assert_allclose(out.to_dense(), a * (b @ c),
                               rtol=3e-4, atol=3e-4)


def test_sddmm_padded_blocks(rng):
    maskd = (rng.random((128, 128)) < 0.1).astype(np.float32)
    coo = BlockCOO.from_dense(maskd, 64, 64, pad_to=8)
    b = rng.normal(size=(128, 128)).astype(np.float32)
    c = rng.normal(size=(128, 128)).astype(np.float32)
    out = sddmm_blockcoo(coo, jnp.asarray(b), jnp.asarray(c), interpret=True)
    np.testing.assert_allclose(out.to_dense(), maskd * (b @ c),
                               rtol=3e-4, atol=3e-4)


def test_sddmm_coo_elementwise_small_k(rng):
    """The paper's GAT case: K=2."""
    m = n = 64
    mask = rng.random((m, n)) < 0.2
    rows, cols = np.nonzero(mask)
    b = rng.normal(size=(m, 2)).astype(np.float32)
    c = rng.normal(size=(2, n)).astype(np.float32)
    vals = sddmm_element_dots(jnp.asarray(rows), jnp.asarray(cols),
                              jnp.asarray(b), jnp.asarray(c))
    expected = (b @ c)[rows, cols]
    np.testing.assert_allclose(np.asarray(vals), expected, rtol=2e-4,
                               atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(nr=st.integers(1, 3), nc=st.integers(1, 3),
       density=st.floats(0.05, 1.0), seed=st.integers(0, 2**31 - 1))
def test_sddmm_property(nr, nc, density, seed):
    rng = np.random.default_rng(seed)
    m, n, k = nr * 64, nc * 128, 128
    maskd = (rng.random((m, n)) < density).astype(np.float32)
    coo = BlockCOO.from_dense(maskd, 64, 128)
    b = rng.normal(size=(m, k)).astype(np.float32)
    c = rng.normal(size=(k, n)).astype(np.float32)
    out = sddmm_blockcoo(coo, jnp.asarray(b), jnp.asarray(c), interpret=True)
    np.testing.assert_allclose(out.to_dense(), maskd * (b @ c),
                               rtol=5e-4, atol=5e-4)
