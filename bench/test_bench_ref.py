"""The yardstick copies in bench/ref agree with the program's own
generator and oracles (CPU, small sizes)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.ref import graph as gref
from bench.ref import moe as mref
from bench.ref import rmat


@pytest.mark.parametrize("scale,seed", [(8, 1), (10, 7), (11, 2**31 + 5)])
def test_rmat_copy_matches_program_generator(scale, seed):
    from repro.sparse import datasets
    want = datasets.rmat(scale, edge_factor=16, seed=seed)
    got = rmat.rmat(scale, 16, seed)
    np.testing.assert_array_equal(got.row_ptr, want.row_ptr)
    np.testing.assert_array_equal(got.col_idx, want.col_idx)
    np.testing.assert_array_equal(got.values, want.values)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_cut_keeps_an_undirected_subgraph_of_the_set_size(seed):
    g = rmat.rmat(10, 16, seed)
    cut = rmat.cut_to(g, 14000, seed)
    assert cut.nnz == 14000 and cut.n == g.n
    key = cut.rows() * g.n + cut.col_idx
    back = np.sort(cut.col_idx.astype(np.int64) * g.n + cut.rows())
    np.testing.assert_array_equal(key, back)         # both directions kept
    full = dict(zip((g.rows() * g.n + g.col_idx).tolist(), g.values))
    assert all(full[k] == w for k, w in zip(key.tolist(), cut.values))
    again = rmat.cut_to(g, 14000, seed)
    np.testing.assert_array_equal(again.col_idx, cut.col_idx)
    with pytest.raises(ValueError):
        rmat.cut_to(g, g.nnz + 2, seed)


def _csr(g):
    from repro.sparse.csr import CSR
    return CSR(g.row_ptr, g.col_idx, g.values)


@pytest.mark.parametrize("seed", [1, 4])
def test_bfs_oracle_matches_program_oracle(seed):
    from repro.sparse import ref
    g = rmat.rmat(10, 16, seed)
    for root in np.flatnonzero(g.degrees() > 0)[:5]:
        np.testing.assert_array_equal(gref.bfs(g, int(root)),
                                      ref.bfs_ref(_csr(g), int(root)))


def test_pagerank_oracle_matches_program_oracle():
    from repro.sparse import ref
    g = rmat.rmat(10, 16, 3)
    np.testing.assert_allclose(gref.pagerank(g, 0.85, 20),
                               ref.pagerank_ref(_csr(g), 0.85, 20),
                               rtol=1e-12, atol=0)


def test_pagerank_bf16_control_is_coarser():
    g = rmat.rmat(10, 16, 3)
    want = gref.pagerank(g, 0.85, 20)
    err = gref.max_rel_err(gref.pagerank(g, 0.85, 20, bf16=True), want)
    assert 1e-3 < err < 0.1


def test_bf16_rounding():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-8, 3.14159265, 1e-7])
    got = gref._bf16(x)
    want = np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)
    np.testing.assert_array_equal(got, want)


def test_moe_reference_matches_moe_einsum_where_nothing_drops():
    from repro.configs import get_config
    from repro.models.moe import moe_einsum
    d, e, k, f = 64, 8, 2, 32
    arch = get_config("olmoe-1b-7b")
    arch = dataclasses.replace(
        arch, d_model=d, moe=dataclasses.replace(
            arch.moe, num_experts=e, top_k=k, d_expert=f,
            capacity_factor=float(e)))       # room for every token
    params = mref.init_params(jax.random.key(0), d, e, f)
    params = {n: w.astype(jnp.float32) for n, w in params.items()}
    x = jax.random.normal(jax.random.key(1), (2, 64, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = moe_einsum(params, x, arch)[0].reshape(-1, d)
    got, margin = mref.forward(params, x, k)
    assert float(jnp.min(margin)) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_moe_fp8_control_is_coarser():
    d, e, k, f = 64, 8, 2, 32
    params = mref.init_params(jax.random.key(0), d, e, f)
    x = jax.random.normal(jax.random.key(1), (128, d), jnp.float32)
    want, _ = mref.forward(params, x, k)
    got, _ = mref.forward(params, x, k, fp8=True)
    err = float(jnp.max(mref.token_rel_err(got, want)))
    assert 0.02 < err < 0.5
