"""Pallas kernels vs pure-jnp oracle: shape/dtype sweep (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.neighbor_agg.ops import neighbor_agg
from repro.kernels.neighbor_agg.ref import neighbor_agg_ref


@pytest.mark.parametrize("kernel", ["row", "tiled"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,b,k", [
    (64, 32, 8, 4),
    (128, 128, 16, 5),
    (50, 96, 4, 3),        # d padded to the 128 lane tile internally
    (200, 256, 32, 15),    # paper's recommended beta=15
    (16, 8, 16, 1),
])
def test_kernel_matches_oracle(n, d, b, k, dtype, kernel, rng):
    feats = jnp.asarray(rng.normal(size=(n, d)), dtype)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.random((b, k)) * (rng.random((b, k)) > 0.3), dtype)
    ref = neighbor_agg(feats, idx, w, use_kernel=False)
    ker = neighbor_agg(feats, idx, w, use_kernel=True, kernel=kernel,
                       d_tile=32 if d % 32 == 0 else 128)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(ker, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("b_tile,k_slab", [(4, 2), (8, 4), (16, 1)])
def test_tiled_kernel_tile_shapes(b_tile, k_slab, rng):
    """Tile sizes that do NOT divide (B, K) force padded rows and padded
    K-slab edges — both must stay exact (zero-weight contributions)."""
    n, d, b, k = 100, 80, 13, 7
    feats = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.random((b, k)) * (rng.random((b, k)) > 0.4),
                    jnp.float32)
    ref = neighbor_agg(feats, idx, w, use_kernel=False)
    ker = neighbor_agg(feats, idx, w, use_kernel=True, kernel="tiled",
                       b_tile=b_tile, k_slab=k_slab)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kernel", ["row", "tiled"])
def test_kernel_zero_weights_give_zero(kernel, rng):
    feats = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 32, (4, 6)), jnp.int32)
    w = jnp.zeros((4, 6), jnp.float32)
    out = neighbor_agg(feats, idx, w, use_kernel=True, kernel=kernel,
                       d_tile=64)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("kernel", ["row", "tiled"])
def test_kernel_is_gcn_aggregation(small_graph, kernel):
    """The kernel computes the paper's Ã-weighted aggregation: compare a
    full-graph GCN aggregation step against einsum on the ELL layout."""
    from repro.core.graph import to_ell
    g = small_graph
    idx, w, w_self = to_ell(g)
    feats = jnp.asarray(g.feats)
    ker = neighbor_agg(feats, jnp.asarray(idx), jnp.asarray(w),
                       use_kernel=True, kernel=kernel,
                       d_tile=16)
    ref = neighbor_agg_ref(feats, jnp.asarray(idx), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("n,d,b,k", [
    (64, 32, 8, 4),
    (100, 80, 13, 7),      # B/D/K all padded
    (200, 256, 32, 15),
])
def test_tiled_kernel_fused_self_epilogue(n, d, b, k, rng):
    """The fused w_self·self_rows epilogue (accumulator init) matches
    aggregate-then-add to f32 tolerance, including padded tiles."""
    feats = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.random((b, k)) * (rng.random((b, k)) > 0.3),
                    jnp.float32)
    sr = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ws = jnp.asarray(rng.random(b), jnp.float32)
    ref = neighbor_agg(feats, idx, w, sr, ws)          # jnp oracle path
    ker = neighbor_agg(feats, idx, w, sr, ws, use_kernel=True,
                       kernel="tiled")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(ker),
                               atol=1e-5, rtol=1e-5)


def test_fused_kernel_vjp_matches_jnp_grads(rng):
    """All four diff args of the fused kernel (feats, w, self_rows,
    w_self) must match jnp autodiff through the oracle path."""
    n, d, b, k = 60, 48, 12, 5
    feats = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.random((b, k)), jnp.float32)
    sr = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    ws = jnp.asarray(rng.random(b), jnp.float32)

    def loss(f, ww, s, sw, use_kernel):
        out = neighbor_agg(f, idx, ww, s, sw, use_kernel=use_kernel,
                           kernel="tiled")
        return jnp.sum(out ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1, 2, 3))(feats, w, sr, ws, False)
    g_ker = jax.grad(loss, argnums=(0, 1, 2, 3))(feats, w, sr, ws, True)
    for a, b_ in zip(g_ref, g_ker):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-3, rtol=1e-3)


def test_kernel_custom_vjp_matches_jnp_grads(rng):
    """Training paths differentiate through the kernel: the custom VJP
    (scatter-add dfeats, gathered-dot dw) must match jnp autodiff."""
    n, d, b, k = 60, 48, 12, 5
    feats = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.random((b, k)), jnp.float32)

    def loss(f, ww, use_kernel):
        out = neighbor_agg(f, idx, ww, use_kernel=use_kernel,
                           kernel="tiled")
        return jnp.sum(out ** 2)

    gf_ref, gw_ref = jax.grad(loss, argnums=(0, 1))(feats, w, False)
    gf_ker, gw_ker = jax.grad(loss, argnums=(0, 1))(feats, w, True)
    np.testing.assert_allclose(np.asarray(gf_ref), np.asarray(gf_ker),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gw_ref), np.asarray(gw_ker),
                               atol=1e-3, rtol=1e-3)


def test_interpret_mode_follows_the_backend(monkeypatch):
    """Off a TPU the kernels interpret; on a TPU they compile, and an
    explicit request to interpret there is refused."""
    import repro.kernels as K
    assert K.resolve_interpret(None) is (jax.default_backend() != "tpu")
    monkeypatch.setattr(K, "default_interpret", lambda: False)
    assert K.resolve_interpret(None) is False
    assert K.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="TPU"):
        K.resolve_interpret(True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiled_kernel_gradients_keep_true_width(dtype, rng):
    """D pads to whole lane tiles inside the kernel's custom VJP, so the
    gradient shapes are the caller's (bf16 172 -> 256 columns is padded
    for the forward only) and match jnp autodiff."""
    n, d, b, k = 40, 172, 16, 6
    feats = jnp.asarray(rng.normal(size=(n, d)), dtype)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.random((b, k)), dtype)

    def loss(f, ww, use_kernel):
        out = neighbor_agg(f, idx, ww, use_kernel=use_kernel,
                           kernel="tiled")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1))(feats, w, False)
    g_ker = jax.grad(loss, argnums=(0, 1))(feats, w, True)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    for a, b_ in zip(g_ref, g_ker):
        assert a.shape == b_.shape and a.dtype == b_.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "self"])
@pytest.mark.parametrize("n", [37, 64], ids=["odd_n", "even_n"])
def test_paired_half_tile_rows_match_oracle(n, fused, rng):
    """A 128-column bf16 row is 64 words, half a lane tile: the kernel
    pairs rows 2m and 2m+1 in one word row and picks the half by the
    id's parity (an odd row count pads one row).  The f32 accumulation
    over the same bf16 values must round to the oracle's bf16 result."""
    d, b, k = 128, 24, 7
    feats = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
    idx = jnp.asarray(rng.integers(0, n, (b, k)), jnp.int32)
    w = jnp.asarray(rng.random((b, k)) * (rng.random((b, k)) > 0.3),
                    jnp.bfloat16)
    sr = jnp.asarray(rng.normal(size=(b, d)), jnp.bfloat16)
    ws = jnp.asarray(rng.random(b), jnp.bfloat16)
    extra = (sr, ws) if fused else ()
    ker = neighbor_agg(feats, idx, w, *extra, use_kernel=True,
                       kernel="tiled", d_tile=128)
    f32 = jnp.float32
    ref = neighbor_agg_ref(feats.astype(f32), idx, w.astype(f32))
    if fused:
        ref = ref + ws.astype(f32)[:, None] * sr.astype(f32)
    assert ker.shape == (b, d) and ker.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref.astype(jnp.bfloat16),
                                          np.float32),
                               rtol=2.0 ** -7, atol=1e-6)
