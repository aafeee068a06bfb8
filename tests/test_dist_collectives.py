"""Distribution-plane unit tests: sync strategies, relay ring, filter math.

Uses 8 forced host devices, mesh (2, 2, 2) = (pod, data, model).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import (
    SyncConfig,
    _topk_mask,
    chunked_topk_exchange,
    estimate_sync_bytes,
    relay_psum,
    sync_gradients,
)
from repro.launch.mesh import make_small_mesh


@pytest.fixture(scope="module")
def mesh():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    return make_small_mesh()


def _podmap(mesh, fn, n_in=1):
    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh,
            in_specs=tuple([P()] * n_in), out_specs=P(),
            axis_names={"pod"}, check_vma=False,
        )
    )


def test_relay_psum_matches_psum(mesh):
    x = jnp.arange(8.0)

    def body(x):
        per_pod = x + jax.lax.axis_index("pod").astype(jnp.float32)
        a = jax.lax.psum(per_pod, "pod")
        b = relay_psum(per_pod, "pod", order=(1, 0))
        return jnp.stack([a, b])

    out = _podmap(mesh, body)(x)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out[1]), rtol=1e-6)


def test_chunked_topk_exchange_mean_semantics(mesh):
    """With density=1.0 the exchange equals a plain pmean; residual zero."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)

    def body(g):
        local = g * (1.0 + jax.lax.axis_index("pod").astype(jnp.float32))
        dense = jax.lax.pmean(local, "pod")
        out, res = chunked_topk_exchange(
            local, jnp.zeros_like(local), axis="pod", density=1.0, chunk=64
        )
        return dense, out, res

    dense, out, res = _podmap(mesh, body)(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=1e-5)
    assert float(jnp.abs(res).max()) == 0.0


def test_chunked_topk_error_feedback_conserves(mesh):
    """sent + residual' == grad + residual per pod (mass conservation)."""
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)

    def body(g, r):
        local = g * (1.0 + jax.lax.axis_index("pod").astype(jnp.float32))
        out, new_r = chunked_topk_exchange(
            local, r, axis="pod", density=0.25, chunk=32
        )
        # reconstruct this pod's sent values: (acc - new_r)
        sent = (local + r) - new_r
        # out is mean over pods of all sent: check via psum
        mean_sent = jax.lax.pmean(sent, "pod")
        return out, mean_sent, new_r, local + r

    out, mean_sent, new_r, acc = _podmap(mesh, lambda g, r: body(g, r), 2)(g, r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(mean_sent), rtol=1e-5)
    # conservation on pod 0's view: sent + residual == acc
    np.testing.assert_allclose(
        np.asarray(mean_sent * 0 + (acc - new_r) + new_r), np.asarray(acc), rtol=1e-6
    )


def _scatter_mask(m, k):
    """``top_k``'s indices scattered as 1.0 into zeros: the oracle for
    :func:`_topk_mask`."""
    rows, chunk = m.shape
    if k >= chunk:
        return jnp.ones_like(m)
    _, idx = jax.lax.top_k(jnp.abs(m), k)
    row_ids = jnp.repeat(jnp.arange(rows), k)
    return jnp.zeros_like(m).at[row_ids, idx.ravel()].set(1.0)


def _mask_rows(case, chunk, k):
    """Rows of ``chunk`` f32 for one named case of the mask test."""
    rng = np.random.default_rng(chunk * 1000 + k)
    normal = rng.normal(size=(4, chunk)).astype(np.float32)
    if case == "zeros":
        return np.zeros((3, chunk), np.float32)
    if case == "neg_zeros":
        return np.full((3, chunk), -0.0, np.float32)
    if case == "signed_zeros":
        return np.where(rng.random((3, chunk)) < 0.5, 0.0, -0.0).astype(np.float32)
    if case == "few_ints":
        # few distinct magnitudes of either sign: ties straddle the k-th place
        return rng.integers(-2, 3, size=(16, chunk)).astype(np.float32)
    if case == "one_nan":
        normal[:, chunk // 3] = np.nan
        return normal
    if case == "nans_over_k":
        cols = rng.permutation(chunk)[: min(k + 3, chunk)]
        normal[:, cols] = np.nan
        return normal
    if case == "pos_inf":
        return np.full((2, chunk), np.inf, np.float32)
    if case == "infs_and_ints":
        x = rng.integers(-3, 4, size=(8, chunk)).astype(np.float32)
        x[x == 3] = np.inf
        x[x == -3] = -np.inf
        return x
    if case == "padded_tail":
        # the exchange's last row: a leaf's tail, then zeros to the chunk
        n = 3 * chunk + chunk // 3
        flat = np.concatenate([rng.integers(-4, 5, size=n), np.zeros(4 * chunk - n)])
        return flat.astype(np.float32).reshape(4, chunk)
    raise ValueError(case)


_MASK_CASES = ["zeros", "neg_zeros", "signed_zeros", "few_ints", "one_nan",
               "nans_over_k", "pos_inf", "infs_and_ints", "padded_tail"]


@pytest.mark.parametrize("case", _MASK_CASES)
@pytest.mark.parametrize("chunk,k", [(16, 1), (16, 7), (16, 15), (2048, 205)])
def test_topk_mask_matches_scatter_mask(case, chunk, k):
    """The compare against each row's k-th value picks exactly the entries
    ``top_k``'s indices name, tie for tie, NaN and inf included: k = 1,
    k = chunk - 1 and granite's 205 of 2048."""
    m = jnp.asarray(_mask_rows(case, chunk, k))
    got = np.asarray(jax.jit(_topk_mask, static_argnums=1)(m, k))
    want = np.asarray(jax.jit(_scatter_mask, static_argnums=1)(m, k))
    assert got.dtype == bool
    np.testing.assert_array_equal(got, want == 1.0)
    assert (got.sum(axis=1) == k).all()


def _scatter_exchange(grad, residual, *, axis, density, chunk, order):
    """``chunked_topk_exchange`` with the scatter mask multiplied in: the
    oracle for its bits."""
    acc = grad + residual
    n = acc.size
    flat = jnp.concatenate([acc.ravel(), jnp.zeros(((-n) % chunk,), jnp.float32)])
    m = flat.reshape(-1, chunk)
    sent = m * _scatter_mask(m, max(1, int(round(density * chunk))))
    new_res = m - sent
    if order is not None:
        out = relay_psum(sent, axis, order=order) / len(order)
    else:
        out = jax.lax.pmean(sent, axis)
    return (out.ravel()[:n].reshape(acc.shape),
            new_res.ravel()[:n].reshape(acc.shape))


@pytest.mark.parametrize("order", [None, (1, 0)])
@pytest.mark.parametrize("with_nonfinite", [False, True])
def test_chunked_topk_exchange_bit_equal_to_scatter_oracle(mesh, order,
                                                           with_nonfinite):
    """``(out, new_res)`` are the same to the bit as with the scatter mask,
    on ties, signed zeros, a padded tail row, and rows with more infs and
    NaNs than k."""
    rng = np.random.default_rng(4)
    g = rng.integers(-3, 4, size=(5, 40)).astype(np.float32) * 0.5
    g[g == 0] = np.where(rng.random((g == 0).sum()) < 0.5, 0.0, -0.0)
    r = rng.normal(size=(5, 40)).astype(np.float32)
    r[:, ::3] = -0.0
    if with_nonfinite:
        g[0, :12] = np.inf
        g[1, 3:20:2] = -np.inf
        g[2, 30:] = np.nan
    kw = dict(axis="pod", density=0.25, chunk=32, order=order)

    def body(g, r):
        local = g * (1.0 + jax.lax.axis_index("pod").astype(jnp.float32))
        return chunked_topk_exchange(local, r, **kw), _scatter_exchange(local, r, **kw)

    (out, new_r), (out_o, new_r_o) = _podmap(mesh, body, 2)(g, r)
    for a, b in ((out, out_o), (new_r, new_r_o)):
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32)
        )


def test_sync_gradients_strategies_agree_at_density_1(mesh):
    rng = np.random.default_rng(2)
    tree = {"a": jnp.asarray(rng.normal(size=(16, 16)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}

    def body(a, b):
        grads = {"a": a * (1.0 + jax.lax.axis_index("pod").astype(jnp.float32)),
                 "b": b}
        res = jax.tree.map(jnp.zeros_like, grads)
        hier, _ = sync_gradients(grads, None, SyncConfig(strategy="hier"),
                                 n_pods=2)
        geo, _ = sync_gradients(
            grads, res,
            SyncConfig(strategy="geococo", density=1.0, chunk=64,
                       min_leaf_size=8),
            n_pods=2,
        )
        return hier["a"], geo["a"], hier["b"], geo["b"]

    ha, ga, hb, gb = _podmap(mesh, body, 2)(tree["a"], tree["b"])
    np.testing.assert_allclose(np.asarray(ha), np.asarray(ga), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hb), np.asarray(gb), rtol=1e-5)


def test_sync_gradients_ring_order_matches_pmean(mesh):
    """A control-plane-fed ring_order routes the exchange through
    relay_psum; the result equals the stock pmean path (up to float
    reassociation) for both dense and filtered strategies."""
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)

    def body(g):
        local = g * (1.0 + jax.lax.axis_index("pod").astype(jnp.float32))
        grads = {"w": local}
        res = jax.tree.map(jnp.zeros_like, grads)
        h0, _ = sync_gradients(grads, None, SyncConfig(strategy="hier"),
                               n_pods=2)
        h1, _ = sync_gradients(
            grads, None, SyncConfig(strategy="hier", ring_order=(1, 0)),
            n_pods=2,
        )
        geo_cfg = SyncConfig(strategy="geococo", density=0.25, chunk=32,
                             min_leaf_size=8)
        g0, r0 = sync_gradients(grads, res, geo_cfg, n_pods=2)
        g1, r1 = sync_gradients(
            grads, res,
            SyncConfig(strategy="geococo", density=0.25, chunk=32,
                       min_leaf_size=8, ring_order=(1, 0)),
            n_pods=2,
        )
        return h0["w"], h1["w"], g0["w"], g1["w"], r0["w"], r1["w"]

    h0, h1, g0, g1, r0, r1 = _podmap(mesh, body)(g)
    np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r0), np.asarray(r1), rtol=1e-6)


def test_sync_config_ring_order_validation():
    assert SyncConfig(ring_order=(2, 0, 1)).ring_order == (2, 0, 1)
    with pytest.raises(ValueError, match="permutation"):
        SyncConfig(ring_order=(0, 2))
    with pytest.raises(ValueError, match="does not cover"):
        sync_gradients({"w": jnp.ones((4,))}, None,
                       SyncConfig(strategy="hier", ring_order=(0, 1, 2)),
                       n_pods=2)


def test_estimate_sync_bytes_ordering():
    n = 10_000_000
    flat = estimate_sync_bytes(n, SyncConfig(strategy="flat"), 2)
    geo = estimate_sync_bytes(n, SyncConfig(strategy="geococo", density=0.05), 2)
    assert geo < flat * 0.2


def test_single_pod_noop():
    g = {"w": jnp.ones((8, 8))}
    out, res = sync_gradients(g, None, SyncConfig(strategy="hier"), n_pods=1)
    assert out is g and res is None
