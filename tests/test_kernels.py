"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes and dtypes per the deliverable spec."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.crdt_merge import ops as crdt_ops
from repro.kernels.rglru_scan import ops as rglru_ops
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.kernels.whitedata_filter import ops as wd_ops


# ---------------------------------------------------------------------------
# whitedata_filter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(256, 256), (512, 384), (8, 128), (1024,),
                                   (3, 5, 7), (1000,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_whitedata_filter_matches_ref(shape, dtype):
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(0, 1, size=shape), dtype)
    r = jnp.asarray(rng.normal(0, 0.1, size=shape), dtype)
    tau = 0.5
    s_k, r_k, k_k = wd_ops.whitedata_filter(g, r, tau, use_kernel=True)
    s_r, r_r, k_r = wd_ops.whitedata_filter_ref(g, r, tau)
    np.testing.assert_allclose(np.asarray(s_k, np.float32),
                               np.asarray(s_r, np.float32), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(r_k, np.float32),
                               np.asarray(r_r, np.float32), rtol=1e-5, atol=1e-5)
    assert int(k_k) == int(k_r)


def test_whitedata_filter_conserves_mass():
    """send + new_r == g + r: filtering defers, never destroys."""
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(0, 1, size=(128, 256)), jnp.float32)
    r = jnp.asarray(rng.normal(0, 1, size=(128, 256)), jnp.float32)
    s, nr, _ = wd_ops.whitedata_filter(g, r, 0.7)
    np.testing.assert_allclose(np.asarray(s + nr), np.asarray(g + r), rtol=1e-6)


def test_whitedata_filter_tau_extremes():
    g = jnp.ones((64, 128))
    r = jnp.zeros((64, 128))
    s, nr, k = wd_ops.whitedata_filter(g, r, 0.0)
    assert int(k) == g.size and float(jnp.abs(nr).sum()) == 0.0
    s, nr, k = wd_ops.whitedata_filter(g, r, 1e9)
    assert int(k) == 0 and float(jnp.abs(s).sum()) == 0.0


def test_filter_gradient_pytree():
    rng = np.random.default_rng(2)
    grads = {
        "a": jnp.asarray(rng.normal(size=(32, 64)), jnp.float32),
        "b": {"w": jnp.asarray(rng.normal(size=(129,)), jnp.float32)},
    }
    res = jax.tree.map(jnp.zeros_like, grads)
    send, new_r, stats = wd_ops.filter_gradient(grads, res, 1.0)
    assert jax.tree.structure(send) == jax.tree.structure(grads)
    assert 0.0 <= float(stats["density"]) <= 1.0
    total = sum(g.size for g in jax.tree.leaves(grads))
    assert int(stats["total"]) == total


# ---------------------------------------------------------------------------
# crdt_merge
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n", [(256, 256), (128, 512), (64, 100), (7, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_crdt_merge_matches_ref(m, n, dtype):
    rng = np.random.default_rng(3)
    if dtype == jnp.int32:
        va = jnp.asarray(rng.integers(0, 100, size=(m, n)), dtype)
        vb = jnp.asarray(rng.integers(0, 100, size=(m, n)), dtype)
    else:
        va = jnp.asarray(rng.normal(size=(m, n)), dtype)
        vb = jnp.asarray(rng.normal(size=(m, n)), dtype)
    ra = jnp.asarray(rng.integers(0, 50, size=(m,)), jnp.int32)
    rb = jnp.asarray(rng.integers(0, 50, size=(m,)), jnp.int32)
    ov_k, or_k = crdt_ops.crdt_merge(va, ra, vb, rb, use_kernel=True)
    ov_r, or_r = crdt_ops.crdt_merge_ref(va, ra, vb, rb)
    np.testing.assert_array_equal(np.asarray(ov_k), np.asarray(ov_r))
    np.testing.assert_array_equal(np.asarray(or_k), np.asarray(or_r))


def test_crdt_merge_is_aci():
    """Kernel-level ACI: commutative on value-identical ties, associative,
    idempotent — the properties the paper's Sec 4.4 proof needs."""
    rng = np.random.default_rng(4)
    m, n = 64, 128
    batches = []
    for i in range(4):
        vals = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        vers = jnp.asarray(rng.integers(0, 20, size=(m,)), jnp.int32)
        batches.append((vals, vers))
    v1, r1 = crdt_ops.crdt_merge_many(batches)
    v2, r2 = crdt_ops.crdt_merge_many(batches[::-1])
    # versions agree in any order; values agree where versions were unique
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    vers = np.stack([np.asarray(b[1]) for b in batches])
    unique = (vers == vers.max(axis=0)).sum(axis=0) == 1
    np.testing.assert_array_equal(np.asarray(v1)[unique], np.asarray(v2)[unique])
    # idempotence: re-merging the result is a no-op
    v3, r3 = crdt_ops.crdt_merge(v1, r1, v1, r1)
    np.testing.assert_array_equal(np.asarray(v3), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(r3), np.asarray(r1))
    # duplicated delivery of one batch changes nothing
    v4, r4 = crdt_ops.crdt_merge_many(batches + [batches[0]])
    np.testing.assert_array_equal(np.asarray(r4), np.asarray(r1))


# ---------------------------------------------------------------------------
# rwkv6_wkv
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,t,h,n", [(2, 64, 2, 16), (1, 128, 4, 32),
                                     (2, 37, 1, 8), (1, 256, 2, 64)])
def test_wkv6_matches_ref(b, t, h, n):
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.normal(0, 1, size=(b, t, h, n)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, size=(b, t, h, n)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, size=(b, t, h, n)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 0.99, size=(b, t, h, n)), jnp.float32)
    u = jnp.asarray(rng.normal(0, 0.2, size=(h, n)), jnp.float32)
    s0 = jnp.asarray(rng.normal(0, 0.1, size=(b, h, n, n)), jnp.float32)
    y_k, s_k = wkv_ops.wkv6(r, k, v, w, u, s0, use_kernel=True)
    y_r, s_r = wkv_ops.wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=2e-5, atol=2e-5)


def test_wkv6_chunking_invariance():
    """Different time chunk sizes give identical results (state carried
    correctly across chunk boundaries)."""
    rng = np.random.default_rng(6)
    b, t, h, n = 1, 96, 2, 16
    args = [jnp.asarray(rng.normal(size=(b, t, h, n)), jnp.float32) for _ in range(3)]
    w = jnp.asarray(rng.uniform(0.6, 0.99, size=(b, t, h, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    s0 = jnp.zeros((b, h, n, n), jnp.float32)
    outs = []
    for tc in (96, 48, 32, 16):
        y, s = wkv_ops.wkv6(*args[:3], w, u, s0, time_chunk=tc)
        outs.append((np.asarray(y), np.asarray(s)))
    for y, s in outs[1:]:
        np.testing.assert_allclose(y, outs[0][0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(s, outs[0][1], rtol=1e-5, atol=1e-5)


def test_wkv6_state_continuation():
    """Processing [0:T1] then [T1:T] with carried state == one pass."""
    rng = np.random.default_rng(7)
    b, t, h, n = 2, 64, 2, 16
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, n)), jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.6, 0.99, size=(b, t, h, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    s0 = jnp.zeros((b, h, n, n), jnp.float32)
    y_full, s_full = wkv_ops.wkv6(r, k, v, w, u, s0)
    t1 = 24
    y1, s1 = wkv_ops.wkv6(r[:, :t1], k[:, :t1], v[:, :t1], w[:, :t1], u, s0)
    y2, s2 = wkv_ops.wkv6(r[:, t1:], k[:, t1:], v[:, t1:], w[:, t1:], u, s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), rtol=1e-5, atol=1e-5)


def test_wkv6_model_integration():
    """models.rwkv6 scan == kernel path."""
    from repro.models.rwkv6 import wkv6_scan

    rng = np.random.default_rng(8)
    b, t, h, n = 2, 32, 2, 16
    mk = lambda: jnp.asarray(rng.normal(size=(b, t, h, n)), jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.6, 0.99, size=(b, t, h, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    s0 = jnp.zeros((b, h, n, n), jnp.float32)
    y_m, s_m = wkv6_scan(r, k, v, w, u, s0)
    y_k, s_k = wkv_ops.wkv6(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y_m), np.asarray(y_k), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_m), np.asarray(s_k), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# rglru_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,t,d", [(2, 64, 128), (1, 100, 64), (3, 256, 512),
                                   (2, 37, 100)])
def test_rglru_matches_ref(b, t, d):
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.uniform(0.5, 0.999, size=(b, t, d)), jnp.float32)
    bb = jnp.asarray(rng.normal(0, 0.5, size=(b, t, d)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    h_k, f_k = rglru_ops.rglru_scan(a, bb, h0, use_kernel=True)
    h_r, f_r = rglru_ops.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r), rtol=1e-5, atol=1e-5)


def test_rglru_matches_associative_scan_in_model():
    """The model's associative-scan path == the kernel's sequential sweep."""
    from repro.models.rglru import _rglru_scan

    rng = np.random.default_rng(10)
    b, t, d = 2, 64, 32
    a = jnp.asarray(rng.uniform(0.5, 0.99, size=(b, t, d)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(b, d)), jnp.float32)
    h_m, last_m = _rglru_scan(a, bb, h0)
    h_k, last_k = rglru_ops.rglru_scan(a, bb, h0)
    np.testing.assert_allclose(np.asarray(h_m), np.asarray(h_k), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last_m), np.asarray(last_k), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# chunked WKV6 (the §Perf iteration-3 path) — property-swept vs the oracle
# ---------------------------------------------------------------------------


def test_wkv6_chunked_property_sweep():
    pytest.importorskip(
        "hypothesis", reason="dev-only dependency; see requirements-dev.txt"
    )
    import hypothesis.strategies as st
    from hypothesis import given, settings

    from repro.models.rwkv6 import wkv6_chunked, wkv6_scan

    @given(
        st.integers(1, 2), st.integers(2, 48), st.integers(1, 2),
        st.integers(4, 16), st.integers(0, 1000),
    )
    @settings(max_examples=25, deadline=None)
    def prop(b, t, h, n, seed):
        rng = np.random.default_rng(seed)
        r, k, v = (
            jnp.asarray(rng.normal(size=(b, t, h, n)), jnp.float32)
            for _ in range(3)
        )
        w = jnp.asarray(rng.uniform(0.4, 0.999, size=(b, t, h, n)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
        s0 = jnp.asarray(rng.normal(0, 0.2, size=(b, h, n, n)), jnp.float32)
        y1, s1 = wkv6_scan(r, k, v, w, u, s0)
        y2, s2 = wkv6_chunked(r, k, v, w, u, s0, chunk=16)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   rtol=5e-4, atol=5e-4)

    prop()


# ---------------------------------------------------------------------------
# interpret mode: only ever on the CPU backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,requested,expected", [
    ("cpu", None, True), ("cpu", False, False), ("cpu", True, True),
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, ValueError),
])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, requested, expected):
    from repro import kernels

    monkeypatch.setattr(kernels.jax, "default_backend", lambda: backend)
    if expected is ValueError:
        with pytest.raises(ValueError, match="interpret"):
            kernels.interpret_mode(requested)
    else:
        assert kernels.interpret_mode(requested) is expected
