"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode
on CPU; the same pallas_call compiles to Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitpack import pack_bits
from repro.kernels import ops, ref
from repro.kernels.spike_attention import spike_attention as attn_raw
from repro.kernels.spike_matmul import spike_matmul as matmul_raw
from repro.core.spiking import (SpikingConfig, lif_lax_scan, lif_loop_reference,
                                lif_scan)


def _spikes(key, shape, p=0.25, dtype=jnp.float32):
    return (jax.random.uniform(key, shape) < p).astype(dtype)


@pytest.mark.parametrize("l,d,blk", [(64, 32, 32), (128, 64, 64),
                                     (256, 128, 128), (96, 48, 32)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spike_attention_sweep(l, d, blk, causal, dtype):
    if l % blk:
        pytest.skip("block must divide L")
    ks = jax.random.split(jax.random.PRNGKey(l + d), 3)
    q, k, v = (_spikes(kk, (4, l, d), dtype=dtype) for kk in ks)
    out = attn_raw(q, k, v, scale=1 / np.sqrt(d), delta=0.3, causal=causal,
                   block_q=blk, block_k=blk)
    want = ref.spike_attention_ref(q.reshape(4, 1, l, d),
                                   k.reshape(4, 1, l, d),
                                   v.reshape(4, 1, l, d),
                                   scale=1 / np.sqrt(d), delta=0.3,
                                   causal=causal).reshape(4, l, d)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


def test_spike_attention_no_binarize_matches_raw_scores_times_v():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (_spikes(kk, (2, 64, 32)) for kk in ks)
    out = attn_raw(q, k, v, scale=0.5, delta=0.0, causal=False,
                   binarize_scores=False, block_q=32, block_k=32)
    want = ref.spike_attention_ref(q[:, None], k[:, None], v[:, None],
                                   scale=0.5, delta=0.0, causal=False,
                                   binarize_scores=False)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


def test_spike_attention_ops_layout_and_grads():
    b, l, h, d = 2, 64, 3, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (_spikes(kk, (b, l, h, d)) for kk in ks)
    out = ops.spike_attention(q, k, v, scale=1 / np.sqrt(d), delta=0.2,
                              causal=True)
    want = ref.spike_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), scale=1 / np.sqrt(d), delta=0.2,
        causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)
    g = jax.grad(lambda q: ops.spike_attention(
        q, k, v, scale=1 / np.sqrt(d), delta=0.2, causal=True).sum())(q)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 256, 192, 32, 64, 32), (64, 64, 64, 64, 64, 64),
    (256, 128, 128, 128, 128, 128), (96, 160, 64, 32, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spike_matmul_sweep(m, k, n, bm, bn, bk, dtype):
    key1, key2 = jax.random.split(jax.random.PRNGKey(m + n))
    s = _spikes(key1, (m, k))
    w = jax.random.normal(key2, (k, n), dtype)
    got = matmul_raw(s, w, block_m=bm, block_n=bn, block_k=bk)
    want = ref.spike_matmul_ref(s, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_spike_matmul_skips_zero_blocks_correctly():
    s = _spikes(jax.random.PRNGKey(0), (128, 256))
    s = s.at[:, 64:192].set(0.0)  # two zero K-stripes
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 64))
    got = ops.spike_matmul(s, w, block_m=64, block_n=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.spike_matmul_ref(s, w)),
                               rtol=1e-5, atol=1e-5)
    from repro.kernels.spike_matmul import block_occupancy
    occ = block_occupancy(s, 64, 64)
    assert not occ[:, 1].any() and not occ[:, 2].any()


def _bits(a):
    return np.asarray(jnp.asarray(a, jnp.float32)).view(np.int32)


# (T, ..., R, D), with the block budget a case forces (None: the kernel's)
# and the (P, R, D) view that budget cuts raggedly: a conv output with a
# minor dim of 64 (T-major view) and one of 128 (the (H*W, T, B, C)
# view); the cell's block layout; R off the (8, 128) tile; a 2-D input;
# a size-1 dim (a decode step's); a ragged last block along D, P (in
# either view) and R
LIF_CASES = [((4, 2, 6, 24, 64), None, None),
             ((4, 2, 14, 14, 128), None, None),
             ((4, 2, 196, 512), None, None), ((2, 5, 10, 100), None, None),
             ((8, 300), None, None), ((4, 6, 1, 256), None, None),
             ((2, 3, 8, 700), None, (3, 8, 700)),
             ((4, 5, 16, 64), 64 << 10, (5, 16, 64)),
             ((4, 2, 14, 14, 128), 48 << 10, (196, 2, 128)),
             ((4, 2, 100, 128), 16 << 10, (2, 100, 128))]


@pytest.mark.parametrize("shape,block_bytes,view", LIF_CASES)
@pytest.mark.parametrize("soft", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lif_kernel_sweep(shape, block_bytes, view, soft, dtype,
                          monkeypatch):
    """One pass over T: spikes (the kernel's) and final membrane bitwise
    those of the op-by-op loop, in the precision the scan keeps."""
    from repro.kernels import lif as K
    if block_bytes:
        monkeypatch.setattr(K, "BLOCK_BYTES", block_bytes)
    if view:
        blocks = K._blocks(shape[0], *view, jnp.dtype(dtype).itemsize)
        assert any(n % b for n, b in zip(view, blocks)), blocks
    cfg = SpikingConfig(time_steps=shape[0], soft_reset=soft)
    x = (jax.random.normal(jax.random.PRNGKey(sum(shape)), shape) * 1.5
         ).astype(dtype)
    s, u = ops.lif_one_pass(x, cfg)
    s_ref, u_ref = lif_loop_reference(x, cfg)
    assert s.dtype == s_ref.dtype == dtype and u.dtype == dtype
    assert s.shape == x.shape and u.shape == x.shape[1:]
    np.testing.assert_array_equal(_bits(s), _bits(s_ref))
    np.testing.assert_array_equal(_bits(u), _bits(u_ref))
    assert 0.05 < float(s.astype(jnp.float32).mean()) < 0.5


@pytest.mark.parametrize("soft", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lif_one_pass_gradient_is_scan_surrogate(soft, dtype):
    """Under differentiation ``ops.lif_one_pass`` is the scan: its
    gradient is the scan's surrogate gradient bit for bit, whatever the
    cotangents, and the differentiated program holds no kernel."""
    cfg = SpikingConfig(soft_reset=soft)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = (jax.random.normal(ks[0], (4, 2, 8, 128)) * 1.5).astype(dtype)
    ws = jax.random.normal(ks[1], x.shape)
    wu = jax.random.normal(ks[2], x.shape[1:])

    def grad(f):
        def g(x):
            s, u = f(x, cfg)
            return (s * ws).sum() + (u * wu).sum()
        return jax.grad(g)

    got, want = grad(ops.lif_one_pass)(x), grad(lif_lax_scan)(x)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert float(jnp.abs(got.astype(jnp.float32)).sum()) > 0
    assert "pallas_call" not in str(jax.make_jaxpr(
        grad(ops.lif_one_pass))(x))


def test_lif_ops_wrapper_arbitrary_dims():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 8, 64))
    cfg = SpikingConfig()
    got = ops.lif_one_pass(x, cfg)
    want = lif_loop_reference(x, cfg)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


@pytest.mark.parametrize("shape,backend,v0,one_pass", [
    ((4, 64, 224, 224, 64), "tpu", False, True),    # the cell's stem
    ((4, 64, 196, 512), "tpu", False, True),        # the cell's blocks
    ((4, 1, 1, 256), "tpu", False, True),           # decode, one slot
    ((4, 64, 196, 512), "tpu", True, False),        # a given membrane
    ((4, 64, 196, 512), "cpu", False, False),       # off TPU: the scan
    ((4, 4, 128, 256), "mesh", False, False),       # a mesh partitions it
])
def test_lif_one_pass_rule(shape, backend, v0, one_pass, monkeypatch):
    """On the chip every LIF from a zero membrane takes one pass, whatever
    its size; a given membrane, every input off TPU, and programs a mesh
    partitions (which cannot hold a Mosaic kernel), the scan."""
    from repro.parallel.sharding import use_rules
    rules = {"batch": "data"} if backend == "mesh" else None
    monkeypatch.setattr(jax, "default_backend",
                        lambda: "tpu" if backend == "mesh" else backend)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    u = jax.ShapeDtypeStruct(shape[1:], jnp.bfloat16) if v0 else None
    with use_rules(rules):
        assert ops._one_pass(u) is one_pass
        jaxpr = jax.make_jaxpr(
            lambda c, u: lif_scan(c, SpikingConfig(), u))(x, u)
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    assert ("custom_vjp_call" in prims) is one_pass
    assert ("scan" in prims) is not one_pass
    assert ("pallas_call" in str(jaxpr)) is one_pass


@pytest.mark.parametrize("l,d", [(64, 64), (128, 128), (64, 256)])
def test_popcount_scores_sweep(l, d):
    ks = jax.random.split(jax.random.PRNGKey(l), 2)
    q = _spikes(ks[0], (3, l, d))
    k = _spikes(ks[1], (3, l, d))
    got = ops.popcount_attention_scores(q, k)
    exact = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exact))
    want = ref.popcount_scores_ref(pack_bits(q), pack_bits(k))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
