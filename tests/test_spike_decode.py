"""Decoded sparse datapath (kernels/spike_decode.py, DESIGN.md §9).

Pins, in order of the pipeline:
  * the cumsum prefix-compaction is *the* M-lane carry-lookahead decoder
    (Eq. 5): chunking the compacted index stream by M reproduces
    ``core.sparsity.multilane_decode_full``'s per-cycle lane sets
    exactly, for every lane count at once;
  * the pow2 occupancy-bucket schedule matches its numpy twin in
    ``sim.balance_sim`` bit-for-bit, and sorting provably never does
    worse than unsorted row order (the load-balancing claim);
  * decoded-mode outputs are bitwise equal to the dense reference and
    the tile kernel across shapes x sparsities x bias x int8 weights,
    including all-zero rows, ragged per-row occupancy, and the
    binary-attention integer-count lanes;
  * gradients flow through the shared custom VJP identically to dense;
  * whole-model logits are bitwise equal across dense/tile/decoded on
    both spikingformer configs;
  * ``sparse='auto'`` picks tile at coherent and at fine-grained/ragged
    sparsity (every live decoded row group costs a full-K dot per
    chunk), decoded only where the occupancy sort leaves whole row
    groups dark, and tile under jit (traced spikes).

Bit-exactness strategy matches tests/test_engine.py: dyadic-grid weights
make fp32 accumulation order-exact, so equality is to the bit, not a
tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container ships the fixed-seed shim
    from _propcheck import given, settings, strategies as st

from repro.core import engine as E
from repro.core import sparsity
from repro.kernels import spike_decode as SD
from repro.kernels.spike_matmul import spike_matmul
from repro.sim import balance_sim

DEC32 = E.EngineConfig(mode="sparse", sparse="decoded",
                       block_m=32, block_n=32, block_k=32)
TILE32 = DEC32.replace(sparse="tile")


def _spikes(key, shape, density):
    return (jax.random.uniform(key, shape) < density).astype(jnp.float32)


def _ragged_spikes(key, m, k, lo=0.0, hi=0.6):
    """Per-row density uniform in [lo, hi] — ragged occupancy, and lo=0
    guarantees (near-)empty rows ride along."""
    k1, k2 = jax.random.split(key)
    dens = jax.random.uniform(k1, (m, 1), minval=lo, maxval=hi)
    return (jax.random.uniform(k2, (m, k)) < dens).astype(jnp.float32)


def _dyadic(key, shape):
    return (jax.random.randint(key, shape, -128, 128)
            .astype(jnp.float32)) * (2.0 ** -8)


# ---------------------------------------------------------------------------
# decode == the Eq. 5 multi-lane decoder
# ---------------------------------------------------------------------------


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 64), st.integers(1, 8), st.integers(0, 10 ** 6))
def test_decode_indices_equals_multilane_decoder(n, m_lanes, seed):
    """The compacted index stream, chunked by the lane count, IS the
    carry-lookahead decoder's per-cycle output — for any M."""
    rng = np.random.default_rng(seed)
    bits = rng.random(n) < rng.random()  # random density incl. empty
    idx, occ = SD.decode_indices(jnp.asarray(bits[None], jnp.float32))
    idx, occ = np.asarray(idx[0]), int(occ[0])
    cycles, n_cycles = sparsity.multilane_decode_full(bits, m_lanes)
    assert n_cycles == sparsity.decode_cycles_for_word(occ, m_lanes)
    flat = np.concatenate(cycles) if occ else np.array([], np.int64)
    np.testing.assert_array_equal(idx[:occ], flat)
    for c, cyc in enumerate(cycles):  # per-cycle lane sets, in order
        np.testing.assert_array_equal(
            idx[c * m_lanes: c * m_lanes + len(cyc)], cyc)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 96), st.integers(0, 10 ** 6))
def test_decode_indices_matches_numpy_prefix_compact(n, seed):
    rng = np.random.default_rng(seed)
    bits = rng.random(n) < 0.4
    idx, occ = SD.decode_indices(jnp.asarray(bits[None], jnp.float32))
    ref_idx, ref_pc = sparsity.prefix_compact(bits)
    assert int(occ[0]) == ref_pc
    np.testing.assert_array_equal(np.asarray(idx[0])[:ref_pc], ref_idx)


def test_decode_cap_guards_concrete_truncation():
    s = jnp.ones((4, 16), jnp.float32)
    with pytest.raises(ValueError, match="max row occupancy"):
        SD.decode_indices(s, cap=8)
    idx, occ = SD.decode_indices(s, cap=16)  # exact cap is fine
    np.testing.assert_array_equal(np.asarray(occ), np.full(4, 16))


# ---------------------------------------------------------------------------
# bucket schedule: numpy twin + load-balancing property
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 200), st.sampled_from([8, 16, 32]),
       st.sampled_from([8, 32, 128]), st.integers(1, 300),
       st.integers(0, 10 ** 6))
def test_schedule_matches_balance_sim_twin(m, block_m, c_block, k, seed):
    rng = np.random.default_rng(seed)
    occ = rng.integers(0, k + 1, size=m)
    ref = balance_sim.bucket_schedule(occ, block_m, c_block, cap=k)
    pad = (-m) % block_m
    occ_j = jnp.asarray(np.concatenate([occ, np.zeros(pad, np.int64)]),
                        jnp.int32)
    got = SD.build_schedule(occ_j, block_m, c_block, cap=k)
    assert ref["executed"] == int(got["executed"])
    assert ref["total"] == int(got["total"])
    assert ref["padded_cap"] == got["padded_cap"]
    np.testing.assert_array_equal(ref["caps"], np.asarray(got["caps"]))


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 12), st.integers(0, 10 ** 6))
def test_occupancy_binning_never_loses_to_unsorted(n_groups, seed):
    """The load-balancing claim: binning rows by occupancy (sort) makes
    each group's pow2 capacity tight, so total executed steps are <= any
    unsorted grouping's — no group waits on a stray dense row."""
    rng = np.random.default_rng(seed)
    block_m, c_block, k = 16, 16, 128
    occ = rng.integers(0, k + 1, size=n_groups * block_m)
    sorted_sched = balance_sim.bucket_schedule(occ, block_m, c_block,
                                               cap=k)
    caps_unsorted = np.minimum(balance_sim._pow2ceil(
        occ.reshape(n_groups, block_m).max(axis=1)),
        sorted_sched["padded_cap"])
    unsorted_steps = int((-(-caps_unsorted // c_block)).sum())
    assert sorted_sched["executed"] <= unsorted_steps
    assert sorted_sched["executed"] <= sorted_sched["total"]
    assert sum(sorted_sched["buckets"].values()) == n_groups
    assert all(c == 0 or c == 1 << (c.bit_length() - 1)
               for c in sorted_sched["buckets"])  # pow2 buckets only


def test_predicted_schedule_tracks_measured():
    """The sim's Binomial density model predicts the measured tensor
    schedule to within a step or two (same distribution, different
    draws) — the bench cross-validation in miniature."""
    key = jax.random.PRNGKey(7)
    m, k, d = 256, 128, 0.1
    s = _spikes(key, (m, k), d)
    occ = (s != 0).sum(-1).astype(jnp.int32)
    meas = SD.build_schedule(occ, 32, 32, cap=k)
    pred = balance_sim.predicted_schedule(m, k, d, 32, 32,
                                          np.random.default_rng(0))
    assert pred["total"] == int(meas["total"])
    ratio = pred["executed"] / max(1, int(meas["executed"]))
    assert 0.5 <= ratio <= 2.0


# ---------------------------------------------------------------------------
# decoded == dense == tile, bitwise
# ---------------------------------------------------------------------------

SHAPES = [((2, 2, 32, 64), 48),     # (T, B, L, K), N
          ((4, 1, 48, 96), 80),     # nothing divides 32 evenly
          ((2, 3, 64, 128), 128)]
SPARSITIES = [0.5, 0.8, 0.95]


@pytest.mark.parametrize("lead_k,n", SHAPES)
@pytest.mark.parametrize("sparsity", SPARSITIES)
@pytest.mark.parametrize("bias", [False, True])
def test_decoded_bit_identical_to_dense_and_tile(lead_k, n, sparsity,
                                                 bias):
    ks = jax.random.split(jax.random.PRNGKey(int(sparsity * 100) + n), 3)
    s = _spikes(ks[0], lead_k, 1.0 - sparsity)
    p = {"w": _dyadic(ks[1], (lead_k[-1], n))}
    if bias:
        p["b"] = _dyadic(ks[2], (n,))
    dense = E.spike_linear(p, s, engine=E.DENSE)
    tile = E.spike_linear(p, s, engine=TILE32)
    dec = E.spike_linear(p, s, engine=DEC32)
    assert dec.shape == (*lead_k[:-1], n)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(dec))
    np.testing.assert_array_equal(np.asarray(tile), np.asarray(dec))


@pytest.mark.parametrize("bias", [False, True])
def test_decoded_ragged_and_all_zero_rows(bias):
    """Ragged per-row occupancy (the decoded path's home regime) incl.
    fully dark rows and a fully dense row."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    s = _ragged_spikes(ks[0], 96, 160, lo=0.0, hi=0.7)
    s = s.at[5].set(0.0).at[17].set(0.0)        # guaranteed empty rows
    s = s.at[40].set(1.0)                       # one fully dense row
    p = {"w": _dyadic(ks[1], (160, 64))}
    if bias:
        p["b"] = _dyadic(ks[2], (64,))
    dense = E.spike_linear(p, s, engine=E.DENSE)
    dec = E.spike_linear(p, s, engine=DEC32)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(dec))
    # empty rows produce exactly bias (or zero)
    want = np.asarray(p["b"]) if bias else np.zeros(64, np.float32)
    np.testing.assert_array_equal(np.asarray(dec[5]), want)


def test_decoded_all_zero_input():
    s = jnp.zeros((64, 96), jnp.float32)
    w = _dyadic(jax.random.PRNGKey(0), (96, 32))
    dec = E.spike_linear({"w": w}, s, engine=DEC32)
    np.testing.assert_array_equal(np.asarray(dec), np.zeros((64, 32)))
    occ = (s != 0).sum(-1).astype(jnp.int32)
    sched = SD.build_schedule(occ, 32, 32, cap=96)
    assert int(sched["executed"]) == 0  # every grid step skipped


def test_gather_matmul_equals_tile_kernel_on_arbitrary_weights():
    """With c_block = K every live entry decodes into chunk 0, so the
    decoded kernel's one executed chunk is the tile kernel's block_k = K
    dot on the same rows (sorted by occupancy, which leaves each row's
    own sum alone): the two agree on arbitrary normal weights, not only
    on order-exact dyadic ones."""
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    s = _ragged_spikes(ks[0], 80, 128, lo=0.0, hi=0.4)
    w = jax.random.normal(ks[1], (128, 48), jnp.float32)
    tile = spike_matmul(s, w, block_m=16, block_n=16, block_k=128,
                        out_dtype=jnp.float32)
    dec = SD.gather_spike_matmul(s, w, block_m=16, block_n=16,
                                 c_block=128)
    np.testing.assert_array_equal(np.asarray(tile), np.asarray(dec))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 70), st.integers(1, 90), st.integers(1, 50),
       st.sampled_from([8, 16, 32]), st.integers(0, 10 ** 6))
def test_gather_matmul_random_shapes_and_blocks(m, k, n, block, seed):
    """Shape-robustness sweep: nothing needs to divide anything."""
    ks = jax.random.split(jax.random.PRNGKey(seed % (1 << 30)), 2)
    s = _ragged_spikes(ks[0], m, k, lo=0.0, hi=0.8)
    w = _dyadic(ks[1], (k, n))
    dense = jnp.dot(s, w, preferred_element_type=jnp.float32)
    dec = SD.gather_spike_matmul(s, w, block_m=block, block_n=block,
                                 c_block=block)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(dec))


# ---------------------------------------------------------------------------
# quantized decoded path (int8 codes, int32 accumulation, counts lanes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("sparsity", [0.5, 0.95])
def test_quant_decoded_bitwise_vs_quant_references(bias, sparsity):
    """int8 decoded == int8 tile == the int-exact dense reference, on
    dyadic scales (DESIGN.md §8 exactness argument, decoded flavor)."""
    from repro.quant.quantize import quantize_weight
    ks = jax.random.split(jax.random.PRNGKey(int(sparsity * 10)), 3)
    s = _spikes(ks[0], (3, 40, 96), 1.0 - sparsity)
    w = jax.random.normal(ks[1], (96, 64), jnp.float32)
    p = quantize_weight(w, "int8", dyadic=True)
    if bias:
        p["b"] = _dyadic(ks[2], (64,))
    dense = E.spike_linear(p, s, engine=E.DENSE)
    tile = E.spike_linear(p, s, engine=TILE32)
    dec = E.spike_linear(p, s, engine=DEC32)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(dec))
    np.testing.assert_array_equal(np.asarray(tile), np.asarray(dec))


def test_quant_decoded_counts_ride_int32_lanes():
    """Binary-attention counts reach 128+ — the decoded quant kernel
    must carry them on int32 lanes like the tile kernel does (an int8
    cast would wrap); pinned against the int-exact dense reference."""
    from repro.quant.quantize import quantize_weight
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    mask = (jax.random.uniform(ks[0], (48, 96)) < 0.1)
    counts = jnp.where(mask, 200.0, 0.0)  # > 127: wraps in int8
    w = jax.random.normal(ks[1], (96, 32), jnp.float32)
    p = quantize_weight(w, "int8", dyadic=True)
    dense = E.dense_quant_linear(p, counts)
    dec = E.spike_linear(p, counts, engine=DEC32, counts=True)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(dec))


# ---------------------------------------------------------------------------
# gradients through the shared custom VJP
# ---------------------------------------------------------------------------


def test_decoded_gradients_match_dense():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    s = _ragged_spikes(ks[0], 64, 64, lo=0.0, hi=0.5).reshape(2, 2, 16, 64)
    w = _dyadic(ks[1], (64, 48))
    b = _dyadic(ks[2], (48,))

    def grads(engine):
        def f(s, w, b):
            y = E.spike_linear({"w": w, "b": b}, s, engine=engine)
            return (y * y).sum()
        return jax.grad(f, argnums=(0, 1, 2))(s, w, b)

    for gd, gs in zip(grads(E.DENSE), grads(DEC32)):
        np.testing.assert_allclose(np.asarray(gd), np.asarray(gs),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# dispatch: sparse=auto crossover + jit fallback
# ---------------------------------------------------------------------------


def _scattered_dark_rows(m, k):
    """Three rows in four all-zero, interleaved with rows holding one
    spike per 32 columns: every 32x32 tile is live, yet the occupancy
    sort fills whole 32-row groups with dark rows."""
    live = (jnp.arange(m)[:, None] % 4 == 0) & (jnp.arange(k)[None] % 32
                                                == 0)
    return live.astype(jnp.float32)


def test_decoded_dot_fraction_counts_full_k_dots():
    """Each executed chunk is a full-K dot: a live group costs at least
    one dense sweep, only sorted-together dark groups cost none."""
    def frac(s):
        occ = (s != 0).sum(-1).astype(jnp.int32)
        return SD.decoded_dot_fraction(SD.build_schedule(occ, 32, 32, 160))
    ragged = _ragged_spikes(jax.random.PRNGKey(0), 96, 160, lo=0.0, hi=0.2)
    assert frac(ragged) >= 1.0
    assert frac(jnp.ones((96, 160))) == 5.0          # all 5 chunks, 3 groups
    assert frac(_scattered_dark_rows(96, 160)) == 1 / 3   # 2 of 3 dark


def test_resolve_sparse_path_modes():
    auto = E.EngineConfig(mode="sparse", sparse="auto", block_m=32,
                          block_n=32, block_k=32)
    coherent = jnp.zeros((96, 160)).at[:, :32].set(1.0)  # dark tiles
    ragged = _ragged_spikes(jax.random.PRNGKey(0), 96, 160,
                            lo=0.0, hi=0.2)
    assert E.resolve_sparse_path(None, ragged) == "tile"
    assert E.resolve_sparse_path(TILE32, ragged) == "tile"
    assert E.resolve_sparse_path(DEC32, coherent) == "decoded"
    assert E.resolve_sparse_path(auto, coherent) == "tile"
    # no tile goes dark, but every live group costs a full-K dot per chunk
    assert E.resolve_sparse_path(auto, ragged) == "tile"
    assert E.resolve_sparse_path(auto, _scattered_dark_rows(96, 160)) == \
        "decoded"

    seen = []

    @jax.jit
    def f(s):
        seen.append(E.resolve_sparse_path(auto, s))
        return s

    f(ragged)
    assert seen == ["tile"]  # traced spikes: static fallback


def test_sparse_auto_engine_end_to_end_bitwise():
    """auto dispatch through spike_linear is still bitwise vs dense on
    every regime (whichever datapath it picks)."""
    auto = E.EngineConfig(mode="sparse", sparse="auto", block_m=32,
                          block_n=32, block_k=32)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    w = _dyadic(ks[2], (160, 64))
    for s in (_ragged_spikes(ks[0], 96, 160, lo=0.0, hi=0.2),
              jnp.zeros((96, 160)).at[:, :32].set(1.0),
              _scattered_dark_rows(96, 160)):
        dense = E.spike_linear({"w": w}, s, engine=E.DENSE)
        got = E.spike_linear({"w": w}, s, engine=auto)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(got))


def test_engine_config_validates_sparse_field():
    with pytest.raises(ValueError, match="sparse datapath"):
        E.EngineConfig(sparse="rowwise")


# ---------------------------------------------------------------------------
# whole model: both spikingformer configs, dense == tile == decoded
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["spikingformer-4-256",
                                  "spikingformer-8-512"])
def test_spikingformer_logits_bitwise_across_sparse_paths(arch):
    from repro.configs import get_config
    from repro.models import registry

    cfg = get_config(arch, smoke=True)
    params = registry.init(cfg, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda a: jnp.round(a * 256) / 256 if a.dtype == jnp.float32 else a,
        params)
    sz = cfg.vision.img_size
    batch = {"images": jax.random.normal(jax.random.PRNGKey(1),
                                         (2, sz, sz, 3)),
             "labels": jnp.zeros((2,), jnp.int32)}
    outs = {}
    for name, eng in [("dense", E.DENSE), ("tile", TILE32),
                      ("decoded", DEC32)]:
        with E.use_engine(eng):
            outs[name], _ = registry.forward(params, cfg, batch)
    np.testing.assert_array_equal(np.asarray(outs["dense"]),
                                  np.asarray(outs["decoded"]))
    np.testing.assert_array_equal(np.asarray(outs["tile"]),
                                  np.asarray(outs["decoded"]))
