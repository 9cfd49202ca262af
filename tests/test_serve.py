"""Serving orchestrator correctness (launch/serve.py).

Pins the continuous-batching contract: slot reuse is isolated (a request
admitted into a freed slot decodes from position 0 over an invalidated
cache — the tentpole bugfix), staggered admission is bitwise-equal to
running each request alone, chunked prefill matches whole-prompt prefill,
and retirement uses the full cache capacity. The mesh-sharded server is
exercised in a subprocess with a forced 8-device host platform.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import steps as steps_lib
from repro.launch.serve import BatchedServer, Request, choose_chunk
from repro.models import registry

ARCHS = ["h2o-danube-3-4b", "spikingformer-lm"]


def _params(cfg):
    return registry.init(cfg, jax.random.PRNGKey(0))


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def _serve(cfg, params, reqs, *, slots, max_len=32, chunk=0):
    server = BatchedServer(cfg, params, slots, max_len, chunk=chunk,
                           trace_logits=True)
    for r in reqs:
        server.submit(r)
    server.run()
    assert len(server.completed) == len(reqs)
    return {r.rid: r for r in server.completed}


def _req(rid, prompt, max_new):
    return Request(rid=rid, prompt=prompt, max_new_tokens=max_new)


def _assert_same_logits(cfg, a, b):
    """Logit rows of one request computed under different wave shapes.

    Spiking configs compare bitwise: their attention is binary and their
    projections see {0,1} spikes, so every sum is order-exact. The analog
    control (h2o-danube-3-4b: softmax attention over real-valued
    activations) reduces fp32 sums whose blocking XLA's CPU backend
    picks per operand shape, and the batch shape differs between the
    runs (slots x chunk width) — its rows agree to fp32 rounding
    (observed: at most 3e-6 absolute), not to the bit."""
    if cfg.spiking is not None:
        np.testing.assert_array_equal(a, b)
    else:
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# slot reuse isolation (the tentpole regression)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_slot_reuse_isolated_from_previous_occupant(arch):
    """slots=1: a short and a long request share the single slot back to
    back; each produces logits bitwise-equal to running alone."""
    cfg = get_config(arch, smoke=True)
    params = _params(cfg)
    mk = lambda: [_req(0, _prompt(cfg, 6, 1), 3),
                  _req(1, _prompt(cfg, 9, 2), 5)]
    shared = _serve(cfg, params, mk(), slots=1)
    for proto in mk():
        solo = _serve(cfg, params, [_req(proto.rid, proto.prompt,
                                         proto.max_new_tokens)], slots=1)
        assert shared[proto.rid].generated == solo[proto.rid].generated
        for a, b in zip(shared[proto.rid].logit_trace,
                        solo[proto.rid].logit_trace):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("arch", ARCHS)
def test_slot_reuse_regression_vs_shared_counter_semantics(arch):
    """Demonstrates the fixed bug. Old behavior: one shared scalar pos, no
    per-slot validity tags — a request admitted into a freed slot was
    decoded at the previous occupant's position over its stale K/V. Replay
    that semantics directly on a dirty cache and confirm it corrupts the
    logits; the orchestrator (per-slot pos + invalidation at admission)
    matches the clean single-request reference instead."""
    cfg = get_config(arch, smoke=True)
    params = _params(cfg)
    prompt_a, prompt_b = _prompt(cfg, 8, 3), _prompt(cfg, 5, 4)
    step = jax.jit(steps_lib.build_serve_step(cfg))

    # request A occupies the slot for 8 positions
    cache = registry.init_cache(cfg, 1, 32)
    for i in range(len(prompt_a)):
        _, cache = step(params, cache, jnp.asarray([[prompt_a[i]]]),
                        jnp.asarray(i, jnp.int32))
    # clean reference for B: fresh cache, positions from 0
    ref_cache = registry.init_cache(cfg, 1, 32)
    ref = []
    for i in range(len(prompt_b)):
        lg, ref_cache = step(params, ref_cache,
                             jnp.asarray([[prompt_b[i]]]),
                             jnp.asarray(i, jnp.int32))
        ref.append(np.asarray(lg[0, 0]))

    # OLD semantics: B decodes in A's slot at A's continuation positions,
    # attending over A's stale entries -> logits differ from the reference
    old_cache, old = cache, []
    for i in range(len(prompt_b)):
        lg, old_cache = step(params, old_cache,
                             jnp.asarray([[prompt_b[i]]]),
                             jnp.asarray(len(prompt_a) + i, jnp.int32))
        old.append(np.asarray(lg[0, 0]))
    assert any(not np.array_equal(o, r) for o, r in zip(old, ref)), \
        "stale-slot replay unexpectedly matched the clean reference"

    # NEW semantics: the orchestrator re-admits the slot with invalidated
    # tags and decodes B from position 0 -> bitwise-equal to the reference
    shared = _serve(cfg, params,
                    [_req(0, prompt_a, 2), _req(1, prompt_b, 3)], slots=1)
    solo = _serve(cfg, params, [_req(1, prompt_b, 3)], slots=1)
    assert shared[1].generated == solo[1].generated
    for a, b in zip(shared[1].logit_trace, solo[1].logit_trace):
        np.testing.assert_array_equal(a, b)


def test_invalidate_slots_resets_only_masked_slot():
    cfg = get_config("h2o-danube-3-4b", smoke=True)
    params = _params(cfg)
    cache = registry.init_cache(cfg, 2, 16)
    step = jax.jit(steps_lib.build_batched_serve_step(cfg))
    toks = jnp.asarray(_prompt(cfg, 4, 0)).reshape(2, 2)
    _, cache = step(params, cache, toks, jnp.zeros(2, jnp.int32),
                    jnp.full(2, 2, jnp.int32))
    tags = np.asarray(cache["layers"]["pos"])
    assert (tags[:, :, :2] >= 0).all()
    cache2 = registry.invalidate_slots(cfg, cache,
                                       jnp.asarray([True, False]))
    tags2 = np.asarray(cache2["layers"]["pos"])
    assert (tags2[:, 0] == -1).all()
    np.testing.assert_array_equal(tags2[:, 1], tags[:, 1])


# ---------------------------------------------------------------------------
# staggered admission
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_staggered_admission_matches_sequential_reference(arch):
    """Three requests with different prompt lengths over two slots: the
    third is admitted mid-flight while the survivors keep decoding. Every
    request's sampled tokens equal its single-request sequential run, and
    so do its logit rows (bitwise for spiking configs, see
    :func:`_assert_same_logits`)."""
    cfg = get_config(arch, smoke=True)
    params = _params(cfg)
    mk = lambda: [_req(0, _prompt(cfg, 7, 5), 4),
                  _req(1, _prompt(cfg, 4, 6), 6),
                  _req(2, _prompt(cfg, 10, 7), 3)]
    shared = _serve(cfg, params, mk(), slots=2)
    for proto in mk():
        solo = _serve(cfg, params, [_req(proto.rid, proto.prompt,
                                         proto.max_new_tokens)], slots=1)
        assert shared[proto.rid].generated == solo[proto.rid].generated
        for a, b in zip(shared[proto.rid].logit_trace,
                        solo[proto.rid].logit_trace):
            _assert_same_logits(cfg, a, b)


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_chunked_prefill_matches_whole_prompt_prefill(arch):
    """The first sampled logits row (the one conditioned on the whole
    prompt) agrees with build_prefill_step's last-position logits, for
    every chunk width; and all chunk widths agree with each other
    (bitwise for spiking configs, see :func:`_assert_same_logits`)."""
    cfg = get_config(arch, smoke=True)
    params = _params(cfg)
    prompt = _prompt(cfg, 11, 8)
    prefill = jax.jit(steps_lib.build_prefill_step(cfg))
    want = np.asarray(prefill(params, {"tokens": jnp.asarray(prompt)[None]})
                      )[0, -1]
    rows = []
    for chunk in (1, 4, 16):
        got = _serve(cfg, params, [_req(0, prompt, 2)], slots=1,
                     chunk=chunk)
        rows.append(got[0].logit_trace[0])
        np.testing.assert_allclose(rows[-1], want, atol=2e-4, rtol=2e-4)
    for r in rows[1:]:
        _assert_same_logits(cfg, rows[0], r)


def test_chunked_prefill_beyond_window_matches_tokenwise():
    """Rolling-window regression: with a prompt longer than the attention
    window, a prefill bite's scatter runs before attention — without ring
    headroom its later writes evict entries still inside earlier in-bite
    queries' windows. The window cache carries chunk-1 extra slots, so
    every chunk width stays bitwise-equal to token-at-a-time prefill."""
    cfg = get_config("h2o-danube-3-4b", smoke=True)
    assert cfg.attn_type == "swa" and cfg.window == 16
    params = _params(cfg)
    prompt = _prompt(cfg, 30, 10)       # prompt >> window
    runs = {}
    for chunk in (1, 8, 16):
        got = _serve(cfg, params, [_req(0, prompt, 4)], slots=1,
                     max_len=48, chunk=chunk)
        runs[chunk] = got[0]
    for chunk in (8, 16):
        assert runs[chunk].generated == runs[1].generated, chunk
        # ring length is window + chunk - 1, so the softmax reduction
        # order differs across chunk widths — tokens must match exactly,
        # logits to fp32 reduction tolerance
        for a, b in zip(runs[chunk].logit_trace, runs[1].logit_trace):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


def test_submit_rejects_degenerate_prompts():
    cfg = get_config("h2o-danube-3-4b", smoke=True)
    server = BatchedServer(cfg, _params(cfg), 1, 16)
    with pytest.raises(ValueError, match="empty"):
        server.submit(_req(0, np.zeros(0, np.int32), 2))
    with pytest.raises(ValueError, match="capacity"):
        server.submit(_req(1, _prompt(cfg, 17, 0), 2))
    with pytest.raises(ValueError, match="max_new_tokens"):
        server.submit(_req(2, _prompt(cfg, 4, 0), 0))


def test_chunk_policy_follows_decode_share():
    """choose_chunk: Eq. 6 argmax widens (never narrows) as the decode
    share of the batch grows, returns 1 with no backlog, and respects the
    cap."""
    assert choose_chunk(0, 3, 32) == 1
    widths = [choose_chunk(64, n_dec, 32) for n_dec in range(4)]
    assert all(b >= a for a, b in zip(widths, widths[1:]))
    assert widths[-1] > widths[0]
    assert all(1 <= w <= 32 for w in widths)
    assert choose_chunk(64, 8, 4) <= 4


# ---------------------------------------------------------------------------
# retirement / capacity
# ---------------------------------------------------------------------------


def test_retirement_uses_full_cache_capacity():
    """A request bounded only by cache capacity generates max_len - L + 1
    tokens: positions 0..max_len-1 all hold written entries, plus the
    final sampled token that is never written back (the old `>= max_len-1`
    check retired one usable position early)."""
    cfg = get_config("h2o-danube-3-4b", smoke=True)
    params = _params(cfg)
    max_len, plen = 16, 10
    got = _serve(cfg, params, [_req(0, _prompt(cfg, plen, 9), 100)],
                 slots=1, max_len=max_len)
    assert len(got[0].generated) == max_len - plen + 1


def test_kv_cache_stats_selects_by_key():
    """Footprint counts exactly the k/v payload bytes (selected by key),
    never the validity tags — whatever their dtype."""
    for arch, packed in (("h2o-danube-3-4b", False),
                         ("spikingformer-lm", True)):
        cfg = get_config(arch, smoke=True)
        server = BatchedServer(cfg, _params(cfg), 2, 16)
        stats = server.kv_cache_stats()
        flat, _ = jax.tree_util.tree_flatten_with_path(server.cache)
        want = sum(l.nbytes for path, l in flat
                   if path[-1].key in ("k", "v"))
        assert stats["kv_bytes"] == want
        assert stats["packed"] is packed
        if packed:   # head_dim=16 spikes in one fp32-replacing uint32 word
            assert stats["compression"] == 16.0


def test_rejects_unslotted_family():
    cfg = get_config("rwkv6-3b", smoke=True)
    with pytest.raises(ValueError, match="slot"):
        BatchedServer(cfg, _params(cfg), 2, 16)


# ---------------------------------------------------------------------------
# mesh-sharded decode (subprocess: needs a forced 8-device host platform)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_serve_mesh
    from repro.launch.serve import BatchedServer, Request
    from repro.models import registry

    assert len(jax.devices()) == 8
    cfg = get_config("{arch}", smoke=True)
    params = registry.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = lambda: [Request(rid=i, prompt=rng2, max_new_tokens=4)
                    for i, rng2 in enumerate(
                        rng.integers(0, cfg.vocab_size, (5, 7))
                        .astype(np.int32))]
    runs = {{}}
    for name, mesh in (("none", None), ("2x2", make_serve_mesh(2, 2)),
                       ("4x2", make_serve_mesh(4, 2))):
        server = BatchedServer(cfg, params, 4, 24, mesh=mesh)
        for r in reqs():
            server.submit(r)
        server.run()
        assert len(server.completed) == 5
        runs[name] = {{r.rid: r.generated for r in server.completed}}
        rng = np.random.default_rng(0)   # same prompts every run
    assert runs["2x2"] == runs["none"], (runs["2x2"], runs["none"])
    assert runs["4x2"] == runs["none"], (runs["4x2"], runs["none"])
    print("MESH-OK")
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_mesh_sharded_server_matches_unsharded(arch):
    """BatchedServer under (data, model) serving meshes on 8 forced host
    devices: sharded cache/params, identical generations."""
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT.format(arch=arch)],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH-OK" in out.stdout
