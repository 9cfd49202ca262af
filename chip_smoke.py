#!/usr/bin/env python3
"""Bring-up smoke of the main path on one TPU chip, at published widths.

    python chip_smoke.py               # phases (a)-(c), one chip
    python chip_smoke.py --four-chips  # the 2x2 serving mesh only

(a) training: ``launch.train.train`` takes 3 steps of spikingformer-8-512
    (224x224, T_s=4, batch 8, float32, dyadic weights). The first step's
    loss is checked against the same forward with ``mode='dense'`` (XLA
    dots instead of the sparse-engine kernels), and the compiled train
    step must hold Pallas kernels.
(b) inference through the layer program: spikingformer-8-512 eval logits
    with ``overlap='fused'`` (the fused_layer kernel) against
    ``overlap='off'`` (the sequential oracle), same params and input.
(c) serving: a full-width spikingformer-lm ``BatchedServer`` answers 8
    requests (4 slots, 128-token prompts, 16 new tokens, 256-entry packed
    KV cache); each request's prefill logits are checked against a
    one-shot whole-prompt forward.
--four-chips: spikingformer-lm served on a 2x2 (data, model) mesh against
    the same requests served unsharded, in float32 (identical tokens)
    and in the published bfloat16 (first logit rows within an RMS bound).

Everything runs in this one process (a chip belongs to one process).
Without a TPU it exits non-zero before any phase. Weights are random
from ``--seed``; the compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache`` in the
checkout. The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.engine import engine_scope  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.serve import BatchedServer, Request  # noqa: E402
from repro.launch.train import make_batch_fn, train  # noqa: E402
from repro.models import registry  # noqa: E402

VISION = "spikingformer-8-512"
LM = "spikingformer-lm"
# (c): chunked prefill through the packed-KV decode step against the
# whole-prompt forward. Both compute the same binary attention exactly;
# the analog projections reduce in bf16-operand / fp32-accumulate dots
# whose blocking follows the wave width, so a logit row moves by the
# rounding of those sums: 0.0406 on a v5e, 0.0078 on the CPU. A planted
# fault (one slot's later prefill bites written one cache entry early)
# moves it by 1.02 on the CPU. The bound sits between the two.
PREFILL_ATOL = 0.1
# --four-chips. In float32 the sharded server must generate the unsharded
# server's tokens, as the CPU mesh test requires: that is the check of
# the partition. A planted fault in the MLP down projection's model-axis
# sum (one device's partial kept, or the sum doubled) parts the tokens
# of 8/8 requests on a CPU 2x2 mesh. In bfloat16 (the published dtype)
# the sharded program sums partials in another order and rounds them to
# 8 significant bits; LIF thresholds fed by them flip spikes, and the
# generations part with no fault at all. There the first logit rows are
# held to an RMS difference relative to their RMS: 0.146 honest on the
# CPU mesh, 0.292 with the partial kept and 0.275 with the sum doubled.
# The bound sits between, so it catches a wrong partition only within
# that margin.
MESH_BF16_RMS = 0.2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def dyadic(tree):
    """Float leaves rounded to the dyadic grid k * 2^-8 (|k| <= 128 at
    these init scales): exact in bf16 and in fp32 sums, so dense and
    sparse-kernel projections of {0,1} spikes agree to the bit — the
    grid tests/test_engine.py draws from."""
    return jax.tree_util.tree_map(
        lambda a: jnp.round(a * 256) / 256
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def peak_hbm() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def custom_calls(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


def aot(fn, *args):
    """(compiled, compile seconds) for jit(fn) at these arguments."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def phase_train(name: str = VISION, smoke: bool = False, batch: int = 8,
                steps: int = 3, seed: int = 0):
    print(f"== (a) training {name}: {steps} steps, batch {batch}, float32")
    cfg = get_config(name, smoke=smoke).replace(dtype="float32")
    params = dyadic(registry.init(cfg, jax.random.PRNGKey(seed)))
    state = registry.init_state(cfg)
    b0 = {k: jnp.asarray(v) for k, v in make_batch_fn(cfg, batch, 0)(0)
          .items()}

    def first_loss(mode):
        c = cfg.replace(engine=cfg.engine.replace(mode=mode))

        def f(p, b, s):
            with engine_scope(c):
                logits, _ = registry.forward(p, c, b, train=True, state=s)
            return steps_lib.loss_from_forward(c, logits, b)
        compiled, secs = aot(f, params, b0, state)
        loss, run = timed(compiled, params, b0, state)
        print(f"   first-step forward mode={mode}: loss {float(loss)!r} "
              f"(compile {secs:.2f}s, run {run:.3f}s, "
              f"{custom_calls(compiled.as_text())} tpu_custom_call)")
        return float(loss)

    l_dense = first_loss("dense")
    l_engine = first_loss(cfg.engine.mode)
    check(np.isfinite(l_dense) and l_engine == l_dense,
          f"first-step loss: engine {l_engine!r} != dense {l_dense!r}")
    stats = {}
    losses = train(name, smoke, total_steps=steps, batch=batch, seq=0,
                   lr=1e-3, ckpt_dir=None, ckpt_every=1 << 30,
                   inject_failure_at=None, compress=False, log_every=1,
                   seed=seed, dtype="float32", params=params, stats=stats)
    n_calls = custom_calls(stats["hlo"])
    print(f"   train step: compile {stats['compile_s']:.2f}s, step seconds "
          f"{[round(s, 4) for s in stats['step_s']]}, losses {losses}, "
          f"{n_calls} tpu_custom_call, peak HBM {peak_hbm()}")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(n_calls > 0, "the compiled train step holds no Pallas kernel")
    print(f"   train step-0 loss {losses[0]!r} vs forward {l_engine!r}: "
          f"|diff| {abs(losses[0] - l_engine):.3g}")
    check(abs(losses[0] - l_engine) <= 1e-4 * abs(l_engine),
          "train step-0 loss strays from its own forward")


def phase_eval(name: str = VISION, smoke: bool = False, batch: int = 8,
               seed: int = 0):
    print(f"== (b) inference {name}: batch {batch}, float32, "
          f"overlap fused vs off")
    cfg = get_config(name, smoke=smoke).replace(dtype="float32")
    params = dyadic(registry.init(cfg, jax.random.PRNGKey(seed)))
    v = cfg.vision
    images = jax.random.normal(jax.random.PRNGKey(seed + 2),
                               (batch, v.img_size, v.img_size,
                                v.in_channels), jnp.float32)
    b = {"images": images}
    out = {}
    for overlap in ("off", "fused"):
        c = cfg.replace(engine=cfg.engine.replace(overlap=overlap))
        compiled, secs = aot(steps_lib.build_prefill_step(c), params, b)
        n_calls = custom_calls(compiled.as_text())
        logits, run = timed(compiled, params, b)
        _, warm = timed(compiled, params, b)
        print(f"   overlap={overlap}: compile {secs:.2f}s, first run "
              f"{run:.3f}s, warm run {warm:.4f}s, {n_calls} "
              f"tpu_custom_call, peak HBM {peak_hbm()}")
        out[overlap] = np.asarray(logits, np.float32)
    fused, off = out["fused"], out["off"]
    diff = float(np.max(np.abs(fused - off)))
    print(f"   logits {fused.shape}: bitwise equal "
          f"{bool(np.array_equal(fused, off))}, max |fused - off| {diff!r}")
    check(bool(np.isfinite(fused).all()), "non-finite fused logits")
    check(n_calls > 0,          # of the last step compiled: the fused one
          "the fused eval step holds no Pallas kernel")
    check(np.array_equal(fused, off), "fused logits differ from the oracle")


def lm_requests(cfg, n: int, prompt_len: int, max_new: int, seed: int):
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (n, prompt_len)
                           ).astype(np.int32)
    return [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]


def serve(cfg, params, reqs, *, slots, max_len, mesh=None):
    server = BatchedServer(cfg, params, slots, max_len, mesh=mesh,
                           trace_logits=True)
    for r in reqs:
        server.submit(r)
    t0 = time.perf_counter()
    waves = server.run()
    secs = time.perf_counter() - t0
    check(len(server.completed) == len(reqs), "server dropped requests")
    return server, {r.rid: r for r in server.completed}, waves, secs


def phase_serve(name: str = LM, smoke: bool = False, n_req: int = 8,
                slots: int = 4, prompt_len: int = 128, max_new: int = 16,
                max_len: int = 256, seed: int = 0):
    print(f"== (c) serving {name}: {n_req} requests, {slots} slots, "
          f"prompt {prompt_len}, max_new {max_new}, max_len {max_len}")
    cfg = get_config(name, smoke=smoke)
    params = registry.init(cfg, jax.random.PRNGKey(seed))
    reqs = lm_requests(cfg, n_req, prompt_len, max_new, seed)
    server, done, waves, secs = serve(cfg, params, reqs, slots=slots,
                                      max_len=max_len)
    kv = server.kv_cache_stats()
    n_gen = sum(len(r.generated) for r in done.values())
    print(f"   {waves} waves in {secs:.2f}s (compiles included), {n_gen} "
          f"tokens generated, kv cache {kv['kv_bytes'] / 2**20:.2f} MiB "
          f"packed={kv['packed']}, peak HBM {peak_hbm()}")
    check(kv["packed"], "spikingformer-lm serves a packed KV cache")
    prefill, secs = aot(steps_lib.build_prefill_step(cfg), params,
                        {"tokens": jnp.asarray(reqs[0].prompt)[None]})
    print(f"   whole-prompt forward compile {secs:.2f}s")
    worst, agree = 0.0, 0
    for r in reqs:
        want = np.asarray(prefill(params, {"tokens": jnp.asarray(
            r.prompt)[None]})[0, -1], np.float32)
        got = np.asarray(done[r.rid].logit_trace[0], np.float32)
        check(bool(np.isfinite(got).all()), f"request {r.rid}: non-finite")
        worst = max(worst, float(np.max(np.abs(got - want))))
        agree += int(np.argmax(got) == np.argmax(want))
    print(f"   prefill logits vs whole-prompt forward: max |diff| "
          f"{worst!r} (bound {PREFILL_ATOL}), first token agrees on "
          f"{agree}/{len(reqs)}")
    check(worst <= PREFILL_ATOL, "chunked prefill strays from the forward")


def device_shares(tree):
    """Bytes of ``tree``'s arrays held on each device."""
    share = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for sh in leaf.addressable_shards:
            share[sh.device.id] = share.get(sh.device.id, 0) + \
                sh.data.nbytes
    return share


def first_divergence(a, b) -> int:
    """Index of the first differing token of two generations (len if
    none)."""
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


def phase_mesh(name: str = LM, smoke: bool = False, n_req: int = 8,
               slots: int = 4, prompt_len: int = 128, max_new: int = 16,
               max_len: int = 256, seed: int = 0):
    from repro.launch.mesh import make_serve_mesh
    mesh = make_serve_mesh(2, 2)
    base = get_config(name, smoke=smoke)
    for dtype in dict.fromkeys(("float32", base.dtype)):
        print(f"== four chips: {name} ({dtype}) on a 2x2 (data, model) "
              f"mesh vs unsharded, {n_req} requests")
        cfg = base.replace(dtype=dtype)
        params = registry.init(cfg, jax.random.PRNGKey(seed))
        runs = {}
        for label, m in (("unsharded", None), ("mesh", mesh)):
            server, runs[label], waves, secs = serve(
                cfg, params, lm_requests(cfg, n_req, prompt_len, max_new,
                                         seed),
                slots=slots, max_len=max_len, mesh=m)
            print(f"   {label}: {waves} waves in {secs:.2f}s (compiles "
                  f"included), peak HBM {peak_hbm()}")
        for what, tree in (("params", server.params),
                           ("cache", server.cache)):
            share = device_shares(tree)
            total = sum(share.values())
            print(f"   {what} per device: " + ", ".join(
                f"dev{d} {b / 2**20:.2f} MiB ({b / total:.0%})"
                for d, b in sorted(share.items())))
            check(len(share) == 4 and max(share.values()) < total,
                  f"{what} not spread over the four devices")
        ref, got = runs["unsharded"], runs["mesh"]
        div = [first_divergence(got[i].generated, ref[i].generated)
               for i in ref]
        want = np.stack([np.asarray(ref[i].logit_trace[0], np.float32)
                         for i in ref])
        have = np.stack([np.asarray(got[i].logit_trace[0], np.float32)
                         for i in ref])
        diff, top = float(np.abs(have - want).max()), \
            float(np.abs(want).max())
        rms = float(np.sqrt(np.mean((have - want) ** 2)
                            / np.mean(want ** 2)))
        same = sum(got[i].generated == ref[i].generated for i in ref)
        print(f"   tokens identical on {same}/{len(ref)} requests, first "
              f"divergence at {div}; prefill logits: max |diff| {diff!r}, "
              f"max |logit| {top!r}, rms diff / rms logit {rms!r}, "
              f"entries that differ {float(np.mean(have != want)):.4f}, "
              f"first token equal "
              f"{int(np.sum(have.argmax(1) == want.argmax(1)))}/{len(ref)}")
        if dtype == "float32":
            check(same == len(ref),
                  "float32 sharded serving generates other tokens")
        else:
            check(rms <= MESH_BF16_RMS,
                  f"{dtype} sharded prefill logits stray past "
                  f"{MESH_BF16_RMS} rms relative")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 2x2 mesh vs unsharded, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if args.four_chips and n_dev < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {n_dev}",
              file=sys.stderr)
        return 2
    cache_dir = setup_compile_cache()
    print(f"chip_smoke: {dev.platform} {dev.device_kind} x{n_dev}, jax "
          f"{jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_mesh(seed=args.seed)
        else:
            phase_train(seed=args.seed)
            phase_eval(seed=args.seed)
            phase_serve(seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
