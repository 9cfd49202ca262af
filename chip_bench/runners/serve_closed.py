"""Closed-loop serving through the program's ``BatchedServer``.

Traffic parameters: ``clients`` callers that each send their next
request as soon as the previous one is answered, from ``lead_in_s``
seconds before the window; a server with ``slots`` cache slots of
``max_len`` positions and prefill chunk ``chunk`` (0 = the server's own
policy); ``requests`` request sizes from the ``prompt`` and
``output`` length laws (``traffic.py``), cycled if the window outlasts
them; ``check`` finished requests compared with the reference after the
window, the longest among them. Decoding is greedy.

Times are host-clock reads after each wave, when its tokens are on the
host: time to first token from submit to the wave that made the first
token, and the gap between tokens as the time between the waves that
made consecutive tokens of one request.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import stats, traffic, weights
from chip_bench.reference import spiking_lm as ref
from chip_bench.runners import common


def _pow2_up_to(n: int):
    w = 1
    while w < n:
        yield w
        w *= 2
    yield w


class Runner:
    def __init__(self, cell, devices):
        self.cell, self.c, self.mix = cell, cell.config, cell.traffic
        self.cfg = common.program_config(self.c)
        rng = weights.seeded_rng(cell.seed, 0)
        self.sizes = traffic.request_sizes(self.mix, rng)
        self.prompts = traffic.prompts(self.sizes, self.c["vocab_size"], rng)
        self.next_req = 0
        self.live = {}   # rid -> [request, submit_t, token times, positions]
        self.finished = []        # (request, submit_t, token times)
        self.tokens = 0           # generated tokens
        self.processed = 0        # positions through waves
        self.context = 0          # sum over them of positions attended
        self.waves = 0

    # -- set-up -------------------------------------------------------------

    def setup(self):
        from repro.launch.serve import BatchedServer
        params = common.seeded_params(self.c, self.cfg, self.cell.seed)
        self.server = BatchedServer(self.cfg, params, self.mix["slots"],
                                    self.mix["max_len"],
                                    chunk=self.mix["chunk"])
        self._warm()
        self._lead_in()

    def _lead_in(self):
        """Run the traffic for ``lead_in_s`` before the window, so that the
        window opens on a steady loop and not on every client's first
        prompt at once; what the lead-in served is not counted."""
        end = time.perf_counter() + self.mix.get("lead_in_s", 0)
        while time.perf_counter() < end:
            self.step()
        self.finished, self.tokens, self.waves = [], 0, 0
        self.processed = self.context = 0

    def _warm(self):
        """Compile every wave width this mix can produce, the argmax the
        server applies to each, and the slot invalidation. The waves carry
        no tokens (``n_tok`` 0), so the cache is left as it was."""
        s = self.server
        self.widths = list(_pow2_up_to(self._widest()))
        slots = self.mix["slots"]
        # the conversions a wave makes of its host arrays, as step() makes
        # them (slot positions are int64 on the host)
        pos = jnp.asarray(np.zeros(slots, np.int64), jnp.int32)
        n_tok = jnp.asarray(np.zeros(slots, np.int32))
        for w in self.widths:
            logits, s.cache = s._step(
                s.params, s.cache, jnp.asarray(np.zeros((slots, w),
                                                        np.int32)),
                pos, n_tok)
            np.asarray(jnp.argmax(logits, axis=-1))
        s.cache = s._invalidate(s.cache, jnp.zeros((slots,), bool))
        jax.block_until_ready(s.cache)

    def _widest(self) -> int:
        """The widest wave this mix can produce: a whole prompt where the
        chunk is fixed; under the server's own policy (``choose_chunk``)
        the widest bite it picks with every other slot decoding, the state
        in which its lane budget is largest, over every backlog one
        prompt can leave."""
        from repro.launch.serve import choose_chunk
        s, longest = self.server, self.mix["prompt"]["max"]
        if s.fixed_chunk:
            return min(s.max_chunk, longest)
        return min(longest, max(
            choose_chunk(b, self.mix["slots"] - 1, s.max_chunk)
            for b in range(1, longest + 1)))

    # -- window -------------------------------------------------------------

    def _submit(self, now):
        from repro.launch.serve import Request
        i = self.next_req % len(self.sizes)
        req = Request(rid=self.next_req, prompt=self.prompts[i],
                      max_new_tokens=self.sizes[i][1])
        self.next_req += 1
        self.server.submit(req)
        self.live[req.rid] = [req, now, [], 0]

    def step(self):
        if not self.live:
            now = time.perf_counter()
            for _ in range(self.mix["clients"]):
                self._submit(now)
        self._wave()

    def _wave(self):
        """One ``BatchedServer.step()`` and its accounting: the positions it
        processed, the tokens it made and when, the requests it finished."""
        s = self.server
        n_done = len(s.completed)
        with jax.profiler.TraceAnnotation("bench.wave"):
            s.step()
        t = time.perf_counter()
        self.waves += 1
        in_slot = {r.rid: i for i, r in enumerate(s.slot_req)
                   if r is not None}
        for rid, entry in self.live.items():
            req, _, times, seen = entry
            if rid in in_slot:
                pos = int(s.slot_pos[in_slot[rid]])
            elif req.done:
                pos = min(len(req.prompt) + len(req.generated) - 1,
                          s.max_len)
            else:
                pos = 0
            # positions seen..pos-1 went through this wave, each attending
            # over itself and all before it
            self.processed += pos - seen
            self.context += (pos * (pos + 1) - seen * (seen + 1)) // 2
            entry[3] = pos
            while len(times) < len(req.generated):
                times.append(t)
                self.tokens += 1
        for req in s.completed[n_done:]:
            self.finished.append(tuple(self.live.pop(req.rid)[:3]))
            self._submit(t)      # the client sends its next request at once

    def drain(self):
        pass

    def counters(self):
        return {"waves": self.waves, "tokens": self.tokens,
                "processed": self.processed, "context": self.context}

    def end_to_end(self, t0, t1):
        ttft, itl = [], []
        for req, sub, times in self.finished + [tuple(v[:3]) for v in
                                                self.live.values()]:
            if times and t0 <= times[0] <= t1:
                ttft.append(times[0] - sub)
            itl += [b - a for a, b in zip(times, times[1:])
                    if t0 <= a and b <= t1]
        self.samples = (len(ttft), len(itl))
        return {"tokens_per_s": stats.rate(self.tokens, t1 - t0),
                "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
                "itl_p95_ms": 1e3 * stats.percentile(itl, 95)}

    def attempted(self):
        return len(self.finished), 0

    def notes(self):
        out = [f"{self.waves} waves, {len(self.finished)} requests finished, "
               f"{self.tokens} tokens generated, {self.processed} processed; "
               f"wave widths warmed {self.widths}"]
        if hasattr(self, "samples"):
            out.append(f"samples: {self.samples[0]} first tokens, "
                       f"{self.samples[1]} gaps between tokens")
        return out

    def scopes(self):
        """The serve step's scopes, joined from the compiled program of each
        warmed width. The widths' programs share one module name, so an
        instruction whose op_name differs between them is left out."""
        from chip_bench.trace import hlo_scopes
        s, slots = self.server, self.mix["slots"]
        pos = jnp.zeros(slots, jnp.int32)
        merged, clash = {}, set()
        for w in self.widths:
            text = s._step.lower(s.params, s.cache,
                                 jnp.zeros((slots, w), jnp.int32), pos,
                                 pos).compile().as_text()
            for key, name in hlo_scopes(text).items():
                if merged.setdefault(key, name) != name:
                    clash.add(key)
        return {k: v for k, v in merged.items() if k not in clash}

    # -- check --------------------------------------------------------------

    def free(self):
        self.params = jax.device_get(self.server.params)
        del self.server

    def check(self, control=None):
        """Gaps by which a served token's reference logit lies below the
        reference's best, over a seeded sample of finished requests with
        the longest among them, and the spike density of each layer there.
        Each prompt with its served tokens is padded to ``max_len``
        (causal: the pad changes nothing before it), so the reference
        compiles once. With ``control`` the tokens read are the control's
        first choices at the same positions of the same sequences:
        ``"int8"`` the program's int8-weight forward, another dtype the
        reference computed in it."""
        rng = weights.seeded_rng(self.cell.seed, 1)
        done = [r for r, _, _ in self.finished]
        if not done:
            raise RuntimeError("the window finished no request")
        longest = max(range(len(done)), key=lambda i: len(done[i].generated))
        rest = [i for i in range(len(done)) if i != longest]
        pick = [longest] + [int(i) for i in rng.choice(
            rest, min(len(rest), self.mix["check"] - 1), replace=False)]
        params = jax.device_put(self.params)
        fwd = jax.jit(functools.partial(ref.forward, self.c))
        other = None if control is None else self._control(params, control)
        gaps, miss, dens = [], [], []
        for i in pick:
            req = done[i]
            gen = np.asarray(req.generated, np.int32)
            seq = np.concatenate([req.prompt, gen[:-1]])
            pad = np.zeros(self.mix["max_len"], np.int32)
            pad[:len(seq)] = seq
            logits, d = fwd(params, jnp.asarray(pad))
            at = slice(len(req.prompt) - 1, len(seq))
            lg = np.asarray(logits)[at]
            if other is not None:
                gen = np.asarray(other(jnp.asarray(pad)))[at].argmax(-1)
            gaps.append(lg.max(-1) - lg[np.arange(len(gen)), gen])
            miss.append(gen != lg.argmax(-1))
            dens.append(np.asarray(d))
        gaps, miss = np.concatenate(gaps), np.concatenate(miss)
        densities = dict(zip(ref.density_names(self.c),
                             np.mean(dens, axis=0).tolist()))
        return {"served_token_gap": float(gaps.max()),
                "served_token_gap_mean": float(gaps.mean()),
                "served_token_mismatch": float(miss.mean()),
                "served_tokens_checked": float(len(gaps))}, densities

    def _control(self, params, control):
        """tokens (max_len,) -> control logits (max_len, vocab)."""
        if control != "int8":
            fwd = jax.jit(functools.partial(ref.forward, self.c,
                                            compute=control))
            return lambda toks: fwd(params, toks)[0]
        from repro.launch import steps
        from repro.quant import quantize_tree
        cfg = self.cfg.replace(engine=self.cfg.engine.replace(
            weights="int8"))
        fwd = jax.jit(steps.build_prefill_step(cfg))
        qparams = quantize_tree(params, "int8")
        return lambda toks: fwd(qparams, {"tokens": toks[None]})[0]
