"""Closed-loop serving of an analog (non-spiking) LM through the
program's ``BatchedServer``: ``serve_closed`` with what is spiking there
replaced. The configuration file carries the model's own Hugging Face
keys; the weights follow the program's dense-family layout with an
expert layer in place of each MLP; the check compares with
``reference/mellum2.py``; the control is that reference computed in a
lower precision. The traffic parameters, the closed loop, the times and
the counters are ``serve_closed``'s, plus ``context_window``: the sum
over processed positions of the positions a sliding-window layer attends
(``sliding_window`` at most).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import traffic, weights
from chip_bench.reference import mellum2 as ref
from chip_bench.runners import serve_closed


def program_config(c: dict):
    """The program's ModelConfig for configuration file ``c``: the registry
    entry in the file's dtype, refused if any size, the layer pattern or a
    rope differs from the file."""
    from repro.configs import get_config
    cfg = get_config(c["registry"], smoke=c.get("smoke", False))
    cfg = cfg.replace(dtype=c["dtype"])
    m, p = cfg.moe, ref.period(c)
    have = {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "rms_norm_eps": cfg.norm_eps, "sliding_window": cfg.window,
        "hidden_act": cfg.act, "tie_word_embeddings": cfg.tie_embeddings,
        "moe_intermediate_size": m.d_ff_expert, "num_experts": m.num_experts,
        "num_experts_per_tok": m.top_k, "norm_topk_prob": m.normalize_topk,
        "layer_types": (["sliding_attention"] * (cfg.global_every - 1)
                        + ["full_attention"])
        * (cfg.num_layers // cfg.global_every),
        "mlp_layer_types": ["sparse"] * cfg.num_layers,
    }
    want = {k: c[k] for k in have}
    have["published_num_experts"] = m.width
    want["published_num_experts"] = c["deployment"]["published_num_experts"]
    rp, y = c["rope_parameters"], cfg.yarn
    have["rope_parameters"] = {
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": cfg.rope_theta},
        "full_attention": {
            "rope_type": "yarn", "rope_theta": cfg.rope_theta,
            "factor": y.factor,
            "original_max_position_embeddings": y.original_max_position,
            "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
            "attention_factor": y.attention_factor}}
    want["rope_parameters"] = rp
    if cfg.attn_type != "local_global" or cfg.global_every != p \
            or m.num_shared or cfg.qk_norm:
        raise ValueError(f"{c['registry']}: the program's layer is not the "
                         f"file's")
    wrong = {k: (v, want[k]) for k, v in have.items() if want[k] != v}
    if wrong:
        raise ValueError(f"{c['registry']}: program and configuration file "
                         f"differ (program, file): {wrong}")
    return cfg


def lm_params(c: dict, key):
    """The program's tree for the dense family with an expert layer:
    ``groups`` stacks (periods, layers of a period, ...)."""
    dt = jnp.dtype(c["dtype"])
    d, v = c["hidden_size"], c["vocab_size"]
    nl, per = c["num_hidden_layers"], ref.period(c)
    lead = (nl // per, per)
    qd = c["num_attention_heads"] * c["head_dim"]
    kvd = c["num_key_value_heads"] * c["head_dim"]
    e, f = c["num_experts"], c["moe_intermediate_size"]
    width = c["deployment"]["published_num_experts"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dtype=dt):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    def lin(k_in, n_out, std=None):
        return {"w": normal(lead + (k_in, n_out),
                            1.0 / math.sqrt(k_in) if std is None else std)}

    def norm(shape):
        return {"scale": (1.0 + normal(shape, 0.1, jnp.float32)).astype(dt)}
    groups = {
        "ln1": norm(lead + (d,)), "wq": lin(d, qd), "wk": lin(d, kvd),
        "wv": lin(d, kvd), "wo": lin(qd, d, 1.0 / math.sqrt(qd * 2 * nl)),
        "ln2": norm(lead + (d,)),
        "moe": {"router": normal(lead + (d, width), 1.0 / math.sqrt(d),
                                 jnp.float32),
                "up": normal(lead + (e, d, f), 1.0 / math.sqrt(d)),
                "gate": normal(lead + (e, d, f), 1.0 / math.sqrt(d)),
                "down": normal(lead + (e, f, d), 1.0 / math.sqrt(f))},
    }
    return {"embed": {"table": normal((v, d), 1.0 / math.sqrt(d))},
            "final_norm": norm((d,)),
            "groups": groups,
            "lm_head": {"w": normal((d, v), 1.0 / math.sqrt(d))}}


def seeded_params(c: dict, cfg, seed: int):
    from repro.models import registry
    params = jax.jit(functools.partial(lm_params, c))(weights.jax_key(seed))
    weights.check_layout(params, jax.eval_shape(
        lambda: registry.init(cfg, jax.random.PRNGKey(0))))
    return params


def window_context(n: int, window: int) -> int:
    """Positions attended by positions 0..n-1 of a sliding-window layer."""
    if n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


class Runner(serve_closed.Runner):
    def __init__(self, cell, devices):
        self.cell, self.c, self.mix = cell, cell.config, cell.traffic
        self.cfg = program_config(self.c)
        rng = weights.seeded_rng(cell.seed, 0)
        self.sizes = traffic.request_sizes(self.mix, rng)
        self.prompts = traffic.prompts(self.sizes, self.c["vocab_size"], rng)
        self.next_req = 0
        self.live = {}
        self.finished = []
        self.tokens = self.processed = self.context = self.waves = 0
        self.context_window = 0

    def setup(self):
        from repro.launch.serve import BatchedServer
        params = seeded_params(self.c, self.cfg, self.cell.seed)
        self.server = BatchedServer(self.cfg, params, self.mix["slots"],
                                    self.mix["max_len"],
                                    chunk=self.mix["chunk"])
        self._warm()
        self._lead_in()

    def _lead_in(self):
        super()._lead_in()
        self.context_window = 0

    def _wave(self):
        # the entries' last positions, before and (entry[3], set in place
        # even for a request the wave finished) after the wave
        before = {rid: (e, e[3]) for rid, e in self.live.items()}
        super()._wave()
        w = self.c["sliding_window"]
        for entry, seen in before.values():
            self.context_window += window_context(entry[3], w) \
                - window_context(seen, w)

    def counters(self):
        return dict(super().counters(), context_window=self.context_window)

    def scopes(self):
        """The serve step's scopes from the widest warmed width's program,
        the one nearly every wave of this mix runs (a 16-token bite is
        almost always in flight), then the narrower ones' for
        instructions it lacks. The widths' programs share one module name
        and reuse instruction names for other ops, so the join that drops
        every name they disagree on leaves most fusions unscoped."""
        from chip_bench.trace import hlo_scopes
        s, slots = self.server, self.mix["slots"]
        pos = jnp.zeros(slots, jnp.int32)
        merged = {}
        for w in sorted(self.widths, reverse=True):
            text = s._step.lower(s.params, s.cache,
                                 jnp.zeros((slots, w), jnp.int32), pos,
                                 pos).compile().as_text()
            for key, name in hlo_scopes(text).items():
                merged.setdefault(key, name)
        return merged

    def free(self):
        self.params = self.server.params
        del self.server

    def check(self, control=None):
        """``serve_closed``'s check against ``reference/mellum2.py``: the
        gaps by which a served token's reference logit lies below the
        reference's best, over a seeded sample of finished requests with
        the longest among them. With ``control`` (a dtype) the tokens read
        are the reference's first choices computed in that dtype. No
        spike densities: the model has no spikes."""
        rng = weights.seeded_rng(self.cell.seed, 1)
        done = [r for r, _, _ in self.finished]
        if not done:
            raise RuntimeError("the window finished no request")
        longest = max(range(len(done)), key=lambda i: len(done[i].generated))
        rest = [i for i in range(len(done)) if i != longest]
        pick = [longest] + [int(i) for i in rng.choice(
            rest, min(len(rest), self.mix["check"] - 1), replace=False)]
        fwd = jax.jit(functools.partial(ref.forward, self.c))
        other = None if control is None else jax.jit(functools.partial(
            ref.forward, self.c, compute=control))
        gaps, miss = [], []
        for i in pick:
            req = done[i]
            gen = np.asarray(req.generated, np.int32)
            seq = np.concatenate([req.prompt, gen[:-1]])
            pad = np.zeros(self.mix["max_len"], np.int32)
            pad[:len(seq)] = seq
            at = slice(len(req.prompt) - 1, len(seq))
            lg = np.asarray(fwd(self.params, jnp.asarray(pad))[at])
            if other is not None:
                gen = np.asarray(other(self.params, jnp.asarray(pad))[at]
                                 ).argmax(-1)
            gaps.append(lg.max(-1) - lg[np.arange(len(gen)), gen])
            miss.append(gen != lg.argmax(-1))
        gaps, miss = np.concatenate(gaps), np.concatenate(miss)
        return {"served_token_gap": float(gaps.max()),
                "served_token_gap_mean": float(gaps.mean()),
                "served_token_mismatch": float(miss.mean()),
                "served_tokens_checked": float(len(gaps))}, {}
