"""Closed-loop batched classification through the program's eval step.

Traffic parameters: ``batch`` images per step, ``batches`` distinct
batches staged on the device before the window and cycled through it,
and ``check`` images compared with the reference after it. At most
``IN_FLIGHT`` steps are dispatched and not yet finished: the host runs
that many steps ahead of the device, so a pause of the host shorter than
``IN_FLIGHT - 1`` steps leaves the device busy. The rate counts finished
work only.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chip_bench import weights
from chip_bench.reference import spikingformer as ref
from chip_bench.runners import common

IN_FLIGHT = 32


class Runner:
    def __init__(self, cell, devices):
        self.cell, self.c, self.mix = cell, cell.config, cell.traffic
        self.cfg = common.program_config(self.c)
        self.batch = self.mix["batch"]
        self.images_done = 0
        self.steps_done = 0
        self.pending = collections.deque()
        self.k = 0

    # -- set-up -------------------------------------------------------------

    def setup(self):
        from repro.launch import steps
        self.params = common.seeded_params(self.c, self.cfg, self.cell.seed)
        n = self.batch * self.mix["batches"]
        staged = weights.images(self.c, self.cell.seed, n)
        self.inputs = [staged[i * self.batch:(i + 1) * self.batch]
                       for i in range(self.mix["batches"])]
        self.fwd = jax.jit(steps.build_prefill_step(self.cfg))
        self.outputs = [None] * len(self.inputs)
        jax.block_until_ready(self.fwd(self.params,
                                       {"images": self.inputs[0]}))

    # -- window -------------------------------------------------------------

    def step(self):
        k = self.k
        out = self.fwd(self.params, {"images": self.inputs[k]})
        self.outputs[k] = out
        self.k = (k + 1) % len(self.inputs)
        self.pending.append(out)
        while len(self.pending) >= IN_FLIGHT:
            self._finish()

    def _finish(self):
        self.pending.popleft().block_until_ready()
        self.images_done += self.batch
        self.steps_done += 1

    def drain(self):
        while self.pending:
            self._finish()

    def counters(self):
        return {"images": self.images_done, "steps": self.steps_done}

    def end_to_end(self, t0, t1):
        return {"images_per_s": self.images_done / (t1 - t0)}

    def attempted(self):
        return self.images_done, 0

    def notes(self):
        return [f"{self.steps_done} steps of {self.batch} images"]

    def scopes(self):
        text = self.fwd.lower(self.params,
                              {"images": self.inputs[0]}).compile().as_text()
        from chip_bench.trace import hlo_scopes
        return hlo_scopes(text)

    # -- check --------------------------------------------------------------

    def free(self):
        """Keep only what the check reads: the inputs and the logits of the
        window's last pass over each staged batch, on the host."""
        self.logits = np.concatenate([np.asarray(o) for o in self.outputs])
        self.images = np.concatenate([np.asarray(x) for x in self.inputs])
        self.params = jax.device_get(self.params)
        del self.inputs, self.outputs, self.fwd, self.pending

    def check(self, control=None):
        """Numbers of a seeded sample of images against the float32
        reference, computed eight images at a time, and the spike density
        of each layer there. With ``control`` the same numbers of the
        control's logits for the same images: ``"int8"`` the program's
        int8-weight path, another dtype the reference computed in it."""
        rng = weights.seeded_rng(self.cell.seed, 1)
        n = self.mix["check"]
        if n % 8:
            raise ValueError(f"check {n} images: not a multiple of 8")
        idx = np.sort(rng.choice(len(self.images), n, replace=False))
        imgs = self.images[idx]
        params = jax.device_put(self.params)
        want, dens = self._reference(params, imgs, "float32")
        got = self.logits[idx] if control is None \
            else self._control(params, imgs, control)
        top = got.argmax(-1)
        gaps = want.max(-1) - want[np.arange(n), top]
        scale = np.sqrt(np.mean(want ** 2))
        numbers = {
            "top1_gap": float(gaps.max()),
            "top1_mismatch": float(np.mean(top != want.argmax(-1))),
            "logit_rel_rms": float(np.sqrt(np.mean((got - want) ** 2))
                                   / scale),
            "mean_logit_rel_err": float(np.sqrt(np.mean(
                (got - want).mean(axis=0) ** 2)) / scale),
        }
        return numbers, dict(zip(ref.density_names(self.c), dens.tolist()))

    def _reference(self, params, imgs, compute):
        fwd = jax.jit(functools.partial(ref.forward, self.c,
                                        compute=compute))
        out = [fwd(params, jnp.asarray(imgs[i:i + 8]))
               for i in range(0, len(imgs), 8)]
        return (np.concatenate([np.asarray(lg) for lg, _ in out]),
                np.mean([np.asarray(d) for _, d in out], axis=0))

    def _control(self, params, imgs, control):
        if control != "int8":
            return self._reference(params, imgs, control)[0]
        from repro.launch import steps
        from repro.quant import quantize_tree
        cfg = self.cfg.replace(engine=self.cfg.engine.replace(
            weights="int8"))
        fwd = jax.jit(steps.build_prefill_step(cfg))
        return np.asarray(fwd(quantize_tree(params, "int8"),
                              {"images": jnp.asarray(imgs)}))
