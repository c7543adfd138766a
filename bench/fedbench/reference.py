"""Plain reference of one federation's rounds: each silo's local AdamW
steps from the round's global, the client encode of its update, and the
server's FedAvg — the semantics a configuration states, written out
without the program.

The model itself comes from the configuration's reference module
(``bench/configs/<reference>.py``: ``init`` and ``loss``). The update
plane follows ``federation.update_dtype``:

* ``float32`` — each silo posts its trained weights scaled by its FedAvg
  weight; pairwise masks cancel in the sum, so the reference adds none.
  The committed global is the weighted mean.
* ``int8`` (or any ``intN``) — each silo posts its weighted delta on the
  cohort-common grid ``quant_range / (2**(N-1) - 1)``, clipped to
  ``±qmax`` steps; the reference keeps the unrounded value on that range
  (stochastic rounding is unbiased, its noise is what the limit allows)
  and carries the clip error as the error-feedback residual.

``variant`` plants a departure for the control and the faults:
``compute_below`` computes every matmul one precision below the stated
``compute_dtype`` (bfloat16 for float32; for bfloat16, fp8: e4m3
operands and e5m2 gradients),
``wire_bf16`` rounds the posted float32 update to bfloat16, ``wire_int4``
quantizes the delta on a 4-bit grid (round to nearest), ``half_batch``
trains on the first half of every batch, ``altered_update`` doubles one
silo's posted delta.

Device memory. With T the model's parameters, ``follow`` holds at most
5T float32 on the device, plus one step's activations: the FedAvg
accumulator, and the silo's params, AdamW moments and gradients inside
its step. The round's global and the int8 residuals wait on the host as
numpy; each silo trains from a fresh device copy of the global, which
its first step consumes (the step donates params and moments), and the
int8 encode takes another copy once the moments are gone. A copy between
host and device is exact, and the accumulator's sums are the same ops on
the same device, so the committed globals are those a reference keeping
everything on the device commits, bit for bit.
"""
from __future__ import annotations

import importlib.util
import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VARIANTS = ("sound", "compute_below", "wire_bf16", "wire_int4", "half_batch",
            "altered_update")
BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def load_model_module(cfg: dict):
    """The configuration's plain model reference, by its file name."""
    path = os.path.join(BENCH, "configs", cfg["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_ref_" + cfg["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def weights_key(seed: int):
    """The key the benchmark's weights are drawn from."""
    word = np.random.SeedSequence([int(seed), 7]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def host(tree):
    return jax.tree.map(lambda a: np.array(a, np.float32), tree)


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def flat(tree) -> dict:
    """``{"stack/attn/wq": array, ...}`` — the comparison's leaf names."""
    return dict(zip(leaf_paths(tree), jax.tree.leaves(tree)))


def adamw_step(grads, state, params, *, lr, b1, b2, eps, weight_decay,
               max_grad_norm):
    """One AdamW step as the configuration states it: the gradient
    clipped to a global norm of ``max_grad_norm``, float32 moments with
    bias correction, decoupled weight decay. Returns ``(params, state,
    gradient norm)``."""
    norm = jnp.sqrt(sum(jnp.vdot(g, g) for g in jax.tree.leaves(grads)))
    clip = jnp.where(norm > max_grad_norm, max_grad_norm / norm, 1.0)
    t = state["count"] + 1
    fix1 = 1.0 - jnp.power(b1, t.astype(jnp.float32))
    fix2 = 1.0 - jnp.power(b2, t.astype(jnp.float32))

    def leaf(p, g, m, v):
        g = g.astype(jnp.float32) * clip
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        step = (m / fix1) / (jnp.sqrt(v / fix2) + eps) + weight_decay * p
        return p - lr * step, m, v

    out = jax.tree.map(leaf, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"m": pick(1), "v": pick(2), "count": t}, norm


class Reference:
    """One configuration's plain federation, driven round by round."""

    def __init__(self, cfg: dict, variant: str = "sound"):
        if variant not in VARIANTS:
            raise ValueError(f"unknown reference variant {variant!r}")
        self.cfg = cfg
        self.model = cfg["model"]
        if variant == "compute_below":
            self.model = dict(self.model, compute_dtype=BELOW[
                self.model["compute_dtype"]])
        self.fed = cfg["federation"]
        self.variant = variant
        self.mod = load_model_module(cfg)
        self._step = jax.jit(self._train_step, donate_argnums=(0, 1))
        self._norms = jax.jit(self._leaf_grad_norms)
        self._init = jax.jit(partial(self.mod.init, self.model))

    def init_params(self, key):
        return self._init(key)

    def _train_step(self, params, state, batch):
        if self.variant == "half_batch":
            batch = {k: a[: a.shape[0] // 2] for k, a in batch.items()}
        (loss, metrics), grads = jax.value_and_grad(
            partial(self.mod.loss, self.model), has_aux=True)(params, batch)
        opt = self.fed["adamw"]
        params, state, gnorm = adamw_step(
            grads, state, params, lr=self.fed["lr"], b1=opt["b1"],
            b2=opt["b2"], eps=opt["eps"], weight_decay=opt["weight_decay"],
            max_grad_norm=opt["max_grad_norm"])
        return params, state, {**metrics, "grad_norm": gnorm,
                               "lr": self.fed["lr"], "loss": loss}

    def _leaf_grad_norms(self, params, batch):
        grads = jax.grad(lambda p: partial(self.mod.loss, self.model)(
            p, batch)[0])(params)
        return jax.tree.map(jnp.linalg.norm, grads)

    def train_silo(self, base, batches):
        """Local AdamW steps from ``base``, a device copy that the first
        step consumes; returns ``(params, last loss, per-leaf gradient
        norms at the first step)``."""
        first = self._norms(base, {"tokens": jnp.asarray(batches[0])})
        params = base
        state = {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                   base),
                 "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                   base),
                 "count": jnp.zeros((), jnp.int32)}
        loss = None
        for tokens in batches:
            params, state, metrics = self._step(
                params, state, {"tokens": jnp.asarray(tokens)})
            loss = metrics["loss"]
        return params, float(loss), {k: float(v) for k, v in
                                     flat(first).items()}

    def _int_bits(self) -> int:
        if self.variant == "wire_int4":
            return 4
        dt = self.fed["update_dtype"]
        return int(dt[3:]) if dt.startswith("int") else 0

    def silo_turn(self, i, glob, batches, residual):
        """Silo ``i``'s local steps from the host global ``glob`` and the
        encode of its update. Returns ``(post, last loss, first gradient
        norms, residual)``: the post on the device, the int8 plane's
        error-feedback residual on the host (``None`` on float32)."""
        params, loss, gnorms = self.train_silo(jax.device_put(glob), batches)
        bits = self._int_bits()
        if self.variant == "altered_update" and i == 0:
            base = jax.device_put(glob)
            params = jax.tree.map(lambda p, g: g + 2.0 * (p - g),
                                  params, base)
            del base
        if not bits:
            if self.variant == "wire_bf16":
                params = jax.tree.map(
                    lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                    params)
            return params, loss, gnorms, None
        delta = jax.tree.map(jnp.subtract, params, jax.device_put(glob))
        del params
        target = delta if residual is None else jax.tree.map(
            jnp.add, delta, jax.device_put(residual))
        del delta
        qmax = (1 << (bits - 1)) - 1
        grid = self.fed["quant_range"] / qmax
        if self.variant == "wire_int4":
            sent = jax.tree.map(lambda t: jnp.clip(
                jnp.round(t / grid), -qmax, qmax) * grid, target)
        else:
            sent = jax.tree.map(lambda t: jnp.clip(
                t, -qmax * grid, qmax * grid), target)
        return sent, loss, gnorms, host(jax.tree.map(jnp.subtract, target,
                                                     sent))

    def run_round(self, glob, silo_batches, residuals=None):
        """One synchronous round over the cohort.

        ``glob``: the round's global, on the host. ``silo_batches``: per
        silo (cohort order), the token batches of its local steps.
        Returns ``(new_global, losses, first_grad_norms, residuals)``,
        the global and the residuals on the host; every silo carries the
        same FedAvg weight (equal example budgets), so the weighted mean
        is the plain mean.
        """
        n = len(silo_batches)
        residuals = residuals or [None] * n
        acc = jax.tree.map(jnp.zeros_like, glob)
        losses, gnorms, new_res = [], [], []
        for i, batches in enumerate(silo_batches):
            post, loss, gn, res = self.silo_turn(i, glob, batches,
                                                 residuals[i])
            acc = jax.tree.map(jnp.add, acc, post)
            del post
            losses.append(loss)
            gnorms.append(gn)
            new_res.append(res)
        mean = jax.tree.map(lambda a: a / np.float32(n), acc)
        del acc
        if self._int_bits():
            mean = jax.tree.map(jnp.add, jax.device_put(glob), mean)
        return host(mean), losses, gnorms, new_res

    def follow(self, seed: int, rounds_batches) -> dict:
        """The rounds from the seed's weights; ``rounds_batches``: per
        round, per silo, the token batches of its local steps."""
        glob = host(self.init_params(weights_key(seed)))
        out = {"init": glob, "globals": [], "losses": [], "grad_norms": []}
        residuals = None
        for batches in rounds_batches:
            glob, losses, gnorms, residuals = self.run_round(
                glob, batches, residuals)
            out["globals"].append(glob)
            out["losses"].append(losses)
            out["grad_norms"].extend(gnorms)
        return out
