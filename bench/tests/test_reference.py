"""The plain reference's rounds on the CPU at the tiny size.

Its outputs are pinned bit for bit to ``Pinned``: a frozen copy of the
rounds as they ran when the global, the residuals and the training
state all stayed on the device and the step donated nothing. Where an
array waits between silos moves no bit, since a copy between host and
device is exact. Digests would pin less well: the CPU backend's last
bits depend on the host's instruction set. Two rounds of the tiny
rehearsal's traffic, per variant.

Besides, the budget's two rules: the start copy a silo trains from is
donated to its first step, and the global and the int8 residuals wait
on the host between silos.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(BENCH, "tests")
sys.path.insert(0, BENCH)

from fedbench import reference  # noqa: E402

SEED = 2**31 + 12345
ROUNDS = 2


def tiny(config: str, traffic: str):
    from fedbench import spec
    with open(os.path.join(HERE, config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, traffic + ".json")) as f:
        trf = json.load(f)
    return spec.Cell(name=f"{config}.{traffic}", config=cfg, traffic=trf,
                     chips=1)


def batches(cell) -> list:
    import control
    return control.batches_by_round(cell, SEED, ROUNDS)


class Pinned(reference.Reference):
    """The rounds with every array on the device and no donation."""

    def __init__(self, cfg, variant="sound"):
        super().__init__(cfg, variant)
        self._step = jax.jit(self._train_step)

    def train_silo(self, base, batches):
        params = base
        state = {"m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                   base),
                 "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                   base),
                 "count": jnp.zeros((), jnp.int32)}
        first = self._norms(base, {"tokens": jnp.asarray(batches[0])})
        loss = None
        for tokens in batches:
            params, state, metrics = self._step(
                params, state, {"tokens": jnp.asarray(tokens)})
            loss = metrics["loss"]
        return params, float(loss), {k: float(v) for k, v in
                                     reference.flat(first).items()}

    def run_round(self, glob, silo_batches, residuals=None):
        n = len(silo_batches)
        residuals = residuals or [None] * n
        bits = self._int_bits()
        acc = jax.tree.map(jnp.zeros_like, glob)
        losses, gnorms, new_res = [], [], []
        for i, batches in enumerate(silo_batches):
            params, loss, gn = self.train_silo(glob, batches)
            losses.append(loss)
            gnorms.append(gn)
            if self.variant == "altered_update" and i == 0:
                params = jax.tree.map(lambda p, g: g + 2.0 * (p - g),
                                      params, glob)
            delta = jax.tree.map(jnp.subtract, params, glob)
            if bits:
                target = delta if residuals[i] is None else jax.tree.map(
                    jnp.add, delta, residuals[i])
                qmax = (1 << (bits - 1)) - 1
                grid = self.fed["quant_range"] / qmax
                if self.variant == "wire_int4":
                    sent = jax.tree.map(lambda t: jnp.clip(
                        jnp.round(t / grid), -qmax, qmax) * grid, target)
                else:
                    sent = jax.tree.map(lambda t: jnp.clip(
                        t, -qmax * grid, qmax * grid), target)
                new_res.append(jax.tree.map(jnp.subtract, target, sent))
                acc = jax.tree.map(jnp.add, acc, sent)
            else:
                post = params
                if self.variant == "wire_bf16":
                    post = jax.tree.map(
                        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                        post)
                acc = jax.tree.map(jnp.add, acc, post)
                new_res.append(None)
        mean = jax.tree.map(lambda a: a / np.float32(n), acc)
        new = (jax.tree.map(jnp.add, glob, mean) if bits else mean)
        return new, losses, gnorms, new_res

    def follow(self, seed, rounds_batches):
        glob = self.init_params(reference.weights_key(seed))
        out = {"init": reference.host(glob), "globals": [], "losses": [],
               "grad_norms": []}
        residuals = None
        for batches in rounds_batches:
            glob, losses, gnorms, residuals = self.run_round(
                glob, batches, residuals)
            out["globals"].append(reference.host(glob))
            out["losses"].append(losses)
            out["grad_norms"].extend(gnorms)
        return out


CASES = [("tiny-sec-f32", v) for v in
         ("sound", "compute_below", "wire_bf16", "altered_update")] + [
         ("tiny-sec-int8", v) for v in ("sound", "wire_int4", "half_batch")]


@pytest.mark.parametrize("config,variant", CASES)
def test_follow_is_pinned(config, variant):
    cell = tiny(config, "tiny-tail")
    rounds = batches(cell)
    got = reference.Reference(cell.config, variant).follow(SEED, rounds)
    want = Pinned(cell.config, variant).follow(SEED, rounds)
    for g, w in zip([got["init"], *got["globals"]],
                    [want["init"], *want["globals"]], strict=True):
        g, w = reference.flat(g), reference.flat(w)
        assert list(g) == list(w)
        for name in w:
            assert np.array_equal(g[name], w[name]), name
    assert got["losses"] == want["losses"]
    assert got["grad_norms"] == want["grad_norms"]


def test_start_copy_donated_and_arrays_wait_on_the_host():
    cell = tiny("tiny-sec-int8", "tiny-tail")
    ref = reference.Reference(cell.config)
    step, turn = ref._step, ref.silo_turn
    starts, turns = [], []

    def first_step(params, state, batch):
        out = step(params, state, batch)
        if int(out[1]["count"]) == 1:
            starts.append(all(a.is_deleted()
                              for a in jax.tree.leaves(params)))
        return out

    def watched(i, glob, silo_batches, residual):
        seen = [glob] + ([] if residual is None else [residual])
        turns.append(all(isinstance(a, np.ndarray)
                         for t in seen for a in jax.tree.leaves(t)))
        post, loss, norms, carried = turn(i, glob, silo_batches, residual)
        turns.append(all(isinstance(a, np.ndarray)
                         for a in jax.tree.leaves(carried)))
        return post, loss, norms, carried

    ref._step, ref.silo_turn = first_step, watched
    ref.follow(SEED, batches(cell))
    n_turns = ROUNDS * len(cell.config["federation"]["organizations"])
    assert starts == [True] * n_turns
    assert turns == [True] * (2 * n_turns)
