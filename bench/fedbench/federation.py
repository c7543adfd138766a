"""The system under test: one federation through the program's normal path.

``Federation`` builds a ``Consortium`` with telemetry on, negotiates the
configuration's contract under governance, creates the job from the
contract and starts it over the benchmark's silo streams; the global
model it starts from is the benchmark's, drawn from the seed. ``warm``
compiles what the cell's window will run through the program's own
process-wide callables. ``step`` is one ``FederationScheduler`` pass.
"""
from __future__ import annotations

import hashlib

import numpy as np

from fedbench.data import SiloStream


class Federation:
    def __init__(self, cfg: dict, traffic: dict, seed: int, init_params):
        import jax

        from repro.core import Consortium
        from repro.core.client import ClientConfig
        from repro.core.telemetry import Telemetry

        fed = cfg["federation"]
        self.cfg, self.traffic, self.fed = cfg, traffic, fed
        self.orgs = list(fed["organizations"])
        master = hashlib.sha256(f"fedbench/{seed}".encode()).digest()
        self.con = Consortium(self.orgs, seed=seed % (1 << 31),
                              master_key=master,
                              telemetry=Telemetry(enabled=True,
                                                  recorder_cap=1 << 20))
        self.tel = self.con.telemetry
        self.streams = [SiloStream(org, i, seed=seed,
                                   vocab=cfg["model"]["vocab"],
                                   seq_len=fed["seq_len"],
                                   alpha=traffic["alpha"])
                        for i, org in enumerate(self.orgs)]
        decisions = {
            "arch": fed["arch"], "reduced": fed["reduced"],
            "rounds": traffic["rounds"],
            "local_steps": traffic["local_steps"],
            "batch_size": fed["batch_size"], "lr": fed["lr"],
            "optimizer": fed["optimizer"],
            "outer_optimizer": fed["outer_optimizer"],
            "aggregation": fed["aggregation"], "protocol": fed["protocol"],
            "secure_aggregation": fed["secure_aggregation"],
            "compression": fed["compression"],
            "data_schema": {"vocab": cfg["model"]["vocab"],
                            "seq_len": fed["seq_len"]},
        }
        if fed["compression"] != "none":
            decisions["quant_range"] = fed["quant_range"]
        contract = self.con.negotiate(decisions)
        self.job = self.con.server.job_creator.from_contract(contract)
        self.run_id = self.con.start(
            self.job, self.streams,
            client_config=ClientConfig(eval_batches=traffic["eval_batches"]))
        self._install(init_params)
        self.t = int(sum(np.prod(a.shape)
                         for a in jax.tree.leaves(init_params)))

    @property
    def server(self):
        return self.con.server

    def _install(self, params):
        """Start the run from the benchmark's weights: same tree, same
        shapes and dtypes as the program's own initial model."""
        import jax
        run = self.server.run
        own = self.server.store.get(run.init_digest)
        mine = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        theirs = jax.tree.map(lambda a: (a.shape, a.dtype), own)
        if mine != theirs:
            raise RuntimeError(
                "the program's model does not have the configuration's "
                f"parameter tree:\n{theirs}\nvs\n{mine}")
        digest = self.server.store.put(params, "init", {
            "run_id": run.run_id, "round": -1, "origin": "benchmark seed"})
        run.global_digest = run.init_digest = digest

    # ------------------------------------------------------------------
    def warm(self, params):
        """Compile every program the window runs (``traffic["warm"]``),
        through the program's callables, at the window's shapes."""
        import jax
        import jax.numpy as jnp

        from repro.core import secure_agg, streaming
        from repro.core.client import shared_model, shared_step
        from repro.core.packing import PackedLayout, pack_pytree, \
            unpack_pytree

        job, fed = self.job, self.fed
        n = len(self.orgs)
        cohort = sorted(self.con.client_ids.values())
        dev = jax.tree.map(jnp.asarray, jax.tree.map(np.asarray, params))
        batch = {"tokens": jnp.asarray(self.streams[0].tokens(
            0, fed["batch_size"]))}
        for part in self.traffic["warm"]:
            if part == "train":
                opt, step = shared_step(job.arch, job.reduced, job.optimizer,
                                        job.lr)
                _, _, met = step(dev, opt.init(dev), batch)
                float(met["loss"])
            elif part == "eval":
                _, _, loss_jit = shared_model(job.arch, job.reduced)
                float(loss_jit(dev, batch)[0])
            elif part == "encode":
                buf, _ = pack_pytree(dev)
                if job.compression == "none":
                    np.asarray(secure_agg.mask_packed(
                        buf * jnp.float32(1.0), cohort[0], cohort,
                        b"warm"))
                else:
                    from repro.core.compression import CHUNK
                    size = self.t + (-self.t) % CHUNK
                    np.asarray(secure_agg.int_mask_offset(
                        size, cohort[0], cohort, b"warm",
                        secure_agg.mask_modulus_bits(n, job.quant_bits)))
            elif part == "combine":
                if job.compression == "none":
                    sink = streaming.MaskedF32Sink(self.t)
                    for _ in range(n):
                        sink.fold(np.zeros(self.t, np.float32))
                else:
                    from repro.core.compression import _qmax
                    sink = streaming.ModularSink(
                        self.t, grid=job.quant_range / _qmax(job.quant_bits),
                        mbits=secure_agg.mask_modulus_bits(n, job.quant_bits))
                    for _ in range(n):
                        sink.fold(np.zeros(self.t, np.uint16))
                total = sink.finalize()
                layout = PackedLayout.for_tree(params)
                jax.tree.map(np.asarray,
                             unpack_pytree(total / np.float32(n), layout))
            else:
                raise ValueError(f"unknown warm-up part {part!r}")
        del dev

    # ------------------------------------------------------------------
    def step(self):
        self.con.scheduler.step()

    def posts(self) -> list:
        """Closed ``client.post`` spans of this run, in end order."""
        return sorted((s for s in self.tel.spans(self.run_id,
                                                 include_open=False)
                       if s.name == "client.post"), key=lambda s: s.t1)

    def at(self, where: dict) -> bool:
        """Whether the run stands where ``where`` says a window opens."""
        run = self.server.run
        if run.phase != where["phase"] or run.round != where["round"]:
            return False
        if where.get("posted") == "all":
            done = {s.actor for s in self.posts()
                    if (s.attrs or {}).get("round") == run.round}
            return len(done) == len(self.orgs)
        return True

    def commit(self, rnd: int, max_ticks: int = 16):
        """Tick the server alone until round ``rnd`` is committed."""
        for _ in range(max_ticks):
            if any(h["round"] == rnd for h in self.server.run.history):
                return
            self.server.tick()
        raise RuntimeError(f"round {rnd} was not committed")

    def results(self) -> dict:
        """What the comparison needs, copied to the host: the start
        model, each committed global, each silo's reported loss, and the
        batch indices each silo's training drew, by round."""
        import jax
        run = self.server.run
        store = self.server.store
        host = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: np.array(a, np.float32), tree)
        rounds = sorted(h["round"] for h in run.history)
        train = [s for s in self.tel.spans(self.run_id, include_open=False)
                 if s.name == "client.train"]
        calls = {}
        for i, org in enumerate(self.orgs):
            cid = self.con.client_ids[org]
            for s in train:
                if s.actor == cid:
                    calls[(i, s.attrs["round"])] = [
                        k for t, k in self.streams[i].calls
                        if s.t0 <= t <= s.t1]
        return {
            "init": host(store.get(run.init_digest)),
            "globals": [host(store.get(h["digest"])) for h in run.history],
            "losses": [[h["train_losses"][self.con.client_ids[o]]
                        for o in self.orgs] for h in run.history],
            "rounds": rounds,
            "calls": calls,
        }
