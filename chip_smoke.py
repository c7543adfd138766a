"""Chip smoke test: a full-width fedforecast-100m federation on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: sharded sinks only

One chip: three silos run the FL-APU lifecycle through ``Consortium`` at
the full published width of fedforecast-100m (12 layers, d_model 768,
vocab 4096; random weights from ``--seed``): negotiate -> contract ->
job -> data validation -> rounds -> evaluate -> deploy -> predict. The
first job folds fp32 secure-aggregation updates (``MaskedF32Sink`` ->
``masked_sum``), the second secure int8 updates (``ModularSink`` ->
``masked_dequant_reduce``). Then each of the four server combine
kernels runs at the model's packed size T against its ``ref.py`` oracle.

``--chips 4``: each streaming sink folds the same full-T rows once over
the four-chip aggregation mesh and once on one chip; the two results
must agree (fp32 to rounding, integer planes bit for bit).

Earlier lines print one JSON record per phase. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without this repo's ``src/`` beside it, the script
exits non-zero and prints no result. The compile cache is placed by
``repro.launch.compile_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "fedforecast-100m"
ORGS = ["windco", "solarx", "gridpower"]
T_FULL = 116_411_136          # packed fp32 parameter count at full width
PLANES = {"secure_f32": {"secure_aggregation": True},
          "secure_int8": {"secure_aggregation": True, "compression": "int8"}}
F32_RTOL = 1e-5


def emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileStats:
    """Counts persistent-cache hits and misses and sums the backend
    compile seconds JAX reports, through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.events = defaultdict(int)
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s,
                "cache_hits": self.events["cache_hits"],
                "cache_misses": self.events["cache_misses"]}


def require_tpu(n_chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX runs on "
                 f"{devices[0].platform!r})")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: {n_chips} chips asked for, "
                 f"{len(devices)} found")
    return devices


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def bytes_in_use() -> int:
    import jax
    return int((jax.devices()[0].memory_stats() or {}).get("bytes_in_use",
                                                            -1))


# --------------------------------------------------------------------------
# board child: the socket transport's board process must not take the chip
# --------------------------------------------------------------------------
def check_board_child():
    from repro.core.transport import SocketTransport, SocketTransportServer
    t0 = time.perf_counter()
    with SocketTransportServer() as server:
        transport = SocketTransport((server.host, server.port))
        try:
            transport.put("probe", b"chip-smoke", "server")
            got = transport.get("probe")
        finally:
            transport.close()
    if got != b"chip-smoke":
        raise RuntimeError(f"board child round trip returned {got!r}")
    emit("board_child", seconds=time.perf_counter() - t0)


def time_host_crypto(t: int, seed: int):
    """One update-sized post through the Communicator's cipher: this is
    host time that every upload and download in the rounds pays. Beside
    the whole encrypt and decrypt, the cipher's pieces on the same
    buffer: the SHAKE-256 keystream, the XOR and the HMAC-SHA256."""
    import hashlib
    import hmac

    import numpy as np

    from repro.core import crypto
    buf = np.random.default_rng(seed).standard_normal(t, np.float32)
    plain_in = buf.tobytes()
    key = crypto.derive_key(b"chip-smoke", "channel")
    t0 = time.perf_counter()
    blob = crypto.encrypt(key, plain_in)
    t1 = time.perf_counter()
    plain = crypto.decrypt(key, blob)
    t2 = time.perf_counter()
    if bytes(plain) != plain_in:
        raise RuntimeError("crypto round trip changed the payload")
    t3 = time.perf_counter()
    stream = hashlib.shake_256(key + bytes(16)).digest(len(plain_in))
    t4 = time.perf_counter()
    np.bitwise_xor(np.frombuffer(plain_in, np.uint8),
                   np.frombuffer(stream, np.uint8))
    t5 = time.perf_counter()
    hmac.new(key, plain_in, hashlib.sha256).digest()
    t6 = time.perf_counter()
    emit("host_crypto", bytes=len(plain), encrypt_s=t1 - t0,
         decrypt_s=t2 - t1, keystream_s=t4 - t3, xor_s=t5 - t4,
         hmac_s=t6 - t5)


# --------------------------------------------------------------------------
# federated jobs through the normal entry points
# --------------------------------------------------------------------------
def run_job(con, plane: str, *, datasets, schema, reduced: bool,
            rounds: int, local_steps: int, batch_size: int, stats):
    from repro.core.reporting import run_report

    before = stats.snapshot()
    t0 = time.perf_counter()
    contract = con.negotiate({
        "arch": ARCH, "reduced": reduced, "rounds": rounds,
        "local_steps": local_steps, "batch_size": batch_size, "lr": 1e-3,
        "data_schema": schema.to_dict(), **PLANES[plane]})
    job = con.server.job_creator.from_contract(contract)
    run_id = con.start(job, datasets)
    setup_s = time.perf_counter() - t0

    t1 = time.perf_counter()
    phase = con.run_to_completion()
    run_s = time.perf_counter() - t1
    if phase != "done":
        raise RuntimeError(f"{plane}: run ended in phase {phase!r}")
    failed = [rid for rid, e in con.scheduler.entries.items()
              if e.state == "failed"]
    if failed:
        raise RuntimeError(f"{plane}: failed admissions {failed}")

    rep = run_report(con.server.metadata, run_id)
    losses = [float(x) for x in rep["loss_curve"]]
    if len(losses) != rounds or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{plane}: bad loss curve {losses}")
    if not con.server.metadata.verify_chain():
        raise RuntimeError(f"{plane}: metadata chain broken")

    node = con.nodes[0]
    prompt = datasets[0].batch(1)["tokens"][:, :16]
    t2 = time.perf_counter()
    pred = node.predict(prompt, n_steps=5)
    predict_s = time.perf_counter() - t2
    if pred.shape != (1, 5) or pred.min() < 0 or pred.max() >= schema.vocab:
        raise RuntimeError(f"{plane}: bad prediction {pred.tolist()}")

    # host seconds by span name over the job (nested spans overlap)
    span_s = defaultdict(float)
    for sp in con.telemetry.spans(run_id, include_open=False):
        span_s[sp.name] += sp.t1 - sp.t0
    after = stats.snapshot()
    emit(f"job_{plane}", run_id=run_id, setup_s=setup_s, run_s=run_s,
         predict_s=predict_s, span_s=dict(sorted(span_s.items())),
         losses=losses, prediction=pred[0].tolist(),
         **{k: after[k] - before[k] for k in after})


def run_federation(*, reduced: bool, vocab: int, seq_len: int,
                   batch_size: int, rounds: int, local_steps: int,
                   seed: int, stats):
    """Both jobs, one after the other, on one consortium."""
    from repro.core import Consortium, DataSchema, Telemetry
    from repro.data import make_silo_datasets

    con = Consortium(ORGS, seed=seed,
                     telemetry=Telemetry(enabled=True, recorder_cap=1 << 16))
    schema = DataSchema(vocab=vocab, seq_len=seq_len)
    datasets = make_silo_datasets(len(ORGS), vocab=vocab, seq_len=seq_len,
                                  seed=seed + 1)
    for plane in PLANES:
        run_job(con, plane, datasets=datasets, schema=schema,
                reduced=reduced, rounds=rounds, local_steps=local_steps,
                batch_size=batch_size, stats=stats)


# --------------------------------------------------------------------------
# server combine kernels at full T against their oracles
# --------------------------------------------------------------------------
def _kernel_in_hlo(op, *args, **kw) -> bool:
    return "tpu_custom_call" in op.lower(*args, **kw).as_text()


def _compare(name, op, ref, args, kw, *, exact: bool):
    import jax
    import jax.numpy as jnp

    if not _kernel_in_hlo(op, *args, **kw):
        raise RuntimeError(f"{name}: no Pallas kernel in the lowered op")
    got = op(*args, **kw).block_until_ready()
    t0 = time.perf_counter()
    got = op(*args, **kw).block_until_ready()
    warm_s = time.perf_counter() - t0
    want = jax.jit(ref)(*args)
    if exact:
        err = int(jnp.sum(got != want))
        ok = err == 0
    else:
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        ok = err <= F32_RTOL
    emit("kernel", name=name, shape=list(args[0].shape),
         warm_call_s=warm_s, bytes_in_use=bytes_in_use(),
         **({"mismatches": err} if exact else {"rel_err": err}))
    if not ok:
        raise RuntimeError(f"{name}: kernel disagrees with its oracle "
                           f"({err})")


def check_kernels(t: int, *, n: int, n_corrected: int, seed: int):
    import jax
    import jax.numpy as jnp

    from repro.kernels.compressed_agg import ops as comp_ops
    from repro.kernels.compressed_agg import ref as comp_ref
    from repro.kernels.secure_agg import ops as sec_ops
    from repro.kernels.secure_agg import ref as sec_ref

    chunk = comp_ops.CHUNK
    tc = t + (-t) % chunk      # the dequant pair takes CHUNK-padded rows
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    # generated under jit: eagerly, random bits at (8, T) stage three
    # more (8, T) uint32 temporaries and overflow the chip
    normal = jax.jit(jax.random.normal, static_argnums=(1,))
    w = jax.random.uniform(k[0], (n,), jnp.float32, 0.5, 2.0)

    x = normal(k[1], (n, t))
    _compare("masked_sum", sec_ops.masked_sum, sec_ref.masked_sum_ref,
             (x, w), {}, exact=False)
    del x
    x, c = normal(k[2], (n_corrected, t)), normal(k[3], (n_corrected, t))
    _compare("masked_sum_corrected", sec_ops.masked_sum_corrected,
             sec_ref.masked_sum_corrected_ref, (x, c, w[:n_corrected]), {},
             exact=False)
    del x, c
    q = jax.jit(lambda key: jax.random.randint(
        key, (n, tc), -127, 128, jnp.int32).astype(jnp.int8))(k[4])
    s = jax.random.uniform(k[5], (n, tc // chunk), jnp.float32, 1e-4, 1e-2)
    _compare("dequant_reduce", comp_ops.dequant_reduce,
             comp_ref.dequant_reduce_ref, (q, s, w), {}, exact=False)
    del q, s
    z = jax.jit(lambda key: jax.random.bits(key, (n, tc), jnp.uint32))(k[1])
    grid = jnp.full((tc // chunk,), 1e-4, jnp.float32)
    _compare("masked_dequant_reduce", comp_ops.masked_dequant_reduce,
             lambda z_, g_: comp_ref.masked_dequant_reduce_ref(z_, g_, 16),
             (z, grid), {"modulus_bits": 16}, exact=True)


# --------------------------------------------------------------------------
# four chips: the mesh-sharded streaming sinks against one chip
# --------------------------------------------------------------------------
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def _fold_collectives(mesh, t: int, n: int) -> list:
    """Collectives in the compiled sharded fp32 fold at (n, t)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.sharding import agg
    tp = t + agg._t_pad(t, mesh.shape[agg.AXIS], agg.LANE)
    x = jax.ShapeDtypeStruct((n, tp), jnp.float32,
                             sharding=NamedSharding(mesh, P(None, agg.AXIS)))
    w = jax.ShapeDtypeStruct((n,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    text = agg._masked_sum_sharded(mesh, None).lower(x, w).compile().as_text()
    return [op for op in COLLECTIVES if op in text]


def check_mesh_sinks(t: int, *, n: int, seed: int):
    import jax
    import numpy as np

    from repro.core import streaming
    from repro.core.compression import CHUNK
    from repro.sharding import agg

    mesh = agg.agg_mesh()
    if mesh is None or mesh.devices.size < 2:
        raise RuntimeError("no aggregation mesh over the chips")
    rng = np.random.default_rng(seed)
    tc = t + (-t) % CHUNK

    def both(make, feed):
        out = []
        for m in (mesh, None):
            sink = make(m)
            t0 = time.perf_counter()
            feed(sink)
            res = sink.finalize()
            out.append((res, time.perf_counter() - t0))
        return out

    x = rng.standard_normal((n, t), np.float32)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    (a, a_s), (b, b_s) = both(
        lambda m: streaming.MaskedF32Sink(t, batch=n, mesh=m),
        lambda s: [s.fold(x[i], w[i]) for i in range(n)])
    f32_err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
    emit("mesh_sink", plane="masked_f32", rel_err=f32_err, mesh_s=a_s,
         single_s=b_s)
    del x

    q = rng.integers(-127, 128, (n, tc), dtype=np.int8)
    scales = rng.uniform(1e-4, 1e-2, (n, tc // CHUNK)).astype(np.float32)
    (a, a_s), (b, b_s) = both(
        lambda m: streaming.QuantSink(tc, batch=n, mesh=m),
        lambda s: [s.fold(f"c{i}", q[i], scales[i], float(w[i]))
                   for i in range(n)])
    int8_err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
    emit("mesh_sink", plane="compressed_int8", rel_err=int8_err,
         mesh_s=a_s, single_s=b_s)
    del q

    z = rng.integers(0, 1 << 16, (n, tc), dtype=np.uint32)
    (a, a_s), (b, b_s) = both(
        lambda m: streaming.ModularSink(tc, mbits=16, grid=1e-4, batch=n,
                                        mesh=m),
        lambda s: [s.fold(z[i]) for i in range(n)])
    mismatches = int(np.sum(a != b))
    emit("mesh_sink", plane="masked_int", mismatches=mismatches,
         mesh_s=a_s, single_s=b_s)

    collectives = _fold_collectives(mesh, t, n)
    emit("mesh_fold", collectives=collectives,
         peak_bytes_per_device=[peak_bytes(d) for d in jax.devices()])
    if f32_err > F32_RTOL or int8_err > F32_RTOL or mismatches:
        raise RuntimeError("sharded sinks disagree with one chip")
    if collectives:
        raise RuntimeError(f"sharded fold has collectives {collectives}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, SRC)

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    devices = require_tpu(args.chips)
    stats = CompileStats()
    dev = devices[0]
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), cache_dir=cache_dir)

    t0 = time.perf_counter()
    if args.chips == 4:
        check_mesh_sinks(T_FULL, n=8, seed=args.seed)
    else:
        check_board_child()
        time_host_crypto(T_FULL, args.seed)
        run_federation(reduced=False, vocab=4096, seq_len=512, batch_size=8,
                       rounds=2, local_steps=2, seed=args.seed, stats=stats)
        gc.collect()               # drop the silos' device buffers
        check_kernels(T_FULL, n=8, n_corrected=4, seed=args.seed)
    emit("total", seconds=time.perf_counter() - t0,
         peak_bytes_in_use=peak_bytes(dev), **stats.snapshot())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
