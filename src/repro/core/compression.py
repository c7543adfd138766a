"""Negotiated lossy update compression (paper §V Communicator: compressed
inter-organizational transfer; DESIGN.md §Compressed data plane).

Cross-silo updates cross WAN links between companies, where update size
directly bounds round cadence (Huang et al., *Cross-Silo Federated
Learning: Challenges and Opportunities*) — posting raw fp32 packed
buffers makes every round pay 4 bytes per parameter per silo, and zlib
on weight bytes is hopeless (crypto.py's auto probe exists precisely to
skip it). This module adds the lossy stage the Communicator promises,
as a *governance-negotiated* job decision (``FLJob.compression``): both
sides of the wire agree on the scheme through the cockpit like any
other contract parameter, and the choice lands on the provenance chain
with the rest of the job.

Two schemes over the packed (T,) fp32 delta buffer (``core.packing``):

``topk``  — magnitude sparsification: keep the ``compression_ratio``
    fraction of largest-|x| coordinates as (int32 index, f32 value)
    pairs. Wire cost ~ 8 bytes * k vs 4 bytes * T.
``int8``  — per-chunk stochastic quantization: one symmetric f32 scale
    per ``CHUNK`` (1024) floats, values stochastically rounded to
    ``quant_bits``-bit integers stored as int8. Stochastic rounding
    (floor(x/s + u), u ~ U[0,1)) keeps the quantizer unbiased; the
    per-chunk scale bounds the per-element error by one quant step of
    the *local* chunk range. The quantized bytes ride the wire
    entropy-coded (zlib over the int8 stream — the standard
    quantize-then-entropy-code pipeline; real update streams sit at
    ~7.3 bits/value, so this claws back the last few percent the
    Communicator's auto probe rightly refuses to chase on the whole
    encrypted blob). Wire cost ~ 0.93 bytes/value + T/256 scale bytes.

Error feedback (Seide et al.; Karimireddy et al., *Error Feedback Fixes
SignSGD*): lossy compression alone biases the update direction — top-k
silently drops 90% of the mass every round. Each client therefore keeps
the residual ``e_t = target_t - decompress(compress(target_t))`` where
``target_t = delta_t + e_{t-1}``, and compresses the *residual-corrected*
delta. The invariant is telescoping: the sum of everything the server
ever decompressed equals the sum of the true deltas minus the current
residual, so nothing is lost, only delayed — sync and async convergence
track the uncompressed twin (tests/test_compression.py,
benchmarks/bench_compression.py).

The server side reduces a cohort of posted wire messages in one pass
(``reduce_compressed``): int8 cohorts go through the fused Pallas
dequantize-scale-accumulate kernel (``kernels/compressed_agg``, jnp
oracle off the TPU); top-k cohorts scatter-add their weighted
(index, value) pairs into the dense (T,) result — never materializing
per-client dense buffers.

Composable privacy (DESIGN.md §Composable privacy): fp32 pairwise masks
do NOT survive lossy coding (a mask only cancels if both endpoints
transmit it bit-exactly), but masks drawn over the *quantized integer*
domain do — ``masked_int8`` quantizes the weighted, error-feedback
corrected delta onto a cohort-common fixed grid (per-client adaptive
scales cannot be applied after a modular sum), widens to uint32, and
adds PRG residues mod ``2**mask_modulus_bits`` that cancel *exactly*
under the server's modular sum (``reduce_masked``). An optional DP
stage L2-clips the weighted buffer and adds Gaussian noise in the
integer domain before masking. ``topk`` stays incompatible with secure
aggregation: its index sets leak the update support (jobs.py
compatibility matrix).
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Optional, Sequence

import numpy as np

from repro.core.secure_agg import int_mask_offset, mask_modulus_bits
from repro.kernels.compressed_agg.ops import CHUNK

SCHEMES = ("none", "topk", "int8")

# cohort-common fixed quantization grid for masked int8 rounds
# (half-range of representable deltas; FLJob.quant_range overrides).
# Sized for the reduced-arch per-round per-coordinate delta magnitudes
# observed in benchmarks/bench_compression.py — anything the grid clips
# is carried forward by error feedback, never lost.
DEFAULT_QUANT_RANGE = 0.02


def _qmax(bits: int) -> int:
    return (1 << (bits - 1)) - 1


def compress(buf, scheme: str, *, ratio: float = 0.1, bits: int = 8,
             rng: Optional[np.random.Generator] = None,
             grid: float = 0.0) -> Dict:
    """Compress a packed (T,) fp32 buffer into a wire dict (msgpack-able
    via ``core.serialization``; every field is a scalar or ndarray).

    ``grid > 0`` pins the int8 path to a *fixed* quantization step of
    ``grid`` for every chunk instead of the adaptive per-chunk scale —
    the grid masked rounds must share cohort-wide, exposed here so a
    plain compressed twin can quantize identically to its masked twin
    (twin-equivalence testing, tests/test_composable_privacy.py).
    """
    x = np.asarray(buf, np.float32).reshape(-1)
    t = x.size
    if scheme == "topk":
        k = max(1, int(round(ratio * t)))
        idx = np.argpartition(np.abs(x), t - k)[t - k:]
        idx = np.sort(idx).astype(np.int32)     # sorted: locality + determinism
        return {"scheme": "topk", "size": t, "idx": idx,
                "val": x[idx].astype(np.float32)}
    if scheme == "int8":
        qmax = _qmax(int(bits))
        pad = (-t) % CHUNK
        xp = np.pad(x, (0, pad)).reshape(-1, CHUNK)
        if grid and grid > 0:
            scales = np.full(xp.shape[0], np.float32(grid), np.float32)
        else:
            scales = (np.abs(xp).max(axis=1) / qmax
                      + 1e-12).astype(np.float32)
        y = xp / scales[:, None]
        u = (rng.random(y.shape, np.float32) if rng is not None
             else np.full_like(y, 0.5))          # no rng: round-to-nearest
        q = np.clip(np.floor(y + u), -qmax, qmax).astype(np.int8)
        return {"scheme": "int8", "size": t, "bits": int(bits),
                "qz": zlib.compress(q.reshape(-1)[:t].tobytes(), 6),
                "scales": scales}
    raise KeyError(f"unknown compression scheme {scheme!r}; "
                   f"known: {SCHEMES[1:]}")


def masked_compress(buf, *, bits: int = 8, grid: float,
                    client_id: str, cohort: Sequence[str],
                    pair_secret: bytes,
                    rng: Optional[np.random.Generator] = None,
                    dp_sigma: float = 0.0,
                    dp_rng: Optional[np.random.Generator] = None):
    """Masked-quantized wire coding (DESIGN.md §Composable privacy).

    Quantizes the (already weighted, already clipped) packed buffer onto
    the cohort-common fixed ``grid``, optionally adds integer-domain
    Gaussian DP noise (std ``dp_sigma`` in buffer units, rounded to grid
    steps, clipped to the 2*qmax headroom ``mask_modulus_bits`` budgets
    for), widens, and adds this client's pairwise mask residues mod
    ``2**mbits``. Returns ``(msg, deq)`` where ``deq`` is the (T,) f32
    dequantization of the *clean* (pre-noise, pre-mask) stream — the
    error-feedback residual must absorb clip+quantization error only;
    folding the noise into the residual would let the noise telescope
    away across rounds, silently cancelling the DP guarantee.

    The masked stream is NOT entropy-coded: residues mod M are uniform
    by construction (that is the point), so zlib would only add bytes —
    the wire rides as a raw uint16/uint32 array (2 or 4 B/value,
    depending on the cohort's modulus) and the crypto layer's
    auto-compression probe skips it.
    """
    x = np.asarray(buf, np.float32).reshape(-1)
    t = x.size
    qmax = _qmax(int(bits))
    pad = (-t) % CHUNK
    xp = np.pad(x, (0, pad))
    y = xp / np.float32(grid)
    u = (rng.random(y.shape, np.float32) if rng is not None
         else np.full_like(y, 0.5))
    q = np.clip(np.floor(y + u), -qmax, qmax).astype(np.int32)
    deq = (q[:t].astype(np.float32)) * np.float32(grid)
    if dp_sigma and dp_sigma > 0:
        if dp_rng is None:
            raise ValueError("dp_sigma > 0 needs a dp_rng")
        noise = np.rint(dp_rng.normal(0.0, float(dp_sigma) / float(grid),
                                      q.shape)).astype(np.int64)
        q = np.clip(q.astype(np.int64) + noise,
                    -2 * qmax, 2 * qmax).astype(np.int32)
    mbits = mask_modulus_bits(len(cohort), bits)
    offset = np.asarray(int_mask_offset(q.size, client_id, cohort,
                                        pair_secret, mbits), np.uint32)
    maskval = np.uint32((1 << mbits) - 1)
    z = (q.astype(np.uint32) + offset) & maskval   # int32 wrap = mod 2**32
    wire_dtype = np.uint16 if mbits <= 16 else np.uint32
    msg = {"scheme": "masked_int8", "size": t, "bits": int(bits),
           "mbits": int(mbits), "grid": float(grid),
           "z": z.astype(wire_dtype)}
    return msg, deq


def quantized_values(msg: Dict) -> np.ndarray:
    """Entropy-decode an int8 wire dict's quantized stream -> (T,) int8."""
    return np.frombuffer(zlib.decompress(msg["qz"]), np.int8)


def decompress(msg: Dict) -> np.ndarray:
    """Invert ``compress`` up to the lossy step: wire dict -> (T,) f32."""
    t = int(msg["size"])
    if msg["scheme"] == "masked_int8":
        raise ValueError(
            "a masked_int8 wire dict cannot be decompressed on its own: "
            "individual streams carry uncancelled pairwise masks (that is "
            "the privacy property); decode a full cohort via "
            "reduce_masked")
    if msg["scheme"] == "topk":
        out = np.zeros(t, np.float32)
        out[np.asarray(msg["idx"], np.int64)] = np.asarray(msg["val"],
                                                           np.float32)
        return out
    if msg["scheme"] == "int8":
        pad = (-t) % CHUNK
        qp = np.pad(quantized_values(msg),
                    (0, pad)).astype(np.float32).reshape(-1, CHUNK)
        return (qp * np.asarray(msg["scales"],
                                np.float32)[:, None]).reshape(-1)[:t]
    raise KeyError(f"unknown compression scheme {msg['scheme']!r}")


def wire_bytes(msg: Dict) -> int:
    """Nominal payload bytes of a wire dict (array bytes only — the
    msgpack/crypto framing is scheme-independent overhead)."""
    if msg["scheme"] == "topk":
        return msg["idx"].nbytes + msg["val"].nbytes
    if msg["scheme"] == "masked_int8":
        return msg["z"].nbytes        # uniform residues: no entropy coding
    return len(msg["qz"]) + msg["scales"].nbytes


def update_norm(msg: Dict) -> float:
    """l2 norm of one wire dict's decompressed delta (standalone/audit
    form; the server-side hot path gets the same numbers fused into the
    reduction via ``reduce_compressed(return_norms=True)``)."""
    if msg["scheme"] == "topk":
        return float(np.linalg.norm(np.asarray(msg["val"], np.float64)))
    if msg["scheme"] == "masked_int8":
        raise ValueError(
            "masked_int8 wire dicts carry no recoverable per-client "
            "norm: the stream is pairwise-masked (contribution scoring "
            "falls back to data_size for masked cohorts)")
    return float(np.linalg.norm(decompress(msg).astype(np.float64)))


def reduce_compressed(msgs: Sequence[Dict], weights: Sequence[float], *,
                      interpret: Optional[bool] = None,
                      return_norms: bool = False):
    """Weighted reduction of a cohort's wire messages -> dense (T,) f32.

    ``sum_i weights_i * decompress(msg_i)`` without ever stacking dense
    per-client buffers: int8 cohorts fold through the fused Pallas
    dequantize-scale-accumulate kernel in bounded batches (a streaming
    ``QuantSink``, ``core/streaming.py`` — O(T) accumulator memory, mesh-
    sharded over T when a mesh is up; jnp oracle off the TPU);
    top-k cohorts accumulate weighted (index, value) pairs into the
    output via fancy indexing (every message's indices are unique by
    construction, so no ``np.add.at``). Weights are used as given — the
    caller normalizes for a weighted mean, exactly like
    ``secure_agg.aggregate_masked_packed``.

    ``return_norms=True`` additionally returns each client's l2 delta
    norm (``(out, [norm_i])``), computed from the already-decoded wire
    arrays in the same pass — the Evaluation Coordinator's update-norm
    measure without a second entropy-decode of the cohort.
    """
    from repro.core import streaming
    return streaming.stream_reduce_compressed(
        msgs, weights, return_norms=return_norms, interpret=interpret)


def reduce_masked(msgs: Sequence[Dict], *,
                  corrections: Optional[Sequence] = None,
                  interpret: Optional[bool] = None) -> np.ndarray:
    """Decode a masked cohort's wire messages -> dense (T,) f32 *sum*.

    Streams the cohort's residue arrays into a (T',) uint32 accumulator
    (``core/streaming.py`` ``ModularSink``) in bounded batches — the
    (N, T') stack never materializes — then one fused masked-dequantize
    decode at the end (jnp oracle off the TPU). uint32 wrap-around
    preserves residues mod M = 2**mbits, so the fold is associative and
    the result is BIT-EXACT regardless of arrival order: the pairwise
    masks cancel exactly, the residue is centered and scaled by the
    cohort-common grid. No weights — clients pre-scale before
    quantization, exactly like the packed fp32 secure plane; the caller
    divides by the cohort's total weight.

    ``corrections``: per-survivor integer repair streams
    (``secure_agg.int_repair_correction``), aligned with ``msgs``,
    subtracted mod M before the decode after a dropout.
    """
    from repro.core import streaming
    return streaming.stream_reduce_masked(msgs, corrections=corrections,
                                          interpret=interpret)


def dp_sigma_total(epsilon: float, delta: float, clip: float) -> float:
    """Gaussian-mechanism noise std for one round's cohort *sum*:
    ``sigma = clip * sqrt(2 ln(1.25/delta)) / epsilon`` (Dwork & Roth,
    Thm A.1) — calibrated to the L2 sensitivity ``clip`` that per-silo
    clipping enforces. Distributed: each of N silos contributes
    ``sigma/sqrt(N)`` so the independent noises sum to std ``sigma``.
    Per-round guarantee; across R rounds the naive composition spends
    ``R * epsilon`` (recorded at run start on the provenance chain)."""
    if epsilon <= 0:
        raise ValueError("dp_epsilon must be > 0")
    if not 0 < delta < 1:
        raise ValueError("dp_delta must be in (0, 1)")
    return float(clip) * math.sqrt(2.0 * math.log(1.25 / float(delta))) \
        / float(epsilon)


class ErrorFeedback:
    """Client-side error-feedback compressor state (one per run).

    ``step(delta)`` compresses ``delta + residual`` and retains the new
    residual, so repeated rounds telescope: the sum of everything posted
    (after decompression) equals the sum of the true deltas minus the
    current residual — compression delays mass, never drops it. The
    int8 path draws its stochastic-rounding bits from a private
    generator seeded per client, so cohort members never share rounding
    noise. ``reset()`` drops the residual (hyperparameter restarts: the
    global model jumps back to init, making the carried residual stale).

    ``quant_range > 0`` pins the int8 grid to the cohort-common fixed
    step ``quant_range / qmax`` (required under masking; optional for
    plain int8, where it makes a run the bit-exact quantization twin of
    a masked run). ``dp`` — ``{"clip", "sigma_total", ...}`` — enables
    the per-silo DP stage of ``step_masked``: L2-clip the weighted
    buffer to ``clip``, then add ``sigma_total/sqrt(N)`` Gaussian noise
    in the integer domain, from a generator independent of the rounding
    stream. The noise is deliberately EXCLUDED from the residual: error
    feedback re-injecting it next round would telescope the noise away
    and void the guarantee.
    """

    def __init__(self, scheme: str, *, ratio: float = 0.1, bits: int = 8,
                 seed: int = 0, quant_range: float = 0.0,
                 dp: Optional[Dict] = None, dp_seed: int = 0):
        if scheme not in SCHEMES or scheme == "none":
            raise ValueError(f"ErrorFeedback needs a lossy scheme, "
                             f"got {scheme!r}")
        self.scheme = scheme
        self.ratio = float(ratio)
        self.bits = int(bits)
        self.quant_range = float(quant_range)
        self.dp = dict(dp) if dp else None
        self.rng = np.random.default_rng(seed)
        self.dp_rng = np.random.default_rng(dp_seed)
        self.residual: Optional[np.ndarray] = None

    @property
    def grid(self) -> float:
        qr = self.quant_range or DEFAULT_QUANT_RANGE
        return qr / _qmax(self.bits)

    def reset(self):
        self.residual = None

    def step(self, delta) -> Dict:
        target = np.asarray(delta, np.float32).reshape(-1)
        if self.residual is not None:
            target = target + self.residual
        msg = compress(target, self.scheme, ratio=self.ratio,
                       bits=self.bits, rng=self.rng,
                       grid=(self.grid if self.scheme == "int8"
                             and self.quant_range > 0 else 0.0))
        self.residual = target - decompress(msg)
        return msg

    def step_masked(self, delta, *, weight: float, client_id: str,
                    cohort: Sequence[str], pair_secret: bytes) -> Dict:
        """Masked twin of ``step`` (DESIGN.md §Composable privacy).

        Pipeline: residual-correct -> pre-scale by the FedAvg ``weight``
        (masks only cancel under equal server-side weights) -> [DP clip]
        -> fixed-grid quantize -> [DP noise, integer domain] -> mask mod
        2**mbits. The residual absorbs exactly what the *server-visible
        clean signal* lost — clip error plus quantization error, divided
        back by ``weight`` — so telescoping survives masking: the sum of
        everything the cohort decode ever recovered equals the sum of
        the true weighted deltas minus the current residuals (noise
        aside, which must not telescope).
        """
        target = np.asarray(delta, np.float32).reshape(-1)
        if self.residual is not None:
            target = target + self.residual
        w = float(weight) or 1.0
        buf = w * target
        dp_sigma = 0.0
        if self.dp is not None:
            nrm = float(np.linalg.norm(buf.astype(np.float64)))
            clip = float(self.dp["clip"])
            if nrm > clip:
                buf = buf * np.float32(clip / nrm)
            dp_sigma = float(self.dp["sigma_total"]) \
                / math.sqrt(max(1, len(cohort)))
        msg, deq = masked_compress(
            buf, bits=self.bits, grid=self.grid, client_id=client_id,
            cohort=cohort, pair_secret=pair_secret, rng=self.rng,
            dp_sigma=dp_sigma, dp_rng=self.dp_rng)
        self.residual = target - deq / np.float32(w)
        return msg


def make_error_feedback(job, noise_id: str) -> ErrorFeedback:
    """EF compressor for a job's negotiated scheme, seeded per silo so
    stochastic-rounding streams are independent across the cohort (full-id
    hash: ids sharing a suffix must not share rounding noise).

    ``noise_id`` must be the silo's *stable* identity (dataset/org), not
    the per-run registered device id: device ids rotate every run, and
    twin-equivalence (tests/test_composable_privacy.py) plus fixed-seed
    DP benches require a re-run over the same silo to draw the same
    streams. The DP noise stream gets its own generator, seeded from
    (job.dp_seed, noise_id) — deterministic per silo for fixed-seed
    smoke runs, independent of the rounding stream."""
    import hashlib
    seed = int.from_bytes(
        hashlib.sha256(noise_id.encode()).digest()[:8], "little")
    dp = None
    dp_seed = 0
    if getattr(job, "dp_epsilon", 0.0) > 0:
        dp = {"epsilon": job.dp_epsilon, "delta": job.dp_delta,
              "clip": job.dp_clip,
              "sigma_total": dp_sigma_total(job.dp_epsilon, job.dp_delta,
                                            job.dp_clip)}
        dp_seed = int.from_bytes(
            hashlib.sha256(f"{job.dp_seed}/{noise_id}".encode()
                           ).digest()[:8], "little")
    return ErrorFeedback(job.compression, ratio=job.compression_ratio,
                         bits=job.quant_bits, seed=seed,
                         quant_range=getattr(job, "quant_range", 0.0),
                         dp=dp, dp_seed=dp_seed)
