"""Secure aggregation via pairwise additive masking (paper §VII Privacy).

Bonawitz-style: every *pair* of clients (i, j) derives a shared mask from a
pairwise secret; client i adds the mask, client j subtracts it, so the sum
over the full cohort telescopes to the true sum while every individual
update the server sees is masked. This preserves FL-APU's privacy property
— "clients should not trust the server" — without homomorphic encryption
(no offline HE library; same architectural seam, see DESIGN.md).

Packed data plane (DESIGN.md §Packed data plane): masking operates on one
contiguous fp32 buffer per client (``repro.core.packing``), not on a pytree
of leaves. All pairwise masks for the whole buffer are derived in a single
jit-compiled pass: the per-pair loop is unrolled at trace time so XLA fuses
every pair's counter-keyed PRG stream and the accumulate into ONE traversal
of the buffer — no (pairs, T) intermediate ever materializes. The
server-side reduction is one (N, T) weighted sum routed through the fused
Pallas kernel in ``repro.kernels.secure_agg`` (jnp oracle as the
interpret-mode fallback). The pytree-level ``mask_update`` /
``aggregate_masked`` entry points survive as thin pack -> packed-op ->
unpack wrappers.

Masks are uniform with standard deviation ``scale`` (range
``scale * [-sqrt(3), sqrt(3))`` — same per-pair mask std as the seed's
gaussian masks): per pair, a keyed integer hash (two rounds of the
lowbias32 mixer over ``counter ^ key``) is bit-twiddled into the f32
mantissa — one uint32 per element, fully vectorizable, ~30x faster than
the old per-leaf numpy loop on CPU hosts (BENCH_secure_agg.json). Like the seed's PCG64 this is a statistical PRG,
not a cryptographic one; ``prg="threefry"`` switches the mask stream to
``jax.random`` counter-based threefry at ~5x the cost. Cancellation is
exact in real arithmetic either way (both endpoints of a pair generate
bit-identical masks from the shared key), so the cohort sum matches the
plain sum to fp32 accumulation error.

Dropout repair (DESIGN.md §Dropout-tolerant rounds): cross-silo cohorts are
small but NOT perfectly reliable — a silo that vanishes mid-round would
leave its pairwise masks uncancelled in the survivor sum. Because both
endpoints of a pair share the mask secret, recovery does not need the full
Bonawitz secret-sharing machinery: the server publishes the dropout set and
every survivor re-derives the sum of its masks toward the dropped peers
(``repair_correction`` — same ``pair_keys`` + unrolled PRG) and posts it as
a packed correction buffer. Subtracting each survivor's correction from its
masked update removes exactly the orphaned mask terms, so the survivor-only
sum telescopes again, bit-exact up to fp32 accumulation
(tests/test_dropout.py).

Weighted FedAvg: pairwise masks only cancel under *equal* server-side
weights, so weighting happens client-side — each client pre-scales its
packed update by ``n_examples / weight_denom`` (the server publishes the
nominal ``weight_denom`` with the round) before masking, and the server
reduces with uniform weights and divides the repaired sum by the survivors'
total scaled weight. The result is exact weighted FedAvg over survivors.
"""
from __future__ import annotations

import hashlib
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.packing import as_matrix, pack_many, pack_pytree, \
    unpack_pytree
from repro.kernels.secure_agg.ops import masked_sum, masked_sum_corrected

DEFAULT_SCALE = 1e-2


def _pair_seed(secret: bytes, i: str, j: str) -> int:
    lo, hi = sorted([i, j])
    h = hashlib.sha256(secret + f"{lo}|{hi}".encode()).digest()
    return int.from_bytes(h[:8], "little") & (2 ** 63 - 1)


def pair_keys(client_id: str, cohort: Sequence[str], pair_secret: bytes):
    """PRNG keys + signs for every pair (client_id, other) in the cohort.

    Returns ``(keys, signs)``: keys is a (P, 2) uint32 array — per peer,
    the two 32-bit words of the shared pair key (also a valid raw threefry
    key); both endpoints derive the identical key from the sorted pair.
    signs is (P,) f32 with +1 where ``client_id`` is the lexicographically
    smaller endpoint and -1 otherwise. O(cohort) host hashing —
    independent of model size.
    """
    others = [c for c in cohort if c != client_id]
    if not others:
        return (jnp.zeros((0, 2), jnp.uint32), jnp.zeros((0,), jnp.float32))
    keys = jnp.stack([jax.random.PRNGKey(_pair_seed(pair_secret, client_id,
                                                    other))
                      for other in others])
    signs = jnp.asarray([1.0 if client_id < other else -1.0
                         for other in others], jnp.float32)
    return keys, signs


def _mix32(x):
    """lowbias32 integer mixer (Wellons) — full avalanche per round."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


_UNIT_STD = 3.4641016  # sqrt(12): scales uniform [-0.5, 0.5) to unit std


def _uniform_from_bits(bits):
    """uint32 -> f32 uniform with zero mean and *unit standard deviation*
    (range [-sqrt(3), sqrt(3))): top 23 bits into the mantissa of [1, 2),
    minus 1.5, times sqrt(12). Unit std keeps mask strength at parity with
    the seed's gaussian masks for the same ``scale``. Exactly reproducible:
    both endpoints of a pair produce bit-identical values."""
    return (jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32)
        - 1.5) * jnp.float32(_UNIT_STD)


@partial(jax.jit, static_argnames=("prg",))
def _apply_masks(buf, keys, signs, scale, *, prg: str = "fast"):
    """buf: (T,) f32; keys: (P, 2) uint32; signs: (P,) -> masked (T,) f32.

    ``prg="fast"`` (default): the pair loop is unrolled at trace time, so
    XLA fuses all P keyed-hash streams and the accumulation into one pass
    over the buffer — one acc read/write total, no (P, T) intermediate.
    ``prg="threefry"``: ``jax.random`` counter-based threefry per pair via
    ``lax.scan`` (cryptographic stream, ~5x slower on CPU). Memory stays
    O(T) regardless of cohort size on both paths.
    """
    T = buf.shape[0]
    acc = buf.astype(jnp.float32)
    if prg == "threefry":
        def body(acc, pair):
            key, sign = pair
            bits = jax.random.bits(key, (T,), jnp.uint32)
            return acc + (sign * scale) * _uniform_from_bits(bits), None
        out, _ = jax.lax.scan(body, acc, (keys, signs))
        return out
    idx = jax.lax.iota(jnp.uint32, T)
    for p in range(keys.shape[0]):
        bits = _mix32(_mix32(idx ^ keys[p, 0]) + keys[p, 1])
        acc = acc + (signs[p] * scale) * _uniform_from_bits(bits)
    return acc


def mask_packed(buf, client_id: str, cohort: Sequence[str],
                pair_secret: bytes, scale: float = DEFAULT_SCALE,
                prg: str = "fast"):
    """Add all pairwise-cancelling masks to a packed (T,) fp32 buffer."""
    keys, signs = pair_keys(client_id, cohort, pair_secret)
    return _apply_masks(jnp.asarray(buf, jnp.float32), keys, signs,
                        jnp.float32(scale), prg=prg)


def aggregate_masked_packed(buffers, weights: Optional[Sequence[float]]
                            = None, *, corrections=None,
                            interpret: bool = None):
    """Combine (N, T) packed masked buffers into the (T,) cohort mean.

    Pairwise masking only telescopes under *equal* weights; for weighted
    FedAvg clients pre-scale their update by their weight before masking
    (handled by the caller). ``weights`` therefore defaults to the uniform
    mean and is exposed only for pre-scaled protocols — unlike
    ``aggregation.aggregate_packed`` it is NOT normalized, so pre-scaled
    sums stay sums. Routed through the fused Pallas combine (jnp oracle off
    the TPU).

    ``corrections`` (dropout repair): an (N, T) matrix of per-survivor
    correction buffers (``repair_correction``), subtracted row-wise before
    the reduction through the fused corrected combine — after a dropout
    the survivor rows still carry masks toward the dropped peers, and the
    corrections cancel exactly those terms.
    """
    x = as_matrix(buffers)
    n = x.shape[0]
    w = (jnp.full((n,), 1.0 / n, jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    if corrections is not None:
        return masked_sum_corrected(x, as_matrix(corrections), w,
                                    interpret=interpret)
    return masked_sum(x, w, interpret=interpret)


def repair_correction(size: int, client_id: str, dropped: Sequence[str],
                      pair_secret: bytes, scale: float = DEFAULT_SCALE,
                      prg: str = "fast"):
    """This survivor's summed pairwise masks against the dropped peers.

    Masking a zero buffer against the cohort ``{client_id} U dropped``
    yields exactly ``sum_{j in dropped} sign(client_id, j) * mask(i, j)``
    — the orphaned mask terms left in the survivor sum after ``dropped``
    vanished. Both sides derive masks from the shared pair secret, so no
    secret-sharing round is needed; the survivor posts this (T,) buffer
    and the server subtracts it in the reduction
    (``aggregate_masked_packed(corrections=...)``).
    """
    return mask_packed(jnp.zeros((size,), jnp.float32), client_id,
                       [client_id, *dropped], pair_secret, scale, prg)


# ---------------------------------------------------------------------------
# integer-domain masking (DESIGN.md §Composable privacy)
#
# fp32 masks do NOT survive lossy coding: quantizing a masked buffer
# re-rounds each endpoint's mask independently, so the telescoping sum
# breaks. Drawing the pairwise masks over the *quantized integer* domain
# instead — uniform residues mod M = 2**modulus_bits added to the widened
# int stream — makes cancellation exact by construction: the server's sum
# wraps in uint32 arithmetic, M divides 2**32, so sum_i mask_i ≡ 0 (mod M)
# holds bit-for-bit, with zero tolerance (tests/test_composable_privacy.py).
# The mask PRG is the same keyed lowbias32 stream as the fp32 plane, under
# a domain-separated pair secret so the two planes never share residues.
# ---------------------------------------------------------------------------
INT_MASK_DOMAIN = b"/intmask"


def mask_modulus_bits(cohort_size: int, quant_bits: int = 8) -> int:
    """Shared mask-modulus width (16 or 32) for a masked-quantized round.

    The modular sum of N clients' quantized values must decode without
    ambiguity: each value is bounded by 2*qmax (qmax from ``quant_bits``
    plus an equal headroom for the DP noise stage), so the signed sum
    lives in ``[-2*N*qmax, 2*N*qmax]`` and centered decoding needs
    ``M > 4*N*qmax``. Both endpoints derive the width from the round
    cohort size alone, so no extra negotiation round is needed; 16-bit
    residues halve the wire cost for typical cross-silo cohorts
    (``M = 2**16`` covers N <= 128 at 8 bits).
    """
    qmax = (1 << (int(quant_bits) - 1)) - 1
    span = 4 * max(1, int(cohort_size)) * qmax
    return 16 if span < (1 << 16) else 32


@partial(jax.jit, static_argnames=("size", "modulus_bits"))
def _int_masks(keys, signs, *, size: int, modulus_bits: int):
    """Summed signed pairwise residues mod 2**modulus_bits, as uint32.

    Same unrolled one-pass structure as ``_apply_masks``: per pair, the
    keyed lowbias32 stream masked down to ``modulus_bits`` bits, added
    with the pair's sign in modular arithmetic (``(M - r) & (M-1)`` is
    ``-r mod M``; uint32 wrap-around preserves residues because M divides
    2**32). Both endpoints of a pair generate bit-identical residues, so
    the cohort sum of all offsets is ≡ 0 (mod M) exactly.
    """
    maskval = jnp.uint32((1 << modulus_bits) - 1)
    idx = jax.lax.iota(jnp.uint32, size)
    acc = jnp.zeros((size,), jnp.uint32)
    for p in range(keys.shape[0]):
        bits = _mix32(_mix32(idx ^ keys[p, 0]) + keys[p, 1]) & maskval
        neg = (jnp.uint32(0) - bits) & maskval
        acc = acc + jnp.where(signs[p] > 0, bits, neg)
    return acc


def int_mask_offset(size: int, client_id: str, cohort: Sequence[str],
                    pair_secret: bytes, modulus_bits: int):
    """This client's total mask offset for a (size,) integer stream.

    The caller adds it to the widened quantized stream and reduces mod
    ``2**modulus_bits``; over the full cohort the offsets cancel exactly.
    Domain-separated from the fp32 mask plane (``INT_MASK_DOMAIN``).
    """
    keys, signs = pair_keys(client_id, cohort,
                            pair_secret + INT_MASK_DOMAIN)
    if keys.shape[0] == 0:
        return jnp.zeros((size,), jnp.uint32)
    return _int_masks(keys, signs, size=int(size),
                      modulus_bits=int(modulus_bits))


def int_repair_correction(size: int, client_id: str,
                          dropped: Sequence[str], pair_secret: bytes,
                          modulus_bits: int):
    """Integer-domain twin of ``repair_correction``: this survivor's
    summed residues against the dropped peers, mod 2**modulus_bits. The
    server subtracts it (modular) before decoding, removing exactly the
    orphaned mask terms — bit-exact, not merely to fp32 accumulation."""
    return int_mask_offset(size, client_id, [client_id, *dropped],
                           pair_secret, modulus_bits)


# ---------------------------------------------------------------------------
# pytree-level compatibility wrappers (pack -> packed op -> unpack)
# ---------------------------------------------------------------------------
def mask_update(update, client_id: str, cohort: Sequence[str],
                pair_secret: bytes, scale: float = DEFAULT_SCALE):
    """Mask a parameter pytree: one pack, one vectorized masking pass."""
    buf, layout = pack_pytree(update)
    return unpack_pytree(
        mask_packed(buf, client_id, cohort, pair_secret, scale), layout)


def aggregate_masked(masked_updates: Sequence, *, interpret: bool = None):
    """Uniform mean of masked pytrees — masks cancel exactly.

    Packs the cohort into one (N, T) matrix, reduces through the kernel
    path and unpacks once.
    """
    stacked, layout = pack_many(masked_updates)
    mean = aggregate_masked_packed(stacked, interpret=interpret)
    return unpack_pytree(mean, layout)
