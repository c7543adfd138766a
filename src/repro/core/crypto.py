"""Message-layer crypto for the Communicator (paper §V "Communicator",
requirement: encrypted, compressed messages; §VII user/server authentication).

stdlib plus numpy (offline container): SHAKE-256 keystream cipher with
encrypt-then-MAC (HMAC-SHA256), plus HKDF-style key derivation. This gives
the architectural properties the paper requires — confidentiality +
authenticity seams living *only* in the Communicator — without an external
crypto dependency. A production deployment would swap in TLS/AES-GCM behind
the same interface.
"""
from __future__ import annotations

import hashlib
import hmac
import os
import secrets
import threading
import zlib

import numpy as np


def derive_key(master: bytes, purpose: str) -> bytes:
    return hmac.new(master, purpose.encode(), hashlib.sha256).digest()


def _keystream(key: bytes, nonce: bytes, n: int) -> bytes:
    # SHAKE-256 XOF: arbitrary-length keystream in one C call. It is the
    # cipher's floor: about 200 MB/s on one core, and it holds the GIL
    # throughout, so nothing else in Python runs beside it but code that
    # releases the GIL (HMAC over large buffers, numpy's XOR).
    return hashlib.shake_256(key + nonce).digest(n)


def _u8(buf) -> np.ndarray:
    return np.frombuffer(buf, np.uint8)


# A sealed message is ``tag ‖ flags ‖ nonce ‖ ct``: the HMAC-SHA256 tag
# over everything after it, one flag byte (0x01: zlib-compressed), the
# 16-byte nonce, and the keystream XOR of the (compressed) plaintext.
_TAG = 32
_HEAD = _TAG + 1 + 16

# Ciphertexts of at least this many bytes have their tag checked on a
# worker thread while the calling thread squeezes the keystream: the
# HMAC releases the GIL over large buffers and the SHAKE digest does
# not, so the two run side by side. Below it (control messages, status)
# starting a thread costs more than the MAC it would hide.
MAC_OVERLAP_BYTES = 1 << 20


def overlapped(blob) -> bool:
    """Whether ``decrypt`` checks this blob's tag beside its keystream."""
    return len(blob) - _HEAD >= MAC_OVERLAP_BYTES


# auto-compression probe: payloads above this size get head, middle and
# tail slices sampled and test-compressed; any slice with a ratio worse
# than _PROBE_RATIO means "substantially incompressible" (fp32 weight
# bytes) and compression is skipped entirely
_PROBE_BYTES = 64 * 1024
_PROBE_SLICE = _PROBE_BYTES // 3
_PROBE_RATIO = 0.9


def _compression_pays(plaintext: bytes) -> bool:
    """Predict whether zlib over the whole payload is worth it.

    A head-only probe mispredicts the common adversarial layout: a
    compressible msgpack/control header followed by an incompressible
    fp32 body — the 64KB prefix compresses beautifully, then zlib churns
    through hundreds of megabytes of weight bytes for ~0% saving. So the
    probe samples head, middle AND tail slices, and only predicts a win
    when *every* region looks compressible: large payloads are dominated
    by their bulk, and a single incompressible region already caps the
    overall ratio near 1. (Skipping a marginally-compressible payload is
    cheap; compressing a near-incompressible one used to dominate every
    large post.)
    """
    n = len(plaintext)
    k = _PROBE_SLICE
    mid = (n - k) // 2
    slices = (plaintext[:k], plaintext[mid:mid + k], plaintext[n - k:])
    return all(len(zlib.compress(s, 1)) < _PROBE_RATIO * len(s)
               for s in slices)


def encrypt(key: bytes, plaintext: bytes, *, compress="auto") -> bytes:
    """zlib-compress, encrypt (SHAKE-256 stream), authenticate (HMAC-SHA256).

    ``compress="auto"`` (default) samples head, middle and tail slices of
    a large payload and compresses only when *every* region looks
    compressible (``_compression_pays``): masked fp32 weight buffers are
    near-incompressible, and running zlib over hundreds of MB to save ~1%
    used to dominate every post — even when a compressible control header
    led the buffer. Small payloads (control messages) always compress at
    level 6; large compressible ones at level 1. ``compress=True/False``
    force the old behaviour.
    """
    if compress == "auto":
        compress = (len(plaintext) <= _PROBE_BYTES
                    or _compression_pays(plaintext))
    flags = b"\x01" if compress else b"\x00"
    if compress:
        level = 1 if len(plaintext) > 8 * 2 ** 20 else 6
        plaintext = zlib.compress(plaintext, level=level)
    nonce = secrets.token_bytes(16)
    n = len(plaintext)
    # one buffer laid out as the wire message; the MAC is written last
    out = np.empty(_HEAD + n, np.uint8)
    out[_TAG] = flags[0]
    out[_TAG + 1:_HEAD] = _u8(nonce)
    np.bitwise_xor(_u8(plaintext),
                   _u8(_keystream(derive_key(key, "enc"), nonce, n)),
                   out=out[_HEAD:])
    out[:_TAG] = _u8(hmac.new(derive_key(key, "mac"), memoryview(out)[_TAG:],
                              hashlib.sha256).digest())
    # the board and transports hold ``bytes``: the one copy left
    return out.tobytes()


def compressed(blob: bytes) -> bool:
    """Whether ``encrypt`` zlib-compressed this blob's plaintext."""
    return blob[_TAG:_TAG + 1] == b"\x01"


def decrypt(key: bytes, blob) -> memoryview:
    """Check the tag, then return the plaintext as a read-only view.

    Every part of the blob is read through ``memoryview`` slices and the
    plaintext is XOR'd into one fresh buffer, so the payload is never
    copied. Above ``MAC_OVERLAP_BYTES`` the tag is checked on a worker
    thread while the keystream is squeezed; the XOR runs only after the
    tag has verified, so a forged blob never yields plaintext. Raises
    ``ValueError`` when the tag does not verify.
    """
    view = memoryview(blob)
    tag, body = view[:_TAG], view[_TAG:]
    nonce, ct = view[_TAG + 1:_HEAD], view[_HEAD:]
    mac = hmac.new(derive_key(key, "mac"), digestmod=hashlib.sha256)
    enc = derive_key(key, "enc")
    if overlapped(view):
        worker = threading.Thread(target=mac.update, args=(body,),
                                  name="crypto.mac")
        worker.start()
        try:
            stream = _keystream(enc, bytes(nonce), len(ct))
        finally:
            worker.join()
    else:
        mac.update(body)
        stream = None
    if not hmac.compare_digest(tag, mac.digest()):
        del stream                     # nothing of a forged blob stays
        raise ValueError("message authentication failed")
    if stream is None:
        stream = _keystream(enc, bytes(nonce), len(ct))
    pt = np.empty(len(ct), np.uint8)
    np.bitwise_xor(_u8(ct), _u8(stream), out=pt)
    if view[_TAG] == 1:
        return memoryview(zlib.decompress(pt))
    return memoryview(pt).toreadonly()


def new_device_token() -> str:
    """Per-process device token (paper §VII step 2: rotated every FL run)."""
    return secrets.token_hex(24)


def hash_password(password: str, salt: bytes = None) -> str:
    salt = salt or os.urandom(16)
    dk = hashlib.pbkdf2_hmac("sha256", password.encode(), salt, 100_000)
    return salt.hex() + ":" + dk.hex()


def verify_password(password: str, stored: str) -> bool:
    salt_hex, dk_hex = stored.split(":")
    dk = hashlib.pbkdf2_hmac("sha256", password.encode(),
                             bytes.fromhex(salt_hex), 100_000)
    return hmac.compare_digest(dk.hex(), dk_hex)


def server_certificate(server_id: str, master: bytes) -> str:
    """Toy certificate: HMAC of the server identity under a CA master key.

    Clients holding the CA key verify genuineness (paper §VII Server
    Authentication). Stands in for X.509 in the offline container.
    """
    return hmac.new(derive_key(master, "ca"), server_id.encode(),
                    hashlib.sha256).hexdigest()


def verify_certificate(server_id: str, cert: str, master: bytes) -> bool:
    return hmac.compare_digest(server_certificate(server_id, master), cert)
