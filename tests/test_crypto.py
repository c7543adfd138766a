"""The message cipher: wire compatibility with the original construction,
tamper resistance on both sides of the MAC-overlap threshold, and the
counter that shows how often the overlap engages."""
import hashlib
import hmac
import secrets
import threading
import zlib

import numpy as np
import pytest

from repro.core import crypto
from repro.core.clients import ClientManagement
from repro.core.communicator import (COUNTER_MAC_OVERLAPPED,
                                     ClientCommunicator, MessageBoard,
                                     ServerCommunicator)
from repro.core.metadata import MetadataStore
from repro.core.telemetry import Telemetry

MASTER = b"m" * 32
KEY = crypto.derive_key(MASTER, "wire-compat")
SMALL = 2 * 1024                          # a control message
LARGE = 5 * 2 ** 20                       # above MAC_OVERLAP_BYTES


# --- the construction as first written: tag ‖ flags ‖ nonce ‖ ct ---------
def _frozen_encrypt(key, plaintext, compress):
    flags = b"\x01" if compress else b"\x00"
    if compress:
        level = 1 if len(plaintext) > 8 * 2 ** 20 else 6
        plaintext = zlib.compress(plaintext, level=level)
    nonce = secrets.token_bytes(16)
    stream = hashlib.shake_256(crypto.derive_key(key, "enc") + nonce
                               ).digest(len(plaintext))
    body = flags + nonce + (np.frombuffer(plaintext, np.uint8)
                            ^ np.frombuffer(stream, np.uint8)).tobytes()
    tag = hmac.new(crypto.derive_key(key, "mac"), body, hashlib.sha256)
    return tag.digest() + body


def _frozen_decrypt(key, blob):
    tag, body = blob[:32], blob[32:]
    want = hmac.new(crypto.derive_key(key, "mac"), body,
                    hashlib.sha256).digest()
    if not hmac.compare_digest(tag, want):
        raise ValueError("message authentication failed")
    flags, nonce, ct = body[:1], body[1:17], body[17:]
    stream = hashlib.shake_256(crypto.derive_key(key, "enc") + nonce
                               ).digest(len(ct))
    pt = (np.frombuffer(ct, np.uint8)
          ^ np.frombuffer(stream, np.uint8)).tobytes()
    return zlib.decompress(pt) if flags == b"\x01" else pt


def _payload(n: int) -> bytes:
    """Half random bytes, half text: zlib has something to do either way."""
    rng = np.random.default_rng(n)
    text = b"round 3 cohort windco solarx " * (n // 58 + 1)
    return rng.bytes(n // 2) + text[:n - n // 2]


@pytest.fixture
def mac_threads(monkeypatch):
    """Record every MAC worker thread ``crypto.decrypt`` starts."""
    started = []

    class Recorded(threading.Thread):
        def start(self):
            started.append(self)
            super().start()

    monkeypatch.setattr(crypto.threading, "Thread", Recorded)
    return started


SIZES = pytest.mark.parametrize("n", [SMALL, LARGE], ids=["2KiB", "5MiB"])
COMPRESS = pytest.mark.parametrize("compress", [True, False],
                                   ids=["zlib", "raw"])


@SIZES
@COMPRESS
@pytest.mark.parametrize("direction", ["frozen_to_new", "new_to_frozen"])
def test_wire_compatible_with_frozen_construction(n, compress, direction):
    pt = _payload(n)
    if direction == "frozen_to_new":
        got = crypto.decrypt(KEY, _frozen_encrypt(KEY, pt, compress))
    else:
        got = _frozen_decrypt(KEY, crypto.encrypt(KEY, pt,
                                                  compress=compress))
    assert bytes(got) == pt


@SIZES
@COMPRESS
def test_encrypt_bytes_equal_frozen_under_one_nonce(n, compress,
                                                    monkeypatch):
    """Same key, plaintext and nonce: the very same bytes on the wire."""
    nonce = bytes(range(16))
    monkeypatch.setattr(secrets, "token_bytes", lambda k: nonce[:k])
    pt = _payload(n)
    blob = crypto.encrypt(KEY, pt, compress=compress)
    assert type(blob) is bytes
    assert blob == _frozen_encrypt(KEY, pt, compress)


@SIZES
def test_decrypt_returns_read_only_plaintext(n, mac_threads):
    pt = _payload(n)
    for compress in (True, False):
        got = crypto.decrypt(KEY, crypto.encrypt(KEY, pt, compress=compress))
        assert isinstance(got, memoryview) and got.format == "B"
        assert got.readonly and got == pt
        with pytest.raises(TypeError):
            got[0] = 0
    # the overlap engages above the threshold only, and its worker is gone
    assert len(mac_threads) == (2 if n == LARGE else 0)
    assert not any(t.is_alive() for t in mac_threads)


def _tamper(blob: bytes, where: str) -> bytes:
    at = {"tag": 5, "flags": 32, "nonce": 40, "ct": len(blob) // 2}[where]
    return blob[:at] + bytes([blob[at] ^ 1]) + blob[at + 1:]


@pytest.mark.parametrize("where", ["tag", "flags", "nonce", "ct",
                                   "wrong_key"])
def test_tampered_large_blob_raises_from_overlapped_path(where, mac_threads):
    pt = _payload(LARGE)
    blob = crypto.encrypt(KEY, pt, compress=False)
    key = KEY
    if where == "wrong_key":
        key = crypto.derive_key(MASTER, "other")
    else:
        blob = _tamper(blob, where)
    assert crypto.overlapped(blob)
    with pytest.raises(ValueError, match="authentication") as err:
        crypto.decrypt(key, blob)
    assert len(mac_threads) == 1 and not mac_threads[0].is_alive()
    # nothing the failed call held leads to the plaintext: no buffer of
    # its length (the plaintext, or the keystream that opens it) is left
    # in the cipher's frames of the traceback
    tb, frames = err.value.__traceback__, 0
    while tb is not None:
        if tb.tb_frame.f_code.co_filename == crypto.__file__:
            frames += 1
            for name, value in tb.tb_frame.f_locals.items():
                if isinstance(value, (bytes, bytearray, np.ndarray)):
                    assert len(value) != len(pt), name
        tb = tb.tb_next
    assert frames == 1


@pytest.mark.parametrize("where", ["tag", "flags", "nonce", "ct"])
def test_tampered_small_blob_raises_without_a_thread(where, mac_threads):
    blob = _tamper(crypto.encrypt(KEY, _payload(SMALL)), where)
    assert not crypto.overlapped(blob)
    with pytest.raises(ValueError, match="authentication"):
        crypto.decrypt(KEY, blob)
    assert mac_threads == []


def test_mac_overlapped_counter_counts_large_opens_only():
    """One count per large blob a communicator opens, on either side;
    control messages leave it where it was."""
    md = MetadataStore()
    cm = ClientManagement(md)
    cm.create_user("bootstrap", "admin", "coord", "pw", role="server_admin")
    cm.create_user("admin", "alice", "windco", "pw-a")
    cid = cm.request_registration("alice", "windco")
    cm.approve_client("admin", cid)
    token = cm.issue_tokens("r1")[cid]
    tel = Telemetry(enabled=True)
    board = MessageBoard(cm, md, telemetry=tel)
    server = ServerCommunicator(board, MASTER)
    client = ClientCommunicator(board, cid, token,
                                channel_key=server.channel_key(cid),
                                broadcast_key=server.broadcast_key(),
                                ca_key=MASTER)
    count = tel.metrics.counter(COUNTER_MAC_OVERLAPPED)
    assert tel.metrics.snapshot()[COUNTER_MAC_OVERLAPPED] == 0

    update = np.random.default_rng(0).standard_normal(2 ** 20, np.float32)
    client.post("runs/r1/update", {"w": update})
    np.testing.assert_array_equal(
        server.collect("runs/r1/update", cid)["w"], update)
    assert count.read() == 1
    server.publish("runs/r1/global", {"w": update})
    np.testing.assert_array_equal(
        client.fetch("runs/r1/global", broadcast=True)["w"], update)
    assert count.read() == 2

    client.post("runs/r1/status", {"n": 1})
    server.publish("runs/r1/control", {"phase": "train"})
    assert server.collect("runs/r1/status", cid) == {"n": 1}
    assert client.fetch("runs/r1/control",
                        broadcast=True) == {"phase": "train"}
    assert count.read() == 2

    flags = [s.attrs["overlapped"] for s in tel.spans("r1")
             if s.name == "wire.decrypt"]
    assert flags == [True, True, False, False]
