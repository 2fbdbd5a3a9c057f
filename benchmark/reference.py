"""The plain reference: the same semantics, written straight down.

Imports nothing of the program. What it holds:

  vote sign-bytes     CanonicalVote, protobuf wire form, length-delimited
                      (upstream types/canonical.go, types/vote.go:152)
  ZIP-215 verify      pure-Python integers, cofactored equation, liberal
                      point decoding, canonical S (a copy of the
                      algorithm of crypto/ref_ed25519.py)
  verify()            exact ZIP-215 verdicts at OpenSSL's speed: what
                      OpenSSL's strict verifier accepts ZIP-215 accepts
                      too, so only its rejections go to the integers
  light_verify()      VerifyCommitLight: the signatures for the block in
                      validator order until their power passes 2/3, the
                      first invalid one named (types/validation.go:65)
  KvStore             the kvstore app's state and its flat app hash

The benchmark signs its commit pool with ``sign`` below and hands the
program only the commits; the expected verdicts are worked out here.
"""

from __future__ import annotations

import hashlib
import struct

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
)

PRECOMMIT = 2
FLAG_COMMIT = 2  # BlockIDFlagCommit

# --- protobuf wire form ---------------------------------------------------


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(field: int, wire: int) -> bytes:
    return _varint(field << 3 | wire)


def _f_varint(field: int, v: int) -> bytes:
    return _tag(field, 0) + _varint(v) if v else b""


def _f_sfixed64(field: int, v: int) -> bytes:
    return _tag(field, 1) + struct.pack("<q", v) if v else b""


def _f_bytes(field: int, v: bytes) -> bytes:
    return _tag(field, 2) + _varint(len(v)) + v if v else b""


def _f_message(field: int, v: bytes) -> bytes:
    return _tag(field, 2) + _varint(len(v)) + v


def vote_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    block_hash: bytes,
    parts_total: int,
    parts_hash: bytes,
    timestamp_ns: int,
) -> bytes:
    """Sign-bytes of a precommit for a block (never nil here)."""
    psh = _f_varint(1, parts_total) + _f_bytes(2, parts_hash)
    bid = _f_bytes(1, block_hash) + _f_message(2, psh)
    secs, nanos = divmod(timestamp_ns, 1_000_000_000)
    body = (
        _f_varint(1, PRECOMMIT)
        + _f_sfixed64(2, height)
        + _f_sfixed64(3, round_)
        + _f_message(4, bid)
        + _f_message(5, _f_varint(1, secs) + _f_varint(2, nanos))
        + _f_bytes(6, chain_id.encode())
    )
    return _varint(len(body)) + body


# --- ed25519 --------------------------------------------------------------

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
_D = (-121665 * pow(121666, P - 2, P)) % P
_SQRT_M1 = pow(2, (P - 1) // 4, P)
_IDENT = (0, 1, 1, 0)


def _add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * t1 * t2 * _D % P
    d = 2 * z1 * z2 % P
    e, f, g, h = (b - a) % P, (d - c) % P, (d + c) % P, (b + a) % P
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def _mul(s: int, p):
    q = _IDENT
    while s > 0:
        if s & 1:
            q = _add(q, p)
        p = _add(p, p)
        s >>= 1
    return q


def decompress(raw: bytes):
    """Extended coordinates of a 32-byte encoding, ZIP-215 liberal
    (y >= p is reduced, the sign of x = 0 is ignored), or None."""
    y = int.from_bytes(raw, "little")
    sign = y >> 255
    y = (y & ((1 << 255) - 1)) % P
    x2 = (y * y - 1) * pow(_D * y * y + 1, P - 2, P) % P
    if x2 == 0:
        return (0, y, 1, 0)
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P:
        x = x * _SQRT_M1 % P
    if (x * x - x2) % P:
        return None
    if x & 1 != sign:
        x = P - x
    return (x, y, 1, x * y % P)


_BY = 4 * pow(5, P - 2, P) % P
_BASE = decompress(_BY.to_bytes(32, "little"))


def verify_zip215(public: bytes, msg: bytes, sig: bytes) -> bool:
    """[8]([S]B - [h]A - R) == identity, S < L."""
    if len(public) != 32 or len(sig) != 64:
        return False
    a = decompress(public)
    r = decompress(sig[:32])
    s = int.from_bytes(sig[32:], "little")
    if a is None or r is None or s >= L:
        return False
    k = int.from_bytes(
        hashlib.sha512(sig[:32] + public + msg).digest(), "little"
    ) % L
    neg = lambda p: ((-p[0]) % P, p[1], p[2], (-p[3]) % P)  # noqa: E731
    diff = _add(_add(_mul(s, _BASE), neg(_mul(k, a))), neg(r))
    x, y, z, _ = _mul(8, diff)
    return x % P == 0 and (y - z) % P == 0


def undecodable_key() -> bytes:
    """A 32-byte string that is no curve point even under ZIP-215."""
    for y in range(2, 1000):
        raw = y.to_bytes(32, "little")
        if decompress(raw) is None:
            return raw
    raise ValueError("no undecodable key found")


class Signer:
    """A validator's key from 32 seed bytes."""

    def __init__(self, seed: bytes) -> None:
        self._key = Ed25519PrivateKey.from_private_bytes(seed)
        self.public = self._key.public_key().public_bytes_raw()
        self.address = hashlib.sha256(self.public).digest()[:20]

    def sign(self, msg: bytes) -> bytes:
        return self._key.sign(msg)


class Verifier:
    """Exact ZIP-215 verdicts; OpenSSL first, the integers on a
    rejection. Keys are parsed once."""

    def __init__(self) -> None:
        self._keys: dict = {}
        self.slow_path = 0

    def verify(self, public: bytes, msg: bytes, sig: bytes) -> bool:
        key = self._keys.get(public)
        if key is None:
            try:
                key = Ed25519PublicKey.from_public_bytes(public)
            except ValueError:
                key = False
            self._keys[public] = key
        if key:
            try:
                key.verify(sig, msg)
                return True
            except InvalidSignature:
                pass
        self.slow_path += 1
        return verify_zip215(public, msg, sig)


def light_verify(verifier: Verifier, chain_id: str, vals, commit):
    """VerifyCommitLight over plain data.

    vals: [(public, power)] in validator order; commit: dict with
    height, round, block_hash, parts_total, parts_hash and sigs
    [(flag, timestamp_ns, signature)] in the same order. Returns
    (error, index, lanes): None, "invalid_signature" with the first
    failing validator's index, or "not_enough_power"; lanes is how many
    signatures light verification reads."""
    total = sum(p for _, p in vals)
    tallied = 0
    lanes = []
    for i, (flag, ts, sig) in enumerate(commit["sigs"]):
        if flag != FLAG_COMMIT:
            continue
        lanes.append(i)
        tallied += vals[i][1]
        if tallied * 3 > total * 2:
            break
    for i in lanes:
        _, ts, sig = commit["sigs"][i]
        msg = vote_sign_bytes(
            chain_id, commit["height"], commit["round"],
            commit["block_hash"], commit["parts_total"],
            commit["parts_hash"], ts,
        )
        if not verifier.verify(vals[i][0], msg, sig):
            return "invalid_signature", i, len(lanes)
    if not tallied * 3 > total * 2:
        return "not_enough_power", None, len(lanes)
    return None, None, len(lanes)


# --- the kvstore app ------------------------------------------------------


class KvStore:
    """The kvstore app's committed state: "key=value" txs, the app hash
    SHA-256(height, then every key and value in key order, each with a
    4-byte length)."""

    def __init__(self) -> None:
        self.state: dict = {}
        self.height = 0

    def apply_block(self, txs) -> bytes:
        for tx in txs:
            k, v = tx.split(b"=", 1)
            self.state[k] = v
        self.height += 1
        return self.app_hash()

    def app_hash(self) -> bytes:
        h = hashlib.sha256(self.height.to_bytes(8, "big"))
        for k in sorted(self.state):
            v = self.state[k]
            h.update(len(k).to_bytes(4, "big") + k)
            h.update(len(v).to_bytes(4, "big") + v)
        return h.digest()
