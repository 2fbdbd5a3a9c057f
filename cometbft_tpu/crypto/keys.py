"""Host-side key API: ed25519 + secp256k1 key types, addresses, signing.

Mirrors the reference's ``crypto.PubKey/PrivKey`` interfaces
(reference crypto/crypto.go) with the same observable behavior:

- address = first 20 bytes of SHA-256(raw pubkey) (crypto/ed25519 and
  tmhash semantics),
- ed25519 signing is RFC 8032 (via the `cryptography`/OpenSSL backend,
  pure-python fallback for odd platforms),
- single-signature verification uses ZIP-215 semantics to match batch
  verification exactly (reference uses curve25519-voi ZIP-215 for both).

The batch path is :mod:`cometbft_tpu.crypto.scheduler` (routing policy:
:mod:`cometbft_tpu.crypto.batch`; kernel: :mod:`cometbft_tpu.ops.ed25519`).
"""

from __future__ import annotations

import hashlib
import os
import threading
from dataclasses import dataclass

from . import ref_ed25519 as _ref

try:
    from cryptography.hazmat.primitives import serialization as _ser
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey as _OsslPriv,
    )

    _HAVE_OSSL = True
except Exception:  # pragma: no cover
    _HAVE_OSSL = False

# middle tier: the system libcrypto through ctypes (crypto/_ossl.py)
# when the `cryptography` wheel is absent; pure python is last resort
from . import _ossl as _ctossl

_HAVE_CTYPES_OSSL = (not _HAVE_OSSL) and _ctossl.available()

ED25519_KEY_TYPE = "ed25519"
SECP256K1_KEY_TYPE = "secp256k1"

ADDRESS_LEN = 20


def address_from_pubkey_bytes(raw: bytes) -> bytes:
    return hashlib.sha256(raw).digest()[:ADDRESS_LEN]


@dataclass(frozen=True)
class PubKey:
    """Interface marker; concrete: Ed25519PubKey, Secp256k1PubKey."""

    key_bytes: bytes

    @property
    def type_(self) -> str:
        raise NotImplementedError

    def address(self) -> bytes:
        return address_from_pubkey_bytes(self.key_bytes)

    def verify(self, msg: bytes, sig: bytes) -> bool:
        raise NotImplementedError

    def __bytes__(self) -> bytes:
        return self.key_bytes


# Constructed-OpenSSL-object cache: validator keys repeat massively
# (a 10k-block replay has ~150 distinct keys for ~1.5M verifies), and
# Ed25519PublicKey.from_public_bytes costs ~1.5x the hash of the vote
# itself (round-5 replay profile). Only VALID constructions are cached;
# invalid keys re-raise (and fall through to the liberal check) every
# time, which is the rare path.
_EVP_CACHE: dict = {}
_EVP_CACHE_MAX = 4096
_EVP_LOCK = threading.Lock()


def _openssl_pub(key_bytes: bytes):
    with _EVP_LOCK:
        evp = _EVP_CACHE.get(key_bytes)
    if evp is None:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PublicKey,
        )

        evp = Ed25519PublicKey.from_public_bytes(key_bytes)
        # verification runs on worker threads (coalesce, statesync,
        # light proxy): eviction must not race — an escaped KeyError
        # here would silently demote the verify to the slow liberal
        # path via the caller's blanket except
        with _EVP_LOCK:
            while len(_EVP_CACHE) >= _EVP_CACHE_MAX:
                _EVP_CACHE.pop(next(iter(_EVP_CACHE)))
            _EVP_CACHE[key_bytes] = evp
    return evp


@dataclass(frozen=True)
class Ed25519PubKey(PubKey):
    @property
    def type_(self) -> str:
        return ED25519_KEY_TYPE

    def verify(self, msg: bytes, sig: bytes) -> bool:
        """ZIP-215 verification.

        Fast path: OpenSSL (accepts a strict subset of ZIP-215 — every
        honestly-generated signature). Only if OpenSSL rejects do we run
        the liberal pure-python cofactored check, so non-canonical /
        small-order edge cases still validate exactly like the TPU
        kernel and the reference's curve25519-voi."""
        if len(self.key_bytes) != 32 or len(sig) != 64:
            return False
        if _HAVE_OSSL:
            try:
                _openssl_pub(self.key_bytes).verify(sig, msg)
                return True
            except Exception:
                pass  # fall through to the liberal ZIP-215 check
        elif _HAVE_CTYPES_OSSL:
            try:
                if _ctossl.ed25519_verify(self.key_bytes, msg, sig):
                    return True
            except Exception:
                pass  # fall through to the liberal ZIP-215 check
        return _ref.verify_zip215(self.key_bytes, msg, sig)


@dataclass(frozen=True)
class Ed25519PrivKey:
    seed: bytes

    @classmethod
    def generate(cls) -> "Ed25519PrivKey":
        return cls(os.urandom(32))

    @classmethod
    def from_seed(cls, seed: bytes) -> "Ed25519PrivKey":
        assert len(seed) == 32
        return cls(seed)

    def pub_key(self) -> Ed25519PubKey:
        if _HAVE_OSSL:
            pk = _OsslPriv.from_private_bytes(self.seed).public_key()
            raw = pk.public_bytes(
                _ser.Encoding.Raw, _ser.PublicFormat.Raw
            )
        elif _HAVE_CTYPES_OSSL:
            raw = _ctossl.ed25519_public(self.seed)
        else:  # pragma: no cover
            raw = _ref.public_from_seed(self.seed)
        return Ed25519PubKey(raw)

    def sign(self, msg: bytes) -> bytes:
        if _HAVE_OSSL:
            return _OsslPriv.from_private_bytes(self.seed).sign(msg)
        if _HAVE_CTYPES_OSSL:
            return _ctossl.ed25519_sign(self.seed, msg)
        return _ref.sign(self.seed, msg)  # pragma: no cover

    def __bytes__(self) -> bytes:
        # 64-byte expanded form (seed || pubkey), matching the
        # reference's on-disk ed25519 private key layout.
        return self.seed + self.pub_key().key_bytes


# --- secp256k1 (CPU-only; mixed-curve sets fall back per split-batch) ---

_SECP_P = 2**256 - 2**32 - 977
_SECP_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
_SECP_G = (
    0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
    0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8,
)


def _secp_add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    x1, y1 = p
    x2, y2 = q
    if x1 == x2 and (y1 + y2) % _SECP_P == 0:
        return None
    if p == q:
        lam = (3 * x1 * x1) * pow(2 * y1, _SECP_P - 2, _SECP_P) % _SECP_P
    else:
        lam = (y2 - y1) * pow(x2 - x1, _SECP_P - 2, _SECP_P) % _SECP_P
    x3 = (lam * lam - x1 - x2) % _SECP_P
    y3 = (lam * (x1 - x3) - y1) % _SECP_P
    return (x3, y3)


def _secp_mul(k: int, p):
    r = None
    while k:
        if k & 1:
            r = _secp_add(r, p)
        p = _secp_add(p, p)
        k >>= 1
    return r


def _secp_decompress(raw: bytes):
    if len(raw) != 33 or raw[0] not in (2, 3):
        return None
    x = int.from_bytes(raw[1:], "big")
    if x >= _SECP_P:
        return None
    y2 = (pow(x, 3, _SECP_P) + 7) % _SECP_P
    y = pow(y2, (_SECP_P + 1) // 4, _SECP_P)
    if y * y % _SECP_P != y2:
        return None
    if (y & 1) != (raw[0] & 1):
        y = _SECP_P - y
    return (x, y)


@dataclass(frozen=True)
class Secp256k1PubKey(PubKey):
    """33-byte compressed SEC1 encoding, like the reference (dcrd)."""

    @property
    def type_(self) -> str:
        return SECP256K1_KEY_TYPE

    def verify(self, msg: bytes, sig: bytes) -> bool:
        """ECDSA verify; sig = 64 bytes r||s (reference-compatible),
        message is hashed with SHA-256. OpenSSL fast path (~100us, the
        mixed-curve host lane of the batch verifier rides this); the
        pure-python implementation remains as fallback + oracle."""
        if len(sig) != 64:
            return False
        try:
            from cryptography.hazmat.primitives import hashes as _h
            from cryptography.hazmat.primitives.asymmetric import ec as _ec
            from cryptography.hazmat.primitives.asymmetric.utils import (
                encode_dss_signature as _dss,
            )

            pub = _ec.EllipticCurvePublicKey.from_encoded_point(
                _ec.SECP256K1(), bytes(self.key_bytes)
            )
            der = _dss(
                int.from_bytes(sig[:32], "big"),
                int.from_bytes(sig[32:], "big"),
            )
            try:
                pub.verify(der, msg, _ec.ECDSA(_h.SHA256()))
                return True
            except Exception:
                return False
        except (ImportError, ValueError):
            pass  # fall through to the pure-python path
        pt = _secp_decompress(self.key_bytes)
        if pt is None:
            return False
        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:], "big")
        if not (1 <= r < _SECP_N and 1 <= s < _SECP_N):
            return False
        z = int.from_bytes(hashlib.sha256(msg).digest(), "big") % _SECP_N
        w = pow(s, _SECP_N - 2, _SECP_N)
        u1, u2 = z * w % _SECP_N, r * w % _SECP_N
        pt2 = _secp_add(_secp_mul(u1, _SECP_G), _secp_mul(u2, pt))
        if pt2 is None:
            return False
        return pt2[0] % _SECP_N == r


@dataclass(frozen=True)
class Secp256k1PrivKey:
    d: int

    @classmethod
    def generate(cls) -> "Secp256k1PrivKey":
        while True:
            d = int.from_bytes(os.urandom(32), "big")
            if 1 <= d < _SECP_N:
                return cls(d)

    def pub_key(self) -> Secp256k1PubKey:
        x, y = _secp_mul(self.d, _SECP_G)
        return Secp256k1PubKey(bytes([2 + (y & 1)]) + x.to_bytes(32, "big"))

    def sign(self, msg: bytes) -> bytes:
        """Deterministic-ish ECDSA (RFC6979-style nonce via HMAC-free
        hash chaining; low-s normalized), sig = r||s 64 bytes."""
        z = int.from_bytes(hashlib.sha256(msg).digest(), "big") % _SECP_N
        k_seed = hashlib.sha256(
            self.d.to_bytes(32, "big") + hashlib.sha256(msg).digest()
        ).digest()
        ctr = 0
        while True:
            k = (
                int.from_bytes(
                    hashlib.sha256(k_seed + ctr.to_bytes(4, "big")).digest(),
                    "big",
                )
                % _SECP_N
            )
            ctr += 1
            if k == 0:
                continue
            pt = _secp_mul(k, _SECP_G)
            r = pt[0] % _SECP_N
            if r == 0:
                continue
            s = (z + r * self.d) * pow(k, _SECP_N - 2, _SECP_N) % _SECP_N
            if s == 0:
                continue
            if s > _SECP_N // 2:
                s = _SECP_N - s
            return r.to_bytes(32, "big") + s.to_bytes(32, "big")


BLS12381_KEY_TYPE = "bls12381"


@dataclass(frozen=True)
class Bls12381PubKey(PubKey):
    """Feature-gated (reference crypto/bls12381 behind the `bls12381`
    build tag; stub otherwise). Construction fails unless
    COMETBFT_TPU_BLS12381 is set, mirroring the stub build's panic."""

    def __post_init__(self):
        from . import bls12381

        if not bls12381.enabled():
            raise NotImplementedError(
                "bls12381 support disabled; set COMETBFT_TPU_BLS12381=1"
            )

    @property
    def type_(self) -> str:
        return BLS12381_KEY_TYPE

    def verify(self, msg: bytes, sig: bytes) -> bool:
        from . import bls12381

        return bls12381.verify(self.key_bytes, msg, sig)


@dataclass(frozen=True)
class Bls12381PrivKey:
    sk: int

    @classmethod
    def generate(cls) -> "Bls12381PrivKey":
        from . import bls12381

        sk, _ = bls12381.keygen()
        return cls(sk)

    @classmethod
    def from_seed(cls, seed: bytes) -> "Bls12381PrivKey":
        from . import bls12381

        sk, _ = bls12381.keygen(seed)
        return cls(sk)

    def pub_key(self) -> Bls12381PubKey:
        from . import bls12381

        return Bls12381PubKey(
            bls12381.g1_compress(bls12381.g1_mul(bls12381.G1, self.sk))
        )

    def sign(self, msg: bytes) -> bytes:
        from . import bls12381

        return bls12381.sign(self.sk, msg)


def pubkey_from_type_bytes(type_: str, raw: bytes) -> PubKey:
    if type_ == ED25519_KEY_TYPE:
        return Ed25519PubKey(raw)
    if type_ == SECP256K1_KEY_TYPE:
        return Secp256k1PubKey(raw)
    if type_ == BLS12381_KEY_TYPE:
        return Bls12381PubKey(raw)
    raise ValueError(f"unknown key type {type_}")
