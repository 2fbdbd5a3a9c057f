"""A batch of ed25519 verify lanes by COLUMNS: the one form a batch has
between the verify seam (types/validation.py) and the device arrays
(ops/ed25519._pack), never a per-lane Python object.

Built two ways and only two: ``from_items``, from the ``(msg,
key_bytes, sig)`` tuples every other caller of
``ops/ed25519.verify_batch_async`` hands it (one pass over the lanes,
at that boundary), and by the seam, from slices of its validator
sets' key rows and its commits' joined signatures (``LaneBatch(msgs,
keys, sigs)``: every key 32 bytes and every signature 64 by
construction, so no lane is refused).
"""

from __future__ import annotations

import itertools

import numpy as np

from .keys import Ed25519PubKey

_NO_LANES = np.zeros(0, np.intp)


def _rows(field, good, n_good: int, width: int) -> np.ndarray:
    """(n, width) uint8 of one fixed-width field, a refused lane's
    row zero."""
    if n_good == len(field):
        return np.frombuffer(b"".join(field), np.uint8).reshape(-1, width)
    out = np.zeros((len(field), width), np.uint8)
    out[good] = np.frombuffer(
        b"".join(itertools.compress(field, good.tolist())), np.uint8
    ).reshape(-1, width)
    return out


class LaneBatch:
    """``msgs``: one ``bytes`` a lane (ragged). ``keys``: (n, 32)
    uint8. ``sigs``: (n, 64) uint8. ``bad``: ascending positions of the
    lanes refused before the device (key not 32 bytes, signature not
    64), whose rows are zero. ``len()`` = lanes."""

    __slots__ = ("msgs", "keys", "sigs", "bad")

    def __init__(self, msgs, keys, sigs, bad=_NO_LANES) -> None:
        self.msgs = msgs
        self.keys = keys
        self.sigs = sigs
        self.bad = bad

    def __len__(self) -> int:
        return len(self.msgs)

    @classmethod
    def from_items(cls, items) -> "LaneBatch":
        """From a non-empty sequence of ``(msg, key_bytes, sig)``."""
        n = len(items)
        msgs, keys, sigs = zip(*items)
        good = (np.fromiter(map(len, keys), np.int32, n) == 32) & (
            np.fromiter(map(len, sigs), np.int32, n) == 64
        )
        n_good = int(np.count_nonzero(good))
        return cls(
            list(map(bytes, msgs)),  # a bytes object stays itself
            _rows(keys, good, n_good, 32),
            _rows(sigs, good, n_good, 64),
            _NO_LANES if n_good == n else np.flatnonzero(~good),
        )

    def __getitem__(self, i: int):
        """Lane ``i`` as the scheduler's ``(PubKey, msg, sig)``: what
        the host plane verifies (crypto/parallel_verify). Of a batch
        the seam built (``bad`` empty): every key is ed25519."""
        return (
            Ed25519PubKey(self.keys[i].tobytes()),
            self.msgs[i],
            self.sigs[i].tobytes(),
        )

    def take(self, at) -> "LaneBatch":
        """The lanes ``at`` (ascending positions), of a batch the seam
        built."""
        return LaneBatch(
            list(map(self.msgs.__getitem__, at)), self.keys[at], self.sigs[at]
        )
