"""Validator / ValidatorSet with proposer-priority rotation.

Behavioral parity with reference types/validator_set.go: weighted
round-robin proposer selection via accumulated priorities, with
centering and scaling to bound priority spread
(PriorityWindowSizeFactor = 2), and the same update semantics
(types/validator_set.go updateWithChangeSet).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from ..crypto import merkle
from ..crypto.keys import Ed25519PubKey, PubKey
from ..utils import proto

PRIORITY_WINDOW_SIZE_FACTOR = 2
MAX_TOTAL_VOTING_POWER = (1 << 63) // 8


@dataclass
class Validator:
    pub_key: PubKey
    voting_power: int
    address: bytes = b""
    proposer_priority: int = 0

    def __post_init__(self):
        if not self.address:
            self.address = self.pub_key.address()

    def copy(self) -> "Validator":
        return Validator(
            self.pub_key, self.voting_power, self.address,
            self.proposer_priority,
        )

    def encode(self) -> bytes:
        """SimpleValidator proto encoding used for ValidatorsHash
        (types/validator.go Bytes: pubkey + voting power)."""
        pk = proto.field_bytes(1, self.pub_key.key_bytes)
        return proto.field_message(1, pk) + proto.field_varint(
            2, self.voting_power
        )

    def compare_proposer_priority(self, other: "Validator") -> int:
        if self.proposer_priority != other.proposer_priority:
            return -1 if self.proposer_priority > other.proposer_priority else 1
        if self.address < other.address:
            return -1
        if self.address > other.address:
            return 1
        return 0


class ValidatorColumns(NamedTuple):
    """A validator set read by columns, in set order: what commit
    verification reads of every validator, once a set and not once a
    signature (types/validation.py plans a commit by selections of
    these)."""

    addresses: List[bytes]
    pub_keys: List[PubKey]
    powers: List[int]
    # the keys' bytes as one (n_vals, 32) uint8 array (read-only), a
    # row a validator: the seam takes a commit's key rows as one slice
    # of it (crypto/lanes.LaneBatch). None unless EVERY key is ed25519
    # with 32 bytes: such a set's lanes go as tuples
    key_rows: Optional[np.ndarray]


def _key_rows(pub_keys: List[PubKey]) -> Optional[np.ndarray]:
    if not all(
        isinstance(pk, Ed25519PubKey) and len(pk.key_bytes) == 32
        for pk in pub_keys
    ):
        return None
    return np.frombuffer(
        b"".join(pk.key_bytes for pk in pub_keys), np.uint8
    ).reshape(-1, 32)


class ValidatorSet:
    def __init__(self, validators: Sequence[Validator]):
        vals = [v.copy() for v in validators]
        vals.sort(key=lambda v: (-v.voting_power, v.address))
        self.validators: List[Validator] = vals
        self._by_address: Dict[bytes, int] = {
            v.address: i for i, v in enumerate(vals)
        }
        if len(self._by_address) != len(vals):
            raise ValueError("duplicate validator address")
        self.proposer: Optional[Validator] = None
        if vals:
            self.proposer = self._compute_max_priority_validator()

    # --- basic accessors -------------------------------------------------

    def __len__(self) -> int:
        return len(self.validators)

    def size(self) -> int:
        return len(self.validators)

    def total_voting_power(self) -> int:
        # memoized like hash(): every add_vote compares accumulated
        # power against the total, so an unmemoized sum here is O(V)
        # per vote = O(V^2) per height (analysis/scaling.py's probe
        # measures the slope). Powers only change through
        # update_with_change_set, which drops the memo.
        tp = getattr(self, "_total_power", None)
        if tp is None:
            tp = sum(v.voting_power for v in self.validators)  # bftlint: disable=ASY117 — memoized: this sum reruns once per membership/power change, not per message
            if tp > MAX_TOTAL_VOTING_POWER:
                raise ValueError("total voting power overflow")
            self._total_power = tp
        return tp

    def columns(self) -> ValidatorColumns:
        # memoized like total_voting_power(): a set lives for
        # thousands of heights and every commit of every one of them
        # is verified against these. Addresses, keys and powers only
        # change through update_with_change_set, which drops the memo.
        cols = getattr(self, "_columns", None)
        if cols is None:
            vals = self.validators
            pub_keys = [v.pub_key for v in vals]
            cols = ValidatorColumns(
                [v.address for v in vals],
                pub_keys,
                [v.voting_power for v in vals],
                _key_rows(pub_keys),
            )
            self._columns = cols
        return cols

    def has_address(self, addr: bytes) -> bool:
        return addr in self._by_address

    def get_by_address(self, addr: bytes):
        i = self._by_address.get(addr)
        if i is None:
            return -1, None
        return i, self.validators[i]

    def get_by_index(self, i: int) -> Optional[Validator]:
        if 0 <= i < len(self.validators):
            return self.validators[i]
        return None

    def hash(self) -> bytes:
        # memoized: the hash covers only (pubkey, power) in canonical
        # order — NOT proposer priorities — so it survives priority
        # rotation and copies unchanged. The replay pipeline hashes
        # the (unchanging) valset twice per height without this.
        h = getattr(self, "_hash", None)
        if h is None:
            h = merkle.hash_from_byte_slices(
                [v.encode() for v in self.validators]
            )
            self._hash = h
        return h

    def copy(self) -> "ValidatorSet":
        vs = ValidatorSet.__new__(ValidatorSet)
        vs.validators = [v.copy() for v in self.validators]
        vs._by_address = dict(self._by_address)
        vs._hash = getattr(self, "_hash", None)
        vs._total_power = getattr(self, "_total_power", None)
        # immutable by convention and equal for the copy (priorities
        # are not in them): shared, like the hash
        vs._columns = getattr(self, "_columns", None)
        vs.proposer = (
            None
            if self.proposer is None
            else vs.validators[self._by_address[self.proposer.address]]
        )
        return vs

    # --- proposer rotation ----------------------------------------------

    def _compute_max_priority_validator(self) -> Validator:
        best = self.validators[0]
        for v in self.validators[1:]:
            if v.compare_proposer_priority(best) < 0:
                best = v
        return best

    def _rescale_priorities(self) -> None:
        if not self.validators:
            return
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        pmax = max(v.proposer_priority for v in self.validators)
        pmin = min(v.proposer_priority for v in self.validators)
        diff = pmax - pmin
        if diff > 0 and diff > diff_max:
            ratio = (diff + diff_max - 1) // diff_max
            for v in self.validators:
                v.proposer_priority = _int_div_round_to_zero(
                    v.proposer_priority, ratio
                )

    def _shift_by_avg_proposer_priority(self) -> None:
        if not self.validators:
            return
        avg = _int_div_round_to_zero(
            sum(v.proposer_priority for v in self.validators),
            len(self.validators),
        )
        for v in self.validators:
            v.proposer_priority -= avg

    def increment_proposer_priority(self, times: int) -> None:
        if not self.validators:
            return
        self._rescale_priorities()
        self._shift_by_avg_proposer_priority()
        proposer = self.proposer
        for _ in range(times):
            for v in self.validators:
                v.proposer_priority += v.voting_power
            proposer = self._compute_max_priority_validator()
            proposer.proposer_priority -= self.total_voting_power()
        self.proposer = proposer

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        vs = self.copy()
        vs.increment_proposer_priority(times)
        return vs

    def get_proposer(self) -> Optional[Validator]:
        return self.proposer

    # --- updates ---------------------------------------------------------

    def update_with_change_set(self, changes: Sequence[Validator]) -> None:
        """Apply validator updates: power 0 removes, new adds, else updates
        (reference types/validator_set.go:updateWithChangeSet)."""
        if not changes:
            return
        seen = set()
        for c in changes:
            if c.address in seen:
                raise ValueError("duplicate address in changes")
            seen.add(c.address)
            if c.voting_power < 0:
                raise ValueError("negative voting power")

        removals = {c.address for c in changes if c.voting_power == 0}
        updates = [c for c in changes if c.voting_power > 0]
        for addr in removals:
            if addr not in self._by_address:
                raise ValueError("removing unknown validator")

        # index once: the per-validator `next(...)` scans here were
        # O(V x changes) — the exact nested-committee-loop shape
        # ASY118 exists to catch (a 128-validator set churning a
        # quarter of its members paid ~8k scans per update)
        upd_by_addr = {c.address: c for c in updates}

        # compute priority for new validators: -1.125 * new total power
        new_total = sum(
            c.voting_power for c in updates if c.address not in self._by_address
        )
        for v in self.validators:
            if v.address not in removals:
                upd = upd_by_addr.get(v.address)
                if upd is None:
                    new_total += v.voting_power
                else:
                    new_total += upd.voting_power
        if new_total > MAX_TOTAL_VOTING_POWER:
            raise ValueError("total voting power overflow after update")

        new_vals: List[Validator] = []
        for v in self.validators:
            if v.address in removals:
                continue
            upd = upd_by_addr.get(v.address)
            if upd is not None:
                v = v.copy()
                v.voting_power = upd.voting_power
                if isinstance(upd.pub_key, type(v.pub_key)):
                    v.pub_key = upd.pub_key
            new_vals.append(v)
        existing = {v.address for v in new_vals}
        for c in updates:
            if c.address not in existing:
                nv = c.copy()
                nv.proposer_priority = -(new_total + new_total // 8)
                new_vals.append(nv)

        if not new_vals:
            raise ValueError("validator set cannot become empty")
        new_vals.sort(key=lambda v: (-v.voting_power, v.address))
        self.validators = new_vals
        self._by_address = {v.address: i for i, v in enumerate(new_vals)}
        # membership/power changed: drop the memos
        self._hash = None
        self._total_power = None
        self._columns = None
        self._shift_by_avg_proposer_priority()
        self.proposer = self._compute_max_priority_validator()

    def validate_basic(self) -> None:
        if not self.validators:
            raise ValueError("empty validator set")
        self.total_voting_power()


def _int_div_round_to_zero(a: int, b: int) -> int:
    """Go-style integer division (truncates toward zero)."""
    q = abs(a) // abs(b)
    if (a < 0) != (b < 0):
        q = -q
    return q


def random_validator_set(n: int, power: int = 100) -> tuple:
    """Test helper: returns (ValidatorSet, [Ed25519PrivKey]) sorted to
    match validator order."""
    from ..crypto.keys import Ed25519PrivKey

    privs = [Ed25519PrivKey.generate() for _ in range(n)]
    vals = [Validator(p.pub_key(), power) for p in privs]
    vs = ValidatorSet(vals)
    order = {v.address: i for i, v in enumerate(vs.validators)}
    privs.sort(key=lambda p: order[p.pub_key().address()])
    return vs, privs
