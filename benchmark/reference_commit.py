"""The plain reference of FULL commit verification: ``VerifyCommit``
(upstream types/validation.go:30), written straight down over plain
data, beside ``reference.light_verify``.

Imports nothing of the program: the sign-bytes, the ``Verifier`` and
the ZIP-215 integers are ``benchmark/reference.py``'s own. What full
verification adds to light verification:

  every non-absent signature is checked, in validator order, the nil
  votes too (over the CanonicalVote of a nil block id: field 4 left
  out), and the first invalid one is named;
  only then the tally: the power of the votes FOR the block against
  more than 2/3 of the set's; a nil vote is verified and not tallied.

So a signature past the lane with which light verification stops is
still read, and a commit whose +2/3 is sound but whose last signature
is not is refused, by that signature's validator index.
"""

from __future__ import annotations

from benchmark import reference
from benchmark.reference import FLAG_COMMIT

FLAG_ABSENT = 1  # BlockIDFlagAbsent
FLAG_NIL = 3  # BlockIDFlagNil


def nil_vote_sign_bytes(
    chain_id: str, height: int, round_: int, timestamp_ns: int
) -> bytes:
    """Sign-bytes of a precommit for nil: ``reference.vote_sign_bytes``
    without the block id (CanonicalVote.block_id is left out when nil,
    upstream types/canonical.go CanonicalizeBlockID)."""
    secs, nanos = divmod(timestamp_ns, 1_000_000_000)
    body = (
        reference._f_varint(1, reference.PRECOMMIT)
        + reference._f_sfixed64(2, height)
        + reference._f_sfixed64(3, round_)
        + reference._f_message(
            5, reference._f_varint(1, secs) + reference._f_varint(2, nanos)
        )
        + reference._f_bytes(6, chain_id.encode())
    )
    return reference._varint(len(body)) + body


def sign_bytes(chain_id: str, commit: dict, flag: int, timestamp_ns: int) -> bytes:
    """What the vote at one commit signature signed, by its flag."""
    if flag == FLAG_COMMIT:
        return reference.vote_sign_bytes(
            chain_id, commit["height"], commit["round"],
            commit["block_hash"], commit["parts_total"],
            commit["parts_hash"], timestamp_ns,
        )
    return nil_vote_sign_bytes(
        chain_id, commit["height"], commit["round"], timestamp_ns
    )


def full_verify(verifier: reference.Verifier, chain_id: str, vals, commit):
    """VerifyCommit over plain data.

    vals: [(public, power)] in validator order; commit: as
    ``reference.light_verify`` takes it, sigs [(flag, timestamp_ns,
    signature)] in the same order, absent ones with any filler.
    Returns (error, index, lanes): None, "invalid_signature" with the
    first failing validator's index, or "not_enough_power"; lanes is
    how many signatures full verification reads (the non-absent
    ones)."""
    total = sum(p for _, p in vals)
    lanes = [
        i for i, (flag, _, _) in enumerate(commit["sigs"]) if flag != FLAG_ABSENT
    ]
    tallied = 0
    for i in lanes:
        flag, ts, sig = commit["sigs"][i]
        if not verifier.verify(
            vals[i][0], sign_bytes(chain_id, commit, flag, ts), sig
        ):
            return "invalid_signature", i, len(lanes)
        if flag == FLAG_COMMIT:
            tallied += vals[i][1]
    if not tallied * 3 > total * 2:
        return "not_enough_power", None, len(lanes)
    return None, None, len(lanes)
