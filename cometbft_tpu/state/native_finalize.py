"""Native finalize lane (native/finalize.cpp): loader + portable twin.

One GIL-releasing call per block performs everything the finalize
data path hashes or encodes per-item in Python: per-tx SHA-256, the
``ExecTxResult`` encodes feeding ``LastResultsHash``, the RFC 6962
fold itself, and the ABCI event/attr encoding shared by the stored
finalize response, the indexer bundle and the fan-out payloads
(state/execution.py threads the :class:`FinalizeArtifacts` through
all three consumers — the events are FLATTENED ONCE here, never
re-walked per consumer).

Follows the wirecodec loader discipline exactly (utils/wirecodec.py,
PR 14): built on demand with g++ into ~/.cache/cometbft_tpu
(override with FINALIZE_SO_DIR), ``prewarm()`` kicks the one-time
build on a daemon thread from ``build_node`` so no event loop ever
pays the compile, ``module()`` never blocks a caller on an in-flight
build, and the portable pure-Python path below is byte-identical —
the semantic source of truth and the no-compiler fallback
(differential-tested in tests/test_native_finalize.py).
GRAFT_NATIVE_FINALIZE=0 disables.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sysconfig
import threading
from typing import List, Optional, Sequence, Tuple

from ..abci import types as abci
from ..utils import proto

_SRC = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    "native",
    "finalize.cpp",
)
_SO = os.path.join(
    os.environ.get(
        "FINALIZE_SO_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "cometbft_tpu"),
    ),
    "_finalize.so",
)

_mod = None
_tried = False
_lock = threading.Lock()


def prewarm():
    """Kick the one-time native build on a daemon thread so no event
    loop ever pays the compile (node/inprocess.build_node calls this
    right next to the wirecodec prewarm). Free once built."""
    if _tried:
        return None
    t = threading.Thread(
        target=module, name="finalize-prewarm", daemon=True
    )
    t.start()
    return t


def module():
    """The extension module, or None (no compiler / disabled).

    Loop-safe by construction (the wirecodec contract): while another
    thread is mid-build the lock acquire is NON-blocking and we
    return None for now — every caller keeps the portable path, and
    the next call after the build finishes gets the module."""
    global _mod, _tried
    if _tried:
        return _mod
    if not _lock.acquire(blocking=False):
        # a build is in flight elsewhere (usually the prewarm
        # thread): fall back rather than park this thread on a
        # multi-second g++ run
        return None
    try:
        if _tried:
            return _mod
        _tried = True
        if os.environ.get("GRAFT_NATIVE_FINALIZE") == "0":
            return None
        try:
            if (
                not os.path.exists(_SO)
                or os.path.getmtime(_SO) < os.path.getmtime(_SRC)
            ):
                os.makedirs(os.path.dirname(_SO), exist_ok=True)
                # one-time lazy native build; loop callers never park
                # here (non-blocking acquire above + build_node
                # prewarm thread) — sanctioned blocking sink
                subprocess.run(  # bftlint: disable=ASY114 — one-time lazy native build; loop callers never park here (non-blocking acquire + prewarm)
                    [
                        "g++",
                        "-O2",
                        "-std=c++17",
                        "-shared",
                        "-fPIC",
                        "-I",
                        sysconfig.get_paths()["include"],
                        _SRC,
                        "-o",
                        _SO,
                        "-ldl",  # sha256 one-shot dlopens libcrypto
                    ],
                    check=True,
                    capture_output=True,
                )
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "_finalize", _SO
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _mod = mod
        except Exception:  # pragma: no cover - toolchain-dependent
            _mod = None
        return _mod
    finally:
        _lock.release()


# --- shared flattened form ---------------------------------------------
#
# FlatEvent = (type_str, [(key_str, value_str, index_bool), ...]).
# Built ONCE per event via abci.attr_kvi — the single flatten every
# downstream consumer (stored response, indexer rows, fan-out attrs)
# reads instead of re-walking Event.attributes itself.

FlatEvent = Tuple[str, List[Tuple[str, str, bool]]]


def flatten_events(events) -> List[FlatEvent]:
    """The one attr_kvi pass per event list."""
    return [
        (e.type_, [abci.attr_kvi(a) for a in e.attributes])
        for e in (events or [])
    ]


def encode_event_flat(fe: FlatEvent) -> bytes:
    """Portable ``_enc_abci_event`` over the flattened form —
    byte-identical to encoding the Event itself."""
    type_, kvis = fe
    out = proto.field_string(1, type_)
    for k, v, idx in kvis:
        out += proto.field_bytes(
            2,
            proto.field_string(1, k)
            + proto.field_string(2, v)
            + proto.field_varint(3, 1 if idx else 0),
        )
    return out


def encode_events_flat(flat: Sequence[FlatEvent]) -> List[bytes]:
    """Encoded-event bytes per flattened event; native when built."""
    nat = module()
    if nat is not None and flat:
        try:
            return nat.encode_events(
                [
                    (
                        t.encode(),
                        [(k.encode(), v.encode(), 1 if i else 0)
                         for k, v, i in kvis],
                    )
                    for t, kvis in flat
                ]
            )
        except Exception:  # pragma: no cover - defensive parity net
            pass
    return [encode_event_flat(fe) for fe in flat]


class FinalizeArtifacts:
    """Everything the finalize path derives from (txs, tx_results),
    computed once per block and threaded through the stored response,
    state update, event bus, indexer and fan-out:

    - ``tx_hashes[i]``       sha256(txs[i]) — EVENT_TX hash attr +
                             the indexer's ``tx:h:`` row key
    - ``results_enc[i]``     ``tx_results[i].encode()`` bytes, reused
                             by BOTH LastResultsHash and the stored
                             finalize response (encoded exactly once)
    - ``results_hash``       RFC 6962 root over ``results_enc``
    - ``tx_events_flat[i]``  flattened events of tx i (FlatEvent)
    - ``tx_events_enc[i]``   ``_enc_abci_event`` bytes per event of
                             tx i, shared by the stored response and
                             the indexer record rows
    - ``block_events_flat``/``block_events_enc`` — same pair for the
      block-level events
    """

    __slots__ = (
        "tx_hashes",
        "results_enc",
        "results_hash",
        "tx_events_flat",
        "tx_events_enc",
        "block_events_flat",
        "block_events_enc",
        "native",
    )

    def __init__(
        self,
        tx_hashes,
        results_enc,
        results_hash,
        tx_events_flat,
        tx_events_enc,
        block_events_flat,
        block_events_enc,
        native: bool,
    ):
        self.tx_hashes = tx_hashes
        self.results_enc = results_enc
        self.results_hash = results_hash
        self.tx_events_flat = tx_events_flat
        self.tx_events_enc = tx_events_enc
        self.block_events_flat = block_events_flat
        self.block_events_enc = block_events_enc
        self.native = native


def _portable_pass(txs, flat_results):
    """Byte-for-byte twin of the native finalize_pass (the semantic
    source of truth): sha256 per tx, ExecTxResult encode per result,
    binary-carry RFC 6962 fold, event encodes."""
    sha = hashlib.sha256
    tx_hashes = [sha(tx).digest() for tx in txs]
    results_enc = []
    tx_events_enc = []
    for code, data, gw, gu, codespace, flat in flat_results:
        results_enc.append(
            proto.field_varint(1, code)
            + proto.field_bytes(2, data)
            + proto.field_varint(5, gw)
            + proto.field_varint(6, gu)
            + proto.field_string(8, codespace)
        )
        tx_events_enc.append([encode_event_flat(fe) for fe in flat])
    from ..crypto import merkle

    res_hash = merkle.hash_from_byte_slices(results_enc)
    return tx_hashes, results_enc, res_hash, tx_events_enc


def finalize_pass(
    txs: Sequence[bytes], resp, portable: Optional[bool] = None
) -> FinalizeArtifacts:
    """The one pass per block. ``resp`` is the app's
    ResponseFinalizeBlock; ``portable=True`` forces the Python twin
    (the differential tests).

    The flatten itself (attr_kvi over every event) happens exactly
    once, HERE, regardless of backend — the artifacts carry the
    flattened form so no downstream consumer walks attributes again.
    """
    tx_events_flat = [flatten_events(r.events) for r in resp.tx_results]
    block_events_flat = flatten_events(resp.events)
    flat_results = [
        (r.code, r.data, r.gas_wanted, r.gas_used, r.codespace, flat)
        for r, flat in zip(resp.tx_results, tx_events_flat)
    ]
    nat = None if portable else module()
    native = False
    if nat is not None:
        try:
            tx_hashes, results_enc, res_hash, tx_events_enc = (
                nat.finalize_pass(
                    list(txs),
                    [
                        (
                            code,
                            data,
                            gw,
                            gu,
                            codespace.encode(),
                            [
                                (
                                    t.encode(),
                                    [
                                        (k.encode(), v.encode(),
                                         1 if i else 0)
                                        for k, v, i in kvis
                                    ],
                                )
                                for t, kvis in flat
                            ],
                        )
                        for code, data, gw, gu, codespace, flat
                        in flat_results
                    ],
                )
            )
            native = True
        except Exception:  # pragma: no cover - defensive parity net
            tx_hashes, results_enc, res_hash, tx_events_enc = (
                _portable_pass(txs, flat_results)
            )
    else:
        tx_hashes, results_enc, res_hash, tx_events_enc = _portable_pass(
            txs, flat_results
        )
    return FinalizeArtifacts(
        tx_hashes=tx_hashes,
        results_enc=results_enc,
        results_hash=res_hash,
        tx_events_flat=tx_events_flat,
        tx_events_enc=tx_events_enc,
        block_events_flat=block_events_flat,
        block_events_enc=encode_events_flat(block_events_flat)
        if not portable
        else [encode_event_flat(fe) for fe in block_events_flat],
        native=native,
    )


def part_leaf_hashes(chunks: Sequence[bytes]) -> Optional[List[bytes]]:
    """Native RFC 6962 leaf hashes for the proposal path's block-part
    chunks (sha256(0x00 || chunk) per part, GIL released), or None
    when the extension is unavailable — PartSet.from_data then hashes
    the leaves in Python via merkle.proofs_from_byte_slices."""
    nat = module()
    if nat is None:
        return None
    try:
        return nat.leaf_hashes(list(chunks))
    except Exception:  # pragma: no cover - defensive parity net
        return None
