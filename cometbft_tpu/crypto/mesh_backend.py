"""Multi-chip mesh batch-verification backend (registered as "mesh").

Promotes the MULTICHIP_r04/r05 dryrun path into a first-class,
config-selectable backend (config.CryptoConfig.batch_backend =
"mesh"): ed25519 lanes are sharded across every local device through
the shard_map/PartitionSpec program ops/ed25519 builds over
parallel/mesh.make_mesh — signature lanes are the data axis, each
device verifies its slice, verdicts gather back in lane order
(docs/PERF.md "Unified verify scheduler", SNIPPETS pjit pattern).

Degradable contract (the common path on a throttled 2-vCPU box with
no mesh): when fewer than two devices materialize — or the device
dispatch itself fails — the batch verifies on the cpu-parallel host
plane instead, bit-identically and WITHOUT wedging. Selecting "mesh"
is therefore always safe; it means "shard when you can, host
otherwise", and the degrade is visible (``LAST_MESH`` + scheduler
``degraded`` counter + the bench verify-sched leg's structured
record).

Unlike the "tpu" backend there is no calibration gate: the operator
explicitly chose sharded dispatch, so any eligible batch (>= the
_MIN_TPU_BATCH floor, set_min_tpu_batch(1) forces) goes to the mesh.
"""

from __future__ import annotations

from typing import List, Tuple

from ..utils import device
from ..utils.log import get_logger
from .batch import (
    BatchVerifier,
    ResolvedVerdicts,
    _PendingVerdicts,
)
from . import batch as crypto_batch
from .keys import Ed25519PubKey, PubKey

_log = get_logger("crypto.mesh")

# Introspection: how the last mesh-backend verify dispatched
# (tests + the bench verify-sched leg's parity gate).
LAST_MESH = {"path": None, "n": 0, "devices": 0}


def mesh_devices() -> int:
    """Local device count (memoised in utils/device); a mesh exists
    when > 1. A backend that cannot start raises."""
    return device.backend().count


class MeshBatchVerifier(BatchVerifier):
    """Shards ed25519 lanes over the device mesh; degrades to the
    cpu-parallel host plane when no mesh materializes. Verdict parity
    with CpuBatchVerifier is differential-tested
    (tests/test_verify_scheduler.py) and gated in-bench."""

    def __init__(self) -> None:
        self.items: List[Tuple[PubKey, bytes, bytes]] = []

    def add(self, pk: PubKey, msg: bytes, sig: bytes) -> None:
        self.items.append((pk, msg, sig))

    def __len__(self) -> int:
        return len(self.items)

    def _split(self):
        ed_idx, ed_items, other_idx = [], [], []
        for i, (pk, msg, sig) in enumerate(self.items):
            if isinstance(pk, Ed25519PubKey):
                ed_idx.append(i)
                ed_items.append((msg, pk.key_bytes, sig))
            else:
                other_idx.append(i)
        return ed_idx, ed_items, other_idx

    def _use_mesh(self, n_ed: int) -> bool:
        devices = mesh_devices()
        floor = max(crypto_batch._MIN_TPU_BATCH, 1)
        use = devices > 1 and n_ed >= floor
        LAST_MESH.update(
            path="mesh" if use else "host", n=n_ed, devices=devices
        )
        return use

    def _host(self, oks, ed_idx, other_idx) -> Tuple[bool, List[bool]]:
        if ed_idx:
            from .parallel_verify import engine

            verdicts = engine().verify([self.items[i] for i in ed_idx])
            for i, v in zip(ed_idx, verdicts):
                oks[i] = v
        for i in other_idx:
            pk, msg, sig = self.items[i]
            oks[i] = pk.verify(msg, sig)
        return all(oks) and bool(oks), oks

    def verify(self) -> Tuple[bool, List[bool]]:
        ed_idx, ed_items, other_idx = self._split()
        oks = [False] * len(self.items)
        if self._use_mesh(len(ed_items)):
            try:
                from ..ops import ed25519 as _ed

                verdicts = _ed.verify_batch(ed_items)
            except Exception as e:
                _log.error(
                    "mesh dispatch failed; host degrade",
                    err=repr(e),
                    lanes=len(ed_items),
                )
                LAST_MESH["path"] = "host-degraded"
                return self._host(oks, ed_idx, other_idx)
            for i, v in zip(ed_idx, verdicts):
                oks[i] = bool(v)
            for i in other_idx:
                pk, msg, sig = self.items[i]
                oks[i] = pk.verify(msg, sig)
            return all(oks) and bool(oks), oks
        return self._host(oks, ed_idx, other_idx)

    def verify_async(self):
        ed_idx, ed_items, other_idx = self._split()
        oks = [False] * len(self.items)
        if not self._use_mesh(len(ed_items)):
            return ResolvedVerdicts(*self._host(oks, ed_idx, other_idx))
        try:
            from ..ops import ed25519 as _ed

            handle = _ed.verify_batch_async(ed_items)
        except Exception as e:
            _log.error(
                "mesh async dispatch failed; host degrade",
                err=repr(e),
                lanes=len(ed_items),
            )
            LAST_MESH["path"] = "host-degraded"
            return ResolvedVerdicts(*self._host(oks, ed_idx, other_idx))
        for i in other_idx:
            pk, msg, sig = self.items[i]
            oks[i] = pk.verify(msg, sig)
        return _PendingVerdicts(handle, ed_idx, oks)
