"""Routing policy of signature verification: where a batch's ed25519
lanes verify, on the device or on the host.

Every consumer submits its ``(pubkey, msg, sig)`` lanes to the verify
scheduler (crypto/scheduler.py), which splits them by curve and asks
``decide()`` here, once a ticket, where the ed25519 lanes go. This
module is that policy and nothing else:

- the floor (``_MIN_TPU_BATCH``, ``set_min_tpu_batch``) under which
  the device is never considered, and whose value 1 FORCES it;
- the measured host-vs-device crossover (``calibration``, an EWMA the
  scheduler's readiness watcher and host chunks feed);
- the backend NAME the operator chose (``set_default_backend``, the
  ``[crypto] batch_backend`` knob): ``tpu`` | ``cpu`` |
  ``cpu-parallel`` | ``mesh``;
- ``decide()``, the whole decision, and ``LAST_ROUTE``, what it last
  chose (tests, the chip smoke and the driver's dryrun read it).

Unlike the reference's random-linear-combination batch
(crypto/batch/batch.go:10) the kernel returns per-signature verdicts,
so there is no second fall-back pass on failure; and where the
reference abandons batching when key types are mixed
(types/validation.go shouldBatchVerify), the scheduler verifies the
non-ed25519 lanes on the host and re-interleaves the verdicts.

``CpuBatchVerifier`` is the plain serial reference (one ``pk.verify``
a lane, in order) that tests and the chip smoke compare verdicts
against; nothing in the node constructs it.
"""

from __future__ import annotations

import threading
from typing import List, Tuple

from ..utils import device
from .keys import Ed25519PubKey, PubKey

# Floor below which the device is never considered. The REAL cutoff is
# measured at runtime (_Calibration below): one XLA dispatch has a
# flat cost that depends on the chip, the lane bucket it pads to and
# the host, so a static constant is wrong somewhere (VERDICT r2 weak
# #3: the r2 value routed 150-sig commits to a 98ms dispatch that
# costs 12ms on host).
# Setting it to <= 1 (set_min_tpu_batch(1)) FORCES the device path,
# bypassing calibration — tests, the chip smoke and the driver dryrun
# rely on that.
_MIN_TPU_BATCH = 64


def set_min_tpu_batch(n: int) -> None:
    global _MIN_TPU_BATCH
    _MIN_TPU_BATCH = n


class _Calibration:
    """Measured host-vs-device crossover (the reference's dual path —
    per-vote single verify vs batch, types/validation.go:15-21 — made
    measurement-driven).

    Model: device_wall(n) = flat + n*lane_s; host_wall(n) = n*host_s.
    All three parameters are EWMAs of observed walls. Samples that are
    clearly compiles (wall > _COMPILE_CUTOFF_S) never enter the EWMA.
    Seeds are optimistic for the device so bulk paths try it; two
    dispatches are enough to learn a high real flat cost and stop
    sending small batches there.
    """

    _COMPILE_CUTOFF_S = 10.0
    _ALPHA = 0.4
    EXPLORE_EVERY = 256

    def __init__(self) -> None:
        self.host_s = 80e-6     # ~80us/sig OpenSSL (measured r2)
        self.lane_s = 3.5e-6    # bulk kernel ~3.5us/lane (BENCH_r02)
        self.flat_s = 5e-3      # optimistic local-chip dispatch seed
        self.device_samples = 0
        self._host_streak = 0
        self._lock = threading.Lock()

    def observe_host(self, n: int, wall: float) -> None:
        if n <= 0 or wall <= 0:
            return
        with self._lock:
            self.host_s += self._ALPHA * (wall / n - self.host_s)

    def observe_device(self, n: int, wall: float) -> None:
        if n <= 0 or not (0 < wall < self._COMPILE_CUTOFF_S):
            return
        with self._lock:
            # The FIRST sample for a process often includes an XLA
            # compile; a 0.1-10s compile wall entering the EWMA would
            # inflate flat_s so far that the device path is never
            # chosen again (and never observed again = frozen). Accept
            # a first sample only when it clearly isn't a compile.
            if self.device_samples == 0 and wall >= 1.0:
                return
            flat_obs = max(wall - n * self.lane_s, 1e-5)
            self.flat_s += self._ALPHA * (flat_obs - self.flat_s)
            self.device_samples += 1

    def device_wins(self, n: int) -> bool:
        with self._lock:
            return self.flat_s + n * self.lane_s < n * self.host_s

    def should_explore(self) -> bool:
        """Recovery path for a poisoned flat_s: a 1-10s recompile or
        device stall that slips past the compile filter inflates the
        EWMA, every batch then routes to host, and without device
        traffic the estimate could never heal. Every EXPLORE_EVERY
        host-routed eligible batches, one is sent to the device anyway;
        its (filtered) wall pulls flat_s back toward reality."""
        with self._lock:
            self._host_streak += 1
            if self._host_streak >= self.EXPLORE_EVERY:
                self._host_streak = 0
                return True
            return False

    def note_device_used(self) -> None:
        with self._lock:
            self._host_streak = 0

    def crossover(self) -> int:
        """Smallest batch the device is predicted to win."""
        with self._lock:
            margin = self.host_s - self.lane_s
            if margin <= 0:
                return 1 << 30
            return max(1, int(self.flat_s / margin) + 1)


calibration = _Calibration()


def _jax_backend_is_cpu() -> bool:
    """True when the process's jax backend is the CPU platform: the
    unforced device route is then pointless (it would XLA-compile the
    kernel for the host, which OpenSSL beats) and is skipped. Forced
    routing (set_min_tpu_batch(1) — the dryrun/tests) is unaffected:
    the virtual-mesh validation deliberately runs the kernel on CPU.
    A backend that cannot start raises (utils/device)."""
    return device.on_cpu()


# Last routing decision (observability: tests, the chip smoke and the
# driver's dryrun report which path decide() actually chose).
LAST_ROUTE = {"path": None, "n": 0, "crossover": None}


class CpuBatchVerifier:
    """Sequential host verification: accumulate lanes with ``add()``,
    ``verify()`` returns ``(all_ok, per_item_ok)`` in ``add()`` order.
    The correctness baseline the scheduler's verdicts are tested
    against."""

    def __init__(self) -> None:
        self.items: List[Tuple[PubKey, bytes, bytes]] = []

    def add(self, pk: PubKey, msg: bytes, sig: bytes) -> None:
        self.items.append((pk, msg, sig))

    def verify(self) -> Tuple[bool, List[bool]]:
        oks = [pk.verify(msg, sig) for pk, msg, sig in self.items]
        return all(oks) and bool(oks), oks

    def __len__(self) -> int:
        return len(self.items)


# Names mirror the config knob (config.CryptoConfig.batch_backend).
BACKENDS = ("tpu", "cpu", "cpu-parallel", "mesh")

_default_backend = "tpu"
_lock = threading.Lock()


def default_backend() -> str:
    """Name of the backend ``decide()`` routes by."""
    with _lock:
        return _default_backend


def set_default_backend(name: str) -> None:
    """One of ``BACKENDS`` (process-wide; mirrors the config knob)."""
    global _default_backend
    assert name in BACKENDS, (name, BACKENDS)
    with _lock:
        _default_backend = name


def decide(n_ed: int) -> Tuple[str, str, bool]:
    """The routing decision for ``n_ed`` ed25519 lanes of one ticket:
    ``(path, backend, degraded)`` with path ``device`` | ``host``,
    backend the configured name (what ``ticket.backend`` becomes) or
    ``mesh-degraded`` where ``mesh`` is configured and fewer than two
    devices exist, and ``degraded`` true in that case alone."""
    backend = default_backend()
    degraded = False
    forced = _MIN_TPU_BATCH <= 1
    cal = calibration
    use_device = False
    if backend == "tpu":
        # calibration first: the backend probe imports jax and
        # initializes the platform, so it must only run when the
        # device route is otherwise about to be taken
        use_device = n_ed >= _MIN_TPU_BATCH and (
            forced
            or (
                (cal.device_wins(n_ed) or cal.should_explore())
                and not _jax_backend_is_cpu()
            )
        )
        if use_device and not forced:
            cal.note_device_used()
    elif backend == "mesh":
        # explicit operator choice: shard whenever a mesh exists
        # (no calibration gate — the mesh IS the configured
        # plane); honor the batch floor so tiny commits stay on
        # host, and degrade to host chunks with no mesh
        if device.backend().count > 1:
            use_device = n_ed > 0 and (forced or n_ed >= _MIN_TPU_BATCH)
        else:
            backend = "mesh-degraded"
            degraded = True
    path = "device" if use_device else "host"
    LAST_ROUTE.update(
        path=path,
        n=n_ed,
        crossover=None if forced else cal.crossover(),
    )
    return path, backend, degraded


def supports_batch_verification(pk: PubKey) -> bool:
    """Mirrors crypto/batch.SupportsBatchVerifier — but note the
    scheduler also absorbs mixed sets by splitting (see module doc)."""
    return isinstance(pk, Ed25519PubKey)
