"""chip_smoke.py rehearsed on the CPU: its phase functions at a tiny
size with the host verifier standing in for the kernel, so wrong
paths, arguments and control flow are found before chip time is spent
(the kernel's own answers are checked only on the chip)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
from cometbft_tpu.crypto import scheduler as crypto_sched  # noqa: E402
from cometbft_tpu.crypto.lanes import LaneBatch  # noqa: E402
from cometbft_tpu.ops import ed25519 as ed  # noqa: E402

SEED = 7


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    home = str(tmp_path_factory.mktemp("smoke_chain"))
    return chip_smoke.build_chain(SEED, 8, 40, home)


@pytest.fixture
def restore_backend():
    yield
    crypto_batch.set_default_backend("cpu")
    crypto_sched.set_scheduler(None)


@pytest.fixture
def host_kernel(monkeypatch):
    """ops/ed25519's two entry points answered by the serial host
    verifier, reporting the dispatch a one-chip run would: call the
    fixture's value with the ladder to report."""

    def install(ladder, interpret=False, n_devices=1):
        import jax.numpy as jnp

        def fake_async(items):
            if isinstance(items, LaneBatch):  # a window's lanes by columns
                items = [
                    (msg, key.tobytes(), sig.tobytes())
                    for msg, key, sig in zip(items.msgs, items.keys, items.sigs)
                ]
            oks = np.array(chip_smoke.host_verdicts(items), bool)
            ed.LAST_DISPATCH.clear()
            ed.LAST_DISPATCH.update(
                sharded=n_devices > 1, n_devices=n_devices, lanes=len(items),
                backend_key=(ladder, False, 0, 0), interpret=interpret,
            )
            return ed.AsyncVerdicts(
                jnp.asarray(oks), np.zeros(len(oks), bool), len(oks)
            )

        monkeypatch.setattr(ed, "verify_batch_async", fake_async)
        monkeypatch.setattr(
            ed, "verify_batch", lambda items: fake_async(items).result()
        )

    return install


def test_catchup_equals_serial_reference(chain, restore_backend, capsys):
    gen, src = chain
    chip_smoke.phase_catchup(
        gen, src, 16, device_backend="cpu-parallel", expect_device=False
    )
    out = capsys.readouterr().out
    assert "catchup[cpu-parallel]: height=39" in out
    assert "catchup[cpu]: height=39" in out


def test_catchup_sends_windows_through_the_scheduler_to_the_device(
    chain, restore_backend, host_kernel, monkeypatch, capsys
):
    """The ``tpu`` leg as the chip runs it — calibrated routing, the
    scheduler's device lane, the dispatch log — with the stand-in
    kernel and the CPU-platform gate opened by the test."""
    gen, src = chain
    host_kernel("xla")
    monkeypatch.setattr(crypto_batch, "calibration", crypto_batch._Calibration())
    monkeypatch.setattr(crypto_batch, "_jax_backend_is_cpu", lambda: False)
    chip_smoke.phase_catchup(gen, src, 16)
    out = capsys.readouterr().out
    assert "catchup[tpu]: height=39" in out
    # the phase itself requires device_dispatches > 0 and checks each;
    # a 15-commit window of 6 signatures each is one of them
    assert "  90 -> 90 | 128 precomp" in out


def test_verdicts_find_the_corrupted_lanes(
    chain, restore_backend, host_kernel, capsys
):
    gen, src = chain
    host_kernel("xla")
    chip_smoke.phase_verdicts(src, gen.chain_id, SEED, 5)
    out = capsys.readouterr().out
    assert "verdicts[commit]: lanes=8 corrupted=5 rejected=5" in out
    assert "verdicts[batch]: lanes=40 corrupted=25 rejected=25" in out


def test_sharded_phase_tallies_on_four_virtual_devices(
    chain, host_kernel, capsys
):
    """``--chips 4``'s phase with the stand-in kernel and the REAL psum
    quorum program on four of the virtual CPU devices."""
    gen, src = chain
    host_kernel("xla", n_devices=4)
    chip_smoke.phase_sharded(src, gen.chain_id, SEED, 10, 4)
    out = capsys.readouterr().out
    assert "verdicts[sharded]: lanes=80 corrupted=25 rejected=25" in out
    assert "quorum tally=550/800 threshold=533 quorum=True host_tally=550" in out


def test_pallas_phase_takes_every_signature_of_the_chain(
    chain, host_kernel, capsys
):
    gen, src = chain
    host_kernel("pallas")
    chip_smoke.phase_pallas(src, gen.chain_id, SEED)
    out = capsys.readouterr().out
    assert "verdicts[pallas]: lanes=320 corrupted=25 rejected=25" in out
    assert "pallas[again]: dispatch lanes=320" in out


@pytest.mark.parametrize(
    "ladder,interpret", [("xla", False), ("pallas", True)],
    ids=["xla_ladder", "interpret_mode"],
)
def test_pallas_phase_refuses_another_ladder(
    chain, host_kernel, ladder, interpret
):
    gen, src = chain
    host_kernel(ladder, interpret)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.phase_pallas(src, gen.chain_id, SEED)


def test_a_missed_corruption_fails_the_phase(chain):
    gen, src = chain
    items, bad = chip_smoke.corrupt(
        chip_smoke.commit_lanes(src, gen.chain_id, [3]), SEED, 1
    )
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_verdicts(
            "all-true", items, bad, [True] * len(items), ref_all=False
        )


@pytest.mark.parametrize(
    "argv", [[], ["--pallas"], ["--chips", "4"]], ids=str
)
def test_main_refuses_without_a_tpu(argv, capsys):
    assert chip_smoke.main(argv) != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line, no phase output
    assert "needs a TPU" in captured.err


@pytest.mark.parametrize(
    "env_dir", [None, "/elsewhere/jax_cache"], ids=["env_unset", "env_set"]
)
def test_compile_cache_has_one_rule(env_dir, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set JAX reads it and no
    directory is set in code; otherwise <checkout>/.jax_cache."""
    import jax

    from cometbft_tpu.utils import device

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: updates.append((key, value))
    )
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        checkout = os.path.dirname(os.path.abspath(chip_smoke.__file__))
        want = os.path.join(checkout, ".jax_cache")
        assert device.setup_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert device.setup_compile_cache() == env_dir
        assert updates == []


def test_only_the_helper_names_the_cache_directory():
    """One helper, no other call to that config key in the tree."""
    root = os.path.dirname(os.path.abspath(chip_smoke.__file__))
    hits = []
    for top, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if not d.startswith(".")]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(top, name)
            if path == os.path.abspath(__file__):
                continue
            with open(path, encoding="utf-8") as f:
                if "jax_compilation_cache_dir" in f.read():
                    hits.append(os.path.relpath(path, root))
    assert hits == [os.path.join("cometbft_tpu", "utils", "device.py")]


def test_an_unstartable_backend_is_an_error_not_the_cpu(monkeypatch):
    import jax

    from cometbft_tpu.ops import fe25519, pallas_ladder
    from cometbft_tpu.utils import device

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    monkeypatch.delenv("GRAFT_COMPACT_FIELD", raising=False)
    monkeypatch.delenv("GRAFT_PALLAS", raising=False)
    monkeypatch.setattr(fe25519, "_COMPACT", None)
    # the mesh branch of the routing decision reads the device count
    monkeypatch.setattr(crypto_batch, "_default_backend", "mesh")
    device.backend.cache_clear()
    try:
        for probe in (
            crypto_batch._jax_backend_is_cpu,
            pallas_ladder.pallas_enabled,
            pallas_ladder.interpret_mode,
            fe25519.compact_mode,
            lambda: crypto_batch.decide(4800),
            lambda: ed._sharded_fn("plain"),
        ):
            with pytest.raises(RuntimeError, match="Unable to initialize"):
                probe()
    finally:
        device.backend.cache_clear()
