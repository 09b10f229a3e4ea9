"""The device contract (core/devices.py) and the chip smoke's phases.

One rule everywhere: run on the accelerator JAX finds, on the CPU only
under an explicit ``JAX_PLATFORMS=cpu``, otherwise exit non-zero. The
compile cache is placed from outside (``JAX_COMPILATION_CACHE_DIR``) or
at one fixed in-checkout path. ``chip_smoke.py`` refuses to run without a
TPU; each of its phases runs here once, tiny, on the CPU.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from igaming_platform_tpu.core import devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- require_device -----------------------------------------------------------


def test_cpu_runs_only_when_asked_for(monkeypatch):
    """This suite pins JAX_PLATFORMS=cpu (conftest), so the CPU is a
    legitimate backend; the same CPU without the request is an exit."""
    from igaming_platform_tpu.serve.server import device_gate

    assert devices.cpu_requested()
    assert devices.require_device() == "cpu"

    monkeypatch.setattr(devices, "cpu_requested", lambda: False)
    for gate in (devices.require_device, device_gate):
        with pytest.raises(SystemExit) as exc:
            gate()
        assert exc.value.code not in (0, None)
        assert "no accelerator" in str(exc.value.code)


def test_backend_that_fails_to_initialise_is_an_exit(monkeypatch):
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(SystemExit) as exc:
        devices.require_device()
    assert "Unable to initialize backend" in str(exc.value.code)


# -- compile cache placement ----------------------------------------------------


@pytest.fixture
def _restore_cache_dir():
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])


def test_cache_dir_from_env_is_left_untouched(monkeypatch, tmp_path,
                                              _restore_cache_dir):
    """JAX binds JAX_COMPILATION_CACHE_DIR itself at import; the program
    adds no sub-directory, no override and no special values — on any
    backend."""
    target = str(tmp_path / "x")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
    jax.config.update("jax_compilation_cache_dir", target)  # what import did
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert devices.enable_persistent_compile_cache() == target
        assert jax.config.jax_compilation_cache_dir == target


def test_cache_dir_unset_resolves_to_fixed_checkout_path(monkeypatch,
                                                         _restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert devices.enable_persistent_compile_cache() is None  # CPU: no cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    want = os.path.join(REPO, ".jax_cache")
    assert devices.enable_persistent_compile_cache() == want
    assert devices.enable_persistent_compile_cache() == want  # twice running
    assert jax.config.jax_compilation_cache_dir == want
    # Every program is cached, not only those over JAX's 1 s default.
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()


# -- chip_smoke.py ---------------------------------------------------------------


def test_chip_smoke_main_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture
def _no_compile_cache(monkeypatch, _restore_cache_dir):
    """The suite's own cache (conftest) taken away: what a CPU boot with
    no JAX_COMPILATION_CACHE_DIR sees."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()  # JAX decides once per process
    yield
    compilation_cache.reset_cache()


def test_smoke_environment_native_and_cache_phases(_no_compile_cache):
    env = chip_smoke.phase_environment(require_tpu=False)
    assert env["device"]["platform"] == "cpu" and env["backend"] == "cpu"
    assert env["cache_dir"] is None and env["cache_dir_from_env"] is False
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_smoke.phase_environment(require_tpu=True)

    native = chip_smoke.phase_native(force=False)
    assert native["gxx"] and os.path.isdir(native["lib_dir"])

    from igaming_platform_tpu.obs.runtime_telemetry import CompileWatcher

    watcher = CompileWatcher()
    watcher.install_listener()
    jax.jit(lambda v: v * 3 + 1)(2.0)
    cache = chip_smoke.phase_cache(watcher, None)
    assert cache["compiles"] >= 1 and cache["persistent_cache_hits"] == 0


_TINY_SERVER = dict(batch_size=32, small_rows=(8,), abuse_events=66,
                    steady_passes=4, singles=1, train_batch=32,
                    store_max_accounts=4096)


@pytest.fixture
def _tiny_server_env(monkeypatch):
    # The model policy (a CPU boot defaults to the heuristic), a short
    # burn-gate idle window and a small table keep the tiny run quick.
    monkeypatch.setenv("ABUSE_CPU_POLICY", "model")
    monkeypatch.setenv("BURN_SHED_IDLE_S", "0.2")
    monkeypatch.setenv("FEATURE_CACHE_CAPACITY", "128")


def test_smoke_server_phase_tiny(_tiny_server_env):
    # The trainer (a ~4 s compile of its own) and the second, meshed
    # server ride the slow-marked test below: tier-1 is near its budget.
    report = chip_smoke.phase_server(train_steps=0, **_TINY_SERVER)
    assert report["steady"]["compiles"] == 0
    assert (report["steady"]["dispatches"]
            == report["steady"]["chunks_sent"] + report["steady"]["hedged"])
    assert report["vs_cpu"]["max_score_delta"] == 0  # CPU vs CPU
    assert report["single_tier"] == "device"         # no host tier on a CPU boot
    assert report["ports_released"] and report["session_rows"]["warm"] > 0
    json.dumps({k: v for k, v in report.items() if k != "index_trace"})

    # Too few devices: the mesh phase says so — it does not pass.
    mesh = chip_smoke.phase_mesh(report, n_devices=len(jax.devices()) + 1)
    assert mesh["result"] == f"not_run ({len(jax.devices())} device)"


@pytest.mark.slow
def test_smoke_trainer_and_mesh_phases_on_virtual_devices(_tiny_server_env):
    one = chip_smoke.phase_server(train_steps=1, **_TINY_SERVER)
    assert one["trainer"]["steps"] == 1 and one["steady"]["compiles"] == 0
    mesh = chip_smoke.phase_mesh(one, 4, train_steps=1, **_TINY_SERVER)
    assert mesh["result"] == "passed"
    assert mesh["parity_vs_one_chip"].startswith("bit-exact")
    assert len(mesh["shards"]["session_ring"]["devices"]) == 4


def test_smoke_kernels_phase_tiny_interpreted():
    report = chip_smoke.phase_kernels(
        interpret=True, attention_shapes=((1, 2, 64, 32),),
        backward_shape=(1, 2, 64, 16), gbdt_batch=256, gbdt_tile=128,
        expert_shape=(512, 1024, 128, 8), second_shape=(256, 2048, 128, 4, 1),
        share_shape=(64, 128, 32, 20), grouped_windows=11, delta_windows=8,
        ssd_windows=8, stream_tiles=1, block_window=40, scan_window=24,
        latent_window=40)
    assert report["interpret"] is True
    # the selective scan (phi4flash's Mamba-1 mixer) on two windows of 24
    # positions, three blocks of eight, against the chunked form
    scanned = report["selective_scan_T24"]
    assert scanned["max_err"] <= chip_smoke.STREAMS_TOL
    assert scanned["core"] == (
        "state-space core: chunks of 128 that hand the state on (5120 "
        "channels, state 16, window 24; not a TPU) (backend=cpu)")
    # the blocked attention core (mellum's two kinds of layer) on one window
    # of 40 positions, a tail of 8 past its blocks of 16-row tiles
    blocked = report["block_attention_T40"]
    assert set(blocked) == {"sliding_attention", "full_attention"}
    for kind, said in blocked.items():
        assert said["max_err"] <= chip_smoke.BACKBONE_TOL, kind
        assert said["core"].startswith(
            "attention core (" + ("window" if kind.startswith("sliding") else "full")
            + "): einsum in query blocks (window 40 in blocks of 48")
        assert said["core"].endswith("not a TPU) (backend=cpu)")
    # the blocked latent core (longcat's attention) on one window of 40
    # positions: one query block of 48 there, so what a trace picks is the
    # one-block core
    latent = report["latent_block_attention_T40"]
    assert latent["max_err"] <= chip_smoke.BACKBONE_TOL
    assert latent["core"] == (
        "attention core: xla-einsum (interleaved rotary pairs: the window "
        "kernel turns by halves) (backend=cpu)")
    assert report["gbdt_vs_gather"] <= chip_smoke.GBDT_TOL
    assert max(report["grouped_experts_M512_E8"]) <= chip_smoke.EXPERTS_TOL
    # how the kernels are fed: hidden 1024 is gathered outside, hidden 2048
    # brought in by ``gate_up`` itself, to the same bits; a ring never
    # makes more first visits wait than two slots do
    assert report["grouped_experts_fed_M512_E8"]["feed"] == (
        "tm=256, ts=64, slots=4/4, rows=gathered")
    fed = report["grouped_experts_fed_M256_E4"]
    assert fed["feed"] == "tm=256, ts=64, slots=4/4, rows=in-kernel"
    assert fed["rows_in_kernel_same_bits"] is True
    waits = fed["first_visits_that_wait"]
    assert 1 <= waits["4_slots"] <= waits["2_slots"] <= fed["experts_with_rows"]
    for key in ("combine_M512_H1024", "combine_share_M64_H128"):
        assert report[key]["max_err"] <= chip_smoke.COMBINE_TOL
        # what a trace would pick here, off the TPU
        assert report[key]["way_back"] == "combine: xla-gather (backend=cpu)"
    # the window kernel's grouped form (keye's attention) on 11 windows, a
    # tile part filled, and the core a trace would pick here, off the TPU
    grouped = report["grouped_attention_W11"]
    assert grouped["max_err"] <= chip_smoke.BACKBONE_TOL
    assert grouped["core"] == (
        "attention core: einsum (not a TPU; mask=keep) (backend=cpu)")
    # handed no mask (``topk`` covers the window: the indexer is not traced)
    assert grouped["no_mask_same_bits_as_causal"] is True
    assert grouped["core_with_no_mask"] == (
        "attention core: einsum (not a TPU; mask=causal; indexer not traced: "
        "topk 2048 >= window 16) (backend=cpu)")
    # the delta-rule window kernel (ling's mixer) on one tile of 8 windows
    delta = report["delta_window_W8"]
    assert delta["max_err"] <= chip_smoke.BACKBONE_TOL
    assert delta["core"] == ("linear-attention core: one chunk by einsums "
                             "(not a TPU) (backend=cpu)")
    # the state-space window kernel (falconh1's mixer) on one tile of 8 windows
    ssd = report["ssd_window_W8"]
    assert ssd["max_err"] <= chip_smoke.BACKBONE_TOL
    assert ssd["core"] == ("state-space core: dual form, one chunk, 16 <= 128 "
                           "(not a TPU) (backend=cpu)")
    # the stream kernels (xing's residual path) on one tile of 128 positions
    streams = report["hyper_streams_T1"]
    assert streams["max_err"] <= chip_smoke.STREAMS_TOL
    assert streams["path"] == ("residual path: xla (not a TPU; 4 streams, 20 "
                               "Sinkhorn rounds) (backend=cpu)")
