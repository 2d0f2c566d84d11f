"""MachineModel layer + always-on telemetry (ISSUE-6).

The machine profile is the paper's latency dial as a runtime input: one
frozen model per named machine, selected process-wide, with every depth
solve / roofline term / feedback-store key derived from the active profile.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core import machine as machine_mod
from repro.core import schedule
from repro.core.machine import (
    MACHINES,
    MachineModel,
    get_machine,
    machine_profile,
    profile_names,
    set_machine,
)
from repro.kernels.coro_gather.coro_gather import row_gather_spec
from repro.kernels.coro_scatter_add.coro_scatter_add import scatter_add_spec
from repro.kernels.decode_attention.decode_attention import decode_spec
from repro.kernels.moe_gmm.moe_gmm import gmm_spec
from repro.kernels.ssd_scan.ssd_scan import ssd_spec
from repro.kernels.stream_copy.stream_copy import triad_spec

# one representative spec per kernel family (the shapes the benches use)
FAMILY_SPECS = {
    "row_gather": lambda: row_gather_spec(8, 128, jnp.float32),
    "scatter_add": lambda: scatter_add_spec(8, 128, jnp.float32),
    "decode": lambda: decode_spec(128, 8, 12, 128, jnp.float32),
    "gmm": lambda: gmm_spec(64, 512, 128, jnp.float32, f_total=2048),
    "ssd": lambda: ssd_spec(64, 8, 64, 128, jnp.float32, seq_len=2048),
    "triad": lambda: triad_spec(128, 512, jnp.float32),
}


# ------------------------------------------------------------ profile table


def test_profile_table_contents():
    for name in ("v5e", "v5e-far-200ns", "v5e-far-800ns", "cpu-interpret",
                 "nh-g"):
        assert name in MACHINES
        assert MACHINES[name].name == name
    assert set(profile_names()) == set(MACHINES)


def test_far_profiles_dial_latency_only():
    base = machine_profile("v5e")
    far2 = machine_profile("v5e-far-200ns")
    far8 = machine_profile("v5e-far-800ns")
    assert far2.hbm_latency_s == pytest.approx(base.hbm_latency_s + 200e-9)
    assert far8.hbm_latency_s == pytest.approx(base.hbm_latency_s + 800e-9)
    # bandwidth held fixed: the dial isolates latency tolerance
    assert far2.hbm_bw == base.hbm_bw == far8.hbm_bw
    # the far AMU provisions more request slots than local HBM's DMA engine
    assert far8.request_slots > base.request_slots


def test_model_is_frozen():
    with pytest.raises(Exception):
        machine_profile("v5e").hbm_bw = 1.0


def test_unknown_profile_raises_with_known_names():
    with pytest.raises(KeyError, match="v5e"):
        machine_profile("tpu9000")


def test_set_machine_by_name_and_model_and_reset():
    assert get_machine().name == "v5e"
    assert set_machine("v5e-far-800ns").name == "v5e-far-800ns"
    assert get_machine().name == "v5e-far-800ns"
    custom = machine_profile("v5e").replace(name="custom", hbm_latency_s=1e-6)
    assert set_machine(custom) is custom
    assert get_machine().hbm_latency_s == 1e-6
    assert set_machine(None).name == "v5e"


def test_env_var_selects_profile(monkeypatch):
    monkeypatch.setenv(machine_mod.MACHINE_ENV, "v5e-far-800ns")
    assert set_machine(None).name == "v5e-far-800ns"
    monkeypatch.setenv(machine_mod.MACHINE_ENV, "nope")
    with pytest.raises(KeyError):
        set_machine(None)
    monkeypatch.delenv(machine_mod.MACHINE_ENV)
    set_machine(None)


def test_default_interpret_follows_backend():
    set_machine("cpu-interpret")
    assert machine_mod.default_interpret() is True


# ------------------------------------------- the profile must fit the chip


def test_check_device_accepts_the_chip_the_profile_describes():
    assert machine_mod.check_device("TPU v5 lite").name == "v5e"
    set_machine("v5e-far-800ns")   # the latency dial keeps the chip
    assert machine_mod.check_device("TPU v5 lite").name == "v5e-far-800ns"


def test_unknown_tpu_device_kind_raises():
    with pytest.raises(machine_mod.DeviceProfileError, match="TPU v9"):
        machine_mod.check_device("TPU v9")


def test_interpret_profile_on_a_tpu_raises():
    for name in ("cpu-interpret", "nh-g"):
        set_machine(name)
        with pytest.raises(machine_mod.DeviceProfileError,
                           match="interpret"):
            machine_mod.check_device("TPU v5 lite")


def test_profile_of_another_chip_raises():
    set_machine(machine_profile("v5e").replace(name="other",
                                               device_kind="TPU v6 lite"))
    with pytest.raises(machine_mod.DeviceProfileError, match="describes"):
        machine_mod.check_device("TPU v5 lite")


def test_default_interpret_on_a_tpu_checks_the_device(monkeypatch):
    """On a TPU backend the kernels compile — after the active profile is
    checked against the attached chip; a mismatch raises, it never falls
    back to the interpreter."""
    import jax

    class Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Chip()])
    assert machine_mod.default_interpret() is False
    Chip.device_kind = "TPU v9"
    with pytest.raises(machine_mod.DeviceProfileError):
        machine_mod.default_interpret()
    Chip.device_kind = "TPU v5 lite"
    set_machine("cpu-interpret")
    with pytest.raises(machine_mod.DeviceProfileError):
        machine_mod.default_interpret()


# --------------------------------------------------- legacy constant aliases


def test_aliases_track_active_profile():
    assert schedule.REQUEST_SLOTS == 64
    assert machine_mod.PEAK_FLOPS == machine_profile("v5e").peak_flops
    set_machine("v5e-far-800ns")
    assert schedule.REQUEST_SLOTS == 256
    assert schedule.HBM_LATENCY_S == pytest.approx(1500e-9)
    assert machine_mod.VMEM_BYTES == 128 * 1024 * 1024
    from repro import roofline
    assert roofline.HBM_BW == machine_profile("v5e-far-800ns").hbm_bw


# -------------------------------------------------- the latency-dial sweep


@pytest.mark.parametrize("family", sorted(FAMILY_SPECS))
def test_far_latency_solves_strictly_deeper(family):
    """REPRO_MACHINE=v5e-far-800ns must pipeline deeper than v5e for EVERY
    kernel family, and depth must be monotone along the 200ns->800ns dial."""
    spec = FAMILY_SPECS[family]()
    depths = {
        name: autotune.choose_depth(spec.profile(),
                                    machine=machine_profile(name),
                                    vars=spec.all_vars())
        for name in ("v5e", "v5e-far-200ns", "v5e-far-800ns")
    }
    assert depths["v5e"] <= depths["v5e-far-200ns"] <= depths["v5e-far-800ns"]
    assert depths["v5e-far-800ns"] > depths["v5e"]


@pytest.mark.parametrize("family", sorted(FAMILY_SPECS))
def test_env_profile_reaches_depth_solver(family, monkeypatch):
    """The full path: env var -> set_machine(None) -> choose_depth."""
    spec = FAMILY_SPECS[family]()

    def solve():
        return autotune.choose_depth(spec.profile(), vars=spec.all_vars())

    monkeypatch.setenv(machine_mod.MACHINE_ENV, "v5e")
    set_machine(None)
    d_near = solve()
    monkeypatch.setenv(machine_mod.MACHINE_ENV, "v5e-far-800ns")
    set_machine(None)
    d_far = solve()
    assert d_far > d_near


# ---------------------------------------------- adaptive re-solve feedback


def test_samples_flip_static_to_adaptive_and_depths_track_latency():
    spec = FAMILY_SPECS["triad"]()
    prof, vars_ = spec.profile(), spec.all_vars()

    d_static = autotune.choose_depth(prof, kernel="stream_triad", vars=vars_)
    assert autotune.telemetry_summary()["kernels"]["stream_triad"]["mode"] \
        == "static"

    for s in np.full(32, 2e-6):
        autotune.record_transfer("stream_triad", float(s))
    d_near = autotune.choose_depth(prof, kernel="stream_triad", vars=vars_)
    assert autotune.telemetry_summary()["kernels"]["stream_triad"]["mode"] \
        == "adaptive"

    autotune.clear_samples("stream_triad")
    for s in np.full(32, 8e-6):
        autotune.record_transfer("stream_triad", float(s))
    d_far = autotune.choose_depth(prof, kernel="stream_triad", vars=vars_)

    # observed 2us tail already exceeds the modelled 700ns; 8us more so
    assert d_static < d_near < d_far
    assert autotune.last_choice("stream_triad") == d_far


def test_machine_switch_invalidates_samples():
    spec = FAMILY_SPECS["gmm"]()
    prof, vars_ = spec.profile(), spec.all_vars()
    autotune.record_transfer("moe_gmm", 5e-6)
    assert autotune.transfer_samples("moe_gmm")
    d_v5e = autotune.choose_depth(prof, kernel="moe_gmm", vars=vars_)
    assert autotune.telemetry_summary()["kernels"]["moe_gmm"]["mode"] \
        == "adaptive"

    set_machine("v5e-far-800ns")
    # the other profile's samples are invisible: static solve again
    assert autotune.transfer_samples("moe_gmm") == []
    autotune.choose_depth(prof, kernel="moe_gmm", vars=vars_)
    assert autotune.telemetry_summary()["kernels"]["moe_gmm"]["mode"] \
        == "static"

    set_machine("v5e")
    assert len(autotune.transfer_samples("moe_gmm")) == 1
    assert autotune.choose_depth(prof, kernel="moe_gmm", vars=vars_) == d_v5e


def test_clear_samples_also_clears_last_choice():
    spec = FAMILY_SPECS["row_gather"]()
    autotune.choose_depth(spec.profile(), kernel="row_gather",
                          vars=spec.all_vars())
    assert autotune.last_choice("row_gather") is not None
    autotune.clear_samples("row_gather")
    assert autotune.last_choice("row_gather") is None
    assert "row_gather" not in autotune.telemetry_summary()["kernels"]


# ------------------------------------------------------ always-on telemetry


def test_kernel_entry_point_feeds_telemetry(rng):
    """Running any kernel entry point twice populates telemetry_summary()
    without the caller ever touching record_transfer — run one is compile
    warmup (dropped), run two records wall-clock/tiles."""
    from repro.kernels.coro_gather.ops import coro_gather

    table = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 64, 32), jnp.int32)

    coro_gather(table, idx, interpret=True)
    assert autotune.transfer_samples("row_gather") == []  # warmup dropped
    coro_gather(table, idx, interpret=True)

    summ = autotune.telemetry_summary()
    assert summ["machine"] == "v5e"
    entry = summ["kernels"]["row_gather"]
    assert entry["samples"] >= 1
    assert entry["depth"] is not None
    assert entry["p99_us"] >= entry["p50_us"] > 0


def test_telemetry_switch_disables_recording(rng):
    from repro.kernels.coro_gather.ops import coro_gather

    table = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 64, 32), jnp.int32)
    autotune.set_telemetry(False)
    try:
        coro_gather(table, idx, interpret=True)
        coro_gather(table, idx, interpret=True)
        assert autotune.transfer_samples("row_gather") == []
    finally:
        autotune.set_telemetry(True)


def test_sample_ring_is_bounded():
    for i in range(autotune.MAX_SAMPLES_PER_KERNEL + 40):
        autotune.record_transfer("k", 1e-6 + i * 1e-9)
    xs = autotune.transfer_samples("k")
    assert len(xs) == autotune.MAX_SAMPLES_PER_KERNEL
    # oldest samples were evicted
    assert min(xs) > 1e-6 + 39e-9
