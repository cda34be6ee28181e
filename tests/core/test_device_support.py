"""Device detection, the kept-failure warmup contract, and the compile
cache placement (mythril_tpu/support/device.py, mythril_tpu/__init__.py)."""

import threading

import pytest

from mythril_tpu.support.device import (
    DeviceFailure,
    DeviceWarmup,
    local_tpu_chips,
    on_tpu,
)


def test_cpu_suite_is_not_on_a_tpu():
    assert on_tpu() is False
    assert local_tpu_chips() == 0  # JAX_PLATFORMS=cpu keeps JAX off chips


def test_warmup_ready_after_compile():
    calls = []
    w = DeviceWarmup("t", lambda: calls.append(1))
    assert w.ready() is False
    w.warmup()
    w.warmup()  # idempotent
    assert w.ready() and calls == [1]


@pytest.mark.parametrize("background", [False, True])
def test_failed_compile_is_kept_and_raised(background):
    def fail():
        raise ValueError("no VMEM")

    w = DeviceWarmup("t", fail)
    if background:
        w.ensure_warming()
        w._done.wait(timeout=30)
    else:
        with pytest.raises(ValueError):
            w.warmup()
    assert w.state == "failed"
    # never retried silently: every later device call raises the failure
    for _ in range(2):
        with pytest.raises(DeviceFailure, match="no VMEM"):
            w.ready()
    with pytest.raises(DeviceFailure):
        w.ensure_warming()


def test_background_claim_is_single():
    gate = threading.Event()
    calls = []

    def slow():
        calls.append(1)
        gate.wait(timeout=30)

    w = DeviceWarmup("t", slow)
    for _ in range(4):
        w.ensure_warming()
    gate.set()
    w.warmup()  # waits for the in-flight compile
    assert calls == [1] and w.ready()


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    import jax

    import mythril_tpu

    monkeypatch.setattr(mythril_tpu, "_compile_cache_armed", False)
    monkeypatch.setattr(mythril_tpu, "_arm_compile_cache_listener",
                        lambda: None)
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    mythril_tpu.arm_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before  # untouched
    monkeypatch.setattr(mythril_tpu, "_compile_cache_armed", False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        mythril_tpu.arm_compile_cache()
        assert jax.config.jax_compilation_cache_dir == \
            mythril_tpu.repo_compile_cache_dir()
        assert mythril_tpu.repo_compile_cache_dir().endswith("/.jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_native_library_keyed_on_source_contents():
    from mythril_tpu.native import build

    lib = build.build_library()
    assert lib.name.startswith("_libmythril_native.")
    assert lib == build._lib_path()  # same sources -> same build
    assert len(lib.name.split(".")[1]) == 16
