"""CPU rehearsal of ``chip_smoke.py`` and the pieces it relies on.

The smoke's serve-and-compare phase runs here on mnist-cnn with the writer
forced to interpret-mode kernels by the test (on a chip the script builds the
compiled kernels and also checks the served program for them), so its
control flow is exercised on every change.  Also: the script refuses to run
without a TPU, and the compile-cache and stream-kernel defaults it depends
on."""
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro import caches  # noqa: E402


def test_serve_and_compare_runs_on_mnist_in_interpret_mode():
    params, res = chip_smoke.build("mnist-cnn", 0, use_kernel=True,
                                   interpret=True)
    writer = res.writers["qjax"]
    assert writer.qpath == "pallas" and writer.int8_act_on
    report = chip_smoke.serve_and_compare("mnist-cnn", params, res, seed=0)
    # every request size at every point: 1, 3, 8 and 11 (split 8 + 3) rows
    assert set(report["bits_views"]) == {8, 4, 2}
    for point in ("w8", "w4", "w2"):
        r = report[point]
        assert r["ref_max_steps"] == 0.0      # interpret mode is bit-exact
        assert r["top1_vs_f32"] >= chip_smoke.TOP1_FLOOR["mnist-cnn"][point]
        assert len(r["warm_request_s"]) == len(chip_smoke.REQUEST_SIZES)


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out and out.out == ""
    assert "needs a TPU" in out.err


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.delenv(caches.COMPILE_CACHE_ENV, raising=False)
    first = caches.compile_cache_dir()
    assert first == caches.compile_cache_dir()           # a fixed path
    assert first == os.path.join(REPO, ".cache", "jax")  # inside the checkout
    monkeypatch.setenv(caches.COMPILE_CACHE_ENV, str(tmp_path))
    assert caches.compile_cache_dir() == str(tmp_path)


def test_enable_compile_cache_sets_no_dir_when_env_is_set(monkeypatch,
                                                          tmp_path):
    monkeypatch.setenv(caches.COMPILE_CACHE_ENV, str(tmp_path))
    dir_before = jax.config.jax_compilation_cache_dir
    floor_before = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert caches.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == dir_before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor_before)


def test_autotune_cache_defaults_inside_the_checkout(monkeypatch):
    from repro.kernels import autotune
    monkeypatch.delenv(autotune.AUTOTUNE_CACHE_ENV, raising=False)
    assert autotune.autotune_cache_path() == os.path.join(
        REPO, ".cache", "autotune.json")


def test_conv2d_stream_interpret_defaults_to_the_backend(monkeypatch):
    """No ``interpret`` argument: compiled on TPU, interpret mode elsewhere
    (the stream writer calls the kernel without one)."""
    from repro.kernels.conv2d_stream import ops
    seen = []
    real = ops.build_call

    def spy(*args, **kw):
        seen.append(kw["interpret"])
        return real(*args, **kw)

    monkeypatch.setattr(ops, "build_call", spy)
    x = jax.numpy.ones((1, 6, 6, 2))
    w = jax.numpy.ones((3, 3, 2, 4))
    ops.conv2d_stream(x, w, jax.numpy.zeros(4))
    assert seen == [jax.default_backend() != "tpu"]


@pytest.mark.parametrize("name", ["mnist-cnn", "separable-cnn"])
def test_smoke_models_build_the_kernel_path(name):
    """The writer the smoke serves resolves to the Pallas path with packed
    W4/W2 storage and int8 activation codes once kernels are on."""
    _, res = chip_smoke.build(name, 0, use_kernel=True, interpret=True)
    w = res.writers["qjax"]
    assert w.qpath == "pallas" and w.packed_storage and w.int8_act_on
