"""The configurations' plain references and the program's build of them.

bench/reference.py is the benchmark's own copy of the models' float32
forward passes; it must compute what the program's models compute.  The
MobileNetV1-0.25 configuration must build through ``DesignFlow`` at D8 and
its exact-integer path must rank classes as the reference does; that runs
here at 32x32 images with the published widths (the CPU holds it)."""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from repro.models import cnn  # noqa: E402


def _config(name, **over):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return {**json.load(f), **over}


def test_mobilenet_config_names_source_and_departures():
    cfg = _config("mobilenet-v1-0.25")
    assert "1704.04861" in cfg["source"] and "0.25" in cfg["source"]
    assert len(cfg["source"]) <= 200
    assert len(cfg["departures"]) == 2
    assert [c for c, _ in cfg["blocks"]] == [16, 32, 32, 64, 64, 128, 128,
                                             128, 128, 128, 128, 256, 256]
    assert reference.fc_in(cfg) == 12544


@pytest.mark.parametrize("name", ["mnist-cnn", "mobilenet-v1-0.25"])
def test_reference_is_the_programs_model(name):
    cfg = _config(name, image_hw=[32, 32])
    params = program.make_weights(cfg, 3)
    pcfg, _ = program.program_config(cfg)
    x = np.random.default_rng(0).random((2, 32, 32, cfg["in_channels"]),
                                        np.float32)
    with jax.default_matmul_precision("highest"):
        want = (cnn.forward(params, x, pcfg)[0] if cfg["family"] == "cnn"
                else cnn.separable_forward(params, x, pcfg))
        got = reference.forward(cfg, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_mobilenet_builds_at_d8_and_ranks_as_the_reference():
    cfg = _config("mobilenet-v1-0.25", image_hw=[32, 32])
    seed = 5
    params = program.make_weights(cfg, seed)
    calib = traffic.calibration_rows((32, 32, 3), seed)
    res = program.build(cfg, params, calib, use_kernel=False)
    writer = res.writers["qjax"]
    assert writer.int8_act_on
    x = np.random.default_rng(1).random((8, 32, 32, 3), np.float32)
    served = np.asarray(res.batched["qjax"](x))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.forward(cfg, params, x))
    assert served.shape == ref.shape == (8, 1000)
    assert np.mean(served.argmax(1) == ref.argmax(1)) >= 0.75
    # and computes the model at its stated precision, code for code
    steps = reference.grid_steps(reference.act_fracs(
        cfg, reference.act_ranges(cfg, params, calib)))
    qw = reference.quantized_weights(cfg, params)
    with jax.default_matmul_precision("highest"):
        qref = np.asarray(reference.quantized_forward(cfg, qw, x, steps))
    np.testing.assert_array_equal(served, qref)


@pytest.mark.parametrize("name", ["mnist-cnn", "mobilenet-v1-0.25"])
def test_build_calibrates_at_the_configured_precision(name, monkeypatch):
    """The flow's calibration runs under the matmul precision the
    configuration states (float32 as ``highest``), not JAX's default,
    which is one bfloat16 pass on a TPU."""
    cfg = _config(name, image_hw=[32, 32])
    assert cfg["calibration_precision"] == "highest"
    seen = []
    calibrate = program.DesignFlow.calibrate

    def spy(self, *args, **kw):
        seen.append(jax.config.jax_default_matmul_precision)
        return calibrate(self, *args, **kw)

    monkeypatch.setattr(program.DesignFlow, "calibrate", spy)
    params = program.make_weights(cfg, 2)
    calib = traffic.calibration_rows((32, 32, cfg["in_channels"]), 2)
    program.build(cfg, params, calib, use_kernel=False)
    assert seen == ["highest"]
    assert jax.config.jax_default_matmul_precision is None
