"""The file hooks (bench/hooks.py): every configuration's model module
exists and has what the benchmark calls, its reference side keeps out of
the program, and every kernel the recorded lowered programs call has an
operation count."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import hooks  # noqa: E402

DATA = BENCH / "tests" / "data"
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
MODELS = sorted(p.stem for p in (BENCH / "models").glob("*.py"))
MODEL_FUNCTIONS = ("init_weights", "forward", "quantized_weights",
                   "quantized_forward", "useful_ops_per_image", "to_ir")


@pytest.mark.parametrize("name", CONFIGS)
def test_config_names_a_model_module(name):
    with open(hooks.path("configs", name, ".json")) as f:
        cfg = json.load(f)
    mod = hooks.module("models", cfg["family"])
    assert mod is not None, cfg["family"]
    for fn in MODEL_FUNCTIONS:
        assert callable(getattr(mod, fn, None)), (cfg["family"], fn)


@pytest.mark.parametrize("name", MODELS)
def test_model_module_imports_nothing_of_the_program(name):
    """Loading a model module (its reference side, with :mod:`reference`)
    in a fresh interpreter leaves ``repro`` unimported: only its ``to_ir``
    imports the program, when called."""
    code = (f"import sys; sys.path.insert(0, {str(BENCH)!r}); import hooks; "
            f"assert hooks.module('models', {name!r}) is not None; "
            "print(sorted(m for m in sys.modules if m.split('.')[0] == "
            "'repro'))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "[]"


def test_every_lowered_kernel_has_a_count():
    kernels = set(re.findall(r'kernel_name = "([^"]+)"',
                             (DATA / "lowered_calls.txt").read_text()))
    assert kernels
    for k in sorted(kernels):
        assert hooks.path("kernels", k) is not None, k
        assert callable(hooks.module("kernels", k).count), k


def test_a_missing_file_is_none():
    assert hooks.path("models", "no-such-model") is None
    assert hooks.module("kernels", "no_such_kernel") is None
