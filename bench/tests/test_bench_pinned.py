"""What the benchmark reads, pinned to the commit before the model modules
(bench/models/) and the kernel count files (bench/kernels/) took over from
branches in reference.py, work.py and program.py: the same weights from the
same seeds, the same float32 and integer-code references, the same useful
operations per image and the same kernel counts.

The digests are sha256 over the arrays in sorted key order (name, dtype,
shape, then the bytes); the logits are the references' on the first 4 of a
4-image seeded pool, jitted as :mod:`check` jits them, under ``highest``
matmul precision, on the CPU.  XLA's CPU backend splits MobileNet's float32
work across cores and sums it in another order on a single core; these are
its values on two cores or more."""
import hashlib
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
import work  # noqa: E402

DATA = BENCH / "tests" / "data"

# (configuration, seed): (weights, float32 logits, integer-code logits)
PINNED = {
    ("mnist-cnn", 3): (
        "ae25d301ad80dd019812b20d789a19a8fc4f502f6af9ca3d3049ffa664726346",
        "9f4c75dc9242715566573c3f2bb0f6673af43c33f1aa885d6556a37e6adcc979",
        "520222716aed62580b3f13343e4b6a5dd9338bd635d74440588196edf0089ae3"),
    ("mnist-cnn", 3500000031): (
        "e767f3d62e87611239a498fa3d3f649c3c553ea03d6d32a08b3dc96b9e97282b",
        "4dd3d0c49c6d6d145166593c65baa74404b9beb67383b5b672e465c4a8c47efe",
        "69d1f0da6cc54d13ea1ac27af06debfa06297a6e973c2f19a27ab41442d20169"),
    ("mobilenet-v1-0.25", 3): (
        "a439225c904a33e9458c9032e2afd807da6e0f6a19046227faa4e781f321a063",
        "22b11cb4e27e33e29362c53b3e1724073c84f3322e35254382aa1328a32015d4",
        "2c9b77f60ff7a9bf43f6020936164f08b1064dd70199fe02e42a9f4895667083"),
    ("mobilenet-v1-0.25", 3500000031): (
        "792c1d9fa1f1bdabf841accb9688df8a2ae630b02b9666ae9119fc26f6c9d631",
        "977300d810a72360d098c90e21ac2c249949dfc7f8028cb4b4eafc94a51f3f40",
        "d236cf9aa883233cabbefbd0744ec84217c6be6688063c1a7cfa8d99f0b8c457"),
}
USEFUL_OPS = {"mnist-cnn": 2063488, "mobilenet-v1-0.25": 122893568}
# (kernel, operations, bytes) of every call in data/lowered_calls.txt, and
# of the trace events in data/trace_calls.txt
LOWERED_COUNTS = [["qconv_dw_kernel", 231211008, 26856448],
                  ["qgemm_kernel", 54525952, 443392],
                  ["qgemm_kernel", 109051904, 672768],
                  ["qgemm_kernel", 205520896, 1623040],
                  ["qgemm_kernel", 3288334336, 14589952]]
TRACE_COUNTS = [["qgemm_kernel", 109051904, 672768]]


def _config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def digest(arrays) -> str:
    h = hashlib.sha256()
    for k in sorted(arrays):
        a = np.ascontiguousarray(np.asarray(arrays[k]))
        h.update(f"{k}:{a.dtype}:{a.shape};".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name, seed", sorted(PINNED))
def test_weights_and_references_are_the_parents(name, seed):
    cfg = _config(name)
    shape = program.image_shape(cfg)
    params = program.make_weights(cfg, seed)
    x = traffic.image_pool({"pool_images": 4}, shape, seed)
    calib = traffic.calibration_rows(shape, seed)
    steps = reference.grid_steps(reference.act_fracs(
        cfg, reference.act_ranges(cfg, params, calib)))
    qw = reference.quantized_weights(cfg, params)
    fwd = jax.jit(lambda p, x: reference.forward(cfg, p, x))
    qfwd = jax.jit(lambda q, s, x: reference.quantized_forward(cfg, q, x, s))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(fwd(params, x))
        qref = np.asarray(qfwd(qw, steps, x))
    assert (digest(params), digest({"l": ref}),
            digest({"l": qref})) == PINNED[(name, seed)]


@pytest.mark.parametrize("name", sorted(USEFUL_OPS))
def test_useful_ops_are_the_parents(name):
    assert work.useful_ops_per_image(_config(name)) == USEFUL_OPS[name]


def test_kernel_counts_are_the_parents():
    lowered = work.lowered_kernels((DATA / "lowered_calls.txt").read_text())
    counts = sorted([k, c.ops, c.bytes] for sig, k in lowered.items()
                    for c in [work.count(k, *sig)])
    assert counts == LOWERED_COUNTS
    events = (DATA / "trace_calls.txt").read_text().splitlines()
    traced = [[c.kernel, c.ops, c.bytes]
              for c in (work.event_call(e, lowered) for e in events) if c]
    assert traced == TRACE_COUNTS
