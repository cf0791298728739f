"""Operation and byte counts of bench/work.py against hand-worked shapes.

The lowered and traced call lines under ``data/`` were recorded on one v5e
from the served programs at bucket 8: mnist-cnn's three qgemm calls,
MobileNetV1-0.25's first depthwise call and its FC."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import work  # noqa: E402

DATA = BENCH / "tests" / "data"
PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def _config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_useful_ops_mnist():
    # conv0 28*28*(3*3*1)*16, conv1 14*14*(3*3*16)*32, FC 1568*10 MACs
    macs = 28 * 28 * 9 * 16 + 14 * 14 * 144 * 32 + 1568 * 10
    assert work.useful_ops_per_image(_config("mnist-cnn")) == 2 * macs
    # conv1 of an 8-image batch is the 1568 x 144 x 32 product
    assert 8 * 2 * 14 * 14 * 144 * 32 == 2 * 1568 * 144 * 32


def test_useful_ops_mobilenet():
    cfg = _config("mobilenet-v1-0.25")
    ops = work.useful_ops_per_image(cfg)
    stem = 224 * 224 * 27 * 8
    fc = 12544 * 1000
    dw0 = 112 * 112 * 9 * 8
    pw0 = 112 * 112 * 8 * 16
    assert ops > 2 * (stem + fc + dw0 + pw0)
    assert 100e6 < ops < 130e6      # ~108 MOP of the body plus stem and FC


@pytest.fixture(scope="module")
def lowered():
    return work.lowered_kernels((DATA / "lowered_calls.txt").read_text())


def test_lowered_kernels_named(lowered):
    names = sorted(lowered.values())
    assert names == ["qconv_dw_kernel"] + ["qgemm_kernel"] * 4


@pytest.mark.parametrize("dims, kernel, ops, nbytes", [
    # mnist conv1, bucket 8: (1568 -> 1664) x (144 -> 256) x (32 -> 128)
    ((1664, 256, 128), "qgemm_kernel", 2 * 1664 * 256 * 128,
     1664 * 256 + 256 * 128 + 4 * 128 + 4 * 128 + 1664 * 128),
    # MobileNet FC, bucket 8: (8 -> 128) x 12544 x (1000 -> 1024)
    ((128, 12544, 1024), "qgemm_kernel", 2 * 128 * 12544 * 1024,
     128 * 12544 + 12544 * 1024 + 4 * 1024 + 4 * 1024 + 128 * 1024),
])
def test_gemm_counts(lowered, dims, kernel, ops, nbytes):
    m, k, n = dims
    sig = work.signature([((m, k), 1), ((k, n), 1), ((1, n), 4), ((1, n), 4)],
                         [((m, n), 1)])
    assert lowered[sig] == kernel
    call = work.count(kernel, *sig)
    assert (call.ops, call.bytes) == (ops, nbytes)


def test_depthwise_counts(lowered):
    # MobileNet dw0, bucket 8: three row views of one (8*114, 120, 128)
    # padded activation array, 9 taps padded to 16 rows, output (8*112, 112,
    # 128): 3 x 3 window, 8 channels padded to 128
    x = ((912, 120, 128), 1)
    sig = work.signature([x, x, x, ((16, 128), 1), ((1, 128), 4),
                          ((1, 128), 4)], [((896, 112, 128), 1)])
    assert lowered[sig] == "qconv_dw_kernel"
    call = work.count("qconv_dw_kernel", *sig)
    assert call.ops == 2 * 896 * 112 * 128 * 9
    assert call.bytes == (912 * 120 * 128 + 16 * 128 + 4 * 128 + 4 * 128
                          + 896 * 112 * 128)


def test_trace_event_is_its_lowered_call(lowered):
    event = (DATA / "trace_calls.txt").read_text().splitlines()[0]
    call = work.event_call(event, lowered)
    assert call == work.KernelCall("qgemm_kernel", 2 * 1664 * 256 * 128,
                                   672768)
    assert work.event_call("%fusion.3 = s8[8,128]{1,0} fusion(...)",
                           lowered) is None
    # memory-bound: 672,768 bytes at 819 GB/s beat 109 MOP at 393 TOP/s
    assert work.least_seconds(call, PEAKS) == pytest.approx(672768 / 819e9)
