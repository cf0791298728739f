"""The per-IR-node scopes of the compiled program and the map that reads
them back (repro.core.writers.scopes): on a compiled CPU program kept as a
data file (``data/mnist-cnn.b8.cpu.hlo.txt``: the served qjax mnist-cnn
program at bucket 8) and on one compiled here."""
from pathlib import Path

import jax
import numpy as np

from repro.configs.mnist_cnn import CONFIG as CNN
from repro.core.adaptive import FixedSelector, WorkingPoint
from repro.core.flow import DesignFlow
from repro.core.reader import cnn_to_ir
from repro.core.writers.scopes import NodeMap, scope_node
from repro.models import cnn
from repro.quant.qtypes import DatatypeConfig

DATA = Path(__file__).resolve().parent / "data"
MNIST_NODES = ["conv0", "pool0", "conv1", "pool1", "flatten", "fc"]
# the nodes of mnist-cnn's served program that compute (flatten is a
# relabelling of its input)
MNIST_COMPUTE = {"conv0", "pool0", "conv1", "pool1", "fc"}


def test_scope_node_takes_the_outermost_node():
    nodes = ["fc", "conv0", "conv0_bn"]
    assert scope_node("jit(run)/conv0/jit(qgemm)/dot_general",
                      nodes) == "conv0"
    assert scope_node("jit(run)/conv0_bn/mul", nodes) == "conv0_bn"
    assert scope_node("jit(run)/fc/conv0/x", nodes) == "fc"
    assert scope_node("jit(run)/mul", nodes) is None


def test_node_map_reads_the_compiled_program():
    """(e) The instruction -> IR node map over the compiled CPU program of
    mnist-cnn's bucket 8: every computing node owns instructions, a fusion
    is found under its scope, and a profile event (name and result type
    with a device layout) finds its node."""
    nodes = NodeMap([(DATA / "mnist-cnn.b8.cpu.hlo.txt").read_text()],
                    MNIST_NODES)
    assert MNIST_COMPUTE <= set(nodes.by_head.values())
    assert nodes("%wrapped_reduce-window.1 = s8[8,7,7,32]{3,2,1,0:T(8,128)} "
                 "fusion(s8[8,14,14,32]{3,2,1,0} %clamp_convert_fusion), "
                 "kind=kLoop") == "pool1"
    assert nodes("%conv_general_dilated.2 = f32[8,28,28,16]{3,2,1,0} "
                 "convolution(%x, %w)") == "conv0"
    assert nodes("%dot_general.0 = s32[8,10]{1,0} dot(%a, %b)") == "fc"
    # the output decode (under no scope) fused with fc's scaling: the
    # fusion takes the node of the instructions inside it
    assert nodes("%broadcast_multiply_fusion = f32[8,10]{1,0} "
                 "fusion(%a, %b)") == "fc"
    # a constant with no metadata lies under no node
    assert nodes("%constant.28 = f32[3,3,16,32]{3,2,1,0} constant({...})") \
        is None
    assert nodes("%nowhere.9 = f32[1]{0} add(%a, %b)") is None


def test_compiled_program_names_its_nodes():
    """(e) The compiled CPU program of a served qjax mnist-cnn bucket
    carries every computing node's name in its ``op_name`` metadata, and
    the map reads the nodes back from it."""
    params = cnn.init_params(CNN, jax.random.PRNGKey(5))
    g = cnn_to_ir(CNN, {k: np.asarray(v) for k, v in params.items()})
    calib = np.random.default_rng(5).random((4, 28, 28, 1), np.float32)
    result = DesignFlow(g).run(targets=("qjax",),
                               dtconfig=DatatypeConfig(8, 8),
                               calib_inputs=(calib,))
    names = [n.name for n in result.graph.nodes]
    assert MNIST_COMPUTE <= set(names)
    pt = WorkingPoint("w8", 8)
    srv = result.serve_adaptive(points=(pt,), selector=FixedSelector(pt),
                                max_batch=8, buckets=(8,))
    x = np.zeros((8, 28, 28, 1), np.float32)
    exe = srv.point_executables["w8"]
    text = exe.executable_for(x).lower(x).compile().as_text()
    for node in MNIST_COMPUTE:
        assert f"/{node}/" in text, node
    assert MNIST_COMPUTE <= set(NodeMap([text], names).by_head.values())
