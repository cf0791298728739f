"""Writer 1: IR -> pure-JAX callable (the reference "software" target).

Faithful to the paper's HLS flow semantics: weights are fake-quantized to Wy
at build time; the activation stream is quantized to Dx at every actor
boundary (the fixed-point dataflow between streaming blocks).  ``capture=True``
returns every intermediate tensor (used for PTQ calibration).

Post pass-pipeline refactor the writer is a thin interpreter over the
annotated IR:

* actor implementations come from the target-keyed op registry
  (:mod:`repro.core.writers.registry`) instead of a hardcoded dict — a
  subclass only sets ``target`` and registers the ops it retargets;
* precision is per layer: a node annotated with ``Node.dtconfig`` (written by
  the precision-assignment pass) quantizes its weights and output FIFOs with
  its own ``Dx-Wy`` point, falling back to the writer's default config;
* every node output is bound into the environment (multi-output ops such as
  ``Split`` work; previously only ``outputs[0]`` was bound);
* ``build_batched`` wraps the interpreter in a :class:`BatchedExecutable` —
  a batch-polymorphic artifact that re-jits per concrete input signature
  with an LRU of traced shapes, so one compiled graph (symbolic leading dim,
  see :data:`repro.core.ir.BATCH`) serves batch 1..N without recompiling;
* each node's actor and its output quantization run under
  ``jax.named_scope(node.name)``, so every operation of the compiled program
  names its IR node in its ``op_name`` metadata, which
  :class:`~repro.core.writers.scopes.NodeMap` reads back (a device
  profile's operations can be tied to the graph).
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import spans
from repro.core.ir import Graph, Node
from repro.core.writers.registry import OP_REGISTRY, registered_ops, resolve
from repro.quant.fixedpoint import fake_quant
from repro.quant.qtypes import DatatypeConfig, fixed_for_range
from repro.quant.ptq import effective_weight_dt, weight_qtype

# Backward-compatible alias: the reference op table (live view of the "jax"
# registry entries).
OP_IMPLS: Dict[str, Callable] = OP_REGISTRY["jax"]

Signature = Tuple[Tuple[Tuple[int, ...], str], ...]


class BatchedExecutable:
    """Batch-polymorphic compiled artifact.

    Wraps a writer's interpreter; each call dispatches on the concrete input
    signature (shapes + dtypes) and re-jits on a miss, keeping at most
    ``max_entries`` traced executables in an LRU.  Each signature gets its
    *own* ``jax.jit`` object so eviction actually releases the trace — one
    shared jit would grow an unbounded internal shape cache, which is what
    this class exists to bound for long-running serving.

    While a span recorder is on (:mod:`repro.spans`), a call whose
    signature missed the cache is one ``exe.compile`` span: the trace,
    lowering, compile (or compile-cache load) and kernel autotuning of that
    signature, and its first dispatch.
    """

    def __init__(self, fn: Callable, max_entries: int = 8,
                 compile_fn: Optional[Callable[[Signature], Callable]] = None,
                 on_compile: Optional[Callable[[Signature], None]] = None,
                 bits: Optional[int] = None):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self._fn = fn
        self._compile = compile_fn or (lambda sig: jax.jit(fn))
        self._cache: "OrderedDict[Signature, Callable]" = OrderedDict()
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        # serving telemetry hook: called with the signature on every trace
        # miss (a scheduler can count retraces per bucket / alert on churn)
        self.on_compile = on_compile
        # weight working point this artifact executes at (packed-weight
        # writers stamp it; AccelServer telemetry attributes batches to it)
        self.bits = bits

    @staticmethod
    def signature(*inputs) -> Signature:
        return tuple((tuple(jnp.shape(x)), str(jnp.result_type(x)))
                     for x in inputs)

    def executable_for(self, *inputs) -> Callable:
        """The compiled executable serving these inputs' signature."""
        sig = self.signature(*inputs)
        exe = self._cache.get(sig)
        if exe is None:
            self.misses += 1
            if self.on_compile is not None:
                self.on_compile(sig)
            exe = self._compile(sig)
            self._cache[sig] = exe
            while len(self._cache) > self.max_entries:
                self._cache.popitem(last=False)
        else:
            self.hits += 1
            self._cache.move_to_end(sig)
        return exe

    def __call__(self, *inputs):
        rec = spans.active()
        if rec is None:
            return self.executable_for(*inputs)(*inputs)
        t0, misses = time.time_ns(), self.misses
        exe = self.executable_for(*inputs)
        if self.misses == misses:
            return exe(*inputs)
        try:
            return exe(*inputs)
        finally:
            rows = jnp.shape(inputs[0])[:1] if inputs else ()
            rec.add("exe.compile", t0, time.time_ns(),
                    bucket=int(rows[0]) if rows else None)

    @property
    def cached_signatures(self) -> Tuple[Signature, ...]:
        return tuple(self._cache)

    @property
    def cached_batches(self) -> Tuple[int, ...]:
        """Leading-dim sizes currently resident (serving telemetry)."""
        return tuple(sig[0][0][0] for sig in self._cache if sig and sig[0][0])

    def has_batch(self, batch: int) -> bool:
        """True when a trace for this leading-dim size is resident — the
        scheduler's bucket policy prefers such sizes (hit beats retrace)."""
        return batch in self.cached_batches

    def telemetry(self) -> Dict[str, Any]:
        """Hit/miss counters + resident traces, for serving dashboards."""
        total = self.hits + self.misses
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / total if total else 0.0,
            "cached_batches": self.cached_batches,
            "capacity": self.max_entries,
            "bits": self.bits,
        }


class JaxWriter:
    """Builds an executable from the (pass-annotated) IR.  Subclasses set
    ``target`` and register retargeted actors in the op registry (StreamWriter
    swaps Conv/FusedConv for the Pallas line-buffer kernel)."""

    target = "jax"

    def __init__(self, graph: Graph,
                 dtconfig: Optional[DatatypeConfig] = None,
                 act_ranges: Optional[Dict[str, float]] = None):
        graph.validate()
        self.graph = graph
        self.dt = dtconfig or DatatypeConfig(32, 32)
        self.act_ranges = act_ranges or {}
        # output names whose activation quant an op impl already applied in
        # its (fused) epilogue — _act_q skips them instead of re-rounding
        self._fused_act: set = set()
        self.weights = self._prepare_weights()

    # -- per-layer precision -------------------------------------------------
    def node_dt(self, node: Optional[Node]) -> DatatypeConfig:
        if node is not None and node.dtconfig is not None:
            return node.dtconfig
        return self.dt

    # -- weights (the Weight/Bias actors) ----------------------------------
    def _prepare_weights(self) -> Dict[str, jax.Array]:
        """Fake-quantize each initializer at its *consumer's* weight
        precision (per-layer Wy); 1-D tensors (biases, norm stats) pass
        through in float."""
        out = {}
        for name, w in self.graph.initializers.items():
            w = jnp.asarray(w)
            dt = effective_weight_dt(self.graph, name, self.dt)
            if dt.weight_bits < 32 and w.ndim >= 2:
                out[name] = fake_quant(w, weight_qtype(w, dt.weight_bits))
            else:
                out[name] = w
        return out

    def op_impl(self, op: str) -> Callable:
        return resolve(op, self.target)

    def op_table(self) -> Dict[str, Callable]:
        return registered_ops(self.target)

    def _act_q(self, name: str, x, node: Optional[Node] = None):
        if name in self._fused_act:
            return x   # an op epilogue already applied this tensor's quant
        bits = self.node_dt(node).act_bits
        if bits >= 32 or not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        qt = fixed_for_range(bits, self.act_ranges.get(name, 8.0))
        return fake_quant(x, qt)

    def _materialize(self, value):
        """Hook: convert one graph *output* to its caller-facing form.  The
        reference writers return values as-is; the packed-weight writer's
        fully-integer mode decodes inter-layer int8 activation codes to float
        here — the ONE place the hot path materializes floats."""
        return value

    # -- build --------------------------------------------------------------
    def _env_seed(self, bits: Optional[int] = None) -> Dict[str, Any]:
        """The environment a built executable starts from.  ``bits`` selects
        the weight working point for writers whose weights are packed master
        codes (target "qjax"); the reference writers bake precision into
        ``self.weights`` at construction and reject it."""
        if bits is not None:
            raise ValueError(
                f"writer target {self.target!r} bakes weight precision at "
                "build; bits= is a parameter of packed-weight writers "
                "(target 'qjax')")
        return self.weights

    def build(self, capture: bool = False,
              bits: Optional[int] = None) -> Callable:
        order = self.graph.topo_order()
        in_names = [t.name for t in self.graph.inputs]
        impls = [(node, self.op_impl(node.op)) for node in order]
        seed = self._env_seed(bits)

        def run(*inputs):
            env: Dict[str, Any] = dict(seed)
            for n, x in zip(in_names, inputs):
                env[n] = self._act_q(n, x)
            for node, impl in impls:
                with jax.named_scope(node.name):
                    y = impl(node, env)
                    outs = y if isinstance(y, tuple) else (y,)
                    for oname, oval in zip(node.outputs, outs):
                        env[oname] = self._act_q(oname, oval, node)
            outs = tuple(self._materialize(env[o]) for o in self.graph.outputs)
            if capture:
                return outs[0] if len(outs) == 1 else outs, env
            return outs[0] if len(outs) == 1 else outs

        return run

    def build_jit(self) -> Callable:
        return jax.jit(self.build())

    def build_batched(self, max_entries: int = 8,
                      on_compile: Optional[Callable] = None,
                      bits: Optional[int] = None) -> BatchedExecutable:
        """Batch-polymorphic executable: one artifact, any leading-dim size,
        LRU of per-signature traces (see :class:`BatchedExecutable`);
        ``on_compile`` observes every trace miss (serving telemetry).
        ``bits`` selects the weight working point on packed-weight writers
        and is stamped on the artifact for batch attribution."""
        return BatchedExecutable(self.build(bits=bits), max_entries=max_entries,
                                 on_compile=on_compile, bits=bits)
