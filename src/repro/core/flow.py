"""End-to-end DesignFlow driver — the paper's Fig. 1, fully automated.

ONNX-like model  ->  Reader (IR)  ->  compiler passes (fusion, constant
folding, DCE, shape inference, per-layer precision)  ->  per-target Writer
->  [PTQ / mixed-precision exploration]  ->  Multi-Dataflow compose  ->
deployable accelerator + reports.

``run`` applies the default pass pipeline before handing the graph to the
writers; ``run(passes=())`` skips all rewrites (raw node-by-node
interpretation, the pre-refactor behaviour), and ``run(passes=[...])``
substitutes a custom pipeline.  Graphs read with a symbolic batch dim
compile to batch-polymorphic artifacts: ``FlowResult.batched[target]``
serves any leading-dim size from one compiled graph (LRU of traced
shapes), and ``fifo_slack`` scales the value_info-derived FIFO depths the
stream writer stamps on its topology.  ``dtconfig`` accepts either a uniform
:class:`~repro.quant.qtypes.DatatypeConfig` or a heterogeneous
:class:`~repro.quant.qtypes.PrecisionMap`; ``explore_mixed_precision``
searches for the latter greedily against the float reference.
While a span recorder is on (:mod:`repro.spans`), ``run`` records its
phases as ``flow.transform``, ``flow.calibrate`` and one ``flow.write`` per
target.
"""
from __future__ import annotations

import inspect
from dataclasses import dataclass, field, fields
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp

from repro import spans
from repro.core.ir import Graph
from repro.core.passes import (PassManager, default_pipeline,
                               explore_mixed_precision, strip_precision,
                               structural_pipeline)
from repro.core.writers.jax_writer import BatchedExecutable, JaxWriter
from repro.core.writers.stream_writer import StreamWriter
from repro.core.writers.dist_writer import DistWriter
from repro.core.writers.qjax_writer import QJaxWriter
from repro.core.adaptive import (AdaptiveAccelerator, PointSelector,
                                 RuntimePolicy, WorkingPoint,
                                 shared_point_executables)
from repro.quant.qtypes import DatatypeConfig, PrecisionMap
from repro.quant.ptq import graph_weight_stats

WRITERS = {"jax": JaxWriter, "stream": StreamWriter, "dist": DistWriter,
           "qjax": QJaxWriter}

# default adaptive ladder: the paper's W8/W4/W2 nested working points
DEFAULT_POINTS = (WorkingPoint("w8", 8), WorkingPoint("w4", 4),
                  WorkingPoint("w2", 2))

Precision = Union[DatatypeConfig, PrecisionMap]


@dataclass(frozen=True)
class WriterOptions:
    """Typed writer configuration — the one validated surface replacing the
    per-writer kwarg sprawl that used to thread through ``writer_kwargs=``
    dicts.  Every field is optional; a set field is forwarded to each target
    writer *that accepts it* (``fifo_slack`` to the stream writer,
    ``default_bits``/``use_kernel``/... to the qjax writer), so one options
    object configures a multi-target run.  ``DesignFlow.run`` validates the
    merged per-writer kwargs once, with unknown-key errors naming the
    writer."""

    fifo_slack: Optional[float] = None      # stream: FIFO depth headroom
    default_bits: Optional[int] = None      # qjax: build(bits=None) point
    use_kernel: Optional[bool] = None       # qjax: force/forbid Pallas path
    interpret: Optional[bool] = None        # qjax: Pallas interpret override
    int8_act: Optional[bool] = None         # qjax: fully-integer dataflow
    packed_weights: Optional[bool] = None   # qjax: sub-byte HBM residency
    dw_mode: Optional[str] = None           # qjax: "direct" | "im2col"

    def __post_init__(self):
        if self.dw_mode is not None and self.dw_mode not in ("direct",
                                                             "im2col"):
            raise ValueError(f"dw_mode must be 'direct' or 'im2col', "
                             f"got {self.dw_mode!r}")
        if self.fifo_slack is not None and self.fifo_slack <= 0:
            raise ValueError(f"fifo_slack must be positive, "
                             f"got {self.fifo_slack}")

    def set_fields(self) -> Dict[str, Any]:
        return {f.name: getattr(self, f.name) for f in fields(self)
                if getattr(self, f.name) is not None}


def _writer_params(cls) -> set:
    """Optional constructor keywords a writer class accepts (everything past
    the positional graph/dtconfig/act_ranges triple)."""
    sig = inspect.signature(cls.__init__)
    return {name for name in sig.parameters
            if name not in ("self", "graph", "dtconfig", "act_ranges")}


@dataclass
class FlowResult:
    graph: Graph                      # the pass-transformed graph
    writers: Dict[str, JaxWriter]
    executables: Dict[str, Callable]  # raw interpreters (shape-polymorphic)
    act_ranges: Dict[str, float]
    stats: Dict[str, float] = field(default_factory=dict)
    # per-target batch-polymorphic artifacts: one compiled graph serving any
    # leading-dim size via an LRU of traced shapes
    batched: Dict[str, BatchedExecutable] = field(default_factory=dict)

    def serve(self, target: str = "jax", **kwargs):
        """A batch-coalescing :class:`~repro.runtime.serve.AccelServer` over
        this result's batched artifact for ``target`` — requests of varying
        sizes are queued, packed to buckets aligned with the artifact's LRU,
        executed once per batch and demuxed.  Keyword arguments (``max_batch``,
        ``max_wait``, ``buckets``, ``policy``, ``point_executables``, ...)
        pass through to the server."""
        from repro.runtime.serve import AccelServer   # lazy: runtime is heavy
        if target not in self.batched:
            raise KeyError(f"no batched artifact for target {target!r}; "
                           f"have {tuple(self.batched)}")
        # the graph knows its true input spec — lock request coalescing to it
        # rather than to whatever the first submitted request looks like
        kwargs.setdefault("signature", tuple(
            (tuple(int(d) for d in t.shape[1:]), str(t.dtype))
            for t in self.graph.inputs))
        return AccelServer(self.batched[target], **kwargs)

    def serve_adaptive(self, points=DEFAULT_POINTS,
                       target: str = "qjax",
                       policy: Optional[PointSelector] = None,
                       batch_cache: int = 8,
                       selector: Optional[PointSelector] = None, **kwargs):
        """An :class:`~repro.runtime.serve.AccelServer` whose per-batch
        precision working points ALL read one shared
        :class:`~repro.quant.pack.PackedWeights` buffer — switching is a
        static kernel-arg change: no re-build, no weight copy (requires the
        packed-weight ``"qjax"`` target in this result).

        ``points`` is a sequence of
        :class:`~repro.core.adaptive.WorkingPoint` or a
        :class:`~repro.dse.ParetoFront` (the explorer's output — the server
        then walks the computed front instead of the hardcoded ladder).  The
        working point per batch comes from ``selector`` (any
        :class:`~repro.core.adaptive.PointSelector`) or the legacy
        ``policy``; with neither, an open-loop
        :class:`~repro.core.adaptive.RuntimePolicy` over ``points`` is
        built."""
        from repro.dse.pareto import ParetoFront   # lazy: optional consumer
        if isinstance(points, ParetoFront):
            points = points.working_points()
        writer = self.writers.get(target)
        if writer is None or not hasattr(writer, "packed"):
            raise KeyError(
                f"serve_adaptive needs a packed-weight writer (target "
                f"'qjax'); this result has {tuple(self.writers)}")
        pts = shared_point_executables(writer, points,
                                       max_entries=batch_cache)
        if selector is not None:
            return self.serve(target, selector=selector,
                              point_executables=pts, **kwargs)
        return self.serve(target, policy=policy or RuntimePolicy(list(points)),
                          point_executables=pts, **kwargs)


def _split_precision(dtconfig: Optional[Precision]
                     ) -> Tuple[Optional[DatatypeConfig], int, int]:
    """(writer default config, min act bits, min weight bits)."""
    if dtconfig is None:
        return None, 32, 32
    if isinstance(dtconfig, PrecisionMap):
        return dtconfig.default, dtconfig.min_act_bits, dtconfig.min_weight_bits
    return dtconfig, dtconfig.act_bits, dtconfig.weight_bits


class DesignFlow:
    """``DesignFlow(graph).run(targets, dtconfig, calib)`` — Fig. 1 automated."""

    def __init__(self, graph: Graph,
                 passes: Optional[Sequence[Callable]] = None):
        graph.validate()
        self.graph = graph
        self.passes = passes          # None => default pipeline per run()

    # -- compiler ------------------------------------------------------------
    def transform(self, dtconfig: Optional[Precision] = None,
                  passes: Optional[Sequence[Callable]] = None) -> Graph:
        """Apply the pass pipeline; ``passes=()`` returns the raw graph."""
        if passes is None:
            passes = self.passes
        if passes is None:
            passes = default_pipeline(dtconfig)
        if not passes:
            return self.graph
        return PassManager(passes).run(self.graph)

    def calibrate(self, *calib_inputs, graph: Optional[Graph] = None
                  ) -> Dict[str, float]:
        """Run the float reference once, record per-FIFO activation ranges.

        The ranges feed every quantizing writer: the f32 fake-quant path
        derives each FIFO's Qm.n split from them, and the fully-integer
        ``qjax`` path turns them into per-FIFO int8 activation-*code* scales
        (:func:`repro.quant.ptq.act_code_qtype`) that the kernels fold into
        their per-channel weight scales — calibration is what lets codes,
        not floats, flow between layers."""
        w = JaxWriter(graph if graph is not None else self.graph)
        _, env = w.build(capture=True)(*calib_inputs)
        return {k: float(jnp.max(jnp.abs(v)))
                for k, v in env.items()
                if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating)}

    def run(self, targets: Sequence[str] = ("jax",),
            dtconfig: Optional[Precision] = None,
            calib_inputs: Optional[tuple] = None,
            passes: Optional[Sequence[Callable]] = None,
            fifo_slack: float = 1.0,
            batch_cache: int = 8,
            writer_kwargs: Optional[Dict[str, Dict]] = None,
            options: Optional[WriterOptions] = None) -> FlowResult:
        """Compile the graph for ``targets``.

        ``fifo_slack`` scales every FIFO depth the stream writer derives from
        ``value_info`` (rate-mismatch headroom); ``batch_cache`` bounds the
        per-target LRU of traced batch shapes in ``FlowResult.batched``;
        ``options`` is the typed writer configuration
        (:class:`WriterOptions` — each set field reaches every target writer
        that accepts it); ``writer_kwargs`` is the legacy per-target kwarg
        escape hatch (it wins over ``options`` where both set a key;
        ``fifo_slack`` is sugar for ``{"stream": {"fifo_slack": ...}}``).
        The merged per-writer kwargs are validated here: an unknown key
        raises a :class:`ValueError` naming the writer instead of a bare
        ``TypeError`` deep in its constructor.
        """
        for t in targets:
            if t not in WRITERS:
                raise KeyError(f"unknown target {t!r}; have {tuple(WRITERS)}")
        default_dt, min_act, min_wt = _split_precision(dtconfig)
        with spans.span("flow.transform"):
            g = self.transform(dtconfig, passes)
        act_ranges: Dict[str, float] = {}
        if calib_inputs is not None and min_act < 32:
            # calibrate on the *float* view of the compiled graph — with the
            # precision annotations stripped — so recorded ranges are true
            # activation ranges, not values already clipped by quantization
            with spans.span("flow.calibrate"):
                act_ranges = self.calibrate(*calib_inputs,
                                            graph=strip_precision(g))
        stray = sorted(set(writer_kwargs or {}) - set(targets))
        if stray:
            raise KeyError(f"writer_kwargs for {stray} not in targets "
                           f"{tuple(targets)}")
        wkw = {t: dict((writer_kwargs or {}).get(t, {})) for t in targets}
        opt_fields = options.set_fields() if options is not None else {}
        for t in targets:
            accepted = _writer_params(WRITERS[t])
            for k, v in opt_fields.items():
                if k in accepted:
                    wkw[t].setdefault(k, v)
        if "stream" in wkw:
            wkw["stream"].setdefault("fifo_slack", fifo_slack)
        for t in targets:
            unknown = sorted(set(wkw[t]) - _writer_params(WRITERS[t]))
            if unknown:
                accepted = sorted(_writer_params(WRITERS[t]))
                raise ValueError(
                    f"unknown option(s) {unknown} for writer {t!r} "
                    f"({WRITERS[t].__name__}); it accepts "
                    f"{accepted if accepted else 'no options'}")
        writers, exes, batched = {}, {}, {}
        for t in targets:
            with spans.span("flow.write", target=t):
                w = WRITERS[t](g, default_dt, act_ranges, **wkw[t])
                writers[t] = w
                exes[t] = w.build()
                batched[t] = w.build_batched(max_entries=batch_cache)
        stats = {}
        if dtconfig is not None and min_wt < 32:
            stats = graph_weight_stats(g, default_dt)
        return FlowResult(g, writers, exes, act_ranges, stats, batched)

    # -- design-space exploration -------------------------------------------
    def explore(self, calib_inputs: tuple, *, budget=None, **kwargs):
        """Resource-constrained design-space exploration: screen candidate
        working points analytically against ``budget`` (a
        :class:`~repro.dse.ResourceBudget`), validate survivors on the
        calibration batch, and return the pruned
        :class:`~repro.dse.ParetoFront`.

        The front plugs straight back into the flow::

            front = DesignFlow(graph).explore(calib, budget=budget)
            result = DesignFlow(graph).run(("qjax",), calib_inputs=calib,
                                           **front.run_kwargs())
            srv = result.serve_adaptive(points=front,
                                        selector=front.selector(slo))

        Extra keyword arguments reach
        :class:`~repro.dse.DesignSpaceExplorer` (``ladder``,
        ``act_bits_choices``, ``fifo_slack_choices``, ``per_layer``, ...).
        Raises :class:`~repro.dse.BudgetInfeasibleError` when nothing
        fits."""
        from repro.dse import DesignSpaceExplorer   # lazy: keeps flow light
        return DesignSpaceExplorer(self.graph, calib_inputs, budget=budget,
                                   **kwargs).explore()

    # -- mixed-precision exploration ----------------------------------------
    def explore_mixed_precision(self, calib_inputs: tuple, **kwargs
                                ) -> Tuple[PrecisionMap, List[Dict]]:
        """Greedy per-layer weight-precision search against the float
        reference (see :func:`repro.core.passes.explore_mixed_precision`).
        The returned PrecisionMap feeds straight back into ``run``."""
        g = PassManager(structural_pipeline()).run(self.graph)
        return explore_mixed_precision(g, calib_inputs, **kwargs)

    # -- adaptive / MDC -----------------------------------------------------
    def compose_adaptive(self, points: Sequence[WorkingPoint],
                         target: str = "stream") -> AdaptiveAccelerator:
        """Merge working points over one shared-weight substrate (MDC step)."""
        base = WRITERS[target](self.graph)

        def apply_fn(params, *inputs):
            g = Graph(self.graph.name, self.graph.nodes, self.graph.inputs,
                      self.graph.outputs, params)
            return WRITERS[target](g).build()(*inputs)

        return AdaptiveAccelerator(apply_fn, dict(base.weights), points)
