"""HLO instruction -> IR node, read from a compiled program.

:class:`~repro.core.writers.jax_writer.JaxWriter` runs each node under
``jax.named_scope(node.name)``, so every instruction of the compiled
program carries its node in its ``op_name`` metadata
(``jit(run)/conv0/jit(qgemm)/dot_general``).  A device profile names its
operations by instruction (``%copy.1 = s8[...]{...} copy(...)``) and
carries no metadata: :class:`NodeMap`, built from the compiled HLO text of
the served programs (``executable.lower(x).compile().as_text()``), ties
those names back to the graph.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Iterator, Optional, Sequence, Tuple

__all__ = ["NodeMap", "scope_node"]

_LAYOUT = re.compile(r"\{[^{}]*\}")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (.*?) [a-z][\w\-]*\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^\s(]+) .*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.\-]+)")

Head = Tuple[str, str]      # (instruction name, result type without layouts)


def _head(text: str) -> Optional[Head]:
    """The :data:`Head` of an HLO instruction, as printed in HLO text or as
    a profile event's name."""
    m = _HEAD.match(_LAYOUT.sub("", text))
    return (m.group(1), m.group(2)) if m else None


def scope_node(op_name: str, nodes: Sequence[str]) -> Optional[str]:
    """The outermost IR node whose scope is a part of the ``op_name`` path
    (``jit(run)/conv0/jit(qgemm)/dot_general`` -> ``conv0``)."""
    path = "/" + op_name + "/"
    best = None
    for n in nodes:
        i = path.find("/" + n + "/")
        if i >= 0 and (best is None or (i, -len(n)) < best):
            best = (i, -len(n), n)
    return best[2] if best else None


class NodeMap:
    """HLO instruction -> IR node over the compiled HLO text of one or more
    programs (one per bucket).  An instruction with no ``op_name`` under a
    node takes the node that most instructions of the computation it calls
    (a fusion's body) are under.  The programs of several buckets reuse
    instruction names, so a name is looked up with its result type first,
    then alone where it names one node only."""

    def __init__(self, hlo_texts: Sequence[str], nodes: Sequence[str]):
        self.by_head: Dict[Head, str] = {}
        by_name: Dict[str, set] = {}
        for text in hlo_texts:
            for head, node in _program(text, nodes):
                self.by_head.setdefault(head, node)
                by_name.setdefault(head[0], set()).add(node)
        self.by_name = {n: next(iter(v)) for n, v in by_name.items()
                        if len(v) == 1}

    def __call__(self, instruction: str) -> Optional[str]:
        """The node of ``instruction`` (an HLO line or a profile event's
        name), or ``None`` under no node's scope."""
        head = _head(instruction)
        if head is None:
            return None
        node = self.by_head.get(head)
        return node if node is not None else self.by_name.get(head[0])


def _program(text: str, nodes: Sequence[str]) -> Iterator[Tuple[Head, str]]:
    comp = None
    members: Dict[str, Counter] = {}
    pending = []            # (head, callee) of instructions under no node
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        head = _head(line)
        if head is None:
            continue
        m = _OP_NAME.search(line)
        node = scope_node(m.group(1), nodes) if m else None
        if node is not None:
            members.setdefault(comp, Counter())[node] += 1
            yield head, node
        else:
            m = _CALLS.search(line)
            if m:
                pending.append((head, m.group(1)))
    for head, callee in pending:
        inner = members.get(callee)
        if inner:
            yield head, inner.most_common(1)[0][0]
