"""Filters as trees of predicate kinds.

A mix's ``filter`` is ``null``, a node ``{"<kind>": body}``, or the form
of the first mixes, ``{"column", "pass_fraction"}``, which is the node
``{"nr": {...}}``. Each kind is the file ``bench/predicates/<kind>.py``
(``harness/kinds.py``) and gives three functions:

- ``bind(body, ctx)``: the concrete parameters of one request, a dict
  (or, for a kind that holds a list, a tuple of bound children). It reads
  only ``ctx.table`` (the configuration's ``Dataset``), ``ctx.vectors``
  (the request's held-out pool vectors, by the names the mix gives them,
  as pool indices) and ``ctx.cache`` (constants of this table, kept
  across requests); ``ctx.bind(desc)`` binds a child.
- ``build(params, ctx)``: the program's query node; ``ctx.pool`` is the
  held-out pool and ``ctx.build(node)`` builds a child. The only part
  that imports the program.
- ``mask(params, view, band, sub)``: the reference's boolean row mask,
  ``(b, n)``, for ``b`` requests of one archetype at once. ``params`` is
  batched: each string is shared, each number an array of ``b``; a
  child's mask is ``sub(child)``. ``view`` is the table on the device
  (``jax.numpy``) or a few rows of it in float64 on the host
  (``harness/reference.py``). ``band`` widens (> 0) or narrows (< 0) a
  boundary that rounding decides by that share of its own size; a kind
  whose test is exact ignores it. It imports nothing of the program.

A bound node is ``(kind, params)``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

Node = Tuple[str, object]


def parse(desc: dict) -> Tuple[str, object]:
    """``(kind, body)`` of a filter description."""
    if "column" in desc:              # the form of the first mixes
        return "nr", desc
    if len(desc) != 1:
        raise ValueError(f"a filter node has one key, its kind: {desc!r}")
    (kind, body), = desc.items()
    return kind, body


class BindContext:
    def __init__(self, table, vectors: Dict[str, int]):
        self.table = table
        self.vectors = vectors
        self.cache = table.cache

    def bind(self, desc: dict) -> Node:
        kind, body = parse(desc)
        return kind, self.table.kinds.module("predicates", kind).bind(body, self)


class BuildContext:
    def __init__(self, ds):
        self.ds = ds
        self.pool = ds.pool

    def build(self, node: Node):
        kind, params = node
        return self.ds.kinds.module("predicates", kind).build(params, self)


def bind(desc: dict, table, vectors: Dict[str, int]) -> Node:
    return BindContext(table, vectors).bind(desc)


def build(node: Node, ds):
    return BuildContext(ds).build(node)


def stack(nodes: Sequence[Node]) -> Node:
    """One batched node from the bound nodes of ``b`` requests of one
    archetype: shared strings, numbers as arrays of ``b``."""
    kind = nodes[0][0]
    first = nodes[0][1]
    if isinstance(first, dict):
        out = {}
        for key, v in first.items():
            if isinstance(v, str):
                out[key] = v
            else:
                out[key] = np.asarray([n[1][key] for n in nodes])
        return kind, out
    return kind, tuple(stack([n[1][i] for n in nodes])
                       for i in range(len(first)))


def split(node: Node) -> Tuple[tuple, List[np.ndarray]]:
    """A batched node as a hashable structure and its arrays."""
    arrays: List[np.ndarray] = []

    def go(n):
        kind, params = n
        if isinstance(params, dict):
            items = []
            for key in sorted(params):
                v = params[key]
                if isinstance(v, str):
                    items.append((key, "s", v))
                else:
                    items.append((key, "a", len(arrays)))
                    arrays.append(v)
            return kind, "d", tuple(items)
        return kind, "t", tuple(go(c) for c in params)

    return go(node), arrays


def join(static: tuple, arrays: Sequence) -> Node:
    kind, form, body = static
    if form == "d":
        return kind, {key: (arrays[v] if how == "a" else v)
                      for key, how, v in body}
    return kind, tuple(join(c, arrays) for c in body)


def mask(node: Node, view, band: float, kinds):
    """The ``(b, n)`` row mask of a batched node over ``view``."""
    kind, params = node
    return kinds.module("predicates", kind).mask(
        params, view, band, lambda child: mask(child, view, band, kinds))


def vectors_of(mix: dict) -> List[str]:
    """The held-out pool vectors each request of ``mix`` draws, by name;
    the first is its V.K query."""
    return list(mix.get("vectors", ["q"]))

