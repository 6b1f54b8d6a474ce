"""Bytes a Spatter pattern moves, from the pattern alone.

A pattern is (index buffer, delta, count) over float32 rows of
``row_width`` elements: op ``i`` touches elements ``delta * i + o`` for
every offset ``o`` in the index buffer (paper §3.1).  Nothing here looks
at how the program lays out tables or indices, so the numbers stay the
same whatever implements the pattern.

- ``useful_bytes``: the paper's §3.5 numerator,
  ``index_len * count * elem_bytes``.
- ``least_bytes``: the least HBM traffic one execution needs.  A gather
  reads each distinct table element once and writes every output
  element; a store scatter reads the values that land (one per distinct
  row written) and writes those rows.  Index bytes count zero: Spatter
  defines the indices by (index buffer, delta), so a kernel can compute
  them instead of reading them.
"""
from __future__ import annotations

ELEM_BYTES = 4          # float32


def parse_index(spec) -> list[int]:
    """The index buffer of a wire pattern: ``"CUSTOM:0,1,2"`` or a list."""
    if isinstance(spec, str):
        head, _, body = spec.partition(":")
        if head != "CUSTOM":
            raise ValueError(f"only CUSTOM index buffers are read here, "
                             f"got {spec!r}")
        return [int(t) for t in body.split(",")]
    return [int(t) for t in spec]


def footprint(index: list[int], delta: int, count: int) -> int:
    """Elements the table must hold: the last op's base plus its span."""
    return delta * (count - 1) + max(index) + 1


def distinct(index: list[int], delta: int, count: int) -> int:
    """Number of distinct elements ``{delta * i + o}`` touched, exactly.

    With ``delta > 0`` write ``o = q * delta + r``: offsets of one residue
    ``r`` touch the runs ``[q, q + count)`` of multiples of ``delta``, so
    the count is the length of the union of those runs, summed over the
    residues.  O(len(index) log len(index)) whatever the count.
    """
    if delta == 0:
        return len(set(index))
    runs: dict[int, set[int]] = {}
    for o in index:
        runs.setdefault(o % delta, set()).add(o // delta)
    total = 0
    for starts in runs.values():
        end = None
        for q in sorted(starts):
            lo = q if end is None else max(q, end)
            end = q + count
            total += max(0, end - lo)
    return total


def useful_bytes(p: dict, row_width: int = 1) -> int:
    return len(parse_index(p["pattern"])) * p["count"] * ELEM_BYTES \
        * row_width


def least_bytes(p: dict, row_width: int = 1) -> int:
    index = parse_index(p["pattern"])
    d = distinct(index, p["delta"], p["count"])
    if p["kernel"] == "gather":
        moved = d + len(index) * p["count"]
    else:
        moved = 2 * d
    return moved * ELEM_BYTES * row_width
