"""Shared HLO / StableHLO text parsing (DESIGN.md §15).

One walker for the two textual formats JAX shows us:

  * **optimized HLO** (``compiled.as_text()``) — shapes spelled
    ``f32[4,8193,1]``; consumed by ``launch.roofline``'s computation
    walk (``shape_bytes`` / ``shape_dims``).
  * **lowered StableHLO** (``fn.lower(*avals).as_text()``) — MLIR
    ``tensor<4x8193x1xf32>`` types plus module attributes
    (``mhlo.num_partitions``, ``mhlo.sharding``, donation markers);
    consumed by ``core.tracing.hlo_stats`` and the static traffic
    accounting in ``analysis.cost`` (``main_io_bytes``).

PR 6 landed the dtype table and shape regexes twice (``launch/roofline``
and ``core/tracing`` each carried a private copy); this module is now
the single home — both re-export from here.  Stdlib-only on purpose:
report tooling parses HLO text without importing jax.
"""
from __future__ import annotations

import re

# --------------------------------------------------------------------------
# optimized-HLO side: f32[4,8193,1]
# --------------------------------------------------------------------------

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(type_str: str) -> int:
    """Total bytes of every ``dtype[dims]`` shape in an HLO type string.

    Tuple types contribute the sum of their elements; unknown dtypes
    (opaque, token) are skipped.
    """
    total = 0
    for dtype, dims in SHAPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def shape_dims(type_str: str) -> list[int]:
    """Dimensions of the first shape in an HLO type string."""
    m = SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


# --------------------------------------------------------------------------
# StableHLO (MLIR) side: tensor<4x8193x1xf32>
# --------------------------------------------------------------------------

TENSOR_RE = re.compile(r"tensor<([^>]*)>")

# MLIR element types; signless iN covers both signed and unsigned jax ints
MLIR_DTYPE_BYTES = {
    "i1": 1, "i8": 1, "ui8": 1, "f8E4M3FN": 1, "f8E5M2": 1,
    "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "i32": 4, "ui32": 4, "f32": 4,
    "i64": 8, "ui64": 8, "f64": 8,
}


def tensor_bytes(inner: str) -> int:
    """Bytes of one MLIR tensor type body (``4x9x1xf32``, ``f32``, ...).

    Unknown element types (complex, dynamic ``?`` dims) count as 0 —
    same convention as ``shape_bytes`` skipping opaque dtypes.
    """
    parts = inner.split("x")
    elem = parts[-1]
    if elem not in MLIR_DTYPE_BYTES:
        return 0
    n = 1
    for d in parts[:-1]:
        if not d.isdigit():
            return 0
        n *= int(d)
    return n * MLIR_DTYPE_BYTES[elem]


_MAIN_RE = re.compile(r"func\.func\s+(?:public\s+)?@main\s*\(")


def main_signature(text: str) -> tuple[str, str]:
    """The ``@main`` argument and result type substrings of a lowered
    StableHLO module (balanced-paren scan; the signature may wrap).

    Returns ``(args, results)`` raw text; ``("", "")`` if no ``@main``.
    """
    m = _MAIN_RE.search(text)
    if not m:
        return "", ""
    # attribute strings ("{devices=[4,2]<=[8]}") contain unbalanced
    # brackets — both scans must skip over quoted spans
    i = m.end()                       # just past the arg-list "("
    depth = 1
    start = i
    while i < len(text) and depth:
        c = text[i]
        if c == '"':
            i = text.find('"', i + 1)
            if i < 0:
                return text[start:], ""
        elif c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        i += 1
    args = text[start:i - 1]
    rest = text[i:]
    arrow = rest.find("->")
    if arrow < 0:
        return args, ""
    # results run from "->" to the body "{" at paren depth 0
    j = arrow + 2
    depth = 0
    while j < len(rest):
        c = rest[j]
        if c == '"':
            j = rest.find('"', j + 1)
            if j < 0:
                break
        elif c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "{" and depth == 0:
            break
        j += 1
    return args, rest[arrow + 2:j]


def main_io_bytes(text: str) -> dict:
    """Launch-boundary traffic of a lowered module: bytes of every
    tensor in the ``@main`` signature.

    ``{"arg_bytes", "result_bytes", "total"}`` — the static analogue of
    what one launch moves across HBM at the executable's boundary
    (global logical shapes; sharding divides them across devices but
    never changes the total).  ``analysis.cost`` reconciles this against
    the byte count predicted from ``ExecKey`` geometry
    (``traffic-conservation``).
    """
    args, results = main_signature(text)
    arg_b = sum(tensor_bytes(t) for t in TENSOR_RE.findall(args))
    res_b = sum(tensor_bytes(t) for t in TENSOR_RE.findall(results))
    return {"arg_bytes": arg_b, "result_bytes": res_b,
            "total": arg_b + res_b}


# --------------------------------------------------------------------------
# lowered-module attribute census (donation / partitioning markers)
# --------------------------------------------------------------------------

RE_PARTITIONS = re.compile(r"num_partitions\s*=\s*(\d+)")
# Shardy annotations: ``sdy.mesh @mesh = <["data"=4, "lane"=2]>`` and, per
# value, ``#sdy.sharding<@mesh, [{"data"}, {"lane"}]>`` (one {} per dim)
RE_SDY_MESH = re.compile(r"sdy\.mesh\s+@([\w.]+)\s*=\s*<\[([^\]]*)\]")
RE_SHARDING = re.compile(r"#sdy\.sharding<@([\w.]+),\s*\[((?:\{[^}]*\}"
                         r"(?:,\s*)?)*)\]")
RE_ALIASING = re.compile(r"tf\.aliasing_output|jax\.buffer_donor")


def _tile(mesh: dict, dims: str) -> str:
    """``devices=[d0,d1,...]``: the shard count of each dim of a Shardy
    sharding — the same tile spelling a GSPMD annotation uses."""
    counts = []
    for dim in re.findall(r"\{([^}]*)\}", dims):
        n = 1
        for ax in re.findall(r'"([^"]+)"', dim):
            n *= mesh.get(ax, 1)
        counts.append(str(n))
    return f"devices=[{','.join(counts)}]"


def hlo_stats(text: str) -> dict:
    """Structured census of a lowered module's text
    (``fn.lower(*avals).as_text()``).

    Returns ``num_partitions`` (1 when unpartitioned), the set of value
    shardings as ``devices=[...]`` tiles (shard count per dim, from the
    Shardy annotations), and ``aliased_params`` — the number of
    input/output aliasing (donation) markers.
    """
    m = RE_PARTITIONS.search(text)
    meshes = {name: {ax: int(n) for ax, n in
                     re.findall(r'"([^"]+)"\s*=\s*(\d+)', body)}
              for name, body in RE_SDY_MESH.findall(text)}
    return {
        "num_partitions": int(m.group(1)) if m else 1,
        "shardings": {_tile(meshes.get(name, {}), dims)
                      for name, dims in RE_SHARDING.findall(text)},
        "aliased_params": len(RE_ALIASING.findall(text)),
    }
