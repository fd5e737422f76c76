"""Static parsing of lowered StableHLO and compiled HLO text.

The parsing layer of the analysis pass framework — grown out of
``parallel/hlo_stats.py`` (which now re-exports from here): under XLA the
collectives, dots and buffer-donation aliases are explicit in the program
text, so every performance invariant the framework establishes (collective
budgets, O(1)-in-prefix decode FLOPs, donation round-trips) is *statically*
checkable from ``jit(...).lower(...)`` output, no accelerator required.

Three families of entry points:

* byte accounting — :func:`shape_bytes` / :func:`shape_bytes_report` /
  :func:`collective_stats`;
* FLOP accounting — :func:`dot_flops` / :func:`dot_flops_report` (the
  report carries ``uncounted_ops`` so dot-like ops the counter cannot
  parse are a signal, not a silent zero);
* program metadata — :func:`input_output_aliases` (compiled-HLO donation
  aliasing) and :func:`instructions` (every instruction of an optimized
  module with its opcode, shape, operands and ``op_name``: what
  ``obs.scopes`` builds its maps on).
"""
from __future__ import annotations

import collections
import re

__all__ = [
    "collective_stats",
    "dot_flops",
    "dot_flops_report",
    "input_output_aliases",
    "instructions",
    "shape_bytes",
    "shape_bytes_report",
    "shape_str",
    "stablehlo_collective_stats",
    "stablehlo_gather_stats",
    "stablehlo_sort_scatter_stats",
]

# Bit widths per HLO/StableHLO element type.  Sub-byte types (s4/u4, the
# fp4/fp8 menagerie) are sized in bits and rounded up per-shape, matching
# XLA's packed layouts closely enough for budget accounting.
_DTYPE_BITS = {
    "f64": 64, "f32": 32, "f16": 16, "bf16": 16,
    "f8e4m3": 8, "f8e4m3fn": 8, "f8e4m3fnuz": 8, "f8e4m3b11fnuz": 8,
    "f8e5m2": 8, "f8e5m2fnuz": 8, "f8e3m4": 8, "f8e8m0fnu": 8,
    "f4e2m1fn": 4,
    "s64": 64, "u64": 64, "s32": 32, "u32": 32, "s16": 16, "u16": 16,
    "s8": 8, "u8": 8, "s4": 4, "u4": 4, "s2": 2, "u2": 2,
    "pred": 8, "c64": 64, "c128": 128,
    # StableHLO spells integers signless (i8, not s8) and bools i1 —
    # the lowered-dialect byte accounting (collective payloads, gather
    # intermediates) reads these; compiled HLO never produces them.
    # i1 is stored one byte per element, like pred.
    "i64": 64, "i32": 32, "i16": 16, "i8": 8, "i4": 4, "i2": 2, "i1": 8,
    "ui64": 64, "ui32": 32, "ui16": 16, "ui8": 8, "ui4": 4, "ui2": 2,
}

# dtype-shaped names only — 'pred', 'bf16', or letter-digit-led tokens
# like f32/s4/u8/c64/f8e4m3fn — so identifier[index] strings in HLO
# metadata (op_name="params[0]", arg names) never read as shapes
_SHAPE_RE = re.compile(r"\b(pred|bf16|[fsuc][0-9][0-9a-z]*)\[([0-9,]*)\]")

# an instruction line: '%name = SHAPE op(...)'.  SHAPE is extracted with a
# balanced-paren scan, not a depth-limited regex: tuple shapes nest (grouped
# async collectives carry tuples of buffers) and TPU layout annotations like
# {1,0:T(8,128)} add parens at arbitrary depth inside them.
_INSTR_RE = re.compile(r"=\s*")
_OP_RE = re.compile(
    r"\s*(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")

def _scan_shape(line, start):
    """Return (shape_str, end_index) for the shape beginning at `start` —
    either a balanced parenthesized tuple or a single whitespace-free
    token."""
    if start < len(line) and line[start] == "(":
        depth = 0
        for i in range(start, len(line)):
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
                if depth == 0:
                    return line[start:i + 1], i + 1
        return line[start:], len(line)
    m = re.match(r"\S+", line[start:])
    if m is None:
        return "", start
    return m.group(0), start + m.end()


def shape_bytes_report(shape_str):
    """(total_bytes, unknown_dtypes) over every 'dtype[dims]' shape in the
    string (tuples ok).  Element types missing from the width table land in
    ``unknown_dtypes`` (sorted, deduped) instead of silently contributing
    zero — the analysis FLOP/byte passes turn a non-empty list into a
    recorded finding."""
    total = 0
    unknown = set()
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        bits = _DTYPE_BITS.get(dtype)
        if bits is None:
            unknown.add(dtype)
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += (n * bits + 7) // 8
    return total, sorted(unknown)


def shape_bytes(shape_str):
    """Total bytes of every 'dtype[dims]' shape in the string (tuples ok).
    Unknown dtypes contribute zero here — use :func:`shape_bytes_report`
    when the caller needs them surfaced."""
    return shape_bytes_report(shape_str)[0]


# numpy/ml_dtypes names -> HLO element-type codes, the inverse direction of
# _SHAPE_RE: renders python-side array metadata into the same 'dtype[dims]'
# strings shape_bytes sizes, so static byte budgets (the decode cache-bytes
# pass) share one width table with the program-text parsers.
_NP_TO_HLO = {
    "float64": "f64", "float32": "f32", "float16": "f16",
    "bfloat16": "bf16", "bool": "pred",
    "int64": "s64", "int32": "s32", "int16": "s16", "int8": "s8",
    "uint64": "u64", "uint32": "u32", "uint16": "u16", "uint8": "u8",
    "int4": "s4", "uint4": "u4", "int2": "s2", "uint2": "u2",
    "float8_e4m3": "f8e4m3", "float8_e4m3fn": "f8e4m3fn",
    "float8_e4m3fnuz": "f8e4m3fnuz", "float8_e4m3b11fnuz": "f8e4m3b11fnuz",
    "float8_e5m2": "f8e5m2", "float8_e5m2fnuz": "f8e5m2fnuz",
    "float8_e3m4": "f8e3m4", "float8_e8m0fnu": "f8e8m0fnu",
    "float4_e2m1fn": "f4e2m1fn",
    "complex64": "c64", "complex128": "c128",
}


def shape_str(shape, dtype):
    """Render ``(shape, dtype)`` as the HLO ``'dtype[dims]'`` string the
    byte accountants parse — e.g. ``shape_str((2, 16, 8), jnp.int8)`` ->
    ``'s8[2,16,8]'``.  Unknown dtypes raise (a silent zero would defeat
    the budget)."""
    import numpy as _np

    name = _np.dtype(dtype).name
    code = _NP_TO_HLO.get(name)
    if code is None:
        raise KeyError("no HLO element-type code for dtype %r" % name)
    return "%s[%s]" % (code, ",".join(str(int(d)) for d in shape))


def _split_top_level(tuple_str):
    """Split '(a, (b, c), d)' into top-level elements ['a', '(b, c)', 'd']."""
    s = tuple_str.strip()
    if not (s.startswith("(") and s.endswith(")")):
        return [s]
    s = s[1:-1]
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(s):
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(s[start:i])
            start = i + 1
    parts.append(s[start:])
    return [p.strip() for p in parts if p.strip()]


def _start_bytes(op, shape_s):
    """Result payload of an async '-start' tuple shape.

    The tuple layout is op-specific (verified against compiled HLO):
    ``all-reduce-start`` has the SAME shape as the sync op — a flat tuple
    of results when XLA combined several all-reduces — so every buffer
    counts.  ``all-gather-start`` / ``reduce-scatter-start`` /
    ``collective-permute-start`` carry
    ``(operand(s), result(s), [u32 context scalars...])`` — count only
    the result element (itself possibly a tuple for grouped ops).
    Summing naively would double those (reduce-scatter-start used to fall
    into the generic fallback and did exactly that, inflating absolute
    KiB/step); taking the single largest buffer (the old rule)
    undercounts any grouped form.
    """
    parts = _split_top_level(shape_s)
    parts = [p for p in parts
             if not re.fullmatch(r"[su]32\[\]\S*", p)]  # context scalars
    if not parts:
        return 0
    if op == "all-reduce":
        return sum(shape_bytes(p) for p in parts)
    if op in ("all-gather", "reduce-scatter", "collective-permute") \
            and len(parts) >= 2:
        return shape_bytes(parts[1])
    # generic async wrapper: ((operands...), results, ctx) — a leading
    # tuple element marks the operand pack; otherwise flat results
    if len(parts) >= 2 and parts[0].startswith("("):
        return shape_bytes(parts[1])
    return sum(shape_bytes(p) for p in parts)


# stablehlo: '%3 = stablehlo.dot_general %1, %2, batching_dims = [0] x [0],
#   contracting_dims = [1] x [0] ... : (tensor<8x128xf32>, ...) -> tensor<...>'
_SH_DOT_GENERAL_RE = re.compile(
    r"dot_general\b.*?contracting_dims\s*=\s*"
    r"\[([0-9,\s]*)\]\s*x\s*\[[0-9,\s]*\]"
    r".*?:\s*\(tensor<([^>]+)>.*?->\s*tensor<([^>]+)>")
# stablehlo non-general dot: '%3 = stablehlo.dot %1, %2 {...} :
#   (tensor<8x128xf32>, tensor<128x32xf32>) -> tensor<8x32xf32>' — matrix /
#   matrix-vector / dot-product semantics: the contraction is always the
#   lhs LAST dimension against the rhs first.
_SH_DOT_RE = re.compile(
    r"stablehlo\.dot\s+[^:]*:\s*\(tensor<([^>]+)>\s*,\s*tensor<([^>]+)>\s*\)"
    r"\s*->\s*tensor<([^>]+)>")
# HLO: '%dot.3 = f32[8,512]{1,0} dot(f32[8,128]{1,0} %a, ...),
#   lhs_contracting_dims={1}, rhs_contracting_dims={0}'
_HLO_DOT_RE = re.compile(
    r"=\s*([a-z][a-z0-9]+\[[0-9,]*\])\S*\s+dot\(\s*([a-z][a-z0-9]+\[[0-9,]*\])"
    r".*?lhs_contracting_dims=\{([0-9,]*)\}")
# stablehlo convolution: '%4 = stablehlo.convolution(%1, %2)
#   dim_numbers = [b, 0, 1, f]x[0, 1, i, o]->[b, 0, 1, f], window = {...}
#   {feature_group_count = 1 : i64, ...} : (tensor<1x8x8x3xf32>,
#   tensor<3x3x3x16xf32>) -> tensor<1x6x6x16xf32>'.  The FLOP model reads
# the RHS (kernel) dim roles from the middle dim_numbers group: per output
# element the contraction is i x spatial (the kernel's i dim is already
# C_in/groups in the IR, so feature_group_count needs no special casing).
_SH_CONV_RE = re.compile(
    r"stablehlo\.convolution\b.*?dim_numbers\s*=\s*\[[^\]]*\]\s*x\s*"
    r"\[([^\]]*)\]\s*->"
    r".*?:\s*\(tensor<([^>]+)>\s*,\s*tensor<([^>]+)>\s*\)"
    r"\s*->\s*tensor<([^>]+)>")
# HLO convolution: '%conv = f32[1,16,6,6]{...} convolution(
#   f32[1,3,8,8]{...} %x, f32[16,3,3,3]{...} %w), window={size=3x3},
#   dim_labels=bf01_oi01->bf01' — kernel dim roles from the middle
# dim_labels group (chars: o, i, spatial digits).
_HLO_CONV_RE = re.compile(
    r"=\s*([a-z][a-z0-9]+\[[0-9,]*\])\S*\s+convolution\("
    r"[^(]*?,\s*([a-z][a-z0-9]+\[[0-9,]*\])"
    r".*?dim_labels=[a-z0-9]+_([a-z0-9]+)->")
# label-less convolution fallbacks: the shapes alone, for lines whose
# dim_labels/dim_numbers metadata was stripped (debug dumps, minimized
# repros).  dim-role parsing stays the PREFERRED path — these only match
# after it fails, and the contraction is inferred from the conventional
# kernel layout (HLO 'oi01': output features FIRST; StableHLO
# '[0, 1, i, o]': output features LAST), cross-checked against the
# result shape before counting.
_HLO_CONV_NOLABEL_RE = re.compile(
    r"=\s*([a-z][a-z0-9]+\[[0-9,]*\])\S*\s+convolution\("
    r"[^(]*?,\s*([a-z][a-z0-9]+\[[0-9,]*\])")
_SH_CONV_NOLABEL_RE = re.compile(
    r"stablehlo\.convolution\b"
    r".*?:\s*\(tensor<([^>]+)>\s*,\s*tensor<([^>]+)>\s*\)"
    r"\s*->\s*tensor<([^>]+)>")

# dot-like ops the counter knows it does NOT model: any appearance goes to
# the report's uncounted_ops so a program using them cannot silently read
# as zero FLOPs.  HLO 'dot(' lines missing contracting-dims metadata,
# convolutions whose shapes defeat even the label-less fallback, and
# unparseable stablehlo dot forms are appended dynamically.
_UNCOUNTED_RE = re.compile(
    r"(stablehlo\.convolution\b"
    r"|(?<![-\w])convolution\("
    r"|stablehlo\.dot_general\b"
    r"|stablehlo\.dot\b"
    r"|(?<![-\w.])dot\()")
_UNCOUNTED_NAMES = {
    "stablehlo.convolution": "stablehlo.convolution",
    "convolution(": "convolution",
    "stablehlo.dot_general": "stablehlo.dot_general",
    "stablehlo.dot": "stablehlo.dot",
    "dot(": "dot",
}


def _tensor_dims(spec):
    """'2x4x64xf32' -> [2, 4, 64] (scalar 'f32' -> [])."""
    return [int(d) for d in spec.split("x")[:-1]]


def _tensor_dtype(spec):
    """'2x4x64xf32' -> 'f32'."""
    return spec.split("x")[-1]


def _bracket_dims(spec):
    """'f32[8,128]' -> [8, 128]."""
    inner = spec[spec.index("[") + 1:spec.index("]")]
    return [int(d) for d in inner.split(",") if d]


def _bracket_dtype(spec):
    """'f32[8,128]' -> 'f32'."""
    return spec[:spec.index("[")]


def _prod(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def _conv_contraction(rhs_dims, rhs_spec):
    """Per-output-element multiply count of a convolution: the kernel's
    ``i`` dim (already C_in / feature_group_count in both dialects) times
    its spatial dims.  ``rhs_spec`` is the kernel dim-role string — a
    stablehlo ``dim_numbers`` group like ``'0, 1, i, o'`` or an HLO
    ``dim_labels`` group like ``'oi01'``.  Returns None (-> uncounted)
    when the roles don't line up with the shape."""
    roles = [t for t in re.split(r"[,\s]+", rhs_spec.strip()) if t] \
        if "," in rhs_spec or " " in rhs_spec else list(rhs_spec.strip())
    if len(roles) != len(rhs_dims) or "i" not in roles:
        return None
    contraction = 1
    for role, dim in zip(roles, rhs_dims):
        if role != "o":
            contraction *= dim
    return contraction


def _conv_contraction_from_shapes(rhs_dims, out_dims, o_first):
    """Per-output-element multiply count of a LABEL-LESS convolution,
    inferred from the kernel and result shapes alone: contraction =
    prod(kernel dims) / output-feature dim.  The output-feature dim is
    taken from the conventional kernel layout of the dialect
    (``o_first`` True for HLO's ``oi01``, False for StableHLO's
    ``[0, 1, i, o]``), cross-checked against the result shape — a
    candidate ``o`` absent from the result dims falls back to the other
    end, and None (-> uncounted) when neither lines up.  Exact when the
    layout convention holds; a floor (never an overcount of the honest
    per-element work) otherwise, since every kernel element multiplies
    at most once per output element."""
    if not rhs_dims or not out_dims:
        return None
    ends = (0, -1) if o_first else (-1, 0)
    for end in ends:
        o = rhs_dims[end]
        if o in out_dims:
            return _prod(rhs_dims) // o
    return None


def dot_flops_report(program_text):
    """Structured matmul-FLOP accounting of a lowered program.

    Returns ``{"flops": int, "dots": [...], "uncounted_ops": [...]}``:

    * ``flops`` — total 2 * result elements * contraction size over every
      parsed dot (StableHLO ``dot_general`` and non-general ``dot``, HLO
      ``dot(`` lines; fusion bodies included) and convolution (either
      dialect: contraction = kernel i-dim x spatial dims, read from
      ``dim_numbers``/``dim_labels`` — grouped convs need no special
      casing, the IR kernel's i dim is already C_in/groups);
    * ``dots`` — one record per parsed line: ``{"op", "dtype"
      (result element type), "flops", "line"}`` — the dtype-lint pass
      reads these to flag f32 dots inside bf16 programs;
    * ``uncounted_ops`` — dot-like ops the counter saw but could not
      model (malformed dot lines, convolutions whose shapes defeat even
      the label-less fallback), as ``{"op", "count"}`` aggregates.  A
      non-empty list means ``flops`` is a floor, not a total — the
      FLOP-coverage pass turns it into an error.

    Convolutions parse through dim-role metadata first
    (``dim_numbers``/``dim_labels``); a LABEL-LESS conv falls back to
    shape inference (:func:`_conv_contraction_from_shapes` — contraction
    = prod(kernel dims) / output-feature dim under the dialect's
    conventional kernel layout) and its dot record carries
    ``"inferred": True`` so audits can tell exact from inferred counts.
    """
    total = 0
    dots = []
    uncounted = {}

    def _count_uncounted(name):
        uncounted[name] = uncounted.get(name, 0) + 1

    for line in program_text.splitlines():
        m = _SH_DOT_GENERAL_RE.search(line)
        if m is not None:
            cdims = [int(d) for d in m.group(1).replace(" ", "").split(",")
                     if d]
            lhs = _tensor_dims(m.group(2))
            out = _tensor_dims(m.group(3))
            flops = 2 * _prod(out) * _prod(lhs[d] for d in cdims)
            total += flops
            dots.append({"op": "stablehlo.dot_general",
                         "dtype": _tensor_dtype(m.group(3)),
                         "flops": flops, "line": line.strip()})
            continue
        m = _SH_DOT_RE.search(line)
        if m is not None:
            lhs = _tensor_dims(m.group(1))
            out = _tensor_dims(m.group(3))
            # stablehlo.dot contracts lhs's last dim; a scalar-shaped lhs
            # (pure dot product) contracts its only dim
            contract = lhs[-1] if lhs else 1
            flops = 2 * _prod(out) * contract
            total += flops
            dots.append({"op": "stablehlo.dot",
                         "dtype": _tensor_dtype(m.group(3)),
                         "flops": flops, "line": line.strip()})
            continue
        m = _HLO_DOT_RE.search(line)
        if m is not None:
            out = _bracket_dims(m.group(1))
            lhs = _bracket_dims(m.group(2))
            cdims = [int(d) for d in m.group(3).split(",") if d]
            flops = 2 * _prod(out) * _prod(lhs[d] for d in cdims)
            total += flops
            dots.append({"op": "dot", "dtype": _bracket_dtype(m.group(1)),
                         "flops": flops, "line": line.strip()})
            continue
        m = _SH_CONV_RE.search(line)
        if m is not None:
            contraction = _conv_contraction(_tensor_dims(m.group(3)),
                                            m.group(1))
            if contraction is not None:
                out = _tensor_dims(m.group(4))
                flops = 2 * _prod(out) * contraction
                total += flops
                dots.append({"op": "stablehlo.convolution",
                             "dtype": _tensor_dtype(m.group(4)),
                             "flops": flops, "line": line.strip()})
                continue
        m = _HLO_CONV_RE.search(line)
        if m is not None:
            contraction = _conv_contraction(_bracket_dims(m.group(2)),
                                            m.group(3))
            if contraction is not None:
                out = _bracket_dims(m.group(1))
                flops = 2 * _prod(out) * contraction
                total += flops
                dots.append({"op": "convolution",
                             "dtype": _bracket_dtype(m.group(1)),
                             "flops": flops, "line": line.strip()})
                continue
        # label-less fallbacks: contraction from operand/result shapes
        # when the dim-role metadata is absent or unparsable (the
        # preferred labeled paths above already failed on this line)
        m = _SH_CONV_NOLABEL_RE.search(line)
        if m is not None and "stablehlo.convolution" in line:
            contraction = _conv_contraction_from_shapes(
                _tensor_dims(m.group(2)), _tensor_dims(m.group(3)),
                o_first=False)
            if contraction is not None:
                out = _tensor_dims(m.group(3))
                flops = 2 * _prod(out) * contraction
                total += flops
                dots.append({"op": "stablehlo.convolution",
                             "dtype": _tensor_dtype(m.group(3)),
                             "flops": flops, "inferred": True,
                             "line": line.strip()})
                continue
        m = _HLO_CONV_NOLABEL_RE.search(line)
        if m is not None:
            contraction = _conv_contraction_from_shapes(
                _bracket_dims(m.group(2)), _bracket_dims(m.group(1)),
                o_first=True)
            if contraction is not None:
                out = _bracket_dims(m.group(1))
                flops = 2 * _prod(out) * contraction
                total += flops
                dots.append({"op": "convolution",
                             "dtype": _bracket_dtype(m.group(1)),
                             "flops": flops, "inferred": True,
                             "line": line.strip()})
                continue
        m = _UNCOUNTED_RE.search(line)
        if m is not None:
            _count_uncounted(_UNCOUNTED_NAMES[m.group(1)])
    return {
        "flops": total,
        "dots": dots,
        "uncounted_ops": [{"op": k, "count": v}
                          for k, v in sorted(uncounted.items())],
    }


def dot_flops(program_text):
    """Total matmul FLOPs (2 * result elements * contraction size) of every
    dot in a lowered program — StableHLO ``dot_general`` / ``dot`` and HLO
    ``dot(`` lines all count, fusion bodies included.

    The decode benchmark's O(1)-in-prefix assertion rests on this: a
    KV-cached decode step's dot FLOPs are a constant while the
    recompute-the-prefix program's grow linearly with T.  Static counting
    (like :func:`collective_stats`) — no execution, backend-independent
    when fed ``jit(...).lower(...).as_text()``.  Dot-like ops the counter
    cannot parse contribute zero here; :func:`dot_flops_report` surfaces
    them as ``uncounted_ops``.
    """
    return dot_flops_report(program_text)["flops"]


_ALIAS_ENTRY_RE = re.compile(
    r"\{([0-9,\s]*)\}:\s*\(\s*([0-9]+)\s*,\s*\{[0-9,\s]*\}")


def input_output_aliases(compiled_text):
    """Donation aliases of a compiled HLO module.

    Parses the module header's ``input_output_alias={ {out}: (param,
    {index}, kind), ... }`` block into a list of ``(output_index_path,
    parameter_number)`` tuples.  An empty list means XLA aliased nothing —
    for a program traced with ``donate_argnums`` that is a dropped
    donation (the donation-auditor pass's error condition).
    """
    # the block lives on the HloModule header line (nested braces, so a
    # balanced scan, not a regex); only that line is consulted so a string
    # constant elsewhere cannot fake a header
    for line in compiled_text.splitlines():
        if "HloModule" not in line:
            continue
        key = "input_output_alias={"
        at = line.find(key)
        if at < 0:
            return []
        depth, start = 1, at + len(key)
        end = start
        for i in range(start, len(line)):
            if line[i] == "{":
                depth += 1
            elif line[i] == "}":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        entries = []
        for out_idx, param in _ALIAS_ENTRY_RE.findall(line[start:end]):
            path = tuple(int(d) for d in out_idx.split(",") if d.strip())
            entries.append((path, int(param)))
        return entries
    return []


# StableHLO collectives (the LOWERED dialect, before backend
# legalization): explicit shard_map collectives — the MoE all-to-all
# dispatch, ring ppermutes, Megatron psums — appear here by name, so the
# roofline traffic accounting (analysis/cost.py) can price a program's
# wire bytes with trace+lower only, no compile.  Result types live on
# the op line (`-> tensor<...>`) except for region-bearing ops
# (all_reduce / reduce_scatter carry a reduction block), whose signature
# lands on the region's closing `}) : (...) -> ...` line.
_SH_COLLECTIVE_RE = re.compile(
    r"\"?stablehlo\.(all_to_all|all_gather|all_reduce|collective_permute"
    r"|collective_broadcast|reduce_scatter)\"?\b")
_SH_RESULT_RE = re.compile(r"->\s*(.+?)\s*$")
_SH_TENSOR_RE = re.compile(r"tensor<([^>]+)>")

# stablehlo op -> the compiled-HLO spelling, so budget files and reports
# share one collective vocabulary across both dialects
_SH_TO_HLO_OP = {
    "all_to_all": "all-to-all", "all_gather": "all-gather",
    "all_reduce": "all-reduce", "collective_permute": "collective-permute",
    "collective_broadcast": "collective-broadcast",
    "reduce_scatter": "reduce-scatter",
}


def _sh_result_bytes(line):
    """Total bytes of every tensor<> in the line's `-> ...` result type
    (tuples sum); None when the line carries no arrow."""
    m = _SH_RESULT_RE.search(line)
    if m is None:
        return None
    total = 0
    for spec in _SH_TENSOR_RE.findall(m.group(1)):
        dims = _tensor_dims(spec)
        bits = _DTYPE_BITS.get(_tensor_dtype(spec))
        if bits is None:
            continue
        total += (_prod(dims) * bits + 7) // 8
    return total


def stablehlo_collective_stats(stablehlo_text):
    """Count collectives and sum their result payloads in LOWERED
    StableHLO text — the same report shape as :func:`collective_stats`
    ({op: {"count", "bytes"}} + "total"), with ops named in the
    compiled-HLO spelling so the two dialects share a vocabulary.
    Region-bearing ops (all_reduce) print their type signature on the
    region's closing line; a pending queue matches them up (reduction
    bodies never nest further collectives)."""
    stats = {}
    pending = []

    def _note(op, nbytes):
        entry = stats.setdefault(op, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += nbytes or 0

    for line in stablehlo_text.splitlines():
        m = _SH_COLLECTIVE_RE.search(line)
        if m is not None:
            op = _SH_TO_HLO_OP[m.group(1)]
            nbytes = _sh_result_bytes(line)
            if nbytes is None:
                pending.append(op)     # region op: signature comes later
            else:
                _note(op, nbytes)
            continue
        if pending and line.lstrip().startswith("})") and "->" in line:
            _note(pending.pop(0), _sh_result_bytes(line))
    total = {"count": sum(e["count"] for e in stats.values()),
             "bytes": sum(e["bytes"] for e in stats.values())}
    stats["total"] = total
    return stats


# Materialized-gather traffic: stablehlo.gather (jnp.take / advanced
# indexing — the decode path's paged_gather walks the whole KV pool
# through one of these) writes its result tensor to memory and the
# consumer reads it back, so each gather's HONEST traffic floor is
# 2x its result bytes ON TOP of the operand reads the arg/output
# accounting already covers.  dynamic_slice is deliberately excluded:
# its results are register/VMEM-sized views a fusion almost never
# materializes, while a gather's data-dependent indices defeat fusion
# into the consumer on every backend we target.
_SH_GATHER_RE = re.compile(r"\"?stablehlo\.(?:dynamic_)?gather\"?\b")


def stablehlo_gather_stats(stablehlo_text):
    """``{"count", "bytes"}`` of materialized gather intermediates in
    LOWERED StableHLO text: ``bytes`` is 2x the summed gather-result
    bytes (one write, one re-read by the consumer).

    This is what makes :func:`~mxnet_tpu.analysis.cost.program_cost`
    price the einsum decode path honestly — ``ops.attention.paged_gather``
    materializes a full (B, M*page_tokens, E) dense-ring view of the KV
    pool per K and V per layer, the single largest intermediate in the
    serving system, which pure arg+output accounting cannot see.  The
    decode row's Pallas kernel has no such gather (it copies the live
    blocks' pages inside the kernel, ``ops/pallas_decode.py``), so a paged
    decode step's priced bytes visibly drop where
    ``ops.attention.decode_kernel_selected`` takes it (``DECODE_PATH``
    ``decode-kernel``)."""
    count = 0
    nbytes = 0
    for line in stablehlo_text.splitlines():
        if _SH_GATHER_RE.search(line) is None:
            continue
        count += 1
        nbytes += 2 * (_sh_result_bytes(line) or 0)
    return {"count": count, "bytes": nbytes}


# Materialized sort/scatter traffic: stablehlo.sort (jnp.argsort /
# lax.sort — the MoE sort-based dispatch's (expert, priority) key sort)
# and stablehlo.scatter (jnp .at[].set/add — the capacity-slot pack)
# write their result tensors to memory and the consumer reads them back,
# so each op's HONEST traffic floor is 2x its result bytes on top of the
# operand reads the arg/output accounting covers — the same rule (and
# reason) as :func:`stablehlo_gather_stats`.  Both ops are REGION-
# BEARING in the pretty dialect (sort carries a comparator block,
# scatter an update computation), so their type signature lands on the
# region's closing ``}) : (...) -> ...`` line, matched with the same
# pending-queue trick as :func:`stablehlo_collective_stats`.  The op
# name is matched exactly (``stablehlo.sort`` / ``stablehlo.scatter``),
# so ``select_and_scatter`` (pooling backward — a windowed op with
# different materialization behavior) never counts here.
_SH_SORT_SCATTER_RE = re.compile(r"\"?stablehlo\.(sort|scatter)\"?\b")


def stablehlo_sort_scatter_stats(stablehlo_text):
    """Per-op ``{"count", "bytes"}`` for materialized sort/scatter
    intermediates in LOWERED StableHLO text, plus a ``"total"`` entry:
    ``bytes`` is 2x the summed result bytes (one write, one re-read by
    the consumer; a multi-result sort — argsort's (keys, payload) pair —
    sums every result tensor).

    This is what lets ``analysis.cost`` compare the MoE dispatch
    algorithms honestly (``MXNET_MOE_DISPATCH``): the sort path's
    intermediates are O(k*N) key/payload vectors plus the slot scatter,
    where the one-hot cumsum pack materializes (k*N, E) int32 one-hot
    and cumsum planes — invisible to arg/output accounting, visible
    here (the cumsum itself lowers to elementwise/reduce-window ops that
    fuse; the one-hot's cost shows up as the E-times-wider scatter and
    iota compares priced into the program's other terms, so the
    comparison floor is conservative for onehot — it can only
    UNDERSTATE the sort path's win)."""
    stats = {}
    pending = []

    def _note(op, nbytes):
        entry = stats.setdefault(op, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += 2 * (nbytes or 0)

    for line in stablehlo_text.splitlines():
        m = _SH_SORT_SCATTER_RE.search(line)
        if m is not None:
            op = m.group(1)
            nbytes = _sh_result_bytes(line)
            if nbytes is None:
                pending.append(op)     # region op: signature comes later
            else:
                _note(op, nbytes)
            continue
        if pending and line.lstrip().startswith("})") and "->" in line:
            _note(pending.pop(0), _sh_result_bytes(line))
    total = {"count": sum(e["count"] for e in stats.values()),
             "bytes": sum(e["bytes"] for e in stats.values())}
    stats["total"] = total
    return stats


# an instruction line's left-hand side: ``[ROOT] %name = `` (analysis.schedule
# walks the entry computation with the same expression)
_LHS_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*")
_META_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_MODULE_NAME_RE = re.compile(r"^HloModule\s+([^\s,]+)")
# a computation's header: ``[ENTRY] %name (params...) -> shape {``
_COMPUTATION_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%?([A-Za-z_][\w.\-]*)\s*$")
_TUPLE_INDEX_RE = re.compile(r"\bindex=(\d+)")
# the computations an instruction runs: a fusion's ``calls=``, a while's
# ``body=`` and ``condition=``, a reduce's ``to_apply=``, a conditional's
# ``branch_computations={...}``
_CALLED_RE = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")

Instruction = collections.namedtuple("Instruction", (
    "name",          # unique within the module, no leading %
    "opcode",        # ``fusion``, ``copy-start``, ``parameter``, ...
    "shape",         # result shape as printed, layout and memory space too
    "bytes",         # of the result; an async start's: what it delivers
    "operands",      # names, in order
    "op_name",       # of its own metadata={...}, or None
    "computation",   # the computation that lists it
    "entry",         # whether that is the module's ENTRY computation
    "called",        # names of the computations it runs
    "root",          # whether it is its computation's ROOT
    "index"))        # a ``parameter(N)``'s N, a ``get-tuple-element``'s
                     # ``index=N``; else None


def _closing(line, start):
    """Index of the parenthesis that closes the one at ``line[start]``."""
    depth = 0
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(line)


def _delivered_bytes(opcode, shape_s):
    """Bytes of an instruction's result.  An async start's tuple holds the
    operand beside the result and context scalars; counted is what the
    matching done delivers: of ``copy-start``'s ``(destination, source,
    context)`` the destination, of the others what :func:`_start_bytes`
    counts."""
    if not (opcode.endswith("-start") and shape_s.startswith("(")):
        return shape_bytes(shape_s)
    if opcode == "copy-start":
        return shape_bytes(_split_top_level(shape_s)[0])
    return _start_bytes(opcode[:-len("-start")], shape_s)


def instructions(hlo_text):
    """``(module name, [Instruction, ...])`` of one HLO module's text:
    every instruction line of every computation (names are unique within a
    module), each with its opcode, its result shape as printed (``f32[12289,
    16,4]{2,1,0:T(8,128)S(1)}``: layout and memory space are part of what a
    move costs), the result's bytes, its operands' names, the ``op_name``
    of its own ``metadata={...}`` (for a fusion XLA keeps its root's) and
    the computations it runs.  The module's name is the text's first line
    (``HloModule jit_step, ...``), the stem a device trace prints on its
    ``XLA Modules`` line.  The one parser under ``obs.scopes``."""
    lines = hlo_text.splitlines()
    head = _MODULE_NAME_RE.match(lines[0]) if lines else None
    out, computation, entry = [], None, False
    for line in lines[1:]:
        m = _LHS_RE.match(line)
        if m is None:
            c = _COMPUTATION_RE.match(line)
            if c is not None:
                computation, entry = c.group(2), bool(c.group(1))
            continue
        shape_s, end = _scan_shape(line, m.end())
        om = _OPCODE_RE.match(line, end)
        if om is None:
            continue
        opcode = om.group(1)
        close = _closing(line, om.end() - 1)
        inner = line[om.end():close]
        number, operands = None, []
        if opcode == "parameter":
            number = int(inner) if inner.strip().isdigit() else None
        elif opcode != "constant":
            for piece in _split_top_level("(%s)" % inner):
                found = _OPERAND_RE.search(piece)
                if found is not None:
                    operands.append(found.group(1))
        # the metadata is the line's tail: search from there, so a
        # quoted op_name inside a backend_config cannot shadow it
        tail = line.rfind("metadata={")
        found = _META_OP_NAME_RE.search(line, tail) if tail >= 0 else None
        if opcode == "get-tuple-element":
            at = _TUPLE_INDEX_RE.search(line, close)
            number = int(at.group(1)) if at else None
        called = []
        for one, many in _CALLED_RE.findall(line, close):
            called += [one] if one else [
                c.strip().lstrip("%") for c in many.split(",") if c.strip()]
        out.append(Instruction(
            m.group(1).lstrip("%"), opcode, shape_s,
            _delivered_bytes(opcode, shape_s), tuple(operands),
            found.group(1) if found else None, computation, entry,
            tuple(called), line.lstrip().startswith("ROOT "), number))
    return (head.group(1) if head else None), out


def collective_stats(hlo_text):
    """Count collectives and sum their result payloads.

    Async start/done pairs count once (the -start carries the shape).
    Returns {op_name: {"count": int, "bytes": int}} plus two aggregate
    entries: "total" over every op, and "overlappable" — the count/bytes
    of collectives the backend emitted as async ``-start``/``-done``
    pairs, i.e. communication the scheduler can overlap with compute
    between the pair (the double-buffered ring's collective-permutes on
    TPU land here; backends that keep sync collectives report 0).
    """
    stats = {}
    overlappable = {"count": 0, "bytes": 0}
    matches = []
    for line in hlo_text.splitlines():
        em = _INSTR_RE.search(line)
        if em is None:
            continue
        shape_s, end = _scan_shape(line, em.end())
        om = _OP_RE.match(line, end)
        if om is None:
            continue
        matches.append((shape_s, om.group(1), om.group(2)))
    for shape_s, op, suffix in matches:
        if suffix == "-done":
            continue
        if suffix == "-start":
            nbytes = _start_bytes(op, shape_s)
            overlappable["count"] += 1
            overlappable["bytes"] += nbytes
        else:
            nbytes = shape_bytes(shape_s)
        entry = stats.setdefault(op, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += nbytes
    total = {"count": sum(e["count"] for e in stats.values()),
             "bytes": sum(e["bytes"] for e in stats.values())}
    stats["total"] = total
    stats["overlappable"] = overlappable
    return stats
