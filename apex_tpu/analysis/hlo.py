"""Optimized-HLO text parsing — the ONE implementation every consumer
of compiled-program structure reads through.

Grew out of the gradient-sync engine's verification hooks
(``apex_tpu/parallel/comm.py``, which now re-exports from here) and the
``tools/comm_structure.py`` artifact generator's overlap scanner; the
analysis passes (:mod:`apex_tpu.analysis.passes`) added buffer-donation
aliasing and host-transfer scans.  Everything operates on the text of
``jit(fn).lower(...).compile().as_text()`` — the backend-agnostic way
to audit what XLA actually scheduled (GSPMD prints the same collective
structure on the CPU mesh as on a pod; see ``tools/comm_structure.py``).

Contents:

- :func:`shape_bytes` / :func:`async_start_result` — HLO shape-string
  arithmetic.
- :func:`collective_summary` / :func:`collective_dtypes` /
  :func:`ring_wire_bytes` — per-kind collective counts, payload bytes
  and dtypes, and the ring-algorithm traffic model.
- :func:`overlap_collect` — which collectives' schedule windows overlap
  compute (the serial-bytes model's refinement).
- :func:`input_output_aliases` — the buffer-donation aliasing XLA
  actually committed to (the donation lint's ground truth).
- :func:`host_transfer_ops` — infeed/outfeed/host send-recv/callback
  custom-calls (the transfer lint's HLO-level ground truth).
- :func:`parse_computations` / :func:`instruction_flops` /
  :func:`instruction_bytes` — the per-instruction reader + cost
  primitives behind step-time attribution
  (:mod:`apex_tpu.observability.attribution`): every instruction as a
  structured record, and the FLOP/byte estimate of one instruction
  from its printed shapes (XLA prints operand shapes inline at every
  use site, so no cross-reference pass is needed).
- :func:`parameter_shardings` / :func:`parse_sharding` /
  :func:`num_partitions` — the GSPMD sharding each ENTRY parameter
  actually compiled with (the sharding-conformance pass's ground
  truth: a ``sharding={replicated}`` on a tensor the plan shards is
  the silent-replication defect).
- :func:`collective_instructions` / :func:`replica_group_size` —
  every collective as a structured record (kind, payload bytes,
  dtypes, replica groups, jax op path), for the per-mesh-axis
  resharding pass.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

__all__ = [
    "DTYPE_BYTES",
    "COLLECTIVE_KINDS",
    "shape_bytes",
    "async_start_result",
    "collective_summary",
    "collective_dtypes",
    "ring_wire_bytes",
    "overlap_collect",
    "input_output_aliases",
    "host_transfer_ops",
    "parse_computations",
    "shape_dims",
    "shape_elements",
    "instruction_flops",
    "instruction_bytes",
    "num_partitions",
    "parameter_shardings",
    "parse_sharding",
    "collective_instructions",
    "replica_group_size",
]

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4,
    "u16": 2, "u8": 1, "pred": 1,
}

COLLECTIVE_KINDS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)

_KINDS_ALT = "|".join(COLLECTIVE_KINDS)

# shape alternative allows one level of tuple nesting: variadic combined
# async ops (XLA's collective combiners) print ((op0, op1), (res0, res1))
# — a flat [^)]* would stop at the first ')' and silently drop the op
_DEF_RE = re.compile(
    r"(?:ROOT\s+)?%?[\w.-]+\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|[^\s]+)\s+"
    rf"({_KINDS_ALT})(-start|-done)?\("
)


def shape_bytes(shape: str) -> int:
    """bytes of an HLO shape string like 'bf16[8,128,1024]' (tuples:
    sum of elements)."""
    total = 0
    for dt, dims in re.findall(r"(\w+)\[([0-9,]*)\]", shape):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def async_start_result(shape: str) -> str:
    """Result element of an async ``-start`` op's tuple shape
    ``(operand(s), result(s)[, contexts...])`` — the second TOP-LEVEL
    element, which for a variadic combined op is itself a tuple whose
    arrays all count.  Depth tracking covers ALL bracket kinds: shape
    strings carry commas inside dims (``[8,128]``) and layouts
    (``{1,0}``), not just nested tuples."""
    if not shape.startswith("("):
        return shape
    parts, depth, cur = [], 0, []
    for ch in shape[1:-1]:
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        cur.append(ch)
    parts.append("".join(cur))
    return parts[1] if len(parts) > 1 else parts[0]


def collective_summary(hlo_text: str) -> dict:
    """Per-kind ``{count, bytes}`` for every collective in optimized HLO.

    Bytes are the shape printed at each op's definition site — the
    RESULT: the full buffer for all-gather/all-to-all, the local shard
    for reduce-scatter (feed :func:`ring_wire_bytes` for a
    notation-normalized traffic number).  Async ``-start``/``-done``
    pairs count once, at ``-start``, with the result element of the
    start tuple.
    """
    out = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line.strip())
        if not m:
            continue
        shape, kind, variant = m.group(1), m.group(2), m.group(3)
        if variant == "-done":
            # async pairs are counted once, at -start
            continue
        if variant == "-start":
            # -start returns (operand(s), result(s)[, contexts]); keep
            # only the result element so bytes match the sync form
            shape = async_start_result(shape)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += shape_bytes(shape)
    return out


def collective_dtypes(hlo_text: str) -> Dict[str, set]:
    """Per-kind set of element dtypes each collective's result moves —
    the collective-consistency pass checks these against the configured
    wire format (an int8 wire must move s8/f32-scale payloads, never a
    full-width f32 gradient buffer)."""
    out: Dict[str, set] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line.strip())
        if not m:
            continue
        shape, kind, variant = m.group(1), m.group(2), m.group(3)
        if variant == "-done":
            continue
        if variant == "-start":
            shape = async_start_result(shape)
        dts = out.setdefault(kind, set())
        for dt, _dims in re.findall(r"(\w+)\[([0-9,]*)\]", shape):
            if dt in DTYPE_BYTES:
                dts.add(dt)
    return out


def ring_wire_bytes(summary: dict, world: int) -> float:
    """Per-chip wire traffic (bytes sent) implied by a
    :func:`collective_summary`, under ring algorithms — normalized for
    XLA's result-shape notation so f32 and quantized paths compare
    apples-to-apples: reduce-scatter prints the SHARD (traffic =
    ``(world-1) * shard``), all-gather/all-to-all print the FULL buffer
    (traffic = ``(world-1)/world * full``), all-reduce streams twice.
    """
    t = 0.0
    for kind, rec in summary.items():
        b = rec["bytes"]
        if kind == "all-reduce":
            t += 2.0 * b * (world - 1) / world
        elif kind == "reduce-scatter":
            t += b * (world - 1)
        elif kind in ("all-gather", "all-to-all"):
            t += b * (world - 1) / world
        elif kind == "collective-permute":
            t += b  # one hop
    return t


# ---------------------------------------------------------------------------
# schedule-overlap windows (from tools/comm_structure.py)
# ---------------------------------------------------------------------------

_COMPUTE_OP_RE = re.compile(
    r"=\s*(?:\([^=]*\)|\S+)\s+(?:fusion|convolution|custom-call|dot)\("
)

_START_RE = re.compile(
    r"%?([\w.-]+)\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|[^\s]+)\s+"
    rf"(?:{_KINDS_ALT})-start\("
)
_DONE_RE = re.compile(rf"(?:{_KINDS_ALT})-done\(\s*%?([\w.-]+)")
_SYNC_RE = re.compile(
    r"%?([\w.-]+)\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|[^\s]+)\s+"
    rf"(?:{_KINDS_ALT})\("
)


def overlap_collect(hlo_text: str) -> dict:
    """Which collectives' windows overlap compute (VERDICT r4 #6).

    The serial-bytes model (:func:`ring_wire_bytes`) assumes every
    collective blocks; XLA actually schedules collectives concurrently
    with independent compute, so that number is an upper bound.  This
    pass walks the optimized HLO in program order and measures each
    collective's *window*:

    * async ``-start``/``-done`` pairs (TPU-scheduled HLO): the window
      is start→done; compute issued inside it is overlap the scheduler
      already committed to.
    * sync collectives (CPU HLO prints these even where the TPU backend
      would go async): the window is the op→its first consumer; compute
      ops strictly inside are provably independent of the result (they
      issue before anything uses it), so an async backend can hide the
      collective behind them — the *overlappable* fraction.

    A collective is counted overlapped if ≥1 compute op (post-fusion:
    ``fusion``/``dot``/``convolution``/``custom-call``) issues inside
    its window.  Returns {"async_pairs", "async_bytes", "sync_count",
    "sync_bytes", "overlapped_count", "overlapped_bytes"} where the
    overlapped columns span both forms.
    """
    open_async = {}  # name -> [bytes, saw_compute]
    open_sync = {}   # name -> [bytes, saw_compute]
    out = {
        "async_pairs": 0, "async_bytes": 0,
        "sync_count": 0, "sync_bytes": 0,
        "overlapped_count": 0, "overlapped_bytes": 0,
    }

    def _close(b, saw):
        if saw:
            out["overlapped_count"] += 1
            out["overlapped_bytes"] += b

    for line in hlo_text.splitlines():
        line = line.strip()
        # close sync windows at their first consumer BEFORE counting
        # this line's compute (compute at first-use is not overlap)
        if open_sync:
            rhs = line.split("=", 1)[1] if "=" in line else line
            # sigil-optional, like the definition regexes above: HLO may
            # print operand names with or without '%'
            for name in [
                n for n in open_sync
                if re.search(
                    r"(?<![\w.%-])%?" + re.escape(n) + r"(?![\w.-])", rhs
                )
            ]:
                _close(*open_sync.pop(name))
        m = _START_RE.search(line)
        if m:
            out["async_pairs"] += 1
            b = shape_bytes(async_start_result(m.group(2)))
            out["async_bytes"] += b
            open_async[m.group(1)] = [b, False]
            continue
        m = _DONE_RE.search(line)
        if m and m.group(1) in open_async:
            _close(*open_async.pop(m.group(1)))
            continue
        m = _SYNC_RE.search(line)
        if m:
            out["sync_count"] += 1
            b = shape_bytes(m.group(2))
            out["sync_bytes"] += b
            open_sync[m.group(1)] = [b, False]
            continue
        if _COMPUTE_OP_RE.search(line):
            for rec in open_async.values():
                rec[1] = True
            for rec in open_sync.values():
                rec[1] = True
    # windows that never closed in-text (result only consumed across a
    # computation boundary / ROOT): their window extends to the end of
    # the region, so trailing compute counts
    for b, saw in list(open_async.values()) + list(open_sync.values()):
        _close(b, saw)
    return out


# ---------------------------------------------------------------------------
# buffer-donation aliasing (the donation lint's ground truth)
# ---------------------------------------------------------------------------


def input_output_aliases(hlo_text: str) -> List[Tuple[int, str]]:
    """Parse the module header's ``input_output_alias={ {0}: (2, {},
    may-alias), ... }`` into ``[(param_number, output_index_str), ...]``.

    This is the aliasing XLA COMMITTED to: a ``donate_argnums`` entry
    that does not appear here kept both buffers live.  Absent header
    (nothing aliased) returns ``[]``.
    """
    key = "input_output_alias={"
    start = hlo_text.find(key)
    if start < 0:
        return []
    # balanced-brace span: output indices are themselves brace-wrapped
    i, depth = start + len(key) - 1, 0
    end = i
    for j in range(i, len(hlo_text)):
        ch = hlo_text[j]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                end = j
                break
    body = hlo_text[start + len(key):end]
    out = []
    for m in re.finditer(r"\{([0-9, ]*)\}\s*:\s*\(\s*(\d+)\s*,", body):
        out.append((int(m.group(2)), m.group(1).strip()))
    return out


# ---------------------------------------------------------------------------
# host transfers (the transfer lint's HLO-level ground truth)
# ---------------------------------------------------------------------------

_INSTR_NAME_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.-]+)\s*=")

#: custom-call targets that round-trip through the host python runtime
_CALLBACK_TARGETS = (
    "xla_python_cpu_callback",
    "xla_ffi_python_cpu_callback",
    "xla_python_gpu_callback",
    "CallbackToHost",
)


def host_transfer_ops(hlo_text: str) -> List[Tuple[str, str]]:
    """``[(op_name, why), ...]`` for every op in the HLO that moves data
    between host and device: infeed/outfeed, send/recv marked
    ``is_host_transfer=true``, and python-callback custom-calls."""
    out = []
    for line in hlo_text.splitlines():
        line = line.strip()
        nm = _INSTR_NAME_RE.match(line)
        name = nm.group(1) if nm else "<unnamed>"
        if re.search(
            r"=\s*(?:\((?:[^()]|\([^()]*\))*\)|[^\s]+)\s+"
            r"(infeed|outfeed)\(", line
        ):
            kind = re.search(r"\s(infeed|outfeed)\(", line).group(1)
            out.append((name, kind))
            continue
        if re.search(r"\s(send|recv|send-done|recv-done)\(", line) and \
                "is_host_transfer=true" in line:
            out.append((name, "host send/recv"))
            continue
        if "custom-call" in line:
            tgt = re.search(r'custom_call_target="([^"]+)"', line)
            if tgt and any(t in tgt.group(1) for t in _CALLBACK_TARGETS):
                out.append((name, f"callback custom-call ({tgt.group(1)})"))
    return out


# ---------------------------------------------------------------------------
# per-instruction reader + cost primitives (step-time attribution)
# ---------------------------------------------------------------------------

#: computation header: ``%name (params) -> shape {`` / ``ENTRY %name ...``
_COMP_HEADER_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.-]+)\s+\(.*\)\s*->\s*\S.*\{\s*$"
)

_INSTR_HEAD_RE = re.compile(
    r"^(?:ROOT\s+)?%?([\w.-]+)\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|[^\s]+)\s+"
    r"([\w-]+)\("
)

_SHAPE_IN_TEXT_RE = re.compile(r"(\w+)\[([0-9,]*)\]")

_OP_NAME_RE = re.compile(r'op_name="([^"]+)"')

#: attrs that reference other computations, per container opcode
_CALLED_COMP_RE = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.-]+)"
)


def shape_dims(shape: str) -> List[int]:
    """Dims of the FIRST array in an HLO shape string (``'f32[8,128]
    {1,0}'`` → ``[8, 128]``; scalars → ``[]``; tuples → first element)."""
    m = _SHAPE_IN_TEXT_RE.search(shape)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def shape_elements(shape: str) -> int:
    """Element count of the first array in a shape string."""
    n = 1
    for d in shape_dims(shape):
        n *= d
    return n


def _balanced_span(text: str, start: int) -> int:
    """Index just past the ')' matching the '(' at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        ch = text[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def parse_computations(hlo_text: str):
    """``(computations, entry_name)`` — every instruction as a record.

    ``computations`` maps computation name → list of instruction dicts
    in program order; each record carries ``name``, ``shape`` (result
    shape string), ``opcode``, ``operands`` (list of operand shape
    strings: as printed inline at the use site, or — the installed XLA
    prints bare ``%name`` operands — looked up from the operand's own
    definition earlier in the computation), ``operand_names``
    (the ``%name`` tokens of the operand list — the def-use edges the
    memory live-range walk follows), ``op_name`` (the jax source path
    from metadata — named scopes land here), ``called`` (referenced
    computation names for fusion/call/while/conditional), and
    ``attrs`` (the raw text after the operand list, for
    opcode-specific parsing like ``lhs_contracting_dims``).
    """
    comps: Dict[str, List[dict]] = {}
    entry = None
    current: Optional[List[dict]] = None
    defs: Dict[str, str] = {}  # instruction name → shape, this computation
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if not line:
            continue
        hm = _COMP_HEADER_RE.match(line)
        if hm and " = " not in line.split("{", 1)[0]:
            name = hm.group(2)
            current = comps.setdefault(name, [])
            defs = {}
            if hm.group(1):
                entry = name
            continue
        if line == "}":
            current = None
            continue
        im = _INSTR_HEAD_RE.match(line)
        if im is None or current is None:
            continue
        name, shape, opcode = im.group(1), im.group(2), im.group(3)
        open_paren = im.end() - 1
        close = _balanced_span(line, open_paren)
        operand_text = line[open_paren + 1:close - 1]
        attrs = line[close:]
        onm = _OP_NAME_RE.search(attrs)
        operand_names = re.findall(r"%([\w.-]+)", operand_text)
        operand_shapes = _SHAPE_IN_TEXT_RE.findall(operand_text)
        if not operand_shapes:
            operand_shapes = [
                sh
                for op in operand_names
                for sh in _SHAPE_IN_TEXT_RE.findall(defs.get(op, ""))
            ]
        defs[name] = shape
        current.append({
            "name": name,
            "shape": shape,
            "opcode": opcode,
            "operands": [f"{dt}[{dims}]" for dt, dims in operand_shapes],
            "operand_names": operand_names,
            "op_name": onm.group(1) if onm else "",
            "called": _CALLED_COMP_RE.findall(attrs),
            "attrs": attrs,
            "root": line.startswith("ROOT"),
        })
    if entry is None and comps:
        # un-ENTRY'd fragments (tests, hand-written snippets): the last
        # computation is the outermost by HLO printing convention
        entry = next(reversed(comps))
    return comps, entry


#: 1-FLOP-per-element transcendentals/arithmetic (coarse on purpose —
#: attribution consumes relative shares, not absolute cycle counts)
_ELEMENTWISE_OPS = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "power", "remainder", "atan2", "and", "or", "xor", "not",
    "negate", "abs", "sign", "compare", "select", "clamp", "convert",
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "tanh", "logistic", "sqrt", "rsqrt", "cbrt", "sine", "cosine",
    "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "is-finite", "shift-left", "shift-right-arithmetic",
    "shift-right-logical", "popcnt", "count-leading-zeros",
    "stochastic-convert", "erf",
))

#: pure data movement / bookkeeping: 0 FLOPs, bytes still count
_ZERO_FLOP_OPS = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "reshape", "transpose", "broadcast", "copy",
    "copy-start", "copy-done", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "gather", "iota", "pad",
    "reverse", "rng", "rng-bit-generator", "after-all", "domain",
    "partition-id", "replica-id", "opt-barrier", "send", "recv",
    "send-done", "recv-done", "infeed", "outfeed", "custom-call",
))

_CONTRACTING_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")


def instruction_flops(instr: dict) -> float:
    """Estimated FLOPs of ONE leaf instruction from its printed shapes.

    - ``dot``: ``2 * result_elements * contracted_elements`` (the lhs
      contracting dims, parsed from the attrs; batch dims are already
      inside the result product).
    - ``convolution``: ``2 * result_elements * kernel_elements /
      out_features`` (out-feature index from ``dim_labels``).
    - elementwise/transcendental: one FLOP per result element.
    - ``reduce``/``reduce-window``: one FLOP per INPUT element.
    - data movement, parameters, collectives, custom-calls: 0 (a
      custom-call's interior is invisible in HLO text; its measured
      time still lands in the right bucket via the trace source).

    Container ops (fusion/call/while/conditional) are costed by the
    caller over their ``called`` computations — see
    :mod:`apex_tpu.observability.attribution`.
    """
    opcode = instr["opcode"]
    if opcode in _ZERO_FLOP_OPS or opcode.startswith(
        ("all-", "reduce-scatter", "collective-")
    ):
        return 0.0
    result_elems = shape_elements(instr["shape"])
    if opcode == "dot":
        contracted = 1
        m = _CONTRACTING_RE.search(instr["attrs"])
        if m and instr["operands"]:
            lhs_dims = shape_dims(instr["operands"][0])
            for idx in m.group(1).split(","):
                if idx and int(idx) < len(lhs_dims):
                    contracted *= lhs_dims[int(idx)]
        return 2.0 * result_elems * contracted
    if opcode == "convolution":
        if len(instr["operands"]) > 1:
            kernel = instr["operands"][1]
            k_elems = shape_elements(kernel)
            out_features = 1
            m = _DIM_LABELS_RE.search(instr["attrs"])
            if m:
                o_idx = m.group(2).find("o")
                kd = shape_dims(kernel)
                if 0 <= o_idx < len(kd):
                    out_features = kd[o_idx]
            elif shape_dims(instr["shape"]):
                out_features = shape_dims(instr["shape"])[-1]
            return 2.0 * result_elems * k_elems / max(1, out_features)
        return 0.0
    if opcode in ("reduce", "reduce-window", "scatter", "sort",
                  "select-and-scatter"):
        src = instr["operands"][0] if instr["operands"] else instr["shape"]
        return float(shape_elements(src))
    if opcode in _ELEMENTWISE_OPS:
        return float(result_elems)
    if opcode in ("map", "fusion", "call", "while", "conditional"):
        return 0.0  # containers: costed over their called computations
    return float(result_elems)  # unknown op: one FLOP/element floor


def instruction_bytes(instr: dict) -> int:
    """HBM-traffic estimate of one instruction: result + operand bytes
    as printed (for a fusion this is exactly the boundary traffic — its
    interior never touches HBM, which is the point of fusing).
    Pointer-shuffling ops (tuple plumbing, bitcasts) move nothing."""
    if instr["opcode"] in (
        "parameter", "constant", "tuple", "get-tuple-element",
        "bitcast", "after-all", "opt-barrier",
    ):
        return 0
    total = shape_bytes(instr["shape"])
    for op_shape in instr["operands"]:
        total += shape_bytes(op_shape)
    return total


# ---------------------------------------------------------------------------
# GSPMD parameter shardings (the sharding-conformance pass's ground truth)
# ---------------------------------------------------------------------------

_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")

_PARAM_RE = re.compile(
    r"^(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(\S+)\s+parameter\((\d+)\)"
)
_SHARDING_ATTR_RE = re.compile(r"sharding=\{([^}]*(?:\{[^}]*\}[^}]*)*)\}")
_TILE_DEVICES_RE = re.compile(r"devices=\[([0-9,]+)\]")


def num_partitions(hlo_text: str) -> int:
    """``num_partitions`` from the module header (1 when absent — a
    single-device compile carries no SPMD structure to verify)."""
    m = _NUM_PARTITIONS_RE.search(hlo_text)
    return int(m.group(1)) if m else 1


def parse_sharding(sharding: Optional[str]) -> dict:
    """Structure one HLO sharding attribute string.

    Returns ``{"kind": "replicated" | "maximal" | "tiled" | "manual" |
    "unknown", "dims": [shards-per-data-dim, ...]}``.  Handles the
    GSPMD print variants::

        replicated
        maximal device=3
        devices=[2,4]<=[8]                          # plain tiling
        devices=[2,1,4]<=[8] last_tile_dim_replicate  # partial replication
        devices=[1,4,2]<=[2,4]T(1,0) last_tile_dim_replicate
        devices=[...] last_tile_dims={manual}       # shard_map interiors

    Trailing subgroup dims (``last_tile_dim_replicate`` /
    ``last_tile_dims={...}``) are dropped from ``dims`` so the result
    is shards-per-DATA-dim — multiply a parameter's printed (local)
    shape by ``dims`` to recover the global logical shape.
    """
    if not sharding:
        return {"kind": "unknown", "dims": []}
    s = sharding.strip()
    if s.startswith("replicated"):
        return {"kind": "replicated", "dims": []}
    if s.startswith("maximal"):
        return {"kind": "maximal", "dims": []}
    m = _TILE_DEVICES_RE.search(s)
    if not m:
        return {"kind": "unknown", "dims": []}
    dims = [int(d) for d in m.group(1).split(",") if d]
    drop = 0
    if "last_tile_dim_replicate" in s:
        drop = 1
    sub = re.search(r"last_tile_dims=\{([^}]*)\}", s)
    if sub:
        drop = len([t for t in sub.group(1).split(",") if t.strip()])
        if "manual" in sub.group(1):
            return {"kind": "manual", "dims": dims[: len(dims) - drop]}
    if drop:
        dims = dims[: len(dims) - drop]
    kind = "tiled"
    if all(d == 1 for d in dims):
        kind = "replicated"  # tiled-in-name-only: one shard per dim
    return {"kind": kind, "dims": dims}


def parameter_shardings(hlo_text: str) -> List[dict]:
    """Every ENTRY-computation parameter as ``{"param": number,
    "name": instr name, "shape": local shard shape string, "op_name":
    jax arg path from metadata ('' when absent), "sharding": raw
    sharding attribute or None, "bytes": local bytes, "global_bytes":
    logical (unsharded) bytes}``, ordered by parameter number.

    The printed shape is the per-device SHARD; ``global_bytes``
    multiplies it back up by the tile counts (replicated parameters
    print the full shape, so local == global there).
    """
    # parse_computations drops the parameter NUMBER (it lives inside
    # the operand parens), so scan entry lines directly
    numbered: List[dict] = []
    in_entry = False
    for raw in hlo_text.splitlines():
        line = raw.strip()
        hm = _COMP_HEADER_RE.match(line)
        if hm and " = " not in line.split("{", 1)[0]:
            in_entry = bool(hm.group(1))
            continue
        if line == "}":
            in_entry = False
            continue
        if not in_entry:
            continue
        m = _PARAM_RE.match(line)
        if not m:
            continue
        name, shape, number = m.group(1), m.group(2), int(m.group(3))
        sh = _SHARDING_ATTR_RE.search(line)
        onm = _OP_NAME_RE.search(line)
        local = shape_bytes(shape)
        parsed = parse_sharding(sh.group(1) if sh else None)
        factor = 1
        for d in parsed["dims"]:
            factor *= d
        numbered.append({
            "param": number,
            "name": name,
            "shape": shape,
            "op_name": onm.group(1) if onm else "",
            "sharding": sh.group(1) if sh else None,
            "bytes": local,
            "global_bytes": local * max(1, factor),
        })
    numbered.sort(key=lambda r: r["param"])
    return numbered


# ---------------------------------------------------------------------------
# per-collective records (the resharding pass's ground truth)
# ---------------------------------------------------------------------------

_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)


def replica_group_size(line: str) -> Optional[int]:
    """Participant count per replica group of one collective line —
    the mesh-axis size the collective spans.  Handles the explicit
    ``{{0,1},{2,3}}`` print and the iota ``[G,S]<=[N]`` form (group
    count G x size S).  None when the op prints no groups (a
    full-world collective on some backends)."""
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _REPLICA_GROUPS_RE.search(line)
    if not m:
        return None
    first = m.group(1).split("}", 1)[0].lstrip("{")
    ids = [t for t in first.split(",") if t.strip()]
    return len(ids)


def _replica_groups(line: str) -> Optional[List[List[int]]]:
    """Replica groups of one collective line as explicit id lists, or
    None when the op prints none.  Handles both the explicit
    ``{{0,1},{2,3}}`` print and XLA's compact iota/V2 form
    ``[G,S]<=[dims](T(perm))`` — ``iota(prod(dims)).reshape(dims)
    .transpose(perm).reshape(G, S)``, rows = groups — so axis
    attribution stays exact (not size-based) even where two mesh axes
    share a size and only the iota form was printed."""
    m = _REPLICA_GROUPS_RE.search(line)
    if m:
        groups = []
        for grp in re.findall(r"\{([0-9, ]*)\}", "{" + m.group(1) + "}"):
            ids = [int(t) for t in grp.split(",") if t.strip()]
            if ids:
                groups.append(ids)
        return groups or None
    m = _IOTA_GROUPS_RE.search(line)
    if not m:
        return None
    g, s = int(m.group(1)), int(m.group(2))
    dims = [int(d) for d in m.group(3).split(",") if d]
    total = 1
    for d in dims:
        total *= d
    if total != g * s:
        return None  # malformed print: refuse to guess
    ids = list(range(total))
    if m.group(4):
        perm = [int(p) for p in m.group(4).split(",") if p]
        if sorted(perm) != list(range(len(dims))):
            return None
        # index math of reshape(dims).transpose(perm).flatten()
        strides = [0] * len(dims)
        acc = 1
        for i in range(len(dims) - 1, -1, -1):
            strides[i] = acc
            acc *= dims[i]
        out_dims = [dims[p] for p in perm]
        out_strides = [strides[p] for p in perm]
        ids = []
        idx = [0] * len(out_dims)
        for _ in range(total):
            ids.append(sum(i * st for i, st in zip(idx, out_strides)))
            for ax in range(len(out_dims) - 1, -1, -1):
                idx[ax] += 1
                if idx[ax] < out_dims[ax]:
                    break
                idx[ax] = 0
    return [ids[i * s:(i + 1) * s] for i in range(g)]


def collective_instructions(hlo_text: str) -> List[dict]:
    """Every collective in the module as ``{"name", "kind", "shape",
    "bytes", "dtypes", "group_size", "groups", "op_name"}``, in
    program order.  Async ``-start``/``-done`` pairs count once (at
    ``-start``, with the result element of the start tuple), matching
    :func:`collective_summary`'s counting."""
    out = []
    for raw in hlo_text.splitlines():
        line = raw.strip()
        m = _DEF_RE.match(line)
        if not m:
            continue
        shape, kind, variant = m.group(1), m.group(2), m.group(3)
        if variant == "-done":
            continue
        if variant == "-start":
            shape = async_start_result(shape)
        nm = _INSTR_NAME_RE.match(line)
        onm = _OP_NAME_RE.search(line)
        dtypes = set()
        for dt, _dims in re.findall(r"(\w+)\[([0-9,]*)\]", shape):
            if dt in DTYPE_BYTES:
                dtypes.add(dt)
        out.append({
            "name": nm.group(1) if nm else "<unnamed>",
            "kind": kind,
            "shape": shape,
            "bytes": shape_bytes(shape),
            "dtypes": dtypes,
            "group_size": replica_group_size(line),
            "groups": _replica_groups(line),
            "op_name": onm.group(1) if onm else "",
        })
    return out
