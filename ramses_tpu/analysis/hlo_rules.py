"""HLO-level rules: hazard classes read off the lowered StableHLO.

Each rule is grounded in a documented incident from this repo's
history (see the rule docstrings).  All of them run on the CPU test
backend from a *lowering* (trace only, no compile), so the whole
audit costs seconds and runs in CI on every push.

The checkers work on :class:`ramses_tpu.analysis.programs.Program`
objects but only duck-type them: anything with ``.name``, ``.text``
and ``.meta`` works, which is what the telemetry run-header hook and
the fixture tests exploit.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List

from ramses_tpu.analysis.rules import Finding, Rule, Severity, register
from ramses_tpu.telemetry import hlo as _hlo

# ---------------------------------------------------------------------
# shared StableHLO text probes
# ---------------------------------------------------------------------
_TENSOR_RE = re.compile(r"tensor<([0-9x]*?)x?([a-z][a-z0-9]*)>")
_CONST_RE = re.compile(
    r"stablehlo\.constant\b[^\n]*?:\s*tensor<([0-9x]*?)x?"
    r"([a-z][a-z0-9]*)>")
_ARG_RE = re.compile(r"%arg\d+: tensor<([0-9x]*?)x?([a-z][a-z0-9]*)>")
# donation shows up as tf.aliasing_output (fixed output aliasing) or
# jax.buffer_donor (compiler-chosen aliasing — what jit emits for
# committed/sharded inputs)
_DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")
_SCATTER_RE = re.compile(r'"stablehlo\.scatter"')
_NUM_PARTITIONS_RE = re.compile(r"mhlo\.num_partitions = (\d+)")

_BITS = {"f64": 64, "f32": 32, "f16": 16, "bf16": 16, "f8": 8,
         "i64": 64, "ui64": 64, "i32": 32, "ui32": 32, "i16": 16,
         "ui16": 16, "i8": 8, "ui8": 8, "i1": 1, "pred": 1}


def _elems(dims_txt: str) -> int:
    n = 1
    for d in dims_txt.split("x"):
        if d:
            n *= int(d)
    return n


def _nbytes(dims_txt: str, dty: str) -> int:
    return (_elems(dims_txt) * _BITS.get(dty, 32) + 7) // 8


def is_partitioned(text: str) -> bool:
    """True when the lowered module targets >1 GSPMD partition (the
    regime where scatter-add reassociation is nondeterministic)."""
    m = _NUM_PARTITIONS_RE.search(text)
    return bool(m) and int(m.group(1)) > 1


def main_args(text: str):
    """``(dims, dtype, attrs)`` per ``@main`` argument of the lowered
    module.  ``attrs`` is the raw text between this argument's type
    and the next argument (sharding strings nest braces, so a plain
    ``\\{[^}]*\\}`` capture truncates — slicing arg-to-arg does not)."""
    m = re.search(r"func\.func public @main\((.*?)\)\s*(->|\{)", text,
                  re.DOTALL)
    if not m:
        return []
    sig = m.group(1)
    hits = list(_ARG_RE.finditer(sig))
    out = []
    for i, h in enumerate(hits):
        end = hits[i + 1].start() if i + 1 < len(hits) else len(sig)
        out.append((h.group(1), h.group(2), sig[h.end():end]))
    return out


def _is_donated(attrs: str) -> bool:
    return any(mk in attrs for mk in _DONATION_MARKERS)


# ---------------------------------------------------------------------
# gather-blowup  (PR 8: the 6^d-duplicated stencil gather)
# ---------------------------------------------------------------------
def check_gather_ratio(text_ref: str, text: str,
                       min_ratio: float = 2.0):
    """``(ok, ref_elems, elems)`` — the blocked/optimized program must
    gather at least ``min_ratio``x fewer RESULT elements than the
    reference formulation.  This IS the legacy
    ``test_hlo_inventory.py`` >=2x gate; the test and the lint rule
    both call it so they cannot drift."""
    ref = _hlo.count_gather_elems(text_ref)
    cur = _hlo.count_gather_elems(text)
    return ref >= min_ratio * cur, ref, cur


def _check_gather_blowup(program) -> List[Finding]:
    meta = program.meta
    out: List[Finding] = []
    elems = _hlo.count_gather_elems(program.text)
    ops = _hlo.raw_gather_count(program.text)
    budget = meta.get("gather_budget_elems")
    if budget is not None and elems > budget:
        out.append(Finding(
            rule="gather-blowup", severity=Severity.ERROR,
            program=program.name,
            message=(f"lowered program gathers {elems:,} result "
                     f"elements, over its budget of {budget:,} "
                     f"({ops} gather ops) — the PR 8 duplicated-"
                     "stencil regression class"),
            key="budget",
            detail={"elems": elems, "budget": budget, "ops": ops}))
    ref_text = meta.get("gather_ref_text")
    if ref_text is not None:
        min_ratio = float(meta.get("min_gather_ratio", 2.0))
        ok, ref, cur = check_gather_ratio(ref_text, program.text,
                                          min_ratio)
        if not ok:
            out.append(Finding(
                rule="gather-blowup", severity=Severity.ERROR,
                program=program.name,
                message=(f"blocked formulation gathers {cur:,} "
                         f"elements vs {ref:,} on the stencil path "
                         f"— under the required {min_ratio:g}x win"),
                key="ratio",
                detail={"elems": cur, "ref_elems": ref,
                        "min_ratio": min_ratio}))
    return out


register(Rule(
    id="gather-blowup", kind="hlo", check=_check_gather_blowup,
    doc=("PR 8: partial-level sweeps once gathered a 6^d-duplicated "
         "per-oct stencil batch (160M elements on the evolved Sedov "
         "tree).  Gates the gathered RESULT element count of the "
         "lowered fused step against a per-program budget and/or a "
         "minimum win ratio over the stencil formulation.")))


# ---------------------------------------------------------------------
# large-constant-capture  (PR 10: the ct_core closed-over table)
# ---------------------------------------------------------------------
CONST_LIMIT_BYTES = 65536


def _check_large_constant(program) -> List[Finding]:
    limit = int(program.meta.get("const_limit_bytes",
                                 CONST_LIMIT_BYTES))
    hits: Dict[str, Dict[str, Any]] = {}
    for dims, dty in _CONST_RE.findall(program.text):
        nb = _nbytes(dims, dty)
        if nb < limit:
            continue
        ty = f"tensor<{dims + 'x' if dims else ''}{dty}>"
        h = hits.setdefault(ty, {"bytes": nb, "count": 0})
        h["count"] += 1
    return [Finding(
        rule="large-constant-capture", severity=Severity.ERROR,
        program=program.name,
        message=(f"{h['count']} stablehlo.constant op(s) of {ty} "
                 f"({h['bytes']:,} B >= {limit:,} B) baked into the "
                 "jitted step body — closed-over arrays replicate "
                 "per partition and defeat donation (the PR 10 "
                 "ct_core remat source); pass them as arguments"),
        key=ty, detail=h) for ty, h in sorted(hits.items())]


register(Rule(
    id="large-constant-capture", kind="hlo",
    check=_check_large_constant,
    doc=("PR 10: mhd/uniform.py ct_core closed over a gather-index "
         "table; XLA baked it into the program as a constant, the "
         "SPMD partitioner could only replicate it, and every coarse "
         "step paid an involuntary full rematerialization.  Flags "
         "any stablehlo.constant over a size threshold inside a "
         "jitted step body.")))


# ---------------------------------------------------------------------
# nondeterministic-scatter  (ROADMAP 2: MHD 1-ulp GSPMD scatter)
# ---------------------------------------------------------------------
def _check_nondet_scatter(program) -> List[Finding]:
    text = program.text
    partitioned = program.meta.get("partitioned")
    if partitioned is None:
        partitioned = is_partitioned(text)
    if not partitioned:
        return []
    hits: Dict[str, int] = {}
    for m in _SCATTER_RE.finditer(text):
        window = text[m.start():m.start() + 4000]
        if "unique_indices = false" not in window:
            continue
        body_end = window.find("}) :")
        body = window[:body_end if body_end > 0 else None]
        if "stablehlo.add" not in body:
            continue                # overwrite scatters reorder safely
        tym = re.search(r"\)\s*->\s*\(?\s*(tensor<[^>]+>)",
                        window[body_end if body_end > 0 else 0:])
        ty = tym.group(1) if tym else "tensor<?>"
        hits[ty] = hits.get(ty, 0) + 1
    return [Finding(
        rule="nondeterministic-scatter", severity=Severity.WARN,
        program=program.name,
        message=(f"{n} scatter-add op(s) onto {ty} with "
                 "unique_indices=false in a GSPMD-partitioned "
                 "program — the partitioner may reassociate the "
                 "float adds across shards (the MHD mesh-of-8 ~1-ulp "
                 "drift); mark indices unique or fold per owner"),
        key=ty, detail={"count": n, "result": ty})
        for ty, n in sorted(hits.items())]


register(Rule(
    id="nondeterministic-scatter", kind="hlo",
    check=_check_nondet_scatter,
    doc=("ROADMAP item 2: MHD partial-level corrections folded "
         "through a GSPMD scatter-add agreed with the mesh-of-1 run "
         "only to ~1 ulp — scatter-adds whose indices are not "
         "declared unique let the partitioner reassociate float "
         "sums.  Flags non-unique scatter-adds in partitioned "
         "programs.")))


# ---------------------------------------------------------------------
# donation-miss  (PR 2 donation plumbing; BASELINE copy regressions)
# ---------------------------------------------------------------------
DONATION_LIMIT_BYTES = 8 << 20


def _check_donation(program) -> List[Finding]:
    args = main_args(program.text)
    out: List[Finding] = []
    donated = sum(1 for _, _, attrs in args if _is_donated(attrs))
    if program.meta.get("expect_donation") and donated == 0:
        out.append(Finding(
            rule="donation-miss", severity=Severity.ERROR,
            program=program.name,
            message=("step chain declared donating but NO lowered "
                     "argument carries a donation marker "
                     "(tf.aliasing_output / jax.buffer_donor) — the "
                     "donation was dropped and every step pays a "
                     "full state copy"),
            key="no-aliasing", detail={"args": len(args)}))
    limit = int(program.meta.get("donation_limit_bytes",
                                 DONATION_LIMIT_BYTES))
    undonated: Dict[str, Dict[str, Any]] = {}
    for dims, dty, attrs in args:
        nb = _nbytes(dims, dty)
        if nb < limit or _is_donated(attrs):
            continue
        ty = f"tensor<{dims + 'x' if dims else ''}{dty}>"
        h = undonated.setdefault(ty, {"bytes": nb, "count": 0})
        h["count"] += 1
    for ty, h in sorted(undonated.items()):
        out.append(Finding(
            rule="donation-miss", severity=Severity.WARN,
            program=program.name,
            message=(f"{h['count']} large input(s) of {ty} "
                     f"({h['bytes']:,} B >= {limit:,} B) never "
                     "donated — a step-chain buffer of this size "
                     "doubles its HBM footprint"),
            key=ty, detail=h))
    return out


register(Rule(
    id="donation-miss", kind="hlo", check=_check_donation,
    doc=("PR 2 added donate_argnums to the fused step chains so the "
         "scan carry aliases its input buffers.  A refactor that "
         "drops the donation (or adds a large undonated buffer) "
         "silently doubles the state footprint; the lowered module "
         "shows it as missing tf.aliasing_output arg attributes.")))


# ---------------------------------------------------------------------
# f64-leak  (x64-enabled hosts tracing f64 into f32 programs)
# ---------------------------------------------------------------------
_F64_RE = re.compile(r"tensor<(?:[0-9x]+x)?f64>")


def _check_f64_leak(program) -> List[Finding]:
    if int(program.meta.get("dtype_bits", 0)) != 32:
        return []                   # only f32-configured programs
    n = len(_F64_RE.findall(program.text))
    if n == 0:
        return []
    return [Finding(
        rule="f64-leak", severity=Severity.WARN,
        program=program.name,
        message=(f"{n} f64 tensor type(s) inside an f32-configured "
                 "program — a host scalar or numpy table traced at "
                 "double precision (2x the bandwidth, and TPUs "
                 "emulate f64); cast at the jit boundary"),
        key="f64", detail={"count": n})]


register(Rule(
    id="f64-leak", kind="hlo", check=_check_f64_leak,
    doc=("The test suite enables jax x64, so an uncast python float "
         "or np.float64 table reaching a trace drags f64 ops into "
         "f32 production programs.  Flags any f64 tensor type in a "
         "program whose configured dtype is f32.")))
