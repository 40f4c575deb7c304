"""The program's one HLO-text parser: the static gather-traffic
inventory, and the device-phase table of the AMR programs (which
``jax.named_scope`` — which level, which phase — owns each instruction
of a compiled program, layout copies included; second half of the file).

The AMR per-cell gap is gather-bound: every partial-level sweep starts
from index gathers out of the flat cell batches, and the gathered
RESULT element count of the lowered program is a backend-independent
proxy for that HBM traffic — countable on the CPU test backend, stable
across XLA versions (it is read from the *lowered* StableHLO, before
the partitioner or fusion touch it).  The blocked Morton-tile path
exists to shrink exactly this number, so the regression test pins it
(tests/test_hlo_inventory.py via the ``gather-blowup`` rule of
:mod:`ramses_tpu.analysis`) and the telemetry run header records it
(``hlo_gather_elems``) for offline trend tracking.

This module is the one low-level implementation: the ``analysis``
rule engine and the legacy telemetry hooks both count through
:func:`gather_inventory`, so the nightly gate and the lint CLI can
never drift apart.
"""

from __future__ import annotations

import re
import warnings
from typing import List, Tuple

# One gather op, pretty OR quoted generic syntax, possibly spanning
# lines (MLIR wraps long attribute dictionaries): anchor on the op
# name, then take the FIRST `-> tensor<...>` result type that follows
# within the op's own text window.  Gathers carry no region, so the
# window never swallows a neighbouring op's arrow: it is cut at the
# next `stablehlo.` op-name occurrence.
# negative lookbehind: `#stablehlo.gather<...>` is the op's
# dimension-numbers ATTRIBUTE, not an op occurrence
_GATHER_OP_RE = re.compile(r"(?<!#)stablehlo\.(?:dynamic_)?gather\b")
_ARROW_RE = re.compile(
    r"->\s*(?:\()?\s*tensor<([0-9x]+)x?([a-z][a-z0-9]*)>", re.DOTALL)


def _result_elems(dims_txt: str) -> int:
    n = 1
    for d in dims_txt.split("x"):
        if d:
            n *= int(d)
    return n


def raw_gather_count(text: str) -> int:
    """Number of ``stablehlo.gather``/``dynamic_gather`` op-name
    occurrences in ``text`` — the cross-check denominator for the
    inventory (a parse that silently drops ops is how a traffic gate
    rots)."""
    return len(_GATHER_OP_RE.findall(text))


def gather_inventory(text: str) -> List[Tuple[int, str]]:
    """All gather ops in lowered StableHLO/HLO ``text`` as
    ``(result_elems, op_text)`` pairs, largest first.

    Handles the pretty syntax (``%9 = stablehlo.gather ... ->
    tensor<...>``), the quoted generic syntax
    (``"stablehlo.gather"(...) <{...}> : (...) -> tensor<...>``), and
    ops whose attribute dictionary wraps across lines.  When the
    number of parsed ops disagrees with the raw op-name count a
    ``RuntimeWarning`` is emitted — the inventory is a CI gate, so a
    silent undercount is itself a bug.
    """
    starts = [m.start() for m in _GATHER_OP_RE.finditer(text)]
    out: List[Tuple[int, str]] = []
    for i, s in enumerate(starts):
        # op text window: from this op name to the next gather op (or
        # a bounded lookahead) — enough to cover a wrapped attr dict
        end = starts[i + 1] if i + 1 < len(starts) else min(
            len(text), s + 4000)
        window = text[s:end]
        # generic syntax puts the function type after `: ( ... ) ->`;
        # pretty syntax is `... -> tensor<...>` directly.  Either way
        # the first arrow-to-tensor in the window is the result type.
        m = _ARROW_RE.search(window)
        if not m:
            continue
        op_txt = " ".join(window[:m.end()].split())
        out.append((_result_elems(m.group(1)), op_txt[:200]))
    if len(out) != len(starts):
        warnings.warn(
            f"gather inventory parsed {len(out)} of {len(starts)} "
            "stablehlo.gather ops — the traffic count is an "
            "UNDERCOUNT; fix telemetry/hlo.py's parser",
            RuntimeWarning, stacklevel=2)
    out.sort(key=lambda t: -t[0])
    return out


def count_gather_elems(text: str) -> int:
    """Total gathered RESULT elements across every gather op in lowered
    ``text``."""
    return sum(n for n, _ in gather_inventory(text))


def lower_fused_step(sim, dt: float = 1e-6) -> str:
    """Lowered (pre-optimization) StableHLO text of one fused AMR coarse
    step for ``sim``'s current tree — the program whose gather traffic
    the inventory counts.  Dispatches on the solver family: MHD sims
    (``sim.bfs``) lower the CT fused step."""
    import jax.numpy as jnp

    dt_arr = jnp.asarray(float(sim.dt_old or dt), sim.dtype)
    spec = sim._fused_spec()
    if hasattr(sim, "bfs"):
        from ramses_tpu.mhd import amr as M

        return M._mhd_fused_coarse_step.lower(
            sim.u, sim.bfs, sim.dev, dt_arr, spec,
            sim.fg if sim.gravity else None).as_text()
    from ramses_tpu.amr import hierarchy as H

    return H._fused_coarse_step.lower(
        sim.u, sim.dev, sim.fg if sim.gravity else {}, dt_arr, spec,
        sim._cool_bundle()).as_text()


def fused_step_gather_elems(sim) -> int:
    """``count_gather_elems`` of the sim's fused coarse step."""
    return count_gather_elems(lower_fused_step(sim))


# ----------------------------------------------------------------------
# device-phase scopes and the op -> phase table
# ----------------------------------------------------------------------
# The ONE table of the ``jax.named_scope`` names the AMR programs carry
# (``amr/hierarchy.py``, ``amr/kernels.py``, ``parallel/dense_slab.py``)
# and the kind of device work under each: ``kernel`` (a ``pallas_call``
# or its XLA twin), ``layout`` (gathers, pads, transposes, read-backs:
# bytes moved, no physics) or ``physics``.  Two levels deep: an outer
# name, which takes the level as `` l<level>``, then an inner one.
# Scopes are metadata: no op, no byte, nothing to switch on or off.
PHASE_KINDS = {
    # outer (hierarchy._advance_traced / _fused_flags / _migrate_level)
    "sweep": "physics", "fluxcorr": "physics", "restrict": "physics",
    "source": "physics", "courant": "physics", "flags": "physics",
    "migrate: copy": "layout", "migrate: interp": "physics",
    # inner (kernels.sweep_level / flags_level and what they call)
    "ghost": "physics", "gather": "layout", "pad": "layout",
    "kernel": "kernel", "scatter": "layout", "criteria": "physics",
}
UNATTRIBUTED = "unattributed"
_LEVEL_RE = re.compile(r" l\d+$")


def phase(name: str, level=None):
    """``jax.named_scope`` of one declared device phase."""
    import jax

    if name not in PHASE_KINDS:
        raise KeyError(f"{name!r} is not in telemetry/hlo.PHASE_KINDS")
    return jax.named_scope(name if level is None else f"{name} l{level}")


def scope_path(op_name: str) -> str:
    """The declared scopes of an ``op_name`` (``jit(f)/jit(main)/sweep
    l8/jit(tile_sweep)/gather/gather`` -> ``sweep l8/gather``), outermost
    first; ``""`` when it holds none.  The last component is the
    primitive (``gather``, ``pad`` and ``scatter`` are primitives too)
    and never a scope."""
    return "/".join(c for c in op_name.split("/")[:-1]
                    if _LEVEL_RE.sub("", c) in PHASE_KINDS)


def phase_kind(path: str) -> str:
    """Kind of a scope path: its innermost scope's."""
    if not path or path == UNATTRIBUTED:
        return UNATTRIBUTED
    return PHASE_KINDS[_LEVEL_RE.sub("", path.rsplit("/", 1)[-1])]


# one instruction of compiled HLO text: optional ROOT, name, result
# type (one array or a tuple), opcode, then operands and attributes
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (\(.*?\)|[a-z]\w*\[[^\]]*\]\S*) "
    r"([a-z][\w\-]*)\((.*)$")
_LOOSE_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%?[\w.\-]+ = \S")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+) (?:\(.*\))? ?.*\{\s*$")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
# computations a device runs op by op: reached from ENTRY through these
# attributes; ``to_apply`` only from a ``call`` (a reduce's is its
# combiner), ``calls`` from anything but a ``fusion``
_CALLED_RE = re.compile(
    r"\b(body|condition|true_computation|false_computation|to_apply|"
    r"calls|branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
# an array of a result type: dtype (its first number is its bits; pred
# has none), then the dimensions
_ARRAY_RE = re.compile(r"\b(?:pred|[a-z]+(\d+)[a-z0-9]*)\[([\d,]*)\]")


def _result_bytes(type_txt: str) -> int:
    """Bytes of an instruction's result, from its shape text (a tuple's
    arrays summed; layout and tiling annotations ignored)."""
    return sum(_result_elems(dims.replace(",", "x"))
               * max(int(bits or 8) // 8, 1)
               for bits, dims in _ARRAY_RE.findall(type_txt))


def _split_computations(text: str):
    """({computation name: [its lines]}, the ENTRY computation's name)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m and not line.startswith((" ", "HloModule")):
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
        elif line.startswith("}"):
            cur = None
        else:
            comps[cur].append(line)
    return comps, entry


def parse_instructions(text: str) -> dict:
    """``{instruction name: {comp, caller, opcode, operands, op_name,
    bytes}}`` (``caller``: the instruction that calls its computation,
    ``""`` in ENTRY) for every instruction of every computation of compiled
    HLO ``text`` that the device runs op by op (ENTRY, ``while`` bodies
    and conditions, conditional branches, called computations; not the
    inside of a fusion or a reduce's combiner).  Instructions parsed !=
    instructions present is a ``RuntimeWarning``."""
    comps, entry = _split_computations(text)
    if entry is None:
        return {}
    todo, seen = [(entry, "")], set()
    out, present = {}, 0
    while todo:
        comp, caller = todo.pop(0)
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            if _LOOSE_INSTR_RE.match(line):
                present += 1
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, type_txt, opcode, rest = m.groups()
            # operands end where the attributes start: the first
            # "), " at nesting depth 0 is enough for %names, which the
            # attribute values that hold them (calls=, body=) follow
            head = rest.split("), ", 1)[0]
            mo = _OPNAME_RE.search(rest)
            out[name] = {
                "comp": comp, "caller": caller, "opcode": opcode,
                "operands": _OPERAND_RE.findall(head),
                "op_name": mo.group(1) if mo else "",
                "bytes": _result_bytes(type_txt)}
            for attr, val in _CALLED_RE.findall(rest):
                if attr == "to_apply" and opcode != "call":
                    continue
                if attr == "calls" and opcode == "fusion":
                    continue
                todo.extend((v.strip().lstrip("%"), name)
                            for v in val.strip("{}").split(","))
    if len(out) != present:
        warnings.warn(
            f"phase table parsed {len(out)} of {present} HLO "
            "instructions — ops are missing from the table; fix "
            "telemetry/hlo.py's parser", RuntimeWarning, stacklevel=2)
    return out


def phase_table(compiled_text: str) -> dict:
    """``{instruction name: (scope path, kind)}`` of compiled HLO text.

    Rule, in this order: the instruction's own ``op_name``'s scope path
    (:func:`scope_path`) if it holds a declared scope; else the scope
    of its first user that has one — a layout copy the compiler
    inserted carries no ``op_name`` and belongs to what reads it —
    followed through scope-less users; else that of its first
    operand's producer; else, in a computation that an instruction
    calls (a ``while`` body the compiler made), that instruction's;
    else ``unattributed``."""
    instrs = parse_instructions(compiled_text)
    path = {n: scope_path(i["op_name"]) for n, i in instrs.items()}
    users = {n: [] for n in instrs}
    for n, i in instrs.items():
        for o in i["operands"]:
            if o in users and instrs[o]["comp"] == i["comp"]:
                users[o].append(n)
    # users follow their operands in the text: resolve back to front
    for n in reversed(list(instrs)):
        if not path[n]:
            path[n] = next((path[u] for u in users[n] if path[u]), "")
    # ENTRY first, callers before what they call
    for n, i in instrs.items():
        if not path[n]:
            first = next((o for o in i["operands"] if o in path
                          and instrs[o]["comp"] == i["comp"]), None)
            path[n] = (path[first] if first else "") \
                or path.get(i["caller"], "")
    return {n: (p or UNATTRIBUTED, phase_kind(p)) for n, p in path.items()}


# ----------------------------------------------------------------------
# what was dispatched under a profiler session, and its phase tables
# ----------------------------------------------------------------------
# signature -> (jitted function, its arguments with every array replaced
# by a ShapeDtypeStruct that keeps the sharding); process-wide, filled
# only while a profiler session is on
_DISPATCHED: dict = {}


def _abstract(x):
    """An array as the ShapeDtypeStruct that lowers as the array did: it
    keeps the sharding of a COMMITTED array only (an uncommitted one
    leaves the placement to jit, and a sharding stated for it would be
    another lowering: another program to compile)."""
    import jax

    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    return x


def note_dispatch(fn, *args):
    """Called where a whole-hierarchy program is dispatched
    (``AmrSim.step_coarse``, ``_criteria_flags``) with the arguments of
    the call.  Only while a profiler session is on, and once per new
    signature, it keeps ``(fn, abstract arguments)`` so that
    :func:`device_phases` can ask the compiler, after the traced window,
    which scope owns each instruction of what ran.  No lowering, no
    compile, no clock here; off (no session): nothing."""
    from jax.profiler import TraceAnnotation

    if not TraceAnnotation.is_enabled():
        return
    from jax.tree_util import tree_flatten, tree_map

    leaves, treedef = tree_flatten(args)
    key = (fn, treedef, tuple(
        (x.shape, x.dtype, x.sharding, x.committed, x.weak_type)
        if hasattr(x, "sharding") else x for x in leaves))
    if key not in _DISPATCHED:
        _DISPATCHED[key] = (fn, tree_map(_abstract, args))


def dispatch_records() -> list:
    """``[(jitted function, abstract arguments)]`` noted so far."""
    return list(_DISPATCHED.values())


def clear_dispatch_records():
    _DISPATCHED.clear()


def module_name(compiled_text: str) -> str:
    m = re.match(r"HloModule ([\w.\-]+)", compiled_text)
    return m.group(1) if m else ""


def device_phases() -> dict:
    """``{module name: phase_table}`` of every program noted by
    :func:`note_dispatch`: each signature is lowered (the lowering the
    call made, from memory) and COMPILED here, when asked — after the
    traced window.  A compile, not a cache load: the compile cache's
    key leaves metadata out, so an entry written by a program without
    these scopes (the parent commit, on a machine both ran on) comes
    back with ITS ``op_name``s and every instruction reads
    ``unattributed``; for this one compile the key takes the metadata
    in, so a hit is a program with these scopes at these lines.  The
    instruction names are those of the executable that ran, but for a
    few ``reshape`` chains XLA numbers by metadata when THAT came from
    another program's cache entry (19 of 1 092 ops on the chip, PR 36:
    a reader counts an op it does not find as unattributed).  A module
    name that left more than one signature (a
    window that crossed a bucket: two programs, one name) is left out
    and named on stderr."""
    import sys

    import jax

    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    # an option set to the value it has: compiles the same program, but
    # not the executable jit keeps in memory (whose text may be stale)
    same = {"exec_time_optimization_effort":
            jax.config.jax_exec_time_optimization_effort}
    texts = {}
    jax.config.update(key, True)
    try:
        for fn, args in dispatch_records():
            text = fn.lower(*args).compile(compiler_options=same).as_text()
            texts.setdefault(module_name(text), []).append(text)
    finally:
        jax.config.update(key, was)
    out = {}
    for name, same_name in texts.items():
        if len(same_name) > 1:
            print(f"device_phases: {len(same_name)} signatures of {name} "
                  "were dispatched under the session; left out",
                  file=sys.stderr)
            continue
        out[name] = phase_table(same_name[0])
    return out
