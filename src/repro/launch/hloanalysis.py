"""Trip-count-aware analysis of compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts the body of a ``while`` loop ONCE, so a
scan-over-layers model under-reports FLOPs by the trip count (65x for a
32-layer model with 8 microbatches).  XLA, however, annotates every
scan-derived while op with ``backend_config={"known_trip_count":{"n": N}}``
— this module parses the HLO module text, propagates computation
*multiplicities* through the call graph (whiles multiply by trip count;
fusions/calls/conditionals inherit), and accumulates:

* ``flops``            — 2 * prod(result dims) * prod(contracting dims) per
                         ``dot``, multiplicity-weighted (matmuls dominate;
                         elementwise FLOPs are not counted — documented),
* ``collective_bytes`` — operand bytes per collective op, by kind,
* ``hbm_bytes``        — sum of (operands + result) bytes over top-level
                         instructions (each top-level fusion/dot/collective
                         reads operands from and writes results to HBM; an
                         upper-bound-flavored traffic model).

This is the §Roofline extraction layer; values feed benchmarks/roofline.py.

:func:`collective_scopes` lists each collective with the ``jax.named_scope``
path it was traced under (its ``op_name`` metadata), which is how a phase of
``core/collectives.py`` or a stage of the train step is found in a compiled
program.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

__all__ = ["analyze_module", "collective_scopes", "HloCost"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0, "s4": 1, "u4": 1,
}

_ARRAY_RE = re.compile(
    r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|s32|s16|s8|s4|u64|u32|u16|u8|u4|"
    r"pred|c64|c128)\[([0-9,]*)\]"
)
_COLL_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "copy", "copy-start", "copy-done", "after-all", "iota", "broadcast",
    "reshape", "transpose",
}


def _type_bytes(type_str: str) -> int:
    return sum(
        _DTYPE_BYTES[d] * _dims_prod(dims) for d, dims in _ARRAY_RE.findall(type_str)
    )


def _dims_prod(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _dims_list(type_str: str) -> list[int]:
    m = _ARRAY_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class _Instr:
    name: str
    result_type: str
    opcode: str
    operands: list[str]
    raw: str


@dataclasses.dataclass
class _Computation:
    name: str
    params: dict[str, str]  # name -> type
    instrs: list[_Instr]


@dataclasses.dataclass
class HloCost:
    flops: float
    collective_bytes: dict[str, int]
    hbm_bytes: float
    num_whiles: int
    unknown_trip_whiles: int

    @property
    def collective_total(self) -> int:
        return int(sum(self.collective_bytes.values()))


_COMP_HEADER = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->", re.M
)
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"(?:calls=|to_apply=)%?([\w.\-]+)")
_COND_BODY_RE = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _parse_computations(text: str) -> tuple[dict[str, _Computation], str]:
    comps: dict[str, _Computation] = {}
    entry = ""
    cur: _Computation | None = None
    for line in text.splitlines():
        if line.endswith("{") and ("->" in line):
            m = _COMP_HEADER.match(line.strip())
            if m:
                is_entry, name, params_str = m.group(1), m.group(2), m.group(3)
                params: dict[str, str] = {}
                for pm in re.finditer(r"([\w.\-]+)\s*:\s*((?:\([^)]*\))|[^,]+)",
                                      params_str):
                    params[pm.group(1)] = pm.group(2)
                cur = _Computation(name, params, [])
                comps[name] = cur
                if is_entry:
                    entry = name
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        im = _INSTR_RE.match(line)
        if not im:
            continue
        name, rest = im.group(2), im.group(3)
        # result type = prefix up to the opcode word.  Tuple types may
        # contain nested parens and /*index=N*/ comments — take the balanced
        # paren region.
        if rest.startswith("("):
            end = _matching_paren(rest)
            result_type = rest[:end]
            after = rest[end:].lstrip()
        else:
            # TPU layouts carry tiling and memory space: {0:T(1024)S(1)}
            sm = re.match(r"([\w\[\]{},:()]+)\s+", rest)
            if not sm:
                continue
            result_type = sm.group(1)
            after = rest[sm.end():]
        om = re.match(r"([\w\-]+)\(", after)
        if not om:
            continue
        opcode = om.group(1)
        args = after[om.end() - 1 :]
        # operands: names inside the first paren group (before attributes)
        paren = args[: _matching_paren(args)]
        operands = _OPERAND_RE.findall(paren)
        cur.instrs.append(_Instr(name, result_type, opcode, operands, rest))
    return comps, entry


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def collective_scopes(text: str) -> list[tuple[str, str]]:
    """``(kind, op_name)`` for every collective of a compiled HLO module,
    an async pair counted once (at its ``-start``).  A collective that
    carries no ``op_name`` takes its reducer's (``to_apply``): the TPU
    compiler rewrites a reduce-scatter into an all-reduce and a slice and
    drops the new all-reduce's metadata, but not its reducer's.  ``""``
    where neither has one."""
    comps, _ = _parse_computations(text)
    out = []
    for comp in comps.values():
        for ins in comp.instrs:
            kind = ins.opcode.removesuffix("-start")
            if kind not in _COLL_KINDS:
                continue
            m = _OP_NAME_RE.search(ins.raw)
            if m is None:
                cm = _CALLS_RE.search(ins.raw)
                reducer = comps.get(cm.group(1)) if cm else None
                root = reducer.instrs[-1].raw if reducer and reducer.instrs \
                    else ""
                m = _OP_NAME_RE.search(root)
            out.append((kind, m.group(1) if m else ""))
    return out


def _matching_paren(s: str) -> int:
    depth = 0
    for i, ch in enumerate(s):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def analyze_module(text: str) -> HloCost:
    comps, entry = _parse_computations(text)

    # ---- call graph with edge weights (while bodies weighted by trip).
    # Edges are tagged: "flow" edges (while/conditional/call) reach
    # computations whose instructions are real top-level HBM operations;
    # "fusion" edges reach fused computations whose internals are
    # VMEM/register-resident (their HBM effect is the fusion op's own
    # result), so they contribute dots/collectives but not HBM traffic. ----
    edges: dict[str, list[tuple[str, float, bool]]] = defaultdict(list)
    num_whiles = unknown = 0
    for cname, comp in comps.items():
        for ins in comp.instrs:
            if ins.opcode == "while":
                num_whiles += 1
                tm = _TRIP_RE.search(ins.raw)
                trip = int(tm.group(1)) if tm else 1
                if tm is None:
                    unknown += 1
                cb = _COND_BODY_RE.search(ins.raw)
                if cb:
                    edges[cname].append((cb.group(1), float(trip), True))
                    edges[cname].append((cb.group(2), float(trip), True))
            elif ins.opcode == "conditional":
                bm = _BRANCHES_RE.search(ins.raw)
                if bm:
                    for b in _OPERAND_RE.findall(bm.group(1)):
                        edges[cname].append((b, 1.0, True))
            elif ins.opcode == "call":
                cm = _CALLS_RE.search(ins.raw)
                if cm:
                    edges[cname].append((cm.group(1), 1.0, True))
            else:
                cm = _CALLS_RE.search(ins.raw)
                if cm:  # fusion / custom-call computations
                    edges[cname].append((cm.group(1), 1.0, False))

    # ---- multiplicities: topological accumulation from ENTRY (the call
    # graph of an HLO module is a DAG).  mult = all paths (dots,
    # collectives); mult_flow = flow-only paths (HBM accounting). ----
    indeg: dict[str, int] = defaultdict(int)
    for cname, outs in edges.items():
        for t, _, _ in outs:
            indeg[t] += 1
    mult: dict[str, float] = defaultdict(float)
    mult_flow: dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    mult_flow[entry] = 1.0
    ready = [c for c in comps if indeg[c] == 0]
    order = []
    indeg_work = dict(indeg)
    while ready:
        c = ready.pop()
        order.append(c)
        for t, w, flow in edges.get(c, ()):  # noqa: B007
            indeg_work[t] -= 1
            if indeg_work[t] == 0:
                ready.append(t)
    for c in order:
        m = mult[c]
        mf = mult_flow[c]
        for t, w, flow in edges.get(c, ()):
            mult[t] += m * w
            if flow:
                mult_flow[t] += mf * w

    flops = 0.0
    hbm = 0.0
    coll: dict[str, int] = defaultdict(int)
    _HBM_SKIP = _SKIP_OPS | {
        "while", "conditional", "call", "custom-call", "optimization-barrier",
    }
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        mf = mult_flow.get(cname, 0.0)
        if m == 0.0:
            continue
        symtab = dict(comp.params)
        for ins in comp.instrs:
            symtab[ins.name] = ins.result_type
        for ins in comp.instrs:
            if ins.opcode == "dot":
                res_dims = _dims_list(ins.result_type)
                lhs_type = symtab.get(ins.operands[0], "") if ins.operands else ""
                lhs_dims = _dims_list(lhs_type)
                cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.raw)
                k = 1
                if cm and lhs_dims:
                    for idx in cm.group(1).split(","):
                        if idx:
                            k *= lhs_dims[int(idx)]
                n = 1
                for d in res_dims:
                    n *= d
                flops += m * 2.0 * n * k
            kind = next((c for c in _COLL_KINDS if ins.opcode.startswith(c)), None)
            if kind is not None and not ins.opcode.endswith("-done"):
                ob = sum(_type_bytes(symtab.get(o, "")) for o in ins.operands)
                coll[kind] += int(m * ob)
            # ---- HBM traffic (flow computations only: fused-computation
            # internals are VMEM/register-resident) ----
            if mf == 0.0 or ins.opcode in _HBM_SKIP:
                continue
            if ins.opcode == "dynamic-update-slice":
                # in-place update: traffic is the update region, not the
                # whole carried buffer
                upd = ins.operands[1] if len(ins.operands) > 1 else None
                hbm += mf * _type_bytes(symtab.get(upd, "")) if upd else 0.0
            else:
                hbm += mf * _type_bytes(ins.result_type)
                if ins.opcode == "dot":
                    hbm += mf * sum(
                        _type_bytes(symtab.get(o, "")) for o in ins.operands
                    )
    return HloCost(
        flops=flops,
        collective_bytes=dict(coll),
        hbm_bytes=hbm,
        num_whiles=num_whiles,
        unknown_trip_whiles=unknown,
    )
