"""Trip-count-aware HLO cost model.

XLA's ``compiled.cost_analysis()`` counts a while-loop body ONCE, so any
scan-over-layers / microbatch-accumulation program is undercounted by the
trip count (verified empirically — see tests/test_roofline.py).  This walker
parses the post-SPMD HLO text and computes, per device:

  * FLOPs       — 2 * out_elems * contraction for every dot (batch dims
                  included in out_elems); elementwise ops ~ out_elems
  * HBM bytes   — operand + output bytes at fusion boundaries and top-level
                  ops (instructions inside a fusion body touch registers,
                  not HBM, so only their FLOPs count)
  * collectives — operand bytes per kind (all-gather / all-reduce /
                  reduce-scatter / all-to-all / collective-permute)

multiplying every while body/cond by its ``known_trip_count`` (recursive;
nested scans compose).  This is the basis of the §Roofline terms.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_TRIP_RE = re.compile(r'known_trip_count[^\d]*(\d+)')
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w\.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _dims(type_str: str) -> List[Tuple[str, List[int]]]:
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
        out.append((m.group(1), dims))
    return out


def _bytes_of(type_str: str) -> int:
    total = 0
    for dt, dims in _dims(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _elems_of(type_str: str) -> int:
    total = 0
    for dt, dims in _dims(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims:
            n *= d
        total += n
    return total


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=lambda: defaultdict(lambda: {"count": 0.0,
                                                     "operand_bytes": 0.0}))

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        self.coll_bytes += other.coll_bytes * mult
        for k, v in other.coll.items():
            self.coll[k]["count"] += v["count"] * mult
            self.coll[k]["operand_bytes"] += v["operand_bytes"] * mult


@dataclasses.dataclass
class _Instr:
    name: str
    out_type: str
    opcode: str
    rest: str


def _operands(rest: str) -> List[str]:
    """Split the operand region of an instruction (``rest`` starts right
    after the opcode's opening paren) into operand tokens.

    Types may be printed inline (``f32[64,32]{1,0} %name``) and contain
    commas/braces/parens of their own, so this is a balanced scan, not a
    ``split(",")``: commas only separate operands at paren depth 1
    outside [] and {}.
    """
    depth_p, depth_b, depth_c = 1, 0, 0
    out: List[str] = []
    cur: List[str] = []
    for ch in rest:
        if ch == "(":
            depth_p += 1
        elif ch == ")":
            depth_p -= 1
            if depth_p == 0:
                break
        elif ch == "[":
            depth_b += 1
        elif ch == "]":
            depth_b -= 1
        elif ch == "{":
            depth_c += 1
        elif ch == "}":
            depth_c -= 1
        if ch == "," and depth_p == 1 and depth_b == 0 and depth_c == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        out.append(tail)
    return [o.strip() for o in out if o.strip()]


def _split_tok(tok: str) -> Tuple[Optional[str], str]:
    """Operand token -> (inline type or None, instruction name).

    Depending on XLA version, operands print as ``%name`` or with the type
    inline: ``f32[64,32]{1,0} %name``.
    """
    tok = tok.strip()
    if " %" in tok:
        typ, name = tok.rsplit(" %", 1)
        return typ, name.split(" ")[0]
    return None, tok.lstrip("%").split(" ")[0]


def _operand_name(tok: str) -> str:
    return _split_tok(tok)[1]


def _operand_type(tok: str, syms: Dict[str, str]) -> Optional[str]:
    typ, name = _split_tok(tok)
    return typ if typ is not None else syms.get(name)


def _split_instr(ln: str) -> Optional[_Instr]:
    """Hand parser: tuple types may contain '(', '=', '/*index=N*/' comments,
    so the type is extracted by balanced-paren scan, not regex."""
    m = _NAME_RE.match(ln)
    if not m:
        return None
    rest = ln[m.end():]
    if rest.startswith("("):
        depth = 0
        end = -1
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        if end < 0:
            return None
        out_type, rest2 = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        out_type, rest2 = rest[:sp], rest[sp + 1:].lstrip()
    mo = _OPCODE_RE.match(rest2)
    if not mo:
        return None
    return _Instr(m.group(1), out_type, mo.group(1), rest2[mo.end():])


class HloModuleCost:
    def __init__(self, hlo_text: str):
        self.comps: Dict[str, List[_Instr]] = {}
        self.entry: Optional[str] = None
        self._parse(hlo_text)
        self._memo: Dict[Tuple[str, bool], Cost] = {}

    # -- parsing --------------------------------------------------------------
    def _parse(self, text: str):
        current = None
        for ln in text.splitlines():
            mc = _COMP_RE.match(ln)
            if mc:
                current = mc.group(1)
                self.comps[current] = []
                if ln.lstrip().startswith("ENTRY"):
                    self.entry = current
                continue
            if ln.strip() == "}":
                current = None
                continue
            if current is None:
                continue
            ins = _split_instr(ln)
            if ins:
                self.comps[current].append(ins)

    # -- cost -----------------------------------------------------------------
    def _symbols(self, comp: str) -> Dict[str, str]:
        return {i.name: i.out_type for i in self.comps.get(comp, [])}

    def comp_cost(self, name: str, count_bytes: bool = True) -> Cost:
        key = (name, count_bytes)
        if key in self._memo:
            return self._memo[key]
        cost = Cost()
        self._memo[key] = cost  # break cycles defensively
        syms = self._symbols(name)
        for ins in self.comps.get(name, []):
            cost.add(self._instr_cost(ins, syms, count_bytes))
        return cost

    def _param_slice_info(self, comp: str):
        """Per fusion-body parameter index: how is it actually touched?

        Returns {param_idx: ("slice", bytes) | ("dus", bytes)} for params
        consumed by dynamic-slice (read one slice per call — the scan-over-
        layers pattern: stacked weights / saved activations) or updated by
        dynamic-update-slice (in-place accumulator — RMW of the region).
        Params absent from the map are read fully.  Memoized.
        """
        cache = getattr(self, "_psi_cache", None)
        if cache is None:
            cache = self._psi_cache = {}
        if comp in cache:
            return cache[comp]
        syms = self._symbols(comp)
        param_of = {}
        for ins in self.comps.get(comp, []):
            if ins.opcode == "parameter":
                m = re.match(r"\s*(\d+)", ins.rest)
                if m:
                    param_of[ins.name] = int(m.group(1))
        # follow simple pass-through chains (convert/bitcast/copy/reshape)
        # back to parameters so dus(convert(param)) still resolves
        passthrough = {}
        for ins in self.comps.get(comp, []):
            if ins.opcode in ("convert", "bitcast", "copy", "reshape",
                              "transpose"):
                srcs = _operands(ins.rest)
                if srcs:
                    passthrough[ins.name] = _operand_name(srcs[0])

        def resolve(name):
            seen = 0
            while name in passthrough and seen < 8:
                name = passthrough[name]
                seen += 1
            return name

        info = {}
        for ins in self.comps.get(comp, []):
            ops = [resolve(_operand_name(o)) for o in _operands(ins.rest)]
            if ins.opcode == "dynamic-slice" and ops and ops[0] in param_of:
                idx = param_of[ops[0]]
                prev = info.get(idx, ("slice", 0))[1]
                info[idx] = ("slice", prev + _bytes_of(ins.out_type))
            if ins.opcode == "dynamic-update-slice" and ops:
                upd = _bytes_of(syms.get(ops[1], "")) if len(ops) > 1 else \
                    _bytes_of(ins.out_type) // 8
                if ops[0] in param_of:
                    idx = param_of[ops[0]]
                    prev = info.get(idx, ("dus", 0))[1]
                    info[idx] = ("dus", prev + 2 * upd)
                else:
                    info.setdefault("_dus_orphan", ("dus_orphan", 0))
                    info["_dus_orphan"] = (
                        "dus_orphan",
                        info["_dus_orphan"][1] + 2 * upd)
        cache[comp] = info
        return info

    def _fusion_boundary_bytes(self, ins: _Instr, syms: Dict[str, str],
                               callee: Optional[str]) -> float:
        info = self._param_slice_info(callee) if callee else {}
        orphan = info.get("_dus_orphan", (None, 0))[1]
        op_bytes = []
        total = 0.0
        aliased_out = False
        for pos, o in enumerate(_operands(ins.rest)):
            if pos in info:
                kind, b = info[pos]
                total += b
                if kind == "dus":
                    aliased_out = True     # accumulator aliased in->out
            else:
                ot = _operand_type(o, syms)
                if ot is not None:
                    op_bytes.append(_bytes_of(ot))
        if orphan and not aliased_out and op_bytes:
            # DUS on an unresolved chain: assume the largest operand is the
            # aliased accumulator
            op_bytes.remove(max(op_bytes))
            total += orphan
            aliased_out = True
        total += sum(op_bytes)
        if not aliased_out:
            total += _bytes_of(ins.out_type)
        return total

    def _operand_bytes(self, ins: _Instr, syms: Dict[str, str]) -> int:
        total = 0
        for op in _operands(ins.rest):
            ot = _operand_type(op, syms)
            if ot is not None:
                total += _bytes_of(ot)
        return total

    def _instr_cost(self, ins: _Instr, syms: Dict[str, str],
                    count_bytes: bool) -> Cost:
        c = Cost()
        op = ins.opcode
        base = op.replace("-start", "").replace("-done", "")

        if base in COLLECTIVES:
            if op.endswith("-done"):
                return c
            ob = self._operand_bytes(ins, syms) or _bytes_of(ins.out_type)
            c.coll_bytes += ob
            c.coll[base]["count"] += 1
            c.coll[base]["operand_bytes"] += ob
            if count_bytes:
                c.bytes += ob + _bytes_of(ins.out_type)
            return c

        if op == "while":
            mt = _TRIP_RE.search(ins.rest)
            trips = int(mt.group(1)) if mt else 1
            mb = _BODY_RE.search(ins.rest)
            mc2 = _COND_RE.search(ins.rest)
            if mb:
                c.add(self.comp_cost(mb.group(1), count_bytes), trips)
            if mc2:
                c.add(self.comp_cost(mc2.group(1), False), trips)
            return c

        if op in ("fusion", "call", "async-start"):
            mcal = _CALLS_RE.search(ins.rest) or \
                re.search(r"(?:to_apply|called_computation)=%?([\w\.\-]+)",
                          ins.rest)
            callee = mcal.group(1) if mcal else None
            if callee:
                inner = self.comp_cost(callee, count_bytes=False)
                c.add(Cost(flops=inner.flops, coll_bytes=inner.coll_bytes,
                           coll=inner.coll))
            if count_bytes:
                c.bytes += self._fusion_boundary_bytes(ins, syms, callee)
            return c

        if op == "conditional":
            branches = re.findall(
                r"(?:true_computation|false_computation|branch_computations=\{)"
                r"[^\}]*", ins.rest)
            names = re.findall(r"%([\w\.\-]+)", ",".join(branches))
            if names:
                worst = Cost()
                for n in set(names):
                    bc = self.comp_cost(n, count_bytes=False)
                    if bc.flops >= worst.flops:
                        worst = bc
                c.add(worst)
            if count_bytes:
                c.bytes += self._operand_bytes(ins, syms) + \
                    _bytes_of(ins.out_type)
            return c

        if op == "dot":
            out_elems = _elems_of(ins.out_type)
            contract = 1
            mcon = _CONTRACT_RE.search(ins.rest)
            dot_ops = _operands(ins.rest)
            lhs_type = _operand_type(dot_ops[0], syms) if dot_ops else None
            if mcon and lhs_type is not None:
                ldims = _dims(lhs_type)
                if ldims:
                    dims = ldims[0][1]
                    for idx in (int(x) for x in mcon.group(1).split(",")
                                if x != ""):
                        if idx < len(dims):
                            contract *= dims[idx]
            c.flops += 2.0 * out_elems * contract
            if count_bytes:
                c.bytes += self._operand_bytes(ins, syms) + \
                    _bytes_of(ins.out_type)
            return c

        if op == "convolution":
            # flops ~ 2 * out_elems * (kernel elems / out_features)
            out_elems = _elems_of(ins.out_type)
            ops = _operands(ins.rest)
            kelems = 0
            ktype = _operand_type(ops[1], syms) if len(ops) > 1 else None
            if ktype is not None:
                kd = _dims(ktype)
                if kd:
                    n = 1
                    for d in kd[0][1]:
                        n *= d
                    kelems = n
                    ofeat = kd[0][1][-1] if kd[0][1] else 1
                    kelems = n // max(ofeat, 1)
            c.flops += 2.0 * out_elems * max(kelems, 1)
            if count_bytes:
                c.bytes += self._operand_bytes(ins, syms) + \
                    _bytes_of(ins.out_type)
            return c

        if op in ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast", "after-all", "partition-id", "replica-id"):
            return c

        if op == "dynamic-update-slice":
            # RMW of the update region only (in-place on TPU): 2x update bytes
            ops = _operands(ins.rest)
            utype = _operand_type(ops[1], syms) if len(ops) > 1 else None
            if count_bytes:
                c.bytes += 2 * _bytes_of(utype or "")
            return c

        if op == "dynamic-slice":
            if count_bytes:
                c.bytes += 2 * _bytes_of(ins.out_type)  # read slice + write
            return c

        # everything else: ~1 flop per output element; bytes at top level
        c.flops += _elems_of(ins.out_type)
        if count_bytes and op not in ("broadcast", "iota", "reshape", "copy"):
            c.bytes += self._operand_bytes(ins, syms) + _bytes_of(ins.out_type)
        elif count_bytes:
            c.bytes += _bytes_of(ins.out_type)
        return c

    def total(self) -> Cost:
        if not self.entry:
            return Cost()
        return self.comp_cost(self.entry, count_bytes=True)


def module_cost(hlo_text: str) -> Cost:
    return HloModuleCost(hlo_text).total()
