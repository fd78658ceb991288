"""Post-optimization HLO analysis: collective bytes per device, and the
named scope of each instruction that runs on the device.

cost_analysis() gives FLOPs and memory bytes but NOT collective traffic;
we parse compiled.as_text() instead (the prompt's prescribed method).

Accounting rules:
  * every all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute instruction contributes its RESULT-shape bytes
    (per-device, since the module is the SPMD per-device program);
  * instructions inside a while body count once per trip — the trip count
    is recovered from the integer constant in the while condition
    (lax.scan lowers to a while loop with a `constant(T)` bound);
  * nested whiles multiply.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

__all__ = ["CollectiveStats", "analyze_collectives", "scope_map"]


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO result type, e.g. 'f32[2,512,1024]' or a tuple."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes_by_kind.values()))

    @property
    def total_count(self) -> int:
        return int(sum(self.count_by_kind.values()))


def _split_computations(hlo: str) -> dict[str, list[str]]:
    """computation name -> its instruction lines.

    A computation header is any line ending in '{' with a '->' return
    annotation (param lists may contain nested tuple parens, so we only
    anchor on the name prefix)."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        ls = line.strip()
        if ls.endswith("{") and "->" in ls and not ls.startswith("ROOT"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(", ls)
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if ls == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(ls)
    return comps


def _entry_name(hlo: str) -> str | None:
    m = re.search(r"ENTRY\s+%?([\w\.\-]+)\s*\(", hlo)
    return m.group(1) if m else None


_COLL_OP_RE = re.compile(
    r"\b(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(")


def _local_collectives(lines: list[str]):
    by_b: dict[str, float] = defaultdict(float)
    by_c: dict[str, int] = defaultdict(int)
    for ls in lines:
        if "=" not in ls:
            continue
        m = _COLL_OP_RE.search(ls)
        if not m:
            continue
        base, suffix = m.group(1), m.group(2)
        if suffix == "-done":
            continue  # counted at -start
        # result-type bytes: everything left of the opcode token holds the
        # instruction name (no brackets) and the result shape(s)
        b = _shape_bytes(ls[:m.start()])
        if suffix == "-start":
            b /= 2  # async start results pair (aliased input, output)
        by_b[base] += b
        by_c[base] += 1
    return by_b, by_c


def _calls(lines: list[str]):
    """(callee, kind) pairs: while bodies/conditions, calls, fusions."""
    out = []
    for ls in lines:
        for m in re.finditer(r"(body|condition|to_apply|calls)=%?([\w\.\-]+)",
                             ls):
            out.append((m.group(2), m.group(1)))
    return out


def _trip_count(cond_lines: list[str]) -> int:
    """Largest integer constant in the loop condition (scan bound)."""
    best = 1
    for ls in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", ls):
            best = max(best, int(m.group(1)))
    return best


def analyze_collectives(hlo: str) -> CollectiveStats:
    comps = _split_computations(hlo)
    entry = _entry_name(hlo)
    memo: dict[str, tuple[dict, dict]] = {}

    def visit(name: str, stack=()) -> tuple[dict, dict]:
        if name in memo:
            return memo[name]
        if name not in comps or name in stack:
            return {}, {}
        lines = comps[name]
        by_b, by_c = _local_collectives(lines)
        by_b, by_c = dict(by_b), dict(by_c)
        # find whiles: while(...) , condition=%c, body=%b
        for ls in lines:
            if re.search(r"\bwhile\(", ls):
                bm = re.search(r"body=%?([\w\.\-]+)", ls)
                cm = re.search(r"condition=%?([\w\.\-]+)", ls)
                if not bm:
                    continue
                trips = _trip_count(comps.get(cm.group(1), [])) if cm else 1
                bb, bc = visit(bm.group(1), stack + (name,))
                for k, v in bb.items():
                    by_b[k] = by_b.get(k, 0) + v * trips
                for k, v in bc.items():
                    by_c[k] = by_c.get(k, 0) + v * trips
            else:
                for callee, kind in _calls([ls]):
                    if kind in ("body", "condition"):
                        continue  # handled via while above
                    bb, bc = visit(callee, stack + (name,))
                    for k, v in bb.items():
                        by_b[k] = by_b.get(k, 0) + v
                    for k, v in bc.items():
                        by_c[k] = by_c.get(k, 0) + v
        memo[name] = (by_b, by_c)
        return memo[name]

    if entry is None:
        # fall back: count everything flat
        by_b, by_c = _local_collectives(hlo.splitlines())
        return CollectiveStats(dict(by_b), dict(by_c))
    by_b, by_c = visit(entry)
    return CollectiveStats(by_b, by_c)

_INSTRUCTION = re.compile(r"(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME = re.compile(r"%([\w.\-]+)")


def _group_end(text: str, i: int) -> int:
    """Index just past the bracket group that opens at text[i]."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in "([{":
            depth += 1
        elif text[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _operands(rest: str) -> list[str]:
    """Operand names of an instruction, from the text after its ' = '."""
    i = _group_end(rest, 0) if rest.startswith("(") else rest.find(" ")
    j = rest.find("(", max(i, 0))
    return _NAME.findall(rest[j:_group_end(rest, j)]) if j >= 0 else []


def scope_map(hlo: str, scopes) -> dict[str, list[str]]:
    """Named scope -> names of the instructions it covers, plus `unscoped`.

    Reads the instructions that run as device ops: those of the entry
    computation and of every while body and condition reached from it,
    not the internals of fused computations. An instruction takes the
    innermost of `scopes` named in its `op_name` metadata. One without an
    `op_name` (a copy or reshape that layout assignment inserted, say)
    takes the scope of its only user, failing that of its first operand,
    failing that it is `unscoped`; so is one whose `op_name` names none
    of `scopes`.
    """
    comps = _split_computations(hlo)
    todo, seen = [_entry_name(hlo)], set()
    insts: dict[str, tuple] = {}            # name -> (op_name, operands)
    users: dict[str, list[str]] = defaultdict(list)
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        todo += [c for c, kind in _calls(comps[comp])
                 if kind in ("body", "condition")]
        for ls in comps[comp]:
            m = _INSTRUCTION.match(ls)
            if not m:
                continue
            rest = ls[m.end():]
            op = _OP_NAME.search(rest)
            ops = _operands(rest)
            insts[m.group(1)] = (op.group(1) if op else "", ops)
            for o in ops:
                users[o].append(m.group(1))
    wanted = set(scopes)
    memo: dict[str, str] = {}

    def resolve(name: str, visiting: frozenset = frozenset()) -> str:
        if name in memo:
            return memo[name]
        if name not in insts or name in visiting:
            return "unscoped"
        op_name, ops = insts[name]
        if op_name:
            named = [p for p in op_name.split("/") if p in wanted]
            memo[name] = named[-1] if named else "unscoped"
            return memo[name]
        visiting = visiting | {name}
        got = "unscoped"
        if len(users[name]) == 1:
            got = resolve(users[name][0], visiting)
        if got == "unscoped" and ops:
            got = resolve(ops[0], visiting)
        memo[name] = got
        return got

    out: dict[str, list[str]] = {s: [] for s in (*scopes, "unscoped")}
    for name in insts:
        out[resolve(name)].append(name)
    return out
