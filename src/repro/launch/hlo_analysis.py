"""Post-optimization HLO text analyzer with while-loop trip-count expansion.

Why this exists: ``compiled.cost_analysis()`` counts a ``while`` body ONCE,
so any scan-over-layers program under-reports FLOPs/bytes/collectives by a
factor of the layer count.  This analyzer parses the optimized HLO text,
builds the computation call graph (fusions, while bodies, conditionals) and
accumulates

  * dot/convolution FLOPs,
  * HBM traffic: operand + result bytes of fusion-BOUNDARY ops (ops inside
    fusion computations stay in registers/VMEM and are not counted),
  * collective link bytes by kind (ring accounting: all-reduce 2x, others
    1x result bytes per device),

multiplying while bodies by their ``known_trip_count`` backend_config
(emitted by XLA when the trip count is static — always true for lax.scan).

Shapes in post-SPMD HLO are per-shard, so all results are PER-DEVICE.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict


def normalize_cost_analysis(cost) -> dict:
    """``compiled.cost_analysis()`` as a fresh dict (``{}`` for ``None``)."""
    return dict(cost or {})


_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16, "s4": 1, "u4": 1}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COMP_START = re.compile(
    r"^\s*(ENTRY\s+)?%?([\w\-.]+)\s*\(.*->.*\{\s*$")
_TRIP = re.compile(r'"known_trip_count":\s*\{\s*"n":\s*"?(\d+)"?')
_OPLINE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w\-.]+\s*=\s*(.*)$")
_KIND = re.compile(r"^(?:\([^)]*\)|(?:[a-z0-9]+\[[0-9,]*\])\S*)\s+"
                   r"([a-z0-9\-]+)")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_SKIP_BYTES = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "while", "conditional", "call", "iota",
               "after-all", "partition-id", "replica-id"}


def _bytes_of(text: str) -> int:
    tot = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        tot += n * _DTYPE_BYTES[dt]
    return tot


_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\-.]+)\s*=\s*(\([^)]+\)|[a-z0-9]+\[[0-9,]*\])")
_OPERANDS = re.compile(r"%([\w\-.]+)")


def _dims(shape_text: str) -> list[int]:
    m = _SHAPE_RE.search(shape_text)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _elems(shape_text: str) -> int:
    n = 1
    for d in _dims(shape_text):
        n *= d
    return n


def _dot_flops(line: str, symtab: dict) -> float:
    m = re.match(r".*?=\s*([a-z0-9]+\[[0-9,]*\])\S*\s+dot\(([^)]*)\)", line)
    if not m:
        return 0.0
    out = _elems(m.group(1))
    cm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
    ops = _OPERANDS.findall(m.group(2))
    if cm is None or not ops or ops[0] not in symtab:
        return 0.0
    lhs = _dims(symtab[ops[0]])
    contract = 1
    for ci in cm.group(1).split(","):
        if ci and int(ci) < len(lhs):
            contract *= lhs[int(ci)]
    return 2.0 * out * contract


def _conv_flops(line: str, symtab: dict) -> float:
    m = re.match(r".*?=\s*([a-z0-9]+\[[0-9,]*\])\S*\s+convolution\(([^)]*)\)",
                 line)
    if not m:
        return 0.0
    res = _elems(m.group(1))
    ops = _OPERANDS.findall(m.group(2))
    if len(ops) < 2 or ops[1] not in symtab:
        return 0.0
    return 2.0 * res * max(_elems(symtab[ops[1]]), 1)


@dataclasses.dataclass
class Totals:
    flops: float = 0.0
    bytes: float = 0.0
    attn_fallback_bytes: float = 0.0   # ops inside named_scope
                                       # "flashattn_fallback" — replaced by
                                       # the fused Pallas kernel on TPU
    coll: dict = dataclasses.field(
        default_factory=lambda: defaultdict(
            lambda: {"count": 0.0, "link_bytes": 0.0}))

    def add(self, o: "Totals", mult: float = 1.0):
        self.flops += o.flops * mult
        self.bytes += o.bytes * mult
        self.attn_fallback_bytes += o.attn_fallback_bytes * mult
        for k, v in o.coll.items():
            self.coll[k]["count"] += v["count"] * mult
            self.coll[k]["link_bytes"] += v["link_bytes"] * mult

    @property
    def coll_link_bytes(self):
        return sum(v["link_bytes"] for v in self.coll.values())


def split_computations(hlo: str):
    comps, entry = {}, None
    cur, buf = None, []
    for line in hlo.splitlines():
        if cur is None:
            m = _COMP_START.match(line)
            if m and line.rstrip().endswith("{"):
                if m.group(1):
                    entry = m.group(2)
                cur = m.group(2)
                buf = []
        else:
            if line.strip() == "}":
                comps[cur] = buf
                cur = None
            else:
                buf.append(line)
    return comps, entry


def analyze(hlo: str) -> dict:
    comps, entry = split_computations(hlo)
    if entry is None:
        entry = next((n for n in comps if "main" in n), None) \
            or next(iter(comps))
    memo: dict[tuple, Totals] = {}
    _tagged: dict[str, bool] = {}

    def comp_tagged(name: str) -> bool:
        """A computation counts as attention-fallback if any of its ops
        carries the named_scope tag (fusions erase per-op metadata at the
        call site, so we look inside)."""
        if name not in _tagged:
            _tagged[name] = any("flashattn_fallback" in l
                                for l in comps.get(name, ()))
        return _tagged[name]

    symtabs: dict[str, dict] = {}

    def symtab(name: str) -> dict:
        if name not in symtabs:
            tab = {}
            for line in comps.get(name, ()):
                dm = _DEF_RE.match(line)
                if dm:
                    tab[dm.group(1)] = dm.group(2)
            symtabs[name] = tab
        return symtabs[name]

    def _io_bytes(line: str, body: str, tab: dict) -> int:
        """Operand + result bytes of one op (fusion-boundary HBM traffic).

        In-place ops get realistic accounting instead of full-buffer I/O:
        dynamic-update-slice ~ 2x update bytes; dynamic-slice ~ 2x result;
        scatter ~ 2x updates + indices (XLA executes these in place)."""
        result = _bytes_of(body.split("(")[0])
        am = re.search(r"\(([^)]*)\)", body)
        operands = []
        if am:
            operands = [_bytes_of(tab[op]) for op in
                        _OPERANDS.findall(am.group(1)) if op in tab]
        if "dynamic-update-slice" in line or "dynamic_update_slice" in line:
            small = [b for b in operands if 0 < b < result]
            upd = max(small) if small else min(operands, default=result)
            return 2 * upd
        if "dynamic-slice" in line or "dynamic_slice" in line:
            return 2 * result
        if " scatter(" in body or "scatter-add" in line:
            small = sorted(b for b in operands if b < result) or [result]
            return 2 * small[-1] + sum(small[:-1])
        return result + sum(operands)

    def comp_totals(name: str, in_fusion: bool, stack=()) -> Totals:
        key = (name, in_fusion)
        if key in memo:
            return memo[key]
        if name in stack or name not in comps:
            return Totals()
        tot = Totals()
        tab = symtab(name)
        for line in comps[name]:
            m = _OPLINE.match(line)
            if not m:
                continue
            body = m.group(1)
            km = _KIND.match(body)
            kind = km.group(1) if km else ""

            if kind == "fusion":
                cm = re.search(r"calls=%?([\w\-.]+)", line)
                if cm:
                    tot.add(comp_totals(cm.group(1), True,
                                        stack + (name,)))
                if not in_fusion:
                    nbytes = _io_bytes(line, body, tab)
                    tot.bytes += nbytes
                    if "flashattn_fallback" in line or \
                            (cm and comp_tagged(cm.group(1))):
                        tot.attn_fallback_bytes += nbytes
                continue
            if kind == "while":
                bm = re.search(r"body=%?([\w\-.]+)", line)
                cm = re.search(r"condition=%?([\w\-.]+)", line)
                tm = _TRIP.search(line)
                mult = float(tm.group(1)) if tm else 1.0
                if bm:
                    tot.add(comp_totals(bm.group(1), False,
                                        stack + (name,)), mult)
                if cm:
                    tot.add(comp_totals(cm.group(1), False,
                                        stack + (name,)), mult + 1)
                continue
            if kind == "conditional":
                for g in re.findall(r"branch_computations=\{([^}]*)\}", line):
                    for cn in g.split(","):
                        tot.add(comp_totals(cn.strip().lstrip("%"),
                                            in_fusion, stack + (name,)))
                continue
            if kind in ("call", "async-start"):
                cm = re.search(r"(?:to_apply|calls)=%?([\w\-.]+)", line)
                if cm:
                    tot.add(comp_totals(cm.group(1), in_fusion,
                                        stack + (name,)))
                continue

            if kind == "dot":
                tot.flops += _dot_flops(line, tab)
            elif kind == "convolution":
                tot.flops += _conv_flops(line, tab)

            coll = next((c for c in COLLECTIVES
                         if kind.startswith(c) and not
                         kind.endswith("-done")), None)
            if coll:
                result = body.split("(")[0]
                nbytes = _bytes_of(result)
                if kind.endswith("-start") and result.startswith("("):
                    nbytes //= 2          # async tuple repeats the buffer
                factor = 2.0 if coll == "all-reduce" else 1.0
                tot.coll[coll]["count"] += 1
                tot.coll[coll]["link_bytes"] += factor * nbytes

            if not in_fusion and kind not in _SKIP_BYTES:
                nbytes = _io_bytes(line, body, tab)
                tot.bytes += nbytes
                if "flashattn_fallback" in line:
                    tot.attn_fallback_bytes += nbytes
        memo[key] = tot
        return tot

    t = comp_totals(entry, False)
    return {
        "flops": t.flops,
        "hbm_bytes": t.bytes,
        "attn_fallback_bytes": t.attn_fallback_bytes,
        "collective_link_bytes": t.coll_link_bytes,
        "collectives": {k: dict(v) for k, v in t.coll.items()},
    }
