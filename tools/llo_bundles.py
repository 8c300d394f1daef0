"""Read a TPU kernel's schedule out of the compiler's bundle dump.

    python tools/llo_bundles.py <dir>/*-<kernel name>.1-71-final_bundles.txt

The dump is what libtpu writes for a compile run with
`LIBTPU_INIT_ARGS="--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true"`
(recipe: .claude/skills/verify/SKILL.md): one VLIW bundle a line,
`<address> [<marker>:] <one '>' a loop depth> { op ;; op ... }`, the
first bundle of a loop body marked `LB:` and the bundle a branch around
a predicated region (a `pl.when`) lands on marked `PF:`.

Printed, for every loop body: its depth, first address, bundles (nested
loops' bundles counted once each, as the text has them) and how many of
the operations that set a vector kernel's pace it holds; then the same
for each part of the body between two `PF:` marks at the body's own
depth: the branches of a `pl.when` pair are separate parts. A bundle is
a cycle where vector work hides the MXU (940 MHz on a v5e)."""

from __future__ import annotations

import re
import sys
from typing import Dict, Iterable, List

# what a body is counted for: matrix pushes, vector stores and loads
# (the mnemonic: a store's `/*vst_source=*/` note is not a second store),
# accesses to spilled registers, cross-lane operations (a reduction is
# two: `v<op>.xlane` and the `vpop.xlane` of its result), lane permutes
OPS = ("vmatmul", "vst", "vld", "_spill]", ".xlane", "vperm")
_OP = {op: re.compile(r"(?<![\w.])" + op + r"\b" if op[0] == "v"
                      else re.escape(op)) for op in OPS}
_LINE = re.compile(
    r"^\s*(0x[0-9a-f]+|\d+)\s+([A-Z]{2})?\s*:\s*(>*)\s*\{(\})?")
_CUTS = ("PF", "PB", "CT")


def _count(text: str) -> Dict[str, int]:
    return {op: len(pattern.findall(text)) for op, pattern in _OP.items()}


def _add(total: Dict[str, int], more: Dict[str, int]) -> None:
    for op, n in more.items():
        total[op] = total.get(op, 0) + n


def bundles(lines: Iterable[str]) -> List[dict]:
    """The dump's bundles in order: {addr, mark, depth, ops}."""
    out, depth = [], 0
    for line in lines:
        m = _LINE.match(line)
        if not m:
            continue
        # an empty bundle (a branch's delay slot) carries no depth of
        # its own: it lies where the bundle before it lies
        if m.group(3) or not m.group(4):
            depth = len(m.group(3))
        out.append({"addr": m.group(1), "mark": m.group(2) or "",
                    "depth": depth, "ops": _count(line)})
    return out


def loop_bodies(rows: List[dict]) -> List[dict]:
    """Every loop body of the dump, in order of its first bundle:
    {depth, addr, bundles, ops, parts}; `parts` are the body's stretches
    between marks of a predicated region at the body's own depth, each
    {addr, bundles, ops}, and a body without such a mark has one."""
    bodies = []
    for i, row in enumerate(rows):
        if row["mark"] != "LB":
            continue
        depth = row["depth"]
        body = {"depth": depth, "addr": row["addr"], "bundles": 0,
                "ops": {}, "parts": []}
        for j in range(i, len(rows)):
            at = rows[j]
            if at["depth"] < depth or (
                    j > i and at["mark"] == "LB" and at["depth"] == depth):
                break
            if j == i or (at["mark"] in _CUTS and at["depth"] == depth):
                body["parts"].append({"addr": at["addr"], "bundles": 0,
                                      "ops": {}})
            for into in (body, body["parts"][-1]):
                into["bundles"] += 1
                _add(into["ops"], at["ops"])
        bodies.append(body)
    return bodies


def table(bodies: List[dict]) -> str:
    head = ("depth", "at", "bundles") + OPS
    out = ["  ".join(f"{h:>8}" for h in head)]

    def line(first, addr, part):
        cells = (first, addr, part["bundles"]) + tuple(
            part["ops"].get(op, 0) for op in OPS)
        return "  ".join(f"{c:>8}" for c in cells)

    for body in bodies:
        out.append(line(">" * body["depth"], body["addr"], body))
        if len(body["parts"]) > 1:
            out += [line("part", p["addr"], p) for p in body["parts"]]
    return "\n".join(out)


def main(argv: List[str]) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[1], encoding="utf-8", errors="replace") as fh:
        print(table(loop_bodies(bundles(fh))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
