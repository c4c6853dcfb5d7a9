"""And-Inverter Graph builder shared by the design families.

A copy of the construction the verifier's own generators use, kept with the
benchmark so that traffic never comes from the system under test.  Literals
are ``2*node + inverted``; const-0 is -2 and const-1 is -1 and are folded
away at build time.  Node labels (PO=0, MAJ=1, XOR=2, AND=3, PI=4) are known
by construction: every XOR and MAJ root is made by the adder macros.
"""
from __future__ import annotations

import numpy as np

PI, AND, PO = 0, 1, 2
LABEL_PO, LABEL_MAJ, LABEL_XOR, LABEL_AND, LABEL_PI = 0, 1, 2, 3, 4
CONST0, CONST1 = -2, -1


def lit_not(lit: int) -> int:
    if lit == CONST0:
        return CONST1
    if lit == CONST1:
        return CONST0
    return lit ^ 1


class Builder:
    """Structurally hashed AIG construction with constant folding."""

    def __init__(self, name: str):
        self.name = name
        self.kind: list[int] = []
        self.fanin0: list[int] = []
        self.fanin1: list[int] = []
        self.label: list[int] = []
        self.pos: list[int] = []
        self.n_pi = 0
        self._strash: dict[tuple[int, int], int] = {}

    def add_pi(self) -> int:
        self.kind.append(PI)
        self.fanin0.append(-3)
        self.fanin1.append(-3)
        self.label.append(LABEL_PI)
        self.n_pi += 1
        return 2 * (len(self.kind) - 1)

    def add_and(self, a: int, b: int, label: int = LABEL_AND) -> int:
        if a == CONST0 or b == CONST0:
            return CONST0
        if a == CONST1:
            return b
        if b == CONST1:
            return a
        if a == b:
            return a
        if a == lit_not(b):
            return CONST0
        key = (min(a, b), max(a, b))
        node = self._strash.get(key)
        if node is not None:
            if label != LABEL_AND and self.label[node] == LABEL_AND:
                self.label[node] = label
            return 2 * node
        self.kind.append(AND)
        self.fanin0.append(key[0])
        self.fanin1.append(key[1])
        self.label.append(label)
        node = len(self.kind) - 1
        self._strash[key] = node
        return 2 * node

    def add_po(self, lit: int) -> None:
        if lit < 0:
            raise ValueError("a generated design has no constant output")
        self.kind.append(PO)
        self.fanin0.append(lit)
        self.fanin1.append(-3)
        self.label.append(LABEL_PO)
        self.pos.append(len(self.kind) - 1)

    def or_(self, a: int, b: int, label: int = LABEL_AND) -> int:
        return lit_not(self.add_and(lit_not(a), lit_not(b), label=label))

    def xor2(self, a: int, b: int) -> int:
        if a in (CONST0, CONST1) or b in (CONST0, CONST1):
            if a == CONST0:
                return b
            if a == CONST1:
                return lit_not(b)
            if b == CONST0:
                return a
            return lit_not(a)
        if a == b:
            return CONST0
        if a == lit_not(b):
            return CONST1
        n1 = self.add_and(a, b)
        n2 = self.add_and(lit_not(a), lit_not(b))
        return self.add_and(lit_not(n1), lit_not(n2), label=LABEL_XOR)

    def half_adder(self, a: int, b: int) -> tuple[int, int]:
        return self.xor2(a, b), self.add_and(a, b, label=LABEL_MAJ)

    def full_adder(self, a: int, b: int, c: int) -> tuple[int, int]:
        x_ab = self.xor2(a, b)
        s = self.xor2(x_ab, c)
        carry = self.or_(self.add_and(a, b), self.add_and(x_ab, c), label=LABEL_MAJ)
        return s, carry

    def compress(self, cols: list[list[int]]) -> list[list[int]]:
        """Carry-save 3:2 / 2:2 compression until each column holds <= 2."""
        while max(len(c) for c in cols) > 2:
            nxt: list[list[int]] = [[] for _ in range(len(cols) + 1)]
            for ci, col in enumerate(cols):
                i = 0
                while len(col) - i >= 3:
                    s, cy = self.full_adder(col[i], col[i + 1], col[i + 2])
                    nxt[ci].append(s)
                    nxt[ci + 1].append(cy)
                    i += 3
                if len(col) - i == 2:
                    s, cy = self.half_adder(col[i], col[i + 1])
                    nxt[ci].append(s)
                    nxt[ci + 1].append(cy)
                    i += 2
                nxt[ci].extend(col[i:])
            while nxt and not nxt[-1]:
                nxt.pop()
            cols = nxt
        return cols

    def ripple(self, cols: list[list[int]]) -> list[int]:
        """Ripple-carry addition of the two carry-save rows left."""
        out: list[int] = []
        carry = CONST0
        for col in cols:
            ops = list(col) + ([carry] if carry != CONST0 else [])
            if not ops:
                out.append(CONST0)
                carry = CONST0
            elif len(ops) == 1:
                out.append(ops[0])
                carry = CONST0
            elif len(ops) == 2:
                s, carry = self.half_adder(ops[0], ops[1])
                out.append(s)
            else:
                s, carry = self.full_adder(ops[0], ops[1], ops[2])
                out.append(s)
        if carry != CONST0:
            out.append(carry)
        return out

    def outputs(self, out: list[int], width: int) -> None:
        for k in range(width):
            self.add_po(out[k] if k < len(out) else CONST0)

    def build(self) -> dict:
        return {
            "name": self.name,
            "kind": np.asarray(self.kind, dtype=np.int8),
            "fanin0": np.asarray(self.fanin0, dtype=np.int64),
            "fanin1": np.asarray(self.fanin1, dtype=np.int64),
            "label": np.asarray(self.label, dtype=np.int8),
            "n_pi": self.n_pi,
            "pos": np.asarray(self.pos, dtype=np.int64),
        }
