"""Signed radix-4 Booth multipliers (two's complement): a MUX network of
partial products with conditional inversion and a "+1" correction, full sign
extension, then the same compressor tree and ripple adder as the CSA family
(arXiv 2511.18297's Booth family)."""
from __future__ import annotations

from families._builder import CONST0, Builder, lit_not

SIGNED = True


def build(bits: int) -> dict:
    if bits % 2:
        raise ValueError("a radix-4 Booth multiplier needs an even width")
    b = Builder(f"booth_mult_{bits}b")
    a_in = [b.add_pi() for _ in range(bits)]
    b_in = [b.add_pi() for _ in range(bits)]
    width = 2 * bits
    cols: list[list[int]] = [[] for _ in range(width)]

    def b_at(j: int) -> int:
        if j < 0:
            return CONST0
        return b_in[min(j, bits - 1)]

    for k in range(bits // 2):
        y0 = a_in[2 * k - 1] if k else CONST0
        y1 = a_in[2 * k]
        y2 = a_in[min(2 * k + 1, bits - 1)]
        one = b.xor2(y0, y1)
        two = b.add_and(b.xor2(y2, y1), lit_not(one))
        shift = 2 * k
        p_top = CONST0
        for j in range(bits + 1):
            p = b.xor2(b.or_(b.add_and(one, b_at(j)), b.add_and(two, b_at(j - 1))), y2)
            if shift + j < width:
                cols[shift + j].append(p)
            if j == bits:
                p_top = p
        for j in range(bits + 1, width - shift):
            cols[shift + j].append(p_top)
        cols[shift].append(y2)
    b.outputs(b.ripple(b.compress(cols)), width)
    return b.build()
