"""Unsigned carry-save-array multipliers: AND partial products, a Wallace-style
3:2 / 2:2 compressor tree, then a ripple-carry adder (arXiv 2511.18297's
CSA family)."""
from __future__ import annotations

from families._builder import Builder

SIGNED = False


def build(bits: int) -> dict:
    b = Builder(f"csa_mult_{bits}b")
    a_in = [b.add_pi() for _ in range(bits)]
    b_in = [b.add_pi() for _ in range(bits)]
    cols: list[list[int]] = [[] for _ in range(2 * bits)]
    for i in range(bits):
        for j in range(bits):
            cols[i + j].append(b.add_and(a_in[i], b_in[j]))
    b.outputs(b.ripple(b.compress(cols)), 2 * bits)
    return b.build()
