"""Plain reference of the verdict: adder extraction over predicted classes.

Given a multiplier AIG and a class per node, pair every predicted MAJ root
with the predicted XOR roots over the same input support into full and half
adders (Ciesielski et al.'s bit-flow extraction, as arXiv 2511.18297 §III-D
feeds it).  Coverage is the share of the true MAJ roots that are the carry of
an extracted adder.  Below 0.999 coverage the verdict is "inconclusive"; at or
above it, a design of at most 64 bits is simulated against the integer
product ("verified" or "falsified") and a wider one is "verified".  Imports
nothing of the system under test.
"""
from __future__ import annotations

import numpy as np

_AND = 1
LABEL_MAJ, LABEL_XOR = 1, 2
COVERAGE_BAR = 0.999
SIMULATE_BITS = 64


def _carry_support(f0, f1, kind, m: int) -> frozenset:
    """The input nodes {a, b, c} of a full-adder carry OR(ab, c*xor(a,b)),
    else the two fanins of a half-adder carry AND(a, b)."""
    u, v = int(f0[m]) >> 1, int(f1[m]) >> 1
    if f0[m] & 1 and f1[m] & 1 and kind[u] == _AND and kind[v] == _AND:
        for t1, t3 in ((u, v), (v, u)):
            a, b = int(f0[t1]) >> 1, int(f1[t1]) >> 1
            for xl, c in ((f0[t3], f1[t3]), (f1[t3], f0[t3])):
                xn = int(xl) >> 1
                if kind[xn] != _AND:
                    continue
                inner = int(f0[xn]) >> 1
                if kind[inner] != _AND:
                    continue
                if {int(f0[inner]) >> 1, int(f1[inner]) >> 1} == {a, b}:
                    return frozenset((a, b, int(c) >> 1))
    return frozenset((int(f0[m]) >> 1, int(f1[m]) >> 1))


def extract(design: dict, pred: np.ndarray) -> tuple[int, int, set]:
    """(full adders, half adders, carry roots of the extracted adders)."""
    kind, f0, f1 = design["kind"], design["fanin0"], design["fanin1"]
    is_and = kind == _AND
    xor_of: dict[frozenset, int] = {}
    for x in np.flatnonzero((pred == LABEL_XOR) & is_and):
        u = int(f0[x]) >> 1
        if kind[u] == _AND:
            xor_of[frozenset((int(f0[u]) >> 1, int(f1[u]) >> 1))] = int(x)
    full = half = 0
    carries: set = set()
    for m in np.flatnonzero((pred == LABEL_MAJ) & is_and):
        sup = _carry_support(f0, f1, kind, int(m))
        if len(sup) == 3:
            s = sorted(sup)
            for pair in ((s[0], s[1]), (s[0], s[2]), (s[1], s[2])):
                inner = xor_of.get(frozenset(pair))
                if inner is None:
                    continue
                (rest,) = sup - set(pair)
                if frozenset((inner, rest)) in xor_of:
                    full += 1
                    carries.add(int(m))
                    break
        elif sup in xor_of:
            half += 1
            carries.add(int(m))
    return full, half, carries


def simulate_ok(design: dict, bits: int, signed: bool, vectors: int = 256,
                seed: int = 0) -> bool:
    """Bit-parallel simulation (one Python int per node, one bit per vector)
    of random operands against the integer product modulo 2^(2 bits)."""
    rng = np.random.default_rng(seed)
    a = [int(v) for v in rng.integers(0, 2, (vectors, bits)) @ (1 << np.arange(bits, dtype=object))]
    b = [int(v) for v in rng.integers(0, 2, (vectors, bits)) @ (1 << np.arange(bits, dtype=object))]
    kind, f0, f1 = design["kind"], design["fanin0"], design["fanin1"]
    full = (1 << vectors) - 1
    val = [0] * len(kind)
    for i in range(bits):
        val[i] = sum(((a[k] >> i) & 1) << k for k in range(vectors))
        val[bits + i] = sum(((b[k] >> i) & 1) << k for k in range(vectors))
    lit = lambda l: val[l >> 1] ^ (full if l & 1 else 0)
    for i in np.flatnonzero(kind == _AND):
        val[i] = lit(int(f0[i])) & lit(int(f1[i]))
    out = [lit(int(f0[p])) for p in design["pos"]]
    mod = 1 << (2 * bits)
    for k in range(vectors):
        got = sum(((o >> k) & 1) << j for j, o in enumerate(out))
        x, y = a[k], b[k]
        if signed:
            x -= (x >> (bits - 1)) << bits
            y -= (y >> (bits - 1)) << bits
        if got != (x * y) % mod:
            return False
    return True


def verdict(design: dict, pred: np.ndarray, *, bits: int, signed: bool) -> dict:
    full, half, carries = extract(design, pred)
    is_and = design["kind"] == _AND
    true_majs = set(np.flatnonzero(design["label"] == LABEL_MAJ).tolist())
    coverage = len(carries & true_majs) / max(len(true_majs), 1)
    if coverage < COVERAGE_BAR:
        status = "inconclusive"
    elif bits > SIMULATE_BITS or simulate_ok(design, bits, signed):
        status = "verified"
    else:
        status = "falsified"
    return {
        "status": status,
        "n_adders": full + half,
        "n_xor_pred": int(((pred == LABEL_XOR) & is_and).sum()),
        "n_maj_pred": int(((pred == LABEL_MAJ) & is_and).sum()),
        "coverage": coverage,
        "nonlinear_terms_eliminated": 4 * full + half,
    }
