"""AIGER round-trip: parse(write(aig)) preserves structure and semantics.

Acceptance criterion: csa/booth at 8/16/32 bits, binary and ASCII
formats, reproduce simulation semantics; node counts and construction
labels survive the trip.
"""
from __future__ import annotations

import io

import numpy as np
import pytest

from repro.core import aig as A
from repro.io import aiger


def _sim_vectors(aig: A.AIG, n: int = 64, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2, (aig.n_pi, n)).astype(bool)


@pytest.mark.parametrize("binary", [True, False], ids=["binary", "ascii"])
@pytest.mark.parametrize("family", ["csa", "booth"])
@pytest.mark.parametrize("bits", [8, 16, 32])
def test_roundtrip_preserves_semantics(family, bits, binary):
    aig = A.make_design(family, bits)
    back = aiger.loads(aiger.dumps(aig, binary=binary))
    assert back.num_nodes == aig.num_nodes
    assert back.n_pi == aig.n_pi
    assert len(back.pos) == len(aig.pos)
    # generated designs keep PIs-then-ANDs-then-POs layout, so labels
    # line up element-wise
    assert np.array_equal(back.label, aig.label)
    v = _sim_vectors(aig)
    assert np.array_equal(back.simulate(v), aig.simulate(v))


def test_ascii_and_binary_parse_identically():
    aig = A.csa_multiplier(8)
    a = aiger.loads(aiger.dumps(aig, binary=False))
    b = aiger.loads(aiger.dumps(aig, binary=True))
    for field in ("kind", "fanin0", "fanin1", "label", "pos"):
        assert np.array_equal(getattr(a, field), getattr(b, field)), field


def test_mapped_and_mixed_decomp_roundtrip():
    aig = A.csa_multiplier(6, mixed_decomp=True, seed=3)
    back = aiger.loads(aiger.dumps(aig))
    v = _sim_vectors(aig)
    assert np.array_equal(back.simulate(v), aig.simulate(v))
    assert np.array_equal(back.label, aig.label)


def test_label_fallback_via_structural_detector():
    """Files without groot comments recover labels structurally."""
    aig = A.csa_multiplier(6)
    back = aiger.loads(aiger.dumps(aig, comments=False))
    assert (back.label == aig.label).mean() > 0.95
    # type-level labels (PI/PO) are always exact
    assert np.array_equal(back.label == A.LABEL_PI, aig.label == A.LABEL_PI)
    assert np.array_equal(back.label == A.LABEL_PO, aig.label == A.LABEL_PO)


def test_structural_hash_is_format_invariant():
    aig = A.booth_multiplier(8)
    h_obj = aiger.structural_hash(aig)
    h_ascii = aiger.structural_hash(aiger.dumps(aig, binary=False))
    h_bin = aiger.structural_hash(aiger.dumps(aig, binary=True, comments=False))
    assert h_obj == h_ascii == h_bin
    assert aiger.structural_hash(A.booth_multiplier(10)) != h_obj
    assert aiger.structural_hash(A.csa_multiplier(8)) != h_obj


def test_dump_load_file(tmp_path):
    aig = A.csa_multiplier(8)
    path = tmp_path / "csa8.aig"
    aiger.dump(aig, path)
    back = aiger.load(path)
    assert back.num_nodes == aig.num_nodes
    v = _sim_vectors(aig)
    assert np.array_equal(back.simulate(v), aig.simulate(v))


def test_rejects_malformed():
    with pytest.raises(aiger.AigerError):
        aiger.loads(b"not an aiger file\n")
    with pytest.raises(aiger.AigerError):
        aiger.loads(b"aag 1 1 1 0 0\n2\n")  # latches unsupported
    with pytest.raises(aiger.AigerError):
        aiger.loads(b"aag 2 1 0 1 1\n2\n4\n4 2 6\n")  # undefined var in AND


# ---------------------------------------------------------------------------
# Binary encoding: byte identity with the per-gate writer it replaced
# ---------------------------------------------------------------------------

def _reference_encode_leb(delta: int, out: bytearray) -> None:
    while delta >= 0x80:
        out.append((delta & 0x7F) | 0x80)
        delta >>= 7
    out.append(delta)


def _reference_binary_dumps(aig: A.AIG, comments: bool) -> bytes:
    """The gate-by-gate binary AIGER writer: the oracle for ``dumps``."""

    def to_lit(lit: int) -> int:
        if lit < 0:
            raise aiger.AigerError("constant literals are folded at build time; cannot export")
        return 2 * int(var[lit >> 1]) + (lit & 1)

    var, and_nodes = aiger._var_map(aig)
    n_and = len(and_nodes)
    m = aig.n_pi + n_and
    outputs = [to_lit(int(aig.fanin0[p])) for p in aig.pos]
    buf = bytearray()
    buf += b"aig %d %d 0 %d %d\n" % (m, aig.n_pi, len(outputs), n_and)
    for o in outputs:
        buf += b"%d\n" % o
    for k, node in enumerate(and_nodes):
        lhs = 2 * (aig.n_pi + 1 + k)
        r0 = to_lit(int(aig.fanin0[node]))
        r1 = to_lit(int(aig.fanin1[node]))
        rhs0, rhs1 = max(r0, r1), min(r0, r1)
        if rhs0 >= lhs:
            raise aiger.AigerError("AND fanins are not topologically ordered")
        _reference_encode_leb(lhs - rhs0, buf)
        _reference_encode_leb(rhs0 - rhs1, buf)
    if comments:
        buf += b"c\n"
        buf += b"groot-name %s\n" % aig.name.encode()
        buf += b"groot-labels %s\n" % aiger._label_string(aig, and_nodes).encode()
    return bytes(buf)


_DESIGNS = {
    **{f"{family}{bits}": (lambda f=family, b=bits: A.make_design(f, b))
       for family in ("csa", "booth") for bits in (8, 16, 32, 64)},
    "csa6_mixed": lambda: A.csa_multiplier(6, mixed_decomp=True, seed=3),
}


@pytest.mark.parametrize("comments", [True, False], ids=["comments", "bare"])
@pytest.mark.parametrize("design", sorted(_DESIGNS))
def test_binary_dumps_matches_per_gate_writer(design, comments):
    aig = _DESIGNS[design]()
    assert aiger.dumps(aig, binary=True, comments=comments) == \
        _reference_binary_dumps(aig, comments)


# 0, 1, then both sides of every group boundary: 127, 128, 16383, 16384, ...
_LEB_EDGES = (0, 1, *(2**(7 * k) + e for k in range(1, 8) for e in (-1, 0)), 2**56 - 1)


def _reference_leb(deltas) -> bytes:
    out = bytearray()
    for d in deltas:
        _reference_encode_leb(d, out)
    return bytes(out)


def test_encode_leb_at_group_boundaries():
    """Every group count from one to eight, at both ends.  A real design
    reaches 2**28 only past 2**27 nodes, so the widest deltas are checked
    on the encoder alone."""
    got = aiger._encode_leb(np.array(_LEB_EDGES, dtype=np.int64))
    assert got == _reference_leb(_LEB_EDGES)
    f = io.BytesIO(got)
    assert [aiger._decode_leb(f) for _ in _LEB_EDGES] == list(_LEB_EDGES)
    assert f.read() == b""
    for d in _LEB_EDGES:  # one delta alone picks its own word width
        assert aiger._encode_leb(np.array([d], dtype=np.int64)) == _reference_leb([d])
    assert aiger._encode_leb(np.zeros(0, dtype=np.int64)) == b""


def _wide_aig(n_pi: int, pairs) -> A.AIG:
    """PIs, then one AND per ``(d0, d1)`` whose binary AIGER deltas are
    exactly those, then one PO on the last AND.  With PIs before ANDs in
    node order, a node literal is its AIGER literal less 2."""
    n_and = len(pairs)
    num = n_pi + n_and + 1
    kind = np.full(num, A.PI, dtype=np.int8)
    kind[n_pi:n_pi + n_and] = A.AND
    kind[-1] = A.PO
    fanin0 = np.full(num, -3, dtype=np.int64)
    fanin1 = np.full(num, -3, dtype=np.int64)
    for k, (d0, d1) in enumerate(pairs):
        rhs0 = 2 * (n_pi + 1 + k) - d0
        fanin0[n_pi + k], fanin1[n_pi + k] = rhs0 - d1 - 2, rhs0 - 2
    fanin0[-1] = 2 * (n_pi + n_and - 1)
    label = np.full(num, A.LABEL_PI, dtype=np.int8)
    label[n_pi:n_pi + n_and] = A.LABEL_AND
    label[-1] = A.LABEL_PO
    return A.AIG(name="wide", kind=kind, fanin0=fanin0, fanin1=fanin1,
                 label=label, n_pi=n_pi, pos=np.array([num - 1], dtype=np.int64))


def test_binary_dumps_deltas_at_group_boundaries():
    pairs = [(1, 0), (127, 128), (128, 127), (16383, 16384), (16384, 16383),
             (2**21 - 1, 2**21), (2**21, 2**21 - 1), (2**21, 0)]
    aig = _wide_aig(2**21, pairs)
    data = aiger.dumps(aig, binary=True)
    assert data == _reference_binary_dumps(aig, comments=True)
    f = io.BytesIO(data)
    for _ in range(2):  # header, the PO line
        f.readline()
    got = [(aiger._decode_leb(f), aiger._decode_leb(f)) for _ in pairs]
    assert got == pairs
    back = aiger.loads(data)
    for field in ("kind", "fanin0", "fanin1", "label", "pos"):
        assert np.array_equal(getattr(back, field), getattr(aig, field)), field


@pytest.mark.parametrize("fault, match", [
    ("constant", "constant"),
    ("later_and", "topologically"),
    ("itself", "topologically"),
])
def test_binary_dumps_rejects_what_it_cannot_encode(fault, match):
    aig = A.csa_multiplier(6)
    and_nodes = np.where(aig.kind == A.AND)[0]
    node = int(and_nodes[3])
    fanin0 = aig.fanin0.copy()
    fanin0[node] = {"constant": A.CONST1, "later_and": 2 * int(and_nodes[-1]),
                    "itself": 2 * node}[fault]
    bad = A.AIG(name=aig.name, kind=aig.kind, fanin0=fanin0, fanin1=aig.fanin1,
                label=aig.label, n_pi=aig.n_pi, pos=aig.pos)
    with pytest.raises(aiger.AigerError, match=match):
        _reference_binary_dumps(bad, comments=False)
    with pytest.raises(aiger.AigerError, match=match):
        aiger.dumps(bad, binary=True)
    with pytest.raises(aiger.AigerError, match=match):
        aiger.structural_hash(bad)


@pytest.mark.parametrize("family, digest, size", [
    ("csa", "2d2746218e875a7d33ae1d52729fd9b1560f5d060f721c57f4e6588fccd3226f", 94125),
    ("booth", "d368daa1613fef14dc5d410a587839bb7ce801e525ba107114e9fe8e6b10c084", 91524),
])
def test_structural_digest_is_pinned(family, digest, size):
    """The service's dedup key and the streamed route's journal key: a
    change to these bytes orphans every cache and journal on disk."""
    assert aiger.structural_digest(A.make_design(family, 64)) == (digest, size)
