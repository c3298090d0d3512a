"""Compulsory bytes: the hand counts, and the footprints against the
regions the registry programs really read and write."""
import pytest

from bench.configs import npb_mg, pop_hdifft
from bench.yardstick import compulsory_bytes


def test_npb_mg_b_hand_count():
    # resid: V 256^3 + U 258^3 read, Rr 256^3 written; psinv: U 256^3 +
    # R 258^3 read, U 256^3 written
    inner, full = 256 ** 3 * 4, 258 ** 3 * 4
    assert compulsory_bytes(npb_mg.footprints(256), 4) == 4 * inner + 2 * full
    assert compulsory_bytes(npb_mg.footprints(256), 4) == 405_823_552


def test_pop_gx1v6_hand_count():
    # T and S over i = 1..319, j = 0..383; dn and dso over 318 x 382; 60
    # levels
    read, write = 60 * 319 * 384 * 4, 60 * 318 * 382 * 4
    got = compulsory_bytes(pop_hdifft.footprints(320, 384, 60), 4)
    assert got == 2 * read + 2 * write == 117_106_560


def _boxes(program):
    """Per array: the bounding box of every read, and of every write."""
    from repro.core.ir import expr_refs

    rng = program.ranges()
    boxes = {"read": {}, "write": {}}

    def see(kind, ref):
        lo_hi = []
        for s in ref.subs:
            lo, hi = rng[s.s]
            lo_hi.append((s.a * lo + int(s.b), s.a * hi + int(s.b)))
        old = boxes[kind].get(ref.name)
        if old:
            lo_hi = [(min(a, c), max(b, d)) for (a, b), (c, d)
                     in zip(old, lo_hi)]
        boxes[kind][ref.name] = lo_hi

    for st in program.body:
        see("write", st.lhs)
        for r in expr_refs(st.rhs):
            if r.subs:
                see("read", r)
    return {k: {n: tuple(b - a + 1 for a, b in box) for n, box in v.items()}
            for k, v in boxes.items()}


@pytest.mark.parametrize("n", [6, 10])
def test_npb_mg_footprints_match_the_programs(n):
    from repro.apps.paper_kernels import get_case

    want = npb_mg.footprints(n)
    for name in ("resid", "psinv"):
        box = _boxes(get_case(name, n + 2).program)
        # psinv reads U at its centre only, so its read box is the interior
        assert box == want[name], name


def test_pop_footprints_match_the_program():
    from repro.apps.paper_kernels import CASES

    box = _boxes(CASES["hdifft_gm"][0](14, 12).program)
    want = pop_hdifft.footprints(14, 12, 1)["hdifft_gm"]
    assert box == {k: {n: s[1:] for n, s in v.items()}
                   for k, v in want.items()}
