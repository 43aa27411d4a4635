"""Pallas fused gate-segment sweep (interpret mode on CPU): parity with
the XLA compile_fn path on random circuits, and of a segment led by two
cross-tile 2 x 2s (PR 50) with the two segments it stands for."""

import numpy as np
import pytest

from qrack_tpu.layers.qcircuit import QCircuit
from qrack_tpu.models import qft as qftm
from qrack_tpu import matrices as mat
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.utils.rng import QrackRandom

from test_pallas_window import (_su, kernel_operands, lead_in_numpy,
                                random_ket, riders, run_window)


def through_window_kernel(circ, n, planes, block_pow):
    """The whole circuit as one window of the Pallas kernel under the
    interpreter: what the engine's flush hands the chip."""
    ops = fu.lower_gates(circ.gates)
    wfn = pk.make_window_fn(n, fu.structure_of(ops), block_pow=block_pow,
                            interpret=True)
    return np.asarray(wfn(planes, *kernel_operands(
        ops, planes.dtype, wfn.block_pow)))


def build_circuit(n, seed, gates=30):
    rng = QrackRandom(seed)
    c = QCircuit(n)
    for _ in range(gates):
        kind = rng.randint(0, 5)
        t = rng.randint(0, n)
        if kind == 0:
            c.append_1q(t, mat.H2)
        elif kind == 1:
            c.append_1q(t, mat.T2)
        elif kind == 2:
            c.append_1q(t, np.asarray(mat.X2))
        elif kind == 3:
            ctl = rng.randint(0, n)
            if ctl != t:
                c.append_ctrl((ctl,), t, np.diag([1.0, -1.0 + 0j]), 1)  # CZ
        else:
            ctl = rng.randint(0, n)
            if ctl != t:
                c.append_ctrl((ctl,), t, np.asarray(mat.X2), 1)  # CNOT
    return c


@pytest.mark.parametrize("seed", [3, 4])
def test_pallas_segments_match_xla(seed):
    import jax

    n = 8
    c = build_circuit(n, seed)
    planes = qftm.basis_planes(n, 5)
    want = np.asarray(jax.jit(c.compile_fn(n))(planes))
    # tiny tiles force multi-block grids AND high-target bridges
    for bp in (4, 6, n):
        got = through_window_kernel(c, n, planes, bp)
        np.testing.assert_allclose(got, want, atol=3e-5, err_msg=f"bp={bp}")


def test_pallas_high_diag_and_controls():
    import jax

    n = 7
    c = QCircuit(n)
    c.append_1q(0, mat.H2)
    c.append_1q(n - 1, mat.H2)
    c.append_ctrl((n - 1,), 0, np.diag([1.0, 1j]), 1)   # high control, diag
    c.append_1q(n - 1, mat.T2)                          # high diag target
    c.append_ctrl((0,), 1, np.asarray(mat.X2), 1)
    planes = qftm.basis_planes(n, 0)
    want = np.asarray(jax.jit(c.compile_fn(n))(planes))
    got = through_window_kernel(c, n, planes, 4)
    np.testing.assert_allclose(got, want, atol=3e-5)


# ---------------- fused compressed-ket kernels ----------------
# (ops/pallas_turboquant.py: dequant -> gate -> requant in one pass)


def test_tq_pallas_matches_xla_path(monkeypatch):
    """QRACK_USE_PALLAS=1 routes compressed gates through the fused
    kernel (interpret mode on CPU): state parity with the XLA chunk
    programs across generic/diagonal/controlled/cross-tile gates."""
    import numpy as np

    from qrack_tpu.engines.turboquant import QEngineTurboQuant
    from qrack_tpu.utils.rng import QrackRandom

    def fidelity(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real
                                          * np.vdot(b, b).real)

    # kernel-parity test: pin per-gate dispatch on BOTH builds (the
    # pallas path never fuses, and windowed recompression rounds int16
    # codes differently enough to nick the 1e-9 fidelity bar)
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "1")

    def build(use_pallas):
        if use_pallas:
            monkeypatch.setenv("QRACK_USE_PALLAS", "1")
        else:
            monkeypatch.delenv("QRACK_USE_PALLAS", raising=False)
        q = QEngineTurboQuant(8, bits=16, chunk_qb=5, block_pow=2,
                              rng=QrackRandom(70), rand_global_phase=False)
        # small tile so cross-TILE routing (target >= tile) is exercised
        q._PALLAS_TILE_POW = 4
        for i in range(8):
            q.H(i)
        q.CNOT(0, 3)        # generic inside tile
        q.T(2)              # diag inside tile
        q.CZ(1, 6)          # diag: control low, target above tile
        q.CNOT(6, 1)        # control above tile, target low (pallas)
        q.RZ(0.37, 7)       # diag above tile
        q.CNOT(0, 7)        # generic above tile -> XLA pair path
        q.RY(0.8, 2)
        return q.GetQuantumState()

    a = build(False)
    b = build(True)
    assert fidelity(a, b) > 1 - 1e-9


def test_tq_pallas_untouched_tiles_exact(monkeypatch):
    """Tiles failing the high-control test keep their codes bit-for-bit
    through the fused kernel (the XLA path's exactness contract)."""
    import numpy as np

    from qrack_tpu.engines.turboquant import QEngineTurboQuant
    from qrack_tpu.utils.rng import QrackRandom

    monkeypatch.setenv("QRACK_USE_PALLAS", "1")
    q = QEngineTurboQuant(7, bits=8, chunk_qb=4, block_pow=2,
                          rng=QrackRandom(71), rand_global_phase=False)
    q._PALLAS_TILE_POW = 4
    for i in range(7):
        q.H(i)
    before = np.asarray(q._codes).copy()
    # control on qubit 6 (above the 16-amp tile): half the tiles must
    # stay untouched exactly
    q.CNOT(6, 1)
    after = np.asarray(q._codes)
    T = 1 << 4
    rows_per_tile = T // 4
    tiles = before.shape[0] // rows_per_tile
    untouched = 0
    for t in range(tiles):
        sl = slice(t * rows_per_tile, (t + 1) * rows_per_tile)
        # tile t covers amplitudes with bit6 = (t >> 2) & 1 at tile_pow 4
        if ((t << 4) >> 6) & 1 == 0:
            assert np.array_equal(before[sl], after[sl]), t
            untouched += 1
    assert untouched == tiles // 2


# ---------------------------------------------------------------------------
# two leads a launch (PR 50): a cross-tile inv/gen that directly follows
# a bare cross-tile inv/gen on another qubit joins its segment
# (plan_window; the planner's cases are tests/test_pallas_window.py's),
# whose orbits are the four tiles over both targets; a member computes
# the first lead's row on the two tiles across the second's bit and the
# second's row over those: the arithmetic of two launches, in their
# order, in one sweep.
# ---------------------------------------------------------------------------

# (width, block_pow): sixteen tiles, on the flat tile and the dense
PAIR_SHAPES = [(11, 7), (14, 10)]
PAIR_KINDS = [("gen", "gen"), ("gen", "inv"), ("inv", "gen")]


def _pair_targets(n, bp):
    """The two leads' targets: on adjacent bits of the tile id, on its
    lowest and its highest, and the second lead's below the first's."""
    return {"adjacent": (bp, bp + 1), "apart": (bp, n - 1),
            "descending": (n - 1, bp + 1)}


def _pair_masks(n, bp, targets, control):
    """``[(cmask, cval), ...]`` of the two leads: no control; a bit
    inside the tile (the second lead's an anti-control); the highest
    qubit above the tile that is neither target; each lead controlled by
    the other's target (the first an anti-control: it acts where the
    second's bit is 0), which no composed 4 x 4 of the two gets right."""
    first, second = targets
    if control == "none":
        return [(0, 0), (0, 0)]
    if control == "tile":
        return [(1 << 1, 1 << 1), (1 << 2, 0)]
    if control == "above":
        high = 1 << [q for q in range(bp, n) if q not in targets][-1]
        return [(high | 1, high | 1), (high, high)]
    assert control == "other"
    return [(1 << second, 0), (1 << first, 1 << first)]


def _pair_of_leads(n, bp, layout, kinds, control, seed=5):
    rng = np.random.default_rng(seed)
    targets = _pair_targets(n, bp)[layout]
    return [fu.FusedOp(kind, target, cmask, cval,
                       _su(rng, 2) if kind == "gen" else
                       np.fliplr(np.diag(np.exp(1j * rng.uniform(0, 6, 2)))))
            for kind, target, (cmask, cval)
            in zip(kinds, targets, _pair_masks(n, bp, targets, control))]


def _pair_cases():
    cases = []
    for n, bp in PAIR_SHAPES:
        for layout in _pair_targets(n, bp):
            for kinds in PAIR_KINDS:
                for control in ("none", "tile", "above", "other"):
                    for behind in (False, True):
                        cases.append(pytest.param(
                            n, bp, layout, kinds, control, behind,
                            id=f"w{n}-bp{bp}-{layout}-{'-'.join(kinds)}-"
                               f"{control}" + ("-riders" if behind else "-bare")))
    return cases


def _dense_2x2(ket, op, n):
    """A controlled 2 x 2 on a complex128 ket, index by index."""
    idx = np.arange(1 << n)
    bit = 1 << op.target
    b = (idx >> op.target) & 1
    m = np.asarray(op.m)
    new = m[b, 0] * ket[idx & ~bit] + m[b, 1] * ket[idx | bit]
    return np.where((idx & op.cmask) == op.cval, new, ket)


@pytest.mark.parametrize("n,bp,layout,kinds,control,behind", _pair_cases())
def test_a_pair_of_leads_is_two_launches_bit_for_bit(n, bp, layout, kinds,
                                                     control, behind):
    """One launch led by both against the first lead's launch and then
    the second's (with the riders behind it): the same bits, but for the
    sign of a zero (a launch's cast writes ``+ 0.0``, which a value that
    stays in VMEM between the two leads does not pass); against the two
    leads in numpy, one IEEE operation at a time; and against the dense
    complex128 reference to float32 rounding."""
    leads = _pair_of_leads(n, bp, layout, kinds, control)
    after = riders(n, bp) if behind else []
    segment, = pk.plan_window(fu.structure_of(leads + after), bp)
    assert [slot[0] for slot in segment["leads"]] == [0, 1]
    assert segment["xgen"] == segment["leads"][0]
    assert pk.segment_kernel_name(segment, bp) == pk.CROSS_KERNEL_NAME
    assert pk.plan_counts(fu.structure_of(leads + after), bp)[::3] == (1, 1)
    ket = random_ket(np.random.default_rng(n + bp), n)
    got = run_window(n, bp, leads + after, ket, donate=True)

    one_by_one = run_window(n, bp, leads[:1], ket, donate=False)
    assert len(pk.plan_window(fu.structure_of(leads[1:] + after), bp)) == 1
    one_by_one = run_window(n, bp, leads[1:] + after, one_by_one, donate=True)
    assert np.array_equal(got, one_by_one), \
        float(np.max(np.abs(got - one_by_one)))

    want = lead_in_numpy(lead_in_numpy(ket, leads[0], n), leads[1], n)
    if after:
        want = run_window(n, bp, after, want, donate=True)
    assert np.array_equal(got, want), float(np.max(np.abs(got - want)))

    if not after:
        dense = (ket[0] + 1j * ket[1]).astype(np.complex128)
        for op in leads:
            dense = _dense_2x2(dense, op, n)
        assert np.max(np.abs(got[0] + 1j * got[1] - dense)) < 1e-6
