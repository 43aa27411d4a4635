"""Placement-table remapping on the 8-device virtual CPU mesh.

The communication-minimizing remap layer (parallel/pager.py placement
table + ops/fusion.py plan_remaps) must stay invisible to every
logical-level contract: state parity with the CPU oracle under the full
fuzz vocabulary, Swap/MetaSwap on any table, checkpoint round-trips
that carry a non-identity table, and elastic shrink mid-remapped-span.
The accounting tests pin the headline claim: ascending-gen-order
circuits (IQFT) ship exactly HALF the exchange bytes under the planner
(docs/PERFORMANCE.md derives why descending-order QFT cannot exceed
2g/(g+1) with per-window prologues — the bound the <= assertion
documents)."""

import numpy as np
import pytest

from qrack_tpu import QEngineCPU, create_quantum_interface
from qrack_tpu import telemetry as tele
from qrack_tpu.ops import fusion as fu
from qrack_tpu.parallel.pager import QPager
from qrack_tpu.utils.rng import QrackRandom

from test_fuzz_api import N, _ops


@pytest.fixture(autouse=True)
def _tele_clean():
    yield
    tele.disable()
    tele.reset()


def _fidelity(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return float(abs(np.vdot(a, b)) ** 2 / (np.vdot(a, a).real
                                            * np.vdot(b, b).real))


def _op_skip_setbit(rng):
    # SetBit measures: cross-stack rng streams legitimately diverge on
    # measuring ops, so the soaks and this fuzz both re-roll it
    while True:
        name, args = _ops(rng)
        if name != "SetBit":
            return name, args


# ---------------------------------------------------------------------------
# fuzz parity: the whole non-measuring op vocabulary on a remap-on pager
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_pages", [4, 8])   # the cells' layout, and 3 page bits
@pytest.mark.parametrize("window", [1, 32])
@pytest.mark.parametrize("trial", range(3))
def test_fuzz_parity_remap_on(trial, window, n_pages, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", str(window))
    rng = np.random.Generator(np.random.PCG64(7000 + trial))
    o = QEngineCPU(N, rng=QrackRandom(trial), rand_global_phase=False)
    s = create_quantum_interface("pager", N, n_pages=n_pages, remap="on",
                                 rng=QrackRandom(trial),
                                 rand_global_phase=False)
    for step in range(25):
        name, args = _op_skip_setbit(rng)
        getattr(o, name)(*args)
        getattr(s, name)(*args)
        if rng.integers(0, 8) == 0:      # mid-stream reads flush windows
            qb = int(rng.integers(0, N))
            assert abs(o.Prob(qb) - s.Prob(qb)) < 3e-5, (trial, step, name)
    f = _fidelity(o.GetQuantumState(), s.GetQuantumState())
    assert f > 1 - 1e-6, (trial, window, n_pages, f)


# ---------------------------------------------------------------------------
# non-identity tables under structural ops
# ---------------------------------------------------------------------------

def _force_nonid(o, p):
    """Drive both engines through a window whose hot paged targets make
    the planner fire, leaving ``p`` with a non-identity table."""
    for eng in (o, p):
        eng.SetPermutation(0b1011001)
        L = 4  # QPager(7, n_pages=8)
        eng.H(L)
        eng.H(L + 1)
        eng.H(L + 2)
        eng.RY(0.3, 1)
    p.GetAmplitude(0)  # read boundary: flush the fused window
    assert p._map_nonid()


def test_swap_meta_swap_on_nonidentity_table():
    n = 7
    o = QEngineCPU(n, rng=QrackRandom(9), rand_global_phase=False)
    p = QPager(n, rng=QrackRandom(9), rand_global_phase=False,
               n_pages=8, remap="on")
    _force_nonid(o, p)
    for eng in (o, p):
        eng.Swap(5, 6)      # page-page under SOME table state
        eng.Swap(0, 5)      # mixed local/global transposition
        eng.ISwap(2, 4)
        eng.CNOT(6, 0)
        eng.Swap(1, 2)      # local-local
    np.testing.assert_allclose(p.GetQuantumState(), o.GetQuantumState(),
                               atol=3e-5)


def test_checkpoint_roundtrip_nonidentity_table(tmp_path):
    from qrack_tpu.checkpoint import load_state, save_state

    n = 7
    o = QEngineCPU(n, rng=QrackRandom(11), rand_global_phase=False)
    p = QPager(n, rng=QrackRandom(11), rand_global_phase=False,
               n_pages=8, remap="on")
    _force_nonid(o, p)
    path = str(tmp_path / "remapped.qckpt")
    save_state(p, path)
    r = load_state(path)
    # the table travels with the pages: raw physical shards + qmap meta
    assert r._map_nonid()
    assert r._qmap == p._qmap
    assert np.array_equal(np.asarray(r.GetQuantumState()),
                          np.asarray(p.GetQuantumState()))
    # and the restored stack CONTINUES correctly from the mapped layout
    for eng in (o, p, r):
        eng.CNOT(5, 1)
        eng.T(6)
        eng.H(2)
    want = np.asarray(o.GetQuantumState())
    for eng in (p, r):
        np.testing.assert_allclose(eng.GetQuantumState(), want, atol=3e-5)


def test_shrink_mid_remapped_span_resets_table():
    n = 7
    o = QEngineCPU(n, rng=QrackRandom(13), rand_global_phase=False)
    p = QPager(n, rng=QrackRandom(13), rand_global_phase=False,
               n_pages=8, remap="on")
    _force_nonid(o, p)
    p.shrink_pages()
    # the repage gathers the LOGICAL view, so the table must reset
    assert p.n_pages == 4 and not p._map_nonid()
    for eng in (o, p):
        eng.H(5)
        eng.CZ(4, 6)
        eng.CNOT(6, 0)
    np.testing.assert_allclose(p.GetQuantumState(), o.GetQuantumState(),
                               atol=3e-5)


# ---------------------------------------------------------------------------
# exchange accounting: the 2x headline and its honest bound
# ---------------------------------------------------------------------------

def _iqft_bytes(width, n_pages, remap_mode, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "32")
    tele.reset()
    tele.enable()
    q = QPager(width, rng=QrackRandom(5), rand_global_phase=False,
               n_pages=n_pages, remap=remap_mode)
    q.SetPermutation(777)
    q.IQFT(0, width)
    _ = q.GetAmplitude(0)  # flush; host fetch rides a SEPARATE counter
    c = tele.snapshot()["counters"]
    tele.disable()
    tele.reset()
    return c


def test_iqft_exchange_bytes_halved(monkeypatch):
    """w10 / 8 pages: the ascending-gen IQFT lets every hot paged target
    remap against a gen-done local, so the planner ships exactly half
    the bytes of the pair-exchange path (3 x nb/2 vs 3 x nb)."""
    off = _iqft_bytes(10, 8, "off", monkeypatch)
    auto = _iqft_bytes(10, 8, "auto", monkeypatch)
    ob = off.get("exchange.pager.bytes", 0)
    ab = auto.get("exchange.pager.bytes", 0)
    assert ab > 0 and ob >= 2 * ab, (ob, ab)
    # the remaps rode fused-window prologues, not separate dispatches
    assert auto.get("remap.pager.windows", 0) >= 1
    assert auto.get("remap.pager.pairs", 0) >= 3
    assert auto.get("exchange.pager.global_2x2", 0) == 0


def _circuit_ops(width, kind):
    """The registers.py gate streams as logical FusedOps (H -> gen,
    controlled phase -> cphase; payloads are placement-irrelevant)."""
    eye = np.eye(2, dtype=np.complex128)
    ops = []
    for i in range(width):
        if kind == "iqft":
            for j in range(i):
                ops.append(fu.FusedOp("cphase", i, 1 << (i - (j + 1)),
                                      1 << (i - (j + 1)), eye))
            ops.append(fu.FusedOp("gen", i, 0, 0, eye))
        else:  # qft: descending-gen order
            h = width - 1 - i
            for j in range(i):
                ops.append(fu.FusedOp("cphase", h + 1 + j, 1 << h,
                                      1 << h, eye))
            ops.append(fu.FusedOp("gen", h, 0, 0, eye))
    return ops


def _account(ops, width, L, window, remap_on):
    """Replay the _dispatch_ops cost accounting host-side: window at a
    time, prologue swaps priced by the lowering's own accounting twin
    (ops/sharded.py exchange_cost — mirrors _tele_remap exactly),
    translated gens on paged targets at nb — exact at any width (pure
    arithmetic, no state allocated)."""
    from qrack_tpu.ops import sharded as shb

    nb = 2 * (1 << width) * 4  # f32 planes
    qmap = list(range(width))
    total = 0.0
    pairs = 0
    for s in range(0, len(ops), window):
        win = ops[s:s + window]
        rest = [("gen" if op.kind in ("gen", "inv") else "diag", op.target)
                for op in ops[s + window:]]
        if remap_on:
            swaps, qmap = fu.plan_remaps(win, L, qmap, rest)
            pairs += len(swaps)
            total += shb.exchange_cost(L, width - L, swaps) * nb
        for op in fu.translate_ops(win, qmap):
            if op.kind in ("gen", "inv") and op.target >= L:
                total += nb
    return total, pairs


def test_w26_iqft_accounting_batched_collective():
    """The acceptance-scale claim without the 2 GiB ket: w26 on 16
    pages (k=4).  The fixed placement pays a whole-state exchange per
    paged qubit; the batched collective ships all four home in one
    exchange at (1 - 2^-4) x nb."""
    w, L = 26, 22
    ops = _circuit_ops(w, "iqft")
    nb = 2 * (1 << w) * 4
    off, _ = _account(ops, w, L, 16, remap_on=False)
    batch, b_pairs = _account(ops, w, L, 16, remap_on=True)
    assert off == 4 * nb
    assert b_pairs == 4 and batch == (1 - 2.0 ** -4) * nb, (batch, b_pairs)


def test_w26_qft_accounting_delivery_ratio():
    """Descending-gen QFT: every remap victim still owes a gen, so a
    prologue priced pair by pair is bound at 2g/(g+1) and never fires
    (remap-off is 3nb at w26/8 pages).  The batched collective breaks
    the bound: two k=3 batches (hot trio in window 1, pay-back trio once
    its victims are gen-done) ship 2 x (1 - 2^-3) x nb = 1.75nb — a
    12/7 ~ 1.71x delivery ratio vs remap-off, >= 1.6x required."""
    w, L = 26, 23
    ops = _circuit_ops(w, "qft")
    nb = 2 * (1 << w) * 4
    off, _ = _account(ops, w, L, 16, remap_on=False)
    batch, _ = _account(ops, w, L, 16, remap_on=True)
    assert off == 3 * nb, off
    assert batch == 2 * (1 - 2.0 ** -3) * nb, batch
    assert off / batch >= 1.6, (off, batch)


# ---------------------------------------------------------------------------
# measured batched collective: telemetry bytes on a real pager, driven
# through QCircuit.Run so the planner sees the full-circuit lookahead
# ---------------------------------------------------------------------------

def _iqft_qcircuit(width):
    """registers.py IQFT gate order as a QCircuit (ascending-gen:
    cphases then H per target) — Run() primes the fuser lookahead."""
    from qrack_tpu.layers.qcircuit import QCircuit

    h = np.array([[1, 1], [1, -1]], dtype=np.complex128) / np.sqrt(2)
    c = QCircuit(width)
    for i in range(width):
        for j in range(i):
            ph = np.exp(-1j * np.pi / 2.0 ** (j + 1))
            c.append_ctrl([i - (j + 1)], i,
                          np.diag([1.0, ph]).astype(np.complex128), 1)
        c.append_1q(i, h)
    return c


def _measured_circuit_bytes(width, n_pages, remap, monkeypatch):
    monkeypatch.setenv("QRACK_TPU_FUSE_WINDOW", "32")
    circ = _iqft_qcircuit(width)
    o = QEngineCPU(width, rng=QrackRandom(3), rand_global_phase=False)
    o.SetPermutation(314)
    circ.Run(o)
    tele.reset()
    tele.enable()
    q = QPager(width, rng=QrackRandom(3), rand_global_phase=False,
               n_pages=n_pages, remap=remap)
    q.SetPermutation(314)
    circ.Run(q)
    _ = q.GetAmplitude(0)  # read boundary: flush the fused window
    c = tele.snapshot()["counters"]
    tele.disable()
    tele.reset()
    f = _fidelity(o.GetQuantumState(), q.GetQuantumState())
    return c, f


def test_collective_measured_w10(monkeypatch):
    """w10 IQFT / 8 pages, measured: the prologue ships exactly
    (1 - 2^-3) x nb in ONE collective — the (1 - 2^-k)x ratio of
    mpiQulacs' fused exchange, on the wire — where the fixed placement
    exchanges the whole state under each of the 3 paged H."""
    nb = 2 * (1 << 10) * 4
    on, f_on = _measured_circuit_bytes(10, 8, "auto", monkeypatch)
    off, f_off = _measured_circuit_bytes(10, 8, "off", monkeypatch)
    assert f_on > 1 - 1e-6 and f_off > 1 - 1e-6, (f_on, f_off)
    assert on.get("exchange.pager.bytes", 0) == (1 - 2.0 ** -3) * nb, on
    assert on.get("exchange.pager.collective_bytes", 0) \
        == on["exchange.pager.bytes"]
    assert on.get("remap.pager.batched", 0) >= 1
    assert off.get("exchange.pager.bytes", 0) == 3 * nb, off
    assert off.get("remap.pager.batched", 0) == 0
    assert off.get("exchange.pager.collective_bytes", 0) == 0


# ---------------------------------------------------------------------------
# the permutation lowering itself: random transposition batches vs the
# numpy bit-permutation oracle, on a real 8-device mesh
# ---------------------------------------------------------------------------

def test_apply_remap_random_oracle():
    """apply_remap must realize the composed bit
    permutation of any transposition sequence — local, mixed and
    page-page, including the page-bit swaps the DCN pass emits."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from qrack_tpu.ops import sharded as shb

    L, g = 4, 3
    n = L + g
    mesh = Mesh(np.array(jax.devices()[:1 << g]), ("pages",))
    sh = NamedSharding(mesh, P(None, "pages"))
    rng = np.random.default_rng(17)
    for trial in range(8):
        swaps = tuple(tuple(int(x) for x in
                            rng.choice(n, size=2, replace=False))
                      for _ in range(int(rng.integers(1, 6))))
        state = rng.normal(size=(2, 1 << n)).astype(np.float32)
        src = shb.compose_swaps(n, swaps)
        j = np.zeros(1 << n, dtype=np.int64)
        for p in range(n):
            j |= ((np.arange(1 << n) >> p) & 1) << src[p]
        want = state[:, j]
        prog = jax.jit(jax.shard_map(
            lambda local: shb.apply_remap(local, 1 << g, L, swaps),
            mesh=mesh, in_specs=P(None, "pages"),
            out_specs=P(None, "pages")))
        got = np.asarray(prog(jax.device_put(state, sh)))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{trial} {swaps}")


# ---------------------------------------------------------------------------
# DCN-aware planning: the multi-host cost model prefers ICI page bits
# ---------------------------------------------------------------------------

def test_plan_remaps_dcn_weights_prefer_ici():
    """With non-uniform page-bit weights (DCN stand-in) the planner
    moves a hot qubit OFF the expensive page bit onto a gen-done ICI
    one — a pure page-bit transposition — when evicting to a local
    would charge the victim at the DCN rate."""
    eye = np.eye(2, dtype=np.complex128)
    L, n = 4, 6            # g=2: page bit 0 ICI, page bit 1 DCN
    weights = (1.0, 4.0)
    ops = [fu.FusedOp("gen", 5, 0, 0, eye)]
    look = [("gen", q) for q in range(L)]  # every local still owes one
    swaps, qmap = fu.plan_remaps(ops, L, list(range(n)), look,
                                 weights=weights)
    assert swaps == ((4, 5),), swaps       # page-page, off the DCN bit
    assert qmap[5] == 4 and qmap[4] == 5
    # uniform weights: same window fires nothing (net-zero local swap)
    swaps_u, qmap_u = fu.plan_remaps(ops, L, list(range(n)), look,
                                     weights=None)
    assert swaps_u == () and qmap_u == list(range(n))


def test_page_bit_weights_standin():
    """cluster.page_bit_weights: single host is uniform (None) unless
    the DCN stand-in forces the top bits to DCN pricing."""
    import jax

    from qrack_tpu.parallel import cluster

    devs = jax.devices()[:8]
    assert cluster.page_bit_weights(devs) is None
    w = cluster.page_bit_weights(devs, dcn_bits=1)
    assert w is not None and len(w) == 3
    assert w[2] == cluster.dcn_weight() and w[0] == w[1] == 1.0
    assert cluster.page_bit_kinds(devs) == ("ici",) * 3


# ---------------------------------------------------------------------------
# structural ops mid-BATCHED-prologue
# ---------------------------------------------------------------------------

def test_shrink_mid_batched_prologue_resets_table():
    """Elastic shrink right after a >= 2-pair batched prologue: the
    repage gathers the LOGICAL view, the table resets, and the stack
    stays on-oracle."""
    n = 7
    o = QEngineCPU(n, rng=QrackRandom(21), rand_global_phase=False)
    p = QPager(n, rng=QrackRandom(21), rand_global_phase=False,
               n_pages=8, remap="on")
    tele.reset()
    tele.enable()
    _force_nonid(o, p)
    c = tele.snapshot()["counters"]
    tele.disable()
    tele.reset()
    assert c.get("remap.pager.batched", 0) >= 1, c
    assert c.get("exchange.pager.collective_bytes", 0) > 0, c
    p.shrink_pages()
    assert p.n_pages == 4 and not p._map_nonid()
    for eng in (o, p):
        eng.RY(0.7, 5)
        eng.CNOT(6, 2)
        eng.H(0)
    np.testing.assert_allclose(p.GetQuantumState(), o.GetQuantumState(),
                               atol=3e-5)
