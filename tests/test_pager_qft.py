"""The paged QFT of the benchmark's cell ``qft_w31.pager4``, on four host
devices at w12 and w14: ``SetPermutation; QFT(0, n); GetAmplitude`` on
``QPager(n_pages=4)`` against a plain numpy simulation written here and
against the closed form; that every application plans the same two
prologues; the per-page fill (``jit_qrack_page_fill``) and the
one-amplitude read and write by (page, offset); and, without allocating
a ket, that these trace and lower at w31 to w33 with no index of the
global axis."""

import cmath
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from qrack_tpu import create_quantum_interface
from qrack_tpu import telemetry as tele
from qrack_tpu.parallel import pager
from qrack_tpu.utils.rng import QrackRandom

from helpers import plan_only_pager

PAGES = 4


def _pager(width, **kwargs):
    return create_quantum_interface("pager", width, n_pages=PAGES,
                                    rng=QrackRandom(5),
                                    rand_global_phase=False, **kwargs)


# -- the plain reference: complex128, gate by gate, nothing of qrack_tpu -----

def _numpy_qft(n, x):
    """Qrack's QFT(0, n) of |x> (QInterface::QFT: H on the top qubit
    first, each H behind the controlled phases of the qubits above it,
    no final swaps), on a ``(2,) * n`` view: axis ``n - 1 - q`` is
    qubit ``q``."""
    ket = np.zeros(1 << n, dtype=np.complex128)
    ket[x] = 1.0
    ket = ket.reshape((2,) * n)
    h = np.array([[1, 1], [1, -1]], dtype=np.complex128) / math.sqrt(2)
    for i in range(n):
        q = n - 1 - i
        for j in range(i):
            both = [slice(None)] * n
            both[n - 1 - q] = 1            # control: the qubit H is about to take
            both[n - 1 - (q + 1 + j)] = 1  # target: a qubit above it
            ket[tuple(both)] *= cmath.exp(1j * math.pi / (1 << (j + 1)))
        ket = np.moveaxis(np.tensordot(h, ket, axes=([1], [n - 1 - q])),
                          0, n - 1 - q)
    return ket.reshape(-1)


def _closed_form(n, x):
    """<y| QFT |x> for every y: the textbook transform with the output
    register bit-reversed."""
    rev = np.array([int(format(y, f"0{n}b")[::-1], 2) for y in range(1 << n)])
    return np.exp(2j * np.pi * ((x * rev) % (1 << n)) / (1 << n)) \
        / math.sqrt(1 << n)


@pytest.mark.parametrize("seed", [11, 12])
@pytest.mark.parametrize("kernel", ["on", "off"])
@pytest.mark.parametrize("width", [12, 14])
def test_paged_qft_matches_numpy_and_the_closed_form(width, kernel, seed,
                                                     monkeypatch):
    """Every amplitude of two applications on one pager; ``on`` takes the
    per-page kernel under the Pallas interpreter, ``off`` the XLA chain,
    both behind the same prologues."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", kernel)
    rng = np.random.default_rng(seed)
    q = _pager(width)
    for _ in range(2):
        x = int(rng.integers(1, 1 << width))
        y = int(rng.integers(0, 1 << width))
        want = _numpy_qft(width, x)
        assert np.max(np.abs(want - _closed_form(width, x))) < 1e-12
        q.SetPermutation(x)
        q.QFT(0, width)
        # the cell's read: one amplitude through the placement table,
        # which the second prologue has put back where the first found
        # it (both exchange the two page bits with the carrier bits; a
        # read through a table that stays moved: test_pager_tfim.py)
        assert q.placement() == tuple(range(width))
        assert abs(q.GetAmplitude(y) - want[y]) / abs(want[y]) < 1e-5
        got = np.asarray(q.GetQuantumState())
        assert np.max(np.abs(got - want)) * math.sqrt(1 << width) < 1e-5


@pytest.mark.parametrize("width", [12, 14])
def test_every_application_plans_the_same_two_prologues(width):
    """2 prologues of k = 2 an application, no gate left on a paged
    qubit, nothing built in the second application, and the table back
    at the identity behind the second prologue (the top two qubits'
    ``H`` each open a window, PR 46: the first exchange brings them
    onto the carrier bits and the third-highest's ``H`` sends them back,
    controls from then on) and behind every ``SetPermutation``."""
    q = _pager(width)
    was = tele._ENABLED
    tele.enable()
    try:
        seen = []
        for x in (5, (1 << width) - 3):
            before = dict(tele.snapshot(include_events=False)["counters"])
            misses = pager._PROGRAMS.misses
            q.SetPermutation(x)
            assert q.placement() == tuple(range(width))
            q.QFT(0, width)
            q.GetAmplitude(1)
            after = tele.snapshot(include_events=False)["counters"]
            moved = {k: v - before.get(k, 0) for k, v in after.items()
                     if k.startswith(("remap.pager.", "exchange.pager."))
                     and v != before.get(k, 0)}
            seen.append((moved, pager._PROGRAMS.misses - misses, q.placement()))
    finally:
        if not was:
            tele.disable()
    for moved, _, table in seen:
        assert moved["remap.pager.prologues.k2"] == 2
        assert not [k for k in moved if k.startswith("remap.pager.prologues.")
                    and k != "remap.pager.prologues.k2"]
        assert "exchange.pager.global_2x2" not in moved
        assert "remap.pager.page_perms" not in moved
        # 1.5 pages a chip: two batches of (1 - 2^-2) of the ket
        assert moved["exchange.pager.bytes"] == 1.5 * (2 * 4 << width)
        assert table == tuple(range(width))
    assert seen[0][0] == seen[1][0] and seen[0][2] == seen[1][2]
    assert seen[1][1] == 0  # the second application built no program


# -- the per-page fill --------------------------------------------------------

@pytest.mark.parametrize("page", range(PAGES))
def test_fill_in_place_writes_one_amplitude_on_one_page(page):
    """A basis state in every page, both planes (a phase): the ket is the
    dense engine's, bit for bit, and the fill took the buffers of the
    ket it was given."""
    width = 10
    x = (page << (width - 2)) | (37 * (page + 1))
    phase = cmath.exp(0.7j)
    dense = create_quantum_interface("tpu", width, rand_global_phase=False)
    dense.SetPermutation(x, phase=phase)
    q = _pager(width)
    old = q._state_raw
    q.SetPermutation(x, phase=phase)
    assert old.is_deleted()  # donated: never alive beside the new ket
    planes = np.asarray(q._state)
    assert planes.shape == (2, 1 << width) and planes.dtype == np.float32
    assert q._state.sharding == q.sharding
    assert np.array_equal(planes, np.asarray(dense._state))
    assert np.count_nonzero(planes) == 2 and planes[0, x] and planes[1, x]
    assert q.GetAmplitude(x) == dense.GetAmplitude(x)
    assert q.GetAmplitude(x ^ 1) == 0


@pytest.mark.parametrize("given", ["none", "deleted", "other-type"])
def test_fill_allocates_a_fresh_ket_where_it_owns_none(given):
    width = 10
    q = _pager(width)
    if given == "none":
        q._state_raw = None
    elif given == "deleted":
        q._state_raw.delete()
    else:  # planes of another type are not the fill's to write over
        q._state_raw = jax.device_put(
            jnp.zeros((2, 1 << width), jnp.bfloat16), q.sharding)
    was = tele._ENABLED
    tele.enable()
    try:
        before = dict(tele.snapshot(include_events=False)["counters"])
        q.SetPermutation(777)
        after = tele.snapshot(include_events=False)["counters"]
    finally:
        if not was:
            tele.disable()
    assert after.get("pager.fill.fresh", 0) - before.get("pager.fill.fresh", 0) == 1
    assert after.get("pager.fill.in_place", 0) == before.get("pager.fill.in_place", 0)
    planes = np.asarray(q._state)
    assert planes.dtype == np.float32 and planes[0, 777] == 1.0
    assert np.count_nonzero(planes) == 1


def test_fill_counts_in_place_and_fresh():
    was = tele._ENABLED
    tele.enable()
    try:
        before = dict(tele.snapshot(include_events=False)["counters"])
        q = _pager(10)           # the constructor's fill: fresh
        q.SetPermutation(3)      # over the ket it owns
        q.SetPermutation(1000)
        after = tele.snapshot(include_events=False)["counters"]
    finally:
        if not was:
            tele.disable()
    assert after["pager.fill.fresh"] - before.get("pager.fill.fresh", 0) == 1
    assert after["pager.fill.in_place"] - before.get("pager.fill.in_place", 0) == 2


@pytest.mark.parametrize("order", ["float32-first", "bfloat16-first"])
def test_plane_types_get_fill_programs_of_their_own(order):
    """One process, one mesh, two plane types: each pager's fill gives
    planes of its own type, fresh and in place (the key of the old
    program left the type out: C15)."""
    types = [jnp.float32, jnp.bfloat16]
    if order == "bfloat16-first":
        types.reverse()
    pagers = [_pager(11, dtype=t) for t in types]
    for q, t in zip(pagers, types):
        assert q._state.dtype == jnp.dtype(t)
    for q, t in zip(pagers, types):
        q.SetPermutation(1234)
        assert q._state.dtype == jnp.dtype(t)
        planes = np.asarray(q._state, dtype=np.float32)
        assert planes[0, 1234] == 1.0 and np.count_nonzero(planes) == 1
    keys = [k for k in pager._PROGRAMS._od if "pagefill" in k]
    assert {k[k.index("pagefill") + 1] for k in keys} >= {"float32", "bfloat16"}


@pytest.mark.parametrize("remapped", [False, True])
def test_set_amplitude_writes_in_place_by_page_and_offset(remapped):
    width = 12
    q = _pager(width)
    dense = create_quantum_interface("tpu", width, rand_global_phase=False)
    for e in (q, dense):
        e.SetPermutation(9)
        if remapped:  # the QFT leaves the table where it found it
            e.QFT(0, width)
            e.H(width - 1)
    q.GetAmplitude(0)  # the pending window's prologue moves the table
    assert (q.placement() != tuple(range(width))) == remapped
    for perm, amp in ((0, 0.5 - 0.25j), ((3 << (width - 2)) + 5, 0.125j),
                      ((1 << width) - 1, -1.0)):
        old = q._state  # flushed: what the write is handed
        q.SetAmplitude(perm, amp)
        dense.SetAmplitude(perm, amp)
        assert old.is_deleted()
        assert q.GetAmplitude(perm) == complex(np.complex64(amp))
    assert np.max(np.abs(np.asarray(q.GetQuantumState())
                         - np.asarray(dense.GetQuantumState()))) < 1e-6


# -- without allocating: the widths the pages hold ----------------------------

def _shapes(q):
    n = q.qubit_count
    rep = NamedSharding(q.mesh, P())
    ket = jax.ShapeDtypeStruct((2, 1 << n), q.dtype, sharding=q.sharding)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    amp = jax.ShapeDtypeStruct((2,), q.dtype, sharding=rep)
    return ket, i32, amp


@pytest.mark.parametrize("program", ["fill-in-place", "fill-fresh", "read",
                                     "write"])
@pytest.mark.parametrize("width,pages", [(31, 4), (32, 4), (33, 8)])
def test_page_programs_lower_with_no_index_of_the_global_axis(width, pages,
                                                              program):
    """Traced and lowered from shapes alone: nothing raises, the ket goes
    in and out as (2, 2^n) and every other array is a page's or smaller;
    no iota, no 64-bit integer."""
    q = plan_only_pager(width, n_pages=pages)
    ket, i32, amp = _shapes(q)
    prog, args = {
        "fill-in-place": (q._p_page_fill(True), (ket, i32, i32, amp)),
        "fill-fresh": (q._p_page_fill(False), (i32, i32, amp)),
        "read": (q._p_page_read(), (ket, i32, i32)),
        "write": (q._p_page_write(), (ket, i32, i32, amp)),
    }[program]
    text = prog.lower(*args).as_text()
    # the sum's replica groups are an attribute of page ids, 64-bit by
    # the dialect; no operand or result is
    text = re.sub(r"replica_groups = dense<[^>]*> : tensor<[0-9x]+xi64>", "",
                  text)
    assert "iota" not in text and "i64" not in text
    page = (1 << width) // pages
    sizes = {int(d) for dims in re.findall(r"tensor<([0-9x]+)x[a-z]", text)
             for d in dims.split("x")}
    assert max(sizes - {1 << width}) == page
    if program != "read":
        assert f"tensor<2x{1 << width}xf32>" in text


@pytest.mark.parametrize("width,pages", [(31, 4), (32, 4), (33, 8)])
def test_basis_indices_past_int32_split_into_page_and_offset(width, pages):
    """``_map_index`` is exact on indices of 2^31 and more, under a table
    that is not the identity, and what it gives splits into two int32."""
    q = plan_only_pager(width, n_pages=pages)
    table = list(range(width))
    table[3], table[width - 1] = table[width - 1], table[3]
    table[width - 2], table[10] = table[10], table[width - 2]
    q._map_assign(table)
    L = q.local_bits
    rng = np.random.default_rng(width)
    low = min(1 << 31, 1 << (width - 1))  # w31 ends at 2^31 - 1
    picks = [(1 << width) - 1, 1 << (width - 1), low + 12345] + [
        int(rng.integers(low, 1 << width, dtype=np.uint64))
        for _ in range(20)]
    for idx in picks:
        want = sum(1 << table[b] for b in range(width) if (idx >> b) & 1)
        phys = q._map_index(idx)
        assert phys == want and q._unmap_index(phys) == idx
        page, off = q._split_index(phys)
        assert page.dtype == off.dtype == np.int32
        assert (int(page) << L) | int(off) == phys
        assert 0 <= int(page) < pages and 0 <= int(off) < (1 << L)
