"""A window's operands are two packed host columns (ops/fusion.py,
"Operand layout"): the layout held value for value against the per-op
arrays the fuser used to put on the device one at a time, the sharded
twin with its masks split at the local bits, and the engines that read
the columns held against each other."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from qrack_tpu.engines.cpu import QEngineCPU
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import gatekernels as gk
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.ops.sharded import split_masks
from qrack_tpu.parallel.pager import QPager
from qrack_tpu.utils.rng import QrackRandom

MATRICES = {
    "cphase": np.diag([1.0, np.exp(0.37j)]),
    "diag": np.diag([np.exp(-0.21j), np.exp(0.53j)]),
    "inv": np.array([[0, np.exp(0.3j)], [np.exp(-0.8j), 0]]),
    "gen": np.array([[np.cos(0.4), -np.exp(0.6j) * np.sin(0.4)],
                     [np.exp(0.2j) * np.sin(0.4),
                      np.exp(0.8j) * np.cos(0.4)]]),
}
DTYPES = {"float32": jnp.float32, "float64": jnp.float64,
          "bfloat16": jnp.bfloat16}


def _per_op_payload(kind, m, dtype):
    """The payload array the per-op layout put on the device: one
    ``jnp.asarray(..., dtype=dtype)`` of the entries, ``gk.mtrx_planes``
    for a gen."""
    if kind == "cphase":
        return jnp.asarray([m[1, 1].real, m[1, 1].imag], dtype=dtype)
    if kind == "diag":
        return jnp.asarray(
            [[m[0, 0].real, m[0, 0].imag], [m[1, 1].real, m[1, 1].imag]],
            dtype=dtype)
    if kind == "inv":
        return jnp.asarray(
            [[m[0, 1].real, m[0, 1].imag], [m[1, 0].real, m[1, 0].imag]],
            dtype=dtype)
    return gk.mtrx_planes(m, dtype)


def _bits(a):
    return np.asarray(a).reshape(-1).view(np.uint8).tolist()


def _op(kind, target, cmask, cval=None):
    cval = cmask if cval is None else cval
    assert fu.classify(MATRICES[kind], cmask, cval) == kind
    return fu.FusedOp(kind, target, cmask, cval, MATRICES[kind])


@pytest.fixture
def x64():
    with jax.enable_x64(True):
        yield


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("controlled", [False, True], ids=["bare", "ctrl"])
@pytest.mark.parametrize("kind", list(MATRICES))
def test_dense_columns_hold_the_per_op_values(x64, kind, controlled, dtype):
    """The op under test sits between two others, so that its slot is
    not the first: values bit for bit (rounded to the planes' dtype as
    ``jnp.asarray`` rounds), offsets those of ``_operand_slots``."""
    dt = DTYPES[dtype]
    cmask = (1 << 2) | (1 << 9) if controlled else 0
    # an anti-control where the kind allows one (cphase is all-ones)
    cval = cmask if kind == "cphase" else cmask & (1 << 9)
    ops = [_op("gen", 1, 1 << 5), _op(kind, 4, cmask, cval),
           _op("cphase", 7, 1 << 3)]
    structure = fu.structure_of(ops)
    assert structure[1] == (kind, 4, controlled)
    slots, nf, ni = pk._operand_slots(structure)
    iv, fv = fu.pack_operands(ops, dt)
    assert type(iv) is np.ndarray and type(fv) is np.ndarray
    assert iv.dtype == np.int32 and iv.shape == (ni, 1)
    assert fv.dtype == jnp.dtype(dt) and fv.shape == (nf, 1)
    assert nf == 8 + pk._NFLOATS[kind] + 2 and ni == 4 + 2 * controlled
    for op, (f, i) in zip(ops, slots):
        want = _per_op_payload(op.kind, np.asarray(op.m), dt)
        assert want.dtype == jnp.dtype(dt)
        assert _bits(fv[f:f + want.size, 0]) == _bits(want)
        if op.cmask:
            assert iv[i:i + 2, 0].tolist() == [op.cmask, op.cval]
    # the views the window bodies read: the per-op arrays, shape and all
    for op, (p, masks) in zip(ops, fu.operand_views(structure, iv, fv)):
        want = _per_op_payload(op.kind, np.asarray(op.m), dt)
        assert p.shape == want.shape and _bits(p) == _bits(want)
        assert [int(x) for x in masks] == ([op.cmask, op.cval]
                                           if op.cmask else [])


def test_uncontrolled_window_keeps_one_dead_int_slot():
    ops = [_op("gen", 1, 0), _op("diag", 3, 0)]
    iv, fv = fu.pack_operands(ops, jnp.float32)
    assert iv.tolist() == [[0]] and fv.shape == (12, 1)
    # the per-op views hold no mask for them
    assert len(fu.per_op_operands(ops, jnp.float32)) == 2


def test_a_mask_past_int32_raises():
    with pytest.raises(OverflowError):
        fu.pack_operands([_op("gen", 1, 1 << 31)], jnp.float32)


L = 10   # local bits of a 4-page w12 pager


@pytest.mark.parametrize("controlled", [False, True], ids=["bare", "ctrl"])
@pytest.mark.parametrize("where", ["local", "paged"])
@pytest.mark.parametrize("kind", list(MATRICES))
def test_sharded_columns_split_masks_at_the_local_bits(kind, where,
                                                       controlled):
    target = 4 if where == "local" else L + 1
    # controls on both sides of the split
    cmask = (1 << 2) | (1 << L) if controlled else 0
    cval = cmask if kind == "cphase" else cmask & (1 << L)
    ops = [_op("diag", L, 1 << 5, 0), _op(kind, target, cmask, cval),
           _op("cphase", 7, 1 << (L + 1))]
    structure = fu.sharded_structure_of(ops)
    skind = "gen" if kind == "inv" else kind   # no invert on the pager
    assert structure[1] == (skind, target, controlled)
    slots, nf, ni = pk._operand_slots(structure, split=True)
    iv, fv = fu.pack_operands(ops, jnp.float32, split_at=L)
    assert iv.shape == (ni, 1) and fv.shape == (nf, 1)
    nmask = (2 if kind == "cphase" else 4) if controlled else 0
    assert ni == 4 + nmask + 2 and nf == 4 + pk._NFLOATS[skind] + 2
    views = fu.operand_views(structure, iv, fv, split=True)
    for op, (k, _, _), (f, i), (p, masks) in zip(ops, structure, slots, views):
        want = _per_op_payload(k, np.asarray(op.m), jnp.float32)
        assert _bits(fv[f:f + want.size, 0]) == _bits(want) == _bits(p)
        assert p.shape == want.shape
        if not op.cmask:
            assert masks == ()
        elif k == "cphase":
            comb = (1 << op.target) | op.cmask
            assert [int(x) for x in masks] \
                == [comb & ((1 << L) - 1), comb >> L]
        else:
            assert tuple(int(x) for x in masks) \
                == split_masks(op.cmask, op.cval, L)
        assert [int(x) for x in masks] == iv[i:i + len(masks), 0].tolist()
    # the flat per-op list (trajectories, the compressed engine) is the
    # same arrays, cut from the same columns
    flat = fu.per_op_operands(ops, jnp.float32, split_at=L)
    assert len(flat) == sum(1 + len(masks) for _, masks in views)


# -- the engines that read the columns, against each other --------------------

W = 12


def _all_four_kinds(q):
    """cphase, diag, inv and gen, bare and controlled, on low, high and
    (for four pages) paged qubits; the windows fill and flush."""
    q.SetPermutation(0b101101110011)
    for t in (0, 5, 9, 10, 11):
        q.H(t)                                   # gen
    q.QFT(2, 9)                                  # gen + cphase
    for t in (1, 6, 11):
        q.RZ(0.3 + 0.1 * t, t)                   # diag
        q.X((t + 3) % W)                         # inv
        q.CNOT(t, (t + 5) % W)                   # inv, controlled
        q.CZ(t, (t + 2) % W)                     # cphase
        q.MCMtrx([(t + 1) % W], MATRICES["gen"], t)     # gen, controlled
        q.MACMtrx([(t + 4) % W], MATRICES["diag"], t)   # diag, anti-control
        q.MACMtrx([(t + 7) % W], MATRICES["inv"], t)    # inv, anti-control
    return np.asarray(q.GetQuantumState())


def _engine(name):
    kw = dict(rng=QrackRandom(7), rand_global_phase=False)
    if name == "cpu":
        return QEngineCPU(W, **kw)
    if name == "dense":
        return QEngineTPU(W, **kw)
    return QPager(W, n_pages=4, **kw)


@pytest.fixture
def lowering(request, monkeypatch):
    """``chain``: the XLA window chain; ``kernel``: the window kernel
    under the interpreter, tiles of 2^6 so that w12 has cross-tile
    targets."""
    if request.param == "kernel":
        monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
        monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", 6)
    else:
        monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "off")
    fu.PROGRAMS.clear()
    yield request.param
    fu.PROGRAMS.clear()


@pytest.fixture(scope="module")
def oracle():
    return _all_four_kinds(_engine("cpu"))


@pytest.mark.parametrize("lowering", ["chain", "kernel"], indirect=True)
@pytest.mark.parametrize("engine", ["dense", "pager"])
def test_engines_agree_with_the_cpu_oracle(oracle, engine, lowering):
    got = _all_four_kinds(_engine(engine))
    assert float(np.max(np.abs(got - oracle))) < 3e-6


@pytest.mark.parametrize("lowering", ["chain", "kernel"], indirect=True)
def test_dense_and_pager_agree(lowering, monkeypatch):
    packed = []
    real = fu.pack_operands

    def spy(ops, dtype, split_at=None, runs=None):
        packed.extend((split_at, op.kind, bool(op.cmask)) for op in ops)
        return real(ops, dtype, split_at, runs)

    monkeypatch.setattr(fu, "pack_operands", spy)
    a = _all_four_kinds(_engine("dense"))
    b = _all_four_kinds(_engine("pager"))
    assert float(np.max(np.abs(a - b))) < 3e-6
    # every kind went through the columns, bare and controlled, in both
    # layouts (a cphase is controlled by definition here)
    for split_at in (None, W - 2):
        assert {(k, c) for s, k, c in packed if s == split_at} >= {
            ("cphase", True), ("diag", False), ("diag", True),
            ("inv", False), ("inv", True), ("gen", False), ("gen", True)}


@pytest.mark.parametrize("engine", ["dense", "pager"])
def test_kernel_and_chain_agree(engine, monkeypatch):
    kets = {}
    for mode in ("off", "on"):
        monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", mode)
        monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", 6)
        fu.PROGRAMS.clear()
        kets[mode] = _all_four_kinds(_engine(engine))
    fu.PROGRAMS.clear()
    assert float(np.max(np.abs(kets["on"] - kets["off"]))) < 3e-6
