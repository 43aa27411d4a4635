"""The program store beside the compile cache
(``checkpoint/warmstart.stored_program``): a window program is exported
by the first process that calls it and read back by every later one,
which traces no window body.

CPU, kernels under the Pallas interpreter, the cache directory a
``tmp_path``.  A "fresh process" here is a cleared in-process program
cache with the window bodies' builders patched to raise: whatever still
dispatches came from the store.  What the chip's compiler makes of a
reloaded program is held where that compiler is
(tests/test_chip_compile.py)."""

import os
import shutil
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from qrack_tpu import telemetry as tele
from qrack_tpu.checkpoint import warmstart
from qrack_tpu.engines.tpu import QEngineTPU
from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk
from qrack_tpu.parallel import pager
from qrack_tpu.parallel.pager import QPager
from qrack_tpu.utils.rng import QrackRandom

W = 10
COUNTERS = ("hit", "miss", "stale", "unexportable")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Interpret-mode kernels on tiles of 2^6 (a w10 ket has cross-tile
    targets), empty in-process caches, telemetry on and empty."""
    monkeypatch.setenv("QRACK_TPU_FUSE_KERNEL", "on")
    monkeypatch.setattr(pk, "DEFAULT_BLOCK_POW", 6)
    _fresh_process()
    tele.reset()
    tele.enable()
    yield
    tele.disable()
    tele.reset()
    _fresh_process()


@pytest.fixture
def store(program_store):
    """The directory the programs are stored in (conftest's
    ``program_store``: a compilation cache directory configured)."""
    return program_store


def _fresh_process():
    fu.PROGRAMS.clear()
    pager._PROGRAMS.clear()


def _dense():
    return QEngineTPU(W, rng=QrackRandom(7), rand_global_phase=False)


def _pager():
    return QPager(W, rng=QrackRandom(7), rand_global_phase=False, n_pages=2)


ENGINES = {"dense": _dense, "pager": _pager}


def _ket(make):
    """Two windows of different structure (a read between them), the
    second with a gate on the top qubit: the pager's paged one."""
    q = make()
    q.SetPermutation(0b1011001101)
    for t in (0, 3, 7):
        q.H(t)
    q.CZ(0, 7)
    q.RZ(0.25, 3)
    q.GetAmplitude(1)
    q.H(W - 1)
    q.RX(0.5, 2)
    q.CNOT(W - 1, 1)
    return np.asarray(q._state)


def _counts():
    c = tele.snapshot(include_events=False)["counters"]
    return tuple(int(c.get("warmstart.program." + k, 0)) for k in COUNTERS)


def _spans(name):
    agg = tele.snapshot(include_events=False)["spans"]
    return int(agg.get("warmstart.program." + name, {"count": 0})["count"])


def _files(store):
    return sorted(os.listdir(store)) if os.path.isdir(store) else []


def _no_window_body(monkeypatch):
    """From here on, tracing a window body is an error."""
    def refuse(*a, **kw):
        raise AssertionError("a window body was built")

    for mod, name in ((pk, "make_window_fn"), (fu, "window_fn"),
                      (fu, "sharded_kernel_window_body"),
                      (fu, "sharded_window_body")):
        monkeypatch.setattr(mod, name, refuse)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_a_fresh_process_reads_its_programs_back(store, monkeypatch, engine):
    first = _ket(ENGINES[engine])
    programs = len(_files(store))
    assert programs >= 2
    assert _counts() == (0, programs, 0, 0)
    assert (_spans("export"), _spans("load")) == (programs, 0)
    _fresh_process()
    tele.reset()
    _no_window_body(monkeypatch)
    again = _ket(ENGINES[engine])
    assert np.array_equal(first, again)  # the same program: bit for bit
    assert _counts() == (programs, 0, 0, 0)
    assert (_spans("export"), _spans("load")) == (0, programs)
    assert len(_files(store)) == programs


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_a_stored_program_is_the_direct_one(store, engine):
    """What the store dispatches, in the process that exports and in the
    one that loads, is the ket the direct ``jax.jit(fn)`` gives."""
    stored = _ket(ENGINES[engine])
    _fresh_process()
    jax.config.update("jax_compilation_cache_dir", None)
    assert np.array_equal(stored, _ket(ENGINES[engine]))


def test_a_changed_byte_of_the_package_misses_every_program(
        store, monkeypatch, tmp_path_factory):
    _ket(_dense)
    programs = len(_files(store))
    copy = tmp_path_factory.mktemp("package") / "qrack_tpu"
    shutil.copytree(warmstart._PACKAGE, copy,
                    ignore=shutil.ignore_patterns("__pycache__", "*.so"))
    monkeypatch.setattr(warmstart, "_PACKAGE", str(copy))
    monkeypatch.setattr(warmstart, "_SOURCE_DIGEST", None)
    same = warmstart.source_digest()
    with open(copy / "ops" / "pallas_kernels.py", "ab") as f:
        f.write(b"#")
    monkeypatch.setattr(warmstart, "_SOURCE_DIGEST", None)
    assert warmstart.source_digest() != same
    _fresh_process()
    tele.reset()
    _ket(_dense)
    assert _counts() == (0, programs, 0, 0)
    assert len(_files(store)) == 2 * programs


def test_the_source_digest_is_the_checkouts(monkeypatch, tmp_path):
    """Path and bytes of every ``.py``, nothing else: a copy of the
    package hashes as the package does."""
    real = warmstart.source_digest()
    copy = tmp_path / "qrack_tpu"
    shutil.copytree(warmstart._PACKAGE, copy)
    monkeypatch.setattr(warmstart, "_PACKAGE", str(copy))
    monkeypatch.setattr(warmstart, "_SOURCE_DIGEST", None)
    assert warmstart.source_digest() == real


STRUCTURE = (("gen", 7, False), ("cphase", 3, True))


def _digest_of(width=W, dtype=jnp.float32, structure=STRUCTURE,
               mesh=None, donate=(0,), x64=False):
    planes = jax.ShapeDtypeStruct(
        (2, 1 << width), dtype,
        sharding=None if mesh is None
        else NamedSharding(mesh, P(None, mesh.axis_names[0])))
    args = (planes, np.zeros((4, 1), np.int32), np.zeros((6, 1), dtype))
    key = ("kernel", "interp", 6, width, str(jnp.dtype(dtype)), structure)
    if not x64:
        return warmstart.program_digest(key, args, {"donate_argnums": donate})
    with jax.enable_x64():
        return warmstart.program_digest(key, args, {"donate_argnums": donate})


def _mesh(pages, name="pages"):
    return Mesh(np.array(jax.devices()[:pages]), (name,))


@pytest.mark.parametrize("changed", [
    {"width": W + 1}, {"dtype": jnp.bfloat16},
    {"structure": STRUCTURE + (("gen", 1, False),)},
    {"structure": (("gen", 7, False), ("cphase", 3, False))},
    {"donate": ()}, {"x64": True},
    {"mesh": lambda: _mesh(2)}, {"mesh": lambda: _mesh(4)},
    {"mesh": lambda: _mesh(2, "rows")}],
    ids=["width", "dtype", "one-op-more", "a-control-less", "donation",
         "x64", "mesh-of-2", "mesh-of-4", "axis-name"])
def test_the_digest_changes_with(changed):
    if "mesh" in changed:  # built in the test: a mesh needs the backend
        changed = {"mesh": changed["mesh"]()}
    assert _digest_of(**changed) != _digest_of()
    assert _digest_of(**changed) == _digest_of(**changed)


def test_the_digest_does_not_hold_which_chips_carry_the_mesh():
    devices = jax.devices()
    a = Mesh(np.array(devices[:2]), ("pages",))
    b = Mesh(np.array(devices[2:4]), ("pages",))
    assert _digest_of(mesh=a) == _digest_of(mesh=b) != _digest_of()


def _truncate(store):
    for name in _files(store):
        path = os.path.join(store, name)
        with open(path, "rb") as f:
            blob = f.read()
        with open(path, "wb") as f:
            f.write(blob[:len(blob) // 2])


def _swap(store):
    """Each program's file holds another program's bytes: whole, and of
    other avals (the operand columns differ in length)."""
    names = _files(store)
    blobs = []
    for name in names:
        with open(os.path.join(store, name), "rb") as f:
            blobs.append(f.read())
    for name, blob in zip(names, blobs[1:] + blobs[:1]):
        with open(os.path.join(store, name), "wb") as f:
            f.write(blob)


@pytest.mark.parametrize("damage", [_truncate, _swap],
                         ids=["truncated", "another-aval"])
def test_a_stale_file_is_rebuilt_and_written_over(store, monkeypatch, damage):
    first = _ket(_dense)
    programs = len(_files(store))
    sizes = [os.path.getsize(os.path.join(store, f)) for f in _files(store)]
    damage(store)
    _fresh_process()
    tele.reset()
    assert np.array_equal(first, _ket(_dense))
    assert _counts() == (0, 0, programs, 0)
    assert (_spans("load"), _spans("stale"), _spans("export")) == (
        0, programs, programs)
    assert sizes == [os.path.getsize(os.path.join(store, f))
                     for f in _files(store)]
    _fresh_process()
    tele.reset()
    _no_window_body(monkeypatch)
    assert np.array_equal(first, _ket(_dense))
    assert _counts() == (programs, 0, 0, 0)


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_no_directory_configured_stores_nothing(tmp_path, monkeypatch,
                                                engine):
    assert jax.config.jax_compilation_cache_dir is None
    built = []
    real = pk.make_window_fn
    monkeypatch.setattr(pk, "make_window_fn",
                        lambda *a, **kw: built.append(a) or real(*a, **kw))
    real_export = jax.export.export
    monkeypatch.setattr(jax.export, "export",
                        lambda *a, **kw: built.append("export")
                        or real_export(*a, **kw))
    _ket(ENGINES[engine])
    assert built and "export" not in built  # traced as it always was
    assert _counts() == (0, 0, 0, 0)
    assert (_spans("load"), _spans("export")) == (0, 0)
    assert warmstart.program_dir() is None


def test_an_export_that_raises_falls_back(store, monkeypatch):
    want = _ket(_dense)
    programs = len(_files(store))
    shutil.rmtree(store)

    def refuse(*a, **kw):
        raise NotImplementedError("an effect export does not take")

    monkeypatch.setattr(jax.export, "export", refuse)
    _fresh_process()
    tele.reset()
    assert np.array_equal(want, _ket(_dense))
    assert _counts() == (0, programs, 0, programs)
    assert _files(store) == []


def test_a_store_that_cannot_be_written_costs_no_dispatch(store, monkeypatch):
    want = _ket(_dense)
    shutil.rmtree(store)

    def refuse(*a, **kw):
        raise OSError("read-only file system")

    monkeypatch.setattr(os, "makedirs", refuse)
    _fresh_process()
    assert np.array_equal(want, _ket(_dense))
    assert _files(store) == []


def test_two_builders_of_one_program_both_end_with_a_whole_file(store):
    """Two threads stand in for two processes: each resolves its own
    ``stored_program`` of one key at once."""
    ops = [fu.FusedOp(kind, target, int(c), int(c), np.eye(2))
           for kind, target, c in STRUCTURE]
    operands = fu.pack_operands(ops, jnp.float32)
    planes = jnp.zeros((2, 1 << W), jnp.float32).at[0, 5].set(1.0)
    want = np.asarray(jax.jit(
        pk.make_window_fn(W, STRUCTURE, block_pow=6, interpret=True))(
            planes, *operands))
    got, start = [], threading.Barrier(2)

    def build():
        prog = warmstart.stored_program(
            ("race", W, STRUCTURE),
            lambda: pk.make_window_fn(W, STRUCTURE, block_pow=6,
                                      interpret=True))
        start.wait(timeout=60)
        got.append(np.asarray(prog(planes, *operands)))

    threads = [threading.Thread(target=build) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    assert len(got) == 2 and all(np.array_equal(want, g) for g in got)
    (name,) = _files(store)  # one program, no temporary left behind
    with open(os.path.join(store, name), "rb") as f:
        reloaded = jax.export.deserialize(bytearray(f.read()))
    assert reloaded.fun_name == "qrack_kernel_window"


def test_a_stored_program_keeps_the_jitted_programs_face(store):
    """What ``telemetry.instrument_jit`` and the tests of the lowered
    text ask of a program: ``_cache_size`` before and after the first
    call, ``lower``, and the module's name from the store."""
    ops = [fu.FusedOp(kind, target, int(c), int(c), np.eye(2))
           for kind, target, c in STRUCTURE]
    operands = fu.pack_operands(ops, jnp.float32)
    planes = jnp.zeros((2, 1 << W), jnp.float32)

    def program():
        return warmstart.stored_program(
            ("face", W, STRUCTURE),
            lambda: pk.make_window_fn(W, STRUCTURE, block_pow=6,
                                      interpret=True), donate_argnums=(0,))

    first = program()
    assert first._cache_size() == 0
    assert "module @jit_qrack_kernel_window" in first.lower(
        planes, *operands).as_text()
    first(jnp.copy(planes), *operands)
    assert first._cache_size() == 1
    loaded = program()
    loaded(jnp.copy(planes), *operands)
    assert _counts() == (1, 1, 0, 0)
    text = loaded.lower(planes, *operands).as_text()
    assert "module @jit_qrack_kernel_window" in text
    assert "tf.aliasing_output" in text  # the donation, stated again
    assert text == first.lower(planes, *operands).as_text()


def test_a_traced_call_is_traced_as_it_always_was(store):
    """Under an outer transformation the arguments are tracers: nothing
    to export on, nothing stored."""
    ops = [fu.FusedOp(kind, target, int(c), int(c), np.eye(2))
           for kind, target, c in STRUCTURE]
    iv, fv = fu.pack_operands(ops, jnp.float32)
    prog = warmstart.stored_program(
        ("traced", W, STRUCTURE), lambda: fu.window_fn(W, STRUCTURE))
    batch = jnp.zeros((3, 2, 1 << W), jnp.float32).at[:, 0, 1].set(1.0)
    out = jax.vmap(lambda p: prog(p, iv, fv))(batch)
    assert out.shape == batch.shape
    assert _files(store) == [] and _counts() == (0, 0, 0, 0)
