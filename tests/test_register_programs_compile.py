"""The programs PR 53 added, compiled for a described TPU v5e without a
chip (section 2 of the on-chip-measurement guide; the fixtures and
helpers are ``tests/test_chip_compile.py``'s): the ALU's table write, a
register's reduction and the collapse at w28 and at w30, and the five
windows of one order-finding attempt (the cell ``shor_w28.library``).

A file of its own, sorted late: these compiles are the chip compiler's
own threads, and beside ``tests/test_fleet.py``'s timing they cost its
acceptance test its latency tail.
"""

import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk

from test_chip_compile import (W, W30, _compile, _dense_args,  # noqa: F401
                               _in_place, _launches, one_chip, topo)

# ---------------------------------------------------------------------------
# PR 53: Shor's order finding at w28 (the cell ``shor_w28.library``): an
# out-of-place modular power as one write of the ket from a table, a
# register measured by one reduction and one collapse.  Each program at
# w28 and at w30 (a 15-bit N beside an 8 GiB ket): the forms hold there,
# so widening the cell is a data change
# ---------------------------------------------------------------------------

REGISTER_TEMP_BYTES = 1 << 20  # beside the slice and the table


def _shape(one_chip, dims, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


def _no_ket_sized_index_work(text, n, iota_in_fusion=False):
    """No ``scatter`` or ``gather`` on an array of the ket's size, no
    copy of the planes, and no int32 array of the ket's length (but the
    collapse's iota, which lives inside its one fusion: the temporaries
    say it is never laid out)."""
    big = 1 << n
    for line in text.splitlines():
        if " gather(" in line or " scatter(" in line:
            sizes = [int(np.prod([int(d) for d in dims.split(",")]))
                     for dims in re.findall(r"\[([\d,]+)\]", line)]
            assert max(sizes, default=0) < big, line
    assert iota_in_fusion or not re.findall(r"s32\[(?:2,)?%d\]" % big, text)
    assert not re.findall(r"f32\[2,%d\]\S* copy\(" % big, text)


@pytest.mark.parametrize("n", [W, W30], ids=["w28", "w30"])
def test_modn_table_write_writes_one_ket(one_chip, n):
    """``POWModNOut(a, N, 0, n/2, n/2)`` as the engine jits it: the slice
    (the ket's first row: a static slice, no gather) and the write, one
    Mosaic launch over the donated ket, which it never reads: the result
    takes its buffer, and beside it stand the slice and the table."""
    from qrack_tpu.engines import tpu

    half = n // 2
    geom = (n, 0, half, half, half)
    ket = 2 * 4 << n
    planes = _shape(one_chip, (2, 1 << n))
    sl = tpu._j_alu_modn_slice.lower(planes, None, *geom).compile()
    assert sl.as_text().startswith("HloModule jit_qrack_alu_modn_slice")
    assert sl.memory_analysis().output_size_in_bytes == 2 * 4 << half
    assert sl.memory_analysis().temp_size_in_bytes <= REGISTER_TEMP_BYTES
    _no_ket_sized_index_work(sl.as_text(), n)

    t0 = time.perf_counter()
    compiled = tpu._j_alu_modn.lower(
        planes, _shape(one_chip, (2, 1 << half)),
        _shape(one_chip, (1 << half,), jnp.int32), *geom, False).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    print(f"compile_s={time.perf_counter() - t0:.2f} qrack_alu_modn "
          f"temp_bytes={memory.temp_size_in_bytes}")
    assert text.startswith("HloModule jit_qrack_alu_modn,")
    assert _launches(compiled) == 1
    assert memory.output_size_in_bytes == ket
    assert memory.alias_size_in_bytes == ket
    assert memory.temp_size_in_bytes <= REGISTER_TEMP_BYTES + (3 * 4 << half)
    assert "input_output_alias={ {}: (0, {}, may-alias) }" in text
    _no_ket_sized_index_work(text, n)


@pytest.mark.parametrize("n", [W, W30], ids=["w28", "w30"])
def test_register_measurement_is_a_reduction_and_a_collapse(one_chip, n):
    """``MReg(0, n/2)``: the reduction is one Mosaic launch that reads
    the planes once into ``2^(n/2)`` float32; the collapse one fusion
    over the donated ket, no predicate of the ket's length beside it
    (the program it replaced kept one: 256 MiB at w28)."""
    from qrack_tpu.engines import tpu

    half, ket = n // 2, 2 * 4 << n
    planes = _shape(one_chip, (2, 1 << n))
    reduced = tpu._j_prob_reg.lower(planes, n, 0, half, False).compile()
    memory, text = reduced.memory_analysis(), reduced.as_text()
    assert text.startswith("HloModule jit_qrack_prob_reg")
    assert _launches(reduced) == 1
    assert memory.output_size_in_bytes == 4 << half
    assert memory.temp_size_in_bytes <= REGISTER_TEMP_BYTES
    _no_ket_sized_index_work(text, n)

    scalar = _shape(one_chip, (), jnp.int32)
    collapsed = tpu._j_collapse.lower(
        planes, scalar, scalar, _shape(one_chip, ())).compile()
    memory, text = collapsed.memory_analysis(), collapsed.as_text()
    assert text.startswith("HloModule jit_qrack_collapse")
    assert memory.alias_size_in_bytes == memory.output_size_in_bytes == ket
    assert memory.temp_size_in_bytes <= REGISTER_TEMP_BYTES
    _no_ket_sized_index_work(text, n, iota_in_fusion=True)


@pytest.fixture(scope="module")
def shor_windows():
    """The windows of one order-finding attempt at w28, as the fuser
    flushes them at the table write and at the measurement."""
    from helpers import benchmark_plans

    with benchmark_plans(W) as windows:
        return [w["structure"] for w in windows("shor")]


@pytest.mark.parametrize("index", range(5),
                         ids=["h-layer", "iqft-1", "iqft-2", "iqft-3",
                              "iqft-4"])
def test_shor_window_is_one_in_tile_sweep(one_chip, shor_windows, index):
    """The H layer and the four windows of ``IQFT(0, 14)``: every target
    under the tile's 16 bits, one launch each, in place."""
    assert [len(s) for s in shor_windows] == [14, 32, 32, 32, 9]
    structure = shor_windows[index]
    plan, why = fu.kernel_lowering(W, structure, backend="tpu")
    assert why is None and plan["sweeps"] == 1 and plan["cross"] == 0
    compiled = _compile(pk.make_window_fn(W, structure),
                        _dense_args(structure, one_chip))
    assert _launches(compiled) == 1
    assert _in_place(compiled)
