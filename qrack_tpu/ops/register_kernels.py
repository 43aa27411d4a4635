"""Whole-ket programs over a register: the ALU's table write and the
register's probabilities (``engines/tpu.py`` ``qrack_alu_modn``,
``qrack_prob_reg``).

Each has two bodies with one meaning.  The *view* body is plain ``jnp``
on the ket seen as ``(2, hi, register, lo)``: right on every backend,
and what the CPU tests run.  On the chip that view is no bitcast: float32
planes ``(2, 2^n)`` lie tiled ``T(2,128)``, the two planes interleaved
every 128 amplitudes, so XLA copies the whole ket into the view's layout
and back (compiled for a described v5e at w28: 2 GiB of temporaries a
program).  The *kernel* body is one ``pallas_call`` over the flat planes
in blocks of ``2^16`` amplitudes, as the window kernels read them
(``ops/pallas_kernels.py``): everything a block needs beside its own
amplitudes is a row of the small operand, picked by the BlockSpec's index
map, and a scalar from the grid step.  A *row* is the ``2^row_pow``
amplitudes below the bits the program works on; the kernel takes rows of
at least one ``(8, 128)`` vreg (``ROW_MIN_POW``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANE_POW = 7
LANES = 1 << LANE_POW
BLOCK_POW = 16
# a row of at least eight sublanes of 128 lanes: below it a block's rows
# are no whole vregs and the body would shuffle sublanes
ROW_MIN_POW = 10
# the operand a write or a reduction keeps beside the ket is a row: the
# table laid out over it (int32), the probabilities summed over it
MODN_ROW_MAX_POW = 22
PROB_ROW_MAX_POW = 18

# the names a device trace knows the two launches by: metadata= rides the
# custom call's frontend attributes, as the window kernels' does
MODN_KERNEL_NAME = "qrack_alu_modn_write"
PROB_KERNEL_NAME = "qrack_prob_reg_sum"

_VMEM_LIMIT_BYTES = 32 << 20


def _pallas():
    """Pallas and its TPU parameters, imported where a kernel is built:
    the engine's module imports this one, and a process that builds no
    kernel (a CPU stack, a fleet worker) should not pay for Pallas."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


# ---------------------------------------------------------------------------
# the table write: out[hi, v, row] = slice[hi, row] where v == f[row]
# ---------------------------------------------------------------------------

def modn_view(n: int, in_start: int, length: int, out_start: int, ol: int):
    """``(dims, out axis, in axis)`` of the ket's index seen from its top
    bit down as ``(hi, upper register, gap, lower register, lo)``."""
    in_low = in_start < out_start
    low, low_len, up, up_len = ((in_start, length, out_start, ol) if in_low
                                else (out_start, ol, in_start, length))
    dims = (1 << (n - up - up_len), 1 << up_len, 1 << (up - low - low_len),
            1 << low_len, 1 << low)
    return (dims, 1, 3) if in_low else (dims, 3, 1)


def modn_write_view(sl, table, planes_shape, dims, out_axis, in_axis):
    """The view body: ``sl`` is the ket's slice at out register 0, of
    shape ``dims`` without the out axis."""
    shape = (2,) + dims
    sl_shape = list(shape)
    sl_shape[out_axis + 1] = 1
    f_shape = [1] * len(shape)
    f_shape[in_axis + 1] = dims[in_axis]
    v = jax.lax.broadcasted_iota(jnp.int32, shape, out_axis + 1)
    out = jnp.where(v == table.reshape(f_shape), sl.reshape(sl_shape),
                    jnp.zeros((), sl.dtype))
    return out.reshape(planes_shape)


def modn_kernel_fits(n, in_start, length, out_start, ol) -> bool:
    """Whether the kernel body takes these registers: the in register and
    everything else the table's value depends on below the out register
    (a row of ``2^out_start`` amplitudes, one table entry each)."""
    return (in_start + length <= out_start
            and ROW_MIN_POW <= out_start <= MODN_ROW_MAX_POW)


def modn_write_kernel(n: int, out_start: int, ol: int, interpret=False):
    """``run(planes, sl, row_table)``: ``planes`` (2, 2^n) is the ket to
    write over, never read (its buffer is the result's); ``sl`` (2,
    2^(n - ol)) the slice; ``row_table`` (1, 2^out_start) int32 the value
    of the out register at which each amplitude of a row stays."""
    pl, compiler_params = _pallas()
    bp = min(BLOCK_POW, out_start + ol)  # a block holds one ``hi``
    block = 1 << bp
    row_pow = min(bp, out_start)  # of the row, what a block holds
    part = 1 << row_pow
    rows = block >> row_pow       # rows a block, consecutive values of v
    parts_pow = out_start - row_pow  # blocks a row
    per_hi_pow = ol + parts_pow - (bp - row_pow)  # blocks a ``hi``
    sub = part >> LANE_POW

    def coords(b):
        """``(hi, first v, part of the row)`` of grid step ``b``."""
        hi, q = b >> per_hi_pow, b & ((1 << per_hi_pow) - 1)
        if rows == 1:
            return hi, q >> parts_pow, q & ((1 << parts_pow) - 1)
        return hi, q * rows, 0

    def kernel(table_ref, sl_ref, _, out_ref):
        v0 = coords(pl.program_id(0))[1]
        f = table_ref[...].reshape(1, sub, LANES)
        s = sl_ref[...].reshape(2, sub, LANES)
        zero = jnp.zeros((), s.dtype)
        out = [jnp.where(f == v0 + k, s, zero) for k in range(rows)]
        out = out[0] if rows == 1 else jnp.concatenate(out, axis=1)
        out_ref[...] = out.reshape(2, block)

    def run(planes, sl, row_table):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((2, 1 << n), planes.dtype),
            grid=(1 << (n - bp),),
            in_specs=[
                pl.BlockSpec((1, part), lambda b: (0, coords(b)[2])),
                pl.BlockSpec((2, part), lambda b: (
                    0, (coords(b)[0] << parts_pow) + coords(b)[2])),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((2, block), lambda b: (0, b)),
            input_output_aliases={2: 0},
            compiler_params=compiler_params,
            interpret=interpret,
            name=MODN_KERNEL_NAME,
            metadata={"qrack_kernel": MODN_KERNEL_NAME},
        )(row_table, sl, planes)

    return run


def modn_row_table(table, in_start: int, length: int, out_start: int):
    """The table laid out over a row of ``2^out_start`` amplitudes:
    entry ``i`` is ``table[(i >> in_start) & (2^length - 1)]``."""
    reps = 1 << (out_start - in_start - length)
    t = jnp.broadcast_to(table[None, :, None],
                         (reps, 1 << length, 1 << in_start))
    return t.reshape(1, 1 << out_start)


# ---------------------------------------------------------------------------
# a register's probabilities: P[r] = sum over planes, hi and lo
# ---------------------------------------------------------------------------

def prob_reg_view(planes, n: int, start: int, length: int):
    x = planes.astype(jnp.float32).reshape(
        2, 1 << (n - start - length), 1 << length, 1 << start)
    return jnp.sum(x * x, axis=(0, 1, 3))


def prob_kernel_fits(n, start, length) -> bool:
    """The kernel sums rows of ``2^(start + length)`` amplitudes over the
    bits above the register; the bits below it are summed after, from
    the row (a small array)."""
    return ROW_MIN_POW <= start + length <= PROB_ROW_MAX_POW


def prob_reg_kernel(n: int, start: int, length: int, interpret=False):
    """``run(planes)`` -> (2^length,) float32: one read of the planes,
    the squares summed in float32 into a row that stays in VMEM."""
    pl, compiler_params = _pallas()
    row_pow = start + length
    bp = min(BLOCK_POW, n)
    part_pow = min(bp, row_pow)       # of the row, what a block holds
    parts_pow = row_pow - part_pow    # blocks a row
    rows = 1 << (bp - part_pow)       # rows a block
    sub = 1 << (part_pow - LANE_POW)
    steps = 1 << (n - bp - parts_pow)  # blocks that add into one part

    def kernel(in_ref, out_ref):
        x = in_ref[...].reshape(2, rows * sub, LANES).astype(jnp.float32)
        p = x[0] * x[0] + x[1] * x[1]
        if rows > 1:
            p = jnp.sum(p.reshape(rows, sub, LANES), axis=0)

        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += p

    def run(planes):
        row = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1 << (row_pow - LANE_POW), LANES),
                                           jnp.float32),
            grid=(1 << parts_pow, steps),
            in_specs=[pl.BlockSpec((2, 1 << bp), lambda j, g: (
                0, (g << parts_pow) + j))],
            out_specs=pl.BlockSpec((sub, LANES), lambda j, g: (j, 0)),
            compiler_params=compiler_params,
            interpret=interpret,
            name=PROB_KERNEL_NAME,
            metadata={"qrack_kernel": PROB_KERNEL_NAME},
        )(planes)
        return jnp.sum(row.reshape(1 << length, 1 << start), axis=1)

    return run
