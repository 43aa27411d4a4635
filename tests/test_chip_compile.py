"""Compile the main path's window programs for a described TPU v5e at
w28 (a 2 GiB float32 ket), without a chip.

Nothing here runs: each case hands the chip's own compiler the shapes
and asserts that it accepts them (section 2 of the on-chip-measurement
guide).  The interpret-mode parity tests cannot see what this sees: a
view Mosaic cannot lay out, a temporary that does not fit 16 GiB.

The topology is described inside a module-scoped fixture, never at
import: only the worker that is handed this file loads the TPU library.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from qrack_tpu.ops import fusion as fu
from qrack_tpu.ops import pallas_kernels as pk

W = 28
KET_BYTES = 2 * 4 << W  # (2, 2^28) float32
SLACK = KET_BYTES >> 6    # operands, masks and the compiler's own scratch

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _ops(structure):
    """Placeholder ops of a window structure: the fuser's own operand
    builders then give the operand shapes, so they cannot drift."""
    return [fu.FusedOp(kind, target, int(has_ctrl), int(has_ctrl), np.eye(2))
            for kind, target, has_ctrl in structure]


def _args(operands, state_sharding, operand_sharding, n=W):
    """Shapes of (planes, *operands), nothing allocated on a device."""
    return [jax.ShapeDtypeStruct((2, 1 << n), jnp.float32,
                                 sharding=state_sharding)] + [
        jax.ShapeDtypeStruct(np.shape(o), o.dtype, sharding=operand_sharding)
        for o in operands]


def _dense_args(structure, sharding):
    return _args(fu.dense_operands(_ops(structure), jnp.float32),
                 sharding, sharding)


def _compile(fn, args):
    return jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()


QFT16 = (("gen", 27, False),) + tuple(
    ("cphase", 26 - k, True) for k in range(15))


@pytest.mark.parametrize("kind,target", [
    ("gen", 0), ("gen", 3), ("gen", 6), ("gen", 7), ("gen", 27),
    ("inv", 0), ("inv", 6)])
def test_xla_one_op_window(one_chip, kind, target):
    structure = ((kind, target, False),)
    compiled = _compile(fu.window_fn(W, structure),
                        _dense_args(structure, one_chip))
    # at most two kets beside the donated one
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * KET_BYTES + SLACK


@pytest.mark.parametrize("structure", [
    (("gen", 0, False),), (("gen", 3, False),), (("gen", 6, False),),
    (("gen", 7, False),), (("gen", 15, False),),
    (("inv", 2, True),), (("inv", 12, True),),
    (("gen", 20, True), ("cphase", 3, True)),
    # the pager's per-page run at w28 / 4 pages that asked for 17 MiB of
    # VMEM: a controlled cross-tile gen with five cphases behind it
    (("gen", 17, True),) + (("cphase", 18, True),) * 5,
], ids=lambda s: "-".join(f"{k}{t}{'c' if c else ''}" for k, t, c in s))
def test_kernel_window(one_chip, structure):
    compiled = _compile(pk.make_window_fn(W, structure),
                        _dense_args(structure, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= KET_BYTES + SLACK


def test_qft_window_xla(one_chip):
    compiled = _compile(fu.window_fn(W, QFT16), _dense_args(QFT16, one_chip))
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * KET_BYTES + SLACK


def test_qft_window_kernel(one_chip):
    compiled = _compile(pk.make_window_fn(W, QFT16),
                        _dense_args(QFT16, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= KET_BYTES + SLACK


def test_sharded_kernel_window_four_pages(topo):
    """The pager's per-page kernel body on a 2x2 mesh: one global gen
    (pair exchange over the pages axis) and one local low-lane gen."""
    npg, L = 4, W - 2
    mesh = Mesh(np.array(topo.devices[:npg]), ("pages",))
    ops = _ops((("gen", 27, False), ("gen", 3, False)))
    body = fu.sharded_kernel_window_body(L, npg, fu.sharded_structure_of(ops))
    args = _args(fu.sharded_operands(ops, L, jnp.float32),
                 NamedSharding(mesh, P(None, "pages")),
                 NamedSharding(mesh, P()))
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, "pages"),) + (P(),) * (len(args) - 1),
                       out_specs=P(None, "pages"), check_vma=False)
    text = _compile(fn, args).as_text()
    assert "collective-permute" in text
    assert "tpu_custom_call" in text
