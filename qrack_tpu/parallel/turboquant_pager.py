"""QPagerTurboQuant: the block-compressed ket sharded over a device mesh.

Composes the two width stories (reference: StateVectorTurboQuant usable
under any engine consumer, include/statevector_turboquant.hpp:1-120 —
there the compressed storage sits under QEngineCPU, which QPager then
pages; here the compressed CHUNK AXIS is itself the sharded axis):

* resident state is the same (B, 2D) int8/int16 codes + (B,) f32 scales
  as QEngineTurboQuant, placed with a NamedSharding over a 1-D "pages"
  mesh on the chunk-major leading axis — each device holds its chunks'
  codes in HBM, so an N-device mesh stores an (int8) ket 4*N x wider
  than one device's f32 planes;
* the chunked gate programs are the SAME run bodies as the single-device
  engine (engines/turboquant.py _mk_*), wrapped in jax.shard_map with
  the per-page chunk-id offset fed in as cid0 — a gate is still O(1)
  dispatches, now SPMD across the mesh;
* a gate target living in the PAGE bits exchanges partner chunks with
  jax.lax.ppermute — the pager's half-buffer pair exchange
  (parallel/pager.py), except the ICI traffic is b-bit codes, 4x (int8)
  less than the f32 pager moves for the same logical amplitudes;
* probability masks psum across the mesh; chunk-aligned collapse stays a
  pure per-chunk scale update (no decompress, no collective).

Everything else (ALU permutations, compose/decompose, amplitude pages)
falls back through the inherited `_state` property: the full-ket
decompress is a plain jitted matmul over the sharded codes, which GSPMD
partitions across the mesh, and the inherited dense kernels then run
auto-partitioned — the CombineAndOp-style escape hatch, kept sharded.
The hatch is only sound up to MAX_DENSE_QB total qubits (the dense
kernels use flat int32 indices); past that the chunked op set — gates,
probabilities, collapse, measurement, SetPermutation — is the whole
legal surface, and fallback ops raise a MemoryError saying so.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engines import turboquant as tqe
from ..ops import gatekernels as gk
from ..utils.bits import is_pow2, log2


def _shard_map(fn, mesh, in_specs, out_specs, **kw):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


class QPagerTurboQuant(tqe.QEngineTurboQuant):
    """Sharded compressed dense ket (chunk axis over a "pages" mesh)."""

    # the Pallas fused path is single-device; the mesh keeps shard_map
    _pallas_capable = False
    # gate-window fusion likewise: the window body is single-device
    # (plain lax.map over local chunks); the sharded gate programs stay
    # per-gate until a shard_map window variant exists
    _fuse_capable = False
    _tele_name = "turboquant_pager"

    def __init__(self, qubit_count: int, init_state: int = 0, devices=None,
                 n_pages=None, **kwargs):
        if devices is None:
            from .pager import pager_devices_from_env

            devices = pager_devices_from_env() or jax.devices()
        if n_pages is None:
            n_pages = 1 << log2(len(devices))
        if not is_pow2(n_pages):
            raise ValueError("n_pages must be a power of two")
        if n_pages > len(devices):
            raise ValueError(
                f"n_pages={n_pages} exceeds available devices "
                f"({len(devices)})")
        if qubit_count <= log2(n_pages):
            raise ValueError(
                f"width {qubit_count} too small for {n_pages} pages")
        self.n_pages = int(n_pages)
        self.g_bits = log2(n_pages)
        self.mesh = Mesh(np.array(list(devices)[:n_pages]), ("pages",))
        self._code_sharding = NamedSharding(self.mesh, P("pages", None))
        self._scale_sharding = NamedSharding(self.mesh, P("pages"))
        super().__init__(qubit_count, init_state=init_state, **kwargs)

    # ------------------------------------------------------------------
    # layout
    # ------------------------------------------------------------------

    def _max_chunk_pow(self, qubit_count: int) -> int:
        # every page must own at least one chunk
        return max(1, qubit_count - self.g_bits)

    def _layout_key(self):
        # mesh identity in the key: cached shard_map programs close over
        # the mesh, so two instances on different device sets must not
        # share them (same rule as QPager._key).  The token is id(mesh)
        # weakly tied to the mesh — entries are purged when it dies.
        return super()._layout_key() + (
            self.n_pages, tqe._PROGRAMS.mesh_token(self.mesh))

    def _local_chunk_bits(self) -> int:
        return self.qubit_count - self._tq_chunk_pow - self.g_bits

    def _check_capacity(self, qubit_count: int) -> None:
        # per-DEVICE compressed cap, multiplied across the mesh
        cap = self._compressed_cap() + self.g_bits
        if qubit_count > cap:
            raise MemoryError(
                f"QPagerTurboQuant width {qubit_count} exceeds "
                f"{self.n_pages} devices' compressed capacity ({cap} at "
                f"{self._tq_bits}-bit codes); add devices or layer "
                "QUnit above")

    def _maybe_repage(self, width: int) -> None:
        """Dispose/Decompose can shrink the width below one chunk per
        page; re-mesh onto a device prefix so every page keeps >= 1
        chunk (the pager's page-count policy under narrowing,
        src/qpager.cpp:89-292 analogue).  `width` is the NEW register
        width (qubit_count itself is adjusted by the structure op after
        the kernel runs)."""
        want = min(self.n_pages, 1 << max(0, width - 1))
        if want == self.n_pages:
            return
        devs = list(self.mesh.devices.flat)[:want]
        self.n_pages = want
        self.g_bits = log2(want)
        self.mesh = Mesh(np.array(devs), ("pages",))
        self._code_sharding = NamedSharding(self.mesh, P("pages", None))
        self._scale_sharding = NamedSharding(self.mesh, P("pages"))

    def _compress_planes(self, planes) -> None:
        import math

        self._maybe_repage(int(round(math.log2(planes.shape[-1]))))
        super()._compress_planes(planes)
        self._codes = jax.device_put(self._codes, self._code_sharding)
        self._scales = jax.device_put(self._scales, self._scale_sharding)

    def _perm_out_shardings(self):
        # codes-native SetPermutation materializes per-shard on the mesh
        return (self._code_sharding, self._scale_sharding)

    def GetDeviceList(self):
        return [int(d.id) for d in self.mesh.devices.flat]

    def resident_bytes_per_device(self) -> int:
        return self.resident_bytes() // self.n_pages

    # ------------------------------------------------------------------
    # sharded program wrappers: same run bodies, shard_map + cid0
    # ------------------------------------------------------------------

    def _wrap(self, run, n_rep: int, donate=(0, 1), psum_out=False):
        """shard_map a _mk_* run body: codes/scales sharded on the chunk
        axis, `n_rep` trailing operands replicated, cid0 = page offset."""
        mesh = self.mesh

        def build():
            def shard_fn(codes3, scales2, *rest):
                pid = jax.lax.axis_index("pages")
                cid0 = (pid * codes3.shape[0]).astype(gk.IDX_DTYPE)
                out = run(codes3, scales2, *rest, cid0)
                if psum_out:
                    return jax.lax.psum(out, "pages")
                return out

            out_specs = (P() if psum_out
                         else (P("pages"), P("pages")))
            f = _shard_map(shard_fn, mesh,
                           (P("pages"), P("pages")) + (P(),) * n_rep,
                           out_specs)
            return jax.jit(f, donate_argnums=donate)

        return build

    def _p_gate_low(self, target: int):
        run = tqe._mk_gate_low(self._tq_chunk_pow, self._block,
                               self._code_np, self._qmax, target)
        return tqe._program(("tqp_low", self._layout_key(), target),
                            self._wrap(run, 7))

    def _p_gate_pair(self, tb_pos: int):
        lcb = self._local_chunk_bits()
        if tb_pos < lcb:
            run = tqe._mk_gate_pair(self._tq_chunk_pow, self._block,
                                    self._code_np, self._qmax, tb_pos)
            return tqe._program(("tqp_pair", self._layout_key(), tb_pos),
                                self._wrap(run, 7))
        return self._p_gate_pair_cross(tb_pos - lcb)

    def _p_gate_pair_cross(self, page_bit: int):
        """Target bit lives in the PAGE bits: ppermute partner chunk
        codes over the mesh (compressed ICI traffic), each side computes
        its half of the 2x2 mix (pager half-buffer exchange,
        parallel/pager.py MetaSwap/global-gate family)."""
        ca, block = self._tq_chunk_pow, self._block
        cdt, qmax = self._code_np, self._qmax
        n_pages, lcb = self.n_pages, self._local_chunk_bits()
        mesh = self.mesh
        perm = [(i, i ^ (1 << page_bit)) for i in range(n_pages)]
        if tqe._tele._ENABLED:
            # compressed ICI: every page ppermutes its whole codes+scales
            # shard to its pair partner (the b-bit win rides the wire too)
            tqe._tele.inc("exchange.turboquant_pager.cross_gate")
            tqe._tele.inc("exchange.turboquant_pager.bytes",
                          self._codes.nbytes + self._scales.nbytes)

        def build():
            def shard_fn(codes3, scales2, rot, rot_t, mp,
                         hi_cmask, hi_cval, lo_cmask, lo_cval):
                pid = jax.lax.axis_index("pages")
                oc = jax.lax.ppermute(codes3, "pages", perm)
                osc = jax.lax.ppermute(scales2, "pages", perm)
                is_a = ((pid >> page_bit) & 1) == 0
                # global chunk id of local chunk i on the pair's a-side
                pid_a = pid & ~(1 << page_bit)
                cid0_a = (pid_a << lcb).astype(gk.IDX_DTYPE)

                def body(args):
                    i, cc, ss, occ, oss = args
                    mine = tqe._rows_to_planes(
                        tqe._dec_rows_f(cc, ss, rot_t, qmax), block)
                    their = tqe._rows_to_planes(
                        tqe._dec_rows_f(occ, oss, rot_t, qmax), block)
                    a = jnp.where(is_a, mine, their)
                    b = jnp.where(is_a, their, mine)
                    na, nb = tqe._pair_mix_f(a, b, mp, lo_cmask, lo_cval)
                    keep = jnp.where(is_a, na, nb)
                    nc, ns = tqe._comp_rows_f(
                        tqe._planes_to_rows(keep, block), rot, qmax, cdt)
                    sel = ((cid0_a + i) & hi_cmask) == hi_cval
                    return jnp.where(sel, nc, cc), jnp.where(sel, ns, ss)

                cids = jnp.arange(codes3.shape[0], dtype=gk.IDX_DTYPE)
                return jax.lax.map(body, (cids, codes3, scales2, oc, osc))

            f = _shard_map(shard_fn, mesh,
                           (P("pages"), P("pages")) + (P(),) * 7,
                           (P("pages"), P("pages")))
            return jax.jit(f, donate_argnums=(0, 1))

        return tqe._program(("tqp_cross", self._layout_key(), page_bit),
                            build, site="turboquant_pager.exchange")

    def _p_diag(self):
        run = tqe._mk_diag(self._tq_chunk_pow, self._block, self._code_np,
                           self._qmax)
        return tqe._program(("tqp_diag", self._layout_key()),
                            self._wrap(run, 12))

    def _p_phase_split(self, key, body_fn, n_targs: int):
        run = tqe._mk_phase_split(self._tq_chunk_pow, self._block,
                                  self._code_np, self._qmax, body_fn)
        mesh = self.mesh

        def build():
            def shard_fn(codes3, scales2, rot, rot_t, *targs):
                pid = jax.lax.axis_index("pages")
                cid0 = (pid * codes3.shape[0]).astype(gk.IDX_DTYPE)
                return run(codes3, scales2, rot, rot_t, cid0, *targs)

            f = _shard_map(shard_fn, mesh,
                           (P("pages"), P("pages")) + (P(),) * (2 + n_targs),
                           (P("pages"), P("pages")))
            return jax.jit(f, donate_argnums=(0, 1))

        if key is None:
            return build()
        return tqe._program(("tqp_phase", self._layout_key(), tuple(key)),
                            build)

    def _p_prob_mask(self):
        run = tqe._mk_prob_mask(self._tq_chunk_pow, self._block, self._qmax)
        return tqe._program(("tqp_probmask", self._layout_key()),
                            self._wrap(run, 5, donate=(), psum_out=True))

    def _p_collapse(self):
        run = tqe._mk_collapse(self._tq_chunk_pow, self._block,
                               self._code_np, self._qmax)
        return tqe._program(("tqp_collapse", self._layout_key()),
                            self._wrap(run, 7))

    # ------------------------------------------------------------------
    # multi-host-safe reads: masses gather with a collective, one-chunk
    # decompression lands replicated (the only legal read patterns when
    # no process addresses every shard — parallel/cluster.py)
    # ------------------------------------------------------------------

    def _chunk_masses(self, c3, s2) -> np.ndarray:
        qmax = self._qmax
        mesh = self.mesh

        def build():
            def shard_fn(codes3, scales2):
                y = (codes3.astype(jnp.float32)
                     * (scales2 / qmax)[..., None])
                local = jnp.sum(y * y, axis=(1, 2))
                return jax.lax.all_gather(local, "pages").reshape(-1)

            # all_gather output IS replicated; the static VMA checker
            # cannot infer that, so disable it for this program only
            f = _shard_map(shard_fn, mesh, (P("pages"), P("pages")), P(),
                           check_vma=False)
            return jax.jit(f)

        prog = tqe._program(("tqp_masses", self._layout_key()), build)
        out = prog(c3, s2)
        if out.is_fully_addressable:
            return np.asarray(out, dtype=np.float64)
        return np.asarray(out.addressable_shards[0].data, dtype=np.float64)

    def _dec_chunk(self, c: int):
        cb, block, qmax = self._chunk_blocks, self._block, self._qmax

        def build():
            def run(codes3, scales2, rot_t, cid):
                # chunk-major dynamic_slice: the chunk id stays int32 at
                # any width (a flat block offset c*cb would overflow)
                cc = jax.lax.dynamic_slice(
                    codes3, (cid, 0, 0), (1, cb, codes3.shape[-1]))
                ss = jax.lax.dynamic_slice(scales2, (cid, 0), (1, cb))
                rows = tqe._dec_rows_f(cc.reshape(cb, -1),
                                       ss.reshape(cb), rot_t, qmax)
                return tqe._rows_to_planes(rows, block)

            return jax.jit(run, out_shardings=NamedSharding(self.mesh, P()))

        prog = tqe._program(("tqp_dec_chunk", self._layout_key()), build)
        c3, s2 = self._chunk3()
        return prog(c3, s2, self._rot_t, jnp.asarray(c, gk.IDX_DTYPE))

    def _fetch_blocks(self, b0: int, nb: int):
        """Replicated per-chunk dynamic-slice fetch of block rows:
        multi-host legal (raw host indexing of the sharded arrays would
        raise on non-addressable shards) and int32-safe via the
        two-level (chunk, block-in-chunk) addressing."""
        cb = self._chunk_blocks
        c3, s2 = self._chunk3()
        parts_c, parts_s = [], []
        b = b0
        left = nb
        while left > 0:
            cid, boff = divmod(b, cb)
            take = min(left, cb - boff)

            def build(take=take):
                def run(codes3, scales2, cid, boff):
                    cc = jax.lax.dynamic_slice(
                        codes3, (cid, boff, 0),
                        (1, take, codes3.shape[-1]))
                    ss = jax.lax.dynamic_slice(scales2, (cid, boff),
                                               (1, take))
                    return cc.reshape(take, -1), ss.reshape(take)

                rep = NamedSharding(self.mesh, P())
                return jax.jit(run, out_shardings=(rep, rep))

            prog = tqe._program(
                ("tqp_blockrows", self._layout_key(), take), build)
            cc, ss = prog(c3, s2, jnp.asarray(cid, gk.IDX_DTYPE),
                          jnp.asarray(boff, gk.IDX_DTYPE))
            parts_c.append(self._host_rows(cc))
            parts_s.append(self._host_rows(ss))
            b += take
            left -= take
        return (np.concatenate(parts_c).astype(np.float32),
                np.concatenate(parts_s).astype(np.float32))

    @staticmethod
    def _host_rows(x) -> np.ndarray:
        if getattr(x, "is_fully_addressable", True):
            return np.asarray(x)
        return np.asarray(x.addressable_shards[0].data)

    def _p_collapse_scales(self):
        run = tqe._mk_collapse_scales()
        mesh = self.mesh

        def build():
            def shard_fn(scales2, mask_hi, val_hi, scale):
                pid = jax.lax.axis_index("pages")
                cid0 = (pid * scales2.shape[0]).astype(gk.IDX_DTYPE)
                return run(scales2, mask_hi, val_hi, scale, cid0)

            f = _shard_map(shard_fn, mesh,
                           (P("pages"),) + (P(),) * 3, P("pages"))
            return jax.jit(f, donate_argnums=(0,))

        return tqe._program(("tqp_collapse_s", self._layout_key()), build)

    # ------------------------------------------------------------------
    # checkpoint protocol: capture inherits (codes come to the host via
    # np.asarray — a real devget); restore re-lands them on the mesh
    # ------------------------------------------------------------------

    _ckpt_kind = "turboquant_pager"

    def _ckpt_place(self, codes: np.ndarray, scales: np.ndarray) -> None:
        self._codes = jax.device_put(jnp.asarray(codes), self._code_sharding)
        self._scales = jax.device_put(jnp.asarray(scales),
                                      self._scale_sharding)
