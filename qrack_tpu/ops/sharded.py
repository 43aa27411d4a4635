"""Pure per-shard gate bodies for 'pages'-mesh programs.

Single source of truth for the sharded gate algebra used by both the
QPager engine programs (qrack_tpu/parallel/pager.py) and the fused
sharded-circuit compiler (QCircuit.compile_sharded_fn). All functions
run INSIDE a shard_map body over mesh axis 'pages': `local` is this
page's (2, 2^L) planes, page selection/masks are split into (local,
page) parts so no global index is ever built (exact past int32).

Reference mapping (SURVEY.md §2.3): in-page broadcast =
src/qpager.cpp:369-397; paged-target pair exchange = :400-447
(ShuffleBuffers becomes lax.ppermute over ICI); meta-controlled page
subsets = :453,563.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import gatekernels as gk


def page_id():
    return jax.lax.axis_index("pages")


def exchange(x, perm):
    """Every page-to-page transfer of this module, under the one scope
    a device trace knows the pager's collectives by."""
    with jax.named_scope("qrack.pager.exchange"):
        return jax.lax.ppermute(x, "pages", perm)


def apply_local_2x2(local, mp, L: int, target: int, lmask, lval, gmask, gval):
    """Non-diagonal gate on an in-page target, optionally page-selected."""
    out = gk.apply_2x2(local, mp, L, target, lmask, lval)
    ok = (page_id() & gmask) == gval
    return jnp.where(ok, out, local)


def apply_global_2x2(local, mp, npg: int, gpos: int, lmask, lval, gmask, gval):
    """Non-diagonal gate on a paged target: half-buffer pair exchange.

    Reference discipline (ShuffleBuffers, src/qpager.cpp:400-447): never
    ship a whole page.  Each page keeps one half (split on the top
    in-page bit), sends the other half to its partner, computes BOTH
    output amplitudes for the half of the local indices it now holds
    complete pairs for, and returns the partner's outputs.  Each
    ppermute payload is half a page and peak extra memory is half a
    page (vs. a full mirror page for whole-page exchange)."""
    if local.shape[-1] < 2:
        # degenerate 1-amplitude page: whole-page exchange
        perm = [(j, j ^ (1 << gpos)) for j in range(npg)]
        pid = page_id()
        b = (pid >> gpos) & 1
        other = exchange(local, perm)
        re, im = mp[0], mp[1]
        dd_re = jnp.where(b == 0, re[0, 0], re[1, 1])
        dd_im = jnp.where(b == 0, im[0, 0], im[1, 1])
        od_re = jnp.where(b == 0, re[0, 1], re[1, 0])
        od_im = jnp.where(b == 0, im[0, 1], im[1, 0])
        out = gk.cmul(dd_re, dd_im, local) + gk.cmul(od_re, od_im, other)
        ok = (pid & gmask) == gval
        return jnp.where(ok, out, local)
    bit = 1 << gpos
    perm = [(j, j ^ bit) for j in range(npg)]
    pid = page_id()
    low = ((pid >> gpos) & 1) == 0   # this page is the pair's a side
    half_n = local.shape[-1] // 2
    # the halves are slices of the minor axis, never a (planes, 2, half)
    # view: behind a kernel launch the TPU compiler lays that view out
    # tile by tile (1172 s for a window of five ops at a 2 GiB page,
    # 2 s sliced: PERF.md §6, PR 35)
    h0, h1 = local[:, :half_n], local[:, half_n:]
    keep = jnp.where(low, h0, h1)
    got = exchange(jnp.where(low, h1, h0), perm)   # half-page payload
    # this page now holds complete (a, b) pairs for local indices with
    # top bit == b: a = partner-0 amplitude, b = partner-1 amplitude.
    # ``keep`` is the a side on a low page and the b side on a high one,
    # so the page picks its four coefficients, not its operands: the
    # sums are the (a, b) form's, term for term, with no page-sized
    # select of (keep, got) into (a, b) and of (a', b') into (mine,
    # theirs) for the compiler to keep beside them (PERF.md §6, PR 39)
    re, im = mp[0], mp[1]

    def coef(on_low, on_high):
        return (jnp.where(low, re[on_low], re[on_high]),
                jnp.where(low, im[on_low], im[on_high]))

    mine = gk.cmul(*coef((0, 0), (1, 1)), keep) \
        + gk.cmul(*coef((0, 1), (1, 0)), got)
    theirs = gk.cmul(*coef((1, 0), (0, 1)), keep) \
        + gk.cmul(*coef((1, 1), (0, 0)), got)
    # control masks: same local index for both outputs, page id differs
    idx = gk.iota_for(keep) + jnp.where(low, 0, half_n)
    lok = (idx & lmask) == lval
    mine = jnp.where(lok & ((pid & gmask) == gval), mine, keep)
    theirs = jnp.where(lok & (((pid ^ bit) & gmask) == gval), theirs, got)
    back = exchange(theirs, perm)    # half-page payload
    lo = jnp.where(low, mine, back)
    hi = jnp.where(low, back, mine)
    return jnp.concatenate([lo, hi], axis=-1)


def apply_diag(local, d0re, d0im, d1re, d1im, tlo, thi, clo, cvlo, chi, cvhi):
    """Diagonal gate with split target/control masks — collective-free."""
    pid = page_id()
    idx = gk.iota_for(local)
    bit = ((idx & tlo) != 0) | ((pid & thi) != 0)
    fre = jnp.where(bit, d1re, d0re)
    fim = jnp.where(bit, d1im, d0im)
    ok = ((idx & clo) == cvlo) & ((pid & chi) == cvhi)
    fre = jnp.where(ok, fre, jnp.ones((), local.dtype))
    fim = jnp.where(ok, fim, jnp.zeros((), local.dtype))
    return gk.cmul(fre, fim, local)


def gather_ring(local, npg: int, L: int, split_body, targs, keep_default=None):
    """Cross-page basis permutation past int32 widths: new[(pid, i)] =
    old[(sp, sl)] with (sp, sl) int32 halves from `split_body` (see
    alu_kernels split variants).  Every page's block rotates once around
    the ring; each page copies out the elements whose source page is the
    block currently in hand.  Traffic: npg-1 page-volumes per device —
    device-side and exact at any width (reference ALU kernels are
    width-generic the same way, qheader_alu.cl:13-810)."""
    pid = page_id()
    lidx = gk.iota_for(local)
    res = split_body(jnp, pid, lidx, L, *targs)
    sp, sl = res[0], res[1]
    keep = res[2] if len(res) > 2 else keep_default
    out = jnp.zeros_like(local)
    buf = local
    perm = [(j, (j - 1) % npg) for j in range(npg)]
    for k in range(npg):
        holder = (pid + k) % npg  # original page id of the block in hand
        take = sp == holder
        if keep is not None:
            take = take & keep
        out = jnp.where(take, buf[:, sl], out)
        if k + 1 < npg:
            buf = exchange(buf, perm)
    return out


def compose_ring(a_local, b, npg: int, L_in: int, start: int, n1: int, n2: int):
    """Device-side Compose: out = A (x) B with B's qubits inserted at
    `start`, built per page with bounded memory (reference:
    CombineEngines assembles each target page from one source page at a
    time, src/qpager.cpp:316-367).

    Runs INSIDE a shard_map body: `a_local` is this page's (2, 2^L_in)
    planes of the n1-qubit ket A, `b` the REPLICATED (2, 2^n2) planes
    of B.  Each output element out[(pid, i)] = A[a_src] * B[j] with
    (a_src, j) decoded from the output's split (page, local) index; the
    ring rotates A's pages so every page sees each source block once.
    Peak per-device memory: out block + one A page + B — never a full
    gather of A (the GSPMD fallback could choose one).  Rounds where
    the source page is always the resident page (B below the page
    bits) skip the rotation entirely and the program is collective-free.
    Requires n1, n2 <= 31 (int32 index lanes); wider composes use the
    einsum fallback."""
    pid = page_id()
    L_out = L_in + n2
    i = jax.lax.iota(gk.IDX_DTYPE, 1 << L_out)

    def field(lo: int, width: int):
        """Bits [lo, lo+width) of the global output index, split-read
        from (i, pid) without forming a >int32 global index."""
        if width <= 0:
            return jnp.zeros((), gk.IDX_DTYPE)
        out = jnp.zeros((), gk.IDX_DTYPE)
        take = 0
        if lo < L_out:
            take = min(width, L_out - lo)
            out = (i >> lo) & ((1 << take) - 1)
        if lo + width > L_out:
            plo = max(lo, L_out) - L_out
            pw = lo + width - max(lo, L_out)
            out = out | (((pid >> plo) & ((1 << pw) - 1)) << take)
        return out

    l = field(0, start)
    j = field(start, n2)
    h = field(start + n2, n1 - start)
    a_src = (h << start) | l
    sp = a_src >> L_in
    sl = a_src & ((1 << L_in) - 1)
    br, bi = b[0][j], b[1][j]
    # B below the page bits (start <= L_in): the source page id equals
    # the resident page id for every element — no rotation needed
    aligned = start <= L_in
    out = jnp.zeros((a_local.shape[0], 1 << L_out), a_local.dtype)
    buf = a_local
    perm = [(k, (k - 1) % npg) for k in range(npg)]
    for k in range(npg if not aligned else 1):
        holder = (pid + k) % npg
        take = sp == holder if not aligned else None
        ar, ai = buf[0][sl], buf[1][sl]
        vr = ar * br - ai * bi
        vi = ar * bi + ai * br
        vals = jnp.stack([vr, vi])
        out = vals if take is None else jnp.where(take, vals, out)
        if k + 1 < npg and not aligned:
            buf = exchange(buf, perm)
    return out


# ---------------------------------------------------------------------------
# batched exchange collectives: ANY sequence of physical bit-position
# transpositions composes into one permutation, which lowers as
#   L_post . page_perm . mixed_batch . L_pre
# where L_pre/L_post are free in-page bit shuffles, mixed_batch moves the
# k boundary-crossing sub-buffers in 2^k-1 sub-block ppermutes totalling
# (1 - 2^-k) state volumes (the sub-block on the diagonal of the k pair
# axes never leaves its page; mpiQulacs' fused multi-qubit exchange,
# arXiv:2203.16044), and page_perm is one whole-slab ppermute for any
# residual page-bit permutation.
# ---------------------------------------------------------------------------

class ExchangePlan(NamedTuple):
    """Static decomposition of a composed bit permutation (host-side)."""
    pre: tuple        # local transpositions before the exchange (free)
    k: int            # boundary-crossing pair count
    gpos: tuple       # page bit paired with carrier local bit (L-k+j)
    page_dest: tuple  # page-bit position map i -> page_dest[i], or None
    post: tuple       # local transpositions after the exchange (free)


def compose_swaps(n: int, swaps):
    """``src[p]`` = original position of the content that a sequential
    application of ``swaps`` leaves at position p."""
    src = list(range(n))
    for p1, p2 in swaps:
        src[p1], src[p2] = src[p2], src[p1]
    return src


def _perm_swaps(f):
    """Transpositions realizing position map f (content at x ends at
    f[x]) when applied in order — selection-sort cycle decomposition,
    <= len(f)-1 pairs."""
    n = len(f)
    cur = list(range(n))   # cur[p] = content at position p
    pos = list(range(n))   # pos[c] = position of content c
    g = [0] * n
    for x in range(n):
        g[f[x]] = x
    out = []
    for p in range(n):
        c = g[p]
        q = pos[c]
        if q != p:
            out.append((p, q))
            c2 = cur[p]
            cur[p], cur[q] = c, c2
            pos[c], pos[c2] = p, q
    return tuple(out)


def plan_exchange(L: int, g: int, swaps):
    """Decompose a transposition sequence over L local + g page bits into
    an :class:`ExchangePlan`.  None when the composition is identity."""
    n = L + g
    src = compose_swaps(n, swaps)
    dest = [0] * n
    for p in range(n):
        dest[src[p]] = p
    if all(dest[c] == c for c in range(n)):
        return None
    cross_in = [c for c in range(L) if dest[c] >= L]   # local -> page
    crossers = [t for t in range(L, n) if dest[t] < L]  # page -> local
    k = len(cross_in)
    carriers = list(range(L - k, L))
    # pair each crossing content with the carrier of the page slot it is
    # DESTINED for whenever that slot is itself vacating (crossers[j]
    # receives carrier j's content) — the planner's disjoint
    # local<->global batches then leave an IDENTITY residual page
    # permutation instead of paying a whole-slab ppermute to fix an
    # arbitrary pairing
    by_dest = {dest[c]: c for c in cross_in}
    ordered = [by_dest.pop(t, None) for t in crossers]
    leftovers = iter(c for c in cross_in if c in by_dest.values())
    cross_in = [c if c is not None else next(leftovers) for c in ordered]
    # which pair rides which carrier is free: a crossing content that
    # already sits on a carrier bit keeps it, so a victim among the top
    # k local bits costs no pass over the page before the exchange (and
    # none after it, where the swap is a plain transposition)
    slots = [None] * k
    rest = []
    for pair in zip(cross_in, crossers):
        j = pair[0] - (L - k)
        if 0 <= j < k and slots[j] is None:
            slots[j] = pair
        else:
            rest.append(pair)
    rest = iter(rest)
    slots = [pair if pair is not None else next(rest) for pair in slots]
    cross_in = [c for c, _ in slots]
    crossers = [t for _, t in slots]
    # pre-shuffle: crossing local contents onto the carrier (top-k) bits,
    # everything else staying put where possible
    A = {c: carriers[j] for j, c in enumerate(cross_in)}
    freeset = {p for p in range(L) if p not in set(A.values())}
    later = []
    for c in range(L):
        if c in A:
            continue
        if c in freeset:
            A[c] = c
            freeset.discard(c)
        else:
            later.append(c)
    for c, p in zip(later, sorted(freeset)):
        A[c] = p
    pre = _perm_swaps([A[c] for c in range(L)])
    gpos = tuple(t - L for t in crossers)
    # residual page permutation after the mixed batch: position t holds
    # the content that crossed in (dest >= L for it), other page bits
    # keep their own content
    content_at_page = {t: cross_in[j] for j, t in enumerate(crossers)}
    page_dest = tuple(dest[content_at_page.get(L + i, L + i)] - L
                      for i in range(g))
    if all(page_dest[i] == i for i in range(g)):
        page_dest = None
    # post-shuffle: carriers now hold the crossed-in page contents; send
    # every local content to its final slot
    content_at = {carriers[j]: t for j, t in enumerate(crossers)}
    content_at.update({A[c]: c for c in range(L) if c not in cross_in})
    post = _perm_swaps([dest[content_at[x]] for x in range(L)])
    return ExchangePlan(pre, k, gpos, page_dest, post)


def page_perm_of(page_dest, g: int):
    """[(src_page, dst_page)] total map for a page-bit position map."""
    npg = 1 << g
    perm = []
    for j in range(npg):
        r = 0
        for i in range(g):
            if (j >> i) & 1:
                r |= 1 << page_dest[i]
        perm.append((j, r))
    return perm


def _pick(index, choices):
    """``choices[index]`` for a traced scalar ``index``: whole-array
    selects over static operands, no traced slice start."""
    out = choices[0]
    for s in range(1, len(choices)):
        out = jnp.where(index == s, choices[s], out)
    return out


def batched_mixed_swap(local, npg: int, k: int, gpos):
    """k disjoint mixed transpositions — carrier local bits [L-k, L)
    against page bits ``gpos`` — as one batched exchange: for every
    non-zero offset d over the k pair axes, each page ships the 2^-k
    sub-block its XOR-d partner needs, in one ppermute.  The d=0
    diagonal never moves, so total traffic is (1 - 2^-k) state volumes
    and all 2^k - 1 transfers are independent (one collective round on
    hardware that overlaps them).

    The sub-blocks are static slices of the minor axis, chosen by
    selects on this page's bits and joined with ``concatenate``: no
    ``(planes, 2^k, -1)`` view and no slice or update at a traced index
    (as ``apply_global_2x2``: PERF.md §6, PR 35 and PR 38)."""
    pid = page_id()
    nsub = 1 << k
    size = local.shape[-1] // nsub
    sub = [local[:, s * size:(s + 1) * size] for s in range(nsub)]
    b = jnp.zeros((), pid.dtype)
    for j, gp in enumerate(gpos):
        b = b | (((pid >> gp) & 1) << j)
    got = [None] * nsub   # got[d]: what the XOR-d partner sent
    for d in range(1, nsub):
        pd = 0
        for j, gp in enumerate(gpos):
            if (d >> j) & 1:
                pd |= 1 << gp
        perm = [(j2, j2 ^ pd) for j2 in range(npg)]
        got[d] = exchange(_pick(b ^ d, sub), perm)
    # slot s keeps its own block on the page whose bits are s and else
    # holds what the partner at offset s ^ b sent
    out = [_pick(b ^ s, [sub[s]] + got[1:]) for s in range(nsub)]
    return jnp.concatenate(out, axis=-1)


def apply_remap(local, npg: int, L: int, swaps):
    """Batched placement change: apply a sequence of PHYSICAL bit-position
    transpositions (p1, p2).  The planner (ops/fusion.py plan_remaps)
    emits these as the prologue of a fused window program, so remap +
    window is ONE dispatch.

    The whole sequence composes into one permutation and lowers through
    :func:`plan_exchange` — free local shuffles, one (1-2^-k)-volume
    mixed batch, one residual page ppermute."""
    g = npg.bit_length() - 1
    plan = plan_exchange(L, g, swaps)
    if plan is None:
        return local
    for p1, p2 in plan.pre:
        local = gk.swap_bits(local, L, p1, p2)
    if plan.k:
        local = batched_mixed_swap(local, npg, plan.k, plan.gpos)
    if plan.page_dest is not None:
        local = exchange(local, page_perm_of(plan.page_dest, g))
    for p1, p2 in plan.post:
        local = gk.swap_bits(local, L, p1, p2)
    return local


def exchange_cost(L: int, g: int, swaps, weights=None) -> float:
    """Host-side accounting twin of :func:`apply_remap`: the fraction of
    state nbytes the lowering ships.  ``weights`` (per page bit, e.g.
    DCN > ICI from parallel/cluster.py) turn bytes into planner cost
    units; None counts raw bytes."""
    def w(bits):
        if not weights:
            return 1.0
        return max(weights[b] for b in bits)

    plan = plan_exchange(L, g, swaps)
    if plan is None:
        return 0.0
    tot = 0.0
    nsub = 1 << plan.k
    for d in range(1, nsub):
        tot += w([plan.gpos[j] for j in range(plan.k)
                  if (d >> j) & 1]) / nsub
    if plan.page_dest is not None:
        npg = 1 << g
        for j, r in page_perm_of(plan.page_dest, g):
            if r != j:
                tot += w([b for b in range(g) if ((j ^ r) >> b) & 1]) / npg
    return tot


def split_masks(mask: int, val: int, local_bits: int):
    lmask = mask & ((1 << local_bits) - 1)
    lval = val & ((1 << local_bits) - 1)
    return lmask, lval, mask >> local_bits, val >> local_bits
