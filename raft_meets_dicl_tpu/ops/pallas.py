"""Pallas TPU kernels for profiled hot paths.

The XLA-composite ops in this package are the default implementation;
kernels here replace the ones where profiling on real hardware showed the
compiler-scheduled form paying large materialization/layout costs.

``convex_combine_8x`` — the RAFT convex-upsampling mask combine
(reference Up8Network core, src/models/impls/raft.py:313-331). The
XLA form (softmax + einsum over a (N, h, w, 64, 9) mask) materializes
~750 MB of f32 intermediates with layout copies per training step at the
bench config (batch 6, 400x720, 12 iterations — the mask is built for
all iterations at once); profiled at ~70 ms/step of the 425 ms total.
The kernel fuses softmax and combine per row tile: only the 576-channel
logits are read and the 128-channel result written, nothing else touches
HBM. Forward and backward are both Pallas; the VJP recomputes the
softmax from the saved logits instead of storing probabilities.

Layout contract (matches torch RAFT's ``view(b, 1, 9, 8, 8, h, w)``):
logits channels are neighbor-major ``k * 64 + s`` (k = 3x3 neighbor
row-major, s = subpixel ``r * 8 + c``); outputs are ``chan * 64 + s``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

def _per_shard(fn):
    """``fn`` mapped over the batch shards of the step being traced.

    Mosaic kernels cannot be partitioned automatically ("wrap the call
    in a shard_map"): under an SPMD mesh the jitted step fails to lower
    at the first kernel. All three families are independent per sample
    (per row, for the combine), and the step builders split the leading
    dimension of every activation over every mesh axis
    (``partition.batch_spec``), so each device runs the kernel on the
    shard it already holds and nothing moves. Off-TPU the XLA references
    are traced, which the partitioner handles itself.
    """
    from ..parallel.mesh import traced_mesh

    mesh = traced_mesh()
    if (mesh is None or mesh.devices.size == 1
            or jax.default_backend() != "tpu"):
        return fn
    lead = P(tuple(mesh.axis_names))
    return jax.shard_map(_under_its_scope(fn), mesh=mesh, in_specs=lead,
                         out_specs=lead, check_vma=False)


_TILE = 512
_K = 9       # 3x3 neighbors
_S = 64      # 8x8 subpixels
_C = 2       # flow channels


def _softmax_slices(logits, inv_temp):
    """Grouped softmax over the 9 neighbor blocks of (T, 576) logits,
    returned as unnormalized exps + reciprocal of the partition sum —
    column-slice arithmetic only (no reshapes: Mosaic-friendly)."""
    xs = [logits[:, _S * k: _S * (k + 1)] * inv_temp for k in range(_K)]
    m = xs[0]
    for k in range(1, _K):
        m = jnp.maximum(m, xs[k])
    es = [jnp.exp(x - m) for x in xs]
    denom = es[0]
    for k in range(1, _K):
        denom = denom + es[k]
    return es, 1.0 / denom


def _fwd_kernel(logits_ref, win_ref, out_ref, *, inv_temp):
    x = logits_ref[:].astype(jnp.float32)   # (T, 576)
    w = win_ref[:].astype(jnp.float32)      # (T, 18), layout k*2 + c

    es, inv = _softmax_slices(x, inv_temp)

    acc0 = es[0] * w[:, 0:1]
    acc1 = es[0] * w[:, 1:2]
    for k in range(1, _K):
        acc0 = acc0 + es[k] * w[:, 2 * k: 2 * k + 1]
        acc1 = acc1 + es[k] * w[:, 2 * k + 1: 2 * k + 2]

    out_ref[:, 0:_S] = acc0 * inv
    out_ref[:, _S: 2 * _S] = acc1 * inv


def _bwd_kernel(logits_ref, win_ref, dout_ref, dlogits_ref, dwin_ref, *,
                inv_temp):
    x = logits_ref[:].astype(jnp.float32)
    w = win_ref[:].astype(jnp.float32)
    d0 = dout_ref[:, 0:_S]
    d1 = dout_ref[:, _S: 2 * _S]

    es, inv = _softmax_slices(x, inv_temp)

    ps, dps, dwin_cols = [], [], []
    s_acc = None
    for k in range(_K):
        p_k = es[k] * inv
        dp_k = d0 * w[:, 2 * k: 2 * k + 1] + d1 * w[:, 2 * k + 1: 2 * k + 2]
        dwin_cols.append(jnp.sum(p_k * d0, axis=1, keepdims=True))
        dwin_cols.append(jnp.sum(p_k * d1, axis=1, keepdims=True))
        term = p_k * dp_k
        s_acc = term if s_acc is None else s_acc + term  # Σ_k p_k·dp_k
        ps.append(p_k)
        dps.append(dp_k)

    dl = [ps[k] * (dps[k] - s_acc) * inv_temp for k in range(_K)]
    dlogits_ref[:] = jnp.concatenate(dl, axis=1).astype(dlogits_ref.dtype)
    dwin_ref[:] = jnp.concatenate(dwin_cols, axis=1)


def _pad_rows(x, tile):
    m = x.shape[0]
    pad = (-m) % tile
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, m


def _run_fwd(logits2d, win2d, inv_temp, interpret=False):
    logits2d, m = _pad_rows(logits2d, _TILE)
    win2d, _ = _pad_rows(win2d, _TILE)
    grid = (logits2d.shape[0] // _TILE,)

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, inv_temp=inv_temp),
        out_shape=jax.ShapeDtypeStruct((logits2d.shape[0], _C * _S),
                                       jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE, _K * _S), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE, _K * _C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_TILE, _C * _S), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
    )(logits2d, win2d)
    return out[:m]


def _run_bwd(logits2d, win2d, dout2d, inv_temp, interpret=False):
    logits2d, m = _pad_rows(logits2d, _TILE)
    win2d, _ = _pad_rows(win2d, _TILE)
    dout2d, _ = _pad_rows(dout2d, _TILE)
    grid = (logits2d.shape[0] // _TILE,)

    dlogits, dwin = pl.pallas_call(
        functools.partial(_bwd_kernel, inv_temp=inv_temp),
        out_shape=(
            jax.ShapeDtypeStruct(logits2d.shape, logits2d.dtype),
            jax.ShapeDtypeStruct(win2d.shape, jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE, _K * _S), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE, _K * _C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE, _C * _S), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((_TILE, _K * _S), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE, _K * _C), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        # f32 callers (the ctf family runs un-mixed) land just past the
        # 16M default with double buffering
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
    )(logits2d, win2d, dout2d)
    return dlogits[:m], dwin[:m]


def _run_fwd_interpret(logits2d, win2d, inv_temp):
    """Interpreter-mode forward (kernel correctness tests off-TPU)."""
    return _run_fwd(logits2d, win2d, inv_temp, interpret=True)


def _run_bwd_interpret(logits2d, win2d, dout2d, inv_temp):
    """Interpreter-mode backward (kernel correctness tests off-TPU)."""
    return _run_bwd(logits2d, win2d, dout2d, inv_temp, interpret=True)


def _combine_reference(logits2d, win2d, inv_temp):
    """XLA fallback with identical semantics (used off-TPU and as the
    numerical reference in tests)."""
    x = logits2d.astype(jnp.float32).reshape(-1, _K, _S) * inv_temp
    p = jax.nn.softmax(x, axis=1)                      # (M, 9, 64)
    w = win2d.astype(jnp.float32).reshape(-1, _K, _C)  # (M, 9, 2)
    out = jnp.einsum("mks,mkc->mcs", p, w)
    return out.reshape(-1, _C * _S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _combine(logits2d, win2d, inv_temp):
    if jax.default_backend() == "tpu":
        return _run_fwd(logits2d, win2d, inv_temp)
    return _combine_reference(logits2d, win2d, inv_temp)


def _combine_fwd(logits2d, win2d, inv_temp):
    return _combine(logits2d, win2d, inv_temp), (logits2d, win2d)


def _combine_bwd(inv_temp, res, dout):
    logits2d, win2d = res
    if jax.default_backend() == "tpu":
        dlogits, dwin = _run_bwd(logits2d, win2d, dout, inv_temp)
        return dlogits, dwin

    def f(lg, wn):
        return _combine_reference(lg, wn, inv_temp)

    _, vjp = jax.vjp(f, logits2d, win2d)
    dlogits, dwin = vjp(dout.astype(jnp.float32))
    return dlogits.astype(logits2d.dtype), dwin.astype(jnp.float32)


_combine.defvjp(_combine_fwd, _combine_bwd)


# ---------------------------------------------------------------------------
# Fused windowed correlation over a feature pyramid.
#
# Mathematical identity with the RAFT all-pairs volume path: pooling and
# bilinear interpolation are both linear in f2, so
#     lookup(pyramid(all_pairs(f1, f2)), coords)
#   = windowed_correlation(f1, avg_pool^l(f2), coords / 2^l)   per level.
# The kernel computes the right-hand side directly — the O(H²W²) volume is
# never materialized, its pyramid never built, and the backward pass
# accumulates into the (tiny) pooled feature maps instead of carrying
# volume-sized gradients through the iteration scan. This is also the
# long-spatial-context kernel (SURVEY §5.7): memory is O(B·H·W·C)
# regardless of resolution, which is what makes 1080p training fit.
#
# Channel order of the output: (level, dx, dy) — identical to
# ops.corr.lookup_pyramid and the reference CorrBlock (raft.py:57-92).

# The kernels. A block is _PBLK consecutive positions of one grid row. One
# pass over a block reads ONE slab of the padded map, (k+8) rows by _XS
# columns, and contracts it with the block's f1 rows on the MXU in the
# features' own type: (_PBLK, C) x (C, (k+8)·_XS) -> f32, the slab's
# columns on the 128 lanes and the block's positions on the sublanes.
# Everything after the contraction runs on full (8, 128) registers, eight
# positions at a time: a position's k+1 rows are picked out of the slab's
# k+8 by a three-stage shifter on the bits of its row offset, the y-lerp
# and the x-lerp (the x+1 neighbour is one lane rotate) follow, and a
# lane gather by address puts tap (dx, dy) of the window that starts at
# column sx on lane dx·k + dy. The costs leave flat on the lanes,
# f32[b, n_i, n_j, L·k·k], and their cotangent enters the two backward
# kernels in the same form, where the same chain runs transposed (the
# gather the other way round, the shifter upwards) and the two
# contractions take the spread cotangent as a bf16 pair (high part and
# remainder) when the features are bf16.
#
# Why these numbers. _XS is the lane width: a slab's columns fill a
# register and a position's window is a lane gather inside one register.
# The slab's first column is a multiple of _XA (a bf16 sublane tile), so a
# window may start up to _XA - 1 columns into it; _PBLK = 80 positions at
# zero flow span 80 + k + 1 columns, which leaves 128 - 90 - 15 = 23
# columns for the flow to differ inside a block, and 240 and 320
# (136x240, 134x320) are whole blocks. The slab's k+8 rows serve window
# rows that start up to _YSPREAD - 1 = 7 rows apart.
#
# A pass serves the positions whose windows lie inside its slab
# (``_wcp_pass``); the slab is anchored on the topmost, then leftmost,
# window still to do, so a pass serves at least one position, and a block
# is passed over until every position is served: once where the flow is
# smooth across the block (``wcp_shared_share`` counts those), twice where
# an object's edge cuts it, and in the worst case once a position. The
# result is exact for any centres whatever.
_PBLK = 80
_XS = 128
_XA = 16
_YSPREAD = 8
# The padded map's margin, in columns past the last window start: a window
# reaches k+1 columns past its start (16 at radius 7, the largest the
# kernels take) and ``_wcp_pads`` rounds the rest up to _XA. The padded
# maps' width, and so the compiled programs' shapes, hang on this number.
_XMARGIN = 24


def _round_up(n, m):
    return -(-n // m) * m


def _wcp_pads(radius, dim_w):
    """(lo, hi_y, hi_x) zero-padding of an f2 map so every clamped window
    is a plain in-bounds slice: window starts lie in [0, lo + dim] after
    clamping centers to [-(r+1), dim+r]; a window extends k+1 rows and
    columns past its start (_XMARGIN covers the columns), a block's slab
    k+8 rows and _XS columns, its first column clamped so that it ends
    with the padded map (whose width is therefore a multiple of _XA and at
    least _XS)."""
    k = 2 * radius + 1
    lo = k
    wp = max(_XS, _round_up(lo + dim_w + _XMARGIN, _XA))
    return lo, k + _YSPREAD, wp - lo - dim_w


def _wcp_window_start(cx, cy, lvl, dim_h, dim_w, radius):
    """Clamped window start indices (into the padded map) and the
    bilinear fractions, for scalar or vector centers."""
    scale = float(2 ** lvl)
    r = radius
    cx = cx / scale
    cy = cy / scale
    # centers whose whole window is out of bounds clamp to positions whose
    # sampled values are all zero (padding) — grid_sample zero semantics
    cx = jnp.clip(cx, -(r + 1.0), dim_w - 1.0 + r + 1.0)
    cy = jnp.clip(cy, -(r + 1.0), dim_h - 1.0 + r + 1.0)
    x0f = jnp.floor(cx)
    y0f = jnp.floor(cy)
    lo = 2 * r + 1
    x0 = x0f.astype(jnp.int32) - r + lo
    y0 = y0f.astype(jnp.int32) - r + lo
    return x0, y0, cx - x0f, cy - y0f


# planes of a block pass's per-position parameters, each broadcast over
# the lanes so the eight-position groups load them as plain registers
_SX, _SY, _FX, _FY, _NOW = range(5)


def _wcp_pass(x0, y0, todo, wp, radius):
    """One pass over a block: where its slab starts and whom it serves.

    ``x0``, ``y0``: the block's window starts, (_PBLK, 1) int32; ``todo``:
    the positions no earlier pass served; ``wp``: the padded map's width.
    The slab's first row is the topmost window's still to do, its first
    column the _XA-aligned start of the leftmost window among those within
    _YSPREAD rows of that (clamped so the slab ends with the map); served
    are the windows that lie inside it, the anchor's always among them.
    Pure ``jnp``: the kernels trace it and ``wcp_shared_share`` maps it.
    """
    k = 2 * radius + 1
    far = jnp.int32(1 << 30)
    ytop = jnp.min(jnp.where(todo, y0, far))
    near = todo & (y0 - ytop < _YSPREAD)
    xmin = jnp.min(jnp.where(near, x0, far))
    xb = jnp.minimum((xmin // _XA) * _XA, wp - _XS)
    now = near & (x0 - xb <= _XS - (k + 1))
    return ytop, xb, now


def _wcp_block_passes(coords_ref, j0, lvl, dim, wp, radius, prm_ref, serve):
    """Pass over the block at ``j0`` until every position is served:
    ``serve(ytop, xb)`` runs once a pass with the pass's parameters in
    ``prm_ref``."""
    cx = coords_ref[0, 0, pl.ds(j0, _PBLK), 0:1]
    cy = coords_ref[0, 0, pl.ds(j0, _PBLK), 1:2]
    x0, y0, fx, fy = _wcp_window_start(cx, cy, lvl, dim[0], dim[1], radius)

    def lanes(v):
        return jnp.broadcast_to(v.astype(jnp.float32), (_PBLK, _XS))

    prm_ref[_FX] = lanes(fx)
    prm_ref[_FY] = lanes(fy)

    def one_pass(todo):
        ytop, xb, now = _wcp_pass(x0, y0, todo > 0, wp, radius)
        xb = pl.multiple_of(xb, _XA)
        prm_ref[_SX] = lanes(x0 - xb)
        prm_ref[_SY] = lanes(y0 - ytop)
        prm_ref[_NOW] = lanes(now)
        serve(ytop, xb)
        return jnp.where(now, 0, todo)

    # the first pass serves the whole block wherever the flow is smooth
    # across it: the loop (a pipeline drain a turn) is for the others
    todo = one_pass(jnp.ones((_PBLK, 1), jnp.int32))

    @pl.when(jnp.max(todo) > 0)
    def _():
        jax.lax.while_loop(lambda todo: jnp.max(todo) > 0, one_pass, todo)


def _wcp_taps(lvl, v, k):
    """What lane register ``v`` of the flat costs holds of level ``lvl``:
    (is a tap of the level, its dx, its dy), each (8, 128)."""
    m = (jax.lax.broadcasted_iota(jnp.int32, (8, _XS), 1)
         + (_XS * v - lvl * k * k))
    # m // k by a float product: exact for these few hundred integers
    dx = ((m.astype(jnp.float32) + 0.5) * (1.0 / k)).astype(jnp.int32)
    return (m >= 0) & (m < k * k), dx, m - dx * k


def _wcp_lane_regs(lvl, kk):
    """The lane registers of the flat costs that level ``lvl`` touches."""
    return range(lvl * kk // _XS, (lvl * kk + kk - 1) // _XS + 1)


def _wcp_group_prm(prm_ref, r0):
    rows = pl.ds(r0, 8)
    return (prm_ref[_SX, rows, :].astype(jnp.int32),
            prm_ref[_SY, rows, :].astype(jnp.int32),
            prm_ref[_FX, rows, :], prm_ref[_FY, rows, :],
            prm_ref[_NOW, rows, :] > 0.0)


def _wcp_select(d_ref, prm_ref, out_ref, j0, lvl, n_out, radius, unroll):
    """Windows out of a pass's product: ``d_ref`` (_PBLK, (k+8)·_XS) holds
    every position's dot product with every slab element, row-major over
    (slab row, slab column); the served positions' lerped (dx, dy) taps
    go to level ``lvl``'s lanes of ``out_ref``, eight positions at a time."""
    k = 2 * radius + 1
    taps = {v: _wcp_taps(lvl, v, k) for v in _wcp_lane_regs(lvl, k * k)}

    def group(g, _):
        r0 = pl.multiple_of(g * 8, 8)
        sx, sy, fx, fy, now = _wcp_group_prm(prm_ref, r0)
        rows = [d_ref[pl.ds(r0, 8), y * _XS:(y + 1) * _XS]
                for y in range(k + _YSPREAD)]
        # rows sy .. sy+k of the slab's k+8, by the bits of sy
        for shift in (4, 2, 1):
            bit = (sy & shift) != 0
            rows = [jnp.where(bit, rows[y + shift], rows[y])
                    for y in range(len(rows) - shift)]
        t = [rows[dy] + fy * (rows[dy + 1] - rows[dy]) for dy in range(k)]
        # x-lerp against the next column (one lane up), then the window's
        # columns sx .. sx+k-1 to the taps' lanes by address
        t = [a + fx * (pltpu.roll(a, _XS - 1, 1) - a) for a in t]
        for v, (valid, dx, dy) in taps.items():
            idx = jnp.clip(sx + dx, 0, _XS - 1)
            val = jnp.zeros((8, _XS), jnp.float32)
            for i in range(k):
                val = jnp.where(dy == i,
                                jnp.take_along_axis(t[i], idx, axis=1), val)
            w = min(_XS, n_out - _XS * v)
            at = (0, 0, pl.ds(pl.multiple_of(j0 + r0, 8), 8),
                  slice(_XS * v, _XS * v + w))
            keep = (valid & now)[:, :w]
            out_ref[at] = jnp.where(keep, val[:, :w], out_ref[at])
        return 0

    # unrolled on the chip: the groups are independent and the scheduler
    # interleaves them (rolled, one group's chain ran at a time, 1.6 times
    # slower); the interpreter keeps the loop, which traces several times
    # faster
    jax.lax.fori_loop(0, _PBLK // 8, group, 0, unroll=unroll)


def _wcp_spread(dout_ref, prm_ref, dd_ref, dv_ref, j0, lvl, n_out, radius,
                unroll):
    """Transpose of ``_wcp_select``: the served positions' cost cotangents
    (level ``lvl``'s lanes of ``dout_ref``) spread over the pass's slab,
    into ``dd_ref`` (_PBLK, (k+8)·_XS); unserved positions get zeros."""
    k = 2 * radius + 1
    kk = k * k
    regs = list(_wcp_lane_regs(lvl, kk))
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, _XS), 1)
    zero = jnp.zeros((8, _XS), jnp.float32)

    def group(g, _):
        r0 = pl.multiple_of(g * 8, 8)
        sx, sy, fx, fy, now = _wcp_group_prm(prm_ref, r0)
        rows8 = pl.ds(pl.multiple_of(j0 + r0, 8), 8)
        dv = {}
        for v in regs:
            w = min(_XS, n_out - _XS * v)
            if w == _XS:
                reg = dout_ref[0, 0, rows8, _XS * v:_XS * (v + 1)]
            else:
                # the costs' last register is short: widen it over zeros
                dv_ref[:, 0:w] = dout_ref[0, 0, rows8, _XS * v:_XS * v + w]
                reg = dv_ref[...]
            dv[v] = jnp.where(now, reg, 0.0)

        rel = lane - sx                       # slab column - window start
        in_win = (rel >= 0) & (rel < k)
        dt = []
        for dy in range(k):
            m = jnp.clip(lvl * kk + rel * k + dy, 0, n_out - 1)
            g0 = zero
            for v in regs:
                got = jnp.take_along_axis(dv[v], m & (_XS - 1), axis=1)
                g0 = got if len(regs) == 1 else jnp.where(
                    (m >> 7) == v, got, g0)
            g0 = jnp.where(in_win, g0, 0.0)
            # x-unlerp: column x takes (1-fx) of tap x-sx and fx of the
            # tap before it
            dt.append(g0 + fx * (pltpu.roll(g0, 1, 1) - g0))
        a = [fy * d for d in dt]
        b = [d - e for d, e in zip(dt, a)]
        rows = [b[0]] + [a[i - 1] + b[i] for i in range(1, k)] + [a[k - 1]]
        # down by sy rows: the shifter of _wcp_select the other way
        for shift in (1, 2, 4):
            bit = (sy & shift) != 0
            n = len(rows)
            rows = [jnp.where(bit,
                              rows[y - shift] if y >= shift else zero,
                              rows[y] if y < n else zero)
                    for y in range(n + shift)]
        for y, row in enumerate(rows):
            dd_ref[pl.ds(r0, 8), y * _XS:(y + 1) * _XS] = row
        return 0

    # unrolled on the chip: the groups are independent and the scheduler
    # interleaves them (rolled, one group's chain ran at a time, 1.6 times
    # slower); the interpreter keeps the loop, which traces several times
    # faster
    jax.lax.fori_loop(0, _PBLK // 8, group, 0, unroll=unroll)


def _wcp_dot_f32(a, b, dims):
    """``a`` (float32, a spread cotangent) contracted with ``b`` (features)
    into float32. Bfloat16 features meet ``a`` as a bfloat16 pair, its
    high part and the remainder (sixteen bits of mantissa between them),
    so the MXU runs at the features' width; float32 features meet it as
    it is."""
    def dot(x):
        return jax.lax.dot_general(x, b, dims,
                                   preferred_element_type=jnp.float32)

    if b.dtype != jnp.bfloat16:
        return dot(a)
    hi = a.astype(jnp.bfloat16)
    return dot(hi) + dot((a - hi.astype(jnp.float32)).astype(jnp.bfloat16))


def _wcp_fwd_block_kernel(coords_ref, f1_ref, *rest, radius, dims, unroll):
    """Block forward: the costs of one grid row, every level, flat."""
    n_lvl = len(dims)
    f2_refs = rest[:n_lvl]
    out_ref, d_ref, prm_ref = rest[n_lvl:]
    k = 2 * radius + 1
    ys = k + _YSPREAD
    n_out = n_lvl * k * k

    def block(bi, _):
        j0 = pl.multiple_of(bi * _PBLK, _PBLK)
        f1b = f1_ref[0, 0, pl.ds(j0, _PBLK), :]
        for lvl, f2_ref in enumerate(f2_refs):
            def serve(ytop, xb, lvl=lvl, f2_ref=f2_ref):
                slab = f2_ref[0, pl.ds(ytop, ys), pl.ds(xb, _XS), :]
                d_ref[...] = jax.lax.dot_general(
                    f1b, slab.reshape(ys * _XS, -1),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                _wcp_select(d_ref, prm_ref, out_ref, j0, lvl, n_out, radius,
                            unroll)

            _wcp_block_passes(coords_ref, j0, lvl, dims[lvl],
                              f2_ref.shape[2], radius, prm_ref, serve)
        return 0

    jax.lax.fori_loop(0, f1_ref.shape[2] // _PBLK, block, 0)


def _wcp_bwd_df1_block_kernel(coords_ref, dout_ref, *rest, radius, dims,
                              unroll):
    """Block df1: per pass the cotangent spread over the slab, contracted
    with the slab, (_PBLK, (k+8)·_XS) x ((k+8)·_XS, C)."""
    n_lvl = len(dims)
    f2_refs = rest[:n_lvl]
    df1_ref, dd_ref, prm_ref, dv_ref, acc_ref = rest[n_lvl:]
    k = 2 * radius + 1
    ys = k + _YSPREAD
    n_out = n_lvl * k * k
    dv_ref[...] = jnp.zeros_like(dv_ref)

    def block(bi, _):
        j0 = pl.multiple_of(bi * _PBLK, _PBLK)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for lvl, f2_ref in enumerate(f2_refs):
            def serve(ytop, xb, lvl=lvl, f2_ref=f2_ref):
                _wcp_spread(dout_ref, prm_ref, dd_ref, dv_ref, j0, lvl,
                            n_out, radius, unroll)
                slab = f2_ref[0, pl.ds(ytop, ys), pl.ds(xb, _XS), :]
                acc_ref[...] += _wcp_dot_f32(
                    dd_ref[...], slab.reshape(ys * _XS, -1),
                    (((1,), (0,)), ((), ())))

            _wcp_block_passes(coords_ref, j0, lvl, dims[lvl],
                              f2_ref.shape[2], radius, prm_ref, serve)
        df1_ref[0, 0, pl.ds(j0, _PBLK), :] = acc_ref[...].astype(
            df1_ref.dtype)
        return 0

    jax.lax.fori_loop(0, df1_ref.shape[2] // _PBLK, block, 0)


def _wcp_bwd_df2_block_kernel(coords_ref, f1_ref, dout_ref, df2_ref, dd_ref,
                              prm_ref, dv_ref, *, radius, lvl, n_lvl, dims,
                              unroll):
    """Block df2 for ONE level: per pass the spread cotangent contracted
    with the block's f1 rows, ((k+8)·_XS, _PBLK) x (_PBLK, C), added into
    the slab's place in the map (resident across the grid rows)."""
    k = 2 * radius + 1
    ys = k + _YSPREAD
    n_out = n_lvl * k * k
    dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(pl.program_id(1) == 0)
    def _():
        df2_ref[...] = jnp.zeros_like(df2_ref)

    def block(bi, _):
        j0 = pl.multiple_of(bi * _PBLK, _PBLK)
        f1b = f1_ref[0, 0, pl.ds(j0, _PBLK), :]

        def serve(ytop, xb):
            _wcp_spread(dout_ref, prm_ref, dd_ref, dv_ref, j0, lvl, n_out,
                        radius, unroll)
            ds2 = _wcp_dot_f32(dd_ref[...], f1b, (((0,), (0,)), ((), ())))
            df2_ref[0, pl.ds(ytop, ys), pl.ds(xb, _XS), :] += ds2.reshape(
                ys, _XS, -1)

        _wcp_block_passes(coords_ref, j0, lvl, dims, df2_ref.shape[2],
                          radius, prm_ref, serve)
        return 0

    jax.lax.fori_loop(0, f1_ref.shape[2] // _PBLK, block, 0)


def _wcp_pad_f2(f2_levels, radius):
    pads = [_wcp_pads(radius, f2.shape[2]) for f2 in f2_levels]
    return tuple(
        jnp.pad(f2, ((0, 0), (lo, hi_y), (lo, hi_x), (0, 0)))
        for f2, (lo, hi_y, hi_x) in zip(f2_levels, pads)
    )


def _wcp_fwd_interpret(f1, f2_levels, coords, radius):
    """Interpreter-mode forward (kernel correctness tests off-TPU)."""
    return _wcp_fwd_tpu(f1, tuple(f2_levels), coords, radius, interpret=True)


def _wcp_bwd_interpret(f1, f2_levels, coords, dout, radius):
    """Interpreter-mode backward (kernel correctness tests off-TPU)."""
    return _wcp_bwd_tpu(f1, tuple(f2_levels), coords, dout, radius,
                        interpret=True)


_WCP_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)


def _wcp_row_spec(n_j, *minor):
    """One (batch, grid row) of a (b, n_i, n_j, ...) operand a step."""
    zeros = (0,) * (1 + len(minor))
    return pl.BlockSpec((1, 1, n_j) + minor, lambda bi, ii: (bi, ii) + zeros,
                        memory_space=pltpu.VMEM)


def _wcp_map_spec(f2):
    """A whole padded map, resident across a sample's grid rows."""
    return pl.BlockSpec((1,) + f2.shape[1:], lambda bi, ii: (bi, 0, 0, 0),
                        memory_space=pltpu.VMEM)


def _wcp_block_pad(n_j, *rows):
    """The position axis padded to whole blocks: zeros, and for the
    centres (last) the row's last centre again, so that the padding joins
    its neighbours' pass; padded positions are sliced off forward and
    carry a zero cotangent backward."""
    n_jp = _round_up(n_j, _PBLK)
    if n_jp == n_j:
        return (n_jp,) + rows
    pad = ((0, 0), (0, 0), (0, n_jp - n_j), (0, 0))
    return (n_jp,) + tuple(jnp.pad(x, pad) for x in rows[:-1]) + (
        jnp.pad(rows[-1], pad, mode="edge"),)


def _wcp_block_scratch(radius):
    """A pass's product (or spread cotangent) and its parameters."""
    k = 2 * radius + 1
    return [pltpu.VMEM((_PBLK, (k + _YSPREAD) * _XS), jnp.float32),
            pltpu.VMEM((5, _PBLK, _XS), jnp.float32)]


def _wcp_fwd_tpu(f1, f2_levels, coords, radius, interpret=False):
    b, n_i, n_j, c = f1.shape
    k = 2 * radius + 1
    n_lvl = len(f2_levels)
    dims = tuple((f2.shape[1], f2.shape[2]) for f2 in f2_levels)
    f2p = _wcp_pad_f2(f2_levels, radius)

    # the costs leave flat, (level, dx, dy) on the lanes: no reshape
    n_jp, f1, coords = _wcp_block_pad(n_j, f1, coords)
    out = pl.pallas_call(
        functools.partial(_wcp_fwd_block_kernel, radius=radius, dims=dims,
                          unroll=not interpret),
        out_shape=jax.ShapeDtypeStruct((b, n_i, n_jp, n_lvl * k * k),
                                       jnp.float32),
        grid=(b, n_i),
        in_specs=[_wcp_row_spec(n_jp, 2), _wcp_row_spec(n_jp, c)]
        + [_wcp_map_spec(f2) for f2 in f2p],
        out_specs=_wcp_row_spec(n_jp, n_lvl * k * k),
        scratch_shapes=_wcp_block_scratch(radius),
        compiler_params=_WCP_PARAMS,
        interpret=interpret,
    )(coords, f1, *f2p)
    return out if n_jp == n_j else out[:, :, :n_j]


def _wcp_strip(df2_l, dim, radius):
    """A padded map's gradient without its padding."""
    lo = 2 * radius + 1
    return df2_l[:, lo:lo + dim[0], lo:lo + dim[1], :]


def _wcp_bwd_tpu(f1, f2_levels, coords, dout, radius, interpret=False):
    b, n_i, n_j, c = f1.shape
    f2p = _wcp_pad_f2(f2_levels, radius)
    dims = tuple((f2.shape[1], f2.shape[2]) for f2 in f2_levels)
    k = 2 * radius + 1
    n_lvl = len(f2_levels)

    # the cotangent enters flat, as the costs left; df1 leaves in the
    # features' type
    n_jp, f1, dout, coords = _wcp_block_pad(n_j, f1, dout, coords)
    coords_spec = _wcp_row_spec(n_jp, 2)
    dout_spec = _wcp_row_spec(n_jp, n_lvl * k * k)
    f1_spec = _wcp_row_spec(n_jp, c)
    scratch = _wcp_block_scratch(radius) + [pltpu.VMEM((8, _XS), jnp.float32)]

    df1 = pl.pallas_call(
        functools.partial(_wcp_bwd_df1_block_kernel, radius=radius, dims=dims,
                          unroll=not interpret),
        out_shape=jax.ShapeDtypeStruct((b, n_i, n_jp, c), f1.dtype),
        grid=(b, n_i),
        in_specs=[coords_spec, dout_spec] + [_wcp_map_spec(f2) for f2 in f2p],
        out_specs=f1_spec,
        scratch_shapes=scratch + [pltpu.VMEM((_PBLK, c), jnp.float32)],
        compiler_params=_WCP_PARAMS,
        interpret=interpret,
    )(coords, dout, *f2p)
    if n_jp != n_j:
        df1 = df1[:, :, :n_j]

    df2_out = []
    for lvl, f2 in enumerate(f2p):
        df2_l = pl.pallas_call(
            functools.partial(_wcp_bwd_df2_block_kernel, radius=radius,
                              lvl=lvl, n_lvl=n_lvl, dims=dims[lvl],
                              unroll=not interpret),
            out_shape=jax.ShapeDtypeStruct(f2.shape, jnp.float32),
            grid=(b, n_i),
            in_specs=[coords_spec, f1_spec, dout_spec],
            out_specs=_wcp_map_spec(f2),
            scratch_shapes=scratch,
            compiler_params=_WCP_PARAMS,
            interpret=interpret,
        )(coords, f1, dout)
        df2_out.append(_wcp_strip(df2_l, dims[lvl], radius))
    return df1, tuple(df2_out)


def _wcp_reference(f1, f2_levels, coords, radius):
    """XLA fallback: per-level windowed correlation (exact same math)."""
    from .corr import windowed_correlation

    out = [
        windowed_correlation(f1, f2, coords, radius, float(2 ** lvl),
                             normalize=False)
        for lvl, f2 in enumerate(f2_levels)
    ]
    return jnp.concatenate(out, axis=-1)


def _wcp_fits_vmem(f1, f2_levels, radius):
    """Static shape check: the kernel holds one (b, i)-row of state plus
    every padded f2 map in VMEM; beyond ~64M even the raised compiler
    budget cannot place it, so oversized shapes take the XLA path.

    Also gates on radius, kept as found: no kernel has been compiled or
    tested past radius 7, where a window's k+1 columns are 16 of the
    pad's _XMARGIN, so larger radii take the (exact) XLA path too.
    """
    if radius > 7:
        return False
    k = 2 * radius + 1
    n_jp = _round_up(f1.shape[2], _PBLK)
    c = f1.shape[3]
    itemsize = 2 if f1.dtype == jnp.bfloat16 else 4
    # a row's blocks, each in two buffers: the flat costs on whole lane
    # registers, the centres (two of 128 lanes), f1
    lanes = _round_up(len(f2_levels) * k * k, _XS)
    total = 2 * n_jp * ((lanes + _XS) * 4 + c * itemsize)
    # a pass's product and its parameters
    total += _PBLK * ((k + _YSPREAD) + 5) * _XS * 4
    for f2 in f2_levels:
        lo, hi_y, hi_x = _wcp_pads(radius, f2.shape[2])
        total += (f2.shape[1] + lo + hi_y) * (f2.shape[2] + lo + hi_x) \
            * c * itemsize
    return total <= 64 * 1024 * 1024


def _wcp_takes_kernel(f1, f2_levels, radius):
    """Which form a windowed-correlation call traces: the Mosaic kernels
    on the TPU where the shapes fit VMEM, the XLA composition everywhere
    else."""
    return jax.default_backend() == "tpu" and _wcp_fits_vmem(f1, f2_levels,
                                                            radius)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _wcp(f1, f2_levels, coords, radius):
    if _wcp_takes_kernel(f1, f2_levels, radius):
        return _wcp_fwd_tpu(f1, f2_levels, coords, radius)
    return _wcp_reference(f1, f2_levels, coords, radius)


def _wcp_vjp_fwd(f1, f2_levels, coords, radius):
    return _wcp(f1, f2_levels, coords, radius), (f1, f2_levels, coords)


def _wcp_vjp_bwd(radius, res, dout):
    f1, f2_levels, coords = res
    if _wcp_takes_kernel(f1, f2_levels, radius):
        df1, df2 = _wcp_bwd_tpu(f1, f2_levels, coords, dout, radius)
    else:
        def f(f1_, f2_):
            return _wcp_reference(f1_, f2_, coords, radius)

        _, vjp = jax.vjp(f, f1, f2_levels)
        df1, df2 = vjp(dout)
    df1 = df1.astype(f1.dtype)
    df2 = tuple(g.astype(f2.dtype) for g, f2 in zip(df2, f2_levels))
    # coords are stop_gradient'ed by every caller (the RAFT iteration
    # detaches them); returning zeros keeps the vjp total
    return df1, df2, jnp.zeros_like(coords)


_wcp.defvjp(_wcp_vjp_fwd, _wcp_vjp_bwd)


def windowed_corr_pyramid(f1, f2_levels, coords, radius=4, mask_costs=(),
                          normalize=True):
    """Fused multi-level windowed correlation (B, H, W, L·(2r+1)²).

    f1: (B, H, W, C) frame-1 features; f2_levels: tuple of frame-2 feature
    maps, level l at 1/2^l of f1's resolution (level 0 same-res); coords:
    (B, H, W, 2) level-0 window centers. Output channels are ordered
    (level, dx, dy) and normalized by sqrt(C) — drop-in identical to
    ``lookup_pyramid(correlation_pyramid(all_pairs_correlation(f1, f2)))``
    without ever building the volume. ``mask_costs`` zeroes whole levels
    by pyramid level id (l + 3), like the reference (raft.py:86).

    On the TPU, where the shapes fit VMEM (``_wcp_fits_vmem``), the call
    is the block kernels above: 80 positions of a grid row against one
    slab of a level's map a pass, the features in their own type on the
    MXU, accumulation and both lerps in float32, the costs written flat
    in the (level, dx, dy) order returned here, so nothing reshapes them
    on the way to ``_WindowConv1x1``; the gradient is float32 costs in,
    ``df1`` and every ``df2`` out in the features' type, zero for the
    centres. ``wcp_shared_share`` gives the share of a field's blocks that
    one pass serves.

    Which form the call traced is counted (``wcp_fused_calls`` /
    ``wcp_fallback_calls``, ``telemetry.note_trace``), as the window
    sampler's is: the fallback is silent in the arithmetic and not in the
    time. The scope gives the three kernels' device operations (forward,
    backward to ``f1``, backward to each map) a name of their own,
    ``wcp.N``, in the compiled program and in a device trace.
    """
    from .. import telemetry

    c = f1.shape[-1]
    k = 2 * radius + 1
    if normalize:
        f1 = (f1 / jnp.sqrt(jnp.asarray(c, jnp.float32))).astype(f1.dtype)

    def correlate(a, b, c):
        telemetry.note_trace(
            "wcp_fused_calls" if _wcp_takes_kernel(a, b, radius)
            else "wcp_fallback_calls", 1)
        return _wcp(a, b, c, radius)

    with jax.named_scope("wcp"):
        out = _per_shard(correlate)(f1, tuple(f2_levels), coords)

    if mask_costs:
        keep = jnp.concatenate([
            jnp.full((k * k,), 0.0 if lvl + 3 in mask_costs else 1.0,
                     jnp.float32)
            for lvl in range(len(f2_levels))
        ])
        out = out * keep
    return out


def wcp_shared_share(coords, dims, radius=4):
    """Share of a call's block·levels that one slab serves.

    ``coords``: (B, H, W, 2) level-0 window centres, as
    ``windowed_corr_pyramid`` takes them; ``dims``: the (height, width) of
    each level's map. A block (_PBLK consecutive positions of a row) whose
    windows all lie inside the slab its first pass anchors takes one pass,
    any other takes a pass more for every cluster of windows it holds. The
    predicate is the one the kernels trace (``_wcp_window_start``,
    ``_wcp_pass``), so the share is the path they take on these centres: 1
    on zero flow, lower the more object edges cut the blocks.
    """
    b, n_i, n_j, _ = coords.shape
    n_jp, coords = _wcp_block_pad(n_j, coords)
    blocks = coords.reshape(b, n_i, n_jp // _PBLK, _PBLK, 2)
    todo = jnp.ones((_PBLK, 1), bool)
    shared = []
    for lvl, (h2, w2) in enumerate(dims):
        lo, _, hi_x = _wcp_pads(radius, w2)

        def one_pass_serves(c, lvl=lvl, h2=h2, w2=w2, wp=lo + w2 + hi_x):
            x0, y0, _, _ = _wcp_window_start(c[:, 0:1], c[:, 1:2], lvl, h2,
                                             w2, radius)
            return _wcp_pass(x0, y0, todo, wp, radius)[2].all()

        shared.append(jax.vmap(jax.vmap(jax.vmap(one_pass_serves)))(blocks))
    return jnp.mean(jnp.stack(shared).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Fused DICL window sampler.
#
# The DICL-family matching path samples the full (2r+1)² displaced feature
# window per position (``ops.sample.sample_window``) — not a dot-product
# readout like the windowed correlation above, but the raw (k, k, C) window
# the MatchingNet then convolves. The XLA form gathers one (k+1)² integer
# patch per position through HBM (a giant take_along_axis) and materializes
# it before the two lerps; this kernel keeps the patch and both separable
# lerps in VMEM and writes the (k², C) window row — nothing patch-sized
# ever touches HBM.
#
# A position's patch is picked by addressing, not by arithmetic: the map
# is handed over in float32 with x as the leading (untiled) axis and y on
# the sublanes, (B, Wp, Hp, C), so column ``x0 + xi`` is a dynamic index
# on an untiled axis and its rows ``y0 … y0+k`` a dynamic-start sublane
# slice, which Mosaic loads at any offset on 32-bit data (on bfloat16 it
# wants the start a proven multiple of 16, hence the widening before the
# call). A position then costs the two lerps of its own (k+1)×(k+1)×C
# patch: k+1 column pairs in, k stores of a dx's k dy-rows out, which is
# the window's dx·k+dy tap order as it stands.
#
# The custom VJP accumulates the window gradient back into the padded map
# (transpose of the two lerps) with the same addressing. Coordinates get a
# zero gradient: every caller (the corr modules inside the RAFT iteration)
# stop-gradients the lookup centers, exactly like the windowed-correlation
# kernel's contract.


_SW_VMEM_LIMIT = 100 * 1024 * 1024


def _sw_pads(radius):
    """(lo, hi) zero-padding of the sampled map on both axes, so that
    every clamped (k+1)-wide patch is a plain in-bounds slice: starts lie
    in [0, lo + dim] after clamping centers to [-(r+1), dim+r]."""
    return 2 * radius + 1, 2 * radius + 2


def _sw_window(cx, cy, dim_h, dim_w, radius):
    """Patch start indices (into the padded map) and bilinear fractions."""
    r = radius
    # centers whose whole window is out of bounds clamp to positions whose
    # sampled values are all zero (padding) — grid_sample zero semantics
    cx = jnp.clip(cx, -(r + 1.0), dim_w + r + 0.0)
    cy = jnp.clip(cy, -(r + 1.0), dim_h + r + 0.0)
    x0f = jnp.floor(cx)
    y0f = jnp.floor(cy)
    lo, _ = _sw_pads(r)
    x0 = x0f.astype(jnp.int32) - r + lo
    y0 = y0f.astype(jnp.int32) - r + lo
    return x0, y0, cx - x0f, cy - y0f


def _sw_fwd_kernel(coords_ref, f2_ref, out_ref, *, radius, dims):
    k = 2 * radius + 1
    h2, w2 = dims
    n_j = out_ref.shape[2]

    def body(j, _):
        cx = coords_ref[0, 0, j, 0]
        cy = coords_ref[0, 0, j, 1]
        x0, y0, fx, fy = _sw_window(cx, cy, h2, w2, radius)

        # y-lerp of the patch's k+1 columns: rows y0… and y0+1… of each
        t = []
        for xi in range(k + 1):
            upper = f2_ref[0, x0 + xi, pl.ds(y0, k), :]
            lower = f2_ref[0, x0 + xi, pl.ds(y0 + 1, k), :]
            t.append((1.0 - fy) * upper + fy * lower)          # (k_dy, C)

        # dx-major (k², C) window rows: dx lerps columns dx / dx+1
        for dx in range(k):
            out_ref[0, 0, j, dx * k:(dx + 1) * k, :] = (
                (1.0 - fx) * t[dx] + fx * t[dx + 1])
        return 0

    jax.lax.fori_loop(0, n_j, body, 0)


def _sw_bwd_kernel(coords_ref, dout_ref, df2_ref, *, radius, dims):
    """df2 accumulated across the i-grid (the padded output block is
    indexed by b only and stays resident in VMEM, like
    ``_wcp_bwd_df2_block_kernel``)."""
    k = 2 * radius + 1
    h2, w2 = dims
    n_j = dout_ref.shape[2]
    c = dout_ref.shape[4]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        df2_ref[:] = jnp.zeros_like(df2_ref)

    def body(j, _):
        cx = coords_ref[0, 0, j, 0]
        cy = coords_ref[0, 0, j, 1]
        x0, y0, fx, fy = _sw_window(cx, cy, h2, w2, radius)

        g = [dout_ref[0, 0, j, dx * k:(dx + 1) * k, :] for dx in range(k)]
        zero = jnp.zeros((1, c), jnp.float32)
        for xi in range(k + 1):
            # transpose of the x-lerp: column xi hears from dx = xi, xi-1
            if xi == 0:
                dt = (1.0 - fx) * g[0]
            elif xi == k:
                dt = fx * g[k - 1]
            else:
                dt = (1.0 - fx) * g[xi] + fx * g[xi - 1]     # (k_dy, C)
            # transpose of the y-lerp: row y gets (1-fy)·dt[y] + fy·dt[y-1]
            dd = ((1.0 - fy) * jnp.concatenate([dt, zero], axis=0)
                  + fy * jnp.concatenate([zero, dt], axis=0))
            df2_ref[0, x0 + xi, pl.ds(y0, k + 1), :] += dd
        return 0

    jax.lax.fori_loop(0, n_j, body, 0)


def _sw_pad_f2(f2, radius):
    """The map as the kernels address it: (B, Wp, Hp, C) float32, zero
    padded, x leading."""
    lo, hi = _sw_pads(radius)
    return jnp.pad(f2.astype(jnp.float32).transpose(0, 2, 1, 3),
                   ((0, 0), (lo, hi), (lo, hi), (0, 0)))


def _sw_fwd_tpu(f2, coords, radius, interpret=False):
    b, n_i, n_j = coords.shape[:3]
    c = f2.shape[-1]
    k = 2 * radius + 1
    dims = (f2.shape[1], f2.shape[2])
    f2p = _sw_pad_f2(f2, radius)

    out = pl.pallas_call(
        functools.partial(_sw_fwd_kernel, radius=radius, dims=dims),
        out_shape=jax.ShapeDtypeStruct((b, n_i, n_j, k * k, c),
                                       jnp.float32),
        grid=(b, n_i),
        in_specs=[
            pl.BlockSpec((1, 1, n_j, 2), lambda bi, ii: (bi, ii, 0, 0),
                         memory_space=pltpu.SMEM),
            # the map changes with b only: one buffer, not two
            pl.BlockSpec((1,) + f2p.shape[1:], lambda bi, ii: (bi, 0, 0, 0),
                         pipeline_mode=pl.Buffered(1),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, n_j, k * k, c),
                               lambda bi, ii: (bi, ii, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SW_VMEM_LIMIT),
        interpret=interpret,
    )(coords, f2p)
    # (b, i, j, dx·k+dy, c) → the sample_window (B, du, dv, H, W, C) layout
    out = out.reshape(b, n_i, n_j, k, k, c)
    return out.transpose(0, 3, 4, 1, 2, 5)


def _sw_bwd_tpu(f2, coords, dout, radius, interpret=False):
    b, n_i, n_j = coords.shape[:3]
    c = f2.shape[-1]
    k = 2 * radius + 1
    lo, hi = _sw_pads(radius)
    h2, w2 = dims = (f2.shape[1], f2.shape[2])
    padded = (b, w2 + lo + hi, h2 + lo + hi, c)

    # (B, du, dv, H, W, C) → the kernel's (b, i, j, dx·k+dy, c) row layout
    doutr = dout.astype(jnp.float32).transpose(0, 3, 4, 1, 2, 5)
    doutr = doutr.reshape(b, n_i, n_j, k * k, c)

    df2 = pl.pallas_call(
        functools.partial(_sw_bwd_kernel, radius=radius, dims=dims),
        out_shape=jax.ShapeDtypeStruct(padded, jnp.float32),
        grid=(b, n_i),
        in_specs=[
            pl.BlockSpec((1, 1, n_j, 2), lambda bi, ii: (bi, ii, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, n_j, k * k, c),
                         lambda bi, ii: (bi, ii, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1,) + padded[1:],
                               lambda bi, ii: (bi, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SW_VMEM_LIMIT),
        interpret=interpret,
    )(coords, doutr)

    # strip the padding, back to (B, H2, W2, C)
    return df2[:, lo:lo + w2, lo:lo + h2, :].transpose(0, 2, 1, 3)


def _sw_fwd_interpret(f2, coords, radius):
    """Interpreter-mode forward (kernel correctness tests off-TPU)."""
    return _sw_fwd_tpu(f2, coords, radius, interpret=True)


def _sw_bwd_interpret(f2, coords, dout, radius):
    """Interpreter-mode backward (kernel correctness tests off-TPU)."""
    return _sw_bwd_tpu(f2, coords, dout, radius, interpret=True)


def _sw_reference(f2, coords, radius):
    """XLA fallback with identical semantics (used off-TPU and as the
    numerical reference in tests)."""
    from .sample import sample_window

    return sample_window(f2, coords, radius)


def _sw_fits_vmem(f2, coords, radius):
    """Static shape check: what the kernels hold in VMEM as Mosaic lays
    it out (float32, rows padded to 8 sublanes, channels to 128 lanes)
    must fit under the limit they are compiled with. Either direction
    keeps one (b, i)-row of window taps in two pipeline buffers and the
    padded map once (the forward asks for one buffer, and the backward's
    accumulator is an output block that only changes with b). The bodies
    unroll over the patch's k+1 columns and are compiled for radius ≤ 7."""
    if radius > 7:
        return False
    lo, hi = _sw_pads(radius)
    k = 2 * radius + 1
    n_j = coords.shape[2]
    lanes = -(-f2.shape[-1] // 128) * 128

    def tiled(leading, rows):
        return leading * (-(-rows // 8) * 8) * lanes * 4

    row = tiled(n_j, k * k)
    padded = tiled(f2.shape[2] + lo + hi, f2.shape[1] + lo + hi)
    # a MiB of room for what Mosaic keeps on its own stack
    return 2 * row + padded + (1 << 20) <= _SW_VMEM_LIMIT


def _sw_takes_kernel(f2, coords, radius):
    """Which form a sampler call traces: the Mosaic kernel on the TPU
    where the shapes fit VMEM, the XLA reference everywhere else."""
    return jax.default_backend() == "tpu" and _sw_fits_vmem(f2, coords,
                                                            radius)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sw(f2, coords, radius):
    if _sw_takes_kernel(f2, coords, radius):
        return _sw_fwd_tpu(f2, coords, radius)
    return _sw_reference(f2, coords, radius)


def _sw_vjp_fwd(f2, coords, radius):
    return _sw(f2, coords, radius), (f2, coords)


def _sw_vjp_bwd(radius, res, dout):
    f2, coords = res
    if _sw_takes_kernel(f2, coords, radius):
        df2 = _sw_bwd_tpu(f2, coords, dout, radius)
    else:
        def f(f2_):
            return _sw_reference(f2_, jax.lax.stop_gradient(coords), radius)

        out, vjp = jax.vjp(f, f2)
        (df2,) = vjp(dout.astype(out.dtype))
    # coords are stop_gradient'ed by every caller (the RAFT iteration
    # detaches them); returning zeros keeps the vjp total
    return df2.astype(f2.dtype), jnp.zeros_like(coords)


_sw.defvjp(_sw_vjp_fwd, _sw_vjp_bwd)


def sample_window_fused(f2, coords, radius=4):
    """Fused (2r+1)² displaced-window sampler, (B, du, dv, H, W, C).

    Drop-in for ``ops.sample.sample_window`` — same zero-padding
    semantics, same (du varies dx) window layout — with the patch gather
    and both separable lerps fused in VMEM on TPU (XLA reference path
    elsewhere / for oversized shapes). Output dtype follows ``f2``; the
    kernel computes in f32 and rounds once on write. Coordinates are
    treated as non-differentiable (zero gradient): callers inside the
    recurrent estimators detach the lookup centers.

    Which form the call traced is counted (``sw_fused_calls`` /
    ``sw_fallback_calls``, ``telemetry.note_trace``): the fallback is
    silent in the arithmetic and costs an order of magnitude in time, so
    the program's ``compile`` and ``aot`` events say which one it holds.
    """
    from .. import telemetry

    def sample(a, c):
        telemetry.note_trace(
            "sw_fused_calls" if _sw_takes_kernel(a, c, radius)
            else "sw_fallback_calls", 1)
        return _sw(a, c, radius)

    return _per_shard(sample)(f2, coords).astype(f2.dtype)


def convex_combine_8x(mask_logits, win, temperature=4.0):
    """Fused softmax-over-neighbors + convex combine.

    mask_logits: (..., 576), channels neighbor-major ``k * 64 + s``
    (torch RAFT's native layout). win: (..., 9, 2) float32 neighbor flow
    windows. Returns (..., 128) float32, channels ``chan * 64 + s`` —
    reshape to (..., 2, 8, 8) and pixel-shuffle for the upsampled flow.
    """
    lead = mask_logits.shape[:-1]
    logits2d = mask_logits.reshape(-1, _K * _S)
    win2d = win.astype(jnp.float32).reshape(-1, _K * _C)
    out = _per_shard(lambda a, b: _combine(a, b, 1.0 / temperature))(
        logits2d, win2d)
    return out.reshape(*lead, _C * _S)


def _under_its_scope(fn):
    """``fn`` under the innermost named scope of the trace that is running,
    stated again. A compiled kernel is called after the scope that holds
    its ``pallas_call`` (``Up8Network_0.2``, ``wcp.27``, ``sampler.3``):
    that is how a capture's readers tell the families apart, and inside
    ``_per_shard``'s map that scope would be ``shard_map``. (At the end of
    the file: the kernels' source lines are part of their compiled text, so
    nothing is added above them for the sake of a mesh step.)"""
    from jax.extend import source_info_util

    scopes = [el.name for el in source_info_util.current_name_stack().stack
              if type(el).__name__ == "Scope"]
    if not scopes:
        return fn

    def named(*args):
        with jax.named_scope(scopes[-1]):
            return fn(*args)

    return named
