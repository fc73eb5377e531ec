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
    return jax.shard_map(fn, mesh=mesh, in_specs=lead, out_specs=lead,
                         check_vma=False)


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

# The slab's x-start is rounded down to a multiple of 8 (Mosaic requires
# statically-provable sublane alignment for dynamic slices); the kernel
# reads a widened 8-aligned slab and folds the residual shift s = x0 - x8
# into a small per-position selection matrix built from iotas. _XW is the
# widened slab width: ceil((k+1) + 7, 8) for r=4 → 24.
_XW = 24


# Band-sharing chunk parameters: _PB consecutive positions share one
# (k+9, _XBW, C) slab read + one MXU contraction when their windows
# overlap enough (the flow-smooth case); otherwise the chunk falls back
# to the per-position path. _XBW covers the (k+1)-lane window + ≤7-lane
# alignment residual + ≤8 lanes of x-spread for radius ≤ 7.
_XBW = 32
_PB = 8


def _wcp_pads(radius):
    """(lo, hi_y, hi_x) zero-padding of the f2 maps so every clamped,
    8-aligned window is a plain in-bounds slice: x-starts lie in
    [0, lo + dim] after clamping centers to [-(r+1), dim+r], and the
    widened slab extends _XW (per-position) / _XBW with k+9 rows
    (band-shared) past the start."""
    lo = 2 * radius + 1
    return lo, 2 * radius + 10, _XBW


def _wcp_window(cx, cy, lvl, dim_h, dim_w, radius):
    """Clamped window start indices (into the padded map), the 8-aligned
    x-start + residual shift, and the bilinear fractions."""
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
    x8 = pl.multiple_of((x0 // 8) * 8, 8)
    return x8, x0 - x8, y0, cx - x0f, cy - y0f


def _x_select(s, fx, k):
    """(_XW, k) selection-and-lerp matrix: column dx picks lanes s+dx and
    s+dx+1 with the bilinear weights — the dynamic lane shift expressed as
    arithmetic instead of an (unsupported) dynamic lane slice."""
    ix = jax.lax.broadcasted_iota(jnp.int32, (_XW, k), 0)
    dxi = jax.lax.broadcasted_iota(jnp.int32, (_XW, k), 1)
    return (jnp.where(ix == dxi + s, 1.0 - fx, 0.0)
            + jnp.where(ix == dxi + s + 1, fx, 0.0))


def _wcp_fwd_kernel(coords_ref, f1_ref, *f2_refs_and_out, radius, dims):
    f2_refs = f2_refs_and_out[:-1]
    out_ref = f2_refs_and_out[-1]
    k = 2 * radius + 1
    kk = k * k
    n_j = f1_ref.shape[2]

    def body(j, _):
        f1j = f1_ref[0, 0, j].astype(jnp.float32)      # (1, C)
        cx = coords_ref[0, 0, j, 0]
        cy = coords_ref[0, 0, j, 1]
        for lvl, f2_ref in enumerate(f2_refs):
            h2, w2 = dims[lvl]
            x8, s, y0, fx, fy = _wcp_window(cx, cy, lvl, h2, w2, radius)

            slab = f2_ref[0, pl.ds(y0, k + 1), pl.ds(x8, _XW), :]
            d = jnp.sum(slab.astype(jnp.float32) * f1j[None, :, :],
                        axis=-1)                       # (k+1, _XW): (y, x)
            t = (1.0 - fy) * d[0:k, :] + fy * d[1:k + 1, :]   # (k, _XW)
            m = _x_select(s, fx, k)                           # (_XW, k)
            v = jnp.sum(t[:, :, None] * m[None, :, :], axis=1)  # (dy, dx)
            vt = v.T                                            # (dx, dy)
            out_ref[0, 0, j, lvl * k:(lvl + 1) * k, :] = vt
        return 0

    jax.lax.fori_loop(0, n_j, body, 0)


def _wcp_fwd_band_kernel(coords_ref, f1_ref, *f2_refs_and_out, radius,
                        dims):
    """Band-shared forward: chunks of _PB consecutive positions.

    Shared path per chunk·level — the bandwidth fix for the per-position
    kernel (PERF.md round 4: slab reads were 8x redundant for smooth
    flow):
      1. ONE (k+9, _XBW, C) slab read;
      2. ONE MXU contraction against the chunk's stacked f1 rows
         ((k+9)·_XBW, C) x (C, _PB);
      3. bilinear windows resolved with arithmetic selection masks —
         y as a pair-lerp plus pure row-selection (static dy loop), x as
         the lerped lane-selection (static dx loop) — no dynamic lane
         slicing, the constraint that killed the round-4 j-vectorization
         attempts.
    The per-position fallback (identical math to _wcp_fwd_kernel) runs
    whenever the chunk's window spread exceeds the shared slab.
    """
    f2_refs = f2_refs_and_out[:-1]
    out_ref = f2_refs_and_out[-1]
    k = 2 * radius + 1
    yb = k + 9
    n_c = f1_ref.shape[2]

    def chunk(ci, _):
        f1c = f1_ref[0, 0, ci].astype(jnp.float32)          # (_PB, C)

        for lvl, f2_ref in enumerate(f2_refs):
            h2, w2 = dims[lvl]
            xs, ys, fxs, fys, xb8, ymin, fits = _wcp_band_params(
                coords_ref, ci, lvl, h2, w2, radius)

            def shared(lvl=lvl, f2_ref=f2_ref, xs=xs, ys=ys, fxs=fxs,
                       fys=fys, xb8=xb8, ymin=ymin):
                slab = f2_ref[0, pl.ds(ymin, yb), pl.ds(xb8, _XBW), :]
                s2 = slab.astype(jnp.float32).reshape(yb * _XBW, -1)
                d = jax.lax.dot_general(
                    s2, f1c, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)     # (yb*_XBW, _PB)
                d3 = d.reshape(yb, _XBW, _PB)

                fyv = jnp.stack(fys).reshape(1, 1, _PB)
                t = (1.0 - fyv) * d3[0:yb - 1] + fyv * d3[1:yb]

                syv = jnp.stack([y - ymin for y in ys]).reshape(1, 1, _PB)
                iy = jax.lax.broadcasted_iota(jnp.int32, (yb - 1, 1, _PB), 0)
                e = jnp.stack([
                    jnp.sum(jnp.where(iy == syv + dy, t, 0.0), axis=0)
                    for dy in range(k)
                ])                                          # (k_dy, _XBW, _PB)

                sxv = jnp.stack([x - xb8 for x in xs]).reshape(1, 1, _PB)
                fxv = jnp.stack(fxs).reshape(1, 1, _PB)
                ix = jax.lax.broadcasted_iota(jnp.int32, (1, _XBW, _PB), 1)
                return jnp.stack([
                    jnp.sum(((ix == sxv + dx) * (1.0 - fxv)
                             + (ix == sxv + dx + 1) * fxv) * e, axis=1)
                    for dx in range(k)
                ])                                          # (k_dx, k_dy, _PB)

            def fallback(lvl=lvl, f2_ref=f2_ref, xs=xs, ys=ys, fxs=fxs,
                         fys=fys):
                vs = []
                for p in range(_PB):
                    x8p = pl.multiple_of((xs[p] // 8) * 8, 8)
                    sp = xs[p] - x8p
                    slab = f2_ref[0, pl.ds(ys[p], k + 1),
                                  pl.ds(x8p, _XW), :]
                    dd = jnp.sum(
                        slab.astype(jnp.float32)
                        * f1c[p:p + 1, :][None, :, :], axis=-1)
                    t = (1.0 - fys[p]) * dd[0:k, :] + fys[p] * dd[1:k + 1, :]
                    m = _x_select(sp, fxs[p], k)
                    v = jnp.sum(t[:, :, None] * m[None, :, :], axis=1)
                    vs.append(v.T)                          # (k_dx, k_dy)
                return jnp.stack(vs, axis=-1)               # (k, k, _PB)

            v = jax.lax.cond(fits, shared, fallback)
            for p in range(_PB):
                out_ref[0, 0, ci * _PB + p,
                        lvl * k:(lvl + 1) * k, :] = v[:, :, p]
        return 0

    jax.lax.fori_loop(0, n_c, chunk, 0)


def _unlerp(dout_ref, j, lvl, s, fx, fy, radius):
    """Transpose of the window lerps: spread the (dy, dx) cost gradient of
    position j at level lvl onto the widened (k+1, _XW) slab."""
    k = 2 * radius + 1
    dv = dout_ref[0, 0, j, lvl * k:(lvl + 1) * k, :].T  # (dy, dx)
    m = _x_select(s, fx, k)                             # (_XW, k)
    dt = jnp.sum(dv[:, None, :] * m[None, :, :], axis=2)  # (k, _XW)
    zr = jnp.zeros((1, _XW), jnp.float32)
    return ((1.0 - fy) * jnp.concatenate([dt, zr], axis=0)
            + fy * jnp.concatenate([zr, dt], axis=0))     # (k+1, _XW)


def _wcp_band_params(coords_ref, ci, lvl, h2, w2, radius):
    """Per-chunk window parameters + the shared-slab fit predicate."""
    k = 2 * radius + 1
    xs, ys, fxs, fys = [], [], [], []
    for p in range(_PB):
        cx = coords_ref[0, 0, ci * _PB + p, 0]
        cy = coords_ref[0, 0, ci * _PB + p, 1]
        x8, s, y0, fx, fy = _wcp_window(cx, cy, lvl, h2, w2, radius)
        xs.append(x8 + s)
        ys.append(y0)
        fxs.append(fx)
        fys.append(fy)
    xmin = functools.reduce(jnp.minimum, xs)
    xmax = functools.reduce(jnp.maximum, xs)
    ymin = functools.reduce(jnp.minimum, ys)
    ymax = functools.reduce(jnp.maximum, ys)
    xb8 = pl.multiple_of((xmin // 8) * 8, 8)
    fits = jnp.logical_and(xmax - xb8 <= _XBW - 1 - (k + 1),
                           ymax - ymin <= 8)
    return xs, ys, fxs, fys, xb8, ymin, fits


def _wcp_band_dv(dout_ref, ci, lvl, radius):
    """The chunk's (k_dx, k_dy, _PB) output-gradient stack."""
    k = 2 * radius + 1
    return jnp.stack([
        dout_ref[0, 0, ci * _PB + p, lvl * k:(lvl + 1) * k, :]
        for p in range(_PB)
    ], axis=-1)


def _wcp_band_dD3(dv, xs, ys, fxs, fys, xb8, ymin, radius):
    """Transpose of the band forward's selection/lerp chain: spread the
    (k, k, _PB) cost gradients onto the shared (k+9, _XBW) slab grid."""
    k = 2 * radius + 1
    yb = k + 9

    sxv = jnp.stack([x - xb8 for x in xs]).reshape(1, 1, _PB)
    fxv = jnp.stack(fxs).reshape(1, 1, _PB)
    ix = jax.lax.broadcasted_iota(jnp.int32, (1, _XBW, _PB), 1)
    de = sum(
        ((ix == sxv + dx) * (1.0 - fxv) + (ix == sxv + dx + 1) * fxv)
        * dv[dx][:, None, :]
        for dx in range(k)
    )                                               # (k_dy, _XBW, _PB)

    syv = jnp.stack([y - ymin for y in ys]).reshape(1, 1, _PB)
    iy = jax.lax.broadcasted_iota(jnp.int32, (yb - 1, 1, _PB), 0)
    dt = sum(
        jnp.where(iy == syv + dy, de[dy][None, :, :], 0.0)
        for dy in range(k)
    )                                               # (yb-1, _XBW, _PB)

    fyv = jnp.stack(fys).reshape(1, 1, _PB)
    zr = jnp.zeros((1, _XBW, _PB), jnp.float32)
    return ((1.0 - fyv) * jnp.concatenate([dt, zr], axis=0)
            + fyv * jnp.concatenate([zr, dt], axis=0))  # (yb, _XBW, _PB)


def _wcp_bwd_df1_band_kernel(coords_ref, dout_ref, *f2_refs_and_out,
                             radius, dims):
    """Band-shared df1: per chunk·level ONE slab read and ONE MXU
    contraction dD3^T(yb*_XBW, _PB) x slab(yb*_XBW, C) -> (_PB, C)."""
    f2_refs = f2_refs_and_out[:-1]
    df1_ref = f2_refs_and_out[-1]
    k = 2 * radius + 1
    yb = k + 9
    n_c = df1_ref.shape[2]

    def chunk(ci, _):
        acc = jnp.zeros((_PB, f2_refs[0].shape[-1]), jnp.float32)
        for lvl, f2_ref in enumerate(f2_refs):
            h2, w2 = dims[lvl]
            xs, ys, fxs, fys, xb8, ymin, fits = _wcp_band_params(
                coords_ref, ci, lvl, h2, w2, radius)
            dv = _wcp_band_dv(dout_ref, ci, lvl, radius)

            def shared(f2_ref=f2_ref, xs=xs, ys=ys, fxs=fxs, fys=fys,
                       xb8=xb8, ymin=ymin, dv=dv):
                dd3 = _wcp_band_dD3(dv, xs, ys, fxs, fys, xb8, ymin,
                                    radius)
                slab = f2_ref[0, pl.ds(ymin, yb), pl.ds(xb8, _XBW), :]
                s2 = slab.astype(jnp.float32).reshape(yb * _XBW, -1)
                return jax.lax.dot_general(
                    dd3.reshape(yb * _XBW, _PB), s2,
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)     # (_PB, C)

            def fallback(f2_ref=f2_ref, xs=xs, ys=ys, fxs=fxs, fys=fys,
                         dv=dv, lvl=lvl):
                outs = []
                for p in range(_PB):
                    x8p = pl.multiple_of((xs[p] // 8) * 8, 8)
                    sp = xs[p] - x8p
                    m = _x_select(sp, fxs[p], k)
                    dvp = dv[:, :, p].T                     # (k_dy, k_dx)
                    dt = jnp.sum(dvp[:, None, :] * m[None, :, :], axis=2)
                    zr = jnp.zeros((1, _XW), jnp.float32)
                    dd = ((1.0 - fys[p])
                          * jnp.concatenate([dt, zr], axis=0)
                          + fys[p] * jnp.concatenate([zr, dt], axis=0))
                    slab = f2_ref[0, pl.ds(ys[p], k + 1),
                                  pl.ds(x8p, _XW), :]
                    part = jnp.sum(dd[:, :, None]
                                   * slab.astype(jnp.float32), axis=(0, 1))
                    outs.append(part)
                return jnp.stack(outs)                      # (_PB, C)

            acc = acc + jax.lax.cond(fits, shared, fallback)
        df1_ref[0, 0, ci] = acc
        return 0

    jax.lax.fori_loop(0, n_c, chunk, 0)


def _wcp_bwd_df2_band_kernel(coords_ref, f1_ref, dout_ref, df2_ref, *,
                             radius, lvl, dims):
    """Band-shared df2 for ONE level: per chunk ONE MXU outer product
    dD3(yb*_XBW, _PB) x f1c(_PB, C) accumulated into the shared slab."""
    k = 2 * radius + 1
    yb = k + 9
    n_c = f1_ref.shape[2]
    h2, w2 = dims
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        df2_ref[:] = jnp.zeros_like(df2_ref)

    def chunk(ci, _):
        f1c = f1_ref[0, 0, ci].astype(jnp.float32)          # (_PB, C)
        xs, ys, fxs, fys, xb8, ymin, fits = _wcp_band_params(
            coords_ref, ci, lvl, h2, w2, radius)
        dv = _wcp_band_dv(dout_ref, ci, 0, radius)

        def shared():
            dd3 = _wcp_band_dD3(dv, xs, ys, fxs, fys, xb8, ymin, radius)
            ds2 = jax.lax.dot_general(
                dd3.reshape(yb * _XBW, _PB), f1c,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (yb*_XBW, C)
            df2_ref[0, pl.ds(ymin, yb), pl.ds(xb8, _XBW), :] += (
                ds2.reshape(yb, _XBW, -1))

        def fallback():
            for p in range(_PB):
                x8p = pl.multiple_of((xs[p] // 8) * 8, 8)
                sp = xs[p] - x8p
                m = _x_select(sp, fxs[p], k)
                dvp = dv[:, :, p].T                         # (k_dy, k_dx)
                dt = jnp.sum(dvp[:, None, :] * m[None, :, :], axis=2)
                zr = jnp.zeros((1, _XW), jnp.float32)
                dd = ((1.0 - fys[p]) * jnp.concatenate([dt, zr], axis=0)
                      + fys[p] * jnp.concatenate([zr, dt], axis=0))
                df2_ref[0, pl.ds(ys[p], k + 1), pl.ds(x8p, _XW), :] += (
                    dd[:, :, None] * f1c[p:p + 1, :][None, :, :])

        jax.lax.cond(fits, shared, fallback)
        return 0

    jax.lax.fori_loop(0, n_c, chunk, 0)


def _wcp_bwd_df1_kernel(coords_ref, dout_ref, *f2_refs_and_out, radius,
                        dims):
    """df1 over all levels (reads the f2 maps, touches no df2 state —
    split from the df2 kernel so each stays under the VMEM budget)."""
    f2_refs = f2_refs_and_out[:-1]
    df1_ref = f2_refs_and_out[-1]
    k = 2 * radius + 1
    n_j = df1_ref.shape[2]

    def body(j, _):
        cx = coords_ref[0, 0, j, 0]
        cy = coords_ref[0, 0, j, 1]
        acc = None
        for lvl, f2_ref in enumerate(f2_refs):
            h2, w2 = dims[lvl]
            x8, s, y0, fx, fy = _wcp_window(cx, cy, lvl, h2, w2, radius)
            dd = _unlerp(dout_ref, j, lvl, s, fx, fy, radius)

            slab = f2_ref[0, pl.ds(y0, k + 1), pl.ds(x8, _XW), :]
            part = jnp.sum(dd[:, :, None] * slab.astype(jnp.float32), axis=0)
            part = jnp.sum(part, axis=0, keepdims=True)   # (1, C)
            acc = part if acc is None else acc + part
        df1_ref[0, 0, j] = acc
        return 0

    jax.lax.fori_loop(0, n_j, body, 0)


def _wcp_bwd_df2_kernel(coords_ref, f1_ref, dout_ref, df2_ref, *, radius,
                        lvl, dims):
    """df2 for ONE pyramid level, accumulated across the i-grid (the
    output block is indexed by b only and stays resident in VMEM).
    ``dout_ref`` carries only this level's (k, k) channel block."""
    k = 2 * radius + 1
    n_j = f1_ref.shape[2]
    h2, w2 = dims
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        df2_ref[:] = jnp.zeros_like(df2_ref)

    def body(j, _):
        f1j = f1_ref[0, 0, j].astype(jnp.float32)      # (1, C)
        cx = coords_ref[0, 0, j, 0]
        cy = coords_ref[0, 0, j, 1]
        x8, s, y0, fx, fy = _wcp_window(cx, cy, lvl, h2, w2, radius)
        dd = _unlerp(dout_ref, j, 0, s, fx, fy, radius)

        df2_ref[0, pl.ds(y0, k + 1), pl.ds(x8, _XW), :] += (
            dd[:, :, None] * f1j[None, :, :])
        return 0

    jax.lax.fori_loop(0, n_j, body, 0)


def _wcp_pad_f2(f2_levels, radius):
    lo, hi_y, hi_x = _wcp_pads(radius)
    return tuple(
        jnp.pad(f2, ((0, 0), (lo, hi_y), (lo, hi_x), (0, 0)))
        for f2 in f2_levels
    )


def _wcp_fwd_interpret(f1, f2_levels, coords, radius, band=None):
    """Interpreter-mode forward (kernel correctness tests off-TPU)."""
    return _wcp_fwd_tpu(f1, tuple(f2_levels), coords, radius,
                        interpret=True, band=band)


def _wcp_bwd_interpret(f1, f2_levels, coords, dout, radius, band=None):
    """Interpreter-mode backward (kernel correctness tests off-TPU)."""
    return _wcp_bwd_tpu(f1, tuple(f2_levels), coords, dout, radius,
                        interpret=True, band=band)


def _wcp_fwd_tpu(f1, f2_levels, coords, radius, interpret=False,
                 band=None):
    b, n_i, n_j, c = f1.shape
    k = 2 * radius + 1
    n_lvl = len(f2_levels)
    dims = tuple((f2.shape[1], f2.shape[2]) for f2 in f2_levels)
    f2p = _wcp_pad_f2(f2_levels, radius)
    if band is None:
        band = _wcp_band_enabled()

    if band:
        # pad the position axis to whole chunks; padded positions sample
        # around coord 0 (in-bounds garbage) and are sliced off below
        n_jp = -(-n_j // _PB) * _PB
        if n_jp != n_j:
            f1 = jnp.pad(f1, ((0, 0), (0, 0), (0, n_jp - n_j), (0, 0)))
            coords = jnp.pad(coords,
                             ((0, 0), (0, 0), (0, n_jp - n_j), (0, 0)))
        f1r = f1.reshape(b, n_i, n_jp // _PB, _PB, c)
        kernel = functools.partial(_wcp_fwd_band_kernel, radius=radius,
                                   dims=dims)
        f1_spec = pl.BlockSpec((1, 1, n_jp // _PB, _PB, c),
                               lambda bi, ii: (bi, ii, 0, 0, 0),
                               memory_space=pltpu.VMEM)
    else:
        n_jp = n_j
        # j rides an untiled axis (the dummy sublane dim keeps the
        # last-two dims static so per-position dynamic indexing is legal)
        f1r = f1.reshape(b, n_i, n_j, 1, c)
        kernel = functools.partial(_wcp_fwd_kernel, radius=radius,
                                   dims=dims)
        f1_spec = pl.BlockSpec((1, 1, n_j, 1, c),
                               lambda bi, ii: (bi, ii, 0, 0, 0),
                               memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_i, n_jp, n_lvl * k, k),
                                       jnp.float32),
        grid=(b, n_i),
        in_specs=[
            pl.BlockSpec((1, 1, n_jp, 2), lambda bi, ii: (bi, ii, 0, 0),
                         memory_space=pltpu.SMEM),
            f1_spec,
        ] + [
            pl.BlockSpec((1,) + f2.shape[1:], lambda bi, ii: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM)
            for f2 in f2p
        ],
        out_specs=pl.BlockSpec((1, 1, n_jp, n_lvl * k, k),
                               lambda bi, ii: (bi, ii, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(coords, f1r, *f2p)
    out = out[:, :, :n_j]
    # (level, dx, dy) channel flatten — (L*k, k) row-major is exactly that
    return out.reshape(b, n_i, n_j, n_lvl * k * k)


def _wcp_band_enabled():
    from ..utils import env

    return env.get_bool("RMD_WCP_BAND")


def _wcp_bwd_tpu(f1, f2_levels, coords, dout, radius, interpret=False,
                 band=None):
    b, n_i, n_j, c = f1.shape
    lo, _hi_y, _hi_x = _wcp_pads(radius)
    f2p = _wcp_pad_f2(f2_levels, radius)
    dims = tuple((f2.shape[1], f2.shape[2]) for f2 in f2_levels)
    if band is None:
        band = _wcp_band_enabled()

    k = 2 * radius + 1
    n_lvl = len(f2_levels)

    if band:
        # whole-chunk padding; padded positions carry zero dout and
        # coords 0 (in-bounds), so they contribute nothing to df1/df2
        n_jp = -(-n_j // _PB) * _PB
        if n_jp != n_j:
            pad = ((0, 0), (0, 0), (0, n_jp - n_j), (0, 0))
            f1 = jnp.pad(f1, pad)
            coords = jnp.pad(coords, pad)
            dout = jnp.pad(dout, pad)
        f1r = f1.reshape(b, n_i, n_jp // _PB, _PB, c)
        row_spec = pl.BlockSpec((1, 1, n_jp // _PB, _PB, c),
                                lambda bi, ii: (bi, ii, 0, 0, 0),
                                memory_space=pltpu.VMEM)
        df1_kernel = functools.partial(_wcp_bwd_df1_band_kernel,
                                       radius=radius, dims=dims)
        df2_kernel = _wcp_bwd_df2_band_kernel
        df1_shape = (b, n_i, n_jp // _PB, _PB, c)
    else:
        n_jp = n_j
        f1r = f1.reshape(b, n_i, n_j, 1, c)
        row_spec = pl.BlockSpec((1, 1, n_j, 1, c),
                                lambda bi, ii: (bi, ii, 0, 0, 0),
                                memory_space=pltpu.VMEM)
        df1_kernel = functools.partial(_wcp_bwd_df1_kernel, radius=radius,
                                       dims=dims)
        df2_kernel = _wcp_bwd_df2_kernel
        df1_shape = (b, n_i, n_j, 1, c)

    doutr = dout.reshape(b, n_i, n_jp, n_lvl * k, k)

    coords_spec = pl.BlockSpec((1, 1, n_jp, 2),
                               lambda bi, ii: (bi, ii, 0, 0),
                               memory_space=pltpu.SMEM)
    dout_spec = pl.BlockSpec((1, 1, n_jp, n_lvl * k, k),
                             lambda bi, ii: (bi, ii, 0, 0, 0),
                             memory_space=pltpu.VMEM)

    df1 = pl.pallas_call(
        df1_kernel,
        out_shape=jax.ShapeDtypeStruct(df1_shape, jnp.float32),
        grid=(b, n_i),
        in_specs=[coords_spec, dout_spec] + [
            pl.BlockSpec((1,) + f2.shape[1:], lambda bi, ii: (bi, 0, 0, 0),
                         memory_space=pltpu.VMEM)
            for f2 in f2p
        ],
        out_specs=row_spec,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
    )(coords, doutr, *f2p).reshape(b, n_i, n_jp, c)[:, :, :n_j]

    df2_out = []
    for lvl, f2 in enumerate(f2p):
        # pass only this level's dout columns; raise the scoped-vmem cap —
        # the accumulated df2 block (revisited across the i-grid) plus its
        # pipeline double-buffer exceed the default budget at level 0
        dout_l = doutr[:, :, :, lvl * k:(lvl + 1) * k, :]
        dout_l_spec = pl.BlockSpec((1, 1, n_jp, k, k),
                                   lambda bi, ii: (bi, ii, 0, 0, 0),
                                   memory_space=pltpu.VMEM)
        df2_l = pl.pallas_call(
            functools.partial(df2_kernel, radius=radius, lvl=lvl,
                              dims=dims[lvl]),
            out_shape=jax.ShapeDtypeStruct(f2.shape, jnp.float32),
            grid=(b, n_i),
            in_specs=[coords_spec, row_spec, dout_l_spec],
            out_specs=pl.BlockSpec((1,) + f2.shape[1:],
                                   lambda bi, ii: (bi, 0, 0, 0),
                                   memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=interpret,
        )(coords, f1r, dout_l)

        # strip the padding back off
        h2, w2 = dims[lvl]
        df2_out.append(df2_l[:, lo:lo + h2, lo:lo + w2, :])

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

    Also gates on radius: the widened slab width _XW covers the
    (k+1)-lane window plus the ≤7-lane alignment shift only for
    radius ≤ 7 — beyond that the x-selection matrix would silently drop
    the last lerp lane, so larger radii take the (exact) XLA path too.
    """
    if radius > 7:
        return False
    lo, hi_y, hi_x = _wcp_pads(radius)
    k = 2 * radius + 1
    n_lvl = len(f2_levels)
    n_j, c = f1.shape[2], f1.shape[3]
    itemsize = 2 if f1.dtype == jnp.bfloat16 else 4
    total = n_j * (n_lvl * k + 8) * 128 * 4        # out block (padded)
    total += n_j * 8 * c * itemsize                # f1 row block
    for f2 in f2_levels:
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
    ``_wcp_bwd_df2_kernel``)."""
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
