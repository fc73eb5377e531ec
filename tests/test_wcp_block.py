"""The block form of the windowed-correlation kernels (``ops/pallas.py``).

A block is 80 consecutive positions of a grid row served from one slab of
the map; a block whose windows do not all lie inside one slab is passed
over again until every position is served. Through the Pallas interpreter
the three kernels (forward, ``df1``, ``df2``) are held to
``_wcp_reference`` on fields of centres that take each path, and
``wcp_shared_share`` to the path the run took. What Mosaic makes of the
kernels is ``tests/test_pallas_compile.py``'s, their numbers on the chip
``scripts/chip_kernels.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu.ops import pallas as pk
from raft_meets_dicl_tpu.ops.pool import avg_pool2d

RADIUS = 4
# (rows, row length, levels): a row of the cell fs-train-1080p (240
# positions, three whole blocks, level 0 alone, as at 136x240), and four
# levels on a row that is no multiple of the block
SHAPES = {"row240-l1": (4, 240, 1), "row104-l4": (8, 104, 4)}
FAR = 1000.0


def _grid(h, w):
    yy, xx = np.meshgrid(np.arange(h, dtype=np.float32),
                         np.arange(w, dtype=np.float32), indexing="ij")
    return xx, yy


def _smooth(h, w):
    """A slow zoom and pan: every block's windows share its slab."""
    xx, yy = _grid(h, w)
    return np.stack((xx + 0.03 * (xx - w / 2) + 1.25,
                     yy + 0.05 * (yy - h / 2) - 0.75), -1)


def _edge(h, w):
    """An object's edge inside every row's first block: the columns from
    37 on move 30 cells right and 11 down against the rest."""
    c = _smooth(h, w)
    c[:, 37:, 0] += 30.4
    c[:, 37:, 1] += 11.3
    return c


def _off(side):
    """A strip of centres whose whole window lies off the map on one
    side (zeros, as ``grid_sample`` pads), inside otherwise smooth rows."""
    def field(h, w):
        c = _smooth(h, w)
        strip = (slice(None), slice(20, 50))
        if side == "left":
            c[strip + (0,)] = -FAR
        elif side == "right":
            c[strip + (0,)] = w + FAR
        elif side == "top":
            c[strip + (1,)] = -FAR
        else:
            c[strip + (1,)] = h + FAR
        return c
    return field


FIELDS = {"fits": _smooth, "edge": _edge, "off-left": _off("left"),
          "off-right": _off("right"), "off-top": _off("top"),
          "off-bottom": _off("bottom")}


def _inputs(shape, field, dtype, channels=32, seed=5):
    h, w, n_lvl = SHAPES[shape]
    rs = np.random.RandomState(seed)
    f1 = jnp.asarray(rs.randn(1, h, w, channels), dtype)
    levels = [jnp.asarray(rs.randn(1, h, w, channels), dtype)]
    for _ in range(n_lvl - 1):
        levels.append(avg_pool2d(levels[-1], 2))
    coords = jnp.asarray(FIELDS[field](h, w)[None], jnp.float32)
    dout = jnp.asarray(rs.randn(1, h, w, n_lvl * 81), jnp.float32)
    return f1, tuple(levels), coords, dout


def _close(got, want, rel):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rel * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("field", list(FIELDS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_block_kernels_match_reference(shape, field, dtype):
    """Forward and both gradients of the block form, interpreted, against
    the XLA composition on the same (representable) values."""
    f1, levels, coords, dout = _inputs(shape, field, dtype)
    wide = (f1.astype(jnp.float32),
            tuple(f.astype(jnp.float32) for f in levels))

    with jax.default_matmul_precision("highest"):
        ref, vjp = jax.vjp(
            lambda a, bb: pk._wcp_reference(a, bb, coords, RADIUS), *wide)
        df1_ref, df2_ref = vjp(dout)

    out = pk._wcp_fwd_interpret(f1, levels, coords, RADIUS)
    assert out.dtype == jnp.float32
    _close(out, ref, 2e-5)

    df1, df2 = pk._wcp_bwd_interpret(f1, levels, coords, dout, RADIUS)
    # df1 leaves in the features' type: a bf16 result rounds at 2^-9
    assert df1.dtype == f1.dtype
    _close(df1, df1_ref, 2 ** -8 if dtype == jnp.bfloat16 else 2e-5)
    for got, want in zip(df2, df2_ref):
        # the spread cotangent meets bf16 features as a bf16 pair: 2^-17
        _close(got, want, 2e-5)


def test_off_map_windows_read_zeros():
    """A centre whose whole window is off the map costs zero everywhere,
    whichever side it left by."""
    for side in ("left", "right", "top", "bottom"):
        f1, levels, coords, _ = _inputs("row104-l4", f"off-{side}",
                                        jnp.float32)
        out = pk._wcp_fwd_interpret(f1, levels, coords, RADIUS)
        assert not np.asarray(out[0, :, 20:50]).any(), side
        assert np.asarray(out[0, :, 60:]).any(), side


def _passes_taken(monkeypatch, run):
    """(first passes, first passes that served their whole block) of the
    block kernels ``run`` interprets, as ``_wcp_pass`` saw them."""
    seen = []
    inner = pk._wcp_pass

    def spy(x0, y0, todo, wp, radius):
        ytop, xb, now = inner(x0, y0, todo, wp, radius)
        jax.debug.callback(lambda first, whole: seen.append(
            (bool(first), bool(whole))), todo.all(), now.all())
        return ytop, xb, now

    monkeypatch.setattr(pk, "_wcp_pass", spy)
    jax.block_until_ready(run())
    jax.effects_barrier()
    first = [whole for is_first, whole in seen if is_first]
    return len(first), sum(first), len(seen)


@pytest.mark.parametrize("field,shape", [("fits", "row240-l1"),
                                         ("edge", "row240-l1"),
                                         ("edge", "row104-l4")])
def test_shared_share_is_the_path_taken(monkeypatch, field, shape):
    """``wcp_shared_share`` says which blocks one slab serves; the
    interpreted forward takes exactly that path: one pass over each such
    block, more over every other."""
    f1, levels, coords, _ = _inputs(shape, field, jnp.float32)
    dims = [f.shape[1:3] for f in levels]
    share = float(pk.wcp_shared_share(coords, dims, RADIUS))

    blocks, whole, passes = _passes_taken(
        monkeypatch, lambda: pk._wcp_fwd_interpret(f1, levels, coords,
                                                   RADIUS))
    h, w, n_lvl = SHAPES[shape]
    assert blocks == h * -(-w // pk._PBLK) * n_lvl
    assert share == pytest.approx(whole / blocks)
    if field == "fits":
        assert share == 1.0 and passes == blocks
    else:
        assert share < 1.0 and passes > blocks


def test_shared_share_is_one_on_zero_flow():
    """The cell's case: at zero flow every block of every level fits,
    at 136x240's row length and at 134x320's with four levels."""
    for h, w, n_lvl in ((8, 240, 1), (8, 320, 4)):
        xx, yy = _grid(h, w)
        coords = jnp.asarray(np.stack((xx, yy), -1)[None])
        dims = [(h >> l, w >> l) for l in range(n_lvl)]
        assert float(pk.wcp_shared_share(coords, dims, RADIUS)) == 1.0


def test_a_pass_serves_its_anchor_whatever_the_centres():
    """Progress: on centres scattered over and far off the map every pass
    serves at least one position, so a block ends in at most 80 passes."""
    rs = np.random.RandomState(11)
    dim, wp = (40, 56), 128
    c = jnp.asarray(rs.uniform(-80, 140, (pk._PBLK, 2)), jnp.float32)
    x0, y0, _, _ = pk._wcp_window_start(c[:, 0:1], c[:, 1:2], 0, *dim, RADIUS)
    todo = jnp.ones((pk._PBLK, 1), bool)
    passes = 0
    while bool(todo.any()):
        ytop, xb, now = pk._wcp_pass(x0, y0, todo, wp, RADIUS)
        assert bool((now & todo).any()) and not bool((now & ~todo).any())
        assert int(xb) % pk._XA == 0 and 0 <= int(xb) <= wp - pk._XS
        sx, sy = np.asarray(x0 - xb)[np.asarray(now)], np.asarray(
            y0 - ytop)[np.asarray(now)]
        assert sx.min() >= 0 and sx.max() <= pk._XS - 2 * RADIUS - 2
        assert sy.min() >= 0 and sy.max() < pk._YSPREAD
        todo = todo & ~now
        passes += 1
    assert 1 < passes <= pk._PBLK


def test_fits_vmem_admits_the_shapes_the_models_dispatch():
    """The gate counts the block form's blocks: it still admits the
    cell's call (136x240, level 0 alone) and 134x320 with four levels in
    bf16, and still refuses four float32 levels at 134x320."""
    def spec(h, w, dtype, n_lvl):
        f1 = jax.ShapeDtypeStruct((1, h, w, 256), dtype)
        return f1, tuple(jax.ShapeDtypeStruct((1, h >> l, w >> l, 256), dtype)
                         for l in range(n_lvl))

    assert pk._wcp_fits_vmem(*spec(136, 240, jnp.bfloat16, 1), RADIUS)
    assert pk._wcp_fits_vmem(*spec(134, 320, jnp.bfloat16, 4), RADIUS)
    assert not pk._wcp_fits_vmem(*spec(134, 320, jnp.float32, 4), RADIUS)
    assert not pk._wcp_fits_vmem(*spec(136, 240, jnp.bfloat16, 1), 8)
