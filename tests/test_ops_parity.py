"""Golden-parity tests for ops/ against torch CPU reference semantics.

The EPE-parity target requires bit-level agreement (within float tolerance)
with torch's grid_sample/avg_pool/unfold/interpolate behavior, which the
reference framework builds on. Each test computes the same quantity with
torch ops directly and with our XLA ops.
"""

import jax
import numpy as np
import pytest

import jax.numpy as jnp
import torch
import torch.nn.functional as F

from raft_meets_dicl_tpu import ops


def rand(*shape, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(*shape).astype(np.float32)


class TestGridSample:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_matches_torch_inbounds_and_out(self, seed):
        img = rand(2, 7, 9, 3, seed=seed)
        # grid in [-1.5, 1.5] to also exercise zero padding out of bounds
        grid = (np.random.RandomState(seed + 10).rand(2, 5, 6, 2).astype(np.float32) - 0.5) * 3.0

        ours = np.asarray(ops.grid_sample(jnp.asarray(img), jnp.asarray(grid)))

        t_img = torch.from_numpy(img).permute(0, 3, 1, 2)
        t_out = F.grid_sample(t_img, torch.from_numpy(grid), align_corners=True)
        theirs = t_out.permute(0, 2, 3, 1).numpy()

        np.testing.assert_allclose(ours, theirs, atol=1e-5)

    def test_identity_grid(self):
        img = rand(1, 4, 4, 2)
        ys, xs = np.meshgrid(np.linspace(-1, 1, 4), np.linspace(-1, 1, 4), indexing="ij")
        grid = np.stack([xs, ys], axis=-1)[None].astype(np.float32)
        out = np.asarray(ops.grid_sample(jnp.asarray(img), jnp.asarray(grid)))
        np.testing.assert_allclose(out, img, atol=1e-5)


class TestWarp:
    def test_zero_flow_is_identity(self):
        img = rand(2, 6, 8, 3)
        flow = np.zeros((2, 6, 8, 2), np.float32)
        est, mask = ops.warp_backwards(jnp.asarray(img), jnp.asarray(flow))
        np.testing.assert_allclose(np.asarray(est), img, atol=1e-5)
        assert np.asarray(mask).all()

    def test_matches_torch_gridsample_formulation(self):
        img = rand(1, 8, 10, 2, seed=3)
        flow = rand(1, 8, 10, 2, seed=4) * 3.0

        est, mask = ops.warp_backwards(jnp.asarray(img), jnp.asarray(flow))

        # torch formulation (reference src/models/common/warp.py:5-33)
        t_img = torch.from_numpy(img).permute(0, 3, 1, 2)
        t_flow = torch.from_numpy(flow).permute(0, 3, 1, 2)
        h, w = 8, 10
        cx = torch.arange(w).view(1, w).expand(h, -1)
        cy = torch.arange(h).view(h, 1).expand(-1, w)
        grid = torch.stack((cx, cy), dim=0).float()
        fpos = (grid + t_flow).permute(0, 2, 3, 1)
        fpos[..., 0] = 2 * fpos[..., 0] / (w - 1) - 1
        fpos[..., 1] = 2 * fpos[..., 1] / (h - 1) - 1
        t_est = F.grid_sample(t_img, fpos, align_corners=True)
        t_mask = F.grid_sample(torch.ones_like(t_img), fpos, align_corners=True) > (1.0 - 1e-5)
        t_est = t_est * t_mask

        np.testing.assert_allclose(np.asarray(est), t_est.permute(0, 2, 3, 1).numpy(), atol=1e-5)
        assert (np.asarray(mask) == t_mask.permute(0, 2, 3, 1).numpy()).all()


class TestCorrVolume:
    def _torch_corr_pyramid(self, f1, f2, num_levels):
        # all-pairs correlation + avg-pool pyramid, torch formulation
        # (reference src/models/impls/raft.py:26-47)
        b, c, h, w = f1.shape
        corr = torch.matmul(f1.view(b, c, h * w).transpose(1, 2), f2.view(b, c, h * w))
        corr = corr.view(b, h, w, 1, h, w) / torch.tensor(float(c)).sqrt()
        pyramid = [corr]
        for _ in range(1, num_levels):
            b_, h1, w1, d, h2, w2 = pyramid[-1].shape
            p = F.avg_pool2d(pyramid[-1].reshape(b_ * h1 * w1, d, h2, w2), 2, stride=2)
            _, _, h2, w2 = p.shape
            pyramid.append(p.reshape(b_, h1, w1, d, h2, w2))
        return pyramid

    def test_all_pairs_matches_torch(self):
        f1, f2 = rand(2, 8, 6, 16, seed=5), rand(2, 8, 6, 16, seed=6)
        ours = np.asarray(ops.all_pairs_correlation(jnp.asarray(f1), jnp.asarray(f2)))

        t1 = torch.from_numpy(f1).permute(0, 3, 1, 2)
        t2 = torch.from_numpy(f2).permute(0, 3, 1, 2)
        theirs = self._torch_corr_pyramid(t1, t2, 1)[0].squeeze(3).numpy()
        np.testing.assert_allclose(ours, theirs, atol=1e-4)

    def test_pyramid_matches_torch(self):
        f1, f2 = rand(1, 8, 8, 4, seed=7), rand(1, 8, 8, 4, seed=8)
        pyr = ops.correlation_pyramid(
            ops.all_pairs_correlation(jnp.asarray(f1), jnp.asarray(f2)), num_levels=3
        )
        t1 = torch.from_numpy(f1).permute(0, 3, 1, 2)
        t2 = torch.from_numpy(f2).permute(0, 3, 1, 2)
        t_pyr = self._torch_corr_pyramid(t1, t2, 3)
        for ours, theirs in zip(pyr, t_pyr):
            np.testing.assert_allclose(np.asarray(ours), theirs.squeeze(3).numpy(), atol=1e-4)

    def test_lookup_matches_torch_gridsample(self):
        b, h, w, c = 1, 8, 8, 4
        radius, levels = 2, 2
        f1, f2 = rand(b, h, w, c, seed=9), rand(b, h, w, c, seed=10)
        coords = rand(b, h, w, 2, seed=11) * 2 + 4  # positions roughly inside

        vol = ops.CorrVolume(jnp.asarray(f1), jnp.asarray(f2), num_levels=levels, radius=radius)
        ours = np.asarray(vol(jnp.asarray(coords)))

        # torch formulation (reference raft.py:49-95)
        t1 = torch.from_numpy(f1).permute(0, 3, 1, 2)
        t2 = torch.from_numpy(f2).permute(0, 3, 1, 2)
        pyramid = self._torch_corr_pyramid(t1, t2, levels)
        t_coords = torch.from_numpy(coords)  # (b, h, w, 2) already

        r = radius
        dx = torch.linspace(-r, r, 2 * r + 1)
        dy = torch.linspace(-r, r, 2 * r + 1)
        delta = torch.stack(torch.meshgrid(dx, dy, indexing="ij"), dim=-1)

        out = []
        for i, corr in enumerate(pyramid):
            b_, h1, w1, d, h2, w2 = corr.shape
            corr = corr.view(b_ * h1 * w1, d, h2, w2)
            cent = t_coords.view(b, h, w, 1, 1, 2) / 2**i + delta
            cent = torch.stack(
                [2 * cent[..., 0] / (w2 - 1) - 1, 2 * cent[..., 1] / (h2 - 1) - 1], dim=-1
            )
            cent = cent.reshape(b * h * w, 2 * r + 1, 2 * r + 1, 2)
            samp = F.grid_sample(corr, cent, align_corners=True)
            out.append(samp.view(b, h, w, -1))
        theirs = torch.cat(out, dim=-1).numpy()

        np.testing.assert_allclose(ours, theirs, atol=1e-4)

    def test_mask_costs_zeroes_level(self):
        f1, f2 = rand(1, 8, 8, 4, seed=12), rand(1, 8, 8, 4, seed=13)
        coords = np.asarray(ops.coordinate_grid(1, 8, 8))
        vol = ops.CorrVolume(jnp.asarray(f1), jnp.asarray(f2), num_levels=2, radius=1)
        out = np.asarray(vol(jnp.asarray(coords), mask_costs=(3,)))
        k2 = 9
        assert (out[..., :k2] == 0).all()
        assert (out[..., k2:] != 0).any()

    def test_windowed_correlation_matches_volume_lookup(self):
        # on-the-fly correlation at level 0 must equal volume lookup level 0
        b, h, w, c = 1, 8, 8, 4
        f1, f2 = rand(b, h, w, c, seed=14), rand(b, h, w, c, seed=15)
        coords = np.asarray(ops.coordinate_grid(b, h, w)) + rand(b, h, w, 2, seed=16)

        vol = ops.CorrVolume(jnp.asarray(f1), jnp.asarray(f2), num_levels=1, radius=2)
        via_volume = np.asarray(vol(jnp.asarray(coords)))

        direct = np.asarray(
            ops.corr.windowed_correlation(
                jnp.asarray(f1), jnp.asarray(f2), jnp.asarray(coords), radius=2, scale=1
            )
        )
        np.testing.assert_allclose(direct, via_volume, atol=1e-4)


class TestUpsample:
    def test_interpolate_matches_torch(self):
        x = rand(2, 5, 7, 3, seed=20)
        ours = np.asarray(ops.interpolate_bilinear(jnp.asarray(x), (13, 11)))
        t = F.interpolate(
            torch.from_numpy(x).permute(0, 3, 1, 2), (13, 11), mode="bilinear", align_corners=True
        )
        np.testing.assert_allclose(ours, t.permute(0, 2, 3, 1).numpy(), atol=1e-5)

    def test_convex_upsample_matches_torch_unfold(self):
        b, h, w = 1, 4, 5
        flow = rand(b, h, w, 2, seed=21)
        mask_logits = rand(b, h, w, 9 * 64, seed=22)
        temperature = 4.0

        ours = np.asarray(
            ops.convex_upsample_8x(jnp.asarray(flow), jnp.asarray(mask_logits), temperature)
        )

        # torch formulation (reference Up8Network.forward, raft.py:313-331)
        t_flow = torch.from_numpy(flow).permute(0, 3, 1, 2)
        t_mask = torch.from_numpy(mask_logits).permute(0, 3, 1, 2)
        mask = t_mask.view(b, 1, 9, 8, 8, h, w)
        mask = torch.softmax(mask / temperature, dim=2)
        up_flow = F.unfold(8 * t_flow, (3, 3), padding=1)
        up_flow = up_flow.view(b, 2, 9, 1, 1, h, w)
        up_flow = torch.sum(mask * up_flow, dim=2)
        up_flow = up_flow.permute(0, 1, 4, 2, 5, 3).reshape(b, 2, h * 8, w * 8)
        theirs = up_flow.permute(0, 2, 3, 1).numpy()

        np.testing.assert_allclose(ours, theirs, atol=1e-5)

    def test_upsample_flow_2x(self):
        flow = rand(1, 4, 4, 2, seed=23)
        up = np.asarray(ops.upsample_flow_2x(jnp.asarray(flow)))
        assert up.shape == (1, 8, 8, 2)
        # corners of align_corners=True resize match original corners (x2)
        np.testing.assert_allclose(up[0, 0, 0], 2 * flow[0, 0, 0], atol=1e-5)
        np.testing.assert_allclose(up[0, -1, -1], 2 * flow[0, -1, -1], atol=1e-5)


class TestPool:
    def test_avg_pool_matches_torch(self):
        x = rand(2, 8, 6, 3, seed=30)
        ours = np.asarray(ops.avg_pool2d(jnp.asarray(x), 2))
        t = F.avg_pool2d(torch.from_numpy(x).permute(0, 3, 1, 2), 2)
        np.testing.assert_allclose(ours, t.permute(0, 2, 3, 1).numpy(), atol=1e-6)

    def test_max_pool_matches_torch(self):
        x = rand(2, 8, 6, 3, seed=31)
        ours = np.asarray(ops.max_pool2d(jnp.asarray(x), 2))
        t = F.max_pool2d(torch.from_numpy(x).permute(0, 3, 1, 2), 2)
        np.testing.assert_allclose(ours, t.permute(0, 2, 3, 1).numpy(), atol=1e-6)


@pytest.mark.parametrize("radius,n_levels", [(4, 4), (7, 2)])
def test_windowed_corr_pyramid_kernel_matches_reference(radius, n_levels):
    """The fused windowed-correlation kernels (interpreter mode off-TPU)
    match the per-level XLA composition, forward and backward, on a row
    that is no whole block and centres scattered far off the map (passes
    over a block again, the zero padding), at the models' radius and at
    the largest ``_wcp_fits_vmem`` admits (a window's 16 columns)."""
    from raft_meets_dicl_tpu.ops import pallas as pk
    from raft_meets_dicl_tpu.ops.pool import avg_pool2d

    rs = np.random.RandomState(3)
    b, h, w, c = 2, 16, 24, 32
    f1 = jnp.asarray(rs.randn(b, h, w, c), jnp.float32)
    f2 = jnp.asarray(rs.randn(b, h, w, c), jnp.float32)
    levels = [f2]
    for _ in range(n_levels - 1):
        levels.append(avg_pool2d(levels[-1], 2))
    levels = tuple(levels)
    assert pk._wcp_fits_vmem(f1, levels, radius)

    gy, gx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    # window centers include far out-of-bounds positions (zero padding)
    coords = (jnp.stack([gx, gy], -1)[None].repeat(b, 0)
              + jnp.asarray(rs.randn(b, h, w, 2) * 8, jnp.float32))

    ref = pk._wcp_reference(f1, levels, coords, radius)
    out = pk._wcp_fwd_interpret(f1, levels, coords, radius)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    dout = jnp.asarray(rs.randn(*ref.shape), jnp.float32)
    _, vjp = jax.vjp(lambda a, bb: pk._wcp_reference(a, bb, coords, radius),
                     f1, levels)
    df1_r, df2_r = vjp(dout)
    df1, df2 = pk._wcp_bwd_interpret(f1, levels, coords, dout, radius)
    assert np.allclose(np.asarray(df1), np.asarray(df1_r), atol=1e-4)
    for got, want in zip(df2, df2_r):
        assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_sample_window_matches_grid_sample_definition():
    """sample_window (patch decomposition + separable lerps) equals the
    per-displacement grid_sample definition on raw (unclamped) centers,
    values and f2 gradients."""
    import jax
    import jax.numpy as jnp

    from raft_meets_dicl_tpu.models.common.corr.common import sample_window
    from raft_meets_dicl_tpu.ops.corr import window_delta
    from raft_meets_dicl_tpu.ops.sample import sample_bilinear

    def sample_window_gs(f2, coords, radius):
        b, h, w = coords.shape[:3]
        c = f2.shape[-1]
        k = 2 * radius + 1
        delta = window_delta(radius, coords.dtype)
        pos = coords[:, None, None] + delta[None, :, :, None, None]
        s = sample_bilinear(f2, pos[..., 0].reshape(b, -1),
                            pos[..., 1].reshape(b, -1))
        return s.reshape(b, k, k, h, w, c)

    rng = np.random.RandomState(4)
    f2 = jnp.asarray(rng.randn(2, 13, 17, 5), jnp.float32)
    raw = jnp.asarray(rng.randn(2, 6, 7, 2) * 12.0, jnp.float32)

    a = sample_window_gs(f2, raw, 3)
    b_ = sample_window(f2, raw, 3)
    np.testing.assert_allclose(np.asarray(b_), np.asarray(a), atol=1e-5)

    g = jnp.asarray(rng.randn(*a.shape), jnp.float32)
    da = jax.grad(lambda m: (sample_window_gs(m, raw, 3) * g).sum())(f2)
    db = jax.grad(lambda m: (sample_window(m, raw, 3) * g).sum())(f2)
    np.testing.assert_allclose(np.asarray(db), np.asarray(da), atol=1e-5)

    # coords gradient: the fractional-lerp terms (fx, fy) are the only
    # coords-differentiable path through the patch decomposition — the
    # iterative models' flow updates backprop through exactly this
    dca = jax.grad(lambda c: (sample_window_gs(f2, c, 3) * g).sum())(raw)
    dcb = jax.grad(lambda c: (sample_window(f2, c, 3) * g).sum())(raw)
    np.testing.assert_allclose(np.asarray(dcb), np.asarray(dca), atol=1e-4)
