"""RAFT baseline (``raft/baseline``), TPU-native.

Re-design of the reference implementation (src/models/impls/raft.py, itself
after Teed & Deng's RAFT) in Flax/JAX:

- the all-pairs correlation volume + pyramid + windowed lookup live in
  ``ops.corr`` (einsum on the MXU + vectorized gathers, raft.py:15-95),
- the iterative GRU update loop is a single ``nn.scan`` over the
  ``(hidden, coords)`` carry (raft.py:401-428's python loop) — one compiled
  step body instead of an unrolled graph,
- per-iteration gradient detaches (coords, flow input, optional corr) map
  to ``lax.stop_gradient``,
- layout is NHWC throughout; flow tensors are (B, H, W, 2) with
  channel 0 = x.

Static switches (``iterations``, ``upnet``, ``corr_flow``,
``corr_grad_stop``, ``mask_costs``, ``return_state``) are python-level
arguments: changing them recompiles, matching the per-stage argument
override model.

Iteration-ladder continuation: ``flow_init``/``hidden_init`` seed the
recurrence carry at the 1/8 grid and ``return_state=True`` returns the
final carry alongside the flow list, so ``iterations=12`` can run as
chained shorter programs (4+4+4) with ``(flow, hidden)`` handed between
them — each rung recomputes the encoders/pyramid (deterministic, same
images), and the carry re-entry is exact: the scan body's first action
is ``flow = coords1 - coords0`` with ``coords1 = coords0 + flow_init``,
an integer-grid add/subtract round-trip that is lossless in f32 for any
flow magnitude a real pair produces. The returned ``delta`` (mean-pixel
L2 of the last iteration's flow change, per sample) is the cheap
convergence probe the serving ladder reads *between* programs — no
data-dependent control flow ever enters the jit.
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...ops import quant as quant_ops
from ...ops.corr import (
    correlation_pyramid_direct,
    lookup_pyramid_levels,
    window_delta,
)
from ...ops.upsample import convex_upsample_8x
from .. import common
from ..common.blocks.dicl import DisplacementAwareProjection
from ..common.util import ConvParams
from ..common.grid import coordinate_grid
from ..common.hsup import upsample2d_bilinear
from ..config import register_loss, register_model
from ..model import Loss, Model, ModelAdapter, Result


class SoftArgMaxFlowRegression(nn.Module):
    """Cost → flow readout: softmax-weighted displacement sum per level.

    Input: lookup output (B, H, W, L*(2r+1)²), channels (level, dx, dy).
    Returns a list of per-level flow deltas (B, H, W, 2), scaled 2^level.
    """

    num_levels: int
    radius: int
    temperature: float = 1.0
    dap: bool = False

    @nn.compact
    def __call__(self, corr):
        # ``corr`` is either the flat (B, H, W, L·K²) lookup or the
        # per-level list of (B, H, W, K, K) windows (layout-copy-free path)
        is_levels = isinstance(corr, (list, tuple))
        b, h, w = corr[0].shape[:3] if is_levels else corr.shape[:3]
        k = 2 * self.radius + 1
        dtype = corr[0].dtype if is_levels else corr.dtype
        delta = window_delta(self.radius, dtype)

        out = []
        for lvl in range(self.num_levels):
            if is_levels:
                # per-level windows are (dy, dx)-ordered; flat channels
                # (and window_delta) are dx-major
                score = corr[lvl].transpose(0, 1, 2, 4, 3)
                score = score.reshape(b, h, w, k * k)
            else:
                score = corr[..., lvl * k * k : (lvl + 1) * k * k]

            if self.dap:
                score = score.reshape(b, h, w, k, k)
                score = DisplacementAwareProjection((self.radius, self.radius))(score)
                score = score.reshape(b, h, w, k * k)

            score = jax.nn.softmax(score / self.temperature, axis=-1)
            flow = jnp.einsum(
                "bhwk,kc->bhwc", score, delta.reshape(k * k, 2) * 2**lvl
            )
            out.append(flow)

        return out


def make_flow_regression(type, num_levels, radius, **kwargs):
    if type == "softargmax":
        return SoftArgMaxFlowRegression(num_levels, radius, dap=False, **kwargs)
    if type == "softargmax+dap":
        return SoftArgMaxFlowRegression(num_levels, radius, dap=True, **kwargs)
    raise ValueError(f"unknown correlation module type '{type}'")


class _WindowConv1x1(nn.Module):
    """1x1 conv over concatenated correlation windows, without the concat.

    Parameter-identical to ``nn.Conv(features, (1, 1))`` on the flat
    (B, H, W, L·K²) lookup tensor (kernel (1, 1, L·K², features) + bias),
    but accepts the per-level list of (B, H, W, K, K) windows and contracts
    each level against its kernel slice directly — the flatten + concat the
    flat form needs costs XLA tile-padded layout copies (a (…, 9, 9) minor
    pair pads to (16, 128) tiles: 25x memory inflation, ~30 ms/step
    profiled at the bench config). Flat tensors still work (shared zoo
    callers pass them), so checkpoints are interchangeable.

    List items may mix two forms (the raft/fs hybrid dispatch produces
    both): rank-5 (B, H, W, K_dy, K_dx) window tensors, and rank-4
    already-flat (B, H, W, n·K²) chunks in the dx-major flat channel
    order (the windowed kernel's native output — contracted directly, no
    reshape/transpose/concat copies).
    """

    features: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        levels = x if isinstance(x, (list, tuple)) else None
        if levels is not None:
            in_features = sum(
                l.shape[-1] if l.ndim == 4 else l.shape[-2] * l.shape[-1]
                for l in levels)
            pdtype = levels[0].dtype
        else:
            in_features = x.shape[-1]
            pdtype = x.dtype

        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (1, 1, in_features, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))

        dt = self.dtype or jnp.promote_types(pdtype, kernel.dtype)
        k2 = kernel.reshape(in_features, self.features).astype(dt)

        if levels is None:
            y = jnp.einsum("bhwc,cf->bhwf", x.astype(dt), k2,
                           preferred_element_type=jnp.float32)
        else:
            y = 0.0
            offset = 0
            for lvl in levels:
                if lvl.ndim == 4:
                    # flat chunk, channels already in the dx-major flat
                    # contract order: plain slice of the kernel matrix
                    n = lvl.shape[-1]
                    y = y + jnp.einsum(
                        "bhwc,cf->bhwf", lvl.astype(dt),
                        k2[offset : offset + n],
                        preferred_element_type=jnp.float32)
                    offset += n
                    continue
                # level windows are (dy, dx)-ordered; the kernel slice is
                # dx-major (the flat-tensor channel contract), so reshape
                # it (dx, dy, f) and contract both axes crosswise
                kdy, kdx = lvl.shape[-2], lvl.shape[-1]
                kl = k2[offset : offset + kdy * kdx].reshape(kdx, kdy,
                                                             self.features)
                y = y + jnp.einsum("bhwka,akf->bhwf", lvl.astype(dt), kl,
                                   preferred_element_type=jnp.float32)
                offset += kdy * kdx
        return y.astype(dt) + bias.astype(dt)


class BasicMotionEncoder(nn.Module):
    """Combine correlation features and current flow into motion features.

    ``corr`` may be the flat (B, H, W, L·K²) lookup tensor or the
    per-level window list (see ``_WindowConv1x1``); parameters are
    identical either way (conv names match the reference's
    convc1/convc2/convf1/convf2/conv, chkpt_convert rules).
    """

    dtype: Any = None

    @nn.compact
    def __call__(self, flow, corr):
        dt = self.dtype
        cor = nn.relu(_WindowConv1x1(256, dtype=dt, name="Conv_0")(corr))
        cor = nn.relu(nn.Conv(192, (3, 3), dtype=dt, name="Conv_1")(cor))

        flo = nn.relu(nn.Conv(128, (7, 7), dtype=dt, name="Conv_2")(flow))
        flo = nn.relu(nn.Conv(64, (3, 3), dtype=dt, name="Conv_3")(flo))

        combined = jnp.concatenate((cor, flo), axis=-1)
        combined = nn.relu(nn.Conv(128 - 2, (3, 3), dtype=dt,
                                   name="Conv_4")(combined))

        flow = flow.astype(combined.dtype)
        return jnp.concatenate((combined, flow), axis=-1)  # 128 channels


class SepConvGru(nn.Module):
    """Separable (1x5 then 5x1) convolutional GRU.

    The z and r gates read the same (h, x) concat, so their convs run as
    one merged conv with doubled output channels (fewer, larger MXU ops:
    the scan body executes 12x per step and small-op overhead dominates
    the profile). Parameters stay per-gate (Conv_0/Conv_1 = z1/r1,
    Conv_3/Conv_4 = z2/r2 — the reference's convz1/convr1/convz2/convr2,
    chkpt_convert rules), merged only at apply time.
    """

    hidden_dim: int = 128
    dtype: Any = None

    @nn.compact
    def __call__(self, h, x):
        from jax.ad_checkpoint import checkpoint_name

        def conv(inp, w, b=None):
            out = jax.lax.conv_general_dilated(
                inp, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return out if b is None else out + b

        dt = self.dtype
        hd = self.hidden_dim
        for i, ksize in enumerate(((1, 5), (5, 1))):
            zk, zb = ConvParams(hd, ksize, name=f"Conv_{3 * i}")(
                h.shape[-1] + x.shape[-1])
            rk, rb = ConvParams(hd, ksize, name=f"Conv_{3 * i + 1}")(
                h.shape[-1] + x.shape[-1])
            qk, qb = ConvParams(hd, ksize, name=f"Conv_{3 * i + 2}")(
                h.shape[-1] + x.shape[-1])

            cdt = dt or zk.dtype
            hc = h.astype(cdt)
            xc = x.astype(cdt)

            # gate convs split along the input-channel axis: the
            # (h, x)-concat conv equals conv(h, W_h) + conv(x, W_x) by
            # linearity. The x-half outputs are checkpoint-named so the
            # remat policy saves them instead of recomputing in the
            # backward pass — the x convs are 2/3 of the gate FLOPs and
            # their saved activations are small (measured net win at the
            # bench config); it also skips the h/x concat materialization.
            zrk_h = jnp.concatenate((zk[:, :, :hd], rk[:, :, :hd]),
                                    axis=-1).astype(cdt)
            zrk_x = jnp.concatenate((zk[:, :, hd:], rk[:, :, hd:]),
                                    axis=-1).astype(cdt)
            zrb = jnp.concatenate((zb, rb)).astype(cdt)

            zr_x = checkpoint_name(conv(xc, zrk_x), "gru_gate_x")
            zr = conv(hc, zrk_h) + zr_x + zrb
            z = nn.sigmoid(zr[..., :hd])
            r = nn.sigmoid(zr[..., hd:])

            q_x = checkpoint_name(conv(xc, qk[:, :, hd:].astype(cdt)),
                                  "gru_gate_x")
            q = jnp.tanh(conv((r * h).astype(cdt), qk[:, :, :hd].astype(cdt))
                         + q_x + qb.astype(cdt))
            h = (1.0 - z) * h + z * q

        return h


class FlowHead(nn.Module):
    """Hidden state → delta flow (returned float32)."""

    hidden_dim: int = 256
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Conv(self.hidden_dim, (3, 3), dtype=self.dtype)(x))
        return nn.Conv(2, (3, 3), dtype=self.dtype)(x).astype(jnp.float32)


class BasicUpdateBlock(nn.Module):
    """One recurrent update: motion encoding + GRU + flow head."""

    hidden_dim: int = 128
    dtype: Any = None

    @nn.compact
    def __call__(self, h, x, corr, flow):
        m = BasicMotionEncoder(dtype=self.dtype)(flow, corr)
        x = jnp.concatenate((x, m.astype(x.dtype)), axis=-1)

        h = SepConvGru(self.hidden_dim, dtype=self.dtype)(h, x)
        d = FlowHead(256, dtype=self.dtype)(h)

        return h, d


class Up8Network(nn.Module):
    """Convex 8x upsampling: per-pixel softmax over 3x3 coarse neighbors.

    Mask channels are neighbor-major (k, sub-row, sub-col) — torch RAFT's
    native layout (``view(b, 1, 9, 8, 8, h, w)``), so converted
    checkpoints import without a channel permutation. The softmax +
    convex combine run as the fused Pallas kernel
    (``ops.pallas.convex_combine_8x``) on TPU — the XLA-scheduled form
    materialized ~750 MB/step of f32 mask intermediates with layout
    copies at the bench config, the single largest cost of the training
    step. The flow window stays f32 throughout: it IS the model output,
    and bf16 ulp at 8·flow magnitudes is ~px-scale.
    """

    temperature: float = 4.0  # 4.0 = 1.0/0.25 in original RAFT
    dtype: Any = None

    @nn.compact
    def __call__(self, hidden, flow):
        mask = nn.Conv(256, (3, 3), dtype=self.dtype)(hidden)
        mask = nn.relu(mask)
        mask = nn.Conv(8 * 8 * 9, (1, 1), dtype=self.dtype)(mask)
        return convex_upsample_8x(flow, mask, temperature=self.temperature)


def upsample_flows(flows, hiddens, carry, full_shape, dtype=None, upnet=True,
                   final_only=False):
    """Scan outputs → list of full-resolution flows: the tail every
    RAFT-family ``__call__`` shares (call it inside ``@nn.compact``).

    ``flows``/``hiddens`` are the scan's stacked per-iteration outputs,
    ``(iterations, B, H/8, W/8, ·)``; ``carry`` is its final ``(hidden,
    flow)``. By default the convex 8x upsampling runs once over all
    ``iterations * B`` samples, outside the scan (one large einsum +
    pixel shuffle instead of 12 rematerialized ones): what the sequence
    loss needs. ``final_only`` — static, set by the builders of programs
    that return ``result.final()`` alone — upsamples the carry instead,
    batch ``B``: a one-element list holding the same final flow (same
    arithmetic on the same operands), and nothing of ``hiddens`` is read,
    so the stack leaves the compiled loop.
    """
    if final_only:
        hiddens, flows = carry[0][None], carry[1][None]
    n, b, hc, wc, _ = flows.shape
    flows_flat = flows.reshape(n * b, hc, wc, 2)
    hiddens_flat = hiddens.reshape(n * b, hc, wc, hiddens.shape[-1])

    # always *called* so its params exist regardless of ``upnet``.
    # remat'd: recomputing the two convs + softmax in the backward pass
    # is cheaper than saving the f32 mask residuals (66MB with layout
    # copies at the bench config)
    # explicit name: the remat wrapper would otherwise prefix the module
    # path ('CheckpointUp8Network_0'), breaking checkpoint compatibility
    ups = nn.remat(Up8Network, prevent_cse=False)(
        dtype=dtype, name="Up8Network_0")(hiddens_flat, flows_flat)
    if not upnet:
        ups = 8.0 * upsample2d_bilinear(flows_flat, full_shape)
    ups = ups.reshape(n, b, *full_shape, 2)

    # unstack the scan axis into per-iteration lists (protocol parity)
    return [ups[i] for i in range(n)]


class _RaftStep(nn.Module):
    """One GRU iteration — the nn.scan body.

    Carry is (hidden, flow); broadcast inputs are the correlation
    pyramid, context features, and the coords0 grid. The carry is the
    *flow* (not coords1) so that a program boundary is a no-op: every
    iteration reconstructs ``coords1 = coords0 + flow`` itself, which is
    exactly what a continuation rung does with ``flow_init`` — chained
    4+4+4 is therefore bit-identical to monolithic 12 in f32 (carrying
    coords1 instead would make re-entry inexact: ``c0 + fl(c1 - c0)``
    loses ulps once |flow| exceeds the coarse coords). Produces the
    coarse-grid flow and hidden state per iteration — the convex 8x
    upsampling runs *outside* the scan, batched over all iterations (its
    full-resolution intermediates would otherwise be rematerialized per
    iteration in the backward pass; profiled as the step's largest cost).
    """

    corr_levels: int
    corr_radius: int
    recurrent_channels: int
    corr_flow: bool
    corr_grad_stop: bool
    mask_costs: Tuple[int, ...]
    corr_reg_type: str
    corr_reg_args: dict
    dtype: Any = None

    @nn.compact
    def __call__(self, carry, pyramid, x, coords0):
        h, flow = carry
        flow = jax.lax.stop_gradient(flow)
        coords1 = coords0 + flow

        # per-level list form: the flatten-to-K² + level concat the flat
        # lookup would do costs tile-padding layout copies (~30 ms/step);
        # every consumer contracts the window axes anyway
        from jax.ad_checkpoint import checkpoint_name

        # the scopes are metadata for the compiled text's readers
        # (compile/owners.py): they change no operation
        with jax.named_scope("lookup"):
            corr = lookup_pyramid_levels(pyramid, coords1, self.corr_radius,
                                         self.mask_costs)
            # named so the remat policy can save the lookup output:
            # recomputing the windowed einsums in the backward pass costs
            # more than the (B, H/8, W/8, L·(2r+1)²) buffer per iteration
            # it saves
            corr = [checkpoint_name(lvl, "corr_features") for lvl in corr]

            # always *call* the readout so its params exist regardless of
            # the static switch (per-stage overrides / checkpoint
            # compatibility); XLA dead-code-eliminates the unused branch
            reg = make_flow_regression(
                self.corr_reg_type, self.corr_levels, self.corr_radius,
                **self.corr_reg_args,
            )
            corr_flows = tuple(flow + d for d in reg(corr))
            if not self.corr_flow:
                corr_flows = ()

            if self.corr_grad_stop:
                corr = jax.lax.stop_gradient(corr)

        with jax.named_scope("update"):
            h, d = BasicUpdateBlock(self.recurrent_channels,
                                    dtype=self.dtype)(h, x, corr, flow)

        coords1 = coords1 + d
        flow = coords1 - coords0

        return (h, flow), (flow, h, corr_flows)


class RaftModule(nn.Module):
    """RAFT flow estimation network (reference RaftModule, raft.py:334-433)."""

    dropout: float = 0.0
    mixed_precision: bool = False
    corr_levels: int = 4
    corr_radius: int = 4
    corr_channels: int = 256
    context_channels: int = 128
    recurrent_channels: int = 128
    encoder_norm: str = "instance"
    context_norm: str = "batch"
    encoder_type: str = "raft"
    context_type: str = "raft"
    corr_reg_type: str = "softargmax"
    corr_reg_args: dict = None
    remat: bool = True

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False, iterations=12,
                 flow_init=None, hidden_init=None, upnet=True, corr_flow=False,
                 corr_grad_stop=False, mask_costs=(), return_state=False,
                 quant=None, quant_clip=1.0, final_only=False):
        hdim = self.recurrent_channels
        cdim = self.context_channels
        reg_args = self.corr_reg_args or {}

        # bf16 compute policy (the reference's autocast regions,
        # src/models/impls/raft.py:377-415): encoders, correlation volume,
        # and update block run in bf16; params, coords/flow arithmetic,
        # softmaxes, and the loss stay float32. MXU contractions accumulate
        # in float32 via preferred_element_type.
        dt = jnp.bfloat16 if self.mixed_precision else None

        fnet = common.encoders.make_encoder_s3(
            self.encoder_type, output_dim=self.corr_channels,
            norm_type=self.encoder_norm, dropout=self.dropout, dtype=dt,
        )
        cnet = common.encoders.make_encoder_s3(
            self.context_type, output_dim=hdim + cdim,
            norm_type=self.context_norm, dropout=self.dropout, dtype=dt,
        )

        with jax.named_scope("encoders"):
            fmap1, fmap2 = fnet((img1, img2), train, frozen_bn)
            if dt is None:
                fmap1 = fmap1.astype(jnp.float32)
                fmap2 = fmap2.astype(jnp.float32)

        # The all-pairs volume + einsum windowed lookup is the FASTEST
        # measured realization on-chip at training crops (the feature-space
        # alternative — ops.pallas.windowed_corr_pyramid, identical math by
        # linearity of pooling/interp in f2 — is what raft/fs uses where
        # the O(H²W²) volume cannot exist at all). Each pyramid level is a
        # direct einsum against pooled f2 (bf16 under the policy: halves
        # volume HBM traffic; lookup einsums still accumulate in f32).
        # quantized matching tier (inference-only, ops.quant): u8 stores
        # the same pyramid affinely mapped per level; i8 additionally runs
        # the correlation dots themselves in int8. Either way the lookup
        # einsums dequantize in-register, so the per-iteration HBM stream
        # is the quantized bytes. quant=None is the bit-exact default.
        qmode = quant_ops.normalize_mode(quant)
        with jax.named_scope("corr"):
            if qmode == "i8":
                pyramid = tuple(quant_ops.correlation_pyramid_int8(
                    fmap1, fmap2, self.corr_levels, clip=quant_clip))
            elif qmode == "u8":
                pyramid = tuple(quant_ops.quantize_pyramid(
                    correlation_pyramid_direct(
                        fmap1, fmap2, self.corr_levels, dtype=dt),
                    qmode, clip=quant_clip))
            else:
                pyramid = tuple(correlation_pyramid_direct(
                    fmap1, fmap2, self.corr_levels, dtype=dt))

        with jax.named_scope("encoders"):
            ctx = cnet(img1, train, frozen_bn)
            h = jnp.tanh(ctx[..., :hdim])
            x = nn.relu(ctx[..., hdim:])
            if hidden_init is not None:
                # continuation rung: re-enter the recurrence with the
                # previous program's final hidden state (the context tanh
                # is DCE'd)
                h = hidden_init.astype(h.dtype)

        b, hc, wc, _ = fmap1.shape
        coords0 = coordinate_grid(b, hc, wc)
        flow = (flow_init.astype(jnp.float32) if flow_init is not None
                else jnp.zeros((b, hc, wc, 2), jnp.float32))  # graftlint: disable=f32-literal -- flow fields are f32 by convention

        # remat the scan body: recompute iteration activations in the
        # backward pass instead of storing 12 iterations' worth in HBM —
        # this is what makes full-resolution training fit on one chip.
        # The correlation lookups are exempted (saved): their einsums are
        # the expensive part of the recompute and their outputs are small
        if self.remat:
            body = nn.remat(
                _RaftStep, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "corr_features", "gru_gate_x"),
            )
        else:
            body = _RaftStep
        step = nn.scan(
            body,
            variable_broadcast="params",
            split_rngs={"params": False, "dropout": True},
            in_axes=nn.broadcast,
            out_axes=0,
            length=iterations,
        )(
            corr_levels=self.corr_levels,
            corr_radius=self.corr_radius,
            recurrent_channels=hdim,
            corr_flow=corr_flow,
            corr_grad_stop=corr_grad_stop,
            mask_costs=tuple(mask_costs),
            corr_reg_type=self.corr_reg_type,
            corr_reg_args=reg_args,
            dtype=dt,
        )

        (h, flow), (flows, hiddens, corr_flows) = step(
            (h, flow), pyramid, x, coords0
        )

        # Up8Network_0 stays the innermost scope of the combine's Mosaic
        # call: the compiler names the call after it
        with jax.named_scope("up8"):
            out = upsample_flows(flows, hiddens, (h, flow),
                                 (img1.shape[1], img1.shape[2]), dtype=dt,
                                 upnet=upnet, final_only=final_only)

        if corr_flow:
            # corr_flows is a tuple over levels of (iterations, B, H, W, 2);
            # return coarse-to-fine level lists, then the final sequence
            per_level = [
                [corr_flows[lvl][i] for i in range(iterations)]
                for lvl in range(self.corr_levels)
            ]
            out = (*reversed(per_level), out)

        if return_state:
            # ladder continuation carry + convergence probe: the coarse
            # final flow/hidden re-seed the next rung; ``delta`` is the
            # per-sample mean-pixel L2 of the last iteration's flow change
            # — the host reads it between programs to decide "converged"
            final = flows[-1]
            if iterations >= 2:
                prev = flows[-2]
            elif flow_init is not None:
                prev = flow_init.astype(jnp.float32)
            else:
                prev = jnp.zeros_like(final)
            diff = (final - prev).astype(jnp.float32)
            delta = jnp.sqrt(jnp.mean(jnp.sum(diff * diff, axis=-1),
                                      axis=(1, 2)))
            return out, {"flow": final, "hidden": h, "delta": delta}

        return out


@register_model
class Raft(Model):
    """Config wrapper for ``raft/baseline`` (reference raft.py:436-559)."""

    type = "raft/baseline"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        param_cfg = cfg["parameters"]
        return cls(
            dropout=float(param_cfg.get("dropout", 0.0)),
            mixed_precision=bool(param_cfg.get("mixed-precision", False)),
            corr_levels=param_cfg.get("corr-levels", 4),
            corr_radius=param_cfg.get("corr-radius", 4),
            corr_channels=param_cfg.get("corr-channels", 256),
            context_channels=param_cfg.get("context-channels", 128),
            recurrent_channels=param_cfg.get("recurrent-channels", 128),
            encoder_norm=param_cfg.get("encoder-norm", "instance"),
            context_norm=param_cfg.get("context-norm", "batch"),
            encoder_type=param_cfg.get("encoder-type", "raft"),
            context_type=param_cfg.get("context-type", "raft"),
            corr_reg_type=param_cfg.get("corr-reg-type", "softargmax"),
            corr_reg_args=param_cfg.get("corr-reg-args", {}),
            arguments=cfg.get("arguments", {}),
            on_stage_args=cfg.get("on-stage", {"freeze_batchnorm": True}),
            on_epoch_args=cfg.get("on-epoch", {}),
        )

    def __init__(self, dropout=0.0, mixed_precision=False, corr_levels=4,
                 corr_radius=4, corr_channels=256, context_channels=128,
                 recurrent_channels=128, encoder_norm="instance",
                 context_norm="batch", encoder_type="raft", context_type="raft",
                 corr_reg_type="softargmax", corr_reg_args={}, arguments={},
                 on_epoch_args={}, on_stage_args={"freeze_batchnorm": True}):
        self.dropout = dropout
        self.mixed_precision = mixed_precision
        self.corr_levels = corr_levels
        self.corr_radius = corr_radius
        self.corr_channels = corr_channels
        self.context_channels = context_channels
        self.recurrent_channels = recurrent_channels
        self.encoder_norm = encoder_norm
        self.context_norm = context_norm
        self.encoder_type = encoder_type
        self.context_type = context_type
        self.corr_reg_type = corr_reg_type
        self.corr_reg_args = corr_reg_args

        super().__init__(
            RaftModule(
                dropout=dropout,
                mixed_precision=mixed_precision,
                corr_levels=corr_levels,
                corr_radius=corr_radius,
                corr_channels=corr_channels,
                context_channels=context_channels,
                recurrent_channels=recurrent_channels,
                encoder_norm=encoder_norm,
                context_norm=context_norm,
                encoder_type=encoder_type,
                context_type=context_type,
                corr_reg_type=corr_reg_type,
                corr_reg_args=corr_reg_args,
            ),
            arguments=arguments,
            on_epoch_arguments=on_epoch_args,
            on_stage_arguments=on_stage_args,
        )

    def get_config(self):
        default_args = {
            "iterations": 12,
            "upnet": True,
            "corr_flow": False,
            "corr_grad_stop": False,
            "mask_costs": [],
        }

        return {
            "type": self.type,
            "parameters": {
                "dropout": self.dropout,
                "mixed-precision": self.mixed_precision,
                "corr-levels": self.corr_levels,
                "corr-radius": self.corr_radius,
                "corr-channels": self.corr_channels,
                "context-channels": self.context_channels,
                "recurrent-channels": self.recurrent_channels,
                "encoder-norm": self.encoder_norm,
                "context-norm": self.context_norm,
                "encoder-type": self.encoder_type,
                "context-type": self.context_type,
                "corr-reg-type": self.corr_reg_type,
                "corr-reg-args": self.corr_reg_args,
            },
            "arguments": default_args | self.arguments,
            "on-stage": {"freeze_batchnorm": True} | self.on_stage_arguments,
            "on-epoch": dict(self.on_epoch_arguments),
        }

    def get_adapter(self) -> ModelAdapter:
        return RaftAdapter(self)


class RaftAdapter(ModelAdapter):
    def wrap_result(self, result, original_shape) -> Result:
        return RaftResult(result)


class RaftResult(Result):
    """Sequence of per-iteration flows; nested per-level lists when the
    corr-flow readouts are enabled (reference raft.py:570-593)."""

    def __init__(self, output):
        super().__init__()
        self.result = output
        self.has_corr_flow = any(isinstance(x, (list, tuple)) for x in output)

    def output(self, batch_index=None):
        if batch_index is None:
            return self.result

        def slice_one(x):
            return x[batch_index : batch_index + 1]

        if not self.has_corr_flow:
            return [slice_one(x) for x in self.result]
        return [[slice_one(x) for x in level] for level in self.result]

    def final(self):
        if not self.has_corr_flow:
            return self.result[-1]
        return self.result[-1][-1]

    def intermediate_flow(self):
        return self.result


@register_loss
class SequenceLoss(Loss):
    """γ-weighted distance over the iteration sequence
    (``raft/sequence``, reference raft.py:596-644)."""

    type = "raft/sequence"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("arguments", {}))

    def __init__(self, arguments={}):
        super().__init__(arguments)

    def get_config(self):
        default_args = {"ord": 1, "gamma": 0.8, "include_invalid": False}
        return {"type": self.type, "arguments": default_args | self.arguments}

    def compute(self, model, result, target, valid, ord=1, gamma=0.8,
                include_invalid=False):
        n = len(result)
        valid_f = valid.astype(jnp.float32)

        loss = 0.0
        for i, flow in enumerate(result):
            weight = gamma ** (n - i - 1)

            if ord == "absmean":
                dist = jnp.abs(flow - target).mean(axis=-1)
            else:
                dist = jnp.linalg.norm(flow - target, ord=ord, axis=-1)

            if include_invalid:
                # invalid pixels enter the mean as zero (original RAFT)
                loss = loss + weight * (dist * valid_f).mean()
            else:
                # mean over valid pixels only
                loss = loss + weight * (dist * valid_f).sum() / jnp.maximum(
                    valid_f.sum(), 1.0
                )

        return loss
