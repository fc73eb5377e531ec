"""RAFT "from scratch" variant: no materialized all-pairs volume.

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/raft_fs.py:13-268: the second frame's features are
avg-pooled into a pyramid and the correlation window is computed
*on the fly* against each level via the framework's windowed-correlation
op — O(B·H·W·K²·C) per lookup instead of the O(B·H²W²) volume. This is the
framework's high-resolution memory story (SURVEY §5.7): the model of
choice when the all-pairs volume does not fit HBM.

The GRU loop is an ``nn.scan`` with rematerialization like the baseline.
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ... import telemetry
from ...ops import quant as quant_ops
from ...ops.corr import correlation_volume, lookup_pyramid_levels
from ...ops.pallas import windowed_corr_pyramid
from ...ops.pool import avg_pool2d
from ..common.encoders.raft import FeatureEncoderS3
from ..common.grid import coordinate_grid
from ..config import register_model
from ..model import Model, ModelAdapter
from .raft import BasicUpdateBlock, RaftAdapter, upsample_flows


def volume_level_split(coarse_shape, corr_levels, itemsize, budget_gib=None):
    """Greedy per-level dispatch decision: how many fine levels stay on
    the windowed kernel.

    Walks the pyramid from the coarsest level (each volume is 4x the
    next coarser one) and moves levels onto materialized volumes while
    twice their running total — the 2x charges the backward's
    volume-gradient accumulation — fits the ``RMD_FS_VOLUME_GIB`` budget
    (default 4 GiB; 0 forces the windowed path everywhere). Returns
    ``n_windowed``: levels ``[0, n_windowed)`` are computed on the fly.

    The budget is PER CHIP: under SPMD the trace sees the global batch
    while each chip holds only its ``1/data_axis_size`` slice of the
    batch-sharded volume, so the estimate divides by the data-parallel
    degree published by the step builders (parallel.mesh).
    """
    from ...parallel.mesh import data_axis_size
    from ...utils import env

    if budget_gib is None:
        budget_gib = env.get_float("RMD_FS_VOLUME_GIB")
    budget = budget_gib * 2 ** 30

    b0, hc0, wc0 = coarse_shape
    n_chips = data_axis_size()
    vol_bytes = [
        b0 * hc0 * wc0 * (hc0 // 2 ** l) * (wc0 // 2 ** l) * itemsize
        // n_chips
        for l in range(corr_levels)
    ]
    n_windowed = corr_levels
    total = 0
    for l in reversed(range(corr_levels)):
        if 2 * (total + vol_bytes[l]) > budget:
            break
        total += vol_bytes[l]
        n_windowed = l
    return n_windowed


def _keep_convs_and_stats(prim, *_, **__):
    """Remat policy of the two encoders: of an encoder's forward pass the
    backward keeps the convolutions' outputs (compact, in the compute
    dtype: they are the instance norm's ``x``) and the norms' statistics
    (``reduce_sum``: the two float32 sums a channel that
    ``norm.instance_norm`` and a live batch norm take; mean and 1/sigma
    follow from them), and recomputes the float32 chains between them.

    Left to itself the backward pass keeps, beside each convolution's
    output, what every layer between two convolutions wrote (the ReLU's
    mask and result, the residual sums), for all three encoder passes and
    across the whole recurrence: at the resolutions this model exists for
    they, not the scan, decide the step's peak memory, and reading them
    back costs more than computing them again from the convolution's
    output. At b1 1088x1920 under the bf16 policy, one v5e (PERF.md
    section 6): 15.28 GiB and 996 ms a step with nothing recomputed (PR
    35, when the instance norm was flax's ``GroupNorm`` and its float32
    intermediates were kept too), 9.40 GiB and 1078 ms with the encoders
    recomputed whole, 8.72 GiB and 827 ms with this policy (the encoders'
    backward pass 145 ms where it was 333); since PR 36's kernels 638 ms,
    and 585 ms at 8.96 GiB since PR 38 runs the feature encoder a frame a
    call (``encoder_ms`` 246.9 -> 194.5).
    """
    return prim.name in ("conv_general_dilated", "reduce_sum")


class _FsStep(nn.Module):
    """One GRU iteration — nn.scan body; carry is (hidden, flow).

    The carry is the flow (reconstructing ``coords1 = coords0 + flow``
    every iteration) so a ladder-rung boundary reproduces the monolithic
    program bit-exactly — see ``raft._RaftStep``.

    ``n_windowed`` is the per-level dispatch split: pyramid levels
    ``[0, n_windowed)`` are computed on the fly by the windowed kernel
    (their volumes don't fit the budget), levels ``[n_windowed, L)`` are
    looked up from materialized volumes. The broadcast ``pyramid`` input
    carries pooled f2 maps for the windowed prefix followed by volumes
    for the coarse suffix.
    """

    corr_levels: int
    corr_radius: int
    recurrent_channels: int
    mask_costs: Tuple[int, ...]
    n_windowed: int = 0
    dtype: Any = None

    def _lookup(self, fmap1, pyramid, coords1):
        n_win = self.n_windowed
        if n_win == 0:
            # small-enough shapes: ``pyramid`` is the materialized volume
            # pyramid, amortized across iterations — same math (pooling
            # commutes with the dot product), and cheaper than the
            # per-step windowed computation wherever it fits
            corr = lookup_pyramid_levels(pyramid, coords1,
                                         self.corr_radius,
                                         mask_costs=self.mask_costs)
        elif n_win == self.corr_levels:
            # on-the-fly windowed dot-product against the pooled feature
            # pyramid — the fused kernel (ops/pallas.py) on TPU,
            # per-level windowed correlation off it; O(B·H·W·C) memory at
            # any resolution. The reference lookup skips the sqrt(C)
            # normalization (raft_fs.py:76) in both realizations.
            corr = windowed_corr_pyramid(
                fmap1, pyramid, coords1, self.corr_radius,
                mask_costs=self.mask_costs, normalize=False,
            )
        else:
            # hybrid: the fine levels' volumes don't fit but the coarse
            # suffix's do (each level is 4x smaller than the last) —
            # kernel for the prefix, volume lookups for the suffix. The
            # mixed list goes straight to the motion encoder's
            # _WindowConv1x1: the kernel's flat (level, dx, dy) chunk
            # contracts as-is and the volume levels contract in their
            # native (dy, dx) window form — no concat, no transposes.
            corr_win = windowed_corr_pyramid(
                fmap1, pyramid[:n_win], coords1, self.corr_radius,
                mask_costs=self.mask_costs, normalize=False,
            )
            corr = [corr_win] + lookup_pyramid_levels(
                pyramid[n_win:], coords1, self.corr_radius,
                mask_costs=self.mask_costs, first_level=n_win,
            )
        return corr

    @nn.compact
    def __call__(self, carry, fmap1, pyramid, x, coords0):
        h, flow = carry
        flow = jax.lax.stop_gradient(flow)
        coords1 = coords0 + flow

        from jax.ad_checkpoint import checkpoint_name

        with jax.named_scope("lookup"):
            corr = self._lookup(fmap1, pyramid, coords1)

            # named so the remat policy saves the correlation output:
            # without it the windowed Pallas kernel's forward runs a second
            # time in the backward pass, and the volume-lookup einsums
            # recompute likewise
            if isinstance(corr, list):
                corr = [checkpoint_name(lvl, "corr_features")
                        for lvl in corr]
            else:
                corr = checkpoint_name(corr, "corr_features")

        with jax.named_scope("update"):
            h, d = BasicUpdateBlock(self.recurrent_channels,
                                    dtype=self.dtype)(h, x, corr, flow)

        coords1 = coords1 + d
        flow = coords1 - coords0

        return (h, flow), (flow, h)


class RaftFsModule(nn.Module):
    """RAFT-fs network (reference RaftModule, raft_fs.py:92-170)."""

    dropout: float = 0.0
    mixed_precision: bool = False
    corr_levels: int = 4
    corr_radius: int = 4
    corr_channels: int = 256
    context_channels: int = 128
    recurrent_channels: int = 128
    encoder_norm: str = "instance"
    context_norm: str = "batch"
    remat: bool = True

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False,
                 iterations=12, flow_init=None, hidden_init=None, upnet=True,
                 mask_costs=(), return_state=False, quant=None,
                 quant_clip=1.0, final_only=False):
        hdim = self.recurrent_channels
        cdim = self.context_channels
        dt = jnp.bfloat16 if self.mixed_precision else None

        # both encoders are rematerialised, keeping their convolutions'
        # outputs and their norms' sums: see _keep_convs_and_stats. Module
        # names and parameter paths are the plain encoders' (checkpoints
        # load unchanged)
        encoder = nn.remat(FeatureEncoderS3, static_argnums=(2, 3),
                           policy=_keep_convs_and_stats)
        fnet = encoder(
            output_dim=self.corr_channels, norm_type=self.encoder_norm,
            dropout=self.dropout, dtype=dt, name="FeatureEncoderS3_0",
        )
        cnet = encoder(
            output_dim=hdim + cdim, norm_type=self.context_norm,
            dropout=self.dropout, dtype=dt, name="FeatureEncoderS3_1",
        )

        with jax.named_scope("encoders"):
            if img1.shape[0] == 1 and self.encoder_norm == "instance":
                # one frame a call: the TPU compiler runs the convolutions
                # of so small a batch in their space-to-batch form, which
                # carries the statistics of a batch of one natively and
                # those of a pair only by broadcasting each mean and 1/sigma
                # to full size in float32 and relaying it (encoders/raft.py).
                # Forward and backward at 1088x1920 on one v5e: 181.6 ms
                # for the pair, 65.3 ms a frame (PERF.md section 6, PR 38)
                fmap1 = fnet(img1, train, frozen_bn)
                fmap2 = fnet(img2, train, frozen_bn)
            else:
                fmap1, fmap2 = fnet((img1, img2), train, frozen_bn)
            ctx = cnet(img1, train, frozen_bn)
        if dt is None:
            fmap1 = fmap1.astype(jnp.float32)
            fmap2 = fmap2.astype(jnp.float32)
        # under the bf16 policy the feature maps stay bf16: halves the
        # windowed-correlation kernel's VMEM blocks (the accumulation is
        # f32 inside the kernel)

        # strategy dispatch: the windowed computation exists so the
        # O(H²W²) volume never has to — but where a level's volume DOES
        # fit, materializing it once and looking it up per iteration is
        # faster (the windowed kernel walks its positions one by one).
        # Identical math either way (pooling/bilinear commute with the
        # dot product). The decision is PER LEVEL, greedy from the
        # coarsest: each level's volume is 4x smaller than the previous,
        # so at 1088x1920 under the bf16 policy the coarse suffix
        # (levels 1-3, 0.70 GB) fits while level 0 (2.13 GB) cannot —
        # moving 3 of 4 levels off the serialized kernel. The estimate
        # charges 2x for the backward's volume-gradient accumulation and
        # is per chip (the global-batch shapes seen at trace time are
        # divided by the SPMD data-parallel degree). RMD_FS_VOLUME_GIB
        # tunes the budget (0 forces the windowed path everywhere).
        b0, hc0, wc0, _ = fmap1.shape
        itemsize = 2 if dt is not None else 4
        n_windowed = volume_level_split(
            (b0, hc0, wc0), self.corr_levels, itemsize)
        telemetry.note_trace("wcp_levels_windowed", n_windowed, scale=False)

        with jax.named_scope("pyramid"):
            # avg-pooled second-frame feature pyramid (raft_fs.py:26-31);
            # the coarse suffix becomes materialized volumes against the
            # same pooled maps (so both dispatch paths correlate against
            # bit-identical f2 levels)
            f2_pyramid = [fmap2]
            for _ in range(1, self.corr_levels):
                f2_pyramid.append(avg_pool2d(f2_pyramid[-1], 2))
            # quantized matching tier (ops.quant): the materialized coarse
            # suffix is stored at the quantized width and dequantized
            # in-register by the lookup einsums. The windowed prefix never
            # materializes a volume, so there is nothing to quantize
            # there — both modes reduce to storage quantization here (the
            # int8 feature-dot construction is a RaftModule path).
            qmode = quant_ops.normalize_mode(quant)
            volumes = [
                correlation_volume(fmap1, f2, dtype=dt, normalize=False)
                for f2 in f2_pyramid[n_windowed:]
            ]
            if qmode is not None:
                volumes = quant_ops.quantize_pyramid(volumes, qmode,
                                                     clip=quant_clip)
        telemetry.note_trace(
            "corr_volume_bytes",
            sum(v.size * v.dtype.itemsize
                for v in jax.tree_util.tree_leaves(volumes)))
        pyramid = f2_pyramid[:n_windowed] + volumes

        with jax.named_scope("encoders"):
            h = jnp.tanh(ctx[..., :hdim])
            x = nn.relu(ctx[..., hdim:])
            if hidden_init is not None:
                h = hidden_init.astype(h.dtype)

        b, hc, wc, _ = fmap1.shape
        coords0 = coordinate_grid(b, hc, wc)
        flow = (flow_init.astype(jnp.float32) if flow_init is not None
                else jnp.zeros((b, hc, wc, 2), jnp.float32))  # graftlint: disable=f32-literal -- flow fields are f32 by convention

        # same remat policy as raft/baseline: save the correlation lookup
        # outputs (recomputing the windowed kernel / lookup einsums in the
        # backward costs far more than the per-iteration (B, H/8, W/8,
        # L·(2r+1)²) buffers) and the GRU x-half gate convs
        if self.remat:
            body = nn.remat(
                _FsStep, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "corr_features", "gru_gate_x"),
            )
        else:
            body = _FsStep
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
            mask_costs=tuple(mask_costs),
            n_windowed=n_windowed,
            dtype=dt,
        )

        with telemetry.trace_site("iteration", iterations):
            (h, flow), (flows, hiddens) = step((h, flow), fmap1,
                                               tuple(pyramid), x, coords0)

        # convex 8x upsampling hoisted out of the remat'd scan, exactly
        # like raft/baseline (raft.upsample_flows). The explicit module
        # name keeps a stable param path going forward; checkpoints from
        # before the hoist (params under the scan-body subtree) are
        # migrated at load time by
        # strategy.checkpoint._remap_legacy_model_state.
        with jax.named_scope("up8"):
            out = upsample_flows(flows, hiddens, (h, flow),
                                 (img1.shape[1], img1.shape[2]), dtype=dt,
                                 upnet=upnet, final_only=final_only)

        if return_state:
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
class RaftFs(Model):
    """``raft/fs`` (reference raft_fs.py:173-268)."""

    type = "raft/fs"
    # the program's trace-time notes (wcp_fused_calls, wcp_fallback_calls,
    # wcp_levels_windowed, corr_volume_bytes) are stored with its
    # executable: a revision in the program keys keeps a run from loading
    # an executable stored before the notes existed (ROADMAP D12). 2: the
    # windowed-correlation kernels' block form (PR 36), for the same
    # reason: the store is keyed by the configuration, not the program.
    # 3: the phase scopes of PR 37 (``compile/owners.py``): an executable
    # stored before them says ``other`` of most of its instructions
    notes_revision = 3

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        p = cfg["parameters"]
        return cls(
            dropout=float(p.get("dropout", 0.0)),
            mixed_precision=bool(p.get("mixed-precision", False)),
            corr_levels=p.get("corr-levels", 4),
            corr_radius=p.get("corr-radius", 4),
            corr_channels=p.get("corr-channels", 256),
            context_channels=p.get("context-channels", 128),
            recurrent_channels=p.get("recurrent-channels", 128),
            encoder_norm=p.get("encoder-norm", "instance"),
            context_norm=p.get("context-norm", "batch"),
            arguments=cfg.get("arguments", {}),
            on_stage_args=cfg.get("on-stage", {"freeze_batchnorm": True}),
            on_epoch_args=cfg.get("on-epoch", {}),
        )

    def __init__(self, dropout=0.0, mixed_precision=False, corr_levels=4,
                 corr_radius=4, corr_channels=256, context_channels=128,
                 recurrent_channels=128, encoder_norm="instance",
                 context_norm="batch", arguments={}, on_epoch_args={},
                 on_stage_args={"freeze_batchnorm": True}):
        self.dropout = dropout
        self.mixed_precision = mixed_precision
        self.corr_levels = corr_levels
        self.corr_radius = corr_radius
        self.corr_channels = corr_channels
        self.context_channels = context_channels
        self.recurrent_channels = recurrent_channels
        self.encoder_norm = encoder_norm
        self.context_norm = context_norm

        super().__init__(
            RaftFsModule(
                dropout=dropout, mixed_precision=mixed_precision,
                corr_levels=corr_levels, corr_radius=corr_radius,
                corr_channels=corr_channels,
                context_channels=context_channels,
                recurrent_channels=recurrent_channels,
                encoder_norm=encoder_norm, context_norm=context_norm,
            ),
            arguments=arguments,
            on_epoch_arguments=on_epoch_args,
            on_stage_arguments=on_stage_args,
        )

    def get_config(self):
        default_args = {"iterations": 12, "upnet": True, "mask_costs": []}
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
            },
            "arguments": default_args | self.arguments,
            "on-stage": {"freeze_batchnorm": True} | self.on_stage_arguments,
            "on-epoch": dict(self.on_epoch_arguments),
        }

    def get_adapter(self) -> ModelAdapter:
        return RaftAdapter(self)
