"""Coarse-to-fine RAFT (single-level correlation per pyramid level).

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/raft_sl_ctf_l{2,3,4}.py — one parametric module instead of
three hand-written variants: pyramid encoders, a per-level all-pairs
correlation volume with ``corr_levels=1`` (einsum volume + MXU-friendly
windowed lookup from ops.corr), shared-or-separate update blocks, hidden-
state upsampling, bilinear inter-level flow upsampling, and convex Up8 on
the finest level.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...ops.corr import all_pairs_correlation, lookup_pyramid
from ...ops.upsample import interpolate_bilinear, upsample_flow_2x
from ..common import encoders, hsup
from ..common.adapters.mlseq import MultiLevelSequenceAdapter
from ..common.grid import coordinate_grid
from ..config import register_model
from ..model import Model, ModelAdapter
from .raft import BasicUpdateBlock, Up8Network, make_flow_regression
from .raft_dicl_ctf import _DEFAULT_ITERATIONS, _PYRAMIDS


class _SlCtfStep(nn.Module):
    """One GRU iteration at a fixed pyramid level — the nn.scan body.

    Parameterized submodules (regression, update block) are shared
    instances from the parent scope so parameter paths are identical to
    the unrolled loop and level sharing composes with the scan."""

    reg: nn.Module
    update: nn.Module
    corr_radius: int
    corr_grad_stop: bool

    @nn.compact
    def __call__(self, carry, _, pyramid, x, coords0):
        from jax.ad_checkpoint import checkpoint_name

        h, coords1 = carry
        coords1 = jax.lax.stop_gradient(coords1)
        flow = coords1 - coords0

        corr = lookup_pyramid(pyramid, coords1, self.corr_radius)
        corr = checkpoint_name(corr, "corr_features")

        # always called so a '+dap' readout's params exist regardless of
        # the static switch; XLA removes the unused branch
        readout = flow + self.reg(corr)[0]

        if self.corr_grad_stop:
            corr = jax.lax.stop_gradient(corr)

        h, d = self.update(h, x, corr, flow)
        coords1 = coords1 + d

        return (h, coords1), (coords1 - coords0, h, readout)


class RaftSlCtfModule(nn.Module):
    """Coarse-to-fine RAFT over ``levels`` pyramid levels, single-level
    all-pairs correlation per level."""

    levels: int = 3
    corr_radius: int = 4
    corr_channels: int = 256
    context_channels: int = 128
    recurrent_channels: int = 128
    dropout: float = 0.0
    encoder_norm: str = "instance"
    context_norm: str = "batch"
    encoder_type: str = "raft"
    context_type: str = "raft"
    corr_reg_type: str = "softargmax"
    corr_reg_args: dict = None
    share_rnn: bool = True
    upsample_hidden: str = "none"
    remat: bool = True
    unroll: bool = False

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False,
                 iterations=None, upnet=True, corr_flow=False,
                 corr_grad_stop=False, final_only=False):
        hdim = self.recurrent_channels
        cdim = self.context_channels
        b, h, w = img1.shape[0], img1.shape[1], img1.shape[2]

        iterations = tuple(iterations or _DEFAULT_ITERATIONS[self.levels])
        assert len(iterations) == self.levels

        level_ids = tuple(range(self.levels + 2, 2, -1))  # coarse→fine

        fnet = _PYRAMIDS[self.levels](
            self.encoder_type, output_dim=self.corr_channels,
            norm_type=self.encoder_norm, dropout=self.dropout,
        )
        cnet = _PYRAMIDS[self.levels](
            self.context_type, output_dim=hdim + cdim,
            norm_type=self.context_norm, dropout=self.dropout,
        )

        f1, f2 = fnet((img1, img2), train, frozen_bn)
        ctx = cnet(img1, train, frozen_bn)

        hidden = [jnp.tanh(c[..., :hdim]) for c in ctx]
        context = [nn.relu(c[..., hdim:]) for c in ctx]

        if self.share_rnn:
            shared_update = BasicUpdateBlock(hdim)
            shared_hup = hsup.make_hidden_state_upsampler(
                self.upsample_hidden, hdim)
            updates = {lvl: shared_update for lvl in level_ids}
            hups = {lvl: shared_hup for lvl in level_ids[1:]}
        else:
            updates = {lvl: BasicUpdateBlock(hdim) for lvl in level_ids}
            hups = {
                lvl: hsup.make_hidden_state_upsampler(self.upsample_hidden, hdim)
                for lvl in level_ids[1:]
            }

        regs = {
            lvl: make_flow_regression(
                self.corr_reg_type, 1, self.corr_radius,
                **(self.corr_reg_args or {}),
            )
            for lvl in level_ids
        }
        # remat'd batched convex upsampler, pinned name for checkpoint
        # stability
        upnet8 = nn.remat(Up8Network, prevent_cse=False)(name="Up8Network_0")

        out = []
        flow = None
        h_state = None

        for li, lvl in enumerate(level_ids):
            scale = 2 ** lvl
            lh, lw = h // scale, w // scale
            fine_idx = lvl - 3
            n_iter = iterations[li]

            coords0 = coordinate_grid(b, lh, lw)
            if flow is None:
                coords1 = coords0
            else:
                flow = upsample_flow_2x(flow)
                coords1 = coords0 + flow

            if h_state is None:
                h_state = hidden[fine_idx]
            else:
                h_state = hups[lvl](h_state, hidden[fine_idx])

            x = context[fine_idx]
            finest = li == self.levels - 1

            # single-level all-pairs volume for this pyramid level
            pyramid = (all_pairs_correlation(f1[fine_idx], f2[fine_idx]),)

            # one nn.scan per level with remat — the raft/baseline
            # iteration discipline (models/impls/raft.py:322-352); the
            # body is batch-norm-free, so the scan covers training too
            if self.remat:
                body = nn.remat(
                    _SlCtfStep, prevent_cse=False,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "corr_features"),
                )
            else:
                body = _SlCtfStep
            shared = dict(
                reg=regs[lvl], update=updates[lvl],
                corr_radius=self.corr_radius,
                corr_grad_stop=corr_grad_stop,
            )

            if self.unroll:
                step = body(**shared)
                carry = (h_state, coords1)
                flows, hiddens, readouts = [], [], []
                for _ in range(n_iter):
                    carry, (fl, hi, ro) = step(
                        carry, jnp.zeros((0,)), pyramid, x, coords0)
                    flows.append(fl)
                    hiddens.append(hi)
                    readouts.append(ro)
                h_state, coords1 = carry

                flows = jnp.stack(flows)
                hiddens = jnp.stack(hiddens)
                readouts = jnp.stack(readouts)
            else:
                step = nn.scan(
                    body,
                    variable_broadcast="params",
                    split_rngs={"params": False, "dropout": True},
                    in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast),
                    out_axes=0,
                )(**shared)

                (h_state, coords1), (flows, hiddens, readouts) = step(
                    (h_state, coords1), jnp.zeros((n_iter, 0)),
                    pyramid, x, coords0,
                )

            flow = flows[-1]

            if finest:
                # convex 8x upsampling, batched over all iterations at once
                flows_flat = flows.reshape(n_iter * b, lh, lw, 2)
                hidden_flat = hiddens.reshape(n_iter * b, lh, lw, hdim)
                ups = upnet8(hidden_flat, flows_flat)
                if not upnet:
                    ups = 8.0 * interpolate_bilinear(flows_flat, (h, w))
                ups = ups.reshape(n_iter, b, h, w, 2)
                out_lvl = [ups[i] for i in range(n_iter)]
            else:
                out_lvl = [flows[i] for i in range(n_iter)]

            if corr_flow:
                out.append([readouts[i] for i in range(n_iter)])
            out.append(out_lvl)

        return out


class _SlCtfModel(Model):
    """Shared config wrapper for the three registered level counts."""

    levels = None

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        p = cfg["parameters"]
        return cls(
            dropout=float(p.get("dropout", 0.0)),
            corr_radius=p.get("corr-radius", 4),
            corr_channels=p.get("corr-channels", 256),
            context_channels=p.get("context-channels", 128),
            recurrent_channels=p.get("recurrent-channels", 128),
            encoder_norm=p.get("encoder-norm", "instance"),
            context_norm=p.get("context-norm", "batch"),
            encoder_type=p.get("encoder-type", "raft"),
            context_type=p.get("context-type", "raft"),
            share_rnn=p.get("share-rnn", True),
            corr_reg_type=p.get("corr-reg-type", "softargmax"),
            corr_reg_args=p.get("corr-reg-args", {}),
            upsample_hidden=p.get("upsample-hidden", "none"),
            arguments=cfg.get("arguments", {}),
            on_stage_args=cfg.get("on-stage", {"freeze_batchnorm": True}),
            on_epoch_args=cfg.get("on-epoch", {}),
        )

    def __init__(self, dropout=0.0, corr_radius=4, corr_channels=256,
                 context_channels=128, recurrent_channels=128,
                 encoder_norm="instance", context_norm="batch",
                 encoder_type="raft", context_type="raft", share_rnn=True,
                 corr_reg_type="softargmax", corr_reg_args={},
                 upsample_hidden="none", arguments={}, on_epoch_args={},
                 on_stage_args={"freeze_batchnorm": True}):
        self.dropout = dropout
        self.corr_radius = corr_radius
        self.corr_channels = corr_channels
        self.context_channels = context_channels
        self.recurrent_channels = recurrent_channels
        self.encoder_norm = encoder_norm
        self.context_norm = context_norm
        self.encoder_type = encoder_type
        self.context_type = context_type
        self.share_rnn = share_rnn
        self.corr_reg_type = corr_reg_type
        self.corr_reg_args = dict(corr_reg_args)
        self.upsample_hidden = upsample_hidden

        super().__init__(
            RaftSlCtfModule(
                levels=self.levels, corr_radius=corr_radius,
                corr_channels=corr_channels,
                context_channels=context_channels,
                recurrent_channels=recurrent_channels, dropout=dropout,
                encoder_norm=encoder_norm, context_norm=context_norm,
                encoder_type=encoder_type, context_type=context_type,
                corr_reg_type=corr_reg_type,
                corr_reg_args=dict(corr_reg_args), share_rnn=share_rnn,
                upsample_hidden=upsample_hidden,
            ),
            arguments=arguments,
            on_epoch_arguments=on_epoch_args,
            on_stage_arguments=on_stage_args,
        )

    def get_config(self):
        default_args = {
            "iterations": _DEFAULT_ITERATIONS[self.levels],
            "upnet": True,
            "corr_flow": False,
            "corr_grad_stop": False,
        }
        return {
            "type": self.type,
            "parameters": {
                "dropout": self.dropout,
                "corr-radius": self.corr_radius,
                "corr-channels": self.corr_channels,
                "context-channels": self.context_channels,
                "recurrent-channels": self.recurrent_channels,
                "encoder-norm": self.encoder_norm,
                "context-norm": self.context_norm,
                "encoder-type": self.encoder_type,
                "context-type": self.context_type,
                "share-rnn": self.share_rnn,
                "corr-reg-type": self.corr_reg_type,
                "corr-reg-args": self.corr_reg_args,
                "upsample-hidden": self.upsample_hidden,
            },
            "arguments": default_args | self.arguments,
            "on-stage": {"freeze_batchnorm": True} | self.on_stage_arguments,
            "on-epoch": dict(self.on_epoch_arguments),
        }

    def get_adapter(self) -> ModelAdapter:
        return MultiLevelSequenceAdapter(self)


@register_model
class RaftSlCtfL2(_SlCtfModel):
    """``raft/sl-ctf-l2`` (reference raft_sl_ctf_l2.py)."""

    type = "raft/sl-ctf-l2"
    levels = 2


@register_model
class RaftSlCtfL3(_SlCtfModel):
    """``raft/sl-ctf-l3`` (reference raft_sl_ctf_l3.py:11-210)."""

    type = "raft/sl-ctf-l3"
    levels = 3


@register_model
class RaftSlCtfL4(_SlCtfModel):
    """``raft/sl-ctf-l4`` (reference raft_sl_ctf_l4.py)."""

    type = "raft/sl-ctf-l4"
    levels = 4
