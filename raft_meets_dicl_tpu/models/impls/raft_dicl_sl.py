"""RAFT+DICL single-level hybrid: RAFT skeleton, DICL cost volume.

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/raft_dicl_sl.py:11-110 — the core hybrid of the thesis:
s3 encoders and the RAFT GRU update loop, but the correlation features come
from a learned DICL matching network evaluated on the (2r+1)² displaced
window around the current flow (``make_cmod``), optionally with a
soft-argmax corr-flow readout per iteration.

The iteration loop is an ``nn.scan`` over the shared-module step body
(``raft_dicl_ctf._CtfStep``) with rematerialization like the RAFT
baseline; when batch norm actually trains, the loop unrolls so the
sequential running-stat updates match the reference's.
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import corr as corr_mod
from ..common import encoders
from ..common.grid import coordinate_grid
from ..config import register_model
from ..model import Model, ModelAdapter
from .raft import BasicUpdateBlock, RaftAdapter, upsample_flows
from .raft_dicl_ctf import _CtfStep


class RaftPlusDiclModule(nn.Module):
    """RAFT+DICL single-level network (reference raft_dicl_sl.py:11-110)."""

    dropout: float = 0.0
    mixed_precision: bool = False
    corr_radius: int = 4
    corr_channels: int = 32
    context_channels: int = 128
    recurrent_channels: int = 128
    dap_init: str = "identity"
    encoder_norm: str = "instance"
    context_norm: str = "batch"
    mnet_norm: str = "batch"
    corr_type: str = "dicl"
    corr_args: dict = None
    corr_reg_type: str = "softargmax"
    corr_reg_args: dict = None
    encoder_type: str = "raft"
    context_type: str = "raft"
    remat: bool = True
    unroll: bool = False

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False, iterations=12,
                 dap=True, upnet=True, corr_flow=False, corr_grad_stop=False,
                 flow_init=None, hidden_init=None, return_state=False,
                 final_only=False):
        hdim = self.recurrent_channels
        cdim = self.context_channels
        dt = jnp.bfloat16 if self.mixed_precision else None

        fnet = encoders.make_encoder_s3(
            self.encoder_type, output_dim=self.corr_channels,
            norm_type=self.encoder_norm, dropout=self.dropout, dtype=dt,
        )
        cnet = encoders.make_encoder_s3(
            self.context_type, output_dim=hdim + cdim,
            norm_type=self.context_norm, dropout=self.dropout, dtype=dt,
        )

        fmap1, fmap2 = fnet((img1, img2), train, frozen_bn)
        fmap1 = fmap1.astype(jnp.float32)
        fmap2 = fmap2.astype(jnp.float32)

        ctx = cnet(img1, train, frozen_bn)
        h = jnp.tanh(ctx[..., :hdim])
        x = nn.relu(ctx[..., hdim:])
        if hidden_init is not None:
            h = hidden_init.astype(h.dtype)

        b, hc, wc, _ = fmap1.shape
        coords0 = coordinate_grid(b, hc, wc)
        flow = (flow_init.astype(jnp.float32) if flow_init is not None
                else jnp.zeros((b, hc, wc, 2), jnp.float32))  # graftlint: disable=f32-literal -- flow fields are f32 by convention

        corr_args = dict(self.corr_args or {})
        # matching nets follow the mixed policy (cost comes back f32);
        # "dot" has no net to cast
        if dt is not None and self.corr_type in ("dicl", "dicl-1x1",
                                                 "dicl-emb"):
            corr_args.setdefault("dtype", dt)
        cvol = corr_mod.make_cmod(
            self.corr_type, self.corr_channels, radius=self.corr_radius,
            dap_init=self.dap_init, norm_type=self.mnet_norm,
            **corr_args,
        )
        # always created (and called in the step) so a '+dap' readout's
        # params exist regardless of the static corr_flow switch
        reg = corr_mod.make_flow_regression(
            self.corr_type, self.corr_reg_type, self.corr_radius,
            **(self.corr_reg_args or {}),
        )
        update = BasicUpdateBlock(hdim, dtype=dt)

        # one (remat-wrapped) step body serves both realizations; scan
        # unless batch norm is actually training (the lifted scan
        # broadcasts batch_stats read-only; see raft_dicl_ctf)
        if self.remat:
            body = nn.remat(
                _CtfStep, prevent_cse=False,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "corr_features"),
            )
        else:
            body = _CtfStep
        shared = dict(
            cmod=cvol, reg=reg, update=update, dap=dap,
            corr_grad_stop=corr_grad_stop, train=train, frozen_bn=frozen_bn,
        )

        if self.unroll or (train and not frozen_bn):
            step = body(**shared)
            carry = (h, flow)
            flows, hiddens, readouts = [], [], []
            for _ in range(iterations):
                carry, (fl, hi, ro, _pv) = step(
                    carry, jnp.zeros((0,), dtype=jnp.bfloat16), fmap1, fmap2, x, coords0)
                flows.append(fl)
                hiddens.append(hi)
                readouts.append(ro)
            h, flow = carry

            flows = jnp.stack(flows)
            hiddens = jnp.stack(hiddens)
            readouts = jnp.stack(readouts)
        else:
            step = nn.scan(
                body,
                variable_broadcast=["params", "batch_stats"],
                split_rngs={"params": False, "dropout": True},
                in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast,
                         nn.broadcast),
                out_axes=0,
            )(**shared)

            (h, flow), (flows, hiddens, readouts, _prevs) = step(
                (h, flow), jnp.zeros((iterations, 0), dtype=jnp.bfloat16),
                fmap1, fmap2, x, coords0,
            )

        out = upsample_flows(flows, hiddens, (h, flow),
                             (img1.shape[1], img1.shape[2]), dtype=dt,
                             upnet=upnet, final_only=final_only)

        if corr_flow:
            out = [[readouts[i] for i in range(iterations)], out]

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
class RaftPlusDicl(Model):
    """``raft+dicl/sl`` (reference raft_dicl_sl.py:113-257)."""

    type = "raft+dicl/sl"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        param_cfg = cfg["parameters"]
        return cls(
            dropout=float(param_cfg.get("dropout", 0.0)),
            mixed_precision=bool(param_cfg.get("mixed-precision", False)),
            corr_radius=param_cfg.get("corr-radius", 4),
            corr_channels=param_cfg.get("corr-channels", 32),
            context_channels=param_cfg.get("context-channels", 128),
            recurrent_channels=param_cfg.get("recurrent-channels", 128),
            dap_init=param_cfg.get("dap-init", "identity"),
            encoder_norm=param_cfg.get("encoder-norm", "instance"),
            context_norm=param_cfg.get("context-norm", "batch"),
            mnet_norm=param_cfg.get("mnet-norm", "batch"),
            corr_type=param_cfg.get("corr-type", "dicl"),
            corr_args=param_cfg.get("corr-args", {}),
            corr_reg_type=param_cfg.get("corr-reg-type", "softargmax"),
            corr_reg_args=param_cfg.get("corr-reg-args", {}),
            encoder_type=param_cfg.get("encoder-type", "raft"),
            context_type=param_cfg.get("context-type", "raft"),
            arguments=cfg.get("arguments", {}),
            on_stage_args=cfg.get("on-stage", {"freeze_batchnorm": True}),
            on_epoch_args=cfg.get("on-epoch", {}),
        )

    def __init__(self, dropout=0.0, mixed_precision=False, corr_radius=4,
                 corr_channels=32, context_channels=128, recurrent_channels=128,
                 dap_init="identity", encoder_norm="instance",
                 context_norm="batch", mnet_norm="batch", corr_type="dicl",
                 corr_args={}, corr_reg_type="softargmax", corr_reg_args={},
                 encoder_type="raft", context_type="raft", arguments={},
                 on_epoch_args={}, on_stage_args={"freeze_batchnorm": True}):
        self.dropout = dropout
        self.mixed_precision = mixed_precision
        self.corr_radius = corr_radius
        self.corr_channels = corr_channels
        self.context_channels = context_channels
        self.recurrent_channels = recurrent_channels
        self.dap_init = dap_init
        self.encoder_norm = encoder_norm
        self.context_norm = context_norm
        self.mnet_norm = mnet_norm
        self.corr_type = corr_type
        self.corr_args = dict(corr_args)
        self.corr_reg_type = corr_reg_type
        self.corr_reg_args = dict(corr_reg_args)
        self.encoder_type = encoder_type
        self.context_type = context_type

        super().__init__(
            RaftPlusDiclModule(
                dropout=dropout, mixed_precision=mixed_precision,
                corr_radius=corr_radius, corr_channels=corr_channels,
                context_channels=context_channels,
                recurrent_channels=recurrent_channels, dap_init=dap_init,
                encoder_norm=encoder_norm, context_norm=context_norm,
                mnet_norm=mnet_norm, corr_type=corr_type,
                corr_args=dict(corr_args), corr_reg_type=corr_reg_type,
                corr_reg_args=dict(corr_reg_args), encoder_type=encoder_type,
                context_type=context_type,
            ),
            arguments=arguments,
            on_epoch_arguments=on_epoch_args,
            on_stage_arguments=on_stage_args,
        )

    def get_config(self):
        default_args = {
            "iterations": 12,
            "dap": True,
            "corr_flow": False,
            "corr_grad_stop": False,
            "upnet": True,
        }
        return {
            "type": self.type,
            "parameters": {
                "dropout": self.dropout,
                "mixed-precision": self.mixed_precision,
                "corr-radius": self.corr_radius,
                "corr-channels": self.corr_channels,
                "context-channels": self.context_channels,
                "recurrent-channels": self.recurrent_channels,
                "dap-init": self.dap_init,
                "encoder-norm": self.encoder_norm,
                "context-norm": self.context_norm,
                "mnet-norm": self.mnet_norm,
                "corr-type": self.corr_type,
                "corr-args": self.corr_args,
                "corr-reg-type": self.corr_reg_type,
                "corr-reg-args": self.corr_reg_args,
                "encoder-type": self.encoder_type,
                "context-type": self.context_type,
            },
            "arguments": default_args | self.arguments,
            "on-stage": {"freeze_batchnorm": True} | self.on_stage_arguments,
            "on-epoch": dict(self.on_epoch_arguments),
        }

    def get_adapter(self) -> ModelAdapter:
        return RaftAdapter(self)
