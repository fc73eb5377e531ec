"""RAFT+DICL coarse-to-fine hybrids — the thesis flagship family.

TPU-native (Flax, NHWC) implementation of the capabilities of reference
src/models/impls/raft_dicl_ctf_l{2,3,4}.py — three hand-written variants of
one structure, realized here as a single parametric module:

- pyramid encoders (p34/p35/p36 for 2/3/4 levels),
- per-level DICL correlation modules and RAFT GRU update blocks, either
  level-shared or separate (``share_dicl`` / ``share_rnn``),
- hidden-state upsampling between levels (none/bilinear/crossattn),
- bilinear inter-level flow upsampling, convex Up8 on the finest level,
- gradient stopping between levels and iterations,
- optional per-iteration ``corr_flow`` readouts and ``prev_flow``
  intermediates (consumed by the restricted multi-level sequence loss,
  reference raft_dicl_ctf_l3.py:401-473).

Output protocol (coarse-to-fine, per reference :247-258): a list of
per-level iteration lists for the MultiLevelSequenceAdapter; with
``corr_flow`` each level contributes its readout list before its flow list;
with ``prev_flow`` entries become (prev, flow) pairs.
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ... import telemetry
from ...ops.upsample import interpolate_bilinear, upsample_flow_2x
from ..common import corr as corr_mod
from ..common import encoders, hsup
from ..common.adapters.mlseq import MultiLevelSequenceAdapter
from ..common.grid import coordinate_grid
from ..common.loss.mlseq import upsample_flow_to
from ..config import register_loss, register_model
from ..model import Loss, Model, ModelAdapter
from .raft import BasicUpdateBlock, Up8Network

_PYRAMIDS = {
    2: encoders.make_encoder_p34,
    3: encoders.make_encoder_p35,
    4: encoders.make_encoder_p36,
}

_DEFAULT_ITERATIONS = {2: (4, 3), 3: (4, 3, 3), 4: (3, 4, 4, 3)}


class _CtfStep(nn.Module):
    """One RAFT+DICL iteration at a fixed pyramid level — the nn.scan body.

    All parameterized submodules are passed in as shared instances created
    in the parent scope, so parameter paths (and with them checkpoints and
    the torch-importer rules) are identical to the unrolled form, and level
    sharing (``share_dicl`` / ``share_rnn``) composes freely with the scan:
    the scan only owns the loop, never the weights.
    """

    cmod: nn.Module
    reg: nn.Module
    update: nn.Module
    dap: bool
    corr_grad_stop: bool
    train: bool
    frozen_bn: bool

    @nn.compact
    def __call__(self, carry, _, f1, f2, x, coords0):
        from jax.ad_checkpoint import checkpoint_name

        # flow (not coords1) carry: program boundaries replay the same
        # ``coords0 + flow`` reconstruction, so ladder rungs chain
        # bit-exactly (see raft._RaftStep)
        h, prev = carry
        prev = jax.lax.stop_gradient(prev)
        coords1 = coords0 + prev

        # ``lookup`` names the phase for the compiled text's readers
        # (compile/owners.py); inside it ``matching/sampler`` stays the
        # sampler call's innermost scope
        with jax.named_scope("lookup"):
            corr = self.cmod(f1, f2, coords1, dap=self.dap,
                             train=self.train, frozen_bn=self.frozen_bn)
            # saved under the remat policy: recomputing the MatchingNet
            # over all (2r+1)² displacements in the backward pass costs
            # far more than the (B, H, W, (2r+1)²) cost volume it would
            # save
            corr = checkpoint_name(corr, "corr_features")

            # readout is always computed so the regression params exist
            # regardless of the static corr_flow switch; XLA removes it
            # when the output is unused
            readout = prev + self.reg(corr)

            if self.corr_grad_stop:
                corr = jax.lax.stop_gradient(corr)

        with jax.named_scope("update"):
            h, d = self.update(h, x, corr, prev)
        coords1 = coords1 + d
        flow = coords1 - coords0

        return (h, flow), (flow, h, readout, prev)


class RaftPlusDiclCtfModule(nn.Module):
    """Coarse-to-fine RAFT+DICL network over ``levels`` pyramid levels
    (finest always 1/8; coarsest 1/(8·2^(levels-1)))."""

    levels: int = 3
    corr_radius: int = 4
    corr_channels: int = 32
    context_channels: int = 128
    recurrent_channels: int = 128
    dap_init: str = "identity"
    encoder_norm: str = "instance"
    context_norm: str = "batch"
    mnet_norm: str = "batch"
    encoder_type: str = "raft"
    context_type: str = "raft"
    corr_type: str = "dicl"
    corr_args: dict = None
    corr_reg_type: str = "softargmax"
    corr_reg_args: dict = None
    share_dicl: bool = False
    share_rnn: bool = True
    upsample_hidden: str = "none"
    mixed_precision: bool = False
    remat: bool = True
    unroll: bool = False

    def _make_cmod(self, dtype=None):
        kwargs = dict(self.corr_args or {})
        # the matching-net cmods all take a compute dtype now; "dot" has
        # no net to cast (its einsum accumulates f32 regardless)
        if dtype is not None and self.corr_type in ("dicl", "dicl-1x1",
                                                    "dicl-emb"):
            kwargs["dtype"] = dtype
        return corr_mod.make_cmod(
            self.corr_type, self.corr_channels, radius=self.corr_radius,
            dap_init=self.dap_init, norm_type=self.mnet_norm,
            **kwargs,
        )

    def _make_reg(self):
        return corr_mod.make_flow_regression(
            self.corr_type, self.corr_reg_type, self.corr_radius,
            **(self.corr_reg_args or {}),
        )

    @nn.compact
    def __call__(self, img1, img2, train=False, frozen_bn=False,
                 iterations=None, dap=True, upnet=True, corr_flow=False,
                 prev_flow=False, corr_grad_stop=False, flow_init=None,
                 hidden_init=None, return_state=False, final_only=False):
        hdim = self.recurrent_channels
        cdim = self.context_channels
        b, h, w = img1.shape[0], img1.shape[1], img1.shape[2]

        # bf16 compute policy (TPU-native analog of the reference's raft
        # autocast, extended to the ctf family): encoders, matching nets,
        # and update blocks run bf16; cost volumes, coords/flow arithmetic,
        # and the Up8 flow window stay float32
        dt = jnp.bfloat16 if self.mixed_precision else None
        if dt is not None and (self.encoder_type != "raft"
                               or self.context_type != "raft"
                               or self.corr_type != "dicl"):
            # silently running parts in f32 would fake the policy
            raise ValueError(
                "mixed-precision is only plumbed through the raft encoders "
                "and the dicl correlation module; got encoder-type="
                f"'{self.encoder_type}', context-type='{self.context_type}',"
                f" corr-type='{self.corr_type}'"
            )
        enc_kw = {"dtype": dt} if dt is not None else {}
        ctx_kw = {"dtype": dt} if dt is not None else {}

        # ladder continuation: with ``hidden_init`` only the finest (1/8)
        # level runs, re-entering its recurrence from the previous rung's
        # ``(flow, hidden)``; an int ``iterations`` means the finest-level
        # count (coarse levels keep their defaults — a continuation never
        # re-runs them, so chained rungs match one longer finest loop)
        cont = hidden_init is not None
        if flow_init is not None and not cont:
            raise ValueError(
                "ctf models take flow_init only together with hidden_init "
                "(a continuation rung at the finest level); the coarse "
                "pyramid has no seeding protocol")
        if isinstance(iterations, int):
            its = list(_DEFAULT_ITERATIONS[self.levels])
            its[-1] = iterations
            iterations = tuple(its)
        else:
            iterations = tuple(iterations or _DEFAULT_ITERATIONS[self.levels])
        assert len(iterations) == self.levels

        # level ids coarse→fine, e.g. (5, 4, 3) for 3 levels; level L = 1/2^L
        level_ids = tuple(range(self.levels + 2, 2, -1))

        fnet = _PYRAMIDS[self.levels](
            self.encoder_type, output_dim=self.corr_channels,
            norm_type=self.encoder_norm, dropout=0, **enc_kw,
        )
        cnet = _PYRAMIDS[self.levels](
            self.context_type, output_dim=hdim + cdim,
            norm_type=self.context_norm, dropout=0, **ctx_kw,
        )

        with jax.named_scope("encoders"):
            f1, f2 = fnet((img1, img2), train, frozen_bn)  # finest-first
            ctx = cnet(img1, train, frozen_bn)

            hidden = [jnp.tanh(c[..., :hdim]) for c in ctx]
            context = [nn.relu(c[..., hdim:]) for c in ctx]

        # shared-or-per-level submodules (reference :40-78); flax modules
        # created once are parameter-shared on repeated calls
        if self.share_dicl:
            shared_cmod, shared_reg = self._make_cmod(dt), self._make_reg()
            cmods = {lvl: shared_cmod for lvl in level_ids}
            regs = {lvl: shared_reg for lvl in level_ids}
        else:
            cmods = {lvl: self._make_cmod(dt) for lvl in level_ids}
            regs = {lvl: self._make_reg() for lvl in level_ids}

        if self.share_rnn:
            shared_update = BasicUpdateBlock(hdim, dtype=dt)
            shared_hup = hsup.make_hidden_state_upsampler(
                self.upsample_hidden, hdim)
            updates = {lvl: shared_update for lvl in level_ids}
            hups = {lvl: shared_hup for lvl in level_ids[1:]}
        else:
            updates = {lvl: BasicUpdateBlock(hdim, dtype=dt) for lvl in level_ids}
            hups = {
                lvl: hsup.make_hidden_state_upsampler(self.upsample_hidden, hdim)
                for lvl in level_ids[1:]
            }

        # remat'd batched convex upsampler, pinned name for checkpoint
        # stability (the wrapper would otherwise prefix the module path)
        upnet8 = nn.remat(Up8Network, prevent_cse=False)(
            dtype=dt, name="Up8Network_0")

        # the lifted scan broadcasts batch_stats read-only; when batch norm
        # actually trains (rare — stages default to freeze_batchnorm) the
        # sequential running-stat updates need the python-unrolled loop
        unrolled = self.unroll or (train and not frozen_bn)

        out = []
        flow = None
        h_state = None

        for li, lvl in enumerate(level_ids):
            finest = li == self.levels - 1
            if cont and not finest:
                continue

            scale = 2 ** lvl
            lh, lw = h // scale, w // scale
            fine_idx = lvl - 3  # index into finest-first feature tuples
            n_iter = iterations[li]

            coords0 = coordinate_grid(b, lh, lw)
            if cont:
                flow = (flow_init.astype(jnp.float32)
                        if flow_init is not None
                        else jnp.zeros((b, lh, lw, 2), jnp.float32))  # graftlint: disable=f32-literal -- flow fields are f32 by convention
                h_state = hidden_init.astype(hidden[fine_idx].dtype)
            else:
                # between levels: the bilinear 2x of flow and hidden state
                with jax.named_scope("up8"):
                    if flow is None:
                        flow = jnp.zeros((b, lh, lw, 2), jnp.float32)  # graftlint: disable=f32-literal -- flow fields are f32 by convention
                    else:
                        flow = upsample_flow_2x(flow)

                    if h_state is None:
                        h_state = hidden[fine_idx]
                    else:
                        h_state = hups[lvl](h_state, hidden[fine_idx])
            if finest:
                entry_flow = flow

            x = context[fine_idx]

            # one (remat-wrapped) step body serves both realizations:
            # iterations share spatial shapes within a level, and remat
            # recomputes iteration activations in the backward pass
            # instead of storing every MatchingNet intermediate (the
            # raft/baseline scan discipline, models/impls/raft.py:322-352)
            if self.remat:
                body = nn.remat(
                    _CtfStep, prevent_cse=False,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        "corr_features"),
                )
            else:
                body = _CtfStep
            shared = dict(
                cmod=cmods[lvl], reg=regs[lvl], update=updates[lvl],
                dap=dap, corr_grad_stop=corr_grad_stop,
                train=train, frozen_bn=frozen_bn,
            )

            # one scope and one trace site a level: the device trace names
            # the level's operations, and the counts the matching notes
            # while it traces (sampler path, matching bytes) stand for the
            # level's ``n_iter`` iterations, once, however often the
            # tracer visits the scan's body
            with jax.named_scope(f"level{lvl}"), \
                    telemetry.trace_site(f"level{lvl}", n_iter):
                if unrolled:
                    # python loop over the same step module — sequential
                    # batch-stat updates, identical parameter paths
                    step = body(**shared)
                    carry = (h_state, flow)
                    flows, hiddens, readouts, prevs = [], [], [], []
                    for _ in range(n_iter):
                        carry, (fl, hi, ro, pv) = step(
                            carry, jnp.zeros((0,), dtype=jnp.bfloat16),
                            f1[fine_idx], f2[fine_idx], x, coords0,
                        )
                        flows.append(fl)
                        hiddens.append(hi)
                        readouts.append(ro)
                        prevs.append(pv)
                    h_state, flow = carry

                    flows = jnp.stack(flows)
                    hiddens = jnp.stack(hiddens)
                    readouts = jnp.stack(readouts)
                    prevs = jnp.stack(prevs)
                else:
                    step = nn.scan(
                        body,
                        variable_broadcast=["params", "batch_stats"],
                        split_rngs={"params": False, "dropout": True},
                        in_axes=(0, nn.broadcast, nn.broadcast, nn.broadcast,
                                 nn.broadcast),
                        out_axes=0,
                    )(**shared)

                    (h_state, flow), (flows, hiddens, readouts, prevs) = step(
                        (h_state, flow), jnp.zeros((n_iter, 0), dtype=jnp.bfloat16),
                        f1[fine_idx], f2[fine_idx], x, coords0,
                    )

            flow = flows[-1]

            if finest:
                # convex 8x upsampling, batched over all iterations at once
                # (the raft/baseline hoist: one large einsum instead of
                # n_iter rematerialized ones); always called so its params
                # exist regardless of ``upnet``
                # (Up8Network_0 stays the combine call's innermost scope)
                with jax.named_scope("up8"):
                    flows_flat = flows.reshape(n_iter * b, lh, lw, 2)
                    hidden_flat = hiddens.reshape(n_iter * b, lh, lw, hdim)
                    ups = upnet8(hidden_flat, flows_flat)
                    if not upnet:
                        ups = 8.0 * interpolate_bilinear(flows_flat, (h, w))
                    ups = ups.reshape(n_iter, b, h, w, 2)
                out_lvl = [ups[i] for i in range(n_iter)]
            else:
                out_lvl = [flows[i] for i in range(n_iter)]

            out_prev = [prevs[i] for i in range(n_iter)]
            out_corr = [readouts[i] for i in range(n_iter)]

            if prev_flow:
                out_lvl = list(zip(out_prev, out_lvl))
                if corr_flow:
                    out_corr = list(zip(out_prev, out_corr))

            if corr_flow:
                out.append(out_corr)
            out.append(out_lvl)

        if return_state:
            # finest-level (1/8) carry + convergence probe, as in raft
            final = flows[-1]
            if iterations[-1] >= 2:
                prev_f = flows[-2]
            else:
                prev_f = entry_flow
            diff = (final - prev_f).astype(jnp.float32)
            delta = jnp.sqrt(jnp.mean(jnp.sum(diff * diff, axis=-1),
                                      axis=(1, 2)))
            return out, {"flow": final, "hidden": h_state, "delta": delta}

        return out


class _CtfModel(Model):
    """Shared config wrapper for the three registered level counts."""

    levels = None
    # 1: the phase scopes of PR 37 (``compile/owners.py``): an executable
    # stored before them, found again by configuration, would say
    # ``other`` of its encoders and its upsampling
    notes_revision = 1

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        p = cfg["parameters"]
        return cls(
            mixed_precision=bool(p.get("mixed-precision", False)),
            corr_radius=p.get("corr-radius", 4),
            corr_channels=p.get("corr-channels", 32),
            context_channels=p.get("context-channels", 128),
            recurrent_channels=p.get("recurrent-channels", 128),
            dap_init=p.get("dap-init", "identity"),
            encoder_norm=p.get("encoder-norm", "instance"),
            context_norm=p.get("context-norm", "batch"),
            mnet_norm=p.get("mnet-norm", "batch"),
            encoder_type=p.get("encoder-type", "raft"),
            context_type=p.get("context-type", "raft"),
            share_dicl=p.get("share-dicl", False),
            share_rnn=p.get("share-rnn", True),
            corr_type=p.get("corr-type", "dicl"),
            corr_args=p.get("corr-args", {}),
            corr_reg_type=p.get("corr-reg-type", "softargmax"),
            corr_reg_args=p.get("corr-reg-args", {}),
            upsample_hidden=p.get("upsample-hidden", "none"),
            arguments=cfg.get("arguments", {}),
            on_stage_args=cfg.get("on-stage", {"freeze_batchnorm": True}),
            on_epoch_args=cfg.get("on-epoch", {}),
        )

    def __init__(self, corr_radius=4, corr_channels=32, context_channels=128,
                 recurrent_channels=128, dap_init="identity",
                 encoder_norm="instance", context_norm="batch",
                 mnet_norm="batch", encoder_type="raft", context_type="raft",
                 share_dicl=False, share_rnn=True, corr_type="dicl",
                 corr_args={}, corr_reg_type="softargmax", corr_reg_args={},
                 upsample_hidden="none", mixed_precision=False, arguments={},
                 on_epoch_args={}, on_stage_args={"freeze_batchnorm": True}):
        self.mixed_precision = mixed_precision
        self.corr_radius = corr_radius
        self.corr_channels = corr_channels
        self.context_channels = context_channels
        self.recurrent_channels = recurrent_channels
        self.dap_init = dap_init
        self.encoder_norm = encoder_norm
        self.context_norm = context_norm
        self.mnet_norm = mnet_norm
        self.encoder_type = encoder_type
        self.context_type = context_type
        self.share_dicl = share_dicl
        self.share_rnn = share_rnn
        self.corr_type = corr_type
        self.corr_args = dict(corr_args)
        self.corr_reg_type = corr_reg_type
        self.corr_reg_args = dict(corr_reg_args)
        self.upsample_hidden = upsample_hidden

        super().__init__(
            RaftPlusDiclCtfModule(
                levels=self.levels, corr_radius=corr_radius,
                corr_channels=corr_channels,
                context_channels=context_channels,
                recurrent_channels=recurrent_channels, dap_init=dap_init,
                encoder_norm=encoder_norm, context_norm=context_norm,
                mnet_norm=mnet_norm, encoder_type=encoder_type,
                context_type=context_type, corr_type=corr_type,
                corr_args=dict(corr_args), corr_reg_type=corr_reg_type,
                corr_reg_args=dict(corr_reg_args), share_dicl=share_dicl,
                share_rnn=share_rnn, upsample_hidden=upsample_hidden,
                mixed_precision=mixed_precision,
            ),
            arguments=arguments,
            on_epoch_arguments=on_epoch_args,
            on_stage_arguments=on_stage_args,
        )

    def get_config(self):
        default_args = {
            "iterations": _DEFAULT_ITERATIONS[self.levels],
            "dap": True,
            "upnet": True,
            "corr_flow": False,
            "prev_flow": False,
            "corr_grad_stop": False,
        }
        return {
            "type": self.type,
            "parameters": {
                "mixed-precision": self.mixed_precision,
                "corr-radius": self.corr_radius,
                "corr-channels": self.corr_channels,
                "context-channels": self.context_channels,
                "recurrent-channels": self.recurrent_channels,
                "dap-init": self.dap_init,
                "encoder-norm": self.encoder_norm,
                "context-norm": self.context_norm,
                "encoder-type": self.encoder_type,
                "context-type": self.context_type,
                "mnet-norm": self.mnet_norm,
                "share-dicl": self.share_dicl,
                "share-rnn": self.share_rnn,
                "corr-type": self.corr_type,
                "corr-args": self.corr_args,
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
class RaftPlusDiclCtfL2(_CtfModel):
    """``raft+dicl/ctf-l2`` (reference raft_dicl_ctf_l2.py)."""

    type = "raft+dicl/ctf-l2"
    levels = 2


@register_model
class RaftPlusDiclCtfL3(_CtfModel):
    """``raft+dicl/ctf-l3`` — the thesis flagship
    (reference raft_dicl_ctf_l3.py:79-260)."""

    type = "raft+dicl/ctf-l3"
    levels = 3


@register_model
class RaftPlusDiclCtfL4(_CtfModel):
    """``raft+dicl/ctf-l4`` (reference raft_dicl_ctf_l4.py)."""

    type = "raft+dicl/ctf-l4"
    levels = 4


@register_loss
class RestrictedMultiLevelSequenceLoss(Loss):
    """``raft+dicl/mlseq-restricted``: per-level loss masked by the
    displacement still representable at that level, relative to the
    previous-iterate flow (reference raft_dicl_ctf_l3.py:401-473).

    Consumes (prev, flow) pairs, i.e. the model must run with
    ``prev_flow=True``.
    """

    type = "raft+dicl/mlseq-restricted"

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)
        return cls(cfg.get("arguments", {}))

    def __init__(self, arguments={}):
        super().__init__(arguments)

    def get_config(self):
        default_args = {
            "ord": 1,
            "gamma": 0.85,
            "alpha": (0.38, 0.6, 1.0),
            "scale": 1.0,
            "delta_range": (128, 64, 32),
            "delta_mode": "bilinear",
        }
        return {"type": self.type, "arguments": default_args | self.arguments}

    def compute(self, model, result, target, valid, ord=1, gamma=0.8,
                alpha=(0.4, 1.0), scale=1.0, delta_range=(128, 64, 32),
                delta_mode="bilinear"):
        if delta_mode != "bilinear":
            raise ValueError(f"unsupported delta_mode '{delta_mode}'")

        th, tw = target.shape[1:3]
        valid_f = valid.astype(jnp.float32)

        loss = 0.0
        for i_level, level in enumerate(result):
            n = len(level)
            for i_seq, (flow_prev, flow) in enumerate(level):
                weight = alpha[i_level] * gamma ** (n - i_seq - 1)

                flow = upsample_flow_to(flow, (th, tw))
                flow_prev = upsample_flow_to(flow_prev, (th, tw))

                # restrict to displacements the level can still correct
                delta = jnp.abs(target - flow_prev)
                in_range = jnp.logical_and(
                    delta[..., 0] <= delta_range[i_level],
                    delta[..., 1] <= delta_range[i_level],
                )
                mask = valid_f * in_range.astype(jnp.float32)

                dist = jnp.linalg.norm(flow - target, ord=float(ord), axis=-1)
                # empty mask contributes zero (the reference skips the term)
                mean = jnp.sum(dist * mask) / jnp.maximum(jnp.sum(mask), 1.0)
                loss = loss + weight * mean

        return loss * scale
