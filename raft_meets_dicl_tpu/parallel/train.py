"""Sharded train/eval step builders.

One jitted SPMD program: the batch shards over the ``data`` mesh axis and
the parameters live wherever the partitioner put them — fully replicated
on the historical 1-D mesh, or sharded over ``model`` on a 2-D
``(data × model)`` mesh (``parallel.partition``). The loss is a global
mean, so XLA's partitioner emits the psum/all-reduce over ICI by itself —
the explicit NCCL choreography the reference delegates to
``nn.DataParallel`` doesn't exist here.

Gradient clipping is an optax transform configured by the strategy layer.
Gradient accumulation has two forms: the legacy host-driven
``optax.MultiSteps`` (k step calls per optimizer update), and the in-step
``accumulate=k`` — a ``lax.scan`` over k microbatches summing gradients
before one optimizer apply, which buys k× effective batch for one extra
params-sized buffer instead of k× activation HBM.
"""

from typing import Any

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compile import (
    ProgramKey, effective_args_key, flag_items, register_step, registry,
)
from . import partition
from .mesh import traced_under


class TrainState(struct.PyTreeNode):
    """Everything the train step carries: params, BN stats, optimizer.

    ``nonfinite_count`` is the cumulative number of optimizer updates the
    skip-guard refused to apply (see ``make_train_step(nonfinite='skip')``)
    — living on device, it rides along for free and lets the host read
    "how many steps tripped since the last fetch" with the same amortized
    fetch that resolves the finite flag, instead of a per-step sync.
    """

    params: Any
    batch_stats: Any
    opt_state: Any
    step: jax.Array
    nonfinite_count: jax.Array

    @classmethod
    def create(cls, variables, tx):
        params = variables["params"]
        return cls(
            params=params,
            batch_stats=variables.get("batch_stats", {}),
            opt_state=tx.init(params),
            step=jnp.zeros((), jnp.int32),
            nonfinite_count=jnp.zeros((), jnp.int32),
        )

    def variables(self):
        return {"params": self.params, "batch_stats": self.batch_stats}


def make_train_step(model, loss_fn, tx, mesh=None, loss_args=None,
                    model_args=None, donate=True, external_lr=False,
                    with_grads=False, wire=None, nonfinite=None,
                    state_sharding=None, accumulate=1, key=None,
                    augment=None):
    """Build the jitted training step, registered as a compiled program.

    Static per-stage configuration (``model_args``, ``loss_args``) is baked
    in — a new stage builds a new step function, recompiling as the
    reference re-builds its optimizer per stage.

    With ``external_lr`` the step takes the learning rate as its second
    argument and scales the optimizer's (lr-less) updates by ``-lr`` — the
    strategy layer's host-side schedulers drive it. Without it, ``tx`` must
    contain its own lr scaling.

    With ``mesh``, input/output shardings are annotated: the batch splits
    on the leading axis over every mesh axis; the state follows
    ``state_sharding`` — a ``TrainState``-shaped pytree of
    ``NamedSharding``s from ``partition.Partitioner.state_shardings``
    (None keeps the historical fully-replicated layout). A genuinely
    sharded layout runs ZeRO-style: params all-gather to replicated for
    the forward/backward, gradients reduce back onto the shards, and the
    optimizer update stays shard-local — params and moments pay per-chip
    HBM divided by the model-axis size at rest. ``donate`` keeps
    donating the (possibly sharded) state buffers to their successors.

    ``accumulate=k`` compiles in-step gradient accumulation: the step
    takes a ``k·B`` batch, ``lax.scan``s over k microbatches of B
    (summing gradients, chaining batch-stats updates), and applies ONE
    optimizer update from the averaged gradients — k× effective batch at
    one microbatch's activation memory. The batch's leading dim must be
    divisible by k (and, under a mesh, each microbatch by the data-axis
    size).

    ``with_grads`` adds the raw gradient pytree to ``aux`` for inspection
    (gradient-statistics metrics). Off by default: returning grads keeps a
    second params-sized buffer alive past the optimizer update, defeating
    donation.

    ``wire`` (a ``models.wire.WireFormat``) makes the step accept
    wire-format batches: compact-dtype images that are dequantized and
    clip/range-normalized on device, f16 flow, optionally bit-packed
    valid masks. The host-side pipeline must then skip normalization
    (``InputSpec.apply(..., normalize=False)``).

    ``nonfinite='skip'`` compiles the skip-step discipline of dynamic
    loss scaling (Micikevicius et al. 2018) into the step: when the
    final flow or the post-clip update tree contains a non-finite value,
    the params/batch-stats/optimizer update is dropped on device (the
    previous state carries forward bit-identically) and
    ``state.nonfinite_count`` increments. ``aux['finite']`` then means
    "this step's update applied"; detection needs no extra host sync.
    The default (None) keeps the unguarded update: NaNs are absorbing
    through the optimizer state, which is what the ``raise`` policy's
    amortized trip detection relies on.

    ``key`` (a ``compile.ProgramKey``) registers the step under a stable
    identity — deduped in the process-wide registry and, when the AOT
    store is enabled, round-tripped through serialized executables so a
    repeat boot compiles nothing. Without a key the step is registered
    anonymously: compile events still attribute to 'train_step', but the
    program is private to the caller (the right default here, since the
    ``tx``/``loss_fn`` closures have no stable identity of their own).

    ``augment`` (a ``data.device_augment.DeviceAugment``) compiles the
    augmentation pipeline into the step: the public signature grows two
    trailing arguments ``(sample_ids [B] uint32, epoch int32)``, and the
    decoded batch is warped/jittered on device under per-sample keys
    derived from ``(sample_id, epoch)`` — deterministic and resumable.
    The augmented program registers as a flag variant
    (``augment=<token>`` appended to ``key``); ``augment=None`` keeps the
    historical signature and key byte-identical, so existing registered
    programs, pins, and AOT artifacts are untouched.
    """
    loss_args = dict(loss_args or {})
    model_args = dict(model_args or {})
    guard = nonfinite == "skip"
    accumulate = max(1, int(accumulate))

    # the augmented step is a distinct program: extend a caller key that
    # doesn't already carry the flag (mirrors make_eval_step's args flag)
    if (augment is not None and key is not None
            and not any(n == "augment" for n, _ in key.flags)):
        key = ProgramKey(kind=key.kind, model=key.model,
                         flags=key.flags
                         + flag_items(augment=augment.describe()))

    # gather-compute only when the layout actually shards something: the
    # degenerate all-replicated sharding keeps the historical program
    # (and its compiled artifact) bit-for-bit
    gather = (mesh is not None and state_sharding is not None
              and partition.is_sharded(state_sharding.params))
    repl_one = partition.replicated(mesh) if mesh is not None else None
    bspec = partition.batch_spec(mesh) if mesh is not None else None

    def forward(params, batch_stats, img1, img2, flow, valid, keys=None):
        # the scopes (here, in ``step`` and in the models) name phases for
        # the compiled text's readers (compile/owners.py): metadata only
        with jax.named_scope("input"):
            if wire is not None:
                img1, img2, flow, valid = wire.decode(img1, img2, flow,
                                                      valid)
            if augment is not None:
                # on-device augmentation of the decoded (normalized)
                # batch, keyed per sample — inside the grad-free data
                # path, XLA schedules it alongside the forward's first
                # convs
                img1, img2, flow, valid = augment.apply(
                    keys, img1, img2, flow, valid)

        def compute_loss(p):
            out, new_bs = model.apply(
                {"params": p, "batch_stats": batch_stats},
                img1, img2, train=True, **model_args,
            )
            result = model.get_adapter().wrap_result(out, img1.shape[1:3])
            with jax.named_scope("loss"):
                l = loss_fn(model, result.output(), flow, valid,
                            **loss_args)
            return l, (new_bs, result.final())

        return jax.value_and_grad(compute_loss, has_aux=True)(params)

    def step(state, lr, img1, img2, flow, valid, sample_ids=None,
             epoch=None):
        # ZeRO-style gather: one all-gather of the sharded params for the
        # compute graph (its overlap with the first encoder convs is the
        # compiler's to schedule; not measured on chips)
        params = (jax.lax.with_sharding_constraint(state.params, repl_one)
                  if gather else state.params)

        keys = (augment.batch_keys(sample_ids, epoch)
                if augment is not None else None)

        if accumulate == 1:
            (loss, (new_bs, final)), grads = forward(
                params, state.batch_stats, img1, img2, flow, valid, keys)
        else:
            # k microbatches through one scan: gradients sum into a
            # params-sized accumulator, batch stats chain microbatch to
            # microbatch (the same sequential update k separate steps
            # would apply), finals stack so aux keeps the full-batch
            # contract for the host-side metrics
            def split(x):
                x = x.reshape((accumulate, x.shape[0] // accumulate)
                              + x.shape[1:])
                if mesh is not None:
                    x = jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, P(None, *bspec)))
                return x

            micro = jax.tree.map(split, (img1, img2, flow, valid))
            if augment is not None:
                # per-sample keys split with their samples; re-derive the
                # leading-axis layout the same way the batch does
                micro = micro + (split(keys),)

            def body(carry, mb):
                bs, gsum, lsum = carry
                (l, (new_bs, fin)), g = forward(params, bs, *mb)
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (new_bs, gsum, lsum + l), fin

            zeros = jax.tree.map(jnp.zeros_like, params)
            (new_bs, gsum, lsum), finals = jax.lax.scan(
                body,
                (state.batch_stats, zeros, jnp.zeros((), jnp.float32)),
                micro,
            )
            # each microbatch loss is a mean over its (equal-sized)
            # slice, so the mean of means is the big-batch mean — and
            # the averaged gradient sum is its gradient
            grads = jax.tree.map(lambda g: g / accumulate, gsum)
            loss = lsum / accumulate
            final = finals.reshape((-1,) + finals.shape[2:])

        # everything after the gradient: clip, norms, the optax update,
        # apply_updates, the finiteness keep
        with jax.named_scope("optimizer"):
            if gather:
                # reduce the gradients back onto the param shards; from
                # here on the optimizer update is elementwise and
                # shard-local
                grads = jax.lax.with_sharding_constraint(
                    grads, state_sharding.params)

            updates, new_opt = tx.update(grads, state.opt_state,
                                         state.params)
            if external_lr:
                updates = jax.tree.map(lambda u: -lr * u, updates)
            new_params = optax.apply_updates(state.params, updates)

            finite = jnp.all(jnp.isfinite(final))
            nf_count = state.nonfinite_count

            if guard:
                # the update tree is where every poison ends up (NaN grads
                # -> NaN moments -> NaN updates; NaN lr -> NaN updates), so
                # one reduce over it catches grad/optimizer/lr poison before
                # the params do — checking it alongside the flow keeps
                # batch_stats poison (via a NaN loss/forward) covered too
                ok = finite
                for leaf in jax.tree.leaves(updates):
                    ok &= jnp.all(jnp.isfinite(leaf))

                def keep(new, old):
                    return jax.tree.map(
                        lambda n, o: jnp.where(ok, n, o), new, old)

                new_params = keep(new_params, state.params)
                new_bs = keep(new_bs, state.batch_stats)
                new_opt = keep(new_opt, state.opt_state)
                finite = ok
                nf_count = nf_count + jnp.where(ok, 0, 1).astype(jnp.int32)

            new_state = state.replace(
                params=new_params,
                batch_stats=new_bs,
                opt_state=new_opt,
                step=state.step + 1,
                nonfinite_count=nf_count,
            )
            aux = {
                "loss": loss,
                "final": final,
                "finite": finite,
                "nonfinite_count": nf_count,
                # in-step global norms: two elementwise reductions fused
                # into the compiled step, fetched host-side only at the
                # amortized finite-check cadence (observability gauges)
                "grad_norm": optax.global_norm(grads),
                "update_norm": optax.global_norm(updates),
            }
            if with_grads:
                aux["grads"] = grads
        return new_state, aux

    if external_lr:
        if augment is not None:
            # exact-arity wrapper: jit sharding specs match positionally
            def public(state, lr, img1, img2, flow, valid, sample_ids,
                       epoch):
                return step(state, lr, img1, img2, flow, valid,
                            sample_ids, epoch)
        else:
            public = step
        n_lead = 2  # (state, lr, ...)
    else:
        # bind a dummy lr so the public signature stays (state, batch...)
        if augment is not None:
            def public(state, img1, img2, flow, valid, sample_ids, epoch):
                return step(state, 0.0, img1, img2, flow, valid,
                            sample_ids, epoch)
        else:
            def public(state, img1, img2, flow, valid):
                return step(state, 0.0, img1, img2, flow, valid)

        n_lead = 1

    # register_step: the registry Program attributes this function's
    # (re)compiles to 'train_step' in compile events, counts them
    # per-program, and (stable key + AOT store on) owns the serialized
    # executables
    if mesh is None:
        prog = register_step(
            "train_step",
            jax.jit(public, donate_argnums=(0,) if donate else ()),
            key=key)
        if augment is not None:
            prog.augment = augment
        return prog

    repl = partition.replicated(mesh)
    data = partition.data_sharding(mesh)
    state_in = state_sharding if state_sharding is not None else repl
    aux_shardings = {"loss": repl, "final": data, "finite": repl,
                     "nonfinite_count": repl, "grad_norm": repl,
                     "update_norm": repl}
    if with_grads:
        # gradients shard exactly like the parameters they differentiate
        aux_shardings["grads"] = (state_sharding.params
                                  if gather else repl)

    in_shardings = (state_in,) + (None,) * (n_lead - 1) + (data,) * 4
    if augment is not None:
        # sample ids shard with their samples; the epoch scalar replicates
        in_shardings = in_shardings + (data, None)
    prog = register_step("train_step", traced_under(
        mesh,
        jax.jit(
            public,
            in_shardings=in_shardings,
            out_shardings=(state_in, aux_shardings),
            donate_argnums=(0,) if donate else (),
        )), key=key)
    prog.mesh_axes = dict(mesh.shape)
    if augment is not None:
        prog.augment = augment
    return prog


def inference_step(kind, model, body, key, extra_inputs=0, mesh=None,
                   wire=None, variables_sharding=None, out_shardings=False,
                   attrs=None):
    """The registered inference program of ``key``: the one builder behind
    ``make_eval_step`` and ``evaluation.make_eval_fn`` / ``make_rung_fn``
    / ``make_warm_fn``, which differ in ``body`` and in their key's flags
    alone.

    ``body(variables, img1, img2, *extra)`` runs after the prologue —
    model-sharded variables gathered to replicated, wire-format images
    decoded on device — and returns the program's outputs. The program
    takes ``extra_inputs`` batch-shaped arrays after the images (a
    previous flow, a hidden state): with a ``mesh`` they shard like the
    images, and with ``out_shardings`` so does the (single, batch-shaped)
    output.

    A ``key`` already in the registry returns that program, ``body``
    unused; ``key=None`` builds an anonymous program, fresh each call.
    ``attrs`` are set on a new program (what its callers read off it:
    ``iterations``, ``cont``, ``warm``, ``quant``).
    """
    if key is not None:
        existing = registry().get(key)
        if existing is not None:
            return existing

    gather = (mesh is not None and variables_sharding is not None
              and partition.is_sharded(variables_sharding))
    repl = partition.replicated(mesh) if mesh is not None else None

    def step(variables, img1, img2, *extra):
        if gather:
            variables = jax.lax.with_sharding_constraint(variables, repl)
        if wire is not None:
            with jax.named_scope("input"):
                img1, img2, _, _ = wire.decode(img1, img2)
        return body(variables, img1, img2, *extra)

    if mesh is None:
        step = jax.jit(step)
    else:
        data = partition.data_sharding(mesh)
        variables_in = (variables_sharding if variables_sharding is not None
                        else repl)
        shardings = {"in_shardings": (variables_in,)
                     + (data,) * (2 + extra_inputs)}
        if out_shardings:
            shardings["out_shardings"] = data
        step = traced_under(mesh, jax.jit(step, **shardings))

    # registry Program: compile events attribute to ``kind``, compiles
    # count per-program (warmup/stats read them), AOT artifacts for
    # stable keys; the raw jit stays reachable via __wrapped__
    step = register_step(kind, step, key=key)
    # a ``pyid:`` key names the model by its id: keep it unique
    step._refs = (model,)
    if mesh is not None:
        step.mesh_axes = dict(mesh.shape)
    for name, value in (attrs or {}).items():
        setattr(step, name, value)
    return step


def make_eval_step(model, mesh=None, model_args=None, wire=None,
                   variables_sharding=None, key=None):
    """Build the jitted inference step returning the final flow.

    ``wire`` decodes compact-dtype images on device (see
    ``make_train_step``). ``variables_sharding`` (a variables-shaped
    pytree of ``NamedSharding``s, e.g. from
    ``partition.Partitioner.variables_sharding``) lets the eval step
    take model-sharded parameters directly — they gather to replicated
    inside the step; None keeps them replicated. ``key`` registers the
    step under a stable ``compile.ProgramKey`` (dedupe + AOT), as in
    ``make_train_step``.

    The program returns ``result.final()`` alone, so it asks the model
    for the final flow only (``final_only``, see ``Model.apply``); the
    switch is part of the effective arguments the key encodes.
    """
    model_args = dict(model_args or {}) | {"final_only": True}

    # a caller-provided key must encode the *effective* model arguments
    # (see ``compile.effective_args_key``): without this, e.g. a
    # non-default ``iterations`` count silently shares the default
    # program's key — and its AOT artifact — with the default-count model
    if key is not None and not any(n == "args" for n, _ in key.flags):
        args_key = effective_args_key(model, model_args)
        if args_key is None:
            key = None  # unkeyable (array-valued) args: never dedupe
        else:
            key = ProgramKey(kind=key.kind, model=key.model,
                             flags=key.flags + flag_items(args=args_key))

    def body(variables, img1, img2):
        out = model.apply(variables, img1, img2, train=False, **model_args)
        result = model.get_adapter().wrap_result(out, img1.shape[1:3])
        return result.final()

    return inference_step("eval_step", model, body, key, mesh=mesh,
                          wire=wire, variables_sharding=variables_sharding,
                          out_shardings=True)
