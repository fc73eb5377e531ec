"""Model framework core: Model/Loss wrappers, adapters, results.

TPU-native redesign of the reference framework classes
(src/models/model.py:5-82). The key difference from the torch original: a
model here is a *pure function* — a Flax linen module whose parameters live
in an explicit variables pytree — so the wrapper exposes ``init``/``apply``
instead of owning state. Per-stage behavior switches (forward arguments,
batchnorm freezing) are python-side static configuration that is threaded
into ``apply`` as static arguments; changing them across stages triggers an
XLA recompile, which is expected and cheap relative to a training stage.

The config-facing surface is identical to the reference: every Model/Loss is
built ``from_config`` and round-trips ``get_config``; per-stage ``model_args``
and ``loss_args`` merge over the config defaults at call time.
"""


class Result:
    """Wraps a model's raw forward output behind a uniform interface.

    ``output()`` is what the loss consumes (model-specific structure),
    ``final()`` is the finest full-resolution flow estimate,
    ``intermediate_flow()`` exposes per-level/iteration flows for inspection.
    """

    def output(self, batch_index=None):
        raise NotImplementedError

    def final(self):
        raise NotImplementedError

    def intermediate_flow(self):
        raise NotImplementedError


class ModelAdapter:
    """Decouples the trainer/evaluator from model-specific output shapes.

    Also relays stage/epoch lifecycle events to the model with config-bound
    default arguments merged in.
    """

    def __init__(self, model):
        self.model = model

    def wrap_result(self, result, original_shape) -> Result:
        raise NotImplementedError

    def on_stage(self, stage, **kwargs):
        self.model.on_stage(stage, **(self.model.on_stage_arguments | kwargs))

    def on_epoch(self, stage, epoch, **kwargs):
        self.model.on_epoch(stage, epoch, **(self.model.on_epoch_arguments | kwargs))


class Model:
    """Config-constructible wrapper around a Flax module.

    Holds the module definition, default forward arguments (merged with
    per-stage overrides at apply time), and lifecycle-event argument sets.
    Parameters are *not* stored here — they are created by ``init`` and
    passed to ``apply`` explicitly, so the same Model object can serve any
    number of parameter sets (e.g. across pmap replicas).
    """

    type = None

    # A stored executable carries what its trace noted
    # (``telemetry.note_trace``) and is found again by configuration, not
    # by source. A model whose notes change their meaning under an
    # unchanged configuration counts this up; it is part of the model's
    # program keys, so that no boot loads the older notes with an older
    # executable. None leaves the keys as they were.
    notes_revision = None

    @classmethod
    def _typecheck(cls, cfg):
        if cfg["type"] != cls.type:
            raise ValueError(f"invalid model type '{cfg['type']}', expected '{cls.type}'")

    def __init__(self, module, arguments, on_epoch_arguments={}, on_stage_arguments={}):
        self.module = module
        self.arguments = dict(arguments)
        self.on_epoch_arguments = dict(on_epoch_arguments)
        self.on_stage_arguments = dict(on_stage_arguments)
        self.frozen_batchnorm = False

    def get_config(self):
        raise NotImplementedError

    def get_adapter(self) -> ModelAdapter:
        raise NotImplementedError

    def init(self, rng, img1, img2, **kwargs):
        """Create the variables pytree (params + batch_stats) for tracing shapes."""
        args = self.arguments | kwargs
        return self.module.init(rng, img1, img2, train=False, **args)

    def apply(self, variables, img1, img2, train=False, rngs=None, **kwargs):
        """Run the forward pass.

        In training mode (unless batchnorm is frozen for the stage) batch
        statistics are mutable and the updated collection is returned
        alongside the output: ``(output, updated_batch_stats)``. In eval
        mode just the output is returned.

        Framework convention: module ``__call__`` signatures take
        ``(img1, img2, train, frozen_bn, **model_args)`` — ``train`` drives
        stochastic layers (dropout), ``frozen_bn`` only switches batch norm
        to running statistics, matching the reference's selective
        ``freeze_batchnorm`` (src/models/common/norm.py:18-32).

        Ladder continuation protocol: every impl accepts ``flow_init`` and
        ``hidden_init`` (traced arrays seeding the recurrence carry at the
        coarse grid) and a static ``return_state`` switch. With
        ``return_state=True`` the raw output becomes ``(output, state)``
        where ``state`` is ``{"flow", "hidden", "delta"}`` — the carry to
        hand to the next rung program plus a per-sample convergence norm.
        The tuple passes through here untouched; rung programs
        (``evaluation.make_rung_fn``) unpack it themselves.

        Final-flow protocol: every impl accepts a static ``final_only``
        switch, set by the builders of inference programs that return
        ``Result.final()`` alone (never by the train step, never from a
        config). With it an impl may leave out intermediates nobody
        reads — the RAFT family upsamples the last iteration only, so
        its output is the one-element list ``[final]`` — while
        ``final()`` and ``state`` stay what they are without it; an impl
        with nothing to leave out ignores it.
        """
        args = self.arguments | kwargs
        frozen = self.frozen_batchnorm

        if train and not frozen and "batch_stats" in variables:
            out, mutated = self.module.apply(
                variables, img1, img2, train=True, frozen_bn=False, rngs=rngs,
                mutable=["batch_stats"], **args,
            )
            return out, mutated["batch_stats"]

        out = self.module.apply(
            variables, img1, img2, train=train, frozen_bn=frozen, rngs=rngs, **args
        )
        if train:
            return out, variables.get("batch_stats", {})
        return out

    def on_stage(self, stage, **kwargs):
        """Default stage hook: support ``freeze_batchnorm`` like the reference
        (src/models/common/norm.py:18-32) via an apply-time switch."""
        self.frozen_batchnorm = bool(kwargs.get("freeze_batchnorm", False))

    def on_epoch(self, stage, epoch, **kwargs):
        pass

    def __call__(self, variables, img1, img2, train=False, rngs=None, **kwargs):
        return self.apply(variables, img1, img2, train=train, rngs=rngs, **kwargs)


class Loss:
    """Config-constructible loss with default-argument merging.

    ``compute`` is a pure jnp function of (result-output, target, valid) and
    must be traceable under jit; the ``model`` argument carries the wrapper
    for losses that regularize parameters.
    """

    type = None

    @classmethod
    def _typecheck(cls, cfg):
        if cfg["type"] != cls.type:
            raise ValueError(f"invalid loss type '{cfg['type']}', expected '{cls.type}'")

    def __init__(self, arguments):
        self.arguments = dict(arguments)

    def get_config(self):
        raise NotImplementedError

    def compute(self, model, result, target, valid, **kwargs):
        raise NotImplementedError

    def __call__(self, model, result, target, valid, **kwargs):
        return self.compute(model, result, target, valid, **(self.arguments | kwargs))
