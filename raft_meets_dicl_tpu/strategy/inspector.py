"""Inspector callback protocol — the trainer is observable, observability
lives elsewhere (reference src/strategy/inspector.py:1-30)."""


class Inspector:
    def setup(self, log, ctx):
        pass

    def wants_host_images(self, step):
        """Whether ``on_batch``/hooks will consume pixel values at this
        step. Under a wire-format input pipeline the trainer only decodes
        host images to normalized f32 when this returns True."""
        return False

    def flush(self):
        """Write out whatever the inspector holds back (a step's scalars
        that it reads one step late). The loop calls this wherever it
        stops stepping or somebody may read what was written: when an
        epoch's loop ends, before a failed-state dump and a rollback."""
        pass

    def on_step_start(self, log, ctx, stage, epoch, i):
        pass

    def on_step_end(self, log, ctx, stage, epoch, i):
        pass

    def on_batch_start(self, log, ctx, stage, epoch, i, img1, img2, target,
                       valid, meta):
        pass

    def on_batch(self, log, ctx, stage, epoch, i, img1, img2, target, valid,
                 meta, result, loss):
        pass

    def on_epoch_start(self, log, ctx, stage, epoch):
        pass

    def on_epoch(self, log, ctx, stage, epoch):
        pass

    def on_stage_start(self, log, ctx, stage):
        pass

    def on_stage(self, log, ctx, stage):
        pass
