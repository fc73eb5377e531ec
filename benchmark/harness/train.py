"""Driver of training traffic: the program's own loop, timed from outside.

The run is assembled the way ``cmd/train.py`` assembles it (environment,
devices, seeds, model, strategy, inspector, ``TrainingContext``) and then
``TrainingContext.run`` is called, once. The benchmark changes nothing in
it. It hands it weights made from the seed, and it wraps the inspector in
a probe that forwards every callback and looks at the clock:

- set-up ends and the window opens at the first program sync point (the
  loop's finiteness fetch, every ``RMD_FINITE_CHECK_EVERY`` steps) at or
  after ``warmup_steps``, with ``block_until_ready`` on the state;
- the window closes at the first sync point at or after ``--seconds``,
  again with ``block_until_ready``; inside it the probe only stamps times;
- with ``--trace 1`` the profiler then records ``trace_steps`` more steps;
- ``request_stop`` ends the loop (its emergency checkpoint falls after the
  window).

The first ``check_steps`` steps of the same loop are the ones the plain
reference follows once the program's state is freed.
"""

import json
import logging
import os
import time
from pathlib import Path

from . import device, stats, xtrace
from .spec import ROOT


class Probe:
    """The inspector, plus a clock. Everything not overridden forwards."""

    def __init__(self, inner, ctl):
        self._inner = inner
        self._ctl = ctl

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def wants_host_images(self, step):
        """Forwarded; the answer says whether the loop hands this step's
        callbacks decoded (normalised f32) images instead of wire ones."""
        fn = getattr(self._inner, "wants_host_images", None)
        self._ctl.decoded = bool(fn(step)) if callable(fn) else True
        return self._ctl.decoded

    def on_step_start(self, log, ctx, stage, epoch, i):
        self._ctl.step_start(i)
        return self._inner.on_step_start(log, ctx, stage, epoch, i)

    def on_batch_start(self, log, ctx, stage, epoch, i, img1, img2, flow,
                       valid, meta):
        self._ctl.batch_start(i, img1, img2, flow, valid)
        return self._inner.on_batch_start(log, ctx, stage, epoch, i, img1,
                                          img2, flow, valid, meta)

    def on_batch(self, log, ctx, stage, epoch, i, img1, img2, flow, valid,
                 meta, result, loss):
        out = self._inner.on_batch(log, ctx, stage, epoch, i, img1, img2,
                                   flow, valid, meta, result, loss)
        self._ctl.batch_end(i, result, loss)
        return out

    def on_step_end(self, log, ctx, stage, epoch, i):
        out = self._inner.on_step_end(log, ctx, stage, epoch, i)
        self._ctl.step_end(ctx, i)
        return out


def _adam_mu(opt_state):
    """The first-moment tree of the optimizer state."""
    import jax
    import optax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


class Controller:
    """Warm-up, window, traced tail: decided at the loop's sync points."""

    def __init__(self, seconds, trace, traffic, sync_every, out_dir, devices,
                 compiles):
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.sync_every = int(sync_every)
        self.warmup_steps = int(traffic["warmup_steps"])
        self.min_blocks = int(traffic.get("min_blocks", 6))
        self.trace_steps = int(traffic.get("trace_steps", 5))
        self.check_steps = int(traffic.get("check_steps", 3))
        self.out_dir = Path(out_dir)
        self.devices = devices
        self.compiles = compiles      # () -> backend compiles so far
        self.phase = "warmup"
        self.starts, self.ends = [], []
        self.open_index = self.close_index = None
        self.compiles_open = self.compiles_close = None
        self.wall_open = self.wall_close = None
        self.t_first_step = None
        self.memory_peak = 0
        self.tail = 0
        self.trace_dir = None
        self.trace_wall = None
        self.decoded = False
        self.value_range = (-1.0, 1.0)     # the model's input range
        # what the reference follows
        self.batches, self.losses = [], []
        self.final0 = self.mu1 = self.params_after = None

    # -- the clock -----------------------------------------------------------

    def step_start(self, i):
        now = time.perf_counter()
        if self.t_first_step is None:
            self.t_first_step = now
        self.starts.append(now)

    def batch_start(self, i, img1, img2, flow, valid):
        if i < self.check_steps:
            if self.decoded:
                # the loop decoded the wire images for the inspector's
                # image dump: back to raw values in the clip interval
                lo, hi = self.value_range
                img1 = (img1 - lo) / (hi - lo)
                img2 = (img2 - lo) / (hi - lo)
            self.batches.append((img1, img2, flow, valid))

    def batch_end(self, i, result, loss):
        if i < self.check_steps:
            import numpy as np

            self.losses.append(float(loss))
            if i == 0:
                self.final0 = np.asarray(result.final())

    def _sync(self, ctx):
        import jax

        jax.block_until_ready(ctx.state)
        return time.perf_counter()

    def step_end(self, ctx, i):
        import jax
        import numpy as np

        self.ends.append(time.perf_counter())
        n = i + 1
        at_sync = n % self.sync_every == 0

        if self.phase == "warmup":
            if i == 0:
                self.mu1 = jax.tree.map(np.asarray, _adam_mu(ctx.state.opt_state))
            if i == self.check_steps - 1:
                self.params_after = jax.tree.map(np.asarray, ctx.state.params)
            if at_sync and n >= self.warmup_steps:
                # what set-up wrote (compile cache, AOT artifacts) goes to
                # disk now, not as a writeback stall inside the window
                os.sync()
                self.ends[-1] = self._sync(ctx)
                self.open_index = i
                self.wall_open = time.time()
                self.compiles_open = self.compiles()
                self.phase = "window"
        elif self.phase == "window":
            elapsed = self.ends[-1] - self.ends[self.open_index]
            blocks = (i - self.open_index) // self.sync_every
            if (at_sync and elapsed >= self.seconds
                    and blocks >= self.min_blocks):
                self.ends[-1] = self._sync(ctx)
                self.close_index = i
                self.wall_close = time.time()
                self.compiles_close = self.compiles()
                self.memory_peak = device.memory_peak_bytes(self.devices)
                if self.trace:
                    self._start_trace()
                    self.phase = "tail"
                else:
                    self._finish(ctx)
        elif self.phase == "tail":
            self.tail += 1
            if self.tail >= self.trace_steps:
                self._sync(ctx)
                self._stop_trace()
                self._finish(ctx)

    def _finish(self, ctx):
        self.phase = "done"
        ctx.request_stop("benchmark window closed")

    def _start_trace(self):
        import jax

        self.trace_dir = self.out_dir / "trace"
        self._t_trace = time.perf_counter()
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=xtrace.profile_options())

    def _stop_trace(self):
        import jax

        jax.profiler.stop_trace()
        self.trace_wall = time.perf_counter() - self._t_trace


def _stage_config(cell):
    """The stage as the program's strategy loader takes it: the traffic
    file's stage with the configuration's crop and the cell's batch. The
    scenes are the same set for every seed (the program keys its stored
    executable by the stage, source and all); the loader draws the epoch's
    order, and so every batch, from the run's seed."""
    traffic, train = cell.traffic, cell.config["train"]
    stage = json.loads(json.dumps(traffic["stage"]))
    batch = int(train["batch_per_chip"]) * cell.chips
    stage["data"] = {
        "epochs": 1,
        "batch-size": batch,
        "source": {"type": "synth", "shape": list(train["crop"]),
                   "size": batch * int(traffic["epoch_steps"]),
                   "seed": 1},
    }
    return {"mode": "continuous", "stages": [stage]}, batch


def run(cell, seed, seconds, trace, out_dir, boot, platform="tpu"):
    """One run of a training cell. Returns the harness's ``Run`` dict."""
    for knob, value in cell.config.get("knobs", {}).items():
        os.environ[knob] = str(value)

    from raft_meets_dicl_tpu import compile as programs
    from raft_meets_dicl_tpu import inspect as inspect_
    from raft_meets_dicl_tpu import models, parallel, strategy, telemetry, utils
    from raft_meets_dicl_tpu.cmd.train import Environment, select_devices
    from raft_meets_dicl_tpu.models.wire import WireFormat
    from raft_meets_dicl_tpu.strategy.training import (NonFinitePolicy,
                                                       TrainingContext)
    from raft_meets_dicl_tpu.telemetry import blackbox, goodput
    from raft_meets_dicl_tpu.utils.compcache import enable_persistent_cache

    # as main.py does before any backend use
    enable_persistent_cache()
    programs.enable_aot()
    env = Environment.load(cell.config["env"])
    env.apply()

    try:
        select_devices(platform, None)
    except ValueError as e:
        raise device.NoAccelerator(str(e)) from e
    devices = device.require(cell.chips, platform)

    import jax
    import numpy as np

    out_dir = Path(out_dir)
    path_run = out_dir / "run"
    path_run.mkdir(parents=True, exist_ok=True)
    utils.logging.setup(path_run / "main.log")
    logging.getLogger().setLevel(logging.WARNING)

    tele = telemetry.activate(telemetry.Telemetry(None))
    if utils.env.get_bool("RMD_GOODPUT"):
        goodput.activate()
    blackbox.activate(capacity=max(1, utils.env.get_int("RMD_BLACKBOX_STEPS")),
                      registry=telemetry.metrics.registry())

    utils.seeds.Seeds(python=seed, numpy=seed, jax=seed).apply()

    model = models.load(cell.config["model"])
    strat_cfg, batch = _stage_config(cell)
    strat = strategy.load(ROOT, strat_cfg)
    inspc = inspect_.load(cell.traffic["inspect"])

    if cell.chips > 1:
        mesh = parallel.make_mesh(None, devices=devices)
    else:
        mesh = None
        jax.config.update("jax_default_device", devices[0])

    inspector, chkptm = inspc.build(model.id, path_run)
    sync_every = max(1, utils.env.get_int("RMD_FINITE_CHECK_EVERY"))

    def compiles():
        return tele.counts().get("compile", 0)

    ctl = Controller(seconds, trace, cell.traffic, sync_every, out_dir,
                     devices, compiles)
    ctl.value_range = tuple(model.input.range)
    wire = WireFormat.from_config(env.wire)
    tctx = TrainingContext(
        utils.logging.Logger(), path_run, strat, model.id, model.model,
        model.model.get_adapter(), model.loss, model.input,
        Probe(inspector, ctl), chkptm, mesh=mesh, step_limit=None,
        loader_args=dict(env.loader_args), wire=wire, eval_buckets=None,
        nonfinite=NonFinitePolicy.from_config(env.nonfinite), accumulate=1,
        augment=None)

    # weights from the seed, made by the benchmark in one jitted call and
    # handed to the program in the tree its own init would build
    import importlib

    from ..reference import common as refc

    ref = importlib.import_module(
        f"benchmark.reference.{cell.config['reference']}")
    spec = ref.spec(cell.config["model"])
    flat = refc.init(spec, seed)
    tctx.variables = refc.nest(flat)
    _same_tree(tctx, strat.stages[0], flat)

    tele.emit("run_start", dir=str(path_run), commit=None, comment="benchmark",
              platform=devices[0].platform,
              device_kind=devices[0].device_kind,
              device_count=len(jax.devices(devices[0].platform)),
              devices_used=len(devices), backend=jax.default_backend())
    try:
        tctx.run(None, None, None)
    finally:
        goodput.deactivate()
        blackbox.deactivate()
        tele.emit("run_end")
    # free the program's state and unload its executables (a loaded
    # program keeps its temporaries reserved) before the reference runs
    tctx.state = tctx.variables = tctx.step_fn = None
    del flat, tctx
    device.release_programs()

    if ctl.close_index is None:
        raise RuntimeError(
            f"the loop ended in phase '{ctl.phase}' after {len(ctl.ends)} "
            f"steps: the epoch ({cell.traffic['epoch_steps']} steps) is "
            f"shorter than warm-up plus window")

    return {
        "kind": "train", "cell": cell, "seed": seed, "out_dir": out_dir,
        "devices": devices, "events": list(tele.events), "ctl": ctl,
        "batch": batch, "sync_every": sync_every, "boot": boot,
        "reference": ref, "spec": spec, "stage": strat_cfg["stages"][0],
        "trace_dir": ctl.trace_dir, "trace_wall_s": ctl.trace_wall,
    }


def _same_tree(tctx, stage, flat):
    """The program's own init must ask for exactly the leaves the
    benchmark made: same names, same shapes."""
    import jax

    from ..reference import common as refc

    img1, img2, *_ = tctx.input.apply(stage.data.source).jax()[0]
    args = dict(tctx.model.arguments)
    if "iterations" in args:
        its = args["iterations"]
        args["iterations"] = (1 if isinstance(its, int)
                              else tuple(1 for _ in its))
    want = jax.eval_shape(
        lambda a, b: tctx.model.init(jax.random.PRNGKey(0), a, b, **args),
        img1[:1], img2[:1])
    want = {k: tuple(v.shape) for k, v in refc.flatten(
        jax.tree.map(lambda x: x, dict(want))).items()}
    have = {k: tuple(v.shape) for k, v in flat.items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))[:6]
        raise RuntimeError(f"the reference's parameter tree is not the "
                           f"program's: {diff}")


def readings(run):
    """Host-clock numbers of the window, and the per-step records."""
    ctl = run["ctl"]
    ends = ctl.ends[: ctl.close_index + 1]
    r = stats.train_readings(ends, run["sync_every"], ctl.open_index,
                             run["batch"])
    r["setup_s"] = run["boot"]["offset_s"] + (ctl.ends[ctl.open_index]
                                              - run["boot"]["t0"])
    r["warmup_s"] = ctl.ends[ctl.open_index] - ctl.t_first_step
    if ctl.trace_wall is not None:
        # the traced steps but the first, which also starts the profiler
        a, b = ctl.close_index + 1, ctl.close_index + ctl.trace_steps
        r["tail_step_ms"] = 1e3 * (ctl.ends[b] - ctl.ends[a]) / max(1, b - a)
    r["window_compiles"] = ctl.compiles_close - ctl.compiles_open
    r["window_wall"] = (ctl.wall_open, ctl.wall_close)
    return r


def write_records(run):
    ctl = run["ctl"]
    with open(run["out_dir"] / "steps.jsonl", "w") as f:
        for i, (s, e) in enumerate(zip(ctl.starts, ctl.ends)):
            phase = ("warmup" if i <= ctl.open_index else
                     "window" if i <= ctl.close_index else "tail")
            block = ((i - ctl.open_index - 1) // run["sync_every"]
                     if phase == "window" else None)
            f.write(json.dumps({"step": i, "start": s, "end": e,
                                "phase": phase, "block": block}) + "\n")
    with open(run["out_dir"] / "events.jsonl", "w") as f:
        for ev in run["events"]:
            f.write(json.dumps(ev, default=str) + "\n")


def attempted_failed(run):
    ctl = run["ctl"]
    attempted = ctl.close_index - ctl.open_index
    failed = sum(1 for ev in run["events"] if ev["kind"] == "nonfinite")
    return attempted, failed


def trace_module(run):
    return run["cell"].traffic.get("trace_module", "jit_step")


def print_rates(run):
    r = run["readings"]
    rates = sorted(r["block_rates"])
    print(f"[blocks] n={r['blocks']} steps={r['steps']} "
          f"pairs/s whole_window={r['train_pairs_per_s']:.4f} block "
          f"min={rates[0]:.4f} median={r['train_block_pairs_per_s']:.4f} "
          f"max={rates[-1]:.4f} window={r['window_s']:.3f}s "
          f"stall_ms={r['train_stall_ms']:.2f} "
          f"median_step_ms={r['median_step_ms']:.3f} "
          f"setup_s={r['setup_s']:.2f} compiles_in_window="
          f"{r['window_compiles']}", flush=True)
    if "tail_step_ms" in r:
        # does the profiler slow the loop it records?
        print(f"[trace] traced tail {r['tail_step_ms']:.1f} ms a step, "
              f"untraced window {1e3 * r['window_s'] / r['steps']:.1f}",
              flush=True)


def memory_peak(run):
    return run["ctl"].memory_peak


def check(run, verdict, limits):
    from . import train_check

    gaps, notes, _ = train_check.check(run, verdict, limits)
    print(f"[check] notes {json.dumps(notes)}", flush=True)
