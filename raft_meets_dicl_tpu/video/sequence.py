"""Sequence runner: temporal warm-start over a frame sequence.

Walks consecutive frame pairs of one video carrying the previous
frame's coarse flow (and optionally the GRU hidden state) into the next
frame's recurrence:

- **frame 0** runs the monolithic full-budget rung program — there is
  no prior, it pays the full iteration count;
- **warm frames** enter through the registered warm-start program
  (:func:`evaluation.make_warm_fn`: bottom ladder rung, previous flow
  forward-projected inside the program) and escalate through the
  existing ``cont=True`` continuation rungs only while the batch's
  flow-delta norm still exceeds the ladder threshold — exactly the
  serve path's balanced-class policy, so a well-predicted frame stops
  at the bottom rung and a cut/occlusion-heavy frame pays more.

Every program involved is a registered ``rung_step`` variant over the
same bucket set: the whole sequence is recompile-free by construction
after the first frame of each mode, and ``warm_pool()``/``--prebuild``
cover the variants for serving.

The runner measures what the warm-start claim needs measuring:
per-frame iterations actually spent, wall seconds, and EPE when ground
truth is supplied — the EPE-vs-iterations evidence that warm frames
reach full-budget quality from the bottom rung. One ``video`` telemetry
event per frame plus a sequence summary event.
"""

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import evaluation, telemetry
from ..serve.ladder import LadderSpec
from .warmstart import project_flow


def fw_bw_flows(step, variables, img1, img2):
    """Forward and backward flow in one doubled-batch program call.

    Concatenates ``[img1; img2]`` against ``[img2; img1]`` on the batch
    axis and runs the *existing* step once — the fw/bw product costs one
    dispatch at 2x batch instead of two, and no new program kind. Use
    offline where the doubled batch shape is free to
    compile once; the serve path instead issues two same-shape calls to
    stay inside its prebuilt bucket programs.

    ``step`` is any ``(variables, a, b) -> (flow, ...)`` program (eval or
    rung). Returns ``(flow_fw, flow_bw)`` with the input batch size.
    """
    b = img1.shape[0]
    a = jnp.concatenate([img1, img2], axis=0)
    c = jnp.concatenate([img2, img1], axis=0)
    out = step(variables, a, c)
    flow = out[0] if isinstance(out, tuple) else out
    return flow[:b], flow[b:]


@dataclass
class FrameResult:
    """One estimated frame pair of a sequence run."""
    frame: int
    flow: np.ndarray          # full-resolution (B, H, W, 2)
    warm: bool
    iterations: int
    rungs: int
    seconds: float
    epe: Optional[float] = None
    carry: Any = None         # device-side {"flow", "hidden", "delta"}


@dataclass
class SequenceResult:
    """A full sequence run: per-frame results + aggregate accounting."""
    frames: List[FrameResult] = field(default_factory=list)
    seconds: float = 0.0

    @property
    def flows(self):
        return [f.flow for f in self.frames]

    def mean_iterations(self):
        if not self.frames:
            return 0.0
        return sum(f.iterations for f in self.frames) / len(self.frames)

    def mean_epe(self):
        vals = [f.epe for f in self.frames if f.epe is not None]
        return sum(vals) / len(vals) if vals else None

    def frames_per_sec(self):
        return len(self.frames) / self.seconds if self.seconds > 0 else 0.0

    def warm_frames(self):
        return sum(1 for f in self.frames if f.warm)


class SequenceRunner:
    """Builds the rung/warm program set once, then runs sequences.

    ``ladder`` defaults to the configured :class:`LadderSpec` (RMD_LADDER
    / RMD_LADDER_THRESHOLD): warm frames start at ``rungs[0]`` and may
    escalate through the continuation increments up to ``rungs[-1]``;
    cold frames run the monolithic ``rungs[-1]`` program.

    ``carry_hidden=True`` additionally threads the GRU hidden state
    across frames: warm frames then enter through a ``cont=True`` rung
    program fed ``(project_flow(prev_flow), prev_hidden)`` instead of
    the flow-only warm program. This trades the zero-init bit-parity
    guarantee (a carried hidden has no cold equivalent) for a better
    prior; the default keeps parity.
    """

    def __init__(self, model, variables, ladder=None, model_id=None,
                 model_args=None, mesh=None, wire=None,
                 carry_hidden=False):
        self.model = model
        self.variables = variables
        self.ladder = ladder if ladder is not None else LadderSpec.from_config()
        self.carry_hidden = bool(carry_hidden)
        kw = dict(model_id=model_id, model_args=model_args, mesh=mesh,
                  wire=wire)
        lad = self.ladder
        self._full = evaluation.make_rung_fn(model, lad.rungs[-1], **kw)
        self._warm = evaluation.make_warm_fn(model, lad.rungs[0], **kw)
        self._conts = {
            inc: evaluation.make_rung_fn(model, inc, cont=True, **kw)
            for inc in sorted(set(lad.increments()))}
        if self.carry_hidden:
            # warm entry via a base-rung-sized continuation program
            self._warm_cont = evaluation.make_rung_fn(
                model, lad.rungs[0], cont=True, **kw)

    def programs(self):
        """Every program the runner can execute (compile accounting)."""
        progs = [self._full, self._warm, *self._conts.values()]
        if self.carry_hidden:
            progs.append(self._warm_cont)
        return progs

    def compiles(self):
        return sum(getattr(p, "compiles", 0) for p in self.programs())

    def _epe(self, flow, target, valid=None):
        d = np.asarray(flow, np.float32) - np.asarray(target, np.float32)  # graftlint: disable=host-sync -- EPE accounting is host math on an already-measured frame
        err = np.sqrt(np.sum(d * d, axis=-1))
        if valid is not None:
            v = np.asarray(valid, bool)  # graftlint: disable=host-sync -- valid masks are host numpy inputs
            return float(err[v].mean()) if v.any() else float("nan")
        return float(err.mean())

    def _run_frame(self, i1, i2, carry):
        """One frame pair: (flow, state, warm, iterations, rungs)."""
        lad = self.ladder
        if carry is None:
            flow, state = self._full(self.variables, i1, i2)
            return flow, state, False, lad.rungs[-1], 1
        if self.carry_hidden:
            init = project_flow(carry["flow"])
            flow, state = self._warm_cont(self.variables, i1, i2, init,
                                          carry["hidden"])
        else:
            flow, state = self._warm(self.variables, i1, i2, carry["flow"])
        executed, rungs = lad.rungs[0], 1
        for inc in lad.increments():
            worst = float(np.max(np.asarray(state["delta"])))  # graftlint: disable=host-sync -- the escalation decision needs the delta norm on host (same policy as serve's balanced class)
            if worst <= lad.threshold:
                break
            flow, state = self._conts[inc](self.variables, i1, i2,
                                           state["flow"], state["hidden"])
            executed += inc
            rungs += 1
        return flow, state, True, executed, rungs

    def run(self, frames, targets=None, valids=None, warm=True,
            keep_flows=True):
        """Walk ``frames`` (list of (B, H, W, 3) arrays) pairwise.

        ``targets``/``valids`` optionally supply per-pair ground truth
        (len(frames) - 1 entries) for EPE accounting. ``warm=False``
        runs every pair cold through the full program — the baseline arm
        of the cold-vs-warm comparison. Returns a
        :class:`SequenceResult`.
        """
        if len(frames) < 2:
            raise ValueError("a sequence needs at least two frames")
        tele = telemetry.get()
        result = SequenceResult()
        t_seq = time.perf_counter()
        carry = None
        for t in range(len(frames) - 1):
            i1 = jnp.asarray(frames[t])
            i2 = jnp.asarray(frames[t + 1])
            t0 = time.perf_counter()
            flow, state, was_warm, its, rungs = self._run_frame(
                i1, i2, carry if warm else None)
            jax.block_until_ready(flow)  # graftlint: disable=host-sync -- per-frame wall seconds are the measurement this runner exists for
            dt = time.perf_counter() - t0
            epe = None
            if targets is not None:
                epe = self._epe(flow, targets[t],
                                None if valids is None else valids[t])
            fr = FrameResult(
                frame=t, flow=np.asarray(flow) if keep_flows else None,  # graftlint: disable=host-sync -- keep_flows opts into fetching results
                warm=was_warm, iterations=its, rungs=rungs,
                seconds=dt, epe=epe, carry=state)
            result.frames.append(fr)
            if tele.enabled:
                tele.emit("video", event="frame", frame=t, warm=was_warm,
                          iterations=its, rungs=rungs,
                          seconds=round(dt, 6),
                          **({} if epe is None else {"epe": round(epe, 4)}))
            carry = state
        result.seconds = time.perf_counter() - t_seq
        if tele.enabled:
            mean_epe = result.mean_epe()
            tele.emit(
                "video", event="sequence", frames=len(result.frames),
                warm_frames=result.warm_frames(),
                mean_iterations=round(result.mean_iterations(), 2),
                frames_per_sec=round(result.frames_per_sec(), 3),
                seconds=round(result.seconds, 4),
                **({} if mean_epe is None
                   else {"mean_epe": round(mean_epe, 4)}))
        return result
