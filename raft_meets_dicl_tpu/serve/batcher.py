"""Request types + per-bucket coalescing for the serving path.

The batcher is the host-side half of continuous batching: every admitted
request is quantized onto the canonical :class:`~..models.input.ShapeBuckets`
set at admission (so its compiled program is known before it ever queues),
then coalesced with same-bucket neighbors into full device batches. A
bucket whose queue reaches the batch size dispatches immediately; a
partial batch dispatches once its oldest request has waited the configured
deadline, filled up to the full batch size by tiling the last request —
the eval-style ``pad_to=`` treatment — so it rides the full batch's
compiled program instead of compiling one per remainder size.

This module is numpy-only (no jax): everything device-side lives in the
scheduler/session.
"""

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np


class ServeRejected(RuntimeError):
    """Typed admission rejection: the request never entered the system.

    ``reason`` is the machine-readable shed class (``queue_full`` for
    backpressure). Sheds are the admission-control contract — the
    dispatch loop never stalls to absorb overload; callers retry or
    back off.
    """

    def __init__(self, reason, detail=""):
        self.reason = reason
        super().__init__(f"request rejected ({reason})"
                         + (f": {detail}" if detail else ""))


class ServeError(RuntimeError):
    """Typed per-request failure.

    ``kind`` is one of:

    - ``malformed`` — the payload failed validation at admission;
    - ``oversized`` — the pair fits no configured bucket (no compiled
      program exists for it);
    - ``decode`` — the request failed while its batch was being
      prepared/decoded (the rest of the batch is unaffected);
    - ``internal`` — the dispatch failed; the batch's requests all carry
      this error, the loop continues;
    - ``unknown_class`` — the latency class does not exist (or the
      session has no ladder);
    - ``no_video`` — a sequence request reached a session built without
      video support (``serve --video``);
    - ``unknown_model`` — the request names a model this server does not
      hold, or names none where the server holds several.
    """

    def __init__(self, kind, detail=""):
        self.kind = kind
        super().__init__(f"request failed ({kind})"
                         + (f": {detail}" if detail else ""))


@dataclass
class FlowRequest:
    """One admitted image pair, already quantized and wire-encoded.

    ``img1``/``img2`` are bucket-shaped arrays in the wire dtype (the
    admission path pads raw pixels up to the bucket and encodes them, so
    the dispatch loop only stacks). ``shape`` keeps the original (H, W)
    for cropping the response.
    """

    rid: int
    client: str
    seq: int
    bucket: Tuple[int, int]
    shape: Tuple[int, int]
    img1: np.ndarray
    img2: np.ndarray
    ticket: Any
    t_submit: float
    t_enqueue: float = 0.0
    klass: str = ""  # latency class ("" = plain eval, no ladder)
    sequence: bool = False  # video-session member (warm-start eligible)
    products: bool = False  # also wants fw/bw occlusion + confidence
    trace: Any = None  # telemetry.trace.RequestTrace (the scheduler's)
    model: str = ""  # id of the model that answers it ("" = a stand-in's)


@dataclass
class FlowResult:
    """One served flow: cropped to the request's original extent, with
    the per-request latency spans (seconds) the telemetry event carries,
    all differences of the request trace's marks: ``admission`` (validate
    + quantize + encode), ``queue`` (enqueue to dispatch), ``dispatch``
    (batch assembly + program call + the device's execution), ``device``
    (result fetch), ``total``."""

    rid: int
    client: str
    bucket: Tuple[int, int]
    shape: Tuple[int, int]
    flow: np.ndarray
    spans: Dict[str, float]
    klass: str = ""
    iterations: int = 0  # recurrence iterations actually executed
    warm: bool = False   # video session: started from a cached carry
    occlusion: Optional[np.ndarray] = None   # fw/bw products (H, W) bool
    confidence: Optional[np.ndarray] = None  # fw/bw products (H, W) f32
    model: str = ""  # id of the model that answered


class _ModelLanes(NamedTuple):
    """What one model's lanes are held to."""

    buckets: Any            # models.input.ShapeBuckets
    batch_size: int
    queue_limit: int


class BucketBatcher:
    """Bounded per-lane FIFO queues + deterministic batch selection.

    A lane is ``(model, bucket, klass, sequence)`` — requests only
    coalesce with same-model, same-bucket, same-latency-class,
    same-sequence-ness neighbors, so every dispatched batch runs one
    model's program under one ladder policy (or the video warm-start
    program) end to end. Without a ladder or video sessions every request
    carries the empty class, and with one model lanes degenerate to plain
    per-bucket queues.

    Buckets, batch size and queue bound are a model's own
    (:meth:`add_model`; the constructor's are the first model's, which is
    all a one-model server has). The bound is per lane, so one model's
    overload sheds its own requests and never another's.

    Selection policy (documented because tests pin it): full batches
    first — among lanes holding at least their model's batch size, the
    one whose head request enqueued earliest wins (ties broken by the
    lane tuple: model, bucket size, class). With no full batch, the
    oldest head whose wait exceeded the caller's deadline dispatches as a
    partial. Within a lane, order is strict FIFO. Everything keys on the
    monotonic enqueue stamp plus the lane tuple, so the same submission
    sequence always coalesces identically. ``take`` returns the *bucket*
    (the compiled-program shape); the batch's model and class ride on its
    requests.
    """

    def __init__(self, buckets, batch_size, queue_limit, model=""):
        self._models = {}
        self._queues = {}
        self.add_model(model, buckets, batch_size, queue_limit)
        # the first model's: what a one-model server reads
        self.buckets = buckets
        self.batch_size = int(batch_size)
        self.queue_limit = int(queue_limit)

    def add_model(self, model, buckets, batch_size, queue_limit):
        """One more model's lanes, under its own buckets and sizes."""
        if not buckets.sizes:
            raise ValueError(
                "serving needs explicit bucket sizes ('HxW,...'): the "
                "warm program pool is built per bucket")
        if model in self._models:
            raise ValueError(f"model {model!r} has its lanes already")
        self._models[model] = _ModelLanes(buckets, int(batch_size),
                                          int(queue_limit))
        for b in buckets.sizes:
            self._queues[(model, b, "", False)] = deque()

    def assign(self, h, w, model=None) -> Optional[Tuple[int, int]]:
        """Smallest bucket of ``model`` fitting (h, w), or None
        (oversized)."""
        return self._buckets(model).assign(h, w)

    def encode_pair(self, img1, img2, bucket, encode, model=None):
        """Pad a raw HWC pair up to ``bucket`` and wire-encode it."""
        buckets = self._buckets(model)
        img1 = buckets.pad_image(img1, bucket)
        img2 = buckets.pad_image(img2, bucket)
        return encode(img1), encode(img2)

    def _buckets(self, model):
        return self.buckets if model is None else self._models[model].buckets

    def offer(self, request) -> bool:
        """Enqueue, or refuse (lane queue at bound — backpressure)."""
        model = getattr(request, "model", "")
        lane = (model, request.bucket, getattr(request, "klass", ""),
                getattr(request, "sequence", False))
        q = self._queues.setdefault(lane, deque())
        if len(q) >= self._models[model].queue_limit:
            return False
        request.t_enqueue = time.perf_counter()
        q.append(request)
        return True

    def pending(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def depths(self) -> Dict[str, int]:
        """Per-lane queue depths keyed ``[model:]HxW[/klass][/seq]`` (the
        model omitted for a stand-in session without an id, klass for the
        empty ladderless class, ``/seq`` marking video session lanes) —
        the /statusz live snapshot."""
        out = {}
        for lane, q in sorted(self._queues.items()):
            out[lane_name(*lane)] = len(q)
        return out

    def take(self, now, max_wait_s, drain=False):
        """Next dispatchable batch, or the wake-up deadline.

        Returns ``(bucket, requests)`` when a batch should dispatch now,
        else ``(None, deadline)`` where ``deadline`` is the absolute
        ``perf_counter`` time the oldest partial becomes dispatchable
        (None when every queue is empty). ``drain`` dispatches partials
        immediately (shutdown flush).
        """
        full = [(q[0].t_enqueue, lane) for lane, q in self._queues.items()
                if len(q) >= self._models[lane[0]].batch_size]
        if full:
            _, lane = min(full)
            return lane[1], self._pop(lane)

        heads = [(q[0].t_enqueue, lane)
                 for lane, q in self._queues.items() if q]
        if not heads:
            return None, None
        t_head, lane = min(heads)
        if drain or now - t_head >= max_wait_s:
            return lane[1], self._pop(lane)
        return None, t_head + max_wait_s

    def _pop(self, lane):
        q = self._queues[lane]
        size = self._models[lane[0]].batch_size
        return [q.popleft() for _ in range(min(len(q), size))]

    def assemble(self, requests):
        """Stack a batch's encoded pairs, filling up to its model's batch
        size by tiling the last request (partial batches ride the full
        batch's compiled program; filled outputs are dropped by the
        response crop). Returns ``(img1, img2, fill)``."""
        img1 = np.stack([r.img1 for r in requests])
        img2 = np.stack([r.img2 for r in requests])
        size = self._models[getattr(requests[0], "model", "")].batch_size
        fill = size - len(requests)
        if fill > 0:
            img1 = np.concatenate([img1, np.repeat(img1[-1:], fill, axis=0)])
            img2 = np.concatenate([img2, np.repeat(img2[-1:], fill, axis=0)])
        return img1, img2, fill


def lane_name(model, bucket, klass="", sequence=False):
    """``[model:]HxW[/klass][/seq]``: a lane as /statusz names it."""
    name = f"{bucket[0]}x{bucket[1]}"
    if model:
        name = f"{model}:{name}"
    if klass:
        name = f"{name}/{klass}"
    if sequence:
        name = f"{name}/seq"
    return name
