"""Model input pipeline: range scaling, modulo padding, batching, loading.

Reference behavior (src/models/input.py) with a jax-native adapter: batches
stay NHWC numpy float32 on the host (TPU-native layout — no NCHW transpose
anywhere), validation marks bad batches via ``meta.valid`` instead of
raising, and the loader is a thread-pooled iterator (cv2/numpy release the
GIL) rather than a torch DataLoader with worker processes.
"""

import concurrent.futures
import copy
import itertools
import threading
import time
from dataclasses import replace

import numpy as np

from .. import utils
from ..data.collection import Metadata, SampleArgs, SampleId

# Technical flow-magnitude limit (not an optimization knob): non-finite flow
# values are clamped here so error magnitudes stay computable before masking.
FLOW_INF = 1e10


# numpy pad modes shared by every padding flavor; the aliases map the
# reference configs' torch-style names onto the equivalent numpy modes
_NUMPY_PAD_MODES = (
    "edge", "maximum", "mean", "median", "minimum", "reflect",
    "symmetric", "wrap",
)
_PAD_MODE_ALIASES = {
    "zeros": ("constant", {"constant_values": 0.0}),
    "ones": ("constant", {"constant_values": 1.0}),
    "torch.replicate": ("edge", {}),
    "torch.reflect": ("reflect", {}),
    "torch.circular": ("wrap", {}),
}


def _raw_pad_constant(value, clip, range):
    """Map a *normalized-space* constant padding value into raw space.

    Wire-format pipelines pad un-normalized values on the host; the
    device-side clip+scale must map the padding back onto the configured
    normalized constant, so the raw constant is the inverse normalization
    (clamped into the clip interval, which the normalization saturates
    anyway)."""
    rmin, rmax = range
    lo, hi = clip
    c = (value - rmin) / (rmax - rmin)
    return float(min(max(c, lo), hi))


def _pad_arrays(img1, img2, flow, valid, meta, pad_h, pad_w, mode, args):
    """Pad one NHWC sample batch by ``pad_h=(top, bottom)`` /
    ``pad_w=(left, right)``: images with ``mode``, flow/valid always
    zero-padded (padded pixels are invalid), metadata extents shifted."""
    ph1, ph2 = pad_h
    pw1, pw2 = pad_w

    pad4 = ((0, 0), (ph1, ph2), (pw1, pw2), (0, 0))
    pad3 = ((0, 0), (ph1, ph2), (pw1, pw2))

    img1 = np.pad(img1, pad4, mode=mode, **args)
    img2 = np.pad(img2, pad4, mode=mode, **args)

    if flow is not None:
        flow = np.pad(flow, pad4, mode="constant", constant_values=0)
        valid = np.pad(valid, pad3, mode="constant", constant_values=False)

    # new Metadata objects — sources may hand out the same instances on
    # every access (e.g. wrap_single), so in-place shifts would accumulate
    meta = [
        replace(
            m,
            original_extents=(
                (m.original_extents[0][0] + ph1, m.original_extents[0][1] + ph1),
                (m.original_extents[1][0] + pw1, m.original_extents[1][1] + pw1),
            ),
        )
        for m in meta
    ]

    return img1, img2, flow, valid, meta


class Padding:
    type = None

    @classmethod
    def _typecheck(cls, cfg):
        if cfg["type"] != cls.type:
            raise ValueError(f"invalid padding type '{cfg['type']}', expected '{cls.type}'")

    def get_config(self):
        raise NotImplementedError

    def apply(self, img1, img2, flow, valid, meta):
        raise NotImplementedError

    def __call__(self, img1, img2, flow, valid, meta):
        return self.apply(img1, img2, flow, valid, meta)

    def raw_variant(self, clip, range):
        """Variant for un-normalized (wire-format) pipelines.

        Constant padding values are defined in *normalized* space
        ("zeros" pads with normalized 0); when normalization moves into
        the jitted step, the host pads raw values, so constants must be
        mapped through the inverse normalization. Non-constant modes
        (edge/reflect/...) are value-independent and pass through.
        """
        return self


class ModuloPadding(Padding):
    """Pad images to a multiple of ``size`` with configurable alignment.

    Flow/valid are always zero-padded (padded pixels are invalid);
    ``meta.original_extents`` shifts so outputs can be cropped back.
    ``torch.replicate``/``torch.reflect``/``torch.circular`` mode aliases
    from reference configs map onto the equivalent numpy modes.
    """

    type = "modulo"

    _NUMPY_MODES = _NUMPY_PAD_MODES
    _ALIASES = _PAD_MODE_ALIASES

    @classmethod
    def from_config(cls, cfg):
        cls._typecheck(cfg)

        size = [int(x) for x in cfg["size"]]
        if len(size) != 2:
            raise ValueError("expected list/tuple of 2 integers for attribute 'size'")

        return cls(
            cfg["mode"],
            size,
            align_hz=cfg.get("align-horizontal", "left"),
            align_vt=cfg.get("align-vertical", "top"),
        )

    def __init__(self, mode, size, align_hz="left", align_vt="top"):
        super().__init__()

        if mode not in self._NUMPY_MODES and mode not in self._ALIASES:
            raise ValueError(f"invalid padding mode: {mode}")
        if align_hz not in ("left", "center", "right"):
            raise ValueError(f"invalid horizontal alignment for padding: {align_hz}")
        if align_vt not in ("bottom", "center", "top"):
            raise ValueError(f"invalid vertical alignment for padding: {align_vt}")

        self.mode = mode
        self.size = size
        self.align_hz = align_hz
        self.align_vt = align_vt

    def get_config(self):
        return {
            "type": self.type,
            "mode": self.mode,
            "size": self.size,
            "align-horizontal": self.align_hz,
            "align-vertical": self.align_vt,
        }

    def _split(self, total, align_lo_name, align):
        if align == align_lo_name:
            return 0, total
        if align == "center":
            return total // 2, total - total // 2
        return total, 0

    def raw_variant(self, clip, range):
        mode, args = self._ALIASES.get(self.mode, (self.mode, {}))
        if "constant_values" not in args:
            return self
        out = copy.copy(self)
        # raw-space constant, clipped into the clip interval so the
        # device-side clip+scale maps it back to the normalized constant
        out._raw_constant = _raw_pad_constant(
            args["constant_values"], clip, range)
        return out

    def apply(self, img1, img2, flow, valid, meta):
        mode, args = self._ALIASES.get(self.mode, (self.mode, {}))
        raw = getattr(self, "_raw_constant", None)
        if raw is not None and "constant_values" in args:
            args = dict(args, constant_values=raw)

        _, h, w, _ = img1.shape
        new_h = -(-h // self.size[1]) * self.size[1]
        new_w = -(-w // self.size[0]) * self.size[0]
        if (new_h, new_w) == (h, w):
            # already aligned: np.pad with zero widths still copies every
            # array — measured ~10 ms/sample of pure memcpy in the loader
            return img1, img2, flow, valid, meta

        pad_h = self._split(new_h - h, "top", self.align_vt)
        pad_w = self._split(new_w - w, "left", self.align_hz)

        return _pad_arrays(img1, img2, flow, valid, meta, pad_h, pad_w,
                           mode, args)


_PADDINGS = {ModuloPadding.type: ModuloPadding}


def _build_padding(cfg):
    if cfg is None:
        return None
    return _PADDINGS[cfg["type"]].from_config(cfg)


class ShapeBuckets:
    """Canonical evaluation shapes: quantize mixed per-sample resolutions
    up to a small fixed set so a whole benchmark sweep compiles at most
    ``len(sizes)`` programs instead of one per distinct padded shape.

    Each sample is padded (bottom/right, so ``meta.original_extents``
    stays put) from its modulo-padded size up to the smallest configured
    bucket that fits; the ``valid`` mask is extended with ``False`` over
    the padded pixels, so masked metrics (EPE, Fl-all, the masked losses)
    provably never see them. An empty ``sizes`` list is the pure
    *grouping* policy: no quantization pad, the loader still groups
    same-shape samples into full batches (``Loader(group_by_shape=True)``)
    so mixed-resolution sets stop degrading to batch 1.

    Assignment is deterministic: buckets are ordered by (area, height,
    width) and the first one that fits both dimensions wins; samples
    larger than every bucket keep their own shape (they batch among
    themselves and compile their own program, like before).
    """

    def __init__(self, sizes=(), mode="zeros"):
        if mode not in _NUMPY_PAD_MODES and mode not in _PAD_MODE_ALIASES:
            raise ValueError(f"invalid bucket padding mode: {mode}")

        parsed = []
        for hw in sizes:
            h, w = (int(x) for x in hw)
            if h <= 0 or w <= 0:
                raise ValueError(f"invalid bucket size {hw!r}")
            parsed.append((h, w))

        self.sizes = sorted(set(parsed), key=lambda s: (s[0] * s[1], s))
        self.mode = mode

    @classmethod
    def from_config(cls, cfg):
        """``None`` | spec string (see :meth:`parse`) | mapping with
        ``sizes`` (list of [H, W]) and optional ``mode``."""
        if cfg is None:
            return None
        if isinstance(cfg, str):
            return cls.parse(cfg)
        if isinstance(cfg, (list, tuple)):
            return cls(cfg)
        return cls(cfg.get("sizes", ()), cfg.get("mode", "zeros"))

    @classmethod
    def parse(cls, spec):
        """CLI/env spec: ``'group'`` (shape grouping only) or a
        comma-separated ``HxW`` list, e.g. ``'384x1280,448x1024'``."""
        spec = spec.strip()
        if not spec:
            return None
        if spec in ("group", "shape"):
            return cls(())
        sizes = []
        for part in spec.split(","):
            try:
                h, w = part.strip().lower().split("x")
                sizes.append((int(h), int(w)))
            except ValueError:
                raise ValueError(
                    f"invalid bucket spec '{part.strip()}' in '{spec}': "
                    "expected 'group' or a comma-separated HxW list "
                    "like '384x1280,448x1024'") from None
        return cls(sizes)

    def get_config(self):
        return {"sizes": [list(s) for s in self.sizes], "mode": self.mode}

    def describe(self):
        if not self.sizes:
            return "group-by-shape (no canonical sizes)"
        return ", ".join(f"{h}x{w}" for h, w in self.sizes)

    def assign(self, h, w):
        """Smallest-area bucket fitting an (h, w) sample, or None when no
        bucket fits (the sample keeps its own shape)."""
        for bh, bw in self.sizes:
            if bh >= h and bw >= w:
                return bh, bw
        return None

    def check_compatible(self, padding):
        """Every bucket must satisfy the model's modulo constraint, else
        the quantized shapes would be rejected by the network's pyramid —
        fail at config time with the offending bucket named."""
        if padding is None or not isinstance(padding, ModuloPadding):
            return
        mw, mh = padding.size  # config order: (w multiple, h multiple)
        for bh, bw in self.sizes:
            if bh % mh or bw % mw:
                raise ValueError(
                    f"bucket {bh}x{bw} is not a multiple of the input "
                    f"padding size {mh}x{mw} (h x w): the model would "
                    "reject the quantized shape")

    def raw_variant(self, clip, range):
        """Variant for un-normalized (wire-format) pipelines: constant
        padding values translate into raw space (see ModuloPadding)."""
        mode, args = _PAD_MODE_ALIASES.get(self.mode, (self.mode, {}))
        if "constant_values" not in args:
            return self
        out = ShapeBuckets(self.sizes, self.mode)
        out._raw_constant = _raw_pad_constant(
            args["constant_values"], clip, range)
        return out

    def pad_image(self, img, bucket):
        """Pad a single HWC (or NHWC) image up to ``bucket`` bottom/right.

        The serving admission path pads each request's images directly to
        their assigned bucket (``check_compatible`` guarantees buckets
        satisfy the model's modulo constraint, so no intermediate modulo
        pad is needed); on a ``raw_variant`` the constant translates into
        raw space exactly like the batch path.
        """
        h, w = img.shape[-3], img.shape[-2]
        bh, bw = bucket
        if (h, w) == (bh, bw):
            return img

        mode, args = _PAD_MODE_ALIASES.get(self.mode, (self.mode, {}))
        raw = getattr(self, "_raw_constant", None)
        if raw is not None and "constant_values" in args:
            args = dict(args, constant_values=raw)

        pad = [(0, 0)] * (img.ndim - 3) + [(0, bh - h), (0, bw - w), (0, 0)]
        return np.pad(img, pad, mode=mode, **args)

    def pad(self, img1, img2, flow, valid, meta):
        """Pad one sample batch up to its bucket (no-op when no bucket
        fits or the sample already sits on one)."""
        _, h, w, _ = img1.shape
        bucket = self.assign(h, w)
        if bucket is None or bucket == (h, w):
            return img1, img2, flow, valid, meta

        mode, args = _PAD_MODE_ALIASES.get(self.mode, (self.mode, {}))
        raw = getattr(self, "_raw_constant", None)
        if raw is not None and "constant_values" in args:
            args = dict(args, constant_values=raw)

        bh, bw = bucket
        return _pad_arrays(img1, img2, flow, valid, meta,
                           (0, bh - h), (0, bw - w), mode, args)

    def __call__(self, img1, img2, flow, valid, meta):
        return self.pad(img1, img2, flow, valid, meta)


class InputSpec:
    """Model input contract: clip range, value range, optional padding."""

    @classmethod
    def from_config(cls, cfg):
        cfg = cfg if cfg is not None else {}

        clip = [float(x) for x in cfg.get("clip", (0, 1))]
        if len(clip) != 2:
            raise ValueError("invalid value for 'clip', expected list/tuple of two floats")

        range_ = cfg.get("range", (-1, 1))
        if len(range_) != 2:
            raise ValueError("invalid value for 'range', expected list/tuple of two floats")

        return cls(clip, range_, _build_padding(cfg.get("padding")))

    def __init__(self, clip=(0.0, 1.0), range=(-1.0, 1.0), padding=None):
        self.clip = clip
        self.range = range
        self.padding = padding

    def get_config(self):
        return {
            "clip": self.clip,
            "range": self.range,
            "padding": self.padding.get_config() if self.padding is not None else None,
        }

    def apply(self, source, normalize=True, buckets=None):
        """Wrap ``source``; ``normalize=False`` defers the clip/range
        scaling to the device (wire-format pipelines). ``buckets`` (a
        ShapeBuckets) quantizes each sample's padded size up to a
        canonical bucket for recompile-free mixed-resolution batching."""
        return Input(source, self.clip, self.range, self.padding,
                     normalize=normalize, buckets=buckets)

    def wrap_single(self, img1, img2, flow=None, valid=None, seq=0, dsid="custom"):
        """Wrap one unbatched image pair as a one-sample input source."""
        img1 = img1[None]
        img2 = img2[None]
        if flow is not None:
            flow = flow[None]
            valid = valid[None]

        meta = [
            Metadata(
                valid=True,
                dataset_id=dsid,
                sample_id=SampleId(
                    format="{dsid}/{seq}/{id}",
                    img1=SampleArgs([], {"dsid": dsid, "seq": seq, "id": 1}),
                    img2=SampleArgs([], {"dsid": dsid, "seq": seq, "id": 2}),
                ),
                original_extents=((0, img1.shape[1]), (0, img1.shape[2])),
            )
        ]

        return self.apply([(img1, img2, flow, valid, meta)])


class Input:
    """Applies clip + range scaling + padding over a Collection.

    With ``normalize=False`` the clip/range scaling is skipped — the
    wire-format path applies it inside the jitted step instead
    (``models.wire.WireFormat.decode``) — and constant padding values
    are translated into raw space so device-side normalization maps the
    padding back onto the configured normalized constant.
    """

    def __init__(self, source, clip=(0.0, 1.0), range=(-1.0, 1.0),
                 padding=None, normalize=True, buckets=None):
        self.source = source
        self.clip = clip
        self.range = range
        self.normalize = normalize
        self.padding = padding
        if padding is not None and not normalize:
            self.padding = padding.raw_variant(clip, range)
        if buckets is not None:
            buckets.check_compatible(padding)
            if not normalize:
                buckets = buckets.raw_variant(clip, range)
        self.buckets = buckets

    def __getitem__(self, index):
        img1, img2, flow, valid, meta = self.source[index]

        if self.normalize:
            lo, hi = self.clip
            rmin, rmax = self.range

            img1 = (rmax - rmin) * np.clip(img1, lo, hi) + rmin
            img2 = (rmax - rmin) * np.clip(img2, lo, hi) + rmin

        if self.padding is not None:
            img1, img2, flow, valid, meta = self.padding(img1, img2, flow, valid, meta)

        if self.buckets is not None:
            img1, img2, flow, valid, meta = self.buckets(img1, img2, flow, valid, meta)

        return img1, img2, flow, valid, meta

    def __len__(self):
        return len(self.source)

    def jax(self, flow=True, wire=None):
        return JaxAdapter(self, flow, wire=wire)

    # alias so call sites written against the reference's `.torch()` read
    # naturally during porting
    def adapter(self, flow=True):
        return JaxAdapter(self, flow)


class JaxAdapter:
    """Validates batches and normalizes them to NHWC float32 numpy.

    Device placement happens later (in the train/eval step or loader
    prefetch), so this stays a pure host-side transform. Non-finite images
    or flow, or empty valid masks, mark the whole sample batch invalid via
    ``meta.valid`` — the trainer skips those batches with a warning, exactly
    like the reference (src/models/input.py:252-299).
    """

    def __init__(self, source, flow=True, validate=True, wire=None):
        self.source = source
        self.flow = flow
        self.validate = validate
        self.wire = wire
        self.log = utils.logging.Logger("data:jax-adapter")

    def __getitem__(self, index):
        img1, img2, flow, valid, meta = self.source[index]

        if self.validate:
            self._validate_images(img1, img2, meta)

        if self.wire is not None:
            # wire compression of the images happens here, inside the
            # loader workers: the compact form is what crosses thread /
            # process / device boundaries. Flow and valid stay exact for
            # host consumers (metrics, inspector); their wire compression
            # is applied at device-put time (WireFormat.encode_batch).
            img1 = self.wire.encode_image(img1)
            img2 = self.wire.encode_image(img2)
        else:
            img1 = np.ascontiguousarray(img1, dtype=np.float32)
            img2 = np.ascontiguousarray(img2, dtype=np.float32)

        if not self.flow:
            return img1, img2, None, None, meta

        assert flow is not None and valid is not None

        if self.validate:
            self._validate_flow(flow, valid, meta)

        flow = np.nan_to_num(flow, nan=0.0, posinf=FLOW_INF, neginf=-FLOW_INF)
        flow = np.clip(flow, -FLOW_INF, FLOW_INF)

        flow = np.ascontiguousarray(flow, dtype=np.float32)
        valid = np.ascontiguousarray(valid, dtype=bool)

        return img1, img2, flow, valid, meta

    def _mark_invalid(self, meta, which, bad_mask):
        for i, bad in enumerate(bad_mask):
            if bad:
                self.log.warn(f"{which}: {meta[i].sample_id}")
        for m in meta:
            m.valid = False

    def _validate_images(self, img1, img2, meta):
        bad1 = ~np.all(np.isfinite(img1), axis=(1, 2, 3))
        if bad1.any():
            self._mark_invalid(meta, "non-finite values in img1 detected", bad1)

        bad2 = ~np.all(np.isfinite(img2), axis=(1, 2, 3))
        if bad2.any():
            self._mark_invalid(meta, "non-finite values in img2 detected", bad2)

    def _validate_flow(self, flow, valid, meta):
        no_valid = ~np.any(valid, axis=(1, 2))
        if no_valid.any():
            self._mark_invalid(meta, "sample contains no valid flow pixels", no_valid)

        nonfinite = np.array(
            [not np.all(np.isfinite(flow[b][valid[b]])) for b in range(flow.shape[0])]
        )
        if nonfinite.any():
            self._mark_invalid(meta, "non-finite values in flow detected", nonfinite)

    def __len__(self):
        return len(self.source)

    def loader(self, batch_size=1, shuffle=False, num_workers=4, drop_last=False,
               seed=None, shard=None, procs=None, group_by_shape=False,
               retries=None, bad_sample_budget=None):
        # no **kwargs catch-all: unknown loader arguments (typos in env
        # configs) must fail loudly instead of being silently dropped
        return Loader(self, batch_size, shuffle, num_workers, drop_last, seed,
                      shard, procs, group_by_shape, retries,
                      bad_sample_budget)


class _Assembly:
    """One batch assembled in place: four arrays allocated once, each
    sample's rows copied once, straight to where they will lie.

    ``counts`` are the rows each sample of the chunk brings. With
    ``shuffle`` the constructor draws the in-batch order from ``rng``, one
    ``permutation(rows)`` a batch and none for a batch of a single row, so
    batches are to be made in batch order. Concatenated row ``c`` (sample
    ``j``'s row ``i`` is ``c = counts[:j].sum() + i``) lands at the row
    ``p`` with ``perm[p] == c``, which is where ``np.concatenate`` followed
    by ``[perm]`` would leave it. The arrays are fresh ``np.empty`` ones a
    batch, made by the first sample placed from its frames and dtypes, and
    never written again once ``batch()`` has handed them out. ``place``
    may run on several threads at once, a sample each: samples write
    disjoint rows, so only the allocation takes the lock.
    """

    def __init__(self, counts, shuffle=False, rng=None):
        self.counts = [int(c) for c in counts]
        starts = np.concatenate(([0], np.cumsum(self.counts))).astype(int)
        rows = np.arange(starts[-1])
        if shuffle and len(rows) > 1:
            rng = rng if rng is not None else np.random
            rows[rng.permutation(len(rows))] = np.arange(len(rows))
        self.rows = [rows[a:b] for a, b in zip(starts, starts[1:])]
        self.arrays = None
        self.meta = [None] * len(rows)
        # each sample's frame shape and metadata, for the mixed-shapes
        # check in chunk order
        self.frames = [None] * len(self.counts)
        self._lock = threading.Lock()

    def place(self, j, sample):
        """Copy sample ``j`` of the chunk into its rows."""
        img1, img2, flow, valid, meta = sample
        if img1.shape[0] != self.counts[j]:
            raise ValueError(
                f"cannot place a sample of {img1.shape[0]} row(s) in a "
                f"batch laid out for {self.counts[j]} an index: the thread "
                "pool draws the in-batch order when a batch is submitted, "
                "from the rows the iteration's first sample had — use "
                "num_workers=0 for sources whose samples differ in rows")
        if flow is None:
            valid = None
        arrays = (img1, img2, flow, valid)
        self.frames[j] = (img1.shape[1:], meta)

        with self._lock:
            if self.arrays is None:
                n = len(self.meta)
                self.arrays = tuple(
                    None if a is None else np.empty((n,) + a.shape[1:], a.dtype)
                    for a in arrays)
        if self.arrays[0].shape[1:] != img1.shape[1:]:
            return      # mixed shapes: batch() raises, in chunk order

        for i, row in enumerate(self.rows[j]):
            for dst, src in zip(self.arrays, arrays):
                if dst is not None:
                    dst[row] = src[i]
            self.meta[row] = meta[i]

    def batch(self):
        """The assembled ``(img1, img2, flow, valid, meta)``, once every
        sample is placed; mixed shapes raise here."""
        def describe(frame, meta):
            ds = meta[0].dataset_id if meta and hasattr(
                meta[0], "dataset_id") else "<unknown dataset>"
            return f"{frame[0]}x{frame[1]} (dataset '{ds}')"

        for frame, meta in self.frames[1:]:
            if frame != self.frames[0][0]:
                raise ValueError(
                    "cannot batch samples of mixed shapes: "
                    f"{describe(*self.frames[0])} vs "
                    f"{describe(frame, meta)} — use shape buckets "
                    "(--buckets / RMD_EVAL_BUCKETS / loader "
                    "group_by_shape=True) or batch size 1 for "
                    "mixed-resolution datasets")
        return (*self.arrays, self.meta)


def collate(samples, shuffle=False, rng=None):
    """Assemble pre-batched samples into one global batch, one copy each.

    Sources may return more than one sample each (fw/bw pairing); the global
    batch is the concatenation, optionally shuffled within the batch so
    paired samples don't always sit next to each other. This is the serial
    form of what the loader's thread pool does a sample a worker: allocate
    once, place every sample at its (shuffled) rows (:class:`_Assembly`).
    """
    batch = _Assembly([s[0].shape[0] for s in samples], shuffle, rng)
    for j, sample in enumerate(samples):
        batch.place(j, sample)
    return batch.batch()


class _DecodeFailed(Exception):
    """Wrapper distinguishing per-sample decode errors (retryable) from
    pool-level failures (fatal) on the decode-process path."""


class Loader:
    """Batching iterator over an adapter: threads or decode processes.

    Epoch order reshuffles on every ``__iter__`` when ``shuffle`` is set;
    within-batch shuffle mixes samples from pre-batched sources. The
    default transport is a thread pool (cv2/numpy release the GIL for the
    heavy work); ``procs > 0`` switches to a decode-process pool with
    shared-memory array transport (models.mpdecode) for pipelines whose
    pure-Python decode path is the bottleneck. ``procs=None`` reads
    ``RMD_LOADER_PROCS`` (0 or unset = thread pool).

    A batch is assembled in place (:class:`_Assembly`): four fresh arrays
    a batch, each sample copied once to the rows the in-batch shuffle
    gives it. The thread pool's workers do that copy themselves, each for
    the sample it fetched, and the pulling thread only draws the order
    and waits; every other path (``num_workers=0``, ``group_by_shape``,
    decode processes) places the samples one after the other on the
    pulling thread (:func:`collate`). The stream is the same either way.
    For the draw the thread pool takes the rows an index from the
    iteration's first sample, so a source has to return the same number
    of rows for every index there (a sample with another count raises).

    Shuffling uses an own Generator. Without an explicit ``seed`` it is
    derived from the global numpy RNG so run-level seeding
    (utils.seeds) still makes data order reproducible.

    ``shard=(index, count)`` restricts the loader to every count-th
    sample of the (shared-seed) epoch order — the per-process slice in
    multi-host training. All shards see the same number of batches
    (processes must step in lockstep), so ``batch_size`` here is the
    per-process size.

    ``group_by_shape`` reorders the epoch into full same-shape batches:
    samples are fetched in epoch order but buffered per (H, W) shape key
    and a batch is emitted whenever one shape's buffer fills (partial
    buffers flush at epoch end, first-seen shape first). Within a batch
    the epoch order — and with it the per-sample ``meta`` order — is
    preserved. Combined with ShapeBuckets quantization this turns a
    mixed-resolution evaluation epoch into at most ``n_buckets`` distinct
    batch shapes instead of one tiny ragged batch per resolution.
    """

    def __init__(self, source, batch_size=1, shuffle=False, num_workers=4,
                 drop_last=False, seed=None, shard=None, procs=None,
                 group_by_shape=False, retries=None, bad_sample_budget=None):
        self.source = source
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.num_workers = num_workers
        self.drop_last = drop_last
        self.shard = shard
        self.group_by_shape = bool(group_by_shape)
        if procs is None:
            procs = utils.env.get_int("RMD_LOADER_PROCS")
        self.procs = max(0, int(procs))
        if seed is None:
            seed = int(np.random.randint(0, 2**31 - 1))
        self.rng = np.random.default_rng(seed)

        # self-healing fetch: a failing sample decode is retried
        # ``retries`` times, then a neighboring sample is substituted in
        # its place (batch shapes — and with them the compiled step
        # programs — stay stable). Every substitution burns one unit of
        # the bad-sample budget; exceeding it aborts the epoch: at that
        # point the data (or its storage) is broken, not flaky.
        if retries is None:
            retries = utils.env.get_int("RMD_LOADER_RETRIES")
        self.retries = max(0, int(retries))
        if bad_sample_budget is None:
            bad_sample_budget = utils.env.get_int("RMD_BAD_SAMPLE_BUDGET")
        self.bad_sample_budget = max(0, int(bad_sample_budget))
        self._bad_samples = 0
        self._bad_lock = threading.Lock()

    def _note_bad_sample(self, index, error):
        from .. import telemetry, utils

        if isinstance(error, _DecodeFailed):
            error = error.__cause__
        if self.bad_sample_budget <= 0:
            # budget 0 = healing off: the original error propagates as-is
            raise error
        with self._bad_lock:
            self._bad_samples += 1
            bad = self._bad_samples
        utils.logging.Logger("data:loader").warn(
            f"sample {index} failed to decode after {self.retries + 1} "
            f"attempt(s) ({type(error).__name__}: {error}); substituting a "
            f"neighbor ({bad}/{self.bad_sample_budget} bad-sample budget)")
        telemetry.get().emit("bad_sample", index=int(index),
                             error=f"{type(error).__name__}: {error}",
                             bad_samples=bad)
        if bad > self.bad_sample_budget:
            raise RuntimeError(
                f"bad-sample budget exceeded ({bad} > "
                f"{self.bad_sample_budget}): the input data is "
                "persistently failing to decode") from error

    def _source_item(self, index):
        """``source[index]``, with the wall seconds it took written on the
        sample's metadata (``fetch_s``): what one worker pays for one
        sample, which the step that consumes the batch reports."""
        t0 = time.perf_counter()
        sample = self.source[index]
        seconds = time.perf_counter() - t0
        for m in sample[4]:
            m.fetch_s = seconds
        return sample

    def _fetch(self, index, fetch=None, retry_on=Exception):
        """``source[index]`` with bounded retry, then substitution.

        ``fetch`` overrides the raw per-index fetch (the decode-process
        path goes through the pool); only ``retry_on`` exceptions count
        as per-sample decode failures — anything else (pool breakage,
        timeouts) propagates immediately. Deterministic neighbor
        substitution keeps batch shapes (and compiled programs) stable;
        repeated samples are harmless to training, unlike a mid-run
        crash.
        """
        index = int(index)
        fetch = fetch if fetch is not None else self._source_item
        last = None
        for _ in range(self.retries + 1):
            try:
                return fetch(index)
            except retry_on as e:  # injected/IO decode failures
                last = e
        self._note_bad_sample(index, last)

        n = len(self.source)
        for k in range(1, min(n, 8)):
            sub = (index + k) % n
            try:
                return fetch(sub)
            except retry_on as e:
                self._note_bad_sample(sub, e)
        raise RuntimeError(
            f"sample {index} and every substitution candidate failed to "
            "decode") from last

    def _pool_result(self, pool, seq, index):
        """Decode-pool result with the same retry/substitute discipline.

        The first attempt consumes the already-pipelined result; retries
        and substitutions go through a blocking submit+result round trip
        (only the failing sample loses pipelining). Pool-level failures
        (worker respawn exhaustion, wedged-pipeline timeouts) are not
        per-sample problems and propagate unretried.
        """
        from .mpdecode import PoolBroken

        state = {"first": True}

        def once(i):
            s = seq if state.pop("first", False) and i == index \
                else pool.submit(i)
            try:
                return pool.result(s)
            except (TimeoutError, PoolBroken):
                raise
            except Exception as e:  # noqa: BLE001 - worker decode error
                raise _DecodeFailed(e) from e

        try:
            return self._fetch(index, fetch=once, retry_on=_DecodeFailed)
        except _DecodeFailed as e:  # pragma: no cover - unwrapped below
            raise e.__cause__

    def _shard_len(self):
        n = len(self.source)
        if self.shard is None:
            return n
        index, count = self.shard
        # every shard gets the same length: floor, so trailing samples
        # that not all shards have are dropped
        return n // count

    def __len__(self):
        n = self._shard_len()
        if self.drop_last:
            return n // self.batch_size
        return -(-n // self.batch_size)

    def _order(self):
        order = self.rng.permutation(len(self.source)) if self.shuffle \
            else np.arange(len(self.source))

        if self.shard is not None:
            index, count = self.shard
            order = order[index::count][: self._shard_len()]
        return order

    def _batches(self):
        order = self._order()

        for start in range(0, len(order), self.batch_size):
            chunk = order[start : start + self.batch_size]
            if self.drop_last and len(chunk) < self.batch_size:
                return
            yield chunk

    def __iter__(self):
        if self.group_by_shape:
            yield from self._iter_grouped()
            return

        if self.procs > 0:
            yield from self._iter_procs()
            return

        if self.num_workers <= 0:
            for chunk in self._batches():
                samples = [self._fetch(i) for i in chunk]
                yield collate(samples, self.shuffle, self.rng)
            return

        with concurrent.futures.ThreadPoolExecutor(self.num_workers) as pool:
            # pipeline: submit the next batch while the consumer works.
            # A worker fetches its sample and copies it into the batch's
            # arrays, at the rows the in-batch order gives it; this thread
            # draws that order when it submits the chunk (the draws come
            # in batch order, as when ``collate`` made them) and waits.
            batches = self._batches()
            chunk = next(batches, None)
            if chunk is None:
                return
            # the draw needs the batch's rows: a source returns the same
            # number an index, which the iteration's first sample shows
            head = pool.submit(self._fetch, chunk[0]).result()
            per_index = head[0].shape[0]
            pending = []

            def fetch_into(batch, j, index):
                batch.place(j, self._fetch(index))

            def submit(chunk, fetched=()):
                batch = _Assembly([per_index] * len(chunk), self.shuffle,
                                  self.rng)
                for j, sample in enumerate(fetched):
                    batch.place(j, sample)
                pending.append((batch, [
                    pool.submit(fetch_into, batch, j, i)
                    for j, i in list(enumerate(chunk))[len(fetched):]]))

            submit(chunk, [head])
            del head
            for chunk in itertools.islice(batches, 1):
                submit(chunk)
            while pending:
                batch, futures = pending.pop(0)
                for f in futures:
                    f.result()
                for chunk in itertools.islice(batches, 1):
                    submit(chunk)
                yield batch.batch()

    def _iter_samples(self):
        """Single samples in epoch order, decode pipelined a window ahead
        (threads, decode processes, or synchronous per ``procs`` /
        ``num_workers`` — same transports as the batch path)."""
        order = self._order()

        if self.procs > 0:
            from . import mpdecode

            pool = mpdecode.DecodePool(self.source, self.procs)
            try:
                it = iter(order)
                pending = []

                def submit_next():
                    i = next(it, None)
                    if i is not None:
                        pending.append((pool.submit(int(i)), int(i)))

                for _ in range(max(2 * self.procs, 4)):
                    submit_next()
                while pending:
                    sample, shm = self._pool_result(pool, *pending.pop(0))
                    # copy out of shared memory immediately: grouped
                    # samples can sit in a bucket buffer for a while, and
                    # segments must not pile up until the batch flushes
                    img1, img2, flow, valid, meta = sample
                    sample = (np.copy(img1), np.copy(img2),
                              None if flow is None else np.copy(flow),
                              None if valid is None else np.copy(valid),
                              meta)
                    shm.close()
                    shm.unlink()
                    submit_next()
                    yield sample
            finally:
                pool.shutdown()
            return

        if self.num_workers <= 0:
            for i in order:
                yield self._fetch(i)
            return

        with concurrent.futures.ThreadPoolExecutor(self.num_workers) as pool:
            it = iter(order)
            pending = []

            def submit_next():
                i = next(it, None)
                if i is not None:
                    pending.append(pool.submit(self._fetch, int(i)))

            for _ in range(max(2 * self.num_workers, 2 * self.batch_size)):
                submit_next()
            while pending:
                sample = pending.pop(0).result()
                submit_next()
                yield sample

    def _iter_grouped(self):
        """Shape-grouping mode: buffer fetched samples per (H, W) key and
        emit a full batch whenever one shape's buffer fills; partial
        buffers flush at epoch end in first-seen order (dropped under
        ``drop_last``). Epoch order is preserved within each group, so
        per-sample ``meta`` order within a batch is stable."""
        groups = {}
        seen = []

        for sample in self._iter_samples():
            key = sample[0].shape[1:3]
            if key not in groups:
                groups[key] = []
                seen.append(key)
            buf = groups[key]
            buf.append(sample)
            if sum(s[0].shape[0] for s in buf) >= self.batch_size:
                groups[key] = []
                yield collate(buf, self.shuffle, self.rng)

        if not self.drop_last:
            for key in seen:
                if groups[key]:
                    yield collate(groups[key], self.shuffle, self.rng)

    def _iter_procs(self):
        """Decode-process path: same two-batch pipelining as the thread
        pool, with samples crossing back through shared memory. Segments
        are released right after collate copies out of them (its one
        copy, into the batch's arrays)."""
        from . import mpdecode

        pool = mpdecode.DecodePool(self.source, self.procs)
        try:
            pending = []
            batches = self._batches()

            def submit_next():
                chunk = next(batches, None)
                if chunk is not None:
                    pending.append([(pool.submit(i), int(i)) for i in chunk])

            submit_next()
            submit_next()
            while pending:
                seqs = pending.pop(0)
                samples, segments = [], []
                for seq, index in seqs:
                    sample, shm = self._pool_result(pool, seq, index)
                    samples.append(sample)
                    segments.append(shm)
                submit_next()
                batch = collate(samples, self.shuffle, self.rng)
                for shm in segments:
                    shm.close()
                    shm.unlink()
                yield batch
        finally:
            pool.shutdown()
