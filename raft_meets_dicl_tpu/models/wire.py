"""Host→device wire format for input batches.

Every training batch crosses the host→device link (tens of MB at the
Things shape). This module defines how a batch crosses that boundary: images ship in a compact dtype (``f32`` raw
floats, ``bf16``, or quantized ``u8``), flow optionally in half precision,
and valid masks optionally bit-packed — and the clip/range normalization
that ``models.input.Input`` otherwise performs on the host moves inside
the jitted step (``decode``), so the host never materializes a second
normalized f32 copy and the device unpacks the wire format on the VPU
essentially for free.

Numerical contract (exercised by tests/test_wire.py):

- ``f32`` wire is exact up to float rounding of the normalization itself
  (same multiply/add, done by XLA instead of numpy): model outputs match
  the host-normalized path to ~1e-5.
- ``bf16`` wire quantizes image values to 8 mantissa bits (≤ 2^-9
  relative); on the mixed-precision models the first convolution casts to
  bf16 anyway, so effective numerics are unchanged. Flow targets ride in
  IEEE f16 (≤ 2^-11 relative, values clamped to ±6e4): loss values match
  to ~1e-2 relative, model outputs (which never see flow) to bf16 noise.
- ``u8`` wire quantizes images to 256 levels over the clip interval
  (≤ 1/510 of the clip span per value) — the coarsest, smallest format.

Wire dtypes per preset (bytes per pixel at the training contract of two
RGB images + 2-channel flow + valid):

    preset   images      flow   valid       B/px    vs f32
    f32      float32×6   f32×2  bool        33.0    1.0×
    bf16     bfloat16×6  f16×2  packed      16.125  2.05×
    u8       uint8×6     f16×2  packed      10.125  3.26×
"""

import numpy as np

# f16 finite range is ±65504; flow values beyond it only occur as the
# FLOW_INF clamp markers on invalid pixels — re-clamp so they stay finite
# (inf * 0-mask would poison the loss with NaNs)
_F16_FLOW_LIMIT = 6.0e4

_IMAGE_DTYPES = ("f32", "bf16", "u8")
_FLOW_DTYPES = ("f32", "f16")

PRESETS = {
    "f32": dict(images="f32", flow="f32", pack_valid=False),
    "bf16": dict(images="bf16", flow="f16", pack_valid=True),
    "u8": dict(images="u8", flow="f16", pack_valid=True),
}


def _bf16():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


class WireFormat:
    """Encode (host) / decode (device) contract for one batch layout.

    ``clip``/``range`` are the model's input normalization (from
    ``InputSpec``); ``decode`` applies them on device, so sources feeding
    a wire-format adapter must *not* normalize on the host
    (``InputSpec.apply(..., normalize=False)``).
    """

    @classmethod
    def from_config(cls, cfg, clip=(0.0, 1.0), range=(-1.0, 1.0)):
        """Build from a preset name ('f32'/'bf16'/'u8') or a mapping with
        explicit ``images``/``flow``/``pack-valid`` keys."""
        if cfg is None:
            return None
        if isinstance(cfg, str):
            if cfg not in PRESETS:
                raise ValueError(
                    f"unknown wire-format preset '{cfg}', "
                    f"expected one of {', '.join(PRESETS)}")
            cfg = PRESETS[cfg]
        return cls(
            images=cfg.get("images", "f32"),
            flow=cfg.get("flow", cfg.get("flow-dtype", "f32")),
            pack_valid=bool(cfg.get("pack-valid", cfg.get("pack_valid", False))),
            clip=clip, range=range,
        )

    def __init__(self, images="f32", flow="f32", pack_valid=False,
                 clip=(0.0, 1.0), range=(-1.0, 1.0)):
        if images not in _IMAGE_DTYPES:
            raise ValueError(f"invalid wire image dtype '{images}', "
                             f"expected one of {_IMAGE_DTYPES}")
        if flow not in _FLOW_DTYPES:
            raise ValueError(f"invalid wire flow dtype '{flow}', "
                             f"expected one of {_FLOW_DTYPES}")
        self.images = images
        self.flow = flow
        self.pack_valid = bool(pack_valid)
        self.clip = (float(clip[0]), float(clip[1]))
        self.range = (float(range[0]), float(range[1]))

    def get_config(self):
        return {
            "images": self.images,
            "flow": self.flow,
            "pack-valid": self.pack_valid,
        }

    def bound(self, clip, range):
        """Copy with the normalization parameters of an ``InputSpec``."""
        return WireFormat(self.images, self.flow, self.pack_valid,
                          clip=clip, range=range)

    def describe(self):
        return (f"images={self.images}, flow={self.flow}, "
                f"valid={'packed' if self.pack_valid else 'bool'}")

    def image_dtype(self):
        """The numpy dtype image arrays take on the wire (what warmup
        dummies and serving buffers must be created in)."""
        if self.images == "bf16":
            return _bf16()
        if self.images == "u8":
            return np.dtype(np.uint8)
        return np.dtype(np.float32)

    # -- host side (numpy) --------------------------------------------------

    def encode_image(self, img):
        """One un-normalized image batch → wire dtype (numpy)."""
        if self.images == "bf16":
            return np.asarray(img, _bf16())
        if self.images == "u8":
            lo, hi = self.clip
            q = (np.asarray(img, np.float32) - lo) * (255.0 / (hi - lo))
            return np.clip(np.rint(q), 0.0, 255.0).astype(np.uint8)
        return np.ascontiguousarray(img, np.float32)

    def encode_flow(self, flow):
        if flow is None or self.flow == "f32":
            return flow
        return np.clip(flow, -_F16_FLOW_LIMIT, _F16_FLOW_LIMIT).astype(
            np.float16)

    def encode_valid(self, valid):
        if valid is None or not self.pack_valid:
            return valid
        return np.packbits(np.asarray(valid, bool), axis=-1)

    def encode_batch(self, batch):
        """(img1, img2, flow, valid) with wire images → full wire tuple.

        Images are expected to already be in wire dtype (the adapter
        encodes them at decode time, inside the loader workers); this
        applies the flow/valid compression right before device placement.
        """
        img1, img2, flow, valid = batch
        return (img1, img2, self.encode_flow(flow), self.encode_valid(valid))

    def nbytes(self, batch):
        """Total bytes of a wire tuple (the per-step transfer volume)."""
        return int(sum(a.nbytes for a in batch if a is not None))

    def decode_images_host(self, img):
        """Wire image batch → normalized f32 on the *host* (numpy).

        The numpy mirror of the device-side decode, for consumers that
        need pixel values host-side (TB image dumps, eval flow images).
        """
        lo, hi = self.clip
        rmin, rmax = self.range
        if self.images == "u8":
            scale = (hi - lo) / 255.0
            x = np.asarray(img, np.float32) * scale + lo
        else:
            x = np.clip(np.asarray(img, np.float32), lo, hi)
        return (rmax - rmin) * x + rmin

    # -- device side (inside jit) -------------------------------------------

    def decode_image(self, img):
        import jax.numpy as jnp

        lo, hi = self.clip
        rmin, rmax = self.range
        if self.images == "u8":
            x = img.astype(jnp.float32) * ((hi - lo) / 255.0) + lo
        else:
            x = jnp.clip(img.astype(jnp.float32), lo, hi)
        return (rmax - rmin) * x + rmin

    def decode(self, img1, img2, flow=None, valid=None):
        """Wire tuple → (img1, img2, flow, valid) in compute dtypes.

        Runs inside the jitted train/eval step: images dequantize +
        normalize, flow widens to f32, packed valid masks unpack to bool
        at the image width.
        """
        import jax.numpy as jnp

        w = img1.shape[2]
        img1 = self.decode_image(img1)
        img2 = self.decode_image(img2)
        if flow is not None and flow.dtype != jnp.float32:
            flow = flow.astype(jnp.float32)
        if valid is not None and self.pack_valid:
            valid = jnp.unpackbits(valid, axis=-1, count=w).astype(bool)
        return img1, img2, flow, valid
