"""On-device synthetic scenario generator: layered scenes with exact flow.

AutoFlow-style synthetic data rendered by XLA instead of loaded from disk:
each scene is a textured background plus ``layers`` random convex polygons
and ellipses, every element carrying a sampled affine motion (translation,
spin, zoom about its center). Because the motion model is closed-form, the
dense optical flow between consecutive frames is *exact* — and so is the
occlusion reasoning: a pixel's flow is the affine motion of the topmost
layer covering it, and the pixel is valid iff the same layer is still the
topmost one at its landing position in the next frame.

Three consumers share the renderer:

- ``Synth`` — a ``data/config.py`` Collection (``type: synth``) that
  trains end-to-end like any dataset, with no disk or decode cost (the
  host pipeline just replays the generator on CPU; the samples are fully
  determined by ``(seed, index)``).
- ``render_sequence`` — coherent multi-frame motion for the streaming
  video path: layers move along constant affine velocity, so warm-start
  runs get realistic temporal coherence instead of constant-shift toys.
- ``perturb`` / ``perturbation_suite`` — standing robustness eval suites
  (fog / blur / noise / low-light at graded severities) over the same
  underlying scenes, with the exact valid masks preserved so metrics
  stay masked.

Values are [0, 1] float32 RGB on the host-collection contract; flow is
(x, y) pixels; valid is bool.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..utils import env as _env
from .collection import Collection, Metadata, SampleArgs, SampleId

PERTURBATIONS = ("fog", "blur", "noise", "low-light")


def _draw_layers(key, h, w, layers, motion, spin, zoom):
    """Per-layer scene parameters, stacked over the leading axis."""
    r_lo, r_hi = 0.08 * min(h, w), 0.30 * min(h, w)

    def one(k):
        ks = jax.random.split(k, 10)
        c0 = jax.random.uniform(
            ks[0], (2,), minval=jnp.array([0.1 * h, 0.1 * w]),
            maxval=jnp.array([0.9 * h, 0.9 * w]))
        vel = jax.random.uniform(ks[1], (2,), minval=-motion, maxval=motion)
        om = jax.random.uniform(ks[2], (), minval=-spin, maxval=spin)
        sc = 2.0 ** jax.random.uniform(ks[3], (), minval=-zoom, maxval=zoom)
        ell = jax.random.bernoulli(ks[4])
        rad = jax.random.uniform(ks[5], (2,), minval=r_lo, maxval=r_hi)
        phi = jax.random.uniform(ks[6], (), maxval=2.0 * jnp.pi)
        prad = jax.random.uniform(ks[7], (5,), minval=r_lo, maxval=r_hi)
        color = jax.random.uniform(ks[8], (3,), minval=0.1, maxval=0.9)
        kt = jax.random.split(ks[9], 3)
        amp = jax.random.uniform(kt[0], (3,), minval=0.05, maxval=0.25)
        freq = jax.random.uniform(kt[1], (3, 2), minval=-0.15, maxval=0.15)
        phase = jax.random.uniform(kt[2], (3,), maxval=2.0 * jnp.pi)
        return dict(c0=c0, vel=vel, om=om, sc=sc, ell=ell, rad=rad, phi=phi,
                    prad=prad, color=color, amp=amp, freq=freq, phase=phase)

    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(layers, dtype=jnp.uint32))
    return jax.vmap(one)(keys)


def _texture(p, p0y, p0x):
    """Sinusoidal texture in layer-canonical coords (moves with the layer);
    ``p`` holds each pixel's own parameters, ``[h, w, 3(, 2)]``."""
    args = (2.0 * jnp.pi * (p["freq"][..., 0] * p0y[..., None]
                            + p["freq"][..., 1] * p0x[..., None])
            + p["phase"])
    return jnp.clip(p["color"] + p["amp"] * jnp.sin(args), 0.0, 1.0)


def _layer_mask(p, p0y, p0x):
    """Shape membership in canonical coords: ellipse or 5-gon half-planes."""
    dy = p0y - p["c0"][0]
    dx = p0x - p["c0"][1]
    cphi, sphi = jnp.cos(p["phi"]), jnp.sin(p["phi"])
    u = cphi * dx + sphi * dy
    v = -sphi * dx + cphi * dy
    mell = (u / p["rad"][0]) ** 2 + (v / p["rad"][1]) ** 2 <= 1.0

    ang = p["phi"] + 2.0 * jnp.pi * jnp.arange(5) / 5.0
    dist = (jnp.cos(ang)[:, None, None] * dx[None]
            + jnp.sin(ang)[:, None, None] * dy[None])
    mpoly = jnp.all(dist <= p["prad"][:, None, None], axis=0)
    return jnp.where(p["ell"], mell, mpoly)


def _pose(p, t):
    """Layer pose at frame ``t``: center and canonical->frame linear map."""
    a = p["om"] * t
    s = p["sc"] ** t
    ca, sa = jnp.cos(a), jnp.sin(a)
    lin = s * jnp.stack((jnp.stack((ca, -sa)), jnp.stack((sa, ca))))
    return p["c0"] + t * p["vel"], lin


def _frame(bg, lay, t, h, w, layers):
    """Render frame ``t``: per-pixel topmost-layer index and RGB image.

    The owner of each pixel is decided first, with its canonical
    coordinates; the texture is then evaluated once a pixel with the
    owner's parameters, not once a layer over the whole frame.
    """
    py, px = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")

    # background (index 0): translation-only motion, full coverage
    own0y = py - t * bg["vel"][0]
    own0x = px - t * bg["vel"][1]
    own = jnp.zeros((h, w), jnp.int32)

    for i in range(layers):
        p = jax.tree.map(lambda x: x[i], lay)
        c_t, lin = _pose(p, float(t))
        det = lin[0, 0] * lin[1, 1] - lin[0, 1] * lin[1, 0]
        i00, i01 = lin[1, 1] / det, -lin[0, 1] / det
        i10, i11 = -lin[1, 0] / det, lin[0, 0] / det
        dy, dx = py - c_t[0], px - c_t[1]
        p0y = p["c0"][0] + i00 * dy + i01 * dx
        p0x = p["c0"][1] + i10 * dy + i11 * dx
        mask = _layer_mask(p, p0y, p0x)
        own0y = jnp.where(mask, p0y, own0y)
        own0x = jnp.where(mask, p0x, own0x)
        own = jnp.where(mask, i + 1, own)

    # the owner's texture: row 0 of the table is the background's
    owner = {k: jnp.concatenate((bg[k][None], lay[k]))[own]
             for k in ("freq", "phase", "color", "amp")}
    return own, _texture(owner, own0y, own0x)


def _flow_and_valid(bg, lay, own_t, own_next, t, h, w, layers):
    """Exact flow frame t -> t+1 plus the occlusion-derived valid mask."""
    py, px = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")

    # background flow: pure translation
    fy = jnp.broadcast_to(bg["vel"][0], (h, w))
    fx = jnp.broadcast_to(bg["vel"][1], (h, w))

    for i in range(layers):
        p = jax.tree.map(lambda x: x[i], lay)
        c_t, _ = _pose(p, float(t))
        # frame-to-frame map is constant per layer: B = R(om) * sc
        ca, sa = jnp.cos(p["om"]), jnp.sin(p["om"])
        b00, b01 = p["sc"] * ca, -p["sc"] * sa
        b10, b11 = p["sc"] * sa, p["sc"] * ca
        dy, dx = py - c_t[0], px - c_t[1]
        lfy = c_t[0] + p["vel"][0] + b00 * dy + b01 * dx - py
        lfx = c_t[1] + p["vel"][1] + b10 * dy + b11 * dx - px
        sel = own_t == i + 1
        fy = jnp.where(sel, lfy, fy)
        fx = jnp.where(sel, lfx, fx)

    # occlusion: the landing pixel must still belong to the same layer
    ly = py + fy
    lx = px + fx
    inb = (ly >= 0) & (ly <= h - 1) & (lx >= 0) & (lx <= w - 1)
    iy = jnp.clip(jnp.round(ly).astype(jnp.int32), 0, h - 1)
    ix = jnp.clip(jnp.round(lx).astype(jnp.int32), 0, w - 1)
    valid = inb & (own_next[iy, ix] == own_t)

    flow = jnp.stack((fx, fy), axis=-1)
    return flow, valid


@functools.partial(jax.jit,
                   static_argnames=("shape", "frames", "layers"))
def render_sequence(key, shape, frames=2, layers=4, motion=8.0,
                    background_motion=2.0, spin=0.05, zoom=0.05):
    """Render a coherent-motion sequence with exact inter-frame flow.

    Returns ``(imgs [T,H,W,3], flows [T-1,H,W,2], valids [T-1,H,W])``;
    flow ``t`` maps frame ``t`` onto frame ``t+1``. Fully determined by
    ``key`` and the static arguments.
    """
    h, w = shape
    kbg, klay = jax.random.split(key)
    lay = _draw_layers(klay, h, w, layers, motion, spin, zoom)

    kb = jax.random.split(kbg, 4)
    bg = dict(
        vel=jax.random.uniform(kb[0], (2,), minval=-background_motion,
                               maxval=background_motion),
        color=jax.random.uniform(kb[1], (3,), minval=0.25, maxval=0.75),
        amp=jnp.full((3,), 0.12),
        freq=jax.random.uniform(kb[2], (3, 2), minval=-0.08, maxval=0.08),
        phase=jax.random.uniform(kb[3], (3,), maxval=2.0 * jnp.pi),
    )

    owns, imgs = [], []
    for t in range(frames):
        own, img = _frame(bg, lay, t, h, w, layers)
        owns.append(own)
        imgs.append(img)

    flows, valids = [], []
    for t in range(frames - 1):
        flow, valid = _flow_and_valid(bg, lay, owns[t], owns[t + 1],
                                      t, h, w, layers)
        flows.append(flow)
        valids.append(valid)

    return (jnp.stack(imgs).astype(jnp.float32),
            jnp.stack(flows).astype(jnp.float32),
            jnp.stack(valids))


def render_pair(key, shape, layers=4, motion=8.0, background_motion=2.0,
                spin=0.05, zoom=0.05):
    """One frame pair: ``(img1, img2, flow, valid)``."""
    imgs, flows, valids = render_sequence(
        key, shape, frames=2, layers=layers, motion=motion,
        background_motion=background_motion, spin=spin, zoom=zoom)
    return imgs[0], imgs[1], flows[0], valids[0]


# -- perturbations ----------------------------------------------------------


def _smooth_field(key, h, w):
    """Cheap smooth [0,1] field: a few random low-frequency sinusoids."""
    py, px = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    kf, kp = jax.random.split(key)
    freq = jax.random.uniform(kf, (4, 2), minval=-0.02, maxval=0.02)
    phase = jax.random.uniform(kp, (4,), maxval=2.0 * jnp.pi)
    field = jnp.zeros((h, w))
    for i in range(4):
        field = field + jnp.sin(
            2.0 * jnp.pi * (freq[i, 0] * py + freq[i, 1] * px) + phase[i])
    return 0.5 + field / 8.0


def _gaussian_blur(img, sigma, taps=11):
    """Separable gaussian blur (depthwise conv, reflect-free same padding)."""
    r = taps // 2
    x = jnp.arange(-r, r + 1, dtype=jnp.float32)
    k = jnp.exp(-0.5 * (x / jnp.maximum(sigma, 1e-3)) ** 2)
    k = k / jnp.sum(k)
    nchw = jnp.moveaxis(img, -1, 0)[None]  # 1,C,H,W
    dn = ("NCHW", "OIHW", "NCHW")
    kv = jnp.broadcast_to(k[None, None, :, None], (3, 1, taps, 1))
    kh = jnp.broadcast_to(k[None, None, None, :], (3, 1, 1, taps))
    out = jax.lax.conv_general_dilated(
        nchw, kv, (1, 1), [(r, r), (0, 0)], dimension_numbers=dn,
        feature_group_count=3)
    out = jax.lax.conv_general_dilated(
        out, kh, (1, 1), [(0, 0), (r, r)], dimension_numbers=dn,
        feature_group_count=3)
    return jnp.moveaxis(out[0], 0, -1)


def perturb(key, img, kind, severity):
    """Apply one standing perturbation to a [0,1] RGB image.

    ``kind`` is one of ``PERTURBATIONS``; ``severity`` in [0, 1]. The
    geometry (and hence flow/valid) is untouched — these are photometric
    corruptions for robustness evals with masked metrics.
    """
    severity = jnp.clip(jnp.asarray(severity, jnp.float32), 0.0, 1.0)
    h, w = img.shape[0], img.shape[1]

    if kind == "fog":
        alpha = severity * (0.35 + 0.5 * _smooth_field(key, h, w))
        return img * (1.0 - alpha[..., None]) + 0.92 * alpha[..., None]
    if kind == "blur":
        return _gaussian_blur(img, 0.4 + 2.6 * severity)
    if kind == "noise":
        return jnp.clip(
            img + 0.12 * severity * jax.random.normal(key, img.shape),
            0.0, 1.0)
    if kind == "low-light":
        dark = img * (1.0 - 0.8 * severity)
        dark = dark ** (1.0 + 0.6 * severity)  # crushed shadows
        return jnp.clip(
            dark + 0.04 * severity * jax.random.normal(key, img.shape),
            0.0, 1.0)
    raise ValueError(f"unknown perturbation '{kind}', "
                     f"expected one of {PERTURBATIONS}")


# -- collection -------------------------------------------------------------


def _host_device():
    """The host CPU device the ``Synth`` collection renders on.

    The collection is the *host* side of the input pipeline: its loader
    threads run next to a train step that owns the accelerator, and a
    sample rendered there would queue behind that step and come back
    through the host anyway. ``cmd.train.select_devices`` keeps the
    ``cpu`` platform in jax's platform list for this.
    """
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "data 'type: synth' renders its samples on the host CPU, but "
            "the jax 'cpu' platform is not available — add it to the "
            f"platform list (e.g. JAX_PLATFORMS=tpu,cpu): {e}") from e


class Synth(Collection):
    """Config-typed synthetic scene source (``type: synth``).

    Samples are fully determined by ``(seed, index)`` — reproducible
    across workers, epochs, and resumes, with zero disk or decode cost.
    ``perturb: {kind, severity}`` applies a standing corruption to both
    frames (robustness eval suites); flow and valid stay exact.
    """

    type = "synth"

    @classmethod
    def from_config(cls, path, cfg):
        cls._typecheck(cfg)
        shape = cfg.get("shape", [96, 128])
        if len(shape) != 2:
            raise ValueError("invalid synth shape, expected [height, width]")
        pert = cfg.get("perturb")
        if pert is not None and pert.get("kind") not in PERTURBATIONS:
            raise ValueError(
                f"invalid perturb kind, expected one of {PERTURBATIONS}")
        return cls(
            size=int(cfg.get("size", 64)),
            shape=(int(shape[0]), int(shape[1])),
            layers=int(cfg.get("layers",
                               _env.get_int("RMD_SYNTH_LAYERS"))),
            motion=float(cfg.get("motion", 8.0)),
            background_motion=float(cfg.get("background-motion", 2.0)),
            seed=int(cfg.get("seed", _env.get_int("RMD_SYNTH_SEED"))),
            perturb=pert,
        )

    def __init__(self, size=64, shape=(96, 128), layers=4, motion=8.0,
                 background_motion=2.0, seed=0, perturb=None):
        super().__init__()
        self.size = int(size)
        self.shape = (int(shape[0]), int(shape[1]))
        self.layers = int(layers)
        self.motion = float(motion)
        self.background_motion = float(background_motion)
        self.seed = int(seed)
        self.perturb = dict(perturb) if perturb else None

    def get_config(self):
        cfg = {
            "type": self.type,
            "size": self.size,
            "shape": list(self.shape),
            "layers": self.layers,
            "motion": self.motion,
            "background-motion": self.background_motion,
            "seed": self.seed,
        }
        if self.perturb is not None:
            cfg["perturb"] = dict(self.perturb)
        return cfg

    def __getitem__(self, index):
        if not 0 <= index < self.size:
            raise IndexError(index)

        with jax.default_device(_host_device()):
            key = jax.random.fold_in(
                jax.random.PRNGKey(self.seed), np.uint32(index))
            img1, img2, flow, valid = render_pair(
                key, self.shape, layers=self.layers, motion=self.motion,
                background_motion=self.background_motion)
            if self.perturb is not None:
                kind = self.perturb["kind"]
                sev = float(self.perturb.get("severity", 0.5))
                k1, k2 = jax.random.split(jax.random.fold_in(key, 1))
                img1 = perturb(k1, img1, kind, sev)
                img2 = perturb(k2, img2, kind, sev)

        h, w = self.shape
        meta = Metadata(
            valid=True, dataset_id="synth",
            sample_id=SampleId(f"synth-{self.seed}-{index}",
                               SampleArgs(), SampleArgs()),
            original_extents=((0, h), (0, w)),
        )
        return (np.asarray(img1)[None], np.asarray(img2)[None],
                np.asarray(flow)[None], np.asarray(valid)[None], [meta])

    def __len__(self):
        return self.size

    def description(self):
        pert = (f", {self.perturb['kind']} perturbed"
                if self.perturb is not None else "")
        return (f"synthetic scenes ({self.size} samples, "
                f"{self.shape[0]}x{self.shape[1]}, "
                f"{self.layers} layers{pert})")


def perturbation_suite(base, severities=(0.25, 0.5, 0.75)):
    """Standing robustness suites over one base ``Synth`` config.

    Returns ``{"<kind>-<severity>": Synth}`` covering every perturbation
    kind at each severity — same seed and scene set as ``base``, so EPE
    deltas isolate the corruption (masked metrics stay exact).
    """
    cfg = base.get_config()
    suites = {}
    for kind in PERTURBATIONS:
        for sev in severities:
            c = dict(cfg, perturb={"kind": kind, "severity": sev})
            c.pop("type")
            shape = c.pop("shape")
            suites[f"{kind}-{sev:g}"] = Synth(
                shape=tuple(shape),
                **{k.replace("-", "_"): v for k, v in c.items()})
    return suites
