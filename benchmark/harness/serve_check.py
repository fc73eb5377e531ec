"""What a serving run is held to, once its window has closed: the flow of a
seeded sample of the requests it served, against the reference's forward
pass on the same images, padded to the bucket the way the configuration
says (bottom/right, with the value that normalises to zero) and cropped
back."""

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import common as refc


def reference_flow(module, model_cfg, flat, quant, img1, img2, bucket):
    """``flat`` (the weights) is an argument of the jitted call, never a
    constant of it: one compiled program serves every seed."""
    P = refc.Params(flat, quant=quant)
    inp = model_cfg.get("input", {})
    clip, rng = inp.get("clip", (0, 1)), inp.get("range", (-1, 1))
    h, w = img1.shape[:2]
    pad = ((0, 0), (0, bucket[0] - h), (0, bucket[1] - w), (0, 0))
    a = jnp.pad(refc.normalize_images(img1[None], clip, rng), pad)
    b = jnp.pad(refc.normalize_images(img2[None], clip, rng), pad)
    return module.final_flow(module.forward(P, model_cfg, a, b))[0, :h, :w]


def sample_flows(run, quants):
    """For each sampled served request: the flow it was served and the
    reference's flow at each precision of ``quants`` (None = float32)."""
    model_cfg = run["cell"].config["model"]
    flat = refc.init(run["spec"], run["seed"])
    buckets = sorted(run["buckets"], key=lambda b: b[0] * b[1])
    fns, out = {}, []
    with jax.default_matmul_precision("highest"):
        for r in run["records"]:
            if not (r.get("keep") and "flow" in r):
                continue
            img1, img2 = run["payloads"][r["shape"]][r["payload"]]
            h, w = img1.shape[:2]
            bucket = next(b for b in buckets if b[0] >= h and b[1] >= w)
            flows = []
            for q in quants:
                if (bucket, q) not in fns:
                    fns[bucket, q] = jax.jit(
                        lambda f, a, b, q=q, bucket=bucket: reference_flow(
                            run["reference"], model_cfg, f, q, a, b, bucket))
                flows.append(np.asarray(fns[bucket, q](
                    flat, jnp.asarray(img1), jnp.asarray(img2))))
            out.append((r["flow"], flows))
    return out


def relative_epe(flow, want):
    epe = np.linalg.norm(flow - want, axis=-1).mean()
    mag = np.linalg.norm(want, axis=-1).mean()
    return float(epe / max(mag, 1e-30)), float(mag)


def check(run, verdict, limits):
    pairs = [relative_epe(served, flows[0])
             for served, flows in sample_flows(run, (None,))]
    gaps = [g for g, _ in pairs]
    verdict.hold("serve_flow_gap", max(gaps) if gaps else None,
                 limits["serve_flow_gap"])
    verdict.hold("sample_missing",
                 float(sum(1 for r in run["records"] if r.get("keep"))
                       - len(gaps)), 0)
    verdict.hold("requests_failed",
                 float(run["readings"]["counted"]
                       - run["readings"]["completed"]), 0)
    return {"sampled": len(gaps), "gaps": gaps,
            "flow_magnitude_px": [m for _, m in pairs]}


def control(run, quant):
    """The control's reading for the same sample: the reference with its
    operands rounded to ``quant``, against the reference."""
    gaps = [relative_epe(flows[1], flows[0])[0]
            for _, flows in sample_flows(run, (None, quant))]
    return {"serve_flow_gap": min(gaps), "gaps": gaps}
