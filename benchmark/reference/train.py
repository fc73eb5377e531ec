"""The training reference: the first steps of a stage, one pair at a time.

Follows what the program's step does to the seed's first batches with the
seed's weights, in float32 and plain arithmetic: forward and loss per pair
(batch norm is frozen, so pairs are independent; the loss divides the
batch's sum by the batch's count of valid pixels), ``jax.grad`` of that,
global-norm clipping, AdamW with decoupled decay, the one-cycle rate. It
returns the numbers the comparison reads: each step's loss, the first
step's final flow, the per-leaf norm of the first gradient as the
optimizer gets it (after clipping), and the per-leaf norm of the
parameters' change after the last step.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C


def hyper(stage):
    """The stage's numbers, read from its configuration."""
    opt = stage["optimizer"]
    if opt["type"] != "adam-w":
        raise ValueError(f"reference implements adam-w, not {opt['type']}")
    p = opt.get("parameters", {})
    sched = stage["lr-scheduler"]["instance"][0]
    if sched["type"] != "one-cycle":
        raise ValueError("reference implements the one-cycle schedule")
    sp = sched["parameters"]
    total = sp["total_steps"]
    total = int(eval(total, {"__builtins__": {}})) if isinstance(total, str) \
        else int(total)
    clip = stage.get("gradient", {}).get("clip")
    return {
        "wd": float(p.get("weight_decay", 1e-2)),
        "eps": float(p.get("eps", 1e-8)),
        "betas": tuple(p.get("betas", (0.9, 0.999))),
        "max_lr": float(sp["max_lr"]), "total_steps": total,
        "pct_start": float(sp.get("pct_start", 0.3)),
        "clip": float(clip["value"]) if clip else None,
        "loss_args": dict(stage.get("loss", {}).get("arguments", {})),
    }


def run(module, model_cfg, stage, flat, batches, quant=None, flow_step=0):
    """``flat``: the seed's weights (flat path -> array); ``batches``: host
    tuples (img1, img2, flow, valid) as the loop fed them, images
    un-normalised in [0, 1]."""
    hp = hyper(stage)
    loss_args = dict(model_cfg.get("loss", {}).get("arguments", {}))
    loss_args.update(hp["loss_args"])
    inp = model_cfg.get("input", {})
    clip, rng = inp.get("clip", (0, 1)), inp.get("range", (-1, 1))

    params = {k: v for k, v in flat.items() if k.startswith("params/")}
    fixed = {k: v for k, v in flat.items() if not k.startswith("params/")}

    # weights are arguments, never closed over: as constants they would
    # make every seed a different program and no compile would be cached
    def pair(params, fixed, img1, img2, flow, valid):
        P = C.Params({**params, **fixed}, quant=quant)
        out = module.forward(P, model_cfg, C.normalize_images(img1, clip, rng),
                             C.normalize_images(img2, clip, rng))
        return module.loss_sum(out, flow, valid, loss_args), \
            module.final_flow(out)

    grad_pair = jax.jit(jax.value_and_grad(pair, has_aux=True))

    mu = {k: jnp.zeros_like(v) for k, v in params.items()}
    nu = {k: jnp.zeros_like(v) for k, v in params.items()}
    count = 0
    start = params
    result = {"loss": [], "lr": []}
    with jax.default_matmul_precision("highest"):
        for t, (img1, img2, flow, valid) in enumerate(batches):
            b = img1.shape[0]
            n_valid = max(float(np.asarray(valid).sum()), 1.0)
            total, grads, finals = 0.0, None, []
            for j in range(b):
                sl = slice(j, j + 1)
                (s, final), g = grad_pair(
                    params, fixed, jnp.asarray(np.asarray(img1[sl], np.float32)),
                    jnp.asarray(np.asarray(img2[sl], np.float32)),
                    jnp.asarray(np.asarray(flow[sl], np.float32)),
                    jnp.asarray(np.asarray(valid[sl])))
                total = total + s
                grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
                if t == flow_step:
                    finals.append(np.asarray(final))
            grads = {k: g / n_valid for k, g in grads.items()}
            result["loss"].append(float(total) / n_valid)
            if t == flow_step:
                result["final"] = np.concatenate(finals)
            if hp["clip"] is not None:
                grads, _ = C.clip_by_global_norm(grads, hp["clip"])
            if t == 0:
                result["grad_norms"] = {k: float(jnp.linalg.norm(g))
                                        for k, g in grads.items()}
            lr = C.one_cycle_lr(t, hp["max_lr"], hp["total_steps"],
                                hp["pct_start"])
            result["lr"].append(lr)
            params, mu, nu, count = C.adamw_step(
                params, grads, mu, nu, count, lr, hp["wd"],
                b1=hp["betas"][0], b2=hp["betas"][1], eps=hp["eps"])
        result["delta_norms"] = {k: float(jnp.linalg.norm(params[k] - start[k]))
                                 for k in params}
    return result


DEAD_LEAF = 1e-3   # of the median leaf's gradient norm


def worst_leaf(program, reference):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    median = float(np.median(list(reference.values())))
    worst, where = 0.0, None
    for k, ref in reference.items():
        gap = abs(program[k] - ref) / max(ref, median, 1e-30)
        if not np.isfinite(gap):
            return float("inf"), k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(program, reference):
    """The numbers compared, each a relative gap (smaller is closer)."""
    loss = max(abs(p - r) / max(abs(r), 1e-30)
               for p, r in zip(program["loss"], reference["loss"]))
    epe = np.linalg.norm(program["final"] - reference["final"], axis=-1).mean()
    mag = np.linalg.norm(reference["final"], axis=-1).mean()
    grad, grad_leaf = worst_leaf(program["grad_norms"], reference["grad_norms"])
    # Leaves whose gradient is zero analytically (a conv bias in front of
    # instance norm) move by Adam's update of rounding noise divided by its
    # own size: their change says nothing about the step and is left out.
    floor = DEAD_LEAF * float(np.median(list(reference["grad_norms"].values())))
    live = [k for k, g in reference["grad_norms"].items() if g >= floor]
    delta, delta_leaf = worst_leaf(
        {k: program["delta_norms"][k] for k in live},
        {k: reference["delta_norms"][k] for k in live})
    return {"loss_gap": float(loss), "flow_gap": float(epe / max(mag, 1e-30)),
            "grad_norm_gap": grad, "param_change_gap": delta}, \
        {"grad_leaf": grad_leaf, "delta_leaf": delta_leaf,
         "flow_magnitude_px": float(mag),
         "dead_leaves": len(reference["grad_norms"]) - len(live)}
