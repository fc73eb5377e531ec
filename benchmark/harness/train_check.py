"""What a training run is held to, once its window has closed."""

import math

import numpy as np

from ..reference import common as refc
from ..reference import train as reftrain


def program_numbers(run):
    """The program's side of the comparison, from what the probe kept of
    the loop's first steps. The first gradient as the optimizer got it is
    read off Adam's first moment after one step: mu_1 = (1 - b1) g_1."""
    ctl = run["ctl"]
    b1 = reftrain.hyper(run["stage"])["betas"][0]
    mu = refc.flatten(ctl.mu1, "params")
    after = refc.flatten(ctl.params_after, "params")
    return {
        "loss": list(ctl.losses),
        "final": ctl.final0,
        "grad_norms": {k: float(np.linalg.norm(v)) / (1.0 - b1)
                       for k, v in mu.items()},
        "after": after,
    }


def check(run, verdict, limits, quant=None):
    ctl = run["ctl"]
    flat = refc.init(run["spec"], run["seed"])
    reference = reftrain.run(run["reference"], run["cell"].config["model"],
                             run["stage"], flat, ctl.batches, quant=quant)
    program = program_numbers(run)
    start = {k: np.asarray(v) for k, v in flat.items()
             if k.startswith("params/")}
    program["delta_norms"] = {
        k: float(np.linalg.norm(program["after"][k] - start[k]))
        for k in start}
    gaps, notes = reftrain.compare(program, reference)
    for name, value in gaps.items():
        verdict.hold(name, value, limits[name])
    finite = all(math.isfinite(x) for x in program["loss"])
    verdict.hold("nonfinite_losses", 0.0 if finite else 1.0, 0)
    return gaps, notes, reference
