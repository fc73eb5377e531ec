#!/usr/bin/env python3
"""Per-op profile of the bench training step on the attached accelerator.

Captures a jax.profiler trace of the same step bench.py measures and
attributes it through graftprof (``analysis.profile``) — the one
trace-reading code path shared with ``scripts/graftprof.py``,
``/profilez`` and the telemetry report. Prints the per-module op-class
attribution plus the top XLA ops by self time. Usage:

    python scripts/profile_bench.py [N]   # N = ops to list (default 30)
"""

import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

from raft_meets_dicl_tpu.analysis import profile as prof  # noqa: E402


def capture(trace_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import raft_meets_dicl_tpu.models as models
    from raft_meets_dicl_tpu import parallel

    batch = int(os.environ.get("BENCH_BATCH", "6"))
    height = int(os.environ.get("BENCH_HEIGHT", "400"))
    width = int(os.environ.get("BENCH_WIDTH", "720"))
    iters = int(os.environ.get("BENCH_ITERS", "12"))
    model_ty = os.environ.get("BENCH_MODEL", "raft/baseline")
    # profile what bench.py measures: bf16 policy on the bench models
    model_params = {"mixed-precision": True} \
        if model_ty in ("raft/baseline", "raft/fs") or \
        model_ty.startswith("raft+dicl/ctf") else {}
    if model_ty.startswith("raft+dicl/ctf"):
        levels = int(model_ty[-1])
        model_args = {"iterations": (iters,) * levels}
        # corpus level weights, finest-last (cfg/model/raft+dicl-*.yaml)
        loss_cfg = {"type": "raft+dicl/mlseq",
                    "arguments": {"alpha": [0.23, 0.38, 0.6, 1.0][-levels:]}}
    else:
        model_args = {"iterations": iters}
        loss_cfg = {"type": "raft/sequence"}
    spec = models.load({
        "name": "bench", "id": "bench",
        "model": {"type": model_ty, "parameters": model_params},
        "loss": loss_cfg,
        "input": None,
    })

    rng = np.random.RandomState(0)
    img1 = jnp.asarray(rng.rand(batch, height, width, 3), jnp.float32)
    img2 = jnp.asarray(rng.rand(batch, height, width, 3), jnp.float32)
    flow = jnp.asarray(rng.randn(batch, height, width, 2), jnp.float32)
    valid = jnp.ones((batch, height, width), bool)

    init_args = dict(model_args)
    init_args["iterations"] = (
        (1,) * len(model_args["iterations"])
        if isinstance(model_args["iterations"], tuple) else 1)
    variables = spec.model.init(jax.random.PRNGKey(0), img1[:1], img2[:1],
                                **init_args)

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(4e-4))
    state = parallel.TrainState.create(variables, tx)
    step = parallel.make_train_step(spec.model, spec.loss, tx,
                                    model_args=model_args)

    state, aux = step(state, img1, img2, flow, valid)
    jax.block_until_ready(aux["loss"])

    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(3):
        state, aux = step(state, img1, img2, flow, valid)
    jax.block_until_ready(aux["loss"])
    dt = (time.perf_counter() - t0) / 3
    jax.profiler.stop_trace()
    print(f"step time: {dt * 1e3:.1f} ms")
    return dt


def parse(trace_dir, top_n=30):
    """Attribute the capture through graftprof and print the rollup."""
    summary = prof.attribute_trace(trace_dir, top_ops=top_n)
    print()
    print(prof.render_attribution(summary))

    ops = {}
    for m in summary["modules"]:
        for o in m["top_ops"]:
            ops[o["op"]] = ops.get(o["op"], 0.0) + o["seconds"]
    print(f"\ntop {top_n} ops by total time (3 steps):")
    for name, s in sorted(ops.items(), key=lambda kv: -kv[1])[:top_n]:
        print(f"  {s * 1e3:8.2f} ms  {name[:110]}")


if __name__ == "__main__":
    top_n = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    trace_dir = os.environ.get("TRACE_DIR", "/tmp/bench_trace")
    os.makedirs(trace_dir, exist_ok=True)
    capture(trace_dir)
    parse(trace_dir, top_n)
