"""Mosaic accepts every Pallas kernel family — checked without a chip.

Off-TPU the kernels only ever run through the Pallas interpreter, which
accepts programs the Mosaic compiler refuses: ``sample_window_fused``
passed every interpreted test from PR 3 on and had never compiled. libtpu
can compile for a TPU topology it is not attached to, so this test hands
each family's kernel entry points, forward and backward, to the real
compiler for one v5e chip at the shape where a model dispatches to it,
and then through the models' entry points under a four-chip mesh, where
the kernels have to map themselves over the batch shards. Numerics on
the chip are ``scripts/chip_kernels.py``'s job.

The compile runs in a child process: it has to bring libtpu up, and
whatever that does must not reach the test process. A host where libtpu
cannot describe the topology skips; a kernel the compiler refuses fails.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).parent.parent

_CHILD = r"""
import sys
sys.path.insert(0, sys.argv[1])
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print(f"no compile-only TPU topology: {type(e).__name__}: {e}")
    sys.exit(3)

from raft_meets_dicl_tpu.ops import pallas as K

chip = SingleDeviceSharding(topo.devices[0])
bf16, f32 = jnp.bfloat16, jnp.float32


def compile_for_v5e(fn, *specs):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# Up8 combine in the raft/baseline train step: b6 400x720, 12 iterations
m = 12 * 6 * 50 * 90
compile_for_v5e(lambda a, b: K._run_fwd(a, b, 0.25),
                ((m, 576), bf16), ((m, 18), f32))
compile_for_v5e(lambda a, b, c: K._run_bwd(a, b, c, 0.25),
                ((m, 576), bf16), ((m, 18), f32), ((m, 128), f32))

# windowed correlation pyramid, raft/fs at 1080p: every level resident
# (lane-wide blocks of 80 positions, the flat costs)
h, w, c, levels = 134, 320, 256, 4
f1 = ((1, h, w, c), bf16)
f2 = tuple(((1, h >> l, w >> l, c), bf16) for l in range(levels))
coords = ((1, h, w, 2), f32)
dout = ((1, h, w, levels * 81), f32)
compile_for_v5e(
    lambda a, cc, *bb: K._wcp_fwd_tpu(a, bb, cc, 4),
    f1, coords, *f2)
compile_for_v5e(
    lambda a, cc, d, *bb: K._wcp_bwd_tpu(a, bb, cc, d, 4),
    f1, coords, dout, *f2)

# ... and the hybrid call of the cell fs-train-1080p: level 0 alone at
# 136x240 (1088x1920), the coarser levels being materialised volumes
h, w = 136, 240
f1 = f2 = ((1, h, w, c), bf16)
coords = ((1, h, w, 2), f32)
dout = ((1, h, w, 81), f32)
compile_for_v5e(
    lambda a, cc, b: K._wcp_fwd_tpu(a, (b,), cc, 4),
    f1, coords, f2)
compile_for_v5e(
    lambda a, cc, d, b: K._wcp_bwd_tpu(a, (b,), cc, d, 4),
    f1, coords, dout, f2)

# ... and the same call without the bf16 policy, which _wcp_fits_vmem
# admits too: float32 features meet the MXU as they are
assert K._wcp_fits_vmem(jax.ShapeDtypeStruct((1, h, w, c), f32),
                        (jax.ShapeDtypeStruct((1, h, w, c), f32),), 4)
f1 = f2 = ((1, h, w, c), f32)
compile_for_v5e(
    lambda a, cc, b: K._wcp_fwd_tpu(a, (b,), cc, 4),
    f1, coords, f2)
compile_for_v5e(
    lambda a, cc, d, b: K._wcp_bwd_tpu(a, (b,), cc, d, 4),
    f1, coords, dout, f2)

# fused DICL window sampler, raft+dicl/ml: b6 384x704, C=32, 4 levels
# (the maps coarser than the centres here; level 0 is ctf3's 48x88 below)
b, h, w, c = 6, 48, 88, 32
for lvl in range(1, 4):
    for dtype in (f32, bf16):
        f2 = ((b, h >> lvl, w >> lvl, c), dtype)
        compile_for_v5e(lambda a, cc: K._sw_fwd_tpu(a, cc, 4),
                        f2, ((b, h, w, 2), f32))
        compile_for_v5e(lambda a, cc, d: K._sw_bwd_tpu(a, cc, d, 4),
                        f2, ((b, h, w, 2), f32), ((b, 9, 9, h, w, c), f32))

# ... and at the three shapes ctf3-train-things dispatches (maps and centres
# alike): the benchmark tells the sampler's calls by their instruction text
# as compiled, and a call it cannot tell leaves sw_ms and sw_roofline unread
from benchmark.harness import sw_kernel


def mosaic_call(compiled):
    (line,) = [ln for ln in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    return sw_kernel.call(line.strip())


for h, w in ((48, 88), (24, 44), (12, 22)):
    for dtype in (f32, bf16):
        f2 = ((b, h, w, c), dtype)
        fwd = compile_for_v5e(lambda a, cc: K._sw_fwd_tpu(a, cc, 4),
                              f2, ((b, h, w, 2), f32))
        direction, window, _ = mosaic_call(fwd)
        assert (direction, window) == ("forward", (b, h, w, 81, c)), fwd
        bwd = compile_for_v5e(lambda a, cc, d: K._sw_bwd_tpu(a, cc, d, 4),
                              f2, ((b, h, w, 2), f32),
                              ((b, 9, 9, h, w, c), f32))
        direction, window, _ = mosaic_call(bwd)
        assert (direction, window) == ("backward", (b, h, w, 81, c)), bwd

# ... and at the edge of what _sw_fits_vmem admits: the gate promises the
# compiler's verdict, and a shape it admits wrongly fails the whole step
f2 = jax.ShapeDtypeStruct((1, 248, 440, c), f32)
assert K._sw_fits_vmem(f2, jax.ShapeDtypeStruct((1, 248, 440, 2), f32), 4)
compile_for_v5e(lambda a, cc: K._sw_fwd_tpu(a, cc, 4),
                ((1, 248, 440, c), f32), ((1, 248, 440, 2), f32))
compile_for_v5e(lambda a, cc, d: K._sw_bwd_tpu(a, cc, d, 4),
                ((1, 248, 440, c), f32), ((1, 248, 440, 2), f32),
                ((1, 9, 9, 248, 440, c), f32))

# Under an SPMD mesh Mosaic refuses to partition a kernel automatically:
# the step builders publish their mesh (parallel.mesh.traced_under) and
# the kernels map themselves over its batch shards. Four chips, batch 8.
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from raft_meets_dicl_tpu.ops.upsample import convex_upsample_8x
from raft_meets_dicl_tpu.parallel.mesh import traced_under

mesh = Mesh(np.array(topo.devices), ("data",))
batch = NamedSharding(mesh, P("data"))
jax.default_backend = lambda: "tpu"    # trace the on-TPU dispatch


def compile_for_four_chips(fn, *specs):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=batch)
            for shape, dtype in specs]
    step = traced_under(mesh, jax.jit(fn, in_shardings=batch))
    compiled = step.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def grad_of(fn, argnum):
    return jax.grad(lambda *a: fn(*a).astype(f32).sum(), argnums=argnum)


compile_for_four_chips(grad_of(convex_upsample_8x, 1),
                       ((8, 50, 90, 2), f32), ((8, 50, 90, 576), bf16))
compile_for_four_chips(
    grad_of(lambda a, b, cc: K.windowed_corr_pyramid(a, (b,), cc), 0),
    ((8, 48, 64, 256), bf16), ((8, 48, 64, 256), bf16),
    ((8, 48, 64, 2), f32))
compile_for_four_chips(
    grad_of(lambda a, cc: K.sample_window_fused(a, cc), 0),
    ((8, 48, 88, 32), f32), ((8, 48, 88, 2), f32))
print("compiled")
"""


# Whole train steps of tiny models, for what the compiled text says of
# its instructions (PR 37): the compiler names a Mosaic call after its
# innermost scope, three readers of the benchmark tell their kernels by
# that name (``wcp.N``, ``sampler.N``, ``Up8Network_0.N``), and the phase
# scopes must not have come between; and the chip's own text, with its
# prefetch copies and allocation calls, must leave the parser of
# ``compile/owners.py`` few instructions without an owner. Off the TPU the
# kernels give way to their XLA twins and there is no call to name: this
# is the one place where that text is rehearsed without a chip.
_CHILD_MODELS = r"""
import os
import sys
import time
sys.path.insert(0, sys.argv[1])
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print(f"no compile-only TPU topology: {type(e).__name__}: {e}")
    sys.exit(3)

import optax
import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu import parallel
from raft_meets_dicl_tpu.compile import owners

chip = SingleDeviceSharding(topo.devices[0])
jax.default_backend = lambda: "tpu"    # trace the on-TPU dispatch
os.environ["RMD_FS_VOLUME_GIB"] = "0"  # raft/fs: every level on the kernel


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def step_text(kind, parameters, loss, h, w, arguments):
    cfg = {"name": "tiny", "id": "tiny", "input": None,
           "model": {"type": kind, "parameters": parameters,
                     "arguments": arguments},
           "loss": loss if isinstance(loss, dict) else {"type": loss}}
    loaded = models.load(cfg)
    model = loaded.model
    model.frozen_batchnorm = True
    variables = jax.eval_shape(
        lambda a, b: model.init(jax.random.PRNGKey(0), a, b),
        jnp.zeros((1, h, w, 3)), jnp.zeros((1, h, w, 3)))
    tx = optax.adamw(1e-3)
    state = jax.eval_shape(lambda v: parallel.TrainState.create(v, tx),
                           variables)
    state = jax.tree.map(lambda x: spec(x.shape, x.dtype), state)
    step = parallel.make_train_step(model, loaded.loss, tx, external_lr=True)
    batch = (spec((2, h, w, 3), jnp.float32), spec((2, h, w, 3), jnp.float32),
             spec((2, h, w, 2), jnp.float32), spec((2, h, w), bool))
    return step.lower(state, spec((), jnp.float32), *batch).compile().as_text()


tiny = {"corr-radius": 2, "corr-channels": 32, "context-channels": 16,
        "recurrent-channels": 16, "mixed-precision": True}
cases = [
    ("raft/baseline", dict(tiny, **{"corr-levels": 2}), "raft/sequence",
     32, 64, {"iterations": 2}, ("Up8Network_",), ()),
    ("raft/fs", dict(tiny, **{"corr-levels": 2}), "raft/sequence",
     32, 64, {"iterations": 2}, ("wcp.", "Up8Network_"), ()),
    ("raft+dicl/ctf-l3", tiny, {"type": "raft+dicl/mlseq", "arguments": {"alpha": [0.38, 0.6, 1.0]}},
     64, 128, {"iterations": [1, 1, 2]}, ("sampler.", "Up8Network_"),
     ("corr",)),        # nothing is built once a step for its look-ups
]
for kind, parameters, loss, h, w, arguments, prefixes, absent in cases:
    t0 = time.time()
    text = step_text(kind, parameters, loss, h, w, arguments)
    calls = [line.split(" = ")[0].split("%")[-1].strip()
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, f"{kind}: no Mosaic call in the compiled step"
    for name in calls:
        assert name.startswith(prefixes), (kind, calls)
    for prefix in prefixes:
        assert any(n.startswith(prefix) for n in calls), (kind, prefix, calls)
    record = owners.parse(text)
    assert record["unowned"] < 0.10 * record["instructions"], \
        (kind, record["rules"])
    named = set(record["owners"]) - {owners.OTHER, owners.UNOWNED}
    assert named == set(owners.PHASES) - {"input", *absent}, \
        (kind, sorted(named))
    print(kind, calls, record["rules"], round(time.time() - t0, 1), flush=True)
print("compiled")
"""


def _compile_only(child, timeout):
    # compile-only: no chip is taken, so libtpu's one-process lock (a
    # stale /tmp/libtpu_lockfile, a neighbour compiling) must not matter
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TPU_WORKER_HOSTNAMES="localhost",
               TPU_ACCELERATOR_TYPE="v5litepod-4",
               ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    proc = subprocess.run(
        [sys.executable, "-c", child, str(REPO)], env=env,
        capture_output=True, text=True, timeout=timeout)
    if proc.returncode == 3:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().endswith("compiled")


def test_every_kernel_family_compiles_for_v5e():
    _compile_only(_CHILD, 600)


def test_model_steps_name_their_mosaic_calls_and_owners_for_v5e():
    _compile_only(_CHILD_MODELS, 600)
