"""What the v5e compiler makes of the MatchingNet's pair form — checked
without a chip.

The compiler runs the net's convolutions with the window batch N = B·81
as the minor (lane) dimension. Joining the frame-one half of the first
layer to the per-displacement half by ``reshape`` + broadcast-add made it
write that half out at the activation's full size, transpose it to
N-minor, and transpose the activation's gradient back to sum it over the
displacements: three copies of ``bf16[486,48,80,96]`` a call, 9% of the
``raft+dicl/ml`` train step, that no XLA:CPU test could see. This test
hands the net, forward and backward under ``jax.checkpoint`` as the
models run it, to the real compiler at the shapes of ``raft+dicl/ml``
(b6 384x640: one level, and the four levels under the stacked-parameter
``vmap`` of ``MlCorrelationModule``) and reads the entry computation: no
``copy`` and no ``broadcast`` of the first activation's size, and the
other large copies exactly the ones that were there (the window's own,
which belong to the sampler's interface), so that nobody trades the
three for others.

``dicl/baseline`` feeds the net's *stacked* form from
``displaced_pair_volume``, 49 integer shifts of the padded frame-two
features beside frame one's, masked where the shifted features sum to
zero. Built first and masked afterwards, the stack crossed the chip in
float32 five times over (a reduction, a broadcast of frame one's half, two
relayout copies, two products: 56 ms of a 207 ms served batch, PR 41).
The second test compiles one ``FlowLevel`` at level 2's served shape
(b8 512x1024: ``8x128x256x32``, range (3, 3), float32) and holds the
compiler to what PR 47 chose the form by: nothing of the stack's size
moves in float32, each half is relaid once, and the first convolution
stays a producer inside the second layer's fusion, its 4.93 GB activation
never written.

Child process and skip rules as in ``test_pallas_compile.py``.
"""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).parent.parent

_CHILD = r"""
import collections, json, re, sys
sys.path.insert(0, sys.argv[1])
levels = int(sys.argv[2])
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

from raft_meets_dicl_tpu.models.common.blocks.dicl import MatchingNet

chip = SingleDeviceSharding(topo.devices[0])
bf16, f32 = jnp.bfloat16, jnp.float32
b, k, h, w, c = 6, 9, 48, 80, 32

net = MatchingNet(norm_type="batch", dtype=bf16)
f1 = jax.ShapeDtypeStruct((b, h, w, c), bf16)
window = jax.ShapeDtypeStruct((b, k, k, h, w, c), f32)  # as the sampler's
variables = jax.eval_shape(
    lambda a, ww: net.init(jax.random.PRNGKey(0), (a, ww.astype(bf16))),
    f1, window)


def apply(v, a, ww):
    # the training configuration: train with frozen batch norm
    return net.apply(v, (a, ww), True, True)


if levels > 1:
    apply = jax.vmap(apply)
    variables, f1, window = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((levels,) + s.shape, s.dtype),
        (variables, f1, window))


def loss(v, a, ww):
    return jnp.sum(jax.checkpoint(apply)(v, a, ww.astype(bf16)) ** 2)


args = jax.tree_util.tree_map(
    lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
    (variables, f1, window))
text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*args).compile().as_text()

entry = text[text.index("ENTRY"):]
entry = entry[:entry.index("\n}")]
census = collections.Counter()
for line in entry.splitlines():
    m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]\S* "
                 r"(copy|broadcast)\(", line)
    if m:
        dtype, dims, opcode = m.groups()
        census[f"{opcode} {dtype}[{dims}]"] += 1
print("census " + json.dumps(census))
"""

_WINDOWS = 6 * 81


def _elements(key):
    return math.prod(int(d) for d in key.split("[")[1].rstrip("]").split(","))


def _census(child, *args):
    """Run a child's compile and hand back the ``census`` line it prints."""
    # compile-only: no chip is taken, so libtpu's one-process lock (a
    # stale /tmp/libtpu_lockfile, a neighbour compiling) must not matter
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TPU_WORKER_HOSTNAMES="localhost",
               TPU_ACCELERATOR_TYPE="v5litepod-4",
               ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    proc = subprocess.run(
        [sys.executable, "-c", child, str(REPO), *map(str, args)], env=env,
        capture_output=True, text=True, timeout=600)
    if proc.returncode == 3:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-4000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("census ")]
    return json.loads(line[len("census "):])


@pytest.mark.parametrize("levels", [1, 4])
def test_pair_form_adds_no_array_of_the_activations_size(levels):
    census = _census(_CHILD, levels)

    # the first layer's output: 6·81 windows, 48x80, 96 channels, a level
    activation = levels * _WINDOWS * 48 * 80 * 96
    same_size = {k: n for k, n in census.items()
                 if _elements(k) == activation}
    assert not same_size, same_size

    # every copy at least as large as the window: the window's own two
    # transposes to N-minor (forward and recomputed) and its gradient's
    # way back, in float32 as the sampler takes it
    lead = f"{levels}," if levels > 1 else ""
    window = levels * _WINDOWS * 48 * 80 * 32
    large = {k: n for k, n in census.items()
             if k.startswith("copy ") and _elements(k) >= window}
    assert large == {f"copy bf16[{lead}486,48,80,32]": 2,
                     f"copy f32[{lead}486,48,80,32]": 1}, census


_CHILD_STACK = r"""
import json, math, re, sys
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, sys.argv[1] + "/tests")
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

from raft_meets_dicl_tpu.models.impls import dicl
from test_dicl_volume import _reference

chip = SingleDeviceSharding(topo.devices[0])
b, h, w, c = 8, 128, 256, 32
level = dicl.FlowLevel(c, 2, (3, 3))


def apply(v, f1, f2):
    # no coarser flow to warp by, no context network: the shift stack,
    # the MatchingNet and the soft-argmin, under the scopes the ladder runs
    # them in
    return level.apply(v, None, f1, f2, None, dap=False, ctx=False)[0]


feat = jax.ShapeDtypeStruct((b, h, w, c), jnp.float32)
variables = jax.eval_shape(
    lambda f1, f2: level.init(jax.random.PRNGKey(0), None, f1, f2, None,
                              dap=False, ctx=False), feat, feat)
args = jax.tree_util.tree_map(
    lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
    (variables, feat, feat))


def compiled():
    # a function of its own each time: jit remembers a trace by function
    return jax.jit(lambda *a: apply(*a)).lower(*args).compile()


new = compiled()
dicl.displaced_pair_volume = _reference  # the form until PR 46
old = compiled()

text = new.as_text()
entry = text[text.index("ENTRY"):]
entry = entry[:entry.index("\n}")]
idle = {"bitcast", "get-tuple-element", "tuple", "parameter", "constant"}
census = []
for line in entry.splitlines():
    m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
    if not m or m.group(2) in idle:
        continue
    op_name = re.search(r'op_name="([^"]*)"', line)
    scope = "mnet" if op_name and "/mnet/" in op_name.group(1) else "matching"
    for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", m.group(1)):
        census.append([scope, m.group(2), dtype,
                       [int(d) for d in dims.split(",")]])
print("census " + json.dumps({
    "instructions": census,
    "temp_new": new.memory_analysis().temp_size_in_bytes,
    "temp_old": old.memory_analysis().temp_size_in_bytes}))
"""


def test_shift_stack_moves_once_in_bfloat16_and_the_first_layer_stays_fused():
    census = _census(_CHILD_STACK)

    half = 8 * 49 * 128 * 256 * 32  # one half of the stack, in elements
    # outside the net nothing of a half's size is float32: the stack is
    # written, relaid and selected from in the bfloat16 the first
    # convolution reads it in
    stack = [x for x in census["instructions"]
             if x[0] == "matching" and math.prod(x[3]) >= half]
    assert stack and all(dtype == "bf16" for _, _, dtype, _ in stack), stack
    # each half is relaid to the item-minor layout once and nothing else is
    copies = [x for x in stack if x[1] == "copy"]
    assert [(dtype, dims) for _, _, dtype, dims in copies] == [
        ("bf16", [392, 128, 256, 32])] * 2, copies
    # frame one's half is written once, by the broadcast itself
    broadcasts = [x for x in stack if x[1] == "broadcast"]
    assert len(broadcasts) <= 1, broadcasts

    # the first layer's activation is no instruction's result: its
    # convolution is a producer inside the second layer's fusion
    first = [x for x in census["instructions"]
             if x[3] == [392, 128, 256, 96]]
    assert not first, first

    print(f"temporaries: {census['temp_new'] / 2**30:.3f} GiB, "
          f"stack first and masked after {census['temp_old'] / 2**30:.3f}")
    assert census["temp_new"] < census["temp_old"], census
