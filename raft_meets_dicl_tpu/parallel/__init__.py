"""Distributed execution layer: device meshes, partitioned train steps.

The reference's entire parallelism story is single-process
``nn.DataParallel`` (src/cmd/train.py:183-184 — scatter the batch over
GPUs, implicit NCCL). The TPU-native equivalent is SPMD over a
``jax.sharding.Mesh``: annotate the batch with a sharded leading axis,
place parameters per the partition rules, and let XLA insert the
gradient all-reduces over ICI. The same step builder serves a single
chip, one host's chips, or several processes (with
``jax.distributed.initialize``) — there is no separate code path.

What has run on chips is the 1-D ``data`` mesh over the four chips of one
v5e host, one process (PR 39, the benchmark's cell
``raft-train-things-dp4``; PERF.md sections 5 and 6 hold what the
partitioner made of the step there: besides the gradient all-reduces, the
all-to-alls of the pair concatenation and of the iterates' reshape round
Up8). The ``model`` axis, in-step accumulation under a mesh and more than
one process have run on virtual CPU devices only, where no time means
anything.

Axes:
- ``data``  — batch parallelism (the reference's DataParallel equivalent)
- ``model`` — parameter/optimizer *storage* sharding (ZeRO-style): the
  regex partitioner in ``partition.py`` maps the wide encoder and
  update-block kernels (and their Adam moments) onto this axis; the
  train step gathers them once per step and the batch still splits over
  every device, so a chip stores less of the parameters without touching
  the data-parallel compute graph (not yet run on chips). ``make_mesh((data, model))`` builds the
  2-D mesh; ``model=1`` degenerates to the historical 1-D layout
  bit-for-bit.
"""

from .distributed import initialize, is_primary, process_count, process_index
from .mesh import (
    batch_nbytes, data_axis_size, data_mesh, make_mesh, mesh_data_size,
    parse_mesh_spec, replicate, scoped_data_axis_size, set_data_axis_size,
    shard_batch,
)
from .partition import DEFAULT_RULES, Partitioner, data_sharding, replicated
from .train import TrainState, make_eval_step, make_train_step

__all__ = [
    "batch_nbytes", "data_axis_size", "data_mesh", "make_mesh",
    "mesh_data_size", "parse_mesh_spec", "replicate",
    "scoped_data_axis_size", "set_data_axis_size", "shard_batch",
    "DEFAULT_RULES", "Partitioner", "data_sharding", "replicated",
    "TrainState", "make_eval_step", "make_train_step",
    "initialize", "is_primary", "process_count", "process_index",
]
