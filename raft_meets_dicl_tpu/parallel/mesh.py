"""Mesh construction and pytree sharding helpers.

Two mesh flavors:

- :func:`data_mesh` — the historical 1-D ``data`` mesh (pure batch
  parallelism, parameters replicated).
- :func:`make_mesh` — the 2-D ``(data × model)`` mesh for true SPMD
  scale-out: the batch shards over ``data``, wide parameter tensors
  (and their optimizer moments) shard over ``model`` via
  ``parallel.partition``. ``model=1`` degenerates to the 1-D data mesh,
  preserving the historical program bit-for-bit.
"""

import contextlib
import contextvars

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# data-parallel degree of the step function currently being built/traced —
# models read this to convert global-batch memory estimates into per-chip
# ones under SPMD. A ContextVar (not a module global) so nested/concurrent
# step builds — a train step and an eval step over different meshes, or a
# process-local validation jit interleaved with the sharded trace — can't
# leak each other's scale factor: each scope restores whatever value its
# enclosing scope had.
_data_axis = contextvars.ContextVar("rmd_data_axis_size", default=1)


@contextlib.contextmanager
def scoped_data_axis_size(n):
    """Scope the published data-parallel degree to the ``with`` body.

    Under SPMD a module traces with the GLOBAL batch, so any HBM budget
    the trace computes from shapes (e.g. raft/fs's volume dispatch,
    ``RMD_FS_VOLUME_GIB``) must be scaled by the data-parallel degree to
    describe one chip. Nested scopes restore the enclosing scope's value
    on exit (not a hard reset to 1), so a sharded trace that triggers an
    inner unsharded build — or vice versa — stays correct.
    """
    token = _data_axis.set(max(1, int(n)))
    try:
        yield
    finally:
        _data_axis.reset(token)


def set_data_axis_size(n):
    """Set the degree without scoping (legacy/test entry point).

    Prefer :func:`scoped_data_axis_size`; this exists for call sites that
    manage their own try/finally discipline.
    """
    _data_axis.set(max(1, int(n)))


def data_axis_size():
    """Data-parallel degree the current trace should assume (>= 1)."""
    return _data_axis.get()


# the mesh of the step function currently being traced (None: a
# single-device step). Code that cannot leave its partitioning to the
# compiler reads it at trace time — the Pallas kernels, which Mosaic
# refuses to partition automatically, map themselves over its shards.
_mesh = contextvars.ContextVar("rmd_traced_mesh", default=None)


def traced_mesh():
    """The mesh the current trace is partitioned over, or None."""
    return _mesh.get()


def traced_under(mesh, fn):
    """Publish ``mesh`` (and the data-parallel degree it implies: the
    step builders split the batch over every mesh axis) to ``fn``'s
    traces.

    The model traces inside the first call of the jitted function, so
    the scope must be pinned around the call, not at build time —
    otherwise an interleaved unsharded trace (e.g. the inspector's
    process-local validation jit) would read a stale value. Both scopes
    restore the enclosing scope's value on exit, so nested/concurrent
    step builds over different meshes can't leak into each other.
    """

    @contextlib.contextmanager
    def scope():
        token = _mesh.set(mesh)
        try:
            with scoped_data_axis_size(mesh.devices.size):
                yield
        finally:
            _mesh.reset(token)

    def wrapped(*args, **kwargs):
        with scope():
            return fn(*args, **kwargs)

    inner_lower = getattr(fn, "lower", None)
    if inner_lower is not None:
        # AOT entry point: tracing happens inside lower(), so it needs
        # the same scope as a live call
        def lower(*args, **kwargs):
            with scope():
                return inner_lower(*args, **kwargs)

        wrapped.lower = lower
    return wrapped


def data_mesh(n_devices=None, axis_name="data", devices=None):
    """1-D mesh over ``n_devices`` (default: all) for data parallelism."""
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devs)} available"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def parse_mesh_spec(spec):
    """Parse a ``--mesh`` / env-config mesh spec into ``(data, model)``.

    Accepted forms:

    - ``None`` / ``''`` / ``'data'`` — pure data parallelism over all
      devices (returns ``None``: the caller builds the default 1-D mesh),
    - ``'D,M'`` or ``'DxM'`` — explicit 2-D shape, e.g. ``'4,2'``;
      ``D = -1`` means "all remaining devices" (``-1,2`` on 8 chips is
      ``(4, 2)``),
    - ``'D'`` — 1-D data mesh over exactly D devices (``(D, 1)``),
    - a mapping with ``data`` / ``model`` keys (env config form).
    """
    if spec is None:
        return None
    if isinstance(spec, dict):
        return (int(spec.get("data", -1)), int(spec.get("model", 1)))
    if isinstance(spec, (tuple, list)):
        d, m = spec
        return (int(d), int(m))
    s = str(spec).strip().lower()
    if not s or s == "data":
        return None
    parts = [p.strip() for p in s.replace("x", ",").split(",") if p.strip()]
    try:
        dims = [int(p) for p in parts]
    except ValueError:
        raise ValueError(
            f"invalid mesh spec '{spec}': expected 'data', 'D', 'D,M' or "
            "'DxM' (e.g. '4,2'; data=-1 fills the remaining devices)"
        ) from None
    if len(dims) == 1:
        return (dims[0], 1)
    if len(dims) != 2:
        raise ValueError(
            f"invalid mesh spec '{spec}': at most two axes (data, model)")
    return (dims[0], dims[1])


def make_mesh(spec=None, devices=None, data_axis="data", model_axis="model"):
    """Build the SPMD mesh from a ``(data, model)`` spec.

    ``spec=None`` or ``model == 1`` returns the historical 1-D ``data``
    mesh over all selected devices — same axes, same device order, so the
    compiled program is bit-identical to the pre-2D-mesh path. A real
    ``model > 1`` returns a 2-D ``(data × model)`` mesh; ``data = -1``
    fills with the remaining devices.
    """
    devs = list(devices if devices is not None else jax.devices())
    if spec is None:
        return Mesh(np.array(devs), (data_axis,))

    data, model = (int(spec[0]), int(spec[1]))
    if model < 1:
        raise ValueError(f"invalid mesh model-axis size {model}")
    if data == -1:
        if len(devs) % model:
            raise ValueError(
                f"{len(devs)} devices do not divide over model={model}")
        data = len(devs) // model
    if data < 1:
        raise ValueError(f"invalid mesh data-axis size {data}")
    if data * model > len(devs):
        raise ValueError(
            f"mesh ({data}, {model}) needs {data * model} devices, "
            f"only {len(devs)} available"
        )
    devs = devs[: data * model]

    if model == 1:
        # bit-for-bit the 1-D data mesh: same program as before the 2-D
        # mesh existed (no degenerate singleton axis in the HLO shardings)
        return Mesh(np.array(devs), (data_axis,))
    return Mesh(np.array(devs).reshape(data, model),
                (data_axis, model_axis))


def mesh_data_size(mesh, axis_name="data"):
    """Size of the mesh's data axis (total devices on a 1-D mesh)."""
    if axis_name in mesh.axis_names:
        return int(mesh.shape[axis_name])
    return int(mesh.devices.size)


def shard_batch(batch, mesh, axis_name=None):
    """Place a host batch on the mesh, sharded along the leading axis.

    Single-process: ``batch`` is the global batch, device_put with a
    sharded layout. Multi-process (multi-host pods): ``batch`` is this
    process's LOCAL slice — the global array is assembled from every
    process's contribution (``jax.make_array_from_process_local_data``),
    so the global batch size is ``local · process_count``. Works on any
    pytree of arrays with a common leading batch dimension. The leading
    axis splits over EVERY mesh axis (``partition.batch_spec``): on a
    2-D mesh the ``model`` axis shards parameter storage between steps
    but carries batch slices during compute. Pass ``axis_name`` to pin
    a single axis instead.
    """
    if axis_name is None:
        names = tuple(mesh.axis_names)
        axis_name = names[0] if len(names) == 1 else names
    spec = NamedSharding(mesh, P(axis_name))
    if jax.process_count() > 1:
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                spec, np.asarray(x)),
            batch,
        )
    return jax.tree.map(lambda x: jax.device_put(x, spec), batch)


def batch_nbytes(batch):
    """Total bytes of a (pytree) host batch — the wire volume one
    ``shard_batch``/``device_put`` call moves across the host→device
    boundary. Telemetry records this per step as ``wire_bytes``."""
    return int(sum(x.nbytes for x in jax.tree.leaves(batch)
                   if hasattr(x, "nbytes")))


def replicate(tree, mesh):
    """Replicate a pytree (params, optimizer state) across the mesh."""
    spec = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, spec), tree)
