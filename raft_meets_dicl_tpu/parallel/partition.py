"""Rule-based parameter/optimizer partitioning over the SPMD mesh.

The historical mesh replicated every parameter (and both Adam moments) on
every chip: batch parallelism only, with optimizer-state HBM paid
``n_devices`` times. This module maps param/optimizer pytrees onto
``PartitionSpec``s via regex rules over the flattened param paths — the
partitioner pattern of large-model JAX trainers (SNIPPETS.md [1]–[3]):

- rules are ``(regex, PartitionSpec)`` pairs matched against
  ``'/'``-joined param paths; the first match wins. The spec is
  right-aligned to the leaf's trailing dimensions, so ``P('model')`` on
  an HWIO conv kernel shards the output-channel dim.
- defaults shard the wide feature/context-encoder and update-block conv
  kernels over the ``model`` axis; biases, norm scales, and scalars stay
  replicated.
- optimizer-state moments (Adam ``mu``/``nu`` & co. — any leaf whose
  path suffix names a parameter of the same shape) clone their param's
  spec; step counters and other scalars replicate.
- a rule whose sharded dimension does not divide by the mesh axis falls
  back to replication for that leaf — partial sharding beats a
  partitioner error on an odd channel count.

Execution model: the rules shard *storage* (ZeRO-style). The train step
all-gathers the sharded params once per step for the forward/backward —
the numerically-proven pure data-parallel compute graph, with the batch
split over every mesh device — then reduces the gradients back onto the
param shards for the (elementwise, shard-local) optimizer update. Per
chip, params and both Adam moments shrink by the model-axis factor at
rest; the transient gather is one params-sized buffer (whether the chip's
compiler hides it behind compute has not been measured: this layout has
only run on virtual CPU devices; the replicated 1-D layout is what the
benchmark's four-chip cell runs, PERF.md section 5). (Letting GSPMD propagate the model axis through the conv
compute itself was measured numerically unsafe on the XLA CPU backend —
the partially-replicated concat/all-reduce path miscompiles — and the
gather-compute form is what the per-chip HBM motivation needs anyway.)

On a mesh without a ``model`` axis (or with ``model=1``) every spec
degenerates to ``P()``: the emitted program is the historical replicated
one, bit for bit.
"""

import re
import warnings

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_flatten_with_path

# Default rules: the parameter mass sits in conv kernels — the siamese
# feature encoder, the context encoder, the recurrent update block
# (motion encoder + GRU + flow head), the convex-upsampling head, and the
# DICL matching/embedding nets. Their kernels shard output channels over
# ``model``; everything else (biases, norm affines, BN stats, scalars)
# replicates.
DEFAULT_RULES = (
    (r"(FeatureEncoder|StackEncoder|PoolEncoder|Rfpm)[^/]*/.*kernel$",
     P("model")),
    (r"(UpdateBlock|MotionEncoder|RecurrentBlock|SepConvGru|ConvGru)"
     r"[^/]*/.*kernel$", P("model")),
    (r"(FlowHead|Up8Network|UpNetwork|MatchingNet|PairEmbedding|DapNetwork)"
     r"[^/]*/.*kernel$", P("model")),
    (r".*", P()),
)


def _path_str(path):
    """``'/'``-joined flattened pytree path (dict keys, attr names,
    sequence indices)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:  # pragma: no cover - future key types
            parts.append(str(k))
    return "/".join(parts)


def batch_spec(mesh):
    """Batch PartitionSpec: the leading dim splits over EVERY mesh axis.

    On the 1-D mesh this is the historical ``P('data')`` (exactly that
    object form — a 1-tuple wrapper is not spec-identical and would make
    jit reshard already-placed batches). On a 2-D ``(data × model)``
    mesh the batch splits over both axes — under the gather-compute
    execution model the ``model`` axis carries batch slices during
    compute (it only shards parameter *storage* between steps), so all
    ``data × model`` devices contribute data parallelism and no
    activation is ever partially replicated.
    """
    names = tuple(mesh.axis_names)
    return P(names[0] if len(names) == 1 else names)


def data_sharding(mesh, axis_name=None):
    """Batch sharding: leading dim split over the mesh (see
    :func:`batch_spec`); pass ``axis_name`` to pin a single axis."""
    if axis_name is not None:
        return NamedSharding(mesh, P(axis_name))
    return NamedSharding(mesh, batch_spec(mesh))


def replicated(mesh):
    """Fully-replicated sharding on ``mesh``."""
    return NamedSharding(mesh, P())


def is_sharded(sharding_tree):
    """True when any leaf of a sharding pytree actually partitions —
    i.e. the tree is not the degenerate fully-replicated layout. The
    step builders use this to skip the gather/reduce constraints (and
    keep the historical program bit-for-bit) when there is nothing to
    gather."""
    leaves = jax.tree.leaves(
        sharding_tree, is_leaf=lambda x: isinstance(x, NamedSharding))
    return any(isinstance(s, NamedSharding) and tuple(s.spec)
               for s in leaves)


class Partitioner:
    """Maps params/optimizer/TrainState pytrees onto mesh shardings.

    One instance per mesh; the step builders and the evaluation path both
    ask it for their shardings instead of hand-constructing
    ``NamedSharding``s, so a sharded-parameter layout propagates
    everywhere at once.
    """

    def __init__(self, mesh, rules=None, data_axis="data",
                 model_axis="model"):
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.rules = [(re.compile(rx), spec)
                      for rx, spec in (DEFAULT_RULES if rules is None
                                       else rules)]

    # -- axis geometry -----------------------------------------------------

    @property
    def data_size(self):
        if self.data_axis in self.mesh.axis_names:
            return int(self.mesh.shape[self.data_axis])
        return int(self.mesh.devices.size)

    @property
    def model_size(self):
        if self.model_axis in self.mesh.axis_names:
            return int(self.mesh.shape[self.model_axis])
        return 1

    # -- spec resolution ---------------------------------------------------

    def spec(self, name, shape):
        """PartitionSpec for one named leaf of the given shape."""
        shape = tuple(shape)
        if self.model_size <= 1 or len(shape) == 0 \
                or int(np.prod(shape)) == 1:
            return P()
        for rx, spec in self.rules:
            if rx.search(name):
                return self._fit(spec, shape)
        return P()

    def _fit(self, spec, shape):
        """Right-align ``spec`` to the leaf's trailing dims; fall back to
        replication when a sharded dim does not divide by its axis."""
        parts = tuple(spec)
        if len(parts) > len(shape):
            return P()
        full = (None,) * (len(shape) - len(parts)) + parts
        for dim, axis in zip(shape, full):
            if axis is None:
                continue
            names = (axis,) if isinstance(axis, str) else tuple(axis)
            size = int(np.prod([self.mesh.shape[n] for n in names]))
            if size and dim % size:
                return P()
        while full and full[-1] is None:
            full = full[:-1]
        return P(*full)

    # -- sharding trees ----------------------------------------------------

    def param_shardings(self, params):
        """NamedSharding pytree for a parameter tree (rule-matched)."""
        return self._map_named(params, self.spec)

    def opt_shardings(self, opt_state, params):
        """NamedSharding pytree for an optimizer state.

        Moment buffers clone their parameter's spec: any opt-state leaf
        whose flattened-path *suffix* names a parameter of identical
        shape (Adam's ``mu``/``nu`` subtrees mirror the param tree under
        their own prefix) inherits that parameter's spec; every other
        leaf — step counts, EMA scalars, clip state — replicates.
        """
        by_path = {}
        for path, leaf in tree_flatten_with_path(params)[0]:
            name = _path_str(path)
            by_path[name] = (tuple(leaf.shape), self.spec(name, leaf.shape))

        def opt_spec(name, shape):
            parts = name.split("/")
            for i in range(len(parts)):
                cand = "/".join(parts[i:])
                hit = by_path.get(cand)
                if hit is not None and hit[0] == tuple(shape):
                    return hit[1]
            return P()

        return self._map_named(opt_state, opt_spec)

    def state_shardings(self, state):
        """Full TrainState sharding: params by rules, optimizer moments
        cloned from them, batch stats and scalar counters replicated."""
        return state.replace(
            params=self.param_shardings(state.params),
            batch_stats=jax.tree.map(
                lambda _: replicated(self.mesh), state.batch_stats),
            opt_state=self.opt_shardings(state.opt_state, state.params),
            step=replicated(self.mesh),
            nonfinite_count=replicated(self.mesh),
        )

    def variables_sharding(self, variables):
        """Model-variables sharding for the eval path: params by rules,
        everything else (batch stats & co.) replicated."""
        out = {k: jax.tree.map(lambda _: replicated(self.mesh), v)
               for k, v in variables.items() if k != "params"}
        out["params"] = self.param_shardings(variables["params"])
        return out

    def batch_sharding(self):
        return data_sharding(self.mesh, self.data_axis)

    def replicated(self):
        return replicated(self.mesh)

    def _map_named(self, tree, spec_fn):
        leaves, treedef = tree_flatten_with_path(tree)
        shardings = [
            NamedSharding(self.mesh, spec_fn(_path_str(path), leaf.shape))
            for path, leaf in leaves
        ]
        return jax.tree.unflatten(treedef, shardings)

    # -- rule-coverage audit -----------------------------------------------

    def coverage(self, params):
        """Static rule-coverage audit over a parameter tree.

        Consults the raw rule list directly — bypassing the
        ``model_size <= 1`` degeneration in :meth:`spec` — so a 1-chip
        CI run still validates the rule set against a real param tree.

        A *dead rule* is one with a non-trivial spec (it was written to
        shard something) that matches zero param paths: a typo'd module
        name silently replicates everything it meant to shard.
        ``unmatched`` lists paths no rule claims at all (impossible with
        the default catch-all, but a custom rule list can drop it).
        """
        paths = [_path_str(p)
                 for p, _ in tree_flatten_with_path(params)[0]]
        counts = [0] * len(self.rules)
        unmatched = []
        for name in paths:
            for i, (rx, _spec) in enumerate(self.rules):
                if rx.search(name):
                    counts[i] += 1
                    break
            else:
                unmatched.append(name)
        dead = [rx.pattern
                for (rx, spec), n in zip(self.rules, counts)
                if n == 0 and tuple(spec)]
        return {
            "n_paths": len(paths),
            "rule_matches": [(rx.pattern, n)
                             for (rx, _), n in zip(self.rules, counts)],
            "dead_rules": dead,
            "unmatched": unmatched,
        }

    # -- placement + accounting --------------------------------------------

    def shard_state(self, state):
        """Place a TrainState according to the rules (device_put)."""
        cov = self.coverage(state.params)
        if cov["dead_rules"] or cov["unmatched"]:
            warnings.warn(
                f"partition rules audit: dead rules {cov['dead_rules']}, "
                f"unmatched paths {cov['unmatched'][:5]}"
                f"{'...' if len(cov['unmatched']) > 5 else ''} "
                f"(of {cov['n_paths']} param paths)",
                stacklevel=2)
        return jax.device_put(state, self.state_shardings(state))

    def shard_variables(self, variables):
        return jax.device_put(variables, self.variables_sharding(variables))

    def report(self, state):
        """Per-chip byte accounting for the telemetry ``sharding`` event.

        ``*_bytes_per_chip`` is what one device actually holds under the
        current placement; ``*_bytes_replicated`` is what it would hold
        fully replicated (the historical layout). The delta is the HBM
        the partitioner bought back per chip.
        """
        def account(tree):
            total = per_chip = n_sharded = n_leaves = 0
            for leaf in jax.tree.leaves(tree):
                nbytes = int(getattr(leaf, "nbytes", 0))
                total += nbytes
                n_leaves += 1
                shards = getattr(leaf, "addressable_shards", None)
                if shards:
                    dev0 = shards[0].device
                    mine = sum(int(s.data.nbytes) for s in shards
                               if s.device == dev0)
                else:
                    mine = nbytes
                per_chip += mine
                if mine < nbytes:
                    n_sharded += 1
            return total, per_chip, n_sharded, n_leaves

        p_tot, p_chip, p_sh, p_n = account(state.params)
        o_tot, o_chip, o_sh, o_n = account(state.opt_state)
        cov = self.coverage(state.params)
        return {
            "dead_rules": cov["dead_rules"],
            "unmatched_paths": len(cov["unmatched"]),
            "mesh": {name: int(self.mesh.shape[name])
                     for name in self.mesh.axis_names},
            "params_bytes_replicated": p_tot,
            "params_bytes_per_chip": p_chip,
            "params_sharded_leaves": p_sh,
            "params_leaves": p_n,
            "opt_bytes_replicated": o_tot,
            "opt_bytes_per_chip": o_chip,
            "opt_sharded_leaves": o_sh,
            "opt_leaves": o_n,
        }
