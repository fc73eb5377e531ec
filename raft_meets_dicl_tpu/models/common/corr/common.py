"""Shared pieces of the correlation modules: window sampling + soft-argmax.

The reference samples the (2r+1)² displaced feature windows with
``F.grid_sample`` per module (src/models/common/corr/dicl.py:26-61 and
siblings); here one helper owns that lookup, built on the framework's
bilinear-sample op, with windows ordered by ``ops.corr.window_delta``
(axis 0 varies dx) so every cost volume in the framework shares one channel
layout.

The XLA sampler lives in ``ops.sample.sample_window`` (re-exported here
for the corr modules and parity tests); ``sample_window_fast`` dispatches
to the fused Pallas kernel on TPU unless the ``RMD_DICL_FAST=0`` escape
hatch forces the reference path.
"""


import flax.linen as nn
import jax.numpy as jnp

from ....ops.corr import window_delta
from ....ops.sample import sample_window  # noqa: F401  (re-export)
from ..blocks.dicl import DisplacementAwareProjection


def dicl_fast_enabled():
    """DICL fast-path switch, read at trace time: ``RMD_DICL_FAST=0``
    restores the reference XLA sampler + per-level matching loops."""
    from ....utils import env

    return env.get_bool("RMD_DICL_FAST")


def sample_window_fast(f2, coords, radius):
    """``sample_window`` through the fused Pallas kernel when enabled.

    Semantics and layout match ``sample_window`` exactly; the fused path
    treats ``coords`` as non-differentiable (every caller sits behind the
    RAFT iteration's stop_gradient on the lookup centers).
    """
    if not dicl_fast_enabled():
        return sample_window(f2, coords, radius)
    from ....ops.pallas import sample_window_fused

    return sample_window_fused(f2, coords, radius)


def record_matching_bytes(*arrays):
    """Trace-time accounting of the matching volumes fed to the cost nets.

    Called while the model traces: the byte count lands in the next
    ``step`` event's counters as ``matching_volume_bytes``, so
    events.jsonl shows the window/volume footprint the matching path moves
    per step — and the drop when the unstacked/bf16 fast path is active.
    The program that owns the trace keeps the count with its executable
    (``telemetry.note_trace``), so a boot that loads the program from the
    store reports it too; a model that runs the matching inside a
    ``telemetry.trace_site`` gets its forward pass's bytes per step.
    """
    from .... import telemetry

    n = sum(int(a.size) * a.dtype.itemsize for a in arrays)
    telemetry.note_trace("matching_volume_bytes", n)
    return n


def stack_pair(f1, f2_window):
    """Broadcast f1 against the sampled window and stack channels:
    (B, du, dv, H, W, 2C) matching volume (reference corr/dicl.py:50-55)."""
    b, du, dv, h, w, c = f2_window.shape
    f1 = jnp.broadcast_to(f1[:, None, None], (b, du, dv, h, w, c))
    return jnp.concatenate((f1, f2_window), axis=-1)


def soft_argmax_flow(cost, radius, temperature=1.0):
    """Softmax-weighted displacement readout: cost (B, H, W, (2r+1)²) →
    flow (B, H, W, 2)."""
    b, h, w, _ = cost.shape
    k = 2 * radius + 1

    score = nn.softmax(cost / temperature, axis=-1)
    delta = window_delta(radius, cost.dtype).reshape(k * k, 2)
    return jnp.einsum("bhwd,dc->bhwc", score, delta)


class SoftArgMaxFlowRegression(nn.Module):
    """Flow readout from a cost volume (reference corr/dicl.py:64-89)."""

    radius: int
    temperature: float = 1.0

    @nn.compact
    def __call__(self, cost):
        return soft_argmax_flow(cost, self.radius, self.temperature)


class SoftArgMaxFlowRegressionWithDap(nn.Module):
    """Flow readout with its own (trained) DAP applied first
    (reference corr/dicl.py:92-119)."""

    radius: int
    temperature: float = 1.0

    @nn.compact
    def __call__(self, cost):
        b, h, w, kk = cost.shape
        k = 2 * self.radius + 1

        vol = cost.reshape(b, h, w, k, k)
        vol = DisplacementAwareProjection((self.radius, self.radius))(vol)
        return soft_argmax_flow(vol.reshape(b, h, w, kk), self.radius,
                                self.temperature)
