#!/usr/bin/env python3
"""Measure whether a hand-scheduled Pallas kernel can beat XLA's batched
einsum on the RAFT lookup contraction (PERF.md round-4 "fused lookup+GRU"
spec, VERDICT item 7).

The windowed bilinear lookup is mathematically a batched (K, H2) x
(H2, W2) contraction per source position (ops/corr.py:_lookup_level).
The fused-kernel estimate (>=25 pairs/s for raft/baseline) assumed
hand-scheduling could lift this off the measured ~5 TFLOP/s batched-
tiny-matmul floor. This probe times the exact level-0 contraction at the
bench config three ways:

  A. XLA batched einsum (what the model runs today)
  B. Pallas, per-position serial dots from VMEM-resident rows
  C. Pallas, both lookup stages fused per position (t = wy @ corr,
     out = t @ wx^T) so the intermediate never leaves VMEM
  D. XLA einsum over the u8-quantized volume, dequantized in-register
     as the stage-1 operand (the ops/corr.py quantized-tier branch) —
     same contraction, 1/4 (f32) or 1/2 (bf16) of the volume bytes
     streamed from HBM

If B/C do not beat A, the contraction is MXU-shape-bound — the 9-row
operand uses 9/128 of the systolic array regardless of who schedules
it — and no fused realization can reach the estimate; together with the
VMEM capacity argument (the b6 volume pyramid is ~54 MB/image vs
~16 MB/core VMEM, so an in-VMEM fused loop cannot hold its operand)
this closes the spec with a measured negative result.

    python scripts/probe_fused_lookup.py [--dtype bf16] [--steps 20]
"""

import argparse
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).parent.parent))

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bench config, level 0: b6 @ 400x720 -> 50x90 coarse grid
B, NI, NJ = 6, 50, 90
K, H2, W2 = 9, 50, 90


def _xla_lookup(wy, corr, wx):
    t = jnp.einsum("bijkh,bijhw->bijkw", wy, corr,
                   preferred_element_type=jnp.float32)
    t = t.astype(wy.dtype)
    return jnp.einsum("bijkw,bijaw->bijka", t, wx,
                      preferred_element_type=jnp.float32)


def _xla_lookup_u8(wy, qvals, scale, wx):
    # the ops/corr.py quantized-tier branch: u8 rows stream from HBM and
    # dequantize in-register as the stage-1 einsum operand (zero point
    # 128); the per-sample scale lands once on the (K, K) output
    deq = qvals.astype(wy.dtype) - jnp.asarray(128, wy.dtype)
    t = jnp.einsum("bijkh,bijhw->bijkw", wy, deq,
                   preferred_element_type=jnp.float32)
    t = t.astype(wy.dtype)
    out = jnp.einsum("bijkw,bijaw->bijka", t, wx,
                     preferred_element_type=jnp.float32)
    return out * scale


def _stage1_kernel(wy_ref, corr_ref, out_ref):
    # one (b, i) row per grid cell: NJ serial (K, H2) x (H2, W2) dots
    for j in range(NJ):
        out_ref[0, 0, j] = jax.lax.dot_general(
            wy_ref[0, 0, j], corr_ref[0, 0, j], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _fused_kernel(wy_ref, corr_ref, wx_ref, out_ref):
    # both lookup stages per position; the (K, W2) intermediate stays in
    # registers/VMEM instead of round-tripping HBM between einsums
    for j in range(NJ):
        t = jax.lax.dot_general(
            wy_ref[0, 0, j], corr_ref[0, 0, j], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[0, 0, j] = jax.lax.dot_general(
            t.astype(wx_ref.dtype), wx_ref[0, 0, j], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def _pallas_stage1(wy, corr):
    return pl.pallas_call(
        _stage1_kernel,
        out_shape=jax.ShapeDtypeStruct((B, NI, NJ, K, W2), jnp.float32),
        grid=(B, NI),
        in_specs=[
            pl.BlockSpec((1, 1, NJ, K, H2), lambda b, i: (b, i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, NJ, H2, W2), lambda b, i: (b, i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, NJ, K, W2),
                               lambda b, i: (b, i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
    )(wy.reshape(B, NI, NJ, K, H2), corr)


def _pallas_fused(wy, corr, wx):
    return pl.pallas_call(
        _fused_kernel,
        out_shape=jax.ShapeDtypeStruct((B, NI, NJ, K, K), jnp.float32),
        grid=(B, NI),
        in_specs=[
            pl.BlockSpec((1, 1, NJ, K, H2), lambda b, i: (b, i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, NJ, H2, W2), lambda b, i: (b, i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, NJ, K, W2), lambda b, i: (b, i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, NJ, K, K),
                               lambda b, i: (b, i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
    )(wy.reshape(B, NI, NJ, K, H2), corr, wx.reshape(B, NI, NJ, K, W2))


def _sync(out):
    return jax.block_until_ready(out)


def _time(fn, *args, steps=20):
    out = fn(*args)  # compile
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / steps, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    dt = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32

    rs = np.random.RandomState(0)
    # realistic hat-matrix sparsity: windows around random in-range centers
    cy = rs.rand(B, NI, NJ, 1) * (H2 - 10) + 5
    cx = rs.rand(B, NI, NJ, 1) * (W2 - 10) + 5
    d = np.arange(-4, 5)
    wy = np.maximum(
        0.0, 1.0 - np.abs((cy + d)[..., None] - np.arange(H2))).astype("f4")
    wx = np.maximum(
        0.0, 1.0 - np.abs((cx + d)[..., None] - np.arange(W2))).astype("f4")
    corr = rs.randn(B, NI, NJ, H2, W2).astype("f4")

    wy, wx, corr = (jnp.asarray(a, dt) for a in (wy, wx, corr))

    flops_s1 = 2 * B * NI * NJ * K * H2 * W2
    flops_full = flops_s1 + 2 * B * NI * NJ * K * W2 * K

    xla = jax.jit(_xla_lookup)
    t_a, out_a = _time(xla, wy, corr, wx, steps=args.steps)
    print(f"A  XLA batched einsum (both stages): {t_a * 1e3:8.3f} ms"
          f"  ({flops_full / t_a / 1e12:.2f} TFLOP/s)")

    try:
        p1 = jax.jit(_pallas_stage1)
        t_b, out_b = _time(p1, wy, corr, steps=args.steps)
        print(f"B  Pallas stage-1 dots:              {t_b * 1e3:8.3f} ms"
              f"  ({flops_s1 / t_b / 1e12:.2f} TFLOP/s)")
        # bit-exactness of B is part of the PERF.md claim, so verify it
        # against the same stage-1 contraction XLA runs (f32 accumulate),
        # not just C's end-to-end output
        ref_s1 = jax.jit(lambda w, c: jnp.einsum(
            "bijkh,bijhw->bijkw", w, c,
            preferred_element_type=jnp.float32))(wy, corr)
        err_b = float(jnp.max(jnp.abs(out_b - ref_s1)))
        print(f"   max |B - A| = {err_b:.3e}  (stage-1 intermediate)")
    except Exception as e:  # pragma: no cover - probe reporting
        print(f"B  Pallas stage-1 dots: FAILED ({type(e).__name__}: "
              f"{str(e)[:140]})")

    try:
        pf = jax.jit(_pallas_fused)
        t_c, out_c = _time(pf, wy, corr, wx, steps=args.steps)
        print(f"C  Pallas fused both stages:         {t_c * 1e3:8.3f} ms"
              f"  ({flops_full / t_c / 1e12:.2f} TFLOP/s)")
        err = float(jnp.max(jnp.abs(
            out_c - out_a.reshape(B, NI, NJ, K, K))))
        print(f"   max |C - A| = {err:.3e}")
    except Exception as e:  # pragma: no cover - probe reporting
        print(f"C  Pallas fused both stages: FAILED ({type(e).__name__}: "
              f"{str(e)[:140]})")

    # D answers a byte-bound question, not a FLOP-bound one: the lookup
    # reads the whole volume row set every iteration, so streaming u8
    # moves 1/4 (f32) or 1/2 (bf16) of arm A's bytes. Quantization is
    # a one-time cost at pyramid build, so it stays outside the timer.
    from raft_meets_dicl_tpu.ops import quant as rmq

    level = rmq.quantize_level(jnp.asarray(corr, jnp.float32), "u8")
    scale = level.scale.astype(jnp.float32)
    t_d, out_d = _time(jax.jit(_xla_lookup_u8), wy, level.values, scale,
                       wx, steps=args.steps)
    err_d = float(jnp.max(jnp.abs(out_d - out_a)))
    ratio = jnp.dtype(dt).itemsize  # u8 volume is 1 B/element
    print(f"D  XLA u8 volume, in-reg dequant:    {t_d * 1e3:8.3f} ms"
          f"  ({flops_full / t_d / 1e12:.2f} TFLOP/s)")
    print(f"   max |D - A| = {err_d:.3e}  (step "
          f"{float(jnp.max(level.scale)):.3e}); volume bytes 1/{ratio} "
          f"of arm A")


if __name__ == "__main__":
    main()
