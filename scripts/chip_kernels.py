#!/usr/bin/env python3
"""Compile every Pallas kernel family with Mosaic and check it on the chip.

The kernels in ``ops/pallas.py`` give way to their XLA references off-TPU,
so the CPU test suite only ever runs them through the Pallas interpreter.
This script is the other half: on a TPU it calls each family's kernel
entry points directly (no dispatch, ``interpret=False``), forward and
backward, at the shape where a model dispatches to it, and compares with
the family's own ``_*_reference`` evaluated at highest matmul precision:

- ``combine`` — the Up8 convex combine in the ``raft/baseline`` train step
  at b6 400x720, 12 iterations: 324,000 rows of 576 logits;
- ``wcp`` — the windowed correlation pyramid of ``raft/fs`` at
  cfg/strategy/highres/raft-fs.hd1k-1080p.yaml: b1 1072x2560, C=256,
  r=4, with all 4 levels on the kernel and with the prefix the
  volume/windowed dispatch leaves on it; and the benchmark cell's shape,
  b1 1088x1920 (136x240), where that prefix is level 0 alone, there on
  three fields of centres (zero
  flow, a smooth field, a Things-like field of objects that move up to
  40 grid cells against their background), each with the milliseconds a
  call of forward, ``df1`` and ``df2`` and the share of its blocks that
  one slab serves (``ops.pallas.wcp_shared_share``);
- ``sw`` — the fused DICL window sampler at ``raft+dicl/ml``'s reference
  shape: b6 384x704, C=32, r=4, levels 48x88 down to 6x11.

A kernel the compiler refuses is reported with the compiler's message,
not skipped. Results go to ``chiprun_out/kernels.json`` and, one line per
case, to stdout; the exit code is 1 if any case failed. Times are printed
for the record (one warm call each); they are not a benchmark.

    chiprun -- python scripts/chip_kernels.py [combine] [wcp] [sw] [PART...]

(``PART``: run only the cases whose name contains one of these.)
"""

import json
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from raft_meets_dicl_tpu.models.impls.raft_fs import volume_level_split  # noqa: E402
from raft_meets_dicl_tpu.ops import pallas as K  # noqa: E402
from raft_meets_dicl_tpu.ops.pool import avg_pool2d  # noqa: E402

# max |kernel - reference| over max |reference|; the reference runs on
# the same (bf16-representable) values at highest precision, the kernels
# accumulate in f32, and a bf16 output rounds at 2^-8
TOLERANCE = {"float32": 2e-3, "bfloat16": 1e-2}


def _err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-12))


def _timed(fn, *args):
    """(result, cold seconds incl. compile, warm milliseconds)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, cold, 1e3 * (time.perf_counter() - t0)


def _highest(fn):
    def wrapped(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(wrapped)


def _coords(rng, b, h, w):
    """Window centers as the recurrence produces them: the pixel grid
    plus a smooth flow (blocks one slab serves), per-pixel noise (blocks
    that spread past the slab) and a strip thrown out of bounds."""
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    fx = 12.0 * np.sin(yy / 17.0) + rng.normal(0, 0.7, (b, h, w))
    fy = 9.0 * np.cos(xx / 23.0) + rng.normal(0, 0.7, (b, h, w))
    fx[:, : h // 8] += rng.normal(0, 6.0, (b, h // 8, w))
    fx[:, -2:] += 3.0 * w
    return jnp.asarray(np.stack((xx + fx, yy + fy), -1), jnp.float32)


# -- combine -----------------------------------------------------------------


def case_combine(dtype, m=12 * 6 * 50 * 90):
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(0, 2.0, (m, 576)), dtype)
    win = jnp.asarray(rng.normal(0, 4.0, (m, 18)), jnp.float32)
    dout = jnp.asarray(rng.normal(0, 1.0, (m, 128)), jnp.float32)
    inv_temp = 0.25

    def ref_bwd(lg, wn, do):
        _, vjp = jax.vjp(lambda a, b: K._combine_reference(a, b, inv_temp),
                         lg.astype(jnp.float32), wn)
        return vjp(do)

    out, cold_f, ms_f = _timed(
        jax.jit(lambda a, b: K._run_fwd(a, b, inv_temp)), logits, win)
    (dlg, dwn), cold_b, ms_b = _timed(
        jax.jit(lambda a, b, c: K._run_bwd(a, b, c, inv_temp)),
        logits, win, dout)
    want, _, ref_ms_f = _timed(
        _highest(lambda a, b: K._combine_reference(a, b, inv_temp)),
        logits, win)
    (wlg, wwn), _, ref_ms_b = _timed(_highest(ref_bwd), logits, win, dout)
    return {
        "shape": f"rows {m} x 576", "dtype": jnp.dtype(dtype).name,
        "err": {"fwd": _err(out, want), "dlogits": _err(dlg, wlg),
                "dwin": _err(dwn, wwn)},
        "compile_s": round(cold_f + cold_b, 2),
        "ms": {"fwd": ms_f, "bwd": ms_b, "ref_fwd": ref_ms_f,
               "ref_bwd": ref_ms_b},
    }


# -- wcp ---------------------------------------------------------------------

_ROWS = 17   # reference row chunk: its (rows, W, 81, C) gathers are GBs
_CALLS = 10  # calls between two syncs, for the milliseconds a call


def _grid(h, w):
    return np.meshgrid(np.arange(h, dtype=np.float64),
                       np.arange(w, dtype=np.float64), indexing="ij")


def _field_zero(rng, b, h, w):
    yy, xx = _grid(h, w)
    return np.stack((xx, yy), -1)[None].repeat(b, 0)


def _field_smooth(rng, b, h, w):
    """A camera's motion: a zoom of a few percent, a pan and a slow wave."""
    yy, xx = _grid(h, w)
    fx = 0.04 * (xx - w / 2) + 3.0 * np.sin(yy / 40.0) + 2.5
    fy = 0.03 * (yy - h / 2) + 2.0 * np.cos(xx / 60.0) - 1.5
    return np.stack((xx + fx, yy + fy), -1)[None].repeat(b, 0)


def _field_things(rng, b, h, w, objects=14, reach=40.0):
    """Piecewise smooth, as FlyingThings3D at 1/8: the smooth background
    and on it objects (ellipses and boxes, a tenth to a half of the frame
    across), each with an affine motion of its own whose displacement
    reaches ``reach`` grid cells, the later drawn over the earlier, with
    discontinuities along every edge."""
    yy, xx = _grid(h, w)
    out = _field_smooth(rng, b, h, w)
    for bi in range(b):
        for _ in range(objects):
            cy, cx = rng.uniform(0, h), rng.uniform(0, w)
            ry, rx = rng.uniform(h / 10, h / 2) / 2, rng.uniform(w / 10,
                                                                w / 2) / 2
            if rng.random() < 0.5:
                inside = ((yy - cy) / ry) ** 2 + ((xx - cx) / rx) ** 2 < 1
            else:
                inside = (abs(yy - cy) < ry) & (abs(xx - cx) < rx)
            t = rng.uniform(-reach, reach, 2)
            a = rng.normal(0, 0.03, (2, 2))
            fx = t[0] + a[0, 0] * (xx - cx) + a[0, 1] * (yy - cy)
            fy = t[1] + a[1, 0] * (xx - cx) + a[1, 1] * (yy - cy)
            out[bi][inside] = np.stack((xx + fx, yy + fy), -1)[inside]
    return out


def _field_noisy(rng, b, h, w):
    return np.asarray(_coords(rng, b, h, w))


FIELDS = {"zero": _field_zero, "smooth": _field_smooth,
          "things": _field_things, "noisy": _field_noisy}


def _ms_a_call(fn, *args):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(_CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / _CALLS


def case_wcp(levels, dtype=jnp.bfloat16, h=134, w=320, c=256,
             field="noisy"):
    b, radius = 1, 4
    rng = np.random.default_rng(1)
    f1 = jnp.asarray(rng.normal(0, 1.0, (b, h, w, c)), dtype)
    f2 = [jnp.asarray(rng.normal(0, 1.0, (b, h, w, c)), dtype)]
    for _ in range(1, levels):
        f2.append(avg_pool2d(f2[-1], 2))
    f2 = tuple(f2)
    coords = jnp.asarray(FIELDS[field](rng, b, h, w), jnp.float32)
    dout = jnp.asarray(rng.normal(0, 1.0, (b, h, w, levels * 81)),
                       jnp.float32)
    if not K._wcp_fits_vmem(f1, f2, radius):
        raise RuntimeError("_wcp_fits_vmem says no: dispatch would take "
                           "the XLA path at this shape")

    fwd = jax.jit(lambda a, bb, cc: K._wcp_fwd_tpu(a, bb, cc, radius))
    bwd = jax.jit(lambda a, bb, cc, d: K._wcp_bwd_tpu(a, bb, cc, d, radius))
    out, cold_f, _ = _timed(fwd, f1, f2, coords)
    (df1, df2), cold_b, _ = _timed(bwd, f1, f2, coords, dout)
    # each kernel alone: the other calls of the jitted pair are dead code
    ms = {"fwd": _ms_a_call(fwd, f1, f2, coords),
          "df1": _ms_a_call(jax.jit(lambda *a: bwd(*a)[0]),
                            f1, f2, coords, dout),
          "df2": _ms_a_call(jax.jit(lambda *a: bwd(*a)[1]),
                            f1, f2, coords, dout)}

    f2_32 = tuple(x.astype(jnp.float32) for x in f2)

    def ref_chunk(f1c, f2s, cc, dc):
        out, vjp = jax.vjp(
            lambda a, bb: K._wcp_reference(a, bb, cc, radius),
            f1c.astype(jnp.float32), f2s)
        return (out,) + vjp(dc)

    ref_chunk = _highest(ref_chunk)
    outs, df1s, df2_sum = [], [], None
    t0 = time.perf_counter()
    for r0 in range(0, h, _ROWS):
        sl = slice(r0, min(h, r0 + _ROWS))
        o, g1, g2 = ref_chunk(f1[:, sl], f2_32, coords[:, sl], dout[:, sl])
        outs.append(o)
        df1s.append(g1)
        df2_sum = g2 if df2_sum is None else jax.tree.map(
            jnp.add, df2_sum, g2)
    jax.block_until_ready(df2_sum)
    ref_s = time.perf_counter() - t0
    report = {
        "shape": f"b1 {h}x{w} C={c} r={radius} levels={levels} "
                 f"field={field}",
        "dtype": jnp.dtype(dtype).name,
        "err": {"fwd": _err(out, jnp.concatenate(outs, 1)),
                "df1": _err(df1, jnp.concatenate(df1s, 1)),
                **{f"df2[{i}]": _err(g, w_)
                   for i, (g, w_) in enumerate(zip(df2, df2_sum))}},
        "compile_s": round(cold_f + cold_b, 2),
        "ms": {**ms, "ref_fwd_bwd_incl_compile": 1e3 * ref_s},
        "shared_share": round(float(K.wcp_shared_share(
            coords, [x.shape[1:3] for x in f2], radius)), 4),
    }
    return report


# -- sw ----------------------------------------------------------------------


def case_sw(dtype, b=6, h=48, w=88, c=32):
    radius, levels = 4, 4
    rng = np.random.default_rng(2)
    coords = _coords(rng, b, h, w)
    report = {"shape": f"b{b} {h}x{w}..{h >> 3}x{w >> 3} C={c} r={radius}",
              "dtype": jnp.dtype(dtype).name,
              "err": {}, "compile_s": 0.0, "ms": {}}
    for lvl in range(levels):
        f2 = jnp.asarray(
            rng.normal(0, 1.0, (b, h >> lvl, w >> lvl, c)), dtype)
        cl = coords / 2 ** lvl
        dout = jnp.asarray(rng.normal(0, 1.0, (b, 9, 9, h, w, c)),
                           jnp.float32)
        if not K._sw_fits_vmem(f2, cl, radius):
            raise RuntimeError(f"_sw_fits_vmem says no at level {lvl}")

        def ref(f2_, cc, do):
            out, vjp = jax.vjp(lambda a: K._sw_reference(a, cc, radius),
                               f2_.astype(jnp.float32))
            return out, vjp(do)[0]

        out, cold_f, ms_f = _timed(
            jax.jit(lambda a, cc: K._sw_fwd_tpu(a, cc, radius)), f2, cl)
        df2, cold_b, ms_b = _timed(
            jax.jit(lambda a, cc, d: K._sw_bwd_tpu(a, cc, d, radius)),
            f2, cl, dout)
        (want, wdf2), _, ref_ms = _timed(_highest(ref), f2, cl, dout)
        report["err"][f"fwd[{lvl}]"] = _err(out, want)
        report["err"][f"df2[{lvl}]"] = _err(df2, wdf2)
        report["compile_s"] = round(report["compile_s"] + cold_f + cold_b, 2)
        report["ms"][f"fwd[{lvl}]"] = ms_f
        report["ms"][f"bwd[{lvl}]"] = ms_b
        report["ms"][f"ref_fwd_bwd[{lvl}]"] = ref_ms
    return report


def cases(families):
    if "combine" in families:
        yield "combine/bf16", case_combine, (jnp.bfloat16,)
        yield "combine/f32", case_combine, (jnp.float32,)
    if "wcp" in families:
        # the prefix the dispatch leaves on the kernel at this shape
        n_win = volume_level_split((1, 134, 320), 4, 2)
        for levels in sorted({4, n_win} - {0}):
            yield f"wcp/levels{levels}/block", case_wcp, (levels,)
        # the cell fs-train-1080p: b1 1088x1920, level 0 alone (levels
        # 1-3 are materialised volumes there)
        n_win = volume_level_split((1, 136, 240), 4, 2)
        for field in ("zero", "smooth", "things"):
            yield f"wcp/136x240/levels{n_win}/block/{field}", case_wcp, (
                n_win, jnp.bfloat16, 136, 240, 256, field)
    if "sw" in families:
        yield "sw/f32", case_sw, (jnp.float32,)
        yield "sw/bf16", case_sw, (jnp.bfloat16,)


def main(argv):
    known = ("combine", "wcp", "sw")
    families = [a for a in argv if a in known] or list(known)
    only = [a for a in argv if a not in known]   # substrings of case names
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_kernels: needs a TPU, jax found '{dev.platform}' — "
                 f"off-TPU the kernels run interpreted, in tests/")
    header = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices()),
              "default_backend": jax.default_backend(),
              "jax": jax.__version__}
    print(json.dumps(header), flush=True)

    results, failed = {}, []
    for name, fn, args in cases(families):
        if only and not any(part in name for part in only):
            continue
        try:
            rep = fn(*args)
            rep["compiled"] = True
            rep["tolerance"] = TOLERANCE[rep["dtype"]]
            rep["ok"] = max(rep["err"].values()) <= rep["tolerance"]
            rep["ms"] = {k: round(v, 2) for k, v in rep["ms"].items()}
        except Exception as e:  # noqa: BLE001 - a refusal is a result
            traceback.print_exc()
            rep = {"compiled": False, "ok": False,
                   "error": f"{type(e).__name__}: {str(e)[:2000]}"}
        results[name] = rep
        if not rep["ok"]:
            failed.append(name)
        print(f"{'OK  ' if rep['ok'] else 'FAIL'} {name}: "
              + json.dumps({k: v for k, v in rep.items() if k != "ok"}),
              flush=True)

    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / "kernels.json").write_text(
        json.dumps({"device": header, "cases": results}, indent=1))
    print(f"chip_kernels: {len(results) - len(failed)}/{len(results)} "
          f"cases ok" + (f", failed: {failed}" if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
