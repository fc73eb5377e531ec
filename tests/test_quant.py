"""Quantized matching-tier tests: ops, program keys, serve routing.

The ops half pins the numeric contract — symmetric per-sample scales
bound the quantize/dequantize roundtrip by half a step, the dequantizing
lookup stays within one step of the float lookup, and the int8
correlation pyramid tracks the float pyramid. The program half pins the
identity contract: ``quant=None`` is the *same registered program* as
the pre-quant builder (existing keys, AOT artifacts, and budget pins
untouched), each quant mode keys its own flag variant, serve routes only
the fast base rung and video warm frames onto the tier, and an
AOT-prepared replica serves quant classes with zero compiles. The
analysis half pins the integer-dtype byte accounting the tier's pinned
HBM savings depend on.
"""

import numpy as np
import pytest

import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu import evaluation, serve
from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu.analysis import collectives, cost
from raft_meets_dicl_tpu.metrics import functional as metrics
from raft_meets_dicl_tpu.models.input import ShapeBuckets
from raft_meets_dicl_tpu.ops import corr, quant
from raft_meets_dicl_tpu.serve import LadderSpec, Scheduler
from raft_meets_dicl_tpu.serve.session import ServeSession

pytestmark = pytest.mark.quant

@pytest.fixture(autouse=True)
def _quant_hygiene(monkeypatch):
    """Every test starts with the quant knobs unset."""
    monkeypatch.delenv("RMD_QUANT", raising=False)
    monkeypatch.delenv("RMD_QUANT_CLIP", raising=False)
    yield


TINY_QUANT_MODEL = {
    "name": "quant tiny", "id": "quant-tiny",
    "model": {"type": "raft/baseline",
              "parameters": {"corr-levels": 2, "corr-radius": 2,
                             "corr-channels": 32, "context-channels": 16,
                             "recurrent-channels": 16}},
    "loss": {"type": "raft/sequence"},
    "input": {"padding": {"type": "modulo", "mode": "zeros",
                          "size": [8, 8]}},
}


def _features(seed=0, shape=(2, 8, 12, 16)):
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp

    return (jnp.asarray(rng.normal(size=shape).astype(np.float32)),
            jnp.asarray(rng.normal(size=shape).astype(np.float32)))


# -- mode parsing -------------------------------------------------------------


def test_normalize_mode_spellings():
    assert quant.normalize_mode(None) is None
    assert quant.normalize_mode(False) is None
    assert quant.normalize_mode("off") is None
    assert quant.normalize_mode("") is None
    assert quant.normalize_mode(True) == "u8"
    assert quant.normalize_mode("u8") == "u8"
    assert quant.normalize_mode("UINT8") == "u8"
    assert quant.normalize_mode("i8") == "i8"
    assert quant.normalize_mode("int8") == "i8"
    assert quant.normalize_mode("s8") == "i8"
    with pytest.raises(ValueError):
        quant.normalize_mode("fp4")


# -- numeric contract ---------------------------------------------------------


@pytest.mark.parametrize("mode", ["u8", "i8"])
def test_quantize_dequantize_roundtrip_bounded_per_level(mode):
    f1, f2 = _features(seed=1)
    pyramid = corr.correlation_pyramid_direct(f1, f2, 3)
    for ref, level in zip(pyramid, quant.quantize_pyramid(pyramid, mode)):
        deq = np.asarray(quant.dequantize_level(level))
        step = np.asarray(level.scale)
        # symmetric rounding: at most half a step per element, per sample
        assert np.all(np.abs(deq - np.asarray(ref)) <= 0.5 * step + 1e-7)
        assert level.values.dtype == (np.uint8 if mode == "u8" else np.int8)
        assert level.scale.shape == (ref.shape[0], 1, 1, 1, 1)


def test_quantize_clip_shrinks_step_and_saturates():
    f1, f2 = _features(seed=2)
    (ref,) = corr.correlation_pyramid_direct(f1, f2, 1)
    full = quant.quantize_level(ref, "u8", clip=1.0)
    clipped = quant.quantize_level(ref, "u8", clip=0.5)
    # half the mapped range -> half the step size, and the tails saturate
    np.testing.assert_allclose(np.asarray(clipped.scale),
                               0.5 * np.asarray(full.scale), rtol=1e-6)
    assert int(np.sum(np.asarray(clipped.values) == 255)) > 0


def test_int8_pyramid_tracks_float_pyramid():
    f1, f2 = _features(seed=3)
    ref = corr.correlation_pyramid_direct(f1, f2, 3)
    got = quant.correlation_pyramid_int8(f1, f2, 3)
    for r, q in zip(ref, got):
        rel = (np.max(np.abs(np.asarray(quant.dequantize_level(q)) -
                             np.asarray(r)))
               / np.max(np.abs(np.asarray(r))))
        # two int8 roundings (features + volume storage) stay a few
        # percent of the level's dynamic range
        assert rel < 0.05


def test_quantized_lookup_within_one_step_of_float():
    import jax.numpy as jnp

    f1, f2 = _features(seed=4)
    pyramid = corr.correlation_pyramid_direct(f1, f2, 2)
    b, h, w, _ = f1.shape
    grid = np.stack(np.meshgrid(np.arange(w, dtype=np.float32),
                                np.arange(h, dtype=np.float32),
                                indexing="xy"), axis=-1)
    coords = jnp.asarray(np.tile(grid[None], (b, 1, 1, 1)) + 0.3)

    full = corr.lookup_pyramid_levels(pyramid, coords, 2)
    quantized = corr.lookup_pyramid_levels(
        quant.quantize_pyramid(pyramid, "u8"), coords, 2)
    for ref, got, level in zip(full, quantized,
                               quant.quantize_pyramid(pyramid, "u8")):
        # the lookup is a convex-ish contraction of per-element errors
        # bounded by step/2, plus bf16 rounding of the dequantized
        # operand — one full step is a safe envelope
        err = np.abs(np.asarray(got) - np.asarray(ref))
        assert np.max(err) <= float(np.max(np.asarray(level.scale))) + 1e-6


# -- program identity ---------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_quant():
    import jax
    import jax.numpy as jnp

    spec = models.load(TINY_QUANT_MODEL)
    rng = np.random.default_rng(5)
    base = rng.random((32, 48, 3), dtype=np.float32)
    img1 = jnp.asarray(base[None])
    img2 = jnp.asarray(np.roll(base, 2, axis=1)[None])
    target = np.zeros((1, 32, 48, 2), np.float32)
    target[..., 0] = 2.0
    variables = spec.model.init(jax.random.PRNGKey(0), img1, img2,
                                iterations=1)
    return spec, variables, img1, img2, jnp.asarray(target)


def test_quant_off_is_the_existing_rung_program(tiny_quant):
    spec, variables, img1, img2, _ = tiny_quant
    plain = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id)
    off = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id,
                                  quant=None)
    # quant=None is not a variant — it IS the pre-quant program: same
    # registered object, same key (so existing AOT artifacts and budget
    # pins keep resolving), no quant flag in the key at all
    assert off is plain
    assert "quant" not in dict(plain.key.flags)
    assert plain.quant is None

    flow_a, state_a = plain(variables, img1, img2)
    flow_b, state_b = evaluation.make_rung_fn(
        spec.model, 2, model_id=spec.id, quant="off")(variables, img1, img2)
    np.testing.assert_array_equal(np.asarray(flow_a), np.asarray(flow_b))
    np.testing.assert_array_equal(np.asarray(state_a["flow"]),
                                  np.asarray(state_b["flow"]))


def test_quant_modes_key_their_own_programs(tiny_quant):
    spec, _, _, _, _ = tiny_quant
    plain = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id)
    u8 = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id,
                                 quant="u8")
    i8 = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id,
                                 quant="int8")
    assert len({plain.key, u8.key, i8.key}) == 3
    assert dict(u8.key.flags)["quant"] == "'u8'"
    assert dict(i8.key.flags)["quant"] == "'i8'"
    assert u8.quant == "u8" and i8.quant == "i8"
    # builder idempotence: same mode -> same registered program
    assert u8 is evaluation.make_rung_fn(spec.model, 2, model_id=spec.id,
                                         quant="u8")


def test_quant_clip_keys_the_program_when_non_default(tiny_quant,
                                                      monkeypatch):
    spec, _, _, _, _ = tiny_quant
    default = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id,
                                      quant="u8")
    monkeypatch.setenv("RMD_QUANT_CLIP", "0.75")
    clipped = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id,
                                      quant="u8")
    assert clipped is not default
    assert dict(clipped.key.flags)["quant_clip"] == "0.75"
    assert "quant_clip" not in dict(default.key.flags)


@pytest.mark.parametrize("mode", ["u8", "i8"])
def test_quant_rung_epe_delta_bounded(tiny_quant, mode):
    spec, variables, img1, img2, target = tiny_quant
    import jax.numpy as jnp

    valid = jnp.ones(target.shape[:3], bool)
    full = evaluation.make_rung_fn(spec.model, 4, model_id=spec.id)
    quantized = evaluation.make_rung_fn(spec.model, 4, model_id=spec.id,
                                        quant=mode)
    flow_f, _ = full(variables, img1, img2)
    flow_q, _ = quantized(variables, img1, img2)
    epe_f = float(np.mean(np.asarray(
        metrics.end_point_error(flow_f, target, valid)["mean"])))
    epe_q = float(np.mean(np.asarray(
        metrics.end_point_error(flow_q, target, valid)["mean"])))
    # masked-metric EPE: the quant tier moves the estimate by well under
    # a tenth of a pixel (measured ~0.003 px at this config)
    assert abs(epe_q - epe_f) < 0.1
    assert float(np.max(np.abs(np.asarray(flow_q) - np.asarray(flow_f)))) \
        < 1.0


def test_quant_warm_variant_zero_init_parity(tiny_quant):
    import jax.numpy as jnp

    spec, variables, img1, img2, _ = tiny_quant
    base = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id,
                                   quant="u8")
    warm = evaluation.make_warm_fn(spec.model, 2, model_id=spec.id,
                                   quant="u8")
    flags = dict(warm.key.flags)
    assert flags["warm"] == "True" and flags["quant"] == "'u8'"

    flow_b, state_b = base(variables, img1, img2)
    flow_w, state_w = warm(variables, img1, img2,
                           jnp.zeros_like(state_b["flow"]))
    # zero carry == cold start on the SAME quant tier, bit for bit
    np.testing.assert_array_equal(np.asarray(flow_w), np.asarray(flow_b))
    np.testing.assert_array_equal(np.asarray(state_w["flow"]),
                                  np.asarray(state_b["flow"]))


# -- serve routing ------------------------------------------------------------


def test_serve_session_routes_fast_and_warm_onto_quant_tier():
    spec = models.load(TINY_QUANT_MODEL)
    lad = LadderSpec(rungs=(2, 4, 6))
    session = ServeSession(spec, ShapeBuckets([(32, 48)]), batch_size=1,
                           ladder=lad, video=True, quant="u8")
    assert session.quant == "u8"
    # fast class (base rung) + video warm frames quantize; the balanced
    # class's continuation rungs and the quality budget stay full
    # precision — escalation crosses onto the full-precision tier
    assert session._rung_fns[(2, False)].quant == "u8"
    assert session._warm_fn.quant == "u8"
    assert session._rung_fns[(2, True)].quant is None
    assert session._rung_fns[(6, False)].quant is None


def test_quant_session_serves_classes_and_reports_warm_pool():
    spec = models.load(TINY_QUANT_MODEL)
    session = ServeSession(spec, ShapeBuckets([(32, 48)]), batch_size=1,
                           ladder=LadderSpec(rungs=(2, 4, 6)),
                           quant="u8")
    outcomes = session.warm_pool()
    by_rung = {o.get("rung"): o for o in outcomes}
    assert by_rung["base:2"]["quant"] == "u8"
    assert "quant" not in by_rung["full:6"]

    c0 = session.compiles()
    rng = np.random.default_rng(6)
    img1 = rng.random((30, 44, 3), dtype=np.float32)
    img2 = rng.random((30, 44, 3), dtype=np.float32)
    sched = Scheduler(session, batch_size=1, max_wait_ms=2.0).start()
    try:
        results = {k: sched.submit(img1, img2, klass=k)
                   .result(timeout=60.0) for k in serve.CLASSES}
    finally:
        sched.stop(drain=True)
    assert results["fast"].iterations == 2
    assert results["quality"].iterations == 6
    for res in results.values():
        assert res.flow.shape == (30, 44, 2)
    # every class rode warm programs — the quant tier compiles in the
    # pool, never on a request
    assert session.compiles() == c0


def test_aot_prepared_replica_serves_quant_classes_zero_compile(tmp_path):
    cfg = dict(TINY_QUANT_MODEL, id="quant-aot", name="quant aot")
    lad = LadderSpec(rungs=(2, 4, 6))
    buckets = [(32, 48)]
    programs.enable_aot(str(tmp_path))
    try:
        programs.reset()
        s1 = ServeSession(models.load(cfg), ShapeBuckets(buckets),
                          batch_size=1, ladder=lad, quant="u8")
        out1 = s1.warm_pool()
        # prebuild exports every program — the quant base rung included
        assert all(o["aot_saves"] == 1 for o in out1)

        # fresh replica: only the exported artifacts remain
        programs.reset()
        s2 = ServeSession(models.load(cfg), ShapeBuckets(buckets),
                          batch_size=1, ladder=lad, quant="u8")
        out2 = s2.warm_pool()
        assert [o["compiles"] for o in out2] == [0] * len(out2)
        assert all(o["aot_hits"] == 1 for o in out2)

        rng = np.random.default_rng(7)
        img1 = rng.random((32, 48, 3), dtype=np.float32)
        img2 = rng.random((32, 48, 3), dtype=np.float32)
        sched = Scheduler(s2, batch_size=1, max_wait_ms=2.0).start()
        try:
            res = sched.submit(img1, img2, klass="fast").result(timeout=60.0)
        finally:
            sched.stop(drain=True)
        assert res.flow.shape == (32, 48, 2)
        assert s2.compiles() == 0
    finally:
        programs.disable_aot()


# -- analysis: integer-dtype byte accounting ----------------------------------


def test_cost_walker_counts_sub_f32_operand_bytes():
    import jax
    import jax.numpy as jnp

    # seeded regression: a u8 volume streamed through a dequantizing dot
    # must be charged 1 B/element — a 4 B fallback would erase the quant
    # tier's pinned HBM saving
    def dequant_dot(q, w):
        deq = q.astype(jnp.bfloat16) - jnp.asarray(128, jnp.bfloat16)
        return jnp.einsum("bkh,bhw->bkw", w, deq,
                          preferred_element_type=jnp.float32)

    q = jnp.zeros((2, 64, 96), jnp.uint8)
    w = jnp.zeros((2, 9, 64), jnp.bfloat16)
    text = jax.jit(dequant_dot).lower(q, w).as_text()
    ops = cost.op_costs(text, expect_bf16=True)
    converts = [o for o in ops if o.op == "convert"
                and "ui8" in text.splitlines()[o.line - 1]]
    assert converts, "u8 convert not found in lowered module"
    n = 2 * 64 * 96
    # operand read at 1 B/elem + bf16 result write at 2 B/elem
    assert any(o.bytes == n * 1 + n * 2 for o in converts)

    # int8 MXU dot: both operands at 1 B/element, i32 accumulate
    def int8_dot(a, b):
        return jnp.einsum("bik,bjk->bij", a, b,
                          preferred_element_type=jnp.int32)

    a = jnp.zeros((1, 16, 32), jnp.int8)
    b = jnp.zeros((1, 24, 32), jnp.int8)
    text = jax.jit(int8_dot).lower(a, b).as_text()
    dots = [o for o in cost.op_costs(text, expect_bf16=False)
            if o.klass == "dot"]
    assert len(dots) == 1
    expected = (16 * 32 + 24 * 32) * 1 + 16 * 24 * 4
    assert dots[0].bytes == expected


def test_tensor_nbytes_narrow_and_f8_widths():
    # direct width pins: sub-byte ints round up per tensor, f8 is 1 B,
    # unknown dtypes (and only those) keep the 4 B fallback
    assert cost._tensor_nbytes((8, 8), "ui8") == 64
    assert cost._tensor_nbytes((8, 8), "i8") == 64
    assert cost._tensor_nbytes((8, 8), "i4") == 32
    assert cost._tensor_nbytes((3,), "i4") == 2      # ceil(3 * 4 / 8)
    assert cost._tensor_nbytes((8, 8), "f8e4m3fn") == 64
    assert cost._tensor_nbytes((8, 8), "f8e5m2") == 64
    assert cost._tensor_nbytes((2,), "mystery") == 8

    # compiled-HLO spellings used by the collective-schedule walker
    assert collectives._shape_bytes("u8", "8,8") == 64
    assert collectives._shape_bytes("u4", "8,8") == 32
    assert collectives._shape_bytes("f8e4m3fn", "8,8") == 64
