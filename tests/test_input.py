"""Input pipeline tests: padding, range scaling, validation, loader."""

import numpy as np
import pytest

from raft_meets_dicl_tpu.data.collection import Metadata, SampleArgs, SampleId
from raft_meets_dicl_tpu.models import input as minput


def _meta(h, w, b=1):
    return [
        Metadata(True, "t", SampleId("s", SampleArgs(), SampleArgs()), ((0, h), (0, w)))
        for _ in range(b)
    ]


def _sample(h=30, w=40, b=1):
    img1 = np.random.rand(b, h, w, 3).astype(np.float32)
    img2 = np.random.rand(b, h, w, 3).astype(np.float32)
    flow = np.random.randn(b, h, w, 2).astype(np.float32)
    valid = np.ones((b, h, w), bool)
    return img1, img2, flow, valid, _meta(h, w, b)


def test_modulo_padding_shapes_and_extents():
    pad = minput.ModuloPadding("zeros", [16, 8])  # (w multiple, h multiple)
    img1, img2, flow, valid, meta = pad(*_sample(30, 40))

    assert img1.shape == (1, 32, 48, 3)
    assert flow.shape == (1, 32, 48, 2)
    assert valid.shape == (1, 32, 48)
    assert not valid[0, 31, 0]  # padded rows invalid
    assert meta[0].original_extents == ((0, 30), (0, 40))


def test_modulo_padding_center_alignment():
    pad = minput.ModuloPadding("zeros", [16, 8], align_hz="center", align_vt="center")
    img1, _, _, _, meta = pad(*_sample(30, 40))
    (y0, y1), (x0, x1) = meta[0].original_extents
    assert (y0, y1) == (1, 31)
    assert (x0, x1) == (4, 44)
    assert img1[0, 0].sum() == 0  # padded border


def test_modulo_padding_torch_mode_aliases():
    pad = minput.ModuloPadding("torch.replicate", [16, 8])
    img1, *_ = pad(*_sample(30, 40))
    # replicated edge rows equal the last content row
    np.testing.assert_array_equal(img1[0, 30], img1[0, 29])


def test_input_range_scaling():
    spec = minput.InputSpec(clip=(0, 1), range=(-1, 1))
    src = [_sample()]
    inp = spec.apply(src)
    img1, *_ = inp[0]
    assert img1.min() >= -1.0 and img1.max() <= 1.0


def test_input_spec_roundtrip():
    cfg = {
        "clip": [0, 1],
        "range": [-1, 1],
        "padding": {"type": "modulo", "mode": "zeros", "size": [8, 8]},
    }
    spec = minput.InputSpec.from_config(cfg)
    cfg2 = spec.get_config()
    assert cfg2["padding"]["size"] == [8, 8]
    spec2 = minput.InputSpec.from_config(cfg2)
    assert spec2.padding.mode == "zeros"


def test_adapter_marks_nonfinite_invalid():
    img1, img2, flow, valid, meta = _sample()
    img1[0, 0, 0, 0] = np.nan

    adapter = minput.JaxAdapter([(img1, img2, flow, valid, meta)])
    *_, meta_out = adapter[0]
    assert not meta_out[0].valid


def test_adapter_scrubs_nonfinite_flow():
    img1, img2, flow, valid, meta = _sample()
    flow[0, 1, 1, 0] = np.inf

    adapter = minput.JaxAdapter([(img1, img2, flow, valid, meta)])
    _, _, flow_out, _, meta_out = adapter[0]
    assert not meta_out[0].valid
    assert np.isfinite(flow_out).all()
    assert flow_out.max() <= minput.FLOW_INF


def test_adapter_empty_valid_mask():
    img1, img2, flow, valid, meta = _sample()
    valid[:] = False

    adapter = minput.JaxAdapter([(img1, img2, flow, valid, meta)])
    *_, meta_out = adapter[0]
    assert not meta_out[0].valid


def test_loader_batches_and_drop_last():
    source = [_sample() for _ in range(5)]
    adapter = minput.JaxAdapter(source)

    loader = adapter.loader(batch_size=2, shuffle=False, num_workers=0, drop_last=True)
    batches = list(loader)
    assert len(batches) == 2
    assert all(b[0].shape[0] == 2 for b in batches)

    loader = adapter.loader(batch_size=2, shuffle=False, num_workers=2, drop_last=False)
    batches = list(loader)
    assert len(batches) == 3
    assert batches[-1][0].shape[0] == 1


def test_collate_concatenates_prebatched():
    s1 = _sample(b=2)
    s2 = _sample(b=1)
    img1, img2, flow, valid, meta = minput.collate([s1, s2])
    assert img1.shape[0] == 3
    assert len(meta) == 3


def test_wrap_single():
    spec = minput.InputSpec()
    img = np.random.rand(30, 40, 3).astype(np.float32)
    inp = spec.wrap_single(img, img)
    img1, img2, flow, valid, meta = inp[0]
    assert img1.shape == (1, 30, 40, 3)
    assert flow is None


def test_loader_shard_partitions_epoch():
    """shard=(i, n) loaders draw disjoint, equal-length slices of the same
    (same-seed) epoch order — the per-process slice in multi-host runs."""
    source = []
    for i in range(9):
        s = _sample()
        # tag each sample so shard membership is observable downstream
        s[0][..., 0] = float(i)
        source.append(s)
    adapter = minput.JaxAdapter(source)

    def sample_keys(shard):
        loader = adapter.loader(batch_size=2, shuffle=True, num_workers=0,
                                seed=7, shard=shard)
        keys = []
        for batch in loader:
            keys += [float(v) for v in batch[0][:, 0, 0, 0]]
        return keys

    k0 = sample_keys((0, 2))
    k1 = sample_keys((1, 2))

    # equal share (floor of 9/2 = 4 each), disjoint
    assert len(k0) == len(k1) == 4
    assert not set(k0) & set(k1)

    # same number of batches on every shard (lockstep stepping)
    l0 = adapter.loader(batch_size=2, shuffle=True, seed=7, shard=(0, 2))
    l1 = adapter.loader(batch_size=2, shuffle=True, seed=7, shard=(1, 2))
    assert len(l0) == len(l1) == 2


# -- the batch assembled in place (PR 42) ------------------------------------


class _TaggedSource:
    """``n`` indices of ``k`` rows each; row ``i`` of index ``x`` holds the
    value ``10 * x + i`` everywhere and is named so in its metadata.
    ``failing`` indices raise (``times`` times each, None = always)."""

    def __init__(self, n, k=1, shape=(6, 8), failing=(), times=None,
                 shapes=None, rows=None):
        self.n, self.k, self.shape = n, k, shape
        self.shapes, self.rows = shapes or {}, rows or {}
        self.failing = {int(i): times for i in failing}
        self.calls = []

    def __len__(self):
        return self.n

    def __getitem__(self, x):
        x = int(x)
        self.calls.append(x)
        if x in self.failing:
            left = self.failing[x]
            if left is None:
                raise IOError(f"sample {x}")
            if left > 0:
                self.failing[x] = left - 1
                raise IOError(f"sample {x}")
        k = self.rows.get(x, self.k)
        h, w = self.shapes.get(x, self.shape)
        tags = (10 * x + np.arange(k)).astype(np.float32)
        img1 = np.broadcast_to(tags[:, None, None, None], (k, h, w, 3)).copy()
        img2 = (img1 + 0.5).astype(np.float16)
        flow = np.broadcast_to(-tags[:, None, None, None], (k, h, w, 2)).copy()
        valid = (np.arange(h * w).reshape(1, h, w) + tags[:, None, None]
                 .astype(int)) % 3 > 0
        meta = [Metadata(True, "tagged",
                         SampleId(f"x{x}r{i}", SampleArgs(), SampleArgs()),
                         ((0, h), (0, w))) for i in range(k)]
        return img1, img2, flow, valid, meta


class _Neighbour(_TaggedSource):
    """Index 7 answered by index 8: what a substitution leaves."""

    def __getitem__(self, x):
        return super().__getitem__(8 if int(x) == 7 else x)


def _reference_stream(source, epochs, batch_size, shuffle, drop_last, seed,
                      shard):
    """What the loader has always delivered, written plainly: the epoch's
    order, chunks of it, ``np.concatenate`` and then ``[perm]`` with one
    ``permutation(rows)`` a batch from the same Generator."""
    rng = np.random.default_rng(seed)
    for _ in range(epochs):
        order = rng.permutation(len(source)) if shuffle \
            else np.arange(len(source))
        if shard is not None:
            order = order[shard[0]::shard[1]][: len(source) // shard[1]]
        for start in range(0, len(order), batch_size):
            chunk = order[start:start + batch_size]
            if drop_last and len(chunk) < batch_size:
                break
            samples = [source[i] for i in chunk]
            arrays = [np.concatenate([s[a] for s in samples], axis=0)
                      for a in range(4)]
            meta = [m for s in samples for m in s[4]]
            if shuffle and arrays[0].shape[0] > 1:
                perm = rng.permutation(arrays[0].shape[0])
                arrays = [a[perm] for a in arrays]
                meta = [meta[i] for i in perm]
            yield (*arrays, meta)


def _assert_same_batch(got, want):
    for g, w in zip(got[:4], want[:4]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    assert [str(m.sample_id) for m in got[4]] == \
        [str(m.sample_id) for m in want[4]]
    assert got[4] == want[4]


@pytest.mark.parametrize("shard", [None, (1, 2)], ids=["whole", "shard"])
@pytest.mark.parametrize("drop_last", [False, True], ids=["keep", "drop"])
@pytest.mark.parametrize("shuffle", [False, True], ids=["ordered", "shuffled"])
@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
@pytest.mark.parametrize("workers", [0, 4], ids=["inline", "pool"])
def test_loader_stream_is_the_plain_reference(workers, k, shuffle, drop_last,
                                              shard):
    """The batch assembled in place, by the pool's workers or one sample
    after the other, is concatenate-then-permute to the bit and in ``meta``
    order, over two epochs of one seeded loader."""
    source = _TaggedSource(23, k=k)
    loader = minput.Loader(source, batch_size=3, shuffle=shuffle,
                           num_workers=workers, drop_last=drop_last, seed=11,
                           shard=shard)
    got = [b for _ in range(2) for b in loader]
    want = list(_reference_stream(_TaggedSource(23, k=k), 2, 3, shuffle,
                                  drop_last, 11, shard))
    assert len(got) == len(want) == 2 * len(loader) > 4
    for g, w in zip(got, want):
        _assert_same_batch(g, w)


@pytest.mark.parametrize("shuffle", [False, True], ids=["ordered", "shuffled"])
def test_collate_is_the_plain_reference_for_uneven_samples(shuffle):
    """The serial form takes samples of differing row counts (the grouped
    and the inline path hand it whatever the source returned)."""
    source = _TaggedSource(5, rows={1: 3, 3: 2})
    samples = [source[i] for i in range(5)]
    ours, rng = np.random.default_rng(5), np.random.default_rng(5)
    got = minput.collate(samples, shuffle, ours)
    arrays = [np.concatenate([s[a] for s in samples]) for a in range(4)]
    meta = [m for s in samples for m in s[4]]
    if shuffle:
        perm = rng.permutation(8)
        arrays, meta = [a[perm] for a in arrays], [meta[i] for i in perm]
    _assert_same_batch(got, (*arrays, meta))
    # the Generator was asked for the same draws: one, or none
    assert ours.bit_generator.state == rng.bit_generator.state


@pytest.mark.parametrize("workers", [0, 4], ids=["inline", "pool"])
def test_loader_mixed_shapes_raise_collates_error(workers):
    source = _TaggedSource(6, shapes={4: (4, 8)})
    loader = minput.Loader(source, batch_size=3, num_workers=workers)
    batches = iter(loader)
    next(batches)
    with pytest.raises(ValueError) as exc:
        next(batches)
    msg = str(exc.value)
    assert msg.startswith("cannot batch samples of mixed shapes: "
                          "6x8 (dataset 'tagged') vs 4x8 (dataset 'tagged')")
    assert "group_by_shape=True" in msg
    # one mixed-resolution sample a batch stays possible
    shapes = [b[0].shape[1:3] for b in minput.Loader(
        source, batch_size=1, num_workers=workers)]
    assert shapes == [(6, 8)] * 4 + [(4, 8), (6, 8)]


@pytest.mark.parametrize("workers", [0, 4], ids=["inline", "pool"])
def test_loader_retries_and_substitutes_into_the_right_rows(workers):
    """Index 4 fails once and is retried; index 7 fails for good and its
    neighbour 8 takes its rows, wherever the in-batch shuffle put them."""
    want = list(_reference_stream(_Neighbour(12, k=2), 1, 4, True, False, 3,
                                  None))
    src = _TaggedSource(12, k=2, failing=(4,), times=1)
    src.failing[7] = None
    loader = minput.Loader(src, batch_size=4, shuffle=True,
                           num_workers=workers, seed=3, retries=1,
                           bad_sample_budget=4)
    got = list(loader)
    assert len(got) == len(want) == 3
    for g, w in zip(got, want):
        _assert_same_batch(g, w)
    assert src.calls.count(4) == 2 and src.calls.count(7) == 2
    assert loader._bad_samples == 1
    assert all(m.fetch_s is not None for b in got for m in b[4])


def test_loader_workers_exception_reaches_the_consumer():
    source = _TaggedSource(8, failing=(5,))
    loader = minput.Loader(source, batch_size=2, num_workers=4, retries=0,
                           bad_sample_budget=0)
    batches = iter(loader)
    next(batches), next(batches)
    with pytest.raises(IOError, match="sample 5"):
        next(batches)


def test_loader_pool_refuses_a_sample_of_another_row_count():
    """The pool lays a batch out when it submits it, from the rows the
    iteration's first sample had; the serial paths take what comes."""
    source = _TaggedSource(6, rows={4: 2})
    with pytest.raises(ValueError, match="2 row.s. in a batch laid out "
                                         "for 1 an index"):
        list(minput.Loader(source, batch_size=3, num_workers=4))
    inline = list(minput.Loader(source, batch_size=3, num_workers=0))
    assert [b[0].shape[0] for b in inline] == [3, 4]


@pytest.mark.parametrize("workers", [0, 4], ids=["inline", "pool"])
def test_a_batch_handed_out_is_never_written_again(workers):
    loader = minput.Loader(_TaggedSource(16, k=2), batch_size=2,
                           shuffle=True, num_workers=workers, seed=2)
    batches = iter(loader)
    held = []
    for _ in range(4):
        batch = next(batches)
        held.append((batch, [a.copy() for a in batch[:4]], list(batch[4])))
        # nothing shares memory with a batch handed out before
        for earlier, _, _ in held[:-1]:
            assert not any(np.shares_memory(a, b)
                           for a in earlier[:4] for b in batch[:4])
    # the first is as it was after the next three were pulled (and two
    # more were being assembled behind them)
    for batch, copies, meta in held:
        assert all(a.tobytes() == c.tobytes()
                   for a, c in zip(batch[:4], copies))
        assert batch[4] == meta
        assert all(a.flags.owndata and a.flags.writeable for a in batch[:4])


def test_transfer_hands_on_the_pull_span():
    """Each batch comes with the interval of the ``next()`` that produced
    it, ahead of its own ``put``."""
    import time

    from raft_meets_dicl_tpu.strategy.training import _device_prefetch

    def slow():
        for i in range(4):
            time.sleep(0.01)
            yield np.full((1,), i), None, None, None, [i]

    def put(batch):
        time.sleep(0.002)
        return batch

    got = list(_device_prefetch(slow(), put, depth=2))
    assert [meta for _, _, meta, _, _ in got] == [[0], [1], [2], [3]]
    for _host, _dev, _meta, (p0, p1), (t0, t1) in got:
        assert t1 - t0 >= 0.009 and p1 - p0 >= 0.0019
        assert t0 < t1 <= p0 < p1
    # one thread, one thing after the other: a pull starts after the put
    # of the batch before
    assert all(a[3][1] <= b[4][0] for a, b in zip(got, got[1:]))
