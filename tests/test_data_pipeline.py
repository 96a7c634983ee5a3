"""Data-pipeline round-2 tests: TFRecord, CIFAR, vision-2.0 transforms,
text pipeline, MT prefetch assembler.

Reference test analogs: ``TEST/dataset/`` + ``TEST/transform/vision/``
specs + ``TFRecordIterator`` usage in the TF importer tests.
"""

import os
import time

import numpy as np
import pytest

from bigdl_tpu.dataset import (DataSet, MTSampleToMiniBatch,
                               SampleToMiniBatch, cifar, text, tfrecord)
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.transform import vision as V


class TestTFRecord:
    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "x.tfrecord")
        tfrecord.write_examples(p, [
            {"img": b"abc", "label": 3, "w": np.array([1.0, 2.0])},
            {"img": b"de", "label": np.array([-1, 5]), "w": [0.25]},
        ])
        exs = list(tfrecord.read_examples(p))
        assert exs[0]["img"] == [b"abc"]
        assert exs[0]["label"].tolist() == [3]
        np.testing.assert_allclose(exs[1]["w"], [0.25])
        assert exs[1]["label"].tolist() == [-1, 5]

    def test_crc_detects_corruption(self, tmp_path):
        p = str(tmp_path / "x.tfrecord")
        tfrecord.write_records(p, [b"payload-one"])
        raw = bytearray(open(p, "rb").read())
        raw[14] ^= 0xFF  # flip a payload byte
        open(p, "wb").write(bytes(raw))
        with pytest.raises(IOError):
            list(tfrecord.read_records(p))

    def test_reads_reference_tf_file_if_present(self):
        p = ("/root/reference/spark/dl/src/test/resources/tf/"
             "mnist_train.tfrecord")
        if not os.path.exists(p):
            pytest.skip("reference resources not available")
        exs = list(tfrecord.read_examples(p))
        assert len(exs) == 10
        assert exs[0]["image/encoded"][0][:4] == b"\x89PNG"
        assert 0 <= int(exs[0]["image/class/label"][0]) <= 9


class TestCifar:
    def test_synthetic_learnable_format(self):
        imgs, labels = cifar.synthetic_cifar(64)
        assert imgs.shape == (64, 32, 32, 3) and imgs.dtype == np.uint8
        assert labels.min() >= 0 and labels.max() <= 9

    def test_bin_format_loader(self, tmp_path):
        # fabricate one binary batch in the CIFAR-10 layout
        n = 10
        rng = np.random.RandomState(0)
        labels = rng.randint(0, 10, n).astype(np.uint8)
        imgs = rng.randint(0, 255, (n, 3, 32, 32)).astype(np.uint8)
        rec = np.concatenate([labels[:, None],
                              imgs.reshape(n, -1)], axis=1)
        d = tmp_path / "cifar-10-batches-bin"
        d.mkdir()
        for i in range(1, 6):
            rec.tofile(str(d / f"data_batch_{i}.bin"))
        rec.tofile(str(d / "test_batch.bin"))
        tr_i, tr_l = cifar.load_cifar10(str(tmp_path), train=True)
        te_i, te_l = cifar.load_cifar10(str(tmp_path), train=False)
        assert tr_i.shape == (50, 32, 32, 3)
        assert te_i.shape == (10, 32, 32, 3)
        np.testing.assert_array_equal(te_l, labels)
        # channel order: record is CHW planes -> loader returns HWC
        np.testing.assert_array_equal(te_i[0, :, :, 0], imgs[0, 0])


class TestVisionTransforms:
    def _feat(self, seed=0):
        rng = np.random.RandomState(seed)
        return V.ImageFeature(rng.randint(0, 255, (8, 6, 3)).astype(
            np.float32), label=1)

    def test_frame_pipeline_compose(self):
        frame = V.ImageFrame.array(
            [np.full((4, 4, 3), 100.0, np.float32)], [0])
        frame = (frame >> V.Brightness(10, 10)
                 >> V.ChannelNormalize((110, 110, 110), (1, 1, 1))
                 >> V.ImageFrameToSample())
        s = frame.features[0]["sample"]
        assert s.feature.shape == (3, 4, 4)
        np.testing.assert_allclose(s.feature, 0.0)

    def test_hsv_roundtrip(self):
        rng = np.random.RandomState(3)
        img = rng.randint(0, 255, (5, 5, 3)).astype(np.float32)
        back = V._hsv_to_rgb(V._rgb_to_hsv(img))
        np.testing.assert_allclose(back, img, atol=0.5)

    def test_saturation_grey_is_fixed_point(self):
        grey = np.full((4, 4, 3), 128.0, np.float32)
        f = V.Saturation(0.5, 0.5).transform(V.ImageFeature(grey))
        np.testing.assert_allclose(f.image, grey, atol=0.6)

    def test_resize_and_aspect_scale(self):
        f = self._feat()
        V.Resize(16, 12).transform(f)
        assert f.image.shape == (16, 12, 3)
        f2 = V.ImageFeature(np.zeros((100, 50, 3), np.float32))
        V.AspectScale(min_size=25).transform(f2)
        assert f2.image.shape == (50, 25, 3)

    def test_resize_bilinear_values(self):
        img = np.array([[0.0, 2.0], [4.0, 6.0]], np.float32)
        out = V._resize_bilinear(img, 4, 4)
        assert out.shape == (4, 4)
        # corners preserved-ish, monotone rows
        assert out[0, 0] == 0.0 and out[-1, -1] == 6.0
        assert (np.diff(out, axis=1) >= 0).all()

    def test_expand_and_random_alter_aspect(self):
        f = self._feat()
        V.Expand(max_expand_ratio=2.0, seed=1).transform(f)
        assert f.image.shape[0] >= 8 and f.image.shape[1] >= 6
        f2 = self._feat()
        V.RandomAlterAspect(target_size=7, seed=2).transform(f2)
        assert f2.image.shape == (7, 7, 3)

    def test_crops_and_flip(self):
        f = self._feat()
        V.CenterCrop(4, 4).transform(f)
        assert f.image.shape == (4, 4, 3)
        g = self._feat()
        img0 = g.image.copy()
        V.HFlip(threshold=1.1).transform(g)  # always flips
        np.testing.assert_allclose(g.image, img0[:, ::-1])

    def test_random_transformer_prob(self):
        always = V.RandomTransformer(V.Brightness(5, 5), prob=1.0)
        never = V.RandomTransformer(V.Brightness(5, 5), prob=0.0)
        base = np.zeros((2, 2, 3), np.float32)
        np.testing.assert_allclose(
            always.transform(V.ImageFeature(base.copy())).image, 5.0)
        np.testing.assert_allclose(
            never.transform(V.ImageFeature(base.copy())).image, 0.0)


class TestTextPipeline:
    def test_tokenizer_and_dictionary(self):
        sents = [text.sentence_tokenizer(s)
                 for s in ["The cat sat.", "The dog sat!"]]
        d = text.Dictionary(sents, vocab_size=4)
        assert d.vocab_size() == 5  # 4 words + <unk>
        assert d.index("the") != d.index("sat")
        assert d.index("zebra") == d.word2index[text.Dictionary.UNKNOWN]

    def test_dictionary_save_load(self, tmp_path):
        d = text.Dictionary([["a", "b", "a"]])
        p = str(tmp_path / "vocab.txt")
        d.save(p)
        d2 = text.Dictionary.load(p)
        assert d2.word2index == d.word2index

    def test_labeled_sentence_pipeline(self):
        corpus = text.synthetic_corpus(20)
        toks = [text.sentence_tokenizer(s) for s in corpus]
        d = text.Dictionary(toks)
        pipe = (text.TextToLabeledSentence(d)
                >> text.LabeledSentenceToSample(fixed_length=12))
        samples = list(pipe(iter(toks)))
        assert len(samples) == 20
        for s in samples:
            assert s.feature.shape == (12,) and s.label.shape == (12,)
        # shift property on an unpadded prefix
        raw = d.encode(toks[0])
        np.testing.assert_array_equal(samples[0].feature[:len(raw) - 1],
                                      raw[:-1])
        np.testing.assert_array_equal(samples[0].label[:len(raw) - 1],
                                      raw[1:])

    def test_ptb_batches(self):
        ids = np.arange(21)
        x, y = text.ptb_batches(ids, num_steps=5)
        assert x.shape == (4, 5)
        np.testing.assert_array_equal(y, x + 1)


class TestMTPrefetch:
    def test_batches_match_serial(self):
        samples = [Sample(np.full((3,), i, np.float32), np.int32(i % 2))
                   for i in range(37)]

        def tf(s):
            return Sample(s.feature * 2.0, s.label)

        mt = MTSampleToMiniBatch(8, tf, workers=4, prefetch=2)
        batches = list(mt(iter(samples)))
        assert len(batches) == 4  # 37 // 8, remainder dropped
        flat = np.concatenate([b.input for b in batches])
        np.testing.assert_allclose(flat[:, 0], np.arange(32) * 2.0)

    def test_keep_remainder(self):
        samples = [Sample(np.zeros(2, np.float32), np.int32(0))
                   for _ in range(10)]
        mt = MTSampleToMiniBatch(4, None, drop_remainder=False)
        sizes = [b.size() for b in mt(iter(samples))]
        assert sizes == [4, 4, 2]

    def test_worker_error_propagates(self):
        def bad(s):
            raise RuntimeError("boom")

        mt = MTSampleToMiniBatch(2, bad)
        with pytest.raises(RuntimeError):
            list(mt(iter([Sample(np.zeros(1), np.int32(0))] * 4)))

    def test_random_augmentation_is_schedule_independent(self):
        # VERDICT r2 weak#2 root cause: ThreadRng draws depended on which
        # worker thread got each sample.  Under the assembler the draws
        # must be a pure function of (seed, stream index): many-worker
        # and single-worker runs produce IDENTICAL batches.
        from bigdl_tpu.dataset import image
        samples = [Sample(np.random.RandomState(i).rand(3, 8, 8)
                          .astype(np.float32), np.int32(0))
                   for i in range(32)]

        def run(workers):
            crop = image.RandomCropper(4, 4, pad=2)
            flip = image.HFlip()

            def aug(s):
                s = next(iter(crop(iter([s]))))
                return next(iter(flip(iter([s]))))

            mt = MTSampleToMiniBatch(8, aug, workers=workers)
            return np.concatenate([b.input for b in mt(iter(samples))])

        a, b, c = run(8), run(8), run(1)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)

    def test_augmentation_varies_across_passes(self):
        # ...but iterating the SAME transformer again (epoch 2 over a
        # fixed-order dataset) must draw FRESH augmentation, not replay
        # epoch 1 (code-review r3 finding)
        from bigdl_tpu.dataset import image
        samples = [Sample(np.random.RandomState(i).rand(3, 8, 8)
                          .astype(np.float32), np.int32(0))
                   for i in range(16)]
        crop = image.RandomCropper(4, 4, pad=2)

        def aug(s):
            return next(iter(crop(iter([s]))))

        mt = MTSampleToMiniBatch(8, aug, workers=4)
        e1 = np.concatenate([b.input for b in mt(iter(samples))])
        e2 = np.concatenate([b.input for b in mt(iter(samples))])
        assert not np.array_equal(e1, e2)

    def test_prefetch_overlaps(self):
        # producer keeps the queue full while the consumer is slow
        samples = [Sample(np.zeros(1, np.float32), np.int32(0))
                   for _ in range(24)]
        mt = MTSampleToMiniBatch(4, None, workers=2, prefetch=3)
        it = mt(iter(samples))
        first = next(it)
        time.sleep(0.05)  # let the producer run ahead
        rest = list(it)
        assert 1 + len(rest) == 6


class _FakePlaced:
    """Stands in for a device array: a snapshot of what the host buffer
    held when it was placed, the device's platform, and whether anyone
    waited for it."""

    def __init__(self, host, platform):
        self.address = host.ctypes.data
        self.snapshot = host.copy()
        self.platform = platform
        self.waited = False

    def devices(self):
        return {self}  # a device is what has a ``platform``

    def block_until_ready(self):
        self.waited = True
        return self


def _ramp_samples(n):
    """Sample i is filled with i: any batch names its own place."""
    for i in range(n):
        yield Sample(np.full((64,), i, np.float32), np.int32(i))


class TestRecycledBuffers:
    """ISSUE 26: a block of one batch is staged as a view, and the
    assembler stacks into buffers the stager hands back once the device
    holds its own copy."""

    @staticmethod
    def _staged_blocks(platform, n_blocks, k=1, batch=4, registry=None):
        from bigdl_tpu.dataset.prefetch import DeviceBlockStager
        from bigdl_tpu.telemetry import Tracer
        placed = []

        def place(xs, ys):
            placed.append(_FakePlaced(xs, platform))
            return placed[-1], _FakePlaced(ys, platform)

        tracer = Tracer()
        mt = MTSampleToMiniBatch(batch, None, workers=2, prefetch=2)
        it = mt(_ramp_samples(batch * k * n_blocks))
        stager = DeviceBlockStager(it, place, tracer=tracer,
                                   registry=registry)
        for _ in range(n_blocks):
            stager.take(k, 10**9)
        it.close()
        spans = [args for ph, name, _c, _t0, _d, tid, args, _f
                 in tracer.events() if name == "assemble"]
        return placed, spans, mt

    @pytest.mark.parametrize("k", [1, 4])
    def test_one_batch_block_is_a_view_and_more_are_copied(self, k):
        from bigdl_tpu.dataset.prefetch import DeviceBlockStager
        batches = list(SampleToMiniBatch(4)(_ramp_samples(16)))
        seen = []
        stager = DeviceBlockStager(iter(batches),
                                   lambda xs, ys: seen.append((xs, ys))
                                   or (xs, ys))
        _, _, sizes = stager.take(k, 10**9)
        xs, ys = seen[0]
        assert sizes == [4] * k and xs.shape == (k, 4, 64)
        np.testing.assert_array_equal(
            xs, np.stack([b.input for b in batches[:k]]))
        np.testing.assert_array_equal(
            ys, np.stack([b.target for b in batches[:k]]))
        shares = [np.shares_memory(xs, b.input) for b in batches[:k]]
        assert shares == ([True] if k == 1 else [False] * 4)
        # what the data-parallel placer cuts from the view (axis 1 over
        # the mesh) is contiguous memory: no hidden copy before the
        # transfer (a cut through k stacked batches never was)
        assert [xs[:, i:i + 2].flags.c_contiguous for i in (0, 2)] == \
            [k == 1] * 2

    def test_sharded_slices_of_the_view_are_contiguous(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        view = np.zeros((8, 6, 6, 3), np.float32)[None]
        sh = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("data",)),
                           P(None, "data"))
        cuts = sh.addressable_devices_indices_map(view.shape).values()
        assert len(cuts) == 4
        for ix in cuts:
            assert view[ix].shape == (1, 2, 6, 6, 3)
            assert view[ix].flags.c_contiguous

    def test_buffers_come_back_from_a_device_and_are_reused(self):
        from bigdl_tpu.telemetry import MetricRegistry
        registry = MetricRegistry()
        n, bound = 24, 2 + 3  # prefetch + 3
        placed, spans, _ = self._staged_blocks("tpu", n,
                                               registry=registry)
        # every block held its own four samples when it was placed
        for i, p in enumerate(placed):
            np.testing.assert_array_equal(
                p.snapshot[0, :, 0], np.arange(4 * i, 4 * i + 4))
        assert len({p.address for p in placed}) <= bound
        assert [a["recycled"] for a in spans[bound:]] == \
            [True] * (n - bound)
        assert not any(a["recycled"] for a in spans[:3])
        assert all(a["bytes"] == 4 * (64 * 4 + 4) for a in spans)
        counters = registry.snapshot()["counters"]
        assert counters["input/buffers_allocated"] <= bound
        assert counters["input/buffers_allocated"] \
            + counters["input/buffers_recycled"] == n
        # a buffer was written again only after its device copy was
        # waited for
        last_at = {}
        for p in placed:
            if p.address in last_at:
                assert last_at[p.address].waited
            last_at[p.address] = p

    @pytest.mark.parametrize("k", [1, 4])
    def test_a_host_platform_never_recycles(self, k):
        placed, spans, _ = self._staged_blocks("cpu", 12, k=k)
        assert len(spans) == 12 * k
        assert not any(a["recycled"] for a in spans)
        assert not any(p.waited for p in placed)
        if k == 1:  # the views: twelve buffers, none written twice
            assert len({p.address for p in placed}) == 12

    def test_a_consumer_that_hands_nothing_back_gets_fresh_arrays(self):
        mt = MTSampleToMiniBatch(4, None, workers=2, prefetch=2)
        batches = list(mt(_ramp_samples(24)))
        assert len(batches) == 6
        for i, b in enumerate(batches):
            np.testing.assert_array_equal(b.input[:, 0],
                                          np.arange(4 * i, 4 * i + 4))
            np.testing.assert_array_equal(b.target,
                                          np.arange(4 * i, 4 * i + 4))
            assert b.lease.recycled is False
            for other in batches[:i]:
                assert not np.shares_memory(b.input, other.input)
                assert not np.shares_memory(b.target, other.target)

    def test_remainder_batch_is_not_pooled(self):
        mt = MTSampleToMiniBatch(4, None, drop_remainder=False)
        full, _, rest = list(mt(_ramp_samples(10)))
        assert (full.size(), rest.size()) == (4, 2)
        assert full.lease._pool is not None
        assert rest.lease._pool is None and not rest.lease.recycled
        rest.lease.release()  # nothing to hand back, nothing happens
        np.testing.assert_array_equal(rest.target, [8, 9])

    @pytest.mark.parametrize("how", ["close", "throw"])
    def test_early_exit_with_buffers_out_reaps_the_thread(self, how):
        import threading
        before = threading.active_count()
        mt = MTSampleToMiniBatch(4, None, workers=2, prefetch=1)
        it = mt(_ramp_samples(4096))
        out = [next(it), next(it), next(it)]
        pool = out[0].lease._pool
        out[0].lease.release()  # one came back, two stay out
        if how == "close":
            it.close()
        else:
            with pytest.raises(RuntimeError, match="step exploded"):
                it.throw(RuntimeError("step exploded"))
        deadline = time.time() + 5.0
        while threading.active_count() > before and time.time() < deadline:
            time.sleep(0.05)
        assert threading.active_count() <= before
        # what is out is dropped as it comes back, and stays whole
        out[1].lease.release()
        out[1].lease.release()
        assert pool._free == []
        np.testing.assert_array_equal(out[1].target, [4, 5, 6, 7])
        np.testing.assert_array_equal(out[2].target, [8, 9, 10, 11])


class TestReviewFixes:
    """Regressions for round-2 review findings on the data pipeline."""

    def test_random_transforms_advance_between_samples(self):
        # one instance must give different draws per call (a fresh instance
        # per sample used to replay the identical 'random' crop forever)
        from bigdl_tpu.dataset import image
        rng_img = np.random.RandomState(0).rand(40, 40, 3).astype(np.float32)
        crop = image.RandomCropper(8, 8)
        outs = {bytes(next(iter(crop(iter([Sample(rng_img, 0)])))).feature)
                for _ in range(20)}
        assert len(outs) > 1, "RandomCropper draws never advance"
        flip = image.HFlip(threshold=0.5)
        decisions = {bool(np.allclose(
            next(iter(flip(iter([Sample(rng_img, 0)])))).feature, rng_img))
            for _ in range(50)}
        assert decisions == {True, False}, "HFlip never varies"

    def test_thread_rng_distinct_across_threads(self):
        from concurrent.futures import ThreadPoolExecutor
        from bigdl_tpu.utils.imgops import ThreadRng
        rng = ThreadRng(1)
        with ThreadPoolExecutor(max_workers=4) as pool:
            draws = list(pool.map(lambda _: rng.random(), range(8)))
        assert len(set(draws)) > 1

    def test_prefetch_consumer_early_exit_unblocks_producer(self):
        import threading
        before = threading.active_count()
        samples = [Sample(np.zeros(4, np.float32), np.int32(0))
                   for _ in range(512)]
        mt = MTSampleToMiniBatch(4, None, workers=2, prefetch=1)
        it = mt(iter(samples))
        next(it)
        it.close()  # early exit mid-epoch
        deadline = time.time() + 5.0
        while threading.active_count() > before and time.time() < deadline:
            time.sleep(0.05)
        assert threading.active_count() <= before + 1, \
            "producer thread leaked after early consumer exit"

    def test_chained_close_propagates_to_inner_assembler(self):
        """Early consumer exit on a CHAINED pipeline: closing the outer
        generator must shut the inner assembler's producer thread down
        deterministically (the outer producer closes its source in its
        finally), not leave it to GC."""
        import threading

        def sample_stream():
            i = 0
            while True:  # infinite: only shutdown propagation ends it
                yield Sample(np.full(3, i, np.float32), np.int32(0))
                i += 1

        before = threading.active_count()
        inner = MTSampleToMiniBatch(4, None, workers=2, prefetch=2)
        rebatch = MTSampleToMiniBatch(2, None, workers=2, prefetch=2)

        def batch_to_samples(batches):
            for b in batches:
                for i in range(b.size()):
                    yield Sample(b.input[i], b.target[i])

        outer = rebatch(batch_to_samples(inner(sample_stream())))
        next(outer)
        outer.close()  # must cascade: outer producer → inner generator
        deadline = time.time() + 5.0
        while threading.active_count() > before and time.time() < deadline:
            time.sleep(0.05)
        assert threading.active_count() <= before, \
            "chained early exit leaked a producer thread"

    def test_throw_mid_epoch_cleans_up_threads_and_queue(self):
        """Exception injected at the consumption point (generator.throw
        — what a crashing training loop does to its data iterator) must
        neither deadlock the bounded queue nor leak the producer."""
        import threading
        before = threading.active_count()
        samples = [Sample(np.zeros(4, np.float32), np.int32(0))
                   for _ in range(4096)]
        mt = MTSampleToMiniBatch(4, None, workers=2, prefetch=1)
        it = mt(iter(samples))
        next(it)
        with pytest.raises(RuntimeError, match="step exploded"):
            it.throw(RuntimeError("step exploded"))
        deadline = time.time() + 5.0
        while threading.active_count() > before and time.time() < deadline:
            time.sleep(0.05)
        assert threading.active_count() <= before, \
            "producer thread leaked after consumer exception"

    def test_shared_lighting_constants(self):
        from bigdl_tpu.dataset import image
        from bigdl_tpu.transform import vision as V
        from bigdl_tpu.utils import imgops
        # both stacks consume the same kernel (no drifting copies)
        f = V.Lighting(alphastd=0.0).transform(
            V.ImageFeature(np.zeros((2, 2, 3), np.float32)))
        np.testing.assert_allclose(f.image, 0.0)
        s = next(iter(image.Lighting(alphastd=0.0)(
            iter([Sample(np.zeros((2, 2, 3), np.float32), 0)]))))
        np.testing.assert_allclose(s.feature, 0.0)
        assert imgops.LIGHTING_EIGVAL.shape == (3,)


class TestSequenceFile:
    def test_roundtrip_and_sync_markers(self, tmp_path):
        from bigdl_tpu.dataset import seqfile as sq
        p = str(tmp_path / "part-0.seq")
        recs = [(f"img{i}\n{i % 7}".encode(), bytes([i % 251]) * (50 + i))
                for i in range(300)]
        sq.write_seqfile(p, recs, sync_interval=64)
        back = list(sq.read_seqfile(p))
        assert len(back) == 300
        assert back[0][0] == b"img0\n0"
        assert back[123][1] == recs[123][1]

    def test_imagenet_key_convention(self, tmp_path):
        from bigdl_tpu.dataset import seqfile as sq
        assert sq.parse_imagenet_key(b"n0123/img.jpg\n42") == \
            ("n0123/img.jpg", 42)
        assert sq.parse_imagenet_key(b"7") == (None, 7)
        p = str(tmp_path / "p.seq")
        sq.write_seqfile(p, [(b"a\n3", b"xyz"), (b"5", b"pq")])
        out = list(sq.seqfiles_to_byte_records([p]))
        assert out == [(3, b"xyz"), (5, b"pq")]

    def test_vint_edge_cases(self):
        from bigdl_tpu.dataset.seqfile import read_vint, write_vint
        for v in (0, 1, -1, 127, -112, 128, -113, 1 << 20, -(1 << 20),
                  (1 << 31) - 1):
            b = write_vint(v)
            got, pos = read_vint(b, 0)
            assert got == v and pos == len(b)

    def test_block_compressed_roundtrip(self, tmp_path):
        # r3: block compression is now READ/WRITTEN (MapReduce default
        # output format); full coverage in test_round3_closures.py
        from bigdl_tpu.dataset import seqfile as sq
        p = str(tmp_path / "c.seq")
        recs = [(f"k{i}".encode(), f"v{i}".encode() * 10)
                for i in range(10)]
        sq.write_seqfile(p, recs, sync_interval=4, block_compressed=True)
        assert list(sq.read_seqfile(p)) == recs


class TestBuiltinLoaders:
    def test_movielens_format_and_parse(self, tmp_path):
        from bigdl_tpu.dataset import movielens
        syn = movielens.synthetic_ratings(n_ratings=50)
        assert syn.shape == (50, 3)
        assert syn[:, 2].min() >= 1 and syn[:, 2].max() <= 5
        p = tmp_path / "ratings.dat"
        p.write_text("\n".join(f"{u}::{i}::{r}::0" for u, i, r in syn))
        back = movielens.load(str(tmp_path))
        np.testing.assert_array_equal(back, syn)
        samples = movielens.to_implicit_samples(syn)
        assert samples[0].feature.shape == (2,)

    def test_news20_tree_and_synthetic(self, tmp_path):
        from bigdl_tpu.dataset import news20
        for cat, docs in (("alt.atheism", ["hello world"]),
                          ("sci.space", ["rockets fly", "orbit high"])):
            d = tmp_path / cat
            d.mkdir()
            for i, t in enumerate(docs):
                (d / f"{i}").write_text(t)
        texts, labels, cats = news20.load(str(tmp_path))
        assert cats == ["alt.atheism", "sci.space"]
        assert list(labels) == [0, 1, 1]
        texts2, labels2, cats2 = news20.synthetic_news(50, 3)
        assert len(texts2) == 50 and set(labels2) <= {0, 1, 2}


def test_seqfile_truncation_detected(tmp_path):
    from bigdl_tpu.dataset import seqfile as sq
    p = str(tmp_path / "t.seq")
    sq.write_seqfile(p, [(b"k", b"v" * 100)])
    raw = open(p, "rb").read()
    open(p, "wb").write(raw[:-20])  # cut mid-value
    with pytest.raises(IOError, match="truncated"):
        list(sq.read_seqfile(p))


def test_seqfile_record_compression_roundtrip(tmp_path):
    from bigdl_tpu.dataset import seqfile as sq
    p = str(tmp_path / "c.seq")
    recs = [(f"k{i}".encode(), (f"payload-{i}-" * 20).encode())
            for i in range(120)]
    sq.write_seqfile(p, recs, compressed=True, sync_interval=50)
    back = list(sq.read_seqfile(p))
    assert back == recs
    # compressed file is smaller than the raw payload total
    import os as _os
    assert _os.path.getsize(p) < sum(len(v) for _, v in recs)


def test_seqfile_unknown_codec_rejected(tmp_path):
    import struct
    from bigdl_tpu.dataset import seqfile as sq
    p = str(tmp_path / "x.seq")
    with open(p, "wb") as f:
        f.write(b"SEQ\x06")
        f.write(sq._hadoop_string(sq.TEXT))
        f.write(sq._hadoop_string(sq.TEXT))
        f.write(bytes([1, 0]))
        f.write(sq._hadoop_string("org.example.SnappyCodec"))
        f.write(struct.pack(">i", 0))
        f.write(b"\x00" * 16)
    with pytest.raises(NotImplementedError, match="codec"):
        list(sq.read_seqfile(p))
