"""Multi-worker batch assembly + prefetch.

Reference: ``DL/dataset/image/MTLabeledBGRImgToBatch.scala`` and
``DL/transform/vision/image/MTImageFeatureToBatch.scala`` — the reference
keeps N Spark-executor cores busy decoding/augmenting while training runs,
assembling MiniBatches on a parallel pipeline.

TPU redesign (SURVEY §7 stage 5 risk "input pipeline throughput"): the
same role on a TPU-VM host — per-sample preprocessing fanned out over a
thread pool (numpy releases the GIL in its kernels) + a bounded
prefetch queue so batch ``i+1`` is assembled while the jit'd step runs
batch ``i``.  Composes as a normal Transformer:

    dataset >> MTSampleToMiniBatch(128, per_sample_fn, workers=8)

The pipeline has TWO prefetch stages since the fused-dispatch rework:

1. host assembly (this transformer): samples → MiniBatches on worker
   threads, buffered in a bounded queue;
2. device staging (:class:`DeviceBlockStager`): consecutive MiniBatches
   → one host-stacked K-step block → asynchronously ``device_put`` so
   block ``i+1`` is already landing in HBM (sharded, for the SPMD
   path) while the jit'd K-step scan crunches block ``i``.
"""

from __future__ import annotations

import collections
import itertools
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterator, Optional

import numpy as np

from bigdl_tpu.dataset.sample import Sample, MiniBatch
from bigdl_tpu.dataset.transformer import Transformer
from bigdl_tpu.telemetry.tracer import NULL_SPAN as _NOOP_CM
from bigdl_tpu.utils.imgops import sample_key


def _leaf_meta(leaf):
    return (tuple(np.shape(leaf)), getattr(leaf, "dtype", None))


def batch_signature(batch: MiniBatch):
    """Structural identity of a batch — pytree structure + per-leaf
    shape/dtype.  Blocks only stack batches with identical signatures
    (a ragged remainder batch, or a bucket change in a padded text/COO
    pipeline, ends the block instead of crashing ``np.stack``)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(
        (batch.input, batch.target))
    return treedef, tuple(_leaf_meta(l) for l in leaves)


def _may_alias_host(placed) -> bool:
    """Whether a placed array may still be the host buffer it was put
    from: the CPU backend's ``device_put`` can alias a numpy array
    without a copy, and what is no device array at all says nothing of
    where it lives."""
    return any(getattr(a, "devices", None) is None
               or any(d.platform == "cpu" for d in a.devices())
               for a in placed)


class DeviceBlockStager:
    """Device-prefetch stage: pulls MiniBatches from the host pipeline,
    stacks up to ``k`` of them along a new leading step axis, and hands
    the stack to ``place_block`` (``jnp.asarray`` tree locally; a
    ``P(None, "data")``-sharded global-array build under SPMD).

    ``jax.device_put``-family transfers are asynchronous, so a driver
    that stages block ``i+1`` right after dispatching block ``i`` gets
    the double-buffer for free: the host→HBM DMA of ``i+1`` overlaps
    the device compute of ``i``, and the jit dispatch never waits on a
    transfer.  The stager itself never looks at driver state — the
    driver passes a step cap (from the trigger probe) and a records
    budget (to the epoch boundary) per block, which is what keeps
    epoch/trigger semantics exact under fusion.
    """

    def __init__(self, batch_iter, place_block, tracer=None,
                 registry=None):
        self._it = batch_iter
        self._place = place_block
        self._held = None  # batch pulled but deferred to the next block
        # telemetry (optional): a bigdl_tpu.telemetry.Tracer records the
        # split of every take() — host_stack (with each pull from the
        # host pipeline and the K-axis copy inside it) vs H2D staging —
        # host-side clock reads only, inert when None; a MetricRegistry
        # counts the assembler's buffers by where they came from
        self._tracer = tracer
        self._recycled = self._allocated = None
        if registry is not None:
            self._recycled = registry.counter("input/buffers_recycled")
            self._allocated = registry.counter("input/buffers_allocated")
        # placed blocks whose batches lent their buffers (``lease``):
        # (device leaves, leases), oldest first.  A lease goes back to
        # the assembler only once the device arrays placed from it are
        # ready and none of them lives on the host platform
        self._lent: collections.deque = collections.deque()

    def reset(self, batch_iter) -> None:
        """Point at a fresh iterator (epoch rollover: the driver
        shuffles and re-opens the dataset, exactly like the unfused
        loop did).  Never called with lookahead in flight — blocks are
        budgeted to stop AT the epoch boundary, so the stager holds no
        stale pre-shuffle batches."""
        close = getattr(self._it, "close", None)
        if close is not None:
            close()
        self._it = batch_iter
        self._held = None
        # the closed pipeline takes nothing back; its buffers stay with
        # whatever transfer still reads them
        self._lent.clear()

    def _return_lent(self) -> None:
        """Hand back the buffers of every placed block but the newest.
        Those blocks were dispatched at least one block ago, so their
        transfers are long over and the wait costs nothing."""
        import jax
        while len(self._lent) > 1:
            placed, leases = self._lent.popleft()
            if _may_alias_host(placed):
                continue  # never written again
            jax.block_until_ready(placed)
            for lease in leases:
                lease.release()

    def _note_assembled(self, lease) -> None:
        """The assembler's own work on a staged batch, from the stamps
        it carries: a span on a track of its own, and the counters."""
        if self._tracer is not None:
            self._tracer.record("assemble", lease.t0_ns, lease.t1_ns,
                                cat="batch_assemble", track="assembler",
                                bytes=lease.nbytes,
                                recycled=lease.recycled)
        if self._recycled is not None:
            (self._recycled if lease.recycled else self._allocated).inc()

    def take(self, k: int, records_budget: int):
        """Stage the next block: up to ``k`` consecutive same-signature
        batches whose cumulative size stays within ``records_budget``
        (the batch that reaches the budget — the epoch-boundary step —
        is included; the NEXT pull would belong to the next epoch).

        Returns ``(dev_xs, dev_ys, sizes)`` where dev arrays carry a
        leading ``len(sizes)`` step axis and ``dev_ys`` is None for
        unlabelled batches.  Raises StopIteration if the host pipeline
        is exhausted with nothing staged (finite iterator misuse — the
        training contract is an infinite shuffled stream)."""
        tr = self._tracer
        span = tr.span if tr is not None else None
        with span("host_stack", cat="stage") if span else _NOOP_CM:
            # where the driver waits for an earlier block's transfer
            # should the runtime fall behind (under a profiler it does)
            with span("buffer_return", cat="buffer_return") \
                    if span else _NOOP_CM:
                self._return_lent()
            batches = []
            sig = None
            total = 0
            while len(batches) < max(1, int(k)) and total < records_budget:
                if self._held is not None:
                    b, self._held = self._held, None
                else:
                    try:
                        # with MTSampleToMiniBatch a wait on the
                        # assembler thread's queue; with an inline
                        # assembler the assembly itself
                        with span("batch_pull", cat="batch_pull",
                                  n=len(batches)) if span else _NOOP_CM:
                            b = next(self._it)
                    except StopIteration:
                        break
                if not isinstance(b, MiniBatch):
                    raise TypeError(
                        "training dataset must yield MiniBatch (attach "
                        "SampleToMiniBatch / MTSampleToMiniBatch)")
                b_sig = batch_signature(b)
                if sig is None:
                    sig = b_sig
                elif b_sig != sig:
                    self._held = b  # ragged/bucket change: next block's
                    break           # head
                batches.append(b)
                total += b.size()
            if not batches:
                raise StopIteration(
                    "training data iterator exhausted mid-epoch — "
                    "train=True iterators must be infinite (see "
                    "AbstractDataSet.data)")
            import jax
            tmap = jax.tree_util.tree_map
            one = len(batches) == 1

            def stack(*leaves):
                # a block of one batch is the batch itself under a step
                # axis of length 1: a view, nothing is copied
                if one:
                    return np.asarray(leaves[0])[None]
                return np.stack([np.asarray(l) for l in leaves])

            # bytes of the block: what h2d_stage hands to the device,
            # and what block_stack copies unless it is a view (telemetry
            # only)
            nbytes = sum(np.asarray(l).nbytes for b in batches
                         for l in jax.tree_util.tree_leaves(
                             (b.input, b.target))) if span else None
            with span("block_stack", cat="block_stack",
                      bytes=0 if one else nbytes) if span else _NOOP_CM:
                xs = tmap(stack, *[b.input for b in batches])
                ys = None if batches[0].target is None else \
                    tmap(stack, *[b.target for b in batches])
        with span("h2d_stage", cat="stage", k=len(batches),
                  bytes=nbytes) if span else _NOOP_CM:
            # the device_put underneath is ASYNCHRONOUS — this span times
            # the host-side staging cost, not the DMA itself (the DMA
            # overlaps the in-flight block's compute by design)
            dev_xs, dev_ys = self._place(xs, ys)
        leases = [b.lease for b in batches if b.lease is not None]
        if leases:
            for lease in leases:
                self._note_assembled(lease)
            self._lent.append(
                (jax.tree_util.tree_leaves((dev_xs, dev_ys)), leases))
        return dev_xs, dev_ys, [b.size() for b in batches]


def fast_forward_records(batch_iter, skip: int) -> int:
    """Advance a fresh epoch iterator past exactly ``skip`` records
    (the mid-epoch resume fast-forward).  Scale-aware callers divide
    the GLOBAL records counter by their per-step record scale first —
    under an elastic resume each of P′ survivors skips its own 1/P′
    share through this one helper.

    Raises a targeted error when the batch boundaries cannot land on
    ``skip`` exactly: silently overshooting would replay the epoch
    from a position the loss trajectory never visited."""
    skipped = 0
    while skipped < skip:
        try:
            skipped += next(batch_iter).size()
        except StopIteration:
            raise ValueError(
                f"dataset fast-forward: epoch exhausted after "
                f"{skipped} records while seeking {skip} — the "
                f"dataset shrank since the snapshot was written"
            ) from None
    if skipped != skip:
        raise ValueError(
            f"dataset fast-forward: batch boundaries land on {skipped} "
            f"records, not the {skip} the snapshot recorded — batch "
            f"size or dataset layout changed since the snapshot was "
            f"written")
    return skipped


class _BufferPool:
    """The recycled host buffers of one pass of ``MTSampleToMiniBatch``.

    A fresh multi-hundred-megabyte array is new pages that the kernel
    faults in while the copy runs; a buffer that came back is mapped
    already.  The pool holds buffer sets of ONE signature (the first
    batch's: length, and shape and dtype of every leaf) and never owns
    more than ``bound`` of them; a batch of another signature (the
    remainder), or one wanted while all ``bound`` are out, takes fresh
    arrays that are not pooled.  A consumer that hands nothing back
    keeps what it was given: the pool holds no reference to a buffer
    that is out."""

    def __init__(self, bound: int):
        self._bound = bound
        self._lock = threading.Lock()
        self._sig = None    # guarded-by: _lock
        self._free = []     # guarded-by: _lock
        self._owned = 0     # guarded-by: _lock
        self._open = True   # guarded-by: _lock

    def take(self, sig):
        """``(buffers, recycled, pooled)`` for a batch of ``sig`` =
        ``((shape, dtype), ...)``, one entry a leaf."""
        with self._lock:
            if self._sig is None:
                self._sig = sig
            pooled = self._open and sig == self._sig
            if pooled and self._free:
                return self._free.pop(), True, True
            pooled = pooled and self._owned < self._bound
            if pooled:
                self._owned += 1
        return [np.empty(shape, dtype) for shape, dtype in sig], \
            False, pooled

    def give_back(self, bufs) -> None:
        with self._lock:
            if self._open:
                self._free.append(bufs)

    def close(self) -> None:
        """Drop what is free; what is out is dropped as it comes back."""
        with self._lock:
            self._open = False
            self._free.clear()


class _Lease:
    """What an assembled batch carries of its making: the stamps of the
    assembler's work (``perf_counter_ns``), its bytes, whether its
    arrays came back from an earlier batch, and the way back."""

    __slots__ = ("t0_ns", "t1_ns", "nbytes", "recycled", "_pool", "_bufs")

    def __init__(self, t0_ns, t1_ns, nbytes, recycled, pool, bufs):
        self.t0_ns = t0_ns
        self.t1_ns = t1_ns
        self.nbytes = nbytes
        self.recycled = recycled
        self._pool = pool   # None: the arrays are not the pool's
        self._bufs = bufs

    def release(self) -> None:
        """Nothing reads the batch's arrays any more: the next batch
        may be written into them.  A second call does nothing."""
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.give_back(self._bufs)


def _stack(samples, pool: _BufferPool) -> MiniBatch:
    """One batch of ``samples``, every byte written once: stacked
    straight into buffers of the pool where the samples are uniform,
    into fresh arrays by ``np.stack`` alone where they are not."""
    t0 = time.perf_counter_ns()
    leaves = [[np.asarray(s.feature) for s in samples]]
    if samples[0].label is not None:
        leaves.append([np.asarray(s.label) for s in samples])
    uniform = all(a.shape == col[0].shape and a.dtype == col[0].dtype
                  for col in leaves for a in col)
    if uniform:
        sig = tuple(((len(col),) + col[0].shape, col[0].dtype)
                    for col in leaves)
        bufs, recycled, pooled = pool.take(sig)
        for col, buf in zip(leaves, bufs):
            np.stack(col, out=buf)
    else:
        bufs, recycled, pooled = [np.stack(col) for col in leaves], \
            False, False
    batch = MiniBatch(bufs[0], bufs[1] if len(bufs) > 1 else None)
    batch.lease = _Lease(t0, time.perf_counter_ns(),
                         sum(b.nbytes for b in bufs), recycled,
                         pool if pooled else None, bufs)
    return batch


class MTSampleToMiniBatch(Transformer):
    """Parallel per-sample transform + batch assembly + prefetch.

    ``transform`` maps one Sample → Sample (e.g. a composed augmentation
    pipeline applied per element); it runs on ``workers`` threads.  Up to
    ``prefetch`` assembled batches are buffered ahead of the consumer.

    A batch is stacked into a buffer that an earlier batch of this pass
    handed back through its ``lease`` (only ``DeviceBlockStager`` does,
    once the device holds its own copy), and into a fresh array when
    none has come back, so a consumer that releases nothing (user code,
    validation, ``list(...)``) gets fresh arrays as ever.  The pass owns
    at most ``prefetch + 3`` buffer sets (the queue, the two blocks a
    training driver runs ahead, the one being written): five global
    batches with the defaults, about 3 GB of host memory at 1,024
    224x224x3 f32 images a batch.
    """

    def __init__(self, batch_size: int,
                 transform: Optional[Callable[[Sample], Sample]] = None,
                 workers: int = 4, prefetch: int = 2,
                 drop_remainder: bool = True):
        self.batch_size = batch_size
        self.transform = transform
        self.workers = workers
        self.prefetch = max(1, prefetch)
        self.drop_remainder = drop_remainder
        # per-instance pass counter folded into the sample key: calling
        # the SAME transformer once per epoch over a fixed-order dataset
        # must still draw fresh augmentation each epoch (run-to-run
        # deterministic, pass-to-pass varying)
        self._passes = itertools.count()

    def __call__(self, it: Iterator[Sample]) -> Iterator[MiniBatch]:
        out_q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        pool = _BufferPool(self.prefetch + 3)
        _END = object()

        def put_or_stop(item) -> bool:
            """Bounded put that stays responsive to consumer shutdown —
            a consumer that exits early must not leave this thread blocked
            on a full queue forever."""
            while not stop.is_set():
                try:
                    out_q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        pass_ix = next(self._passes)

        def keyed_transform(ix_sample):
            # bracket the transform in the stream position so ThreadRng
            # draws are a pure function of (seed, pass, sample index) —
            # run-to-run deterministic no matter which worker thread
            # executes it
            ix, sample = ix_sample
            with sample_key((pass_ix << 40) | ix):
                return self.transform(sample)

        # the producer's terminal error, recorded OUT of band: queue
        # delivery can fail (e.g. the pool itself refuses to start under
        # thread exhaustion), and the consumer must still be able to
        # surface the ORIGINAL error instead of blocking on get() forever
        failure: list = [None]

        def producer():
            workers = None
            stream_ix = 0
            try:
                # inside the try: a ThreadPoolExecutor that cannot start
                # (resource exhaustion) must take the error path below,
                # not kill this thread with the consumer still blocked
                workers = ThreadPoolExecutor(max_workers=self.workers)
                buf = []
                # map the per-sample transform with bounded lookahead:
                # chunks of one batch keep memory flat
                src = iter(it)
                while not stop.is_set():
                    chunk = []
                    try:
                        for _ in range(self.batch_size):
                            chunk.append(next(src))
                    except StopIteration:
                        pass
                    if not chunk:
                        break
                    if self.transform is not None:
                        chunk = list(workers.map(
                            keyed_transform,
                            enumerate(chunk, start=stream_ix)))
                    stream_ix += len(chunk)
                    buf.extend(chunk)
                    while len(buf) >= self.batch_size:
                        if not put_or_stop(
                                _stack(buf[:self.batch_size], pool)):
                            return
                        buf = buf[self.batch_size:]
                    if len(chunk) < self.batch_size:
                        break
                if buf and not self.drop_remainder:
                    put_or_stop(_stack(buf, pool))
            except BaseException as e:  # surface worker errors to consumer
                failure[0] = e  # out-of-band first: survives a failed put
                put_or_stop(e)
            finally:
                # cancel queued per-sample work so idle workers exit now
                # instead of grinding through a chunk nobody will read
                if workers is not None:
                    workers.shutdown(wait=False, cancel_futures=True)
                # propagate shutdown upstream: in a chained pipeline the
                # source is itself a generator (possibly another MT
                # assembler) whose own cleanup must run NOW, on the one
                # thread that consumed it — not whenever GC finds it
                # (that is the thread-leak window the early-exit
                # regression tests pin down)
                close = getattr(it, "close", None)
                if close is not None:
                    try:
                        close()
                    except Exception:  # source cleanup must not mask
                        pass           # the original error/_END delivery
                # _END must be DELIVERED, not best-effort: a put_nowait
                # here can hit a momentarily-full queue while the consumer
                # is alive and leave it blocked on get() forever.  The
                # stop-aware bounded put gives up only once the consumer
                # has exited (stop set in its finally).
                put_or_stop(_END)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                try:
                    # bounded get + liveness check: a producer thread
                    # that died without delivering _END (or its error)
                    # must surface on the next pull — the downstream
                    # DeviceBlockStager.take() sits directly on this
                    # generator, and an unbounded get() here would wedge
                    # the training driver forever
                    item = out_q.get(timeout=0.2)
                except queue.Empty:
                    if t.is_alive() or not out_q.empty():
                        continue
                    if failure[0] is not None:
                        raise failure[0]
                    raise RuntimeError(
                        "batch-assembly producer thread died without "
                        "delivering an end-of-stream marker or error")
                if item is _END:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            pool.close()
            # drain so the producer can observe `stop` and exit, then
            # reap it DETERMINISTICALLY: close()/throw() mid-epoch must
            # not leave the thread (or its queued batches) behind.  The
            # join is bounded — a producer stuck in a pathological
            # user transform stays a daemon and cannot hang teardown.
            while True:
                try:
                    # drained items are DATA batches discarded so the
                    # producer can observe `stop` — no futures ride
                    # this queue; graftlint: disable=GL203
                    out_q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5.0)
            while True:  # items put during the join window
                try:
                    # same deliberate discard as above
                    # graftlint: disable=GL203
                    out_q.get_nowait()
                except queue.Empty:
                    break
