"""Sample and MiniBatch.

Reference: ``DL/dataset/Sample.scala:32`` (features+label ndarrays, flat
storage) and ``DL/dataset/MiniBatch.scala:34`` (``ArrayTensorMiniBatch``
with ``slice`` for per-thread sub-batching).

Host-side data is numpy (cheap mutation, no device churn); a MiniBatch's
arrays move to device HBM when the jit'd step consumes them.  ``slice``
is kept for parity/sub-batching; per-core sub-batching itself is obsolete
under SPMD (the mesh shards the batch instead).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np


class Sample:
    """One training example: feature array(s) + label array(s)."""

    __slots__ = ("feature", "label")

    def __init__(self, feature, label=None):
        self.feature = feature
        self.label = label

    @staticmethod
    def from_ndarray(feature, label=None) -> "Sample":
        f = np.asarray(feature)
        l = None if label is None else np.asarray(label)
        return Sample(f, l)

    def feature_size(self):
        return self.feature.shape

    def label_size(self):
        return None if self.label is None else self.label.shape

    def __repr__(self):
        ls = None if self.label is None else self.label.shape
        return f"Sample(feature={self.feature.shape}, label={ls})"


class MiniBatch:
    """Batched input/target pair (pytrees of arrays with leading batch dim).

    ``lease`` is set by an assembler that lends the batch its arrays
    (``MTSampleToMiniBatch``): ``lease.release()`` hands them back for
    the next batch to be written into, and is for the one consumer that
    knows nothing reads them any more (``DeviceBlockStager``).  Everyone
    else leaves it alone and owns the arrays as ever."""

    __slots__ = ("input", "target", "lease")

    def __init__(self, input, target=None):
        self.input = input
        self.target = target
        self.lease = None

    def size(self) -> int:
        leaf = self.input
        while isinstance(leaf, (tuple, list, dict)):
            leaf = next(iter(leaf.values())) if isinstance(leaf, dict) \
                else leaf[0]
        return leaf.shape[0]

    def slice(self, offset: int, length: int) -> "MiniBatch":
        """Sub-batch [offset, offset+length) (reference
        ``MiniBatch.scala:155``)."""

        def cut(x):
            if isinstance(x, dict):
                return {k: cut(v) for k, v in x.items()}
            if isinstance(x, (tuple, list)):
                return type(x)(cut(e) for e in x)
            return x[offset:offset + length]

        return MiniBatch(cut(self.input),
                         None if self.target is None else cut(self.target))

    def __repr__(self):
        return f"MiniBatch(size={self.size()})"


@dataclass
class PaddingParam:
    """Variable-length padding config (reference ``Transformer.scala``
    PaddingParam): pad every sequence in the batch to the longest (or to
    ``fixed_length``) with ``padding_value``.

    ``buckets``: pad to the smallest listed length >= the batch's
    natural max instead — under XLA each distinct padded length is a
    separate compile, so bucketing bounds the compile count to
    ``len(buckets)`` (the SURVEY §7 "recompilation storms" mitigation;
    the reference pads per-batch because the JVM has no such cost)."""

    padding_value: float = 0.0
    fixed_length: Optional[int] = None
    buckets: Optional[Sequence[int]] = None


def _stack_padded(arrays: Sequence[np.ndarray], param: Optional[PaddingParam]):
    """Stack arrays; if ragged in dim 0 (sequence), pad per PaddingParam."""
    shapes = {a.shape for a in arrays}
    if len(shapes) == 1 and param is None:
        return np.stack(arrays)
    if param is None:
        raise ValueError(
            f"ragged samples {sorted(shapes)} need a PaddingParam")
    max_len = param.fixed_length or max(a.shape[0] for a in arrays)
    if param.buckets is not None and param.fixed_length is None:
        fitting = [b for b in sorted(param.buckets) if b >= max_len]
        if not fitting:
            raise ValueError(
                f"sequence length {max_len} exceeds the largest bucket "
                f"{max(param.buckets)}")
        max_len = fitting[0]
    out_shape = (len(arrays), max_len) + arrays[0].shape[1:]
    out = np.full(out_shape, param.padding_value, dtype=arrays[0].dtype)
    for i, a in enumerate(arrays):
        out[i, :a.shape[0]] = a
    return out


def batch_samples(samples: Sequence[Sample],
                  feature_padding: Optional[PaddingParam] = None,
                  label_padding: Optional[PaddingParam] = None) -> MiniBatch:
    """Collate samples into a MiniBatch (reference ``SampleToMiniBatch``
    internals)."""
    feats = _stack_padded([s.feature for s in samples], feature_padding)
    if samples[0].label is None:
        return MiniBatch(feats, None)
    labels = _stack_padded([np.asarray(s.label) for s in samples],
                           label_padding)
    return MiniBatch(feats, labels)


class SparseSample:
    """One example whose feature (or one of whose features) is a sparse
    1-D vector in COO form (reference ``Sample`` over ``SparseTensor``,
    ``DL/tensor/SparseTensor.scala:55-57``): ``indices[k]`` holds
    ``values[k]``, dense width ``size``.  ``dense`` optionally carries
    extra dense feature arrays alongside (the Wide&Deep layout)."""

    __slots__ = ("indices", "values", "size", "dense", "label")

    def __init__(self, indices, values, size: int, dense=None, label=None):
        self.indices = np.asarray(indices, np.int32).reshape(-1)
        self.values = np.asarray(values, np.float32).reshape(-1)
        assert self.indices.shape == self.values.shape
        self.size = int(size)
        if dense is not None and not isinstance(dense, (list, tuple)):
            dense = [dense]  # one dense side-feature, not a list of parts
        self.dense = dense
        self.label = None if label is None else np.asarray(label)

    @property
    def nnz(self) -> int:
        return len(self.indices)

    def __repr__(self):
        return (f"SparseSample(nnz={self.nnz}, size={self.size}, "
                f"dense={None if self.dense is None else 'yes'})")


class SparseMiniBatch(MiniBatch):
    """MiniBatch whose ``input`` begins with a batch-COO sparse feature
    (reference ``SparseMiniBatch``, ``DL/dataset/MiniBatch.scala:588``:
    per-batch COO tensors built from sparse samples).

    ``input`` is ``coo`` alone, or ``(coo, *dense_parts)`` when the
    samples carried dense side-features; ``coo`` is an
    ``nn.sparse.COOBatch`` ready for SparseLinear/LookupTableSparse.
    ``slice`` is unsupported: a flat COO stream has no per-sample
    alignment (sub-batching is the mesh's job under SPMD anyway)."""

    def size(self) -> int:
        coo = self.input[0] if isinstance(self.input, tuple) else self.input
        return coo.dense_shape[0]

    def slice(self, offset, length):
        raise TypeError("SparseMiniBatch does not support slice(); "
                        "shard the batch via the mesh instead")


def batch_sparse_samples(samples: Sequence[SparseSample],
                         nnz_buckets: Optional[Sequence[int]] = None
                         ) -> SparseMiniBatch:
    """Collate sparse samples into one batch-COO ``SparseMiniBatch``.

    The flat non-zero stream is padded to a STATIC length — the
    smallest fitting value of ``nnz_buckets``, or the next power of two
    — so XLA compiles one kernel per bucket instead of one per batch
    (the SURVEY §7 "recompilation storms" mitigation; padding entries
    are (row 0, col 0, value 0) and contribute nothing)."""
    from bigdl_tpu.nn.sparse import COOBatch
    import jax.numpy as jnp

    n = len(samples)
    total = sum(s.nnz for s in samples)
    if nnz_buckets is not None:
        fitting = [b for b in sorted(nnz_buckets) if b >= total]
        if not fitting:
            raise ValueError(f"batch nnz {total} exceeds the largest "
                             f"bucket {max(nnz_buckets)}")
        cap = fitting[0]
    else:
        cap = 1 if total == 0 else 1 << (total - 1).bit_length()
    row = np.zeros(cap, np.int32)
    col = np.zeros(cap, np.int32)
    val = np.zeros(cap, np.float32)
    pos = 0
    width = samples[0].size
    for i, s in enumerate(samples):
        assert s.size == width, "all sparse samples must share a width"
        row[pos:pos + s.nnz] = i
        col[pos:pos + s.nnz] = s.indices
        val[pos:pos + s.nnz] = s.values
        pos += s.nnz
    coo = COOBatch(jnp.asarray(row), jnp.asarray(col), jnp.asarray(val),
                   (n, width))
    if samples[0].dense is not None:
        dense = [np.stack([np.asarray(s.dense[i]) for s in samples])
                 for i in range(len(samples[0].dense))]
        inp = (coo, *dense)
    else:
        inp = coo
    label = None
    if samples[0].label is not None:
        label = np.stack([s.label for s in samples])
    return SparseMiniBatch(inp, label)
