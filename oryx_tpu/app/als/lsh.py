"""Locality-sensitive hashing for candidate pruning in top-N scoring.

Reference: app/oryx-app-serving/src/main/java/com/cloudera/oryx/app/
serving/als/model/LocalitySensitiveHash.java — hash/bits-differing
selection from target sample rate and core count (:41-124), sign-bit
hyperplane hash (:142-150), Hamming-ball candidate partitions (:156-177).

On the device LSH is a DATA LAYOUT, as it is in the reference
(PartitionedFeatureVectors: the item matrix partitioned by bucket, the
partitions inside the Hamming ball scanned).  The serving model lays its
one item store out by bucket (FeatureVectorStore.partition_by: every
phase-A step of the store holds rows of one bucket) and the streaming
scan visits only the steps of the buckets a window's queries can reach,
so a 0.3 deployment streams the bytes of its candidates, not the
catalog's.  Until PR 36 the candidate set was a per-row mask over the
whole store (popcount(bucket XOR target) fused into every tile): the
same bytes as the exact scan and more work, which the measured-cost
router therefore never served.  What the chip said of the layout is in
PERF.md (section 6, PR 36).

Every bucket product — an item's at load and on a write, a query row's
in a window — is computed by ``_bucket_kernel`` at
``Precision.HIGHEST``: at the default precision the MXU rounds the
float32 hyperplanes to bfloat16, an error of ~1e-3 in a product that is
N(0, 1) for unit-variance factors, which puts an item in fifty on the
wrong side of some hyperplane.  At HIGHEST a sign can differ from the
float32 reference's only within summation-order rounding of zero.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...common.rand import RandomManager

__all__ = ["LocalitySensitiveHash", "PUBLISHED_CORES", "choose_hash_count"]

MAX_HASHES = 20
# rows ``bucket_of`` hashes in one device call
_BUCKET_CHUNK = 1 << 20

# The "cores" the reference's rule (choose_hash_count) partitions for.
# On a chip the rule's keep-the-cores-busy half means nothing and only
# its sample-rate half binds, so the one number that makes a sample
# rate mean what the reference's published rows meant by it is the core
# count those rows ran on: the 32-core Haswell Xeon of
# https://oryx.io/docs/performance.html.  At 0.3 it gives 8 hyperplanes
# and a Hamming radius of 2: 37 of 256 buckets, 14.45% of the catalog
# (and 668 / 134 ms = 5.0x is what 1 / 0.1445 = 6.9x less fixed costs
# bears out).  The former hidden default of 8 made 0.3 mean 7 hashes,
# radius 1: 6.25%, under the 0.1 the reference warns against.
PUBLISHED_CORES = 32


def _binom(n: int, k: int) -> int:
    return math.comb(n, k)


def choose_hash_count(sample_rate: float, num_cores: int) -> tuple[int, int]:
    """(num_hashes, max_bits_differing) achieving approximately the target
    sample rate while keeping ~num_cores partitions in play — the
    reference's selection loop (:41-75), reimplemented from its contract."""
    num_hashes = 0
    bits_differing = 0
    while num_hashes < MAX_HASHES:
        bits_differing = 0
        num_partitions_to_try = 1
        while bits_differing < num_hashes and num_partitions_to_try < num_cores:
            bits_differing += 1
            num_partitions_to_try += _binom(num_hashes, bits_differing)
        if bits_differing == num_hashes and num_partitions_to_try < num_cores:
            num_hashes += 1
            continue
        if num_partitions_to_try <= sample_rate * (1 << num_hashes):
            break
        num_hashes += 1
    return num_hashes, bits_differing


@partial(jax.jit, static_argnames=("num_hashes",))
def _bucket_kernel(vectors, hyperplanes, num_hashes: int):
    """Sign-bit bucket ids for a block of vectors: one matmul + packbits,
    the products in float32 at HIGHEST (module docstring)."""
    signs = jnp.matmul(vectors.astype(jnp.float32), hyperplanes.T,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST) > 0.0
    weights = jnp.asarray([1 << i for i in range(num_hashes)], dtype=jnp.int32)
    return jnp.sum(signs.astype(jnp.int32) * weights[None, :], axis=1)


@jax.jit
def _popcount(x):
    # 32-bit popcount, classic SWAR
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    return (x * 0x01010101) >> 24


class LocalitySensitiveHash:
    """Hyperplane LSH over factor vectors."""

    def __init__(self, sample_rate: float, num_features: int,
                 num_cores: int = PUBLISHED_CORES):
        self.sample_rate = sample_rate
        self.num_features = num_features
        self._hp_dev: jax.Array | None = None
        self.num_hashes, self.max_bits_differing = choose_hash_count(
            sample_rate, num_cores)
        rng = RandomManager.random()
        if self.num_hashes > 0:
            # near-orthogonal hyperplanes: random Gaussian block, then QR
            # when rank allows (cleaner than the reference's random search
            # for "most orthogonal next vector"; same goal)
            g = rng.standard_normal((self.num_hashes, num_features))
            if self.num_hashes <= num_features:
                q, _ = np.linalg.qr(g.T)
                g = q.T[:self.num_hashes]
            self.hyperplanes = np.ascontiguousarray(g, dtype=np.float32)
        else:
            self.hyperplanes = np.zeros((0, num_features), dtype=np.float32)

    @property
    def num_partitions(self) -> int:
        return 1 << self.num_hashes

    def _device_hyperplanes(self) -> jax.Array:
        if self._hp_dev is None:
            self._hp_dev = jnp.asarray(self.hyperplanes)
        return self._hp_dev

    def bucket_of(self, vectors: np.ndarray) -> np.ndarray:
        """Bucket index for each row vector (reference getIndexFor :142)."""
        if self.num_hashes == 0:
            return np.zeros(len(vectors), dtype=np.int32)
        # in row chunks, in the dtype they are stored in: a 20M x 250
        # bfloat16 matrix is hashed 1M rows (0.5 GB) at a time
        out = np.empty(len(vectors), dtype=np.int32)
        for at in range(0, len(vectors), _BUCKET_CHUNK):
            part = vectors[at:at + _BUCKET_CHUNK]
            n = len(part)
            if at and n < _BUCKET_CHUNK:    # one compiled shape for the tail
                part = np.concatenate([part, np.zeros(
                    (_BUCKET_CHUNK - n, part.shape[1]), part.dtype)])
            out[at:at + n] = np.asarray(
                self.device_buckets(jnp.asarray(part)))[:n]
        return out

    def device_buckets(self, vectors: jax.Array) -> jax.Array:
        """Bucket ids of device-resident vectors (at their true width)."""
        if self.num_hashes == 0:
            return jnp.zeros(vectors.shape[0], dtype=jnp.int32)
        return _bucket_kernel(vectors, self._device_hyperplanes(),
                              self.num_hashes)

    def candidate_mask(self, query_vector: np.ndarray,
                       item_buckets: jax.Array) -> jax.Array:
        """Device-side bool mask of items within the Hamming ball of the
        query's bucket (reference getCandidateIndices :156-177 as a mask).
        Fully asynchronous: the target bucket is computed on device too,
        so building the mask never blocks on a host round trip."""
        if self.num_hashes == 0 or self.max_bits_differing >= self.num_hashes:
            return jnp.ones(item_buckets.shape, dtype=bool)
        q = jnp.asarray(np.asarray(query_vector, np.float32)[None, :])
        target = _bucket_kernel(q, self._device_hyperplanes(),
                                self.num_hashes)[0]
        diff = _popcount(jnp.bitwise_xor(item_buckets, target))
        return diff <= self.max_bits_differing

    def candidate_indices(self, query_vector: np.ndarray) -> np.ndarray:
        """All bucket ids within the Hamming ball (for partition-oriented
        callers; reference getCandidateIndices return form)."""
        target = int(self.bucket_of(query_vector[None, :])[0])
        if self.max_bits_differing >= self.num_hashes:
            return np.arange(self.num_partitions, dtype=np.int32)
        all_buckets = np.arange(self.num_partitions, dtype=np.int32)
        diff = np.bitwise_xor(all_buckets, target)
        pop = np.vectorize(lambda v: bin(v).count("1"))(diff) if len(diff) else diff
        return all_buckets[pop <= self.max_bits_differing]
