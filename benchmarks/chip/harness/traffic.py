"""The one traffic generator: every mix under ``traffic/`` is a data file of
parameters that this module reads.

Ids follow a Zipf law over each feature's vocabulary (rank = id, id 0 the
most frequent) or a uniform law, drawn by inverse-CDF sampling so one draw
costs a binary search and not a scan of the vocabulary.  Labels follow a
planted-concept click model: each id belongs to one of ``n_latent``
concepts (a hash of the id and the feature), each concept carries a weight,
and the click probability is the sigmoid of the summed weights, a linear
term in the dense features and Gaussian noise.  A training configuration may
give each feature a bag of a fixed number of ids (``bag_sizes``): the bags
lie side by side in the id columns, and a bag adds the mean of its ids'
concept weights.  A bag is built as MLPerf DLRM-DCNv2 builds its multi-hot
data (mlcommons/training ``recommendation_v2/torchrec_dlrm/multi_hot.py``,
distribution ``uniform``): its first id is drawn from the feature's law and
the others are fixed for that id, uniform over the vocabulary.

Seeds: the run's seed orders the work and draws the ids; the multiset of
query sizes and inter-arrival gaps is drawn from the mix's own fixed seed,
so every run seed offers the same amount of work in another order.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def load_mix(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        mix = json.load(f)
    if mix.get("name") != name:
        raise ValueError(f"traffic file {name}.json names itself {mix.get('name')!r}")
    return mix


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one purpose of one run; any non-negative seed,
    however large, maps to its own stream."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


class IdSampler:
    """Inverse-CDF sampler of ids for each feature's vocabulary."""

    def __init__(self, vocab_sizes, law: str, zipf_a: float = 1.1):
        if law not in ("zipf", "uniform"):
            raise ValueError(f"unknown id law {law!r}")
        self.vocab_sizes = tuple(int(v) for v in vocab_sizes)
        self.law = law
        self._cdf = []
        if law == "zipf":
            for v in self.vocab_sizes:
                w = np.arange(1, v + 1, dtype=np.float64) ** -zipf_a
                cdf = np.cumsum(w)
                self._cdf.append(cdf / cdf[-1])

    def sample(self, rng: np.random.Generator, n: int, bag_sizes=None) -> np.ndarray:
        """(n, sum(bag_sizes)) int32 ids, feature f's bag in the columns
        from ``sum(bag_sizes[:f])`` on; one id a feature by default.  A
        bag's first id is drawn from the feature's law, with the same draws
        as without bags; its j-th is ``bag_id`` of that first id."""
        feats = bag_features(len(self.vocab_sizes), bag_sizes)
        u = rng.random((len(self.vocab_sizes), n))
        out = np.empty((n, len(feats)), np.int32)
        col = 0
        for f, v in enumerate(self.vocab_sizes):
            if self.law == "zipf":
                ids = np.searchsorted(self._cdf[f], u[f], side="right")
            else:
                ids = (u[f] * v).astype(np.int64)
            out[:, col] = np.minimum(ids, v - 1)
            size = 1 if bag_sizes is None else int(bag_sizes[f])
            for j in range(1, size):
                out[:, col + j] = bag_id(out[:, col], f, j, v)
            col += size
        return out


def bag_features(n_features: int, bag_sizes=None) -> np.ndarray:
    """The feature of each id column: feature f repeated ``bag_sizes[f]``
    times, in feature order (each feature once without bags)."""
    if bag_sizes is None:
        return np.arange(n_features)
    if len(bag_sizes) != n_features or min(bag_sizes) < 1:
        raise ValueError(f"bag_sizes {bag_sizes} for {n_features} features")
    return np.repeat(np.arange(n_features), bag_sizes)


def bag_id(first: np.ndarray, f: int, j: int, vocab: int) -> np.ndarray:
    """The j-th id (j >= 1) of feature f's bags that start with ``first``:
    a fixed hash of (feature, first id, j), uniform over ``[0, vocab)``.
    MLPerf keeps these ids in a table drawn once from a fixed seed; a hash
    gives the same law with no table of vocab x bag ids."""
    salt = ((f * 4096 + j) * 0xD1B54A32D192ED03 + 1) & 0xFFFFFFFFFFFFFFFF
    x = first.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + np.uint64(salt)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return (((x >> np.uint64(32)) * np.uint64(vocab)) >> np.uint64(32)).astype(np.int32)


def _concept(ids: np.ndarray, f: int, n_latent: int) -> np.ndarray:
    x = ids.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + np.uint64(f * 0x632BE5AB + 1)
    x ^= x >> np.uint64(29)
    return (x % np.uint64(n_latent)).astype(np.int64)


class ClickModel:
    """The planted-concept labels; its weights come from the mix's seed."""

    def __init__(self, n_features: int, n_dense: int, n_latent: int, noise: float,
                 seed: int):
        rng = rng_for(seed, 1)
        self.n_latent = n_latent
        self.noise = noise
        self.concept_w = rng.normal(0.0, 1.0, (n_features, n_latent))
        self.dense_w = rng.normal(0.0, 0.3, n_dense)

    def labels(self, rng: np.random.Generator, dense, sparse, bag_sizes=None) -> np.ndarray:
        logit = dense.astype(np.float64) @ self.dense_w
        feats = bag_features(len(self.concept_w), bag_sizes)
        for f in range(len(self.concept_w)):
            cols = np.flatnonzero(feats == f)
            bag = self.concept_w[f][_concept(sparse[:, cols[0]], f, self.n_latent)]
            for j in cols[1:]:
                bag = bag + self.concept_w[f][_concept(sparse[:, j], f, self.n_latent)]
            logit = logit + bag / len(cols)
        logit = logit + rng.normal(0.0, self.noise, len(logit))
        return (rng.random(len(logit)) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)


def samples(sampler: IdSampler, n_dense: int, rng: np.random.Generator, n: int,
            bag_sizes=None):
    """(dense (n, n_dense) f32 N(0, 1), sparse (n, sum(bag_sizes)) int32)."""
    dense = rng.standard_normal((n, n_dense), dtype=np.float32)
    return dense, sampler.sample(rng, n, bag_sizes)


def train_pool(mix: dict, vocab_sizes, n_dense: int, batch: int, seed: int,
               bag_sizes=None) -> list[dict]:
    """``mix["pool_batches"]`` distinct labelled batches, cycled by the
    window, so the window never waits on the generator; ``bag_sizes``, the
    configuration's, gives each feature a bag of that many ids."""
    sampler = IdSampler(vocab_sizes, mix["ids"], mix.get("zipf_a", 1.1))
    click = ClickModel(len(vocab_sizes), n_dense, mix["n_latent"], mix["noise"],
                       mix["mix_seed"])
    rng = rng_for(seed, 2)
    pool = []
    for _ in range(int(mix["pool_batches"])):
        dense, sparse = samples(sampler, n_dense, rng, batch, bag_sizes)
        pool.append({"dense": dense, "sparse": sparse,
                     "label": click.labels(rng, dense, sparse, bag_sizes)})
    return pool


def query_schedule(mix: dict, seconds: float, seed: int):
    """Open-loop queries due in [0, seconds): (due offsets in s, sizes).

    Gaps are exponential at ``rate_qps`` (Poisson arrivals) and sizes
    uniform in [size_min, size_max]; both are drawn from the mix's fixed
    seed and then shuffled by the run seed."""
    rate = float(mix["rate_qps"])
    n = int(np.ceil(rate * seconds))
    fixed = rng_for(mix["mix_seed"], 3)
    gaps = fixed.exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()  # exactly ``n`` queries over the window
    sizes = fixed.integers(mix["size_min"], mix["size_max"] + 1, n)
    order = rng_for(seed, 4)
    gaps = order.permutation(gaps)
    sizes = order.permutation(sizes)
    due = np.cumsum(gaps) - gaps[0]
    return due, sizes
