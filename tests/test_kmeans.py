"""K-means: quality, distributed == serial, subsampling."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmeans as km


def _blobs(key, n_per=50, k=5, d=8, spread=0.05):
    kc, kx = jax.random.split(key)
    centers = jax.random.normal(kc, (k, d)) * 2
    pts = centers[:, None] + spread * jax.random.normal(kx, (k, n_per, d))
    return pts.reshape(-1, d), centers


def test_kmeans_recovers_blobs():
    x, centers = _blobs(jax.random.PRNGKey(0))
    res = km.kmeans(jax.random.PRNGKey(1), x, k=5, niter=25)
    # every found centroid is near a true center
    d = np.linalg.norm(
        np.asarray(res.centroids)[:, None] - np.asarray(centers)[None], axis=-1
    )
    assert d.min(axis=1).max() < 0.2
    # inertia ~ noise level
    assert float(res.inertia) / x.shape[0] < 0.1


def test_kmeans_plus_plus_spreads_seeds():
    x, _ = _blobs(jax.random.PRNGKey(2))
    seeds = km.kmeans_plus_plus(jax.random.PRNGKey(3), x, 5)
    d = np.linalg.norm(np.asarray(seeds)[:, None] - np.asarray(seeds)[None], axis=-1)
    np.fill_diagonal(d, np.inf)
    assert d.min() > 0.5  # no two seeds from the same blob


def test_assign_kernel_route():
    x, _ = _blobs(jax.random.PRNGKey(4))
    c = jax.random.normal(jax.random.PRNGKey(5), (7, 8))
    a1 = km.assign(x, c, use_kernel=False)
    a2 = km.assign(x, c, use_kernel=True)
    assert (np.asarray(a1) == np.asarray(a2)).mean() > 0.99


def test_distributed_kmeans_matches_serial_single_shard():
    """On a 1-device axis the distributed algorithm IS the serial one."""
    x, _ = _blobs(jax.random.PRNGKey(6))
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    from repro.compat import shard_map

    def run(xs):
        c, a = km.distributed_kmeans(jax.random.PRNGKey(7), xs, 5, "data", niter=20)
        return c, a

    from jax.sharding import PartitionSpec as P

    f = shard_map(run, mesh=mesh, in_specs=P("data"), out_specs=(P(), P("data")))
    c_dist, a_dist = f(x)
    res = km.kmeans(jax.random.PRNGKey(7), x, 5, niter=20)
    # same seeds + same data -> same result up to float order
    d = np.linalg.norm(
        np.asarray(c_dist)[:, None] - np.asarray(res.centroids)[None], axis=-1
    )
    assert d.min(axis=1).max() < 1e-3


def test_subsample_caps_points():
    idx = km.subsample(jax.random.PRNGKey(8), n=100_000, k=16, max_points_per_centroid=256)
    assert idx.shape[0] == 16 * 256
    assert len(np.unique(np.asarray(idx))) == idx.shape[0]
    idx2 = km.subsample(jax.random.PRNGKey(8), n=100, k=16)
    assert idx2.shape[0] == 100


def test_empty_cluster_stability():
    """Centroids with no points keep their position (no NaNs)."""
    x = jnp.ones((10, 4))
    res = km.kmeans(jax.random.PRNGKey(9), x, k=5, niter=5)
    assert bool(jnp.isfinite(res.centroids).all())
