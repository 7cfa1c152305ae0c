"""Checkpoint store: atomic commit, async, retention, cross-mesh restore."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointManager,
    load_checkpoint,
    reshard_restore,
    save_checkpoint,
)
from repro.checkpoint.store import list_checkpoints


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "w": jax.random.normal(k, (8, 4)),
        "opt": {"m": jnp.zeros((8, 4)), "t": jnp.int32(7)},
    }


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t, extra={"note": "x"})
    step, back, extra = load_checkpoint(str(tmp_path), template=t)
    assert step == 3 and extra == {"note": "x"}
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoints_ignored(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    p = save_checkpoint(str(tmp_path), 2, t)
    os.remove(os.path.join(p, "_COMMITTED"))  # simulate crash mid-save
    step, _, _ = load_checkpoint(str(tmp_path), template=t)
    assert step == 1


def test_async_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for s in range(5):
        mgr.save_async(s, _tree(s))
    mgr.wait()
    steps = [s for s, _ in list_checkpoints(str(tmp_path))]
    assert steps == [3, 4]
    step, back, _ = mgr.restore_latest(_tree())
    assert step == 4
    want = _tree(4)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(want["w"]))


def test_async_error_surfaces(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "sub"), keep_last=1)
    mgr.save_async(0, _tree())
    mgr.wait()
    # replace the checkpoint dir with a FILE: the background writer must
    # fail, and the failure must surface on the next wait() (tests run as
    # root, so permission bits alone wouldn't fail)
    shutil.rmtree(mgr.directory)
    with open(mgr.directory, "w") as f:
        f.write("not a directory")
    try:
        mgr.save_async(1, _tree())
        with pytest.raises(BaseException):
            mgr.wait()
    finally:
        os.remove(mgr.directory)


def test_reshard_restore_other_sharding(tmp_path):
    """Save unsharded, restore onto an explicit (1-device) mesh sharding —
    the elastic-rescale path in miniature."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = _tree()
    save_checkpoint(str(tmp_path), 0, t)
    _, host, _ = load_checkpoint(str(tmp_path), template=t)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    sh = {
        "w": NamedSharding(mesh, P("data", None)),
        "opt": {"m": NamedSharding(mesh, P()), "t": NamedSharding(mesh, P())},
    }
    placed = reshard_restore(host, sh)
    assert placed["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(placed["w"]), np.asarray(t["w"]))


def test_sectioned_restore_drops_and_defaults_toplevel_keys(tmp_path):
    """New-format checkpoints carry a top-level section index: a reader
    missing a stored section drops it by NAME, a reader with a NEW
    section keeps its template default — no leaf-count arithmetic."""
    t = _tree()
    stored = dict(t, aux=[np.arange(5), np.float64(2.5)])
    save_checkpoint(str(tmp_path), 1, stored)
    # reader without "aux": section dropped
    _, back, _ = load_checkpoint(str(tmp_path), template=t)
    assert "aux" not in back
    np.testing.assert_array_equal(
        np.asarray(back["w"]), np.asarray(t["w"]))
    # reader with an extra section the writer lacked: template default kept
    t2 = dict(t, trigger=[np.int64(0), np.zeros(3)])
    _, back2, _ = load_checkpoint(str(tmp_path), template=t2)
    np.testing.assert_array_equal(np.asarray(back2["trigger"][1]), np.zeros(3))
    np.testing.assert_array_equal(np.asarray(back2["w"]), np.asarray(t["w"]))
    # shared sections still shape-check: a wrong-shape template fails
    bad = dict(t, w=np.zeros((2, 2)))
    with pytest.raises(ValueError):
        load_checkpoint(str(tmp_path), template=bad)


def test_sectioned_restore_prefers_consuming_over_dropping(tmp_path):
    """A candidate that MIGRATES a stored section must win over an
    earlier candidate that would merely drop it."""
    t = _tree()
    stored = dict(t, counts=[np.arange(4, dtype=np.int64)])
    save_checkpoint(str(tmp_path), 1, stored)
    dropper = dict(t)  # would match by dropping "counts"
    migrator = dict(t, counts=[np.zeros(4, np.int64)])

    def convert(tree):
        return dict(tree, counts=[tree["counts"][0] * 10])

    _, back, _ = load_checkpoint(
        str(tmp_path), migrations=[(dropper, None), (migrator, convert)]
    )
    np.testing.assert_array_equal(
        np.asarray(back["counts"][0]), np.arange(4) * 10
    )
