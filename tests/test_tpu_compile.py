"""Compile the DLRM-CCE main path for a described TPU v5e, no chip needed.

Interpret-mode tests cannot see what Mosaic and the TPU compiler refuse:
block shapes off the (8, 128) tiling, scoped-VMEM overflow, a step that
does not fit the chip's HBM.  These tests compile, at the published
Criteo widths (``configs.dlrm_criteo.CONFIG``), the two kernels of the
main path, the donated train steps and the sharded transition, with
``interpret=False``.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library, and
every test worker imports every test file.  Keep these tests in this one
file, so one worker loads it.  The persistent compilation cache is off
around them (a chip-less compile cannot be read back).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import dlrm_criteo
from repro.core.cce import CCE
from repro.kernels import ops
from repro.launch import steps, train
from repro.launch.mesh import MODEL_AXIS, make_host_mesh, ptr_partition_spec
from repro.models import dlrm
from repro.optim import sgd
from repro.train import transition
from repro.train.loop import split_buffers

BATCH = 2048
HBM_BYTES = 16 * 10**9  # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels compile for the chip (the CPU backend would pick interpret
    mode)."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _criteo_slab():
    (grp,) = dlrm_criteo.CONFIG.collection.groups  # ONE universal supertable
    return grp.n_cols, grp.n_tables, grp.k_pad, grp.dsub


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_cce_lookup_compiles_at_criteo_shapes(one_chip, mosaic, direction):
    C, T, K, D = _criteo_slab()
    idx = jax.ShapeDtypeStruct((C, BATCH, T), jnp.int32, sharding=one_chip)
    tab = jax.ShapeDtypeStruct((C, T, K, D), jnp.float32, sharding=one_chip)
    if direction == "fwd":
        _compile(ops.cce_lookup, idx, tab)
    else:
        _compile(lambda i, t: jax.grad(
            lambda tt: ops.cce_lookup(i, tt).sum())(t), idx, tab)


#: MLPerf DLRM-DCNv2's Criteo-1TB tables and bags, CCE at 65,536 parameters
#: a table (the ``dlrm_dcnv2_criteo_1tb`` benchmark configuration)
CRITEO_1TB = dict(
    vocab_sizes=(40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 40000000,
                 3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14, 40000000, 40000000,
                 40000000, 590152, 12973, 108, 36),
    bag_sizes=(3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12, 100, 27, 10,
               3, 1, 1),
    emb_dim=128, emb_method="cce", emb_param_cap=65536, emb_c=4)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_bag_lookup_compiles_at_criteo_1tb_shapes(one_chip, mosaic, direction):
    """The multi-hot lookup at dsub 32, one launch per run of equal bag
    length (bags of 1 to 100 ids), at MLPerf's per-chip batch of 8192."""
    (grp,) = dlrm.DLRMConfig(**CRITEO_1TB).collection.groups
    assert (grp.n_cols, grp.dsub, grp.k_pad) == (104, 32, 256)
    runs = tuple(jax.ShapeDtypeStruct((n, 8192, bag * grp.n_tables), jnp.int32,
                                      sharding=one_chip) for n, bag in grp.runs)
    tab = jax.ShapeDtypeStruct((grp.n_cols, grp.n_tables, grp.k_pad, grp.dsub),
                               jnp.float32, sharding=one_chip)
    if direction == "fwd":
        _compile(ops.cce_lookup, runs, tab)
    else:
        _compile(lambda r, t: jax.grad(lambda tt: ops.cce_lookup(r, tt).sum())(t), runs, tab)


def test_kmeans_assign_compiles_at_criteo_shapes(one_chip, mosaic):
    """One ``assign_all`` chunk (``emb_cluster_chunk`` ids) against one
    column's codebook — the transition's full-vocabulary pass."""
    cfg = dlrm_criteo.CONFIG
    k = max(t.k for t in cfg.collection.tables if isinstance(t, CCE))
    dsub = cfg.emb_dim // cfg.emb_c
    x = jax.ShapeDtypeStruct((cfg.emb_cluster_chunk, dsub), jnp.float32,
                             sharding=one_chip)
    c = jax.ShapeDtypeStruct((k, dsub), jnp.float32, sharding=one_chip)
    _compile(ops.kmeans_assign, x, c)


def test_train_step_compiles_and_fits_one_chip(one_chip, mosaic):
    """The donated 1-device step the ``--no-reduced`` trainer runs, from
    ``eval_shape`` shapes, fits one chip's HBM."""
    args = train.parse_args(["--arch", "dlrm", "--no-reduced",
                             "--batch", str(BATCH)])
    cfg = train.dlrm_config(args)
    _, buffers = jax.eval_shape(lambda: dlrm.init(jax.random.PRNGKey(0), cfg))
    _, static = split_buffers(buffers)
    step, optimizer = train.dlrm_train_step(
        cfg, args, static, train.dlrm_tracker(cfg, args)
    )
    state = _on(one_chip, steps.dlrm_abstract_state(cfg, optimizer))
    batch = _on(one_chip, {
        "dense": jax.ShapeDtypeStruct((1, BATCH, cfg.n_dense), jnp.float32),
        "label": jax.ShapeDtypeStruct((1, BATCH), jnp.float32),
        "sparse": jax.ShapeDtypeStruct((1, BATCH, cfg.n_sparse), jnp.int32),
    })
    compiled = step.lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0  # the state is donated
    assert _bytes(mem) < HBM_BYTES, mem


def test_sharded_train_step_compiles_on_four_chips(topo, mosaic):
    """The ``--model-shards 4`` step (supertable k-sharded, ids routed by
    all-to-all) on a mesh of the described chips: fits each chip's HBM
    and routes through all-to-all."""
    args = train.parse_args(["--arch", "dlrm", "--no-reduced",
                             "--batch", str(BATCH), "--model-shards", "4"])
    cfg = train.dlrm_config(args)
    mesh = make_host_mesh(data=1, model=4, devices=topo.devices)
    assert set(mesh.devices.flat) == set(topo.devices)
    _, buffers = jax.eval_shape(lambda: dlrm.init(jax.random.PRNGKey(0), cfg))
    _, static = split_buffers(buffers)
    step, (state, batch), _ = steps.build_dlrm_train_step(
        cfg, mesh, batch_size=BATCH, optimizer=sgd(momentum=args.momentum),
        static_buffers=static, with_sparse=True,
    )
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-to-all" in text
    assert _bytes(compiled.memory_analysis()) < HBM_BYTES


def test_sharded_transition_compiles_on_four_chips(topo, mosaic):
    """The largest table's ``cluster_sharded`` transition as the sharded
    trainer runs it (one jitted program, ``kmeans_assign`` on): the
    pointer table enters at its at-rest layout and fits each chip."""
    args = train.parse_args(["--arch", "dlrm", "--no-reduced",
                             "--model-shards", "4"])
    cfg = train.dlrm_config(args)
    table = max((t for t in cfg.collection.tables if isinstance(t, CCE)),
                key=lambda t: t.d1)
    mesh = make_host_mesh(data=1, model=4, devices=topo.devices)

    def on(spec, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    n = min(table.d1, 256 * table.k)  # transition_table's point budget
    cluster = transition._cluster_program(
        table, mesh, MODEL_AXIS, cfg.emb_cluster_chunk, True, 256
    )
    compiled = cluster.lower(
        on(P(), (2,), jnp.uint32),
        {"tables": on(P(), (table.c, 2, table.k, table.dsub), jnp.float32)},
        {"ptr": on(ptr_partition_spec(table.c, table.d1, 4, MODEL_AXIS),
                   (table.c, table.d1), jnp.int32),
         "hs": on(P(), (table.c, 2), jnp.uint32),
         "epoch": on(P(), (), jnp.int32)},
        sample_ids=on(P(), (n,), jnp.int32),
        sample_weights=on(P(), (n,), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled.memory_analysis()) < HBM_BYTES
