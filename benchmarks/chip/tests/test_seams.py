"""What a training configuration brings in its own files, with no edit to
the harness: program keys, dense weights, an optimizer with state (the
test-only ``seam_model.json`` and ``seam_reference.py`` beside this file),
multi-hot bags in the traffic, the lookup work of bags, and span args
read from a trace."""
import glob
import json
import threading
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import compare, program, traffic, train, weights as W, work
from harness import trace as tr

TESTS = Path(__file__).resolve().parent
SEAM = {"name": "train_seam_model", "config": "seam_model", "traffic": "zipf_train",
        "chips": 1}


def _seam_cfg() -> dict:
    return json.loads((TESTS / "seam_model.json").read_text())


@pytest.mark.parametrize("traced", [False, True])
def test_own_model_rehearses_correct(traced):
    """A configuration with a DLRMConfig field the harness names nowhere
    (``emb_use_kernel``), its own ``make_dense`` and SGD with momentum runs
    through ``bench.run`` and comes out correct."""
    r = tiny.bench.run(SEAM["name"], 3, 0.5, traced, require_chip=False,
                       overrides={"cell": SEAM, "config_dir": str(TESTS),
                                  "traffic": tiny.TRAFFIC["train"]})
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0 and r["window_compiles"] == 0


def test_own_model_reaches_the_program():
    cfg = _seam_cfg()
    pcfg = program.dlrm_config(cfg)
    assert pcfg.emb_use_kernel is False and pcfg.top_mlp == (24, 12, 1)
    ref = tiny.bench.load_module(TESTS / "seam_reference.py")
    make = W.make_fn(cfg, ref)
    params, bufs = make[0](W.seed_key(0)), make[1](W.seed_key(0))
    assert [layer["w"].shape for layer in params["top"]] == [(16 + 21, 24), (24, 12), (12, 1)]
    assert all(float(jnp.abs(layer["b"]).max()) > 0 for layer in params["top"])
    prog, _ = program.to_program(pcfg, params, bufs)
    assert sorted(prog) == ["bottom", "emb", "top"] and prog["top"] is params["top"]


def test_momentum_state_is_carried():
    """The readings see the optimizer's state: three steps with momentum
    move the parameters apart from three without, by far more than the
    limit on the change."""
    cfg = _seam_cfg()
    ref = tiny.bench.load_module(TESTS / "seam_reference.py")
    make, key = W.make_fn(cfg, ref), W.seed_key(4)
    mix = {**traffic.load_mix("zipf_train"), **tiny.TRAFFIC["train"]}
    pool = traffic.train_pool(mix, cfg["vocab_sizes"], cfg["n_dense"], cfg["train_batch"], 4)
    with_m = train.reference_readings(ref, make, key, pool, cfg, "highest")
    flat = {**cfg, "optimizer": {**cfg["optimizer"], "momentum": 0.0}}
    without = train.reference_readings(ref, make, key, pool, flat, "highest")
    np.testing.assert_array_equal(with_m["grad"], without["grad"])
    gaps = np.abs(with_m["change"] - without["change"]) / without["change"]
    assert np.median(gaps) > 100 * cfg["limits"]["train"]["change_gap_median"]


def test_unknown_key_fails_with_its_name():
    with pytest.raises(ValueError, match="cross_layerz"):
        program.dlrm_config({**_seam_cfg(), "cross_layerz": 3})


def test_optimizer_block_reaches_the_entry_point(monkeypatch):
    """Every key of the optimizer block is handed to ``dlrm_train_step``:
    the name as ``optimizer``, the clip norm as ``clip_norm``, once the
    entry point names the optimizers it picks by."""
    import repro.launch.train as entry

    seen = {}
    monkeypatch.setattr(entry, "dlrm_train_step",
                        lambda pcfg, args, static, trk: seen.update(vars(args)) or "step")
    monkeypatch.setattr(entry, "DLRM_OPTIMIZERS", ("sgd", "adagrad"), raising=False)
    cfg = {**_seam_cfg(), "optimizer": {"name": "adagrad", "lr": 0.004, "momentum": 0.0,
                                        "clip_norm": 0.25, "eps": 1e-8}}
    assert program.train_step(program.dlrm_config(cfg), cfg, {}, None) == "step"
    assert seen == {"optimizer": "adagrad", "lr": 0.004, "momentum": 0.0, "clip_norm": 0.25,
                    "eps": 1e-8, "accum": 1}
    cfg["optimizer"]["name"] = "lamb"
    with pytest.raises(ValueError, match="'lamb'"):
        program.train_step(program.dlrm_config(cfg), cfg, {}, None)


@pytest.mark.parametrize("name,clip", [("adagrad", 1.0), ("sgd", 0.25)])
def test_optimizer_the_entry_point_ignores_is_refused(monkeypatch, name, clip):
    """An entry point that names no optimizers trains SGD at the step's
    default clip whatever it is handed: another name or clip is refused by
    name before a step is built, and SGD at clip 1.0 goes through."""
    import repro.launch.train as entry

    monkeypatch.delattr(entry, "DLRM_OPTIMIZERS", raising=False)
    monkeypatch.setattr(entry, "dlrm_train_step", lambda pcfg, args, static, trk: "step")
    cfg = {**_seam_cfg(), "optimizer": {"name": name, "lr": 0.01, "momentum": 0.0,
                                        "clip_norm": clip}}
    with pytest.raises(ValueError, match=f"not '{name}' at clip_norm {clip}"):
        program.train_step(program.dlrm_config(cfg), cfg, {}, None)
    cfg["optimizer"].update(name="sgd", clip_norm=1.0)
    assert program.train_step(program.dlrm_config(cfg), cfg, {}, None) == "step"


def test_a_dense_block_of_its_own_is_carried():
    """A dense block the old harness could not make (a ``cross`` key) goes
    into the program's params and back unchanged, and the leaf norms of
    the readings report its leaves."""
    cfg = _seam_cfg()
    seam = tiny.bench.load_module(TESTS / "seam_reference.py")

    def make_dense(cfg, key):
        return {**seam.make_dense(cfg, key),
                "cross": [{"w": jnp.full((3, 4), 2.0), "b": jnp.ones(4)}]}

    make = W.make_fn(cfg, types.SimpleNamespace(make_dense=make_dense))
    params, bufs = make[0](W.seed_key(1)), make[1](W.seed_key(1))
    pcfg = program.dlrm_config(cfg)
    prog, _ = program.to_program(pcfg, params, bufs)
    assert sorted(prog) == ["bottom", "cross", "emb", "top"] and prog["cross"] is params["cross"]
    back = program.per_feature(pcfg, prog)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    moved = jax.tree.map(lambda x: x, back)  # new containers, the same leaves
    moved["cross"][0]["w"] = moved["cross"][0]["w"] + 1.0
    norms = np.asarray(compare.diff_norms(moved, params))
    paths = [jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    assert len(norms) == len(paths)
    nonzero = [i for i, n in enumerate(norms) if n != 0]
    assert [paths[i] for i in nonzero] == ["['cross'][0]['w']"]
    assert norms[nonzero[0]] == pytest.approx(np.sqrt(12.0))


def test_dlrm_reference_refuses_what_it_does_not_train():
    ref = tiny.bench.load_module(tiny.bench.HERE / "configs" / "dlrm_reference.py")
    cfg = {"optimizer": {"name": "sgd", "lr": 0.1, "momentum": 0.9, "clip_norm": 1.0}}
    with pytest.raises(ValueError, match="plain SGD"):
        ref.init_opt({}, cfg)


def test_bags_in_the_pool():
    """Bags as MLPerf's multi-hot data has them: each bag's first id is
    today's one-hot draw, and its other ids are fixed for that first id."""
    mix = {**traffic.load_mix("zipf_train"), "pool_batches": 2}
    vocab = [100, 5000, 7]
    pool = traffic.train_pool(mix, vocab, 13, 32, 2**33 + 5, bag_sizes=[3, 1, 2])
    today = traffic.train_pool(mix, vocab, 13, 32, 2**33 + 5)
    for b, t in zip(pool, today):
        assert b["sparse"].shape == (32, 6) and b["sparse"].dtype == np.int32
        for j, v in enumerate([100, 100, 100, 5000, 7, 7]):
            assert 0 <= b["sparse"][:, j].min() and b["sparse"][:, j].max() < v
        assert set(np.unique(b["label"])) <= {0.0, 1.0}
        np.testing.assert_array_equal(b["sparse"][:, [0, 3, 4]], t["sparse"])
        for first, rest in ((0, [1, 2]), (4, [5])):
            for i in np.unique(b["sparse"][:, first]):
                bags = b["sparse"][b["sparse"][:, first] == i][:, rest]
                assert (bags == bags[0]).all()
    # Zipf 1.1 puts the first id at 0 in a third of the rows; the others are not
    assert (pool[0]["sparse"][:, 0] != pool[0]["sparse"][:, 1]).any()
    one = traffic.train_pool(mix, vocab, 13, 32, 2**33 + 5, bag_sizes=[1, 1, 1])
    for a, b in zip(one, today):
        for k in a:
            assert a[k].dtype == b[k].dtype and a[k].tobytes() == b[k].tobytes()
    with pytest.raises(ValueError):
        traffic.train_pool(mix, vocab, 13, 32, 1, bag_sizes=[1, 0, 1])


def test_bag_ids_are_uniform_over_the_vocabulary():
    """The ids after a bag's first are spread over the whole table, as
    MLPerf's ``uniform`` multi-hot distribution spreads them, and are
    nearly all distinct, even where the first ids repeat."""
    v, size = 40_000_000, 100
    first = np.repeat(np.arange(50, dtype=np.int32), 20)
    rest = np.stack([traffic.bag_id(first, 20, j, v) for j in range(1, size)], axis=1)
    assert rest.dtype == np.int32 and rest.min() >= 0 and rest.max() < v
    per_bag = rest[::20]
    assert len(np.unique(per_bag)) > 0.99 * per_bag.size
    assert abs(per_bag.mean() / v - 0.5) < 0.01
    assert abs(np.median(per_bag) / v - 0.5) < 0.02
    np.testing.assert_array_equal(rest[0], rest[19])
    assert (traffic.bag_id(first, 21, 1, v) != rest[:, 0]).mean() > 0.99


def test_bag_label_is_the_mean_of_its_concepts():
    click = traffic.ClickModel(2, 1, 8, 0.0, seed=3)
    sparse = np.array([[1, 2, 3]], np.int32)  # feature 0's bag (1, 2), feature 1's (3)
    want = (np.mean([click.concept_w[0][traffic._concept(np.array([i]), 0, 8)[0]]
                     for i in (1, 2)]) + click.concept_w[1][traffic._concept(
                         np.array([3]), 1, 8)[0]])
    rng_a, rng_b = traffic.rng_for(1, 9), traffic.rng_for(1, 9)
    got = click.labels(rng_a, np.zeros((1, 1), np.float32), sparse, bag_sizes=[2, 1])
    noise = rng_b.normal(0.0, 0.0, 1)
    p = 1.0 / (1.0 + np.exp(-(want + noise)))
    assert got[0] == float(rng_b.random(1)[0] < p[0])


def test_lookup_work_of_bags():
    cfg = {"vocab_sizes": [1000, 3], "emb_dim": 16, "emb_method": "cce",
           "emb_param_cap": 512, "emb_c": 4}
    shapes = W.table_shapes(cfg)
    # CCE: 3 ids a bag, each 32 B of indices and 32 adds, one 64 B output
    # row a bag; full: 2 ids a bag, each 4 B and 16 adds, one 64 B row
    adds, nbytes = work.lookup_work(shapes, 8, backward=False, bag_sizes=[3, 2])
    assert adds == 8 * (3 * 32 + 2 * 16)
    assert nbytes == 8 * (3 * 32 + 64) + 2048 + 8 * (2 * 4 + 64) + 192
    assert work.lookup_work(shapes, 8, backward=True, bag_sizes=[1, 1]) == work.lookup_work(
        shapes, 8, backward=True)


def test_load_keeps_span_args_and_threads(tmp_path):
    """A span's keyword arguments come back from a CPU profile as its args,
    on the line of the thread that opened it."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("seam-span", admitted=7):
            jnp.ones(4).block_until_ready()
        with jax.profiler.TraceAnnotation("seam-plain"):
            pass

        def other():
            with jax.profiler.TraceAnnotation("seam-span", admitted=5):
                pass
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    trace = tr.load_file(path)
    spans = [e for e in trace.host if e.name == "seam-span"]
    assert sorted(e.args["admitted"] for e in spans) == [5, 7]
    assert len({e.thread for e in spans}) == 2
    main = [e.thread for e in spans if e.args["admitted"] == 7][0]
    assert sum(e.args["admitted"] for e in spans if e.thread == main) == 7
    plain = [e for e in trace.host if e.name == "seam-plain"]
    assert len(plain) == 1 and plain[0].args is None and plain[0].thread == main
