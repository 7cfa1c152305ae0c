"""The DLRM-DCNv2 configuration (``configs/dlrm_dcnv2_criteo_1tb.json`` and
its reference) through the whole harness: the program it builds at the
published widths, a tiny size of the cell that comes out correct, traced
and untraced, and faults it must catch — among them a bag fault that only
``sketch_delta_gap`` sees."""
import json

import numpy as np
import pytest

import tiny
from harness import faults, program

CELL = "train_1tb_dcnv2_cce"
#: the tiny size: six features with bags of mixed lengths, 1 among them
TINY = {"bag_sizes": [3, 1, 5, 2, 1, 4], "emb_dim": 16, "cross_layers": 2, "cross_rank": 8,
        "bottom_mlp": [32, 16], "top_mlp": [32, 8, 1]}


def _config() -> dict:
    return json.loads((tiny.bench.HERE / "configs" / "dlrm_dcnv2_criteo_1tb.json").read_text())


def _run(**kw):
    return tiny.run(CELL, "train", config=TINY, **kw)


def test_program_at_the_published_widths():
    """Every published number reaches the program: one universal group of
    104 columns of dsub 32 (18 CCE tables of k = 256, 8 full tables), one
    launch per run of equal bag length, DCNv2 with Adagrad."""
    from repro.launch import train as entry

    cfg = _config()
    pcfg = program.dlrm_config(cfg)
    assert pcfg.interaction == "dcnv2" and (pcfg.cross_layers, pcfg.cross_rank) == (3, 512)
    assert sum(pcfg.bag_sizes) == 214 and sum(pcfg.vocab_sizes) == 204_184_588
    (grp,) = pcfg.collection.groups
    assert (grp.kind, grp.n_cols, grp.n_tables, grp.k_pad, grp.dsub) == ("univ", 104, 2, 256, 32)
    assert sum(n for n, _ in grp.runs) == 104 and len(grp.runs) == 20
    assert sum(n * bag for n, bag in grp.runs) == 4 * 214
    assert cfg["optimizer"]["name"] in entry.DLRM_OPTIMIZERS
    assert cfg["optimizer"]["clip_norm"] is None and cfg["reduced"] == ["train_batch"]


def test_model_flops_per_example():
    """``train_mfu.dcnv2`` counts 96.17 MFLOP a trained example (0.79 TFLOP
    a step of 8192): the dense arch, three rank-512 cross layers over
    3,456 inputs and the over arch, backward twice the forward."""
    mfu = tiny.bench.load_module(tiny.bench.HERE / "metrics" / "train_mfu.dcnv2.py")
    cfg = _config()
    fwd = (2 * (13 * 512 + 512 * 256 + 256 * 128) + 3 * 4 * 3456 * 512
           + 2 * (3456 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256))
    assert mfu.dcnv2_train_flops(cfg) == 3 * fwd - 2 * 13 * 512 == 96_169_472
    assert mfu.read({"mode": "train", "cfg": {**cfg, "interaction": "dot"},
                     "run": {}, "peak": {"flops_per_s": 1.0}}) is None


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_cell_rehearses_correct(traced):
    r = _run(seed=2**31 + 77, trace=traced)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0 and r["window_compiles"] == 0
    assert set(r["checks"]) == {"loss_gap", "grad_gap_median", "change_gap_median",
                                "sketch_delta_gap", "head_miss_share", "head_count_gap"}


def _bag_heads_only(trk):
    """The in-step counter and the fold read each bag's first id in every
    position of the bag."""
    bags = [trk.bag_sizes[f] for f in trk.tracked]
    first = np.cumsum([0] + bags)[:-1]
    trk.tracked_cols = np.repeat(trk.tracked_cols[first], bags)


def test_bag_fault_fails_the_sketch_delta():
    """Counting one id a bag, not the whole bag, leaves the model's
    numbers within their limits; ``sketch_delta_gap`` catches it."""
    checks = _run(seed=5, seconds=0.3, hooks={"tracker_hook": _bag_heads_only})["checks"]
    assert checks["sketch_delta_gap"]["value"] > checks["sketch_delta_gap"]["limit"], checks
    for name in ("loss_gap", "grad_gap_median", "change_gap_median"):
        assert checks[name]["value"] <= checks[name]["limit"], checks


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "wrong_hash"])
def test_fault_fails(fault):
    r = _run(seed=5, seconds=0.3, hooks=faults.hooks(fault))
    assert not r["correct"], r["checks"]


def test_control_reads_apart_from_the_program():
    """The control (the reference at ``high`` in the program's place) reads
    a loss gap the program does not; whether it crosses a limit is shown
    at the cell's own size on the chip (``calibrate.py``)."""
    prog = _run(seed=9, seconds=0.3)["checks"]
    ctl = _run(seed=9, seconds=0.3, control="high")["checks"]
    assert ctl["loss_gap"]["value"] > 4 * prog["loss_gap"]["value"]
    assert ctl["loss_gap"]["value"] > 0
