"""A run with its timed path broken underneath comes out not correct, once
for each fault a cell can have; and the control (the reference in the
program's place at the precision below the configuration's) reads apart
from the program."""
import pytest

import tiny
from harness import faults

TRAIN_FAULTS = ["state_unchanged", "half_batch"]
SKETCH_FAULTS = ["cells_to_zero", "wrong_hash", "dropped_folds", "no_head_updates", "no_decay"]


@pytest.mark.parametrize("workload", ["train_kaggle_cce", "train_kaggle_full"])
@pytest.mark.parametrize("fault", TRAIN_FAULTS)
def test_train_fault_fails(workload, fault):
    r = tiny.run(workload, "train", seed=5, seconds=0.3, hooks=faults.hooks(fault))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault,number", [
    ("cells_to_zero", "sketch_delta_gap"), ("wrong_hash", "sketch_delta_gap"),
    ("dropped_folds", "head_count_gap"), ("no_head_updates", "head_miss_share"),
    ("no_decay", "head_count_gap")])
def test_sketch_fault_fails(fault, number):
    """A fault in the sketch counter or the tracker's fold fails the number
    that watches it, with the model's numbers still within their limits."""
    r = tiny.run("train_kaggle_cce", "train", seed=5, seconds=0.6, hooks=faults.hooks(fault))
    checks = r["checks"]
    assert not r["correct"]
    assert checks[number]["value"] > checks[number]["limit"], checks
    assert checks["loss_gap"]["value"] <= checks["loss_gap"]["limit"], checks


@pytest.mark.parametrize("workload", ["serve_kaggle_cce_zipf", "serve_kaggle_cce_uniform"])
@pytest.mark.parametrize("fault", ["altered_answer", "half_answers"])
def test_serve_fault_fails(workload, fault):
    r = tiny.run(workload, "serve", seed=5, seconds=0.5,
                 hooks={**faults.hooks(fault), "drain_s": 2.0})
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload,mode", [("train_kaggle_cce", "train"),
                                           ("train_kaggle_full", "train"),
                                           ("serve_kaggle_cce_zipf", "serve")])
def test_control_reads_apart_from_the_program(workload, mode):
    """The control (the reference at ``high`` in the program's place) reads
    a gap the program does not.  Whether that gap crosses a limit is shown
    at the cell's own size on the chip (``calibrate.py``): at this size the
    control's loss gap is under the limit set there."""
    prog = tiny.run(workload, mode, seed=9, seconds=0.3)["checks"]
    ctl = tiny.run(workload, mode, seed=9, seconds=0.3, control="high")["checks"]
    name = "loss_gap" if mode == "train" else "logit_gap"
    assert ctl[name]["value"] > 4 * prog[name]["value"] and ctl[name]["value"] > 0
