"""Work counts against hand counts at small shapes."""
import pytest

from harness import weights, work

CFG = {"vocab_sizes": [1000, 3], "n_dense": 13, "emb_dim": 16, "bottom_mlp": [64, 16],
       "top_mlp": [32, 1], "emb_method": "cce", "emb_param_cap": 512, "emb_c": 4}


def test_table_shapes_follow_the_cap():
    cce, full = weights.table_shapes(CFG)
    assert (cce.kind, cce.k, cce.c) == ("cce", 16, 4)  # 512 // (2 * 16)
    assert (full.kind, full.d1) == ("full", 3)  # 3 * 16 <= 512


def test_dlrm_flops():
    # bottom 2*(13*64 + 64*16) = 3712; top (16 + 3 pairs) -> 2*(19*32 + 32) = 1280;
    # interaction 2 * 3 pairs * 16 = 96
    assert work.dlrm_forward_flops(CFG) == 3712 + 1280 + 96
    # forward + backward (2x), less the first layer's unused input gradient
    assert work.dlrm_train_flops(CFG) == 3 * 5088 - 2 * 13 * 64


def test_published_widths_train_flops():
    cfg = dict(CFG, vocab_sizes=[1] * 26, bottom_mlp=[512, 256, 64, 16],
               top_mlp=[512, 256, 1])
    assert work.dlrm_forward_flops(cfg) == 959_968
    assert work.dlrm_train_flops(cfg) == pytest.approx(2.88e6, rel=0.01)


def test_lookup_work():
    shapes = weights.table_shapes(CFG)
    # per CCE id: 2 rows x 4 columns of int32 indices (32 B), a 64 B row out,
    # 32 adds; per full id: 4 B, 64 B, 16 adds; tables 2048 B and 192 B
    assert work.lookup_work(shapes, 8, backward=False) == (
        8 * (32 + 16), 8 * (32 + 64) + 2048 + 8 * (4 + 64) + 192)
    assert work.lookup_work(shapes, 8, backward=True) == work.lookup_work(
        shapes, 8, backward=False)
    adds, nbytes = work.lookup_misses_work(shapes, misses=10, launches=2)
    assert adds == 10 * 24 and nbytes == 10 * (18 + 64) + 2 * 2240


def test_peaks():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert work.roofline_s(1e12, 819e9, p) == 1.0
    assert work.roofline_s(197e12, 1.0, p) == 1.0
    with pytest.raises(KeyError):
        work.peaks("cpu")
