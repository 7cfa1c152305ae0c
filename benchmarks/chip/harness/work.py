"""Work counts from shapes, kept with the benchmark so that every PR is
measured against the same work: the DLRM model FLOPs per example, the
fused lookup's bytes and adds, and the chip's peaks."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device that is not in the
    table is an error, not a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def _mlp_flops(sizes) -> int:
    return sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))


def dlrm_forward_flops(cfg: dict) -> int:
    """Forward multiply-adds (x2) per example: both MLPs and the upper
    triangle of the pairwise dot interaction.  Gathers, biases and
    activations count 0."""
    n_vec = len(cfg["vocab_sizes"]) + 1
    n_pairs = n_vec * (n_vec - 1) // 2
    bottom = (cfg["n_dense"], *cfg["bottom_mlp"])
    top = (cfg["bottom_mlp"][-1] + n_pairs, *cfg["top_mlp"])
    return _mlp_flops(bottom) + _mlp_flops(top) + 2 * n_pairs * cfg["emb_dim"]


def dlrm_train_flops(cfg: dict) -> int:
    """Forward + backward per example: the backward of a dense layer costs
    twice its forward (input and weight gradients), except the first
    layer's input gradient, which nobody needs; the interaction's backward
    costs twice its forward as well."""
    first = 2 * cfg["n_dense"] * cfg["bottom_mlp"][0]
    return 3 * dlrm_forward_flops(cfg) - first


def _per_id(s, itemsize: int):
    """(index bytes, output bytes, adds) of one id of table shape ``s``:
    a CCE id reads a main and a helper row index per column and sums the
    two rows; a full-table id reads one index and one row."""
    n_rows = 2 * s.c if s.kind == "cce" else 1
    t = 2 if s.kind == "cce" else 1
    return n_rows * 4, s.d2 * itemsize, t * s.d2


def _table_bytes(s, itemsize: int) -> int:
    return (2 * s.c * s.k * (s.d2 // s.c) if s.kind == "cce" else s.d1 * s.d2) * itemsize


def lookup_work(shapes, batch: int, *, backward: bool, itemsize: int = 4,
                bag_sizes=None):
    """(adds, bytes) that a batch's embedding lookup must do, whatever its
    algorithm: read every row index and every table once, write the
    output once, and sum each output element's rows.  The backward reads
    the indices and the output gradient once, writes the table gradient
    once, and adds each gradient row into its table rows.  (A one-hot
    matmul does far more; this is the work it is measured against.)
    With ``bag_sizes`` (one id a feature by default) each example looks
    up a bag of ids a feature and pools them into one output row: index
    bytes and adds count per id, output bytes per bag."""
    adds = nbytes = 0
    for f, s in enumerate(shapes):
        idx, out, a = _per_id(s, itemsize)
        n_ids = batch * (1 if bag_sizes is None else int(bag_sizes[f]))
        adds += n_ids * a
        nbytes += n_ids * idx + batch * out + _table_bytes(s, itemsize)
    return adds, nbytes


def lookup_misses_work(shapes, misses: int, launches: int, *, itemsize: int = 4):
    """(adds, bytes) of ``launches`` serve lookups over ``misses`` ids that
    the hot cache did not answer: each launch reads the tables once, each
    missed id its row indices and its output, at the features' mean cost
    per id (the counters do not say which feature missed)."""
    per = [_per_id(s, itemsize) for s in shapes]
    idx = sum(p[0] for p in per) / len(per)
    out = sum(p[1] for p in per) / len(per)
    a = sum(p[2] for p in per) / len(per)
    tables = sum(_table_bytes(s, itemsize) for s in shapes)
    return misses * a, misses * (idx + out) + launches * tables


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take for this work."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
