"""The two training cells read as they did before the configuration seams:
the program's ``DLRMConfig`` at the published widths, and at the tiny size
the weights, the batch pool, the program's layout of the weights and the
reference's readings, each against a hash taken before the seams."""
import hashlib
import json

import jax
import numpy as np
import pytest

import tiny
from harness import program, traffic, train, weights as W

CONFIGS = tiny.bench.HERE / "configs"
SEED = 2**31 + 12345
#: (weights, buffers, pool, program params, program buffers, reference readings)
PINNED = {
    "dlrm_criteo_kaggle": ("8f367a4c624e6595", "92f58cdeb507ab61", "2757838bb5d2c9a0",
                           "d7fda7cac0bdce65", "92f58cdeb507ab61", "57d5b40db5026c2f"),
    "dlrm_criteo_kaggle_full": ("669ea148662d9c87", "e3b0c44298fc1c14", "2757838bb5d2c9a0",
                                "669ea148662d9c87", "e3b0c44298fc1c14", "c45e3279d64e59fc"),
}


def _digest(leaves) -> str:
    h = hashlib.sha256()
    for x in leaves:
        a = np.asarray(x)
        h.update(str(a.dtype).encode() + str(a.shape).encode() + a.tobytes())
    return h.hexdigest()[:16]


def _config(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(PINNED))
def test_program_config_at_published_widths(name):
    """Every key the configuration names reaches ``DLRMConfig``, which is
    the one the harness built from its fixed list of keys."""
    from repro.models.dlrm import DLRMConfig

    cfg = _config(name)
    before = DLRMConfig(
        vocab_sizes=tuple(cfg["vocab_sizes"]), n_dense=cfg["n_dense"],
        emb_dim=cfg["emb_dim"], bottom_mlp=tuple(cfg["bottom_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]), emb_method=cfg["emb_method"],
        emb_param_cap=cfg["emb_param_cap"], emb_c=cfg["emb_c"],
        emb_fuse=cfg.get("emb_fuse", "univ"))
    got = program.dlrm_config(cfg)
    assert got == before
    assert got.dtype is before.dtype


@pytest.mark.parametrize("name", sorted(PINNED))
def test_tiny_inputs_and_readings_as_before(name):
    cfg = {**_config(name), **tiny.CONFIG}
    ref = tiny.bench.load_module(CONFIGS / f"{cfg['reference']}.py")
    key = W.seed_key(SEED)
    make = W.make_fn(cfg, ref)
    params, bufs = make[0](key), make[1](key)
    mix = {**traffic.load_mix("zipf_train"), **tiny.TRAFFIC["train"]}
    pool = traffic.train_pool(mix, cfg["vocab_sizes"], cfg["n_dense"], cfg["train_batch"],
                              SEED, bag_sizes=cfg.get("bag_sizes"))
    pcfg = program.dlrm_config(cfg)
    prog_params, prog_bufs = program.to_program(pcfg, params, bufs)
    read = train.reference_readings(ref, make, key, pool, cfg, cfg["matmul_precision"])
    got = (_digest(jax.tree.leaves(params)), _digest(jax.tree.leaves(bufs)),
           _digest([b[k] for b in pool for k in ("dense", "sparse", "label")]),
           _digest(jax.tree.leaves(prog_params)), _digest(jax.tree.leaves(prog_bufs)),
           _digest([np.asarray(read["losses"], np.float64), read["grad"], read["change"]]))
    assert got == PINNED[name]
    back = program.per_feature(pcfg, prog_params)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    assert _digest(jax.tree.leaves(back)) == PINNED[name][0]
