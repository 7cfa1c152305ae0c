"""Model FLOPs of the DLRM-DCNv2 examples trained in the window over the
window's wall time and the chip's peak, in %: the dense arch, the low-rank
cross layers (``V_l`` and ``W_l``) and the over arch, forward and backward.
The backward of a dense layer costs twice its forward (input and weight
gradients), but for the first layer's input gradient, which nobody needs.
Gathers, bag sums, biases, activations and the cross layers' element-wise
products count 0.  Nothing for a configuration without cross layers."""
from harness import work


def dcnv2_train_flops(cfg: dict) -> int:
    width = cfg["bottom_mlp"][-1] + len(cfg["vocab_sizes"]) * cfg["emb_dim"]
    cross = cfg["cross_layers"] * 2 * (2 * width * cfg["cross_rank"])
    forward = (work._mlp_flops((cfg["n_dense"], *cfg["bottom_mlp"])) + cross
               + work._mlp_flops((width, *cfg["top_mlp"])))
    return 3 * forward - 2 * cfg["n_dense"] * cfg["bottom_mlp"][0]


def read(ctx):
    r, peak, cfg = ctx["run"], ctx["peak"], ctx["cfg"]
    if ctx["mode"] != "train" or peak is None or cfg.get("interaction") != "dcnv2":
        return None
    flops = dcnv2_train_flops(cfg) * r["examples"]
    return 100.0 * flops / r["window_s"] / peak["flops_per_s"]
