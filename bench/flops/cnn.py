"""Model operations of a convolutional network, counted from its shapes.

The model FLOPs of one training sample are 3 x the forward FLOPs of the
non-private model (forward, and a backward that costs twice the forward),
with a multiply-add counted as 2 FLOPs.  Only convolutions and dense layers
count; biases, activations and pooling are left out.  The per-example norm
and contribution work of DP-SGD is overhead and is not model FLOPs.
"""
from __future__ import annotations


def _conv_out(h: int, k: int, s: int, p: int) -> int:
    return (h + 2 * p - k) // s + 1


def forward_macs(cfg: dict) -> int:
    """Multiply-adds of one example's forward pass."""
    cin, side = cfg["image"][0], cfg["image"][1]
    if cfg["image"][1] != cfg["image"][2]:
        raise ValueError("square images only")
    pk, ps = cfg["pool"]["kernel"], cfg["pool"]["stride"]
    macs = 0
    for out, k, s, p, pool in cfg["convs"]:
        side = _conv_out(side, k, s, p)
        macs += out * cin * k * k * side * side
        if pool:
            side = _conv_out(side, pk, ps, 0)
        cin = out
    dims = [cin * side * side] + list(cfg["fc"]) + [cfg["n_classes"]]
    macs += sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return macs


def train_flops_per_sample(cfg: dict) -> int:
    return 3 * 2 * forward_macs(cfg)
