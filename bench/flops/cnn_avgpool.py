"""Model operations of a convolutional network whose classifier reads an
adaptive average pool of side ``avgpool``, counted from its shapes as
``cnn.py`` counts them: 3 x the forward FLOPs of the non-private model, a
multiply-add as 2 FLOPs, convolutions and dense layers only.
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
    dims = [cin * cfg["avgpool"] ** 2] + list(cfg["fc"]) + [cfg["n_classes"]]
    macs += sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return macs


def train_flops_per_sample(cfg: dict) -> int:
    return 3 * 2 * forward_macs(cfg)
