"""End-to-end driver: DP training of a transformer LM with ghost clipping,
checkpointing, fault tolerance and privacy accounting — the production
workflow at laptop scale.  ``--d-model 640 --layers 12`` gives a ~100M
model (hours on this CPU; the default is a quick demonstration).

    PYTHONPATH=src python examples/dp_finetune_lm.py --steps 120
    PYTHONPATH=src python examples/dp_finetune_lm.py \
        --d-model 640 --layers 12 --steps 300        # ~100M params
"""
import argparse
import sys

from repro.launch import train as train_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--noise", type=float, default=0.6)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    argv = ["--arch", "llama3.2-1b", "--steps", str(args.steps),
            "--batch", "16", "--seq", "128", "--lr", "3e-3",
            "--clip", "1.0", "--noise", str(args.noise),
            "--strategy", "bk", "--microbatches", "2",
            "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "25"]
    if args.d_model:
        argv += ["--d-model", str(args.d_model)]
    if args.layers:
        argv += ["--layers", str(args.layers)]
    losses = train_mod.main(argv).losses
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f} over {args.steps} steps")


if __name__ == "__main__":
    main()
