"""End-to-end training driver: pipe-fed input pipeline -> jitted train step
-> checkpoint/restart.

The token stream arrives through a PipeGen data pipe (the paper's transport
feeding the trainer — no file materialization between the "tokenizer" and
the training loop); the loop is ``repro.launch.train``'s.  Defaults to a
reduced config that trains in seconds on CPU; ``--arch smollm-360m --full``
selects the real 360M config (sized for accelerators).

    PYTHONPATH=src python examples/train_lm.py --steps 60
    PYTHONPATH=src python examples/train_lm.py --steps 60 --resume  # restart
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, "src")

from repro.launch.train import main as train_main


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--full", action="store_true",
                    help="full-size config (accelerator-scale)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-dir", default=os.path.join(
        tempfile.gettempdir(), "pipegen-train-ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=20)
    args = ap.parse_args()

    argv = ["--arch", args.arch, "--steps", str(args.steps),
            "--batch", str(args.batch), "--seq", str(args.seq),
            "--ckpt-dir", args.ckpt_dir,
            "--ckpt-every", str(args.ckpt_every)]
    if not args.full:
        argv.append("--reduced")
    if args.resume:
        argv.append("--resume")
    return train_main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
