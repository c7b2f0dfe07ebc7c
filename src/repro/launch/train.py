"""Training launcher.

Single-host entry point for real runs:

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 100 \
        [--smoke] [--ckpt-dir /path] [--microbatches 2]

On a TPU fleet the same entry point runs under your cluster's process
launcher (one process per host; jax.distributed.initialize is invoked when
the standard cluster env vars are present). The XLA flags below enable the
latency-hiding scheduler so the per-layer FSDP all-gathers and grad
reduce-scatters overlap with compute — set BEFORE jax initializes.
"""

import os

# compute/communication overlap (harmless on CPU, required for perf on TPU)
os.environ.setdefault(
    "LIBTPU_INIT_ARGS",
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true")

import argparse   # noqa: E402
import logging    # noqa: E402

import jax        # noqa: E402

from repro.configs import get_config, get_smoke  # noqa: E402
from repro.data import DataConfig, SyntheticLM   # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.optim import AdamWConfig              # noqa: E402
from repro.train import TrainConfig, Trainer     # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-sized)")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    enable_compile_cache()
    if os.environ.get("COORDINATOR_ADDRESS"):
        jax.distributed.initialize()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    tc = TrainConfig(
        steps=args.steps,
        microbatches=args.microbatches,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=50,
        log_every=10,
        opt=AdamWConfig(lr=args.lr, kahan=True),
    )
    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch,
        vision_patches=cfg.vision.n_patches if cfg.vision else 0,
        n_frames=cfg.encoder.n_frames if cfg.encoder else 0,
        d_model=cfg.d_model))
    trainer = Trainer(cfg, tc, data)
    final = trainer.run()
    print(f"final: {final}")


if __name__ == "__main__":
    main()
