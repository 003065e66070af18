"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --smoke --steps 50 --batch 8 --seq 128 --ckpt /tmp/run1

On real hardware the same entry point runs the full config on the
production mesh (``--mesh single|multi``); on this CPU container use
``--smoke`` (reduced config, 1 device) — the code path (data -> step ->
checkpoint -> resume) is identical.

Latency-hiding flags: on TPU, set
    XLA_FLAGS="--xla_tpu_enable_latency_hiding_scheduler=true \
               --xla_tpu_megacore_fusion_allow_ags=true"
(collective/compute overlap); they are set here when a TPU backend is
detected so the launcher is copy-paste deployable.
"""

from __future__ import annotations

import argparse
import os


def _tpu_flags() -> None:
    if "libtpu" in os.environ.get("TPU_LIBRARY_PATH", "") or os.environ.get("TPU_NAME"):
        os.environ.setdefault(
            "XLA_FLAGS",
            "--xla_tpu_enable_latency_hiding_scheduler=true",
        )


_tpu_flags()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--quant", default="none", choices=["none", "bnn"],
                    help="bnn = the paper's technique on all hidden projections")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import dataclasses

    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.train import TrainLoopConfig, train

    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.quant != "none":
        cfg = dataclasses.replace(cfg, quant=args.quant)
    loop = TrainLoopConfig(
        total_steps=args.steps,
        batch_size=args.batch,
        seq_len=args.seq,
        peak_lr=args.lr,
        checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt,
        seed=args.seed,
    )
    out = train(cfg, loop)
    print(
        f"[train] arch={cfg.name} quant={cfg.quant} final_step={out['final_step']} "
        f"loss[first->last]={out['losses'][0]:.4f}->{out['losses'][-1]:.4f} "
        f"steps/s={out.get('steps_per_s', 0):.2f}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
