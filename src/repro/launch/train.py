"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b --smoke \
        --steps 50 --policy train_default

On a real TPU fleet this process runs per host with jax.distributed
initialization; on CPU it drives the same code single-host.  The mesh,
sharding rules and step function are identical to the dry-run's.
"""
import argparse

import jax

from repro.configs.registry import ARCH_IDS, get_config
from repro.core.policy import get_policy
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import configure_compile_cache
from repro.optim import adamw
from repro.train import trainer as trainer_lib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-mpfp-100m", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--policy", default="train_default")
    ap.add_argument("--backend", default="",
                    help="mp_matmul dispatch backend (ref/pallas/"
                         "pallas_interpret/sharded); '' = context default")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory ('' = no checkpoints)")
    ap.add_argument("--moment-dtype", default="float32")
    args = ap.parse_args()
    configure_compile_cache()

    if args.backend:
        # one-shot process configuration (replaces REPRO_MP_BACKEND env)
        import repro.mp as mp
        mp.configure(backend=args.backend)

    cfg = get_config(args.arch, smoke=args.smoke)
    if not args.smoke and cfg.param_count() > 1e9 \
            and jax.default_backend() == "cpu":
        raise SystemExit(
            f"{args.arch} full config is {cfg.param_count():,} params — use "
            f"--smoke on CPU, or launch on the production mesh (see "
            f"repro.launch.dryrun for the lowering proof).")

    trainer, pipe = make_trainer(
        cfg, batch=args.batch, seq=args.seq, steps=args.steps,
        policy=get_policy(args.policy), ckpt_dir=args.ckpt_dir,
        moment_dtype=args.moment_dtype)
    state, history = trainer.run(pipe, num_steps=args.steps, log_every=10)
    print(f"done: loss {history[0]:.4f} -> {history[-1]:.4f}")


def make_trainer(cfg, *, batch: int, seq: int, steps: int, policy,
                 ckpt_dir: str = "", moment_dtype: str = "float32"):
    """The trainer and synthetic data pipeline the launcher runs
    (``chip_smoke.py`` trains through the same pair).  An empty
    ``ckpt_dir`` writes no checkpoints."""
    pipe = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=seq + 1, global_batch=batch,
        frontend=cfg.frontend, d_model=cfg.d_model,
        n_patches=cfg.n_patches))
    tcfg = trainer_lib.TrainerConfig(
        opt=adamw.AdamWConfig(moment_dtype=moment_dtype),
        total_steps=steps, warmup=max(2, steps // 20),
        ckpt_dir=ckpt_dir, ckpt_every=max(10, steps // 5))
    return trainer_lib.Trainer(cfg, tcfg, policy=policy), pipe


if __name__ == "__main__":
    main()
