"""End-to-end driver: train a ViG image classifier with dynamic graph
construction in every block, on the synthetic class-conditional image
stream, with checkpoint/resume.

Default config is CPU-sized; --full trains the real ViG-Ti (~10M params
at 224x224) for --steps steps.

    PYTHONPATH=src python examples/train_vig.py --steps 100
"""

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as ckpt
from repro.core import available_impls, get_builder
from repro.data.pipeline import DataConfig, image_pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models import vig
from repro.models.module import init_params
from repro.train.optimizer import OptConfig
from repro.train.trainer import init_train_state, make_train_step


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--full", action="store_true", help="real ViG-Ti config")
    # choices from the registry by name only (no eager builder imports);
    # distributed builders are rejected after parsing, importing just
    # the selected one.
    ap.add_argument("--digc-impl", default="blocked",
                    choices=list(available_impls()))
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args(argv)
    if get_builder(args.digc_impl).distributed:
        ap.error(f"--digc-impl {args.digc_impl} needs a device mesh; "
                 "this single-host example cannot drive it")

    if args.full:
        cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
            num_classes=args.num_classes, digc_impl=args.digc_impl
        )
        args.image_size = cfg.image_size
    else:
        cfg = vig.VIG_VARIANTS["vig_ti_iso"].replace(
            image_size=args.image_size, embed_dims=(48,), depths=(4,), k=5,
            num_classes=args.num_classes, digc_impl=args.digc_impl,
        )

    params = init_params(vig.vig_param_spec(cfg), jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"ViG ({'full' if args.full else 'reduced'}): {n_params/1e6:.1f}M params, "
          f"grid {cfg.base_grid}x{cfg.base_grid}, digc={args.digc_impl}")

    oc = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                   total_steps=args.steps, weight_decay=0.01)
    step_fn = jax.jit(make_train_step(cfg, oc, loss_fn=vig.vig_loss_fn,
                                      param_dtype=jnp.float32))
    opt = init_train_state(params)
    start = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        restored, start = ckpt.restore(args.ckpt_dir, {"p": params, "o": opt})
        params, opt = restored["p"], restored["o"]
        print(f"resumed from step {start}")

    dc = DataConfig(seq_len=1, global_batch=args.batch, vocab_size=1, seed=0)
    pipe = image_pipeline(dc, args.image_size, args.num_classes, start_step=start)
    losses, accs = [], []
    try:
        for step, raw in pipe:
            if step >= args.steps:
                break
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            params, opt, m = step_fn(params, opt, batch)
            losses.append(float(m["loss"]))
            logits = vig.vig_forward(params, batch["images"], cfg)
            accs.append(float(jnp.mean(jnp.argmax(logits, -1) == batch["labels"])))
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss {losses[-1]:.4f} acc {accs[-1]:.2f}")
            if args.ckpt_dir and (step + 1) % 50 == 0:
                ckpt.save(args.ckpt_dir, step + 1, {"p": params, "o": opt})
    finally:
        pipe.close()
    k = max(len(losses) // 5, 1)
    print(f"loss {np.mean(losses[:k]):.3f} -> {np.mean(losses[-k:]):.3f}; "
          f"acc {np.mean(accs[:k]):.2f} -> {np.mean(accs[-k:]):.2f}")


if __name__ == "__main__":
    main()
