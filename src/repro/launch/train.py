"""Training launcher: pjit the train step over the current device mesh.

On a pod this builds the production mesh and shards per
``repro.models.params``; on CPU (tests/examples) it builds a mesh over
however many host devices exist and trains a reduced config for real.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
      --steps 20 --batch 4 --seq 64
"""
from __future__ import annotations

import argparse

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_configs, reduced
from repro.data import data_iterator
from repro.launch.mesh import data_axes, make_mesh
from repro.models.params import param_shardings, tp_adjusted_config
from repro.models.transformer import Model
from repro.training.optimizer import AdamWConfig, init_adamw
from repro.training.train_loop import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_configs(), default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data", type=int, default=0,
                    help="data-parallel degree (0 = all devices)")
    ap.add_argument("--model", type=int, default=1,
                    help="model-parallel degree")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    n_dev = len(jax.devices())
    dp = args.data or max(1, n_dev // args.model)
    mesh = make_mesh((dp, args.model), ("data", "model"))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    cfg = tp_adjusted_config(cfg, mesh.shape["model"])
    model = Model(cfg, remat=True)

    params = model.init(jax.random.PRNGKey(args.seed))
    params_sh = param_shardings(cfg, mesh)
    params = jax.device_put(params, params_sh)
    opt_state = init_adamw(params)
    opt_sh = type(opt_state)(step=NamedSharding(mesh, P()), mu=params_sh,
                             nu=params_sh)
    opt_state = jax.device_put(opt_state, opt_sh)

    opt_cfg = AdamWConfig(lr=args.lr, schedule=cfg.lr_schedule,
                          warmup_steps=max(2, args.steps // 10),
                          total_steps=args.steps)
    step_fn = jax.jit(make_train_step(model, opt_cfg),
                      in_shardings=(params_sh, opt_sh, None),
                      out_shardings=(params_sh, opt_sh, None),
                      donate_argnums=(0, 1))

    data = data_iterator(cfg, seq_len=args.seq, batch_size=args.batch,
                         seed=args.seed)
    dp_axes = data_axes(mesh)
    for step in range(args.steps):
        batch = next(data)
        batch = {k: jax.device_put(
            v, NamedSharding(mesh, P(dp_axes, *([None] * (v.ndim - 1)))))
            for k, v in batch.items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e}")
    return params


if __name__ == "__main__":
    main()
