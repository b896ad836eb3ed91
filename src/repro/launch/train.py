"""Training driver: data pipeline -> sharded train step -> checkpoints, with
watchdog, preemption handling and retry — the single-process version of the
fleet runtime (multi-host launch documented in README §Scale).

CPU-friendly examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m --reduced ...
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.data import DataConfig, TokenPipeline
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update, accumulate_grads
from repro.optim.schedule import wsd_schedule
from repro.checkpoint import CheckpointManager
from repro.runtime import StepWatchdog, PreemptionHandler, retry_step
from repro.distributed.sharding import (param_shardings, batch_specs,
                                        make_shard_ctx)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import StepConfig, build_train_step


def make_mesh_for_host(model: int = 1):
    """(data, model) mesh over this host's devices: ``model``-way tensor
    parallel, the rest data parallel."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices do not split {model}-way")
    return make_mesh((n // model, model), ("data", "model"))


def train(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
          ckpt_dir: str | None, n_micro: int = 1, remat: str = "none",
          lr: float = 3e-4, save_every: int = 50, seed: int = 0,
          log_every: int = 10, mesh: Mesh | None = None,
          fail_at_step: int | None = None, tune: str | None = None,
          quant: str | None = None):
    if tune:
        # pre-tune the ops-level kernel families at this run's geometry so
        # any cfg="auto" dispatch resolves from the persisted cache instead
        # of searching — including the flash_attention/-_bwd pair the train
        # step itself hits when cfg.attn_backend="pallas" (the forward and
        # the dK/dV backward coarsen different axes, so both are warmed).
        from repro.tune import warm_from_flag
        warm_from_flag(cfg, tune, seq=seq, batch=batch)
    mesh = mesh or make_mesh_for_host()
    with mesh:
        losses, params = _train_in_mesh(
            cfg, steps=steps, batch=batch, seq=seq, ckpt_dir=ckpt_dir,
            n_micro=n_micro, remat=remat, lr=lr, save_every=save_every,
            seed=seed, log_every=log_every, mesh=mesh,
            fail_at_step=fail_at_step)
    if quant and quant != "none":
        _quant_eval(cfg, params, quant, batch=batch, seq=seq, seed=seed)
    return losses, params


def _quant_eval(cfg: ModelConfig, params, quant: str, *, batch, seq, seed):
    """Post-training weight-only quantization report: quantize the trained
    params (repro.quant) and compare the eval loss on one held-out batch
    against the f32 path — the serving-readiness parity check for --quant."""
    from repro.quant import quantize_params, tree_nbytes
    data = TokenPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed + 991,
        frontend=cfg.frontend, d_model=cfg.d_model,
        src_len=min(seq, 512), is_encdec=cfg.is_encdec))
    hb = jax.tree.map(jnp.asarray, data.next_batch())
    loss_f = jax.jit(lambda p, b: M.lm_loss(p, b, cfg)[0])
    dense = float(loss_f(params, hb))
    qparams, rep = quantize_params(params, quant, group=cfg.quant_group)
    quant_loss = float(loss_f(qparams, hb))
    print(f"quant[{quant}]: eval loss {quant_loss:.4f} vs f32 {dense:.4f} "
          f"(delta {quant_loss - dense:+.4f}); params "
          f"{tree_nbytes(params) / 2**20:.2f} -> "
          f"{tree_nbytes(qparams) / 2**20:.2f} MiB "
          f"({rep['quantized']} leaves quantized)")


def _train_in_mesh(cfg: ModelConfig, *, steps, batch, seq, ckpt_dir, n_micro,
                   remat, lr, save_every, seed, log_every, mesh,
                   fail_at_step):
    sc = StepConfig(seq=seq, batch=batch, kind="train", n_micro=n_micro,
                    remat=remat, opt=AdamWConfig(lr=lr))
    step_fn, _, in_sh, out_sh = build_train_step(cfg, mesh, sc)
    jit_step = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1))

    data = TokenPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed + 7,
        frontend=cfg.frontend, d_model=cfg.d_model,
        src_len=min(seq, 512), is_encdec=cfg.is_encdec))

    params = jax.jit(
        lambda k: M.lm_init(k, cfg), out_shardings=in_sh[0]
    )(jax.random.PRNGKey(seed))
    opt_state = jax.jit(adamw_init, out_shardings=in_sh[1])(params)

    start_step = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep=3, save_interval_steps=save_every)
        latest = mgr.latest_step()
        if latest is not None:
            skel = jax.tree.map(np.asarray, {"params": params, "opt": opt_state})
            restored, manifest = mgr.restore(
                skel, shardings={"params": in_sh[0], "opt": in_sh[1]})
            params, opt_state = restored["params"], restored["opt"]
            start_step = manifest["extra"]["step"]
            data.load_state_dict(manifest["extra"]["data"])
            print(f"resumed from step {start_step}")

    wd = StepWatchdog(threshold=4.0, hang_timeout=3600)
    pre = PreemptionHandler().install()
    losses = []
    bsh = in_sh[2]

    for step in range(start_step, steps):
        if pre.preempted:
            print(f"preempted at step {step}; checkpointing")
            break
        batch_np = data.next_batch()
        hb = jax.tree.map(lambda a, s: jax.device_put(a, s), batch_np, bsh)

        def run():
            if fail_at_step == step and not getattr(run, "failed", False):
                run.failed = True
                from repro.runtime import SimulatedFailure
                raise SimulatedFailure(f"injected failure at step {step}")
            return jit_step(params, opt_state, hb)

        with wd.step(step):
            params, opt_state, loss, gn = retry_step(
                run, retries=2,
                on_retry=lambda a, e: print(f"  retry {a}: {e}"))
        if step % log_every == 0 or step == steps - 1:
            print(f"step {step:5d} loss {float(loss):.4f} gnorm {float(gn):.3f}")
        losses.append(float(loss))
        if mgr and mgr.should_save(step):
            mgr.save(step, {"params": params, "opt": opt_state},
                     extra={"step": step + 1, "data": data.state_dict()})
    else:
        step = steps - 1

    if mgr:
        mgr.save(step + 1 if not pre.preempted else step,
                 {"params": params, "opt": opt_state},
                 extra={"step": step + 1, "data": data.state_dict()},
                 blocking=True)
    pre.uninstall()
    return losses, params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    from repro.tune import TUNE_CHOICES
    ap.add_argument("--tune", default=None, choices=[None, *TUNE_CHOICES],
                    help="warm the coarsening tuning cache before training")
    ap.add_argument("--attn-backend", default=None, choices=["ref", "pallas"],
                    help="training attention dispatch: 'pallas' routes the "
                         "blocks through the coarsened custom-VJP flash "
                         "kernel (attn_cfg/attn_bwd_cfg from the tuning "
                         "cache --tune warms)")
    ap.add_argument("--attn-sparse", default=None, choices=["auto", "off"],
                    help="block-sparse dispatch for local-attention layers "
                         "(window set): 'auto' routes eligible prefill "
                         "geometries through the live-index kernel, 'off' "
                         "pins the dense-mask kernel")
    ap.add_argument("--attn-global-stride", type=int, default=None,
                    help="LongFormer-style global columns on local layers: "
                         "every Nth kv position stays visible past the "
                         "window (needs a windowed arch; training through "
                         "a strided pattern differentiates the jnp oracle)")
    ap.add_argument("--quant", default=None,
                    choices=[None, "none", "int8", "int4"],
                    help="after training, quantize the weights (repro.quant "
                         "weight-only) and report the eval-loss delta vs "
                         "f32 on a held-out batch")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.attn_backend:
        import dataclasses
        cfg = dataclasses.replace(cfg, attn_backend=args.attn_backend)
    if args.attn_sparse:
        import dataclasses
        cfg = dataclasses.replace(cfg, attn_sparse=args.attn_sparse)
    if args.attn_global_stride:
        import dataclasses
        cfg = dataclasses.replace(cfg,
                                  attn_global_stride=args.attn_global_stride)
    losses, _ = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                      ckpt_dir=args.ckpt_dir, n_micro=args.n_micro,
                      remat=args.remat, lr=args.lr,
                      save_every=args.save_every, tune=args.tune,
                      quant=args.quant)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    if args.tune:
        from repro.tune import tune_report
        print(tune_report())


if __name__ == "__main__":
    main()
