"""Smoke run of the main paths on a TPU, at qwen3-0.6b's published widths.

    python chip_smoke.py             # one chip: serve phase, then train phase
    python chip_smoke.py --chips 4   # four chips: one train step on a 2x2
                                     # (data, model) mesh vs the same step on
                                     # one chip, and nothing else

Weights are random, drawn from ``--seed``; nothing is downloaded.  Everything
runs in this one process.  The script stops with a non-zero exit when JAX
finds no TPU: it never falls back to the CPU.  Any failed check raises.  The
last line of stdout is one JSON object naming the device; the times printed
before it are smoke times (compilation included), not benchmarks.

Serve phase: `Scheduler` + `PagedEngine` with the Pallas paged-decode kernel
serve a few requests; the compiled decode step must hold the kernel, and the
first decode step's logits must match the same requests served through the
dense ``decode_backend="ref"`` path.  Train phase: `repro.launch.train.train`
takes a few steps with the Pallas flash-attention kernels; every loss must be
finite and the first must match the same step with ``attn_backend="ref"``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402

ARCH = "qwen3-0.6b"

# Pallas vs ref parity bounds; CHANGES.md gives the reasoning for each.
# Decode logits: max |pallas - ref| over the running slots, relative to the
# largest |ref| logit.  Both paths keep bf16 activations between layers and
# the ref path rounds q*scale and the probabilities to bf16, so 28 layers
# of bf16 rounding alone leave about 2e-2; a wrong page, row or mask is O(1).
LOGIT_RTOL = 1e-1
# First-step training loss (nats): the two attention paths differ only in
# accumulation order inside attention (about 2e-5 at 28 layers).
LOSS_ATOL = 5e-3
# Sharded vs one-chip step: the bounds of the repo's own sharded-step test.
MESH_LOSS_ATOL = 2e-2
MESH_GNORM_RTOL = 5e-2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums the XLA compile seconds JAX reports (persistent-cache reads
    included), so each phase can print what it spent compiling."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration


class _FirstDecode:
    """Logits hook on the engine's decode path (the seam the fault harness
    uses): keeps the first decode step's logits and the slots then running,
    and passes the logits through unchanged."""

    def __init__(self, engine):
        self.engine, self.logits, self.active = engine, None, None

    def corrupt_logits(self, lg, site):
        if self.logits is None:
            self.logits = np.array(lg[:, 0])
            self.active = self.engine.active.copy()
        return lg


def kernels_in_decode_step(engine) -> int:
    """Pallas kernels in the engine's decode step: ``tpu_custom_call`` ops
    of the compiled program on a TPU; on the CPU, where Pallas runs
    interpreted and leaves no custom call, the ``pallas_call`` equations
    of its jaxpr."""
    n = max(engine._decode_fns)
    args = (engine.params, engine.cache,
            jax.ShapeDtypeStruct((engine.slots, 1), np.int32),
            jax.ShapeDtypeStruct((engine.slots,), np.int32),
            jax.ShapeDtypeStruct((engine.slots, engine.npp), np.int32))
    fn = engine._decode_fn(n)
    if jax.devices()[0].platform == "tpu":
        return fn.lower(*args).compile().as_text().count("tpu_custom_call")
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


def serve_phase(cfg, params, *, slots: int, max_len: int, page_size: int,
                chunk: int, decode_block: int, prompt_lens, gen: int,
                decode_cfg: str, seed: int) -> dict:
    """Serve the same requests through the Pallas paged-decode kernel and
    through the dense ref path; check completion, the kernel's presence and
    first-decode-step logit parity.  Returns what it measured."""
    from repro.serve import PagedEngine, Scheduler, State
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab, n)]
               for n in prompt_lens]
    runs = {}
    for backend in ("pallas", "ref"):
        engine = PagedEngine(
            dataclasses.replace(cfg, decode_backend=backend,
                                decode_attn_cfg=decode_cfg),
            params, slots=slots, page_size=page_size, max_len=max_len,
            num_pages=slots * -(-max_len // page_size) + 1, chunk=chunk,
            decode_block=decode_block)
        probe = engine.fault_hook = _FirstDecode(engine)
        sched = Scheduler(engine)
        for p in prompts:
            sched.submit(p, gen)
        t0 = time.perf_counter()
        done = sched.run_until_done()
        wall = time.perf_counter() - t0
        bad = [r.rid for r in done
               if r.state is not State.FINISHED or len(r.output) != gen]
        if len(done) != len(prompts) or bad:
            raise AssertionError(f"{backend}: requests {bad} unfinished")
        engine.pool.check()
        if engine.pool.num_live:
            raise AssertionError(f"{backend}: {engine.pool.num_live} pages "
                                 f"leaked")
        if probe.logits is None or not np.isfinite(probe.logits).all():
            raise AssertionError(f"{backend}: no finite decode logits")
        runs[backend] = dict(outputs=[r.output for r in done],
                             logits=probe.logits, active=probe.active,
                             wall_s=wall)
        if backend == "pallas":
            kernels = kernels_in_decode_step(engine)
            if kernels < 1:
                raise AssertionError("the decode step holds no Pallas kernel:"
                                     " the gather fallback served instead")
        del engine, sched, probe

    act = runs["pallas"]["active"]
    if not (act == runs["ref"]["active"]).all() or not act.any():
        raise AssertionError("the two runs decoded different slot sets")
    got, want = runs["pallas"]["logits"][act], runs["ref"]["logits"][act]
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    if not err <= LOGIT_RTOL * scale:
        raise AssertionError(f"decode logits differ by {err} "
                             f"(bound {LOGIT_RTOL} x {scale})")
    toks = [(a, b) for oa, ob in zip(runs["pallas"]["outputs"],
                                     runs["ref"]["outputs"])
            for a, b in zip(oa, ob)]
    return dict(requests=len(prompts), gen=gen, kernels=kernels,
                logit_max_abs_diff=err, logit_scale=scale,
                token_agreement=sum(a == b for a, b in toks) / len(toks),
                pallas_wall_s=runs["pallas"]["wall_s"],
                ref_wall_s=runs["ref"]["wall_s"])


def _kernels_in_train_step(cfg, *, batch: int, seq: int, remat: str) -> int:
    """pallas_call equations in the traced train step: proves the flash
    kernels are dispatched rather than the mea fallback."""
    from repro.launch.steps import StepConfig, build_train_step
    from repro.launch.train import make_mesh_for_host
    mesh = make_mesh_for_host()
    fn, abstract, _, _ = build_train_step(
        cfg, mesh, StepConfig(seq=seq, batch=batch, remat=remat))
    with mesh:
        return str(jax.make_jaxpr(fn)(*abstract)).count("pallas_call")


def train_phase(cfg, *, batch: int, seq: int, steps: int, remat: str,
                attn_cfg: str, seed: int) -> dict:
    """A few `train` steps with the Pallas flash kernels, then the first
    step again with ``attn_backend="ref"``."""
    from repro.launch.train import train
    pallas = dataclasses.replace(cfg, attn_backend="pallas",
                                 attn_cfg=attn_cfg, attn_bwd_cfg=attn_cfg)
    kernels = _kernels_in_train_step(pallas, batch=batch, seq=seq,
                                     remat=remat)
    if kernels < 1:
        raise AssertionError("the train step holds no Pallas kernel")
    kw = dict(batch=batch, seq=seq, ckpt_dir=None, remat=remat, seed=seed,
              log_every=1)
    losses, _ = train(pallas, steps=steps, **kw)
    ref_losses, _ = train(dataclasses.replace(cfg, attn_backend="ref"),
                          steps=1, **kw)
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss: {losses}")
    diff = abs(losses[0] - ref_losses[0])
    if not diff <= LOSS_ATOL:
        raise AssertionError(f"first-step loss {losses[0]} vs ref "
                             f"{ref_losses[0]} (bound {LOSS_ATOL})")
    return dict(losses=losses, ref_first_loss=ref_losses[0],
                first_loss_diff=diff, kernels=kernels)


def one_train_step(cfg, mesh, *, batch: int, seq: int, remat: str,
                   seed: int):
    """(loss, grad-norm) of the first train step on ``mesh``."""
    from repro.launch.steps import StepConfig, build_train_step
    from repro.optim import adamw_init
    fn, _, in_sh, out_sh = build_train_step(
        cfg, mesh, StepConfig(seq=seq, batch=batch, remat=remat))
    tok = np.random.default_rng(seed).integers(0, cfg.vocab, (batch, seq + 1))
    with mesh:
        params = jax.jit(lambda k: M.lm_init(k, cfg), out_shardings=in_sh[0])(
            jax.random.PRNGKey(seed))
        opt = jax.jit(adamw_init, out_shardings=in_sh[1])(params)
        data = jax.device_put({"tokens": tok[:, :-1].astype(np.int32),
                               "labels": tok[:, 1:].astype(np.int32)},
                              in_sh[2])
        _, _, loss, gn = jax.jit(fn, in_shardings=in_sh,
                                 out_shardings=out_sh,
                                 donate_argnums=(0, 1))(params, opt, data)
        return float(loss), float(gn)


def mesh_phase(cfg, *, batch: int, seq: int, remat: str, seed: int) -> dict:
    """One step data- and tensor-parallel on a 2x2 mesh from
    `make_mesh_for_host`, against the same step on one chip."""
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_mesh_for_host
    kw = dict(batch=batch, seq=seq, remat=remat, seed=seed)
    loss4, gn4 = one_train_step(cfg, make_mesh_for_host(model=2), **kw)
    loss1, gn1 = one_train_step(
        cfg, make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1]),
        **kw)
    if not (np.isfinite([loss4, gn4]).all()
            and abs(loss4 - loss1) <= MESH_LOSS_ATOL
            and abs(gn4 - gn1) <= MESH_GNORM_RTOL * gn1):
        raise AssertionError(f"2x2 step (loss {loss4}, gnorm {gn4}) vs one "
                             f"chip (loss {loss1}, gnorm {gn1})")
    return dict(loss_2x2=loss4, gnorm_2x2=gn4, loss_1=loss1, gnorm_1=gn1)


def _phase(name, clock, fn, **kw):
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn(**kw)
    print(f"{name}: compile {clock.seconds - c0:.1f}s, smoke wall "
          f"{time.perf_counter() - t0:.1f}s (compilation included) | "
          f"{json.dumps(out)}", flush=True)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 (data, model) train step and "
                         "its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found {dev.platform} "
                 f"({dev.device_kind}), not a TPU; nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devs)} device(s)")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)

    if args.chips == 4:
        _phase("mesh 2x2 vs 1", clock, mesh_phase, cfg=cfg, batch=2,
               seq=1024, remat="full", seed=args.seed)
    else:
        params = M.lm_init(jax.random.PRNGKey(args.seed), cfg)
        _phase("serve", clock, serve_phase, cfg=cfg, params=params, slots=8,
               max_len=2048, page_size=16, chunk=64, decode_block=8,
               prompt_lens=(384, 256, 320, 192, 448, 256), gen=33,
               decode_cfg="con4", seed=args.seed)
        del params
        _phase("train", clock, train_phase, cfg=cfg, batch=2, seq=1024,
               steps=3, remat="full", attn_cfg="con4", seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
