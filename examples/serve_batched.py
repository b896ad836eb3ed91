"""Client of the serving API: submit a mixed-length request trace to the
paged scheduler/engine (or the contiguous BatchedServer with --cache
contiguous) and print per-request outputs.

    PYTHONPATH=src python examples/serve_batched.py --requests 8 --slots 4

Undersize the pool to watch preemption + requeue keep every request's
output identical to running it alone:

    PYTHONPATH=src python examples/serve_batched.py --requests 8 --slots 4 \
        --num-pages 12 --page-size 8

Weight-only quantization + int8 KV pool, coarsened paged decode kernel:

    PYTHONPATH=src python examples/serve_batched.py --requests 8 --slots 4 \
        --quant int8 --kv-quant int8 --decode-backend pallas
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.serve import BatchedServer, device_name
from repro.models import model as M
from repro.serve import PagedEngine, Scheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCHS)
    ap.add_argument("--cache", default="paged",
                    choices=["paged", "contiguous"])
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--gen-tokens", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool pages incl. null (default: fits all slots)")
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--decode-block", type=int, default=4)
    ap.add_argument("--decode-backend", default=None,
                    choices=[None, "ref", "pallas"])
    ap.add_argument("--quant", default=None,
                    choices=[None, "none", "int8", "int4"])
    ap.add_argument("--kv-quant", default=None,
                    choices=[None, "none", "int8"])
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(
        1, cfg.vocab, int(rng.integers(args.min_prompt,
                                       args.max_prompt + 1)))))
               for _ in range(args.requests)]

    t0 = time.perf_counter()
    if args.cache == "paged":
        num_pages = args.num_pages if args.num_pages is not None else \
            args.slots * -(-args.max_len // args.page_size) + 1
        engine = PagedEngine(cfg, params, slots=args.slots,
                             num_pages=num_pages, page_size=args.page_size,
                             max_len=args.max_len, chunk=args.chunk,
                             decode_block=args.decode_block,
                             decode_backend=args.decode_backend,
                             quant=args.quant, kv_quant=args.kv_quant)
        sched = Scheduler(engine)
        for p in prompts:
            sched.submit(p, args.gen_tokens)
        done = sched.run_until_done()
        dt = time.perf_counter() - t0
        for r in done:
            tag = f" ({r.preemptions} preemptions)" if r.preemptions else ""
            print(f"req {r.rid}: prompt[{len(r.prompt)}] -> "
                  f"{r.output[:8]}...{tag}")
        rate = engine.decoded_tokens / max(engine.decode_device_s, 1e-9)
        print(f"{len(done)} requests in {dt:.2f}s | pool "
              f"{engine.pool.capacity} pages x {engine.page_size} tok | "
              f"decode {rate:.1f} tok/s ({device_name()})")
    else:
        server = BatchedServer(cfg, params, slots=args.slots,
                               max_len=args.max_len, chunk=args.chunk,
                               decode_block=args.decode_block,
                               decode_backend=args.decode_backend,
                               quant=args.quant, kv_quant=args.kv_quant)
        pending = list(prompts)
        while pending or server.any_active:
            while pending and server.try_admit(pending[0], args.gen_tokens):
                pending.pop(0)
            if not server.any_active:
                break
            server.step()
        dt = time.perf_counter() - t0
        for i, out in enumerate(server.completed):
            print(f"req {i}: -> {out[:8]}...")
        rate = server.decoded_tokens / max(server.decode_device_s, 1e-9)
        print(f"{len(server.completed)} requests in {dt:.2f}s | decode "
              f"{rate:.1f} tok/s ({device_name()})")


if __name__ == "__main__":
    main()
