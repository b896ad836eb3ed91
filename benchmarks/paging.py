"""Paged-KV serving table: effective capacity at a fixed HBM budget plus
paged-vs-contiguous decode cost.

Rows:

  paging,capacity,B<budget>   admission sim on a heterogeneous-length trace
                              at a fixed KV-token budget.  The contiguous
                              cache reserves a full max_len stripe per slot,
                              so short requests strand the tail of their
                              stripe; the paged pool holds page-granular
                              allocations, so the same budget admits more
                              live tokens.  ``ratio`` (paged/contiguous
                              admitted tokens) is the acceptance headline —
                              the PR gate is ratio >= 1.5 on this trace.
  paging,kernel,...           contiguous split-KV decode vs the block-table
                              paged kernel at the same geometry: wall us
                              (CPU interpret) + modeled v5e us (paged pays
                              per-page descriptors + table-lookup latency).
  paging,serve,...            end-to-end tok/s of the BatchedServer vs the
                              Scheduler+PagedEngine on the SAME trace, with
                              the paged pool sized to HALF the contiguous
                              footprint (forcing page pressure); both are
                              CPU interpret-scale, reported for trend only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CoarseningConfig
from repro.core.analysis import decode_attention_cost
from repro.kernels import ops
from repro.serve import pages_needed
from benchmarks.common import wall_us, emit

MAX_LEN = 512           # contiguous per-slot reservation
GEN = 64                # generation budget per request
PAGE = 16               # paged allocation granularity


def _trace(rng, n: int) -> list[int]:
    """Heterogeneous request lengths (prompt+gen), lognormal-ish: mostly
    short, a heavy tail near max_len — the shape that starves a contiguous
    cache."""
    lens = np.exp(rng.normal(4.6, 0.8, n)).astype(int) + GEN
    return [int(min(max(v, GEN + 8), MAX_LEN)) for v in lens]


def capacity_rows(rng) -> None:
    lens = _trace(rng, 256)
    for slots in (2, 4, 8):
        budget = slots * MAX_LEN                      # KV tokens of HBM
        # contiguous: a request occupies a whole max_len stripe
        cont = lens[:slots]
        # paged: worst-case (fully generated) page footprint per request
        pool, paged = budget // PAGE, []
        for ln in lens:
            need = pages_needed(ln, PAGE)
            if need > pool:
                break
            pool -= need
            paged.append(ln)
        ratio = sum(paged) / max(sum(cont), 1)
        emit(f"paging,capacity,B{budget}", -1.0, -1.0,
             contiguous_reqs=len(cont), paged_reqs=len(paged),
             contiguous_tokens=sum(cont), paged_tokens=sum(paged),
             ratio=round(ratio, 2))


def kernel_rows() -> None:
    b, h, hkv, d, s = 2, 8, 4, 32, 256
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, 1, h, d))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    pos = jnp.full((b,), s - 1, jnp.int32)
    ps = 64
    npp = s // ps
    n_pages = b * npp + 1
    kp = jnp.zeros((n_pages, hkv, ps, d), kc.dtype)
    vp = jnp.zeros((n_pages, hkv, ps, d), vc.dtype)
    perm = np.random.default_rng(0).permutation(np.arange(1, n_pages))
    bt = jnp.asarray(perm.reshape(b, npp), jnp.int32)
    for bb in range(b):
        for lp in range(npp):
            pg = int(bt[bb, lp])
            kp = kp.at[pg].set(kc[bb, lp * ps:(lp + 1) * ps].swapaxes(0, 1))
            vp = vp.at[pg].set(vc[bb, lp * ps:(lp + 1) * ps].swapaxes(0, 1))
    for label in ("none", "con2", "gap2"):
        cfg = CoarseningConfig.parse(label) if label != "none" \
            else CoarseningConfig()
        c_cont = decode_attention_cost(b, h, hkv, s, d, cfg, bkv=ps)
        c_page = decode_attention_cost(b, h, hkv, s, d, cfg, bkv=ps,
                                       page_size=ps)
        emit(f"paging,kernel,contig,S{s},{label}",
             wall_us(lambda: ops.decode_attention(q, kc, vc, pos, cfg,
                                                  bkv=ps)),
             c_cont.modeled_s * 1e6)
        emit(f"paging,kernel,paged,S{s},{label}",
             wall_us(lambda: ops.paged_decode_attention(q, kp, vp, bt, pos,
                                                        cfg)),
             c_page.modeled_s * 1e6,
             overhead=round(c_page.modeled_s / c_cont.modeled_s, 3))


def serve_rows(rng) -> None:
    from repro.configs import get_config
    from repro.models import model as M
    from repro.launch.serve import BatchedServer
    from repro.obs import Registry
    from repro.serve import PagedEngine, Scheduler

    cfg = get_config("qwen3-0.6b").reduced()
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    slots, max_len, gen, ps = 3, 48, 8, 8
    prompts = [list(map(int, rng.integers(1, cfg.vocab, int(n))))
               for n in rng.integers(5, 33, 6)]

    srv = BatchedServer(cfg, params, slots=slots, max_len=max_len,
                        chunk=16, decode_block=4)
    pending = list(prompts)
    while pending or srv.any_active:
        while pending and srv.try_admit(pending[0], gen):
            pending.pop(0)
        if not srv.any_active:
            break
        srv.step()
    # tok/s over device time (the jitted decode calls + sync) so the row
    # measures the kernel path, not host bookkeeping
    emit("paging,serve,contiguous", -1.0, -1.0,
         decode_tok_s=round(
             srv.decoded_tokens / max(srv.decode_device_s, 1e-9), 1),
         kv_tokens=slots * max_len)

    # paged pool at HALF the contiguous KV footprint
    reg = Registry()
    num_pages = (slots * max_len) // (2 * ps) + 1
    eng = PagedEngine(cfg, params, slots=slots, num_pages=num_pages,
                      page_size=ps, max_len=max_len, chunk=16,
                      decode_block=4, metrics=reg)
    sched = Scheduler(eng, metrics=reg)
    for p in prompts:
        sched.submit(p, gen)
    done = sched.run_until_done()
    dec_tok = int(reg.value("engine_decode_tokens_total"))
    assert dec_tok == eng.decoded_tokens
    emit("paging,serve,paged", -1.0, -1.0,
         decode_tok_s=round(
             dec_tok / max(eng.decode_device_s, 1e-9), 1),
         kv_tokens=eng.pool.tokens_capacity,
         preemptions=int(reg.value("sched_preemptions_total")),
         completed=len(done))


def main() -> None:
    rng = np.random.default_rng(0)
    capacity_rows(rng)
    kernel_rows()
    serve_rows(rng)


if __name__ == "__main__":
    main()
