"""jit'd public wrappers for every kernel family.

Each op takes a CoarseningConfig and dispatches to the Pallas kernel
(interpret=True on CPU; on TPU the same pallas_call lowers via Mosaic) or, for
``backend='ref'``, to the pure-jnp oracle — the path used by model training
on CPU and by the XLA dry-run lowering.

The ``cfg`` argument also accepts strings: a spec label ("con4+pipe2") is
parsed, and ``"auto"`` resolves through the repro.tune autotuner — modeled
ranking against the persisted tuning cache, so the second call with the same
geometry never re-searches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.coarsening import CoarseningConfig
from repro.kernels import (
    ew_stream as _ew,
    gather_stream as _gather,
    matmul as _matmul,
    stencil as _stencil,
    chunk_scan as _scan,
    flash_attention as _flash,
    sparse_attention as _sparse,
    decode_attention as _decode,
    moe_ffn as _moe_ffn,
    ssd as _ssd,
    rglru as _rglru,
    ref,
)

BASE = CoarseningConfig()


def _interpret() -> bool:
    """Pallas lowering mode for the jit'd ops: interpret on CPU hosts, the
    real Mosaic lowering on accelerator backends — which is what lets
    tune.wall_measurer time COMPILED kernels (measured provenance) on a TPU
    host while keeping interpret-mode timing as the CPU fallback."""
    return jax.default_backend() == "cpu"


@functools.lru_cache(maxsize=1024)
def _auto_cfg(cache_path, family, shape, dtype, backend, params):
    from repro.tune import KernelSpec, autotune, default_cache
    spec = KernelSpec(family=family, shape=shape, dtype=dtype,
                      backend=backend, params=params)
    return autotune(spec, cache=default_cache())


def resolve_cfg(cfg, family: str, shape, *, dtype="float32",
                backend: str = "pallas", **params) -> CoarseningConfig:
    """Normalise an op's cfg argument: CoarseningConfig passes through,
    "auto" goes through the tuner (cache-backed), any other string is a
    coarsening spec label.

    Callers must pass the REAL array dtype (and, for quantized ops, the
    wbits/kv_bits params): the tuner cache is keyed on it, and bf16 vs f32
    vs quantized instances of one geometry cost — and can win — differently.
    The "float32" default only serves dtype-less specs."""
    if isinstance(cfg, CoarseningConfig):
        return cfg
    if cfg == "auto":
        if backend == "ref":              # oracle path: nothing to tune
            return BASE
        # keyed on the cache path so repointing REPRO_TUNE_CACHE is honoured
        from repro.tune import default_cache_path
        return _auto_cfg(default_cache_path(), family,
                         tuple(int(s) for s in shape), str(dtype),
                         backend, tuple(sorted(params.items())))
    return CoarseningConfig.parse(cfg)


@functools.lru_cache(maxsize=256)
def _ew_fn(n, cfg, n_loads, ai, variant, block):
    return jax.jit(_ew.make_kernel(n, cfg, n_loads=n_loads, ai=ai,
                                   variant=variant, block=block,
                                   interpret=_interpret()))


def ew_stream(inputs, cfg: CoarseningConfig | str = BASE, *, ai: int = 6,
              variant: str = "base", block: int = 1024):
    n = inputs[0].shape[0]
    cfg = resolve_cfg(cfg, "ew_stream", (n,), dtype=inputs[0].dtype.name,
                      n_loads=len(inputs), ai=ai, variant=variant,
                      block=block)
    fn = _ew_fn(n, cfg, len(inputs), ai, variant, block)
    return fn(*inputs)


@functools.lru_cache(maxsize=256)
def _gather_fn(n, table, cfg, n_loads, ai, block):
    return jax.jit(_gather.make_kernel(n, table, cfg, n_loads=n_loads, ai=ai,
                                       block=block, interpret=_interpret()))


def gather_stream(idx, tables, cfg: CoarseningConfig | str = BASE, *,
                  ai: int = 6, block: int = 1024):
    cfg = resolve_cfg(cfg, "gather_stream",
                      (idx.shape[0], tables[0].shape[0]),
                      dtype=tables[0].dtype.name,
                      n_loads=len(tables), ai=ai, block=block)
    fn = _gather_fn(idx.shape[0], tables[0].shape[0], cfg, len(tables), ai, block)
    return fn(idx, *tables)


@functools.lru_cache(maxsize=256)
def _matmul_fn(m, n, k, cfg, bm, bn, bk, backend):
    if backend == "ref":
        return jax.jit(ref.matmul)
    return jax.jit(_matmul.make_kernel(m, n, k, cfg, bm=bm, bn=bn, bk=bk,
                                       interpret=_interpret()))


def matmul(a, b, cfg: CoarseningConfig | str = BASE, *, bm: int = 128,
           bn: int = 128, bk: int = 256, backend: str = "pallas"):
    m, k = a.shape
    n = b.shape[1]
    cfg = resolve_cfg(cfg, "matmul", (m, n, k), dtype=a.dtype.name,
                      backend=backend, bm=bm, bn=bn, bk=bk)
    return _matmul_fn(m, n, k, cfg, bm, bn, bk, backend)(a, b)


@functools.lru_cache(maxsize=256)
def _quant_matmul_fn(m, n, k, cfg, bits, group, bm, bn, bk, backend):
    if backend == "ref":
        return jax.jit(ref.matmul)
    return jax.jit(_matmul.make_qkernel(m, n, k, cfg, bits=bits, group=group,
                                        bm=bm, bn=bn, bk=bk,
                                        interpret=_interpret()))


def quant_matmul(a, qw, cfg: CoarseningConfig | str = BASE, *, bm: int = 128,
                 bn: int = 128, bk: int = 256, backend: str = "pallas"):
    """Dequant-fused matmul against a QTensor weight: ``a (m,k) @ qw (k,n)``
    with the packed weight pane DMA'd and dequantized in VMEM once per
    program.  The tuner spec carries ``wbits``/``group``, so quantized and
    dense instances of the same geometry occupy DIFFERENT cache keys and can
    pick different coarsening degrees.  backend='ref' is the dense-dequant
    oracle."""
    m, k = a.shape
    n = qw.shape[-1]
    if qw.shape != (k, n):
        raise ValueError(f"quant_matmul: a {a.shape} vs qw {qw.shape}")
    cfg = resolve_cfg(cfg, "matmul", (m, n, k), dtype=a.dtype.name,
                      backend=backend, bm=bm, bn=bn, bk=bk,
                      wbits=qw.bits, group=qw.group)
    if backend == "ref":
        from repro.quant.qtypes import dequantize
        return _quant_matmul_fn(m, n, k, cfg, qw.bits, qw.group, bm, bn, bk,
                                backend)(a, dequantize(qw))
    return _quant_matmul_fn(m, n, k, cfg, qw.bits, qw.group, bm, bn, bk,
                            backend)(a, qw.q, qw.scale)


@functools.lru_cache(maxsize=256)
def _stencil_fn(rows, cols, cfg, block_rows):
    return jax.jit(_stencil.make_kernel(rows, cols, cfg, block_rows=block_rows,
                                        interpret=_interpret()))


def stencil5(x, cfg: CoarseningConfig | str = BASE, *, block_rows: int = 8):
    cfg = resolve_cfg(cfg, "stencil5", x.shape, dtype=x.dtype.name,
                      block_rows=block_rows)
    return _stencil_fn(x.shape[0], x.shape[1], cfg, block_rows)(x)


@functools.lru_cache(maxsize=256)
def _scan_fn(rows, cols, cfg):
    return jax.jit(_scan.make_kernel(rows, cols, cfg, interpret=_interpret()))


def dp_scan(cost, cfg: CoarseningConfig | str = BASE):
    cfg = resolve_cfg(cfg, "dp_scan", cost.shape, dtype=cost.dtype.name)
    return _scan_fn(cost.shape[0], cost.shape[1], cfg)(cost)


@functools.lru_cache(maxsize=256)
def _flash_vjp_fn(b, h, hkv, sq, sk, d, cfg, bwd_cfg, bq, bkv, causal,
                  window, scale, dtype_name):
    """Custom-VJP flash attention for one geometry: the VJP forward saves
    the (o, m, l) online-softmax residuals; the backward runs the dK/dV
    kernel coarsened on the KV-BLOCK axis (``bwd_cfg``) and the dQ kernel
    coarsened on the q-row axis matching the forward (``cfg``) —
    independent degrees, since the two passes stream different axes.

    Forward-only calls stay pure-forward: the primal runs a residual-free
    kernel (a pallas_call's outputs can't be DCE'd, so emitting m/l there
    would write two dead (B,H,Sq) f32 arrays per call), and ``bwd_cfg``
    may arrive unresolved ("auto") — the flash_attention_bwd family is
    searched and the backward kernels built only when a backward trace
    actually runs."""
    fwd = _flash.make_kernel(b, h, hkv, sq, d, cfg, bq=bq, bkv=bkv,
                             causal=causal, window=window, scale=scale,
                             sk=sk, interpret=_interpret())
    fwd_res = _flash.make_kernel(b, h, hkv, sq, d, cfg, bq=bq, bkv=bkv,
                                 causal=causal, window=window, scale=scale,
                                 sk=sk, return_residuals=True,
                                 interpret=_interpret())

    @jax.custom_vjp
    def attn(q, k, v):
        return fwd(q, k, v)

    def attn_fwd(q, k, v):
        from jax.ad_checkpoint import checkpoint_name
        o, m, l = fwd_res(q, k, v)
        # name ALL the kernel's outputs, not just o: the backward consumes
        # (o, m, l), so a remat policy that saved o alone would still
        # replay the whole pallas_call to rebuild m/l
        o = checkpoint_name(o, "flash_attn_out")
        m = checkpoint_name(m, "flash_attn_out")
        l = checkpoint_name(l, "flash_attn_out")
        return o, (q, k, v, o, m, l)

    def attn_bwd(res, g):
        rbwd = resolve_cfg(bwd_cfg, "flash_attention_bwd",
                           (b, h, hkv, sq, sk, d), dtype=dtype_name,
                           backend="pallas", bq=bq, bkv=bkv,
                           causal=bool(causal))
        bwd_dq = _flash.make_bwd_dq_kernel(b, h, hkv, sq, d, cfg, bq=bq,
                                           bkv=bkv, causal=causal,
                                           window=window, scale=scale, sk=sk,
                                           interpret=_interpret())
        bwd_dkv = _flash.make_bwd_dkv_kernel(b, h, hkv, sq, d, rbwd, bq=bq,
                                             bkv=bkv, causal=causal,
                                             window=window, scale=scale,
                                             sk=sk, interpret=_interpret())
        q, k, v, o, m, l = res
        g = g.astype(jnp.float32)
        delta = jnp.sum(g * o, axis=-1)                # (B,H,Sq) f32
        dq = bwd_dq(q, k, v, g, m, l, delta)
        dk, dv = bwd_dkv(q, k, v, g, m, l, delta)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))

    attn.defvjp(attn_fwd, attn_bwd)
    return jax.jit(attn)


@functools.lru_cache(maxsize=256)
def _flash_ref_fn(causal, window, scale):
    return jax.jit(functools.partial(ref.attention, causal=causal,
                                     window=window, scale=scale))


def flash_attention(q, k, v, cfg: CoarseningConfig | str = BASE, *,
                    bwd_cfg: CoarseningConfig | str | None = None,
                    bq: int = 128, bkv: int = 128, causal: bool = True,
                    window: int | None = None, scale: float | None = None,
                    backend: str = "pallas"):
    """Differentiable coarsened flash attention.  q: (B,H,Sq,D);
    k, v: (B,Hkv,Sk,D) -> (B,H,Sq,D) f32.

    ``cfg`` coarsens the forward (and the dQ backward pass) on the q-row
    axis; ``bwd_cfg`` (default "auto" through the ``flash_attention_bwd``
    tuner family) coarsens the dK/dV backward pass on the kv-block axis.
    ``scale`` overrides the default 1/sqrt(D) logit scaling.
    """
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if backend == "ref":
        return _flash_ref_fn(causal, window, scale)(q, k, v)
    cfg = resolve_cfg(cfg, "flash_attention", (b, h, hkv, sq, sk, d),
                      dtype=q.dtype.name, backend=backend, bq=bq, bkv=bkv,
                      causal=bool(causal))
    if bwd_cfg is None:
        bwd_cfg = "auto"
    # bwd_cfg stays unresolved here: forward-only callers must not pay a
    # flash_attention_bwd search (or a cache write) they never use — the
    # VJP rule resolves it when a backward trace happens
    if isinstance(bwd_cfg, str):
        bwd_cfg = bwd_cfg if bwd_cfg == "auto" \
            else CoarseningConfig.parse(bwd_cfg)
    return _flash_vjp_fn(b, h, hkv, sq, sk, d, cfg, bwd_cfg, bq, bkv,
                         causal, window, scale, q.dtype.name)(q, k, v)


@functools.lru_cache(maxsize=256)
def _flash_sparse_fn(b, h, hkv, sq, sk, d, cfg, bwd_cfg, bq, bkv, causal,
                     window, global_stride, scale, dtype_name):
    """Custom-VJP block-sparse flash attention for one geometry + pattern.

    The per-q-block live-KV index is a pure function of the geometry, so it
    is built host-side here and closed over as a jit constant — callers
    never thread it.  The forward runs the sparse kernel (coarsened over
    the live-slot axis by ``cfg``); the backward reuses the DENSE-mask
    backward kernels: the sparse forward's (m, l) residuals are identical
    to the dense-mask forward's (the index covers the pattern mask
    exactly; verified in tests), so `make_bwd_dq_kernel` /
    `make_bwd_dkv_kernel` consume them unchanged.  For global-stride
    patterns the dense backward kernels can't express the strided columns,
    so the backward differentiates the jnp oracle instead — strided
    TRAINING pays dense cost (documented fallback); strided prefill still
    takes the sparse kernel.
    """
    # kept as a host numpy constant: converting to a device array here
    # would bind it to whatever trace is active at build time (this
    # factory is lru-cached, so that tracer would leak into later traces);
    # as numpy it is lifted per-trace like any closure constant
    idx = _sparse.build_block_index(sq, sk, bq, bkv, causal=causal,
                                    window=window,
                                    global_stride=global_stride)
    max_live = int(idx.shape[1])
    mk = functools.partial(_sparse.make_kernel, b, h, hkv, sq, d, cfg,
                           bq=bq, bkv=bkv, max_live=max_live, causal=causal,
                           window=window, global_stride=global_stride,
                           scale=scale, sk=sk, interpret=_interpret())
    fwd = mk()
    fwd_res = mk(return_residuals=True)

    @jax.custom_vjp
    def attn(q, k, v):
        return fwd(q, k, v, idx)

    def attn_fwd(q, k, v):
        from jax.ad_checkpoint import checkpoint_name
        o, m, l = fwd_res(q, k, v, idx)
        o = checkpoint_name(o, "flash_attn_out")
        m = checkpoint_name(m, "flash_attn_out")
        l = checkpoint_name(l, "flash_attn_out")
        return o, (q, k, v, o, m, l)

    def attn_bwd(res, g):
        q, k, v, o, m, l = res
        g = g.astype(jnp.float32)
        if global_stride:
            primal = functools.partial(
                _sparse.ref_sparse_attention, causal=causal, window=window,
                global_stride=global_stride, scale=scale)
            _, vjp = jax.vjp(primal, q, k, v)
            dq, dk, dv = vjp(g)
            return (dq.astype(q.dtype), dk.astype(k.dtype),
                    dv.astype(v.dtype))
        rbwd = resolve_cfg(bwd_cfg, "flash_attention_bwd",
                           (b, h, hkv, sq, sk, d), dtype=dtype_name,
                           backend="pallas", bq=bq, bkv=bkv,
                           causal=bool(causal))
        # dQ at BASE: cfg's degree is a live-SLOT degree, not a q-row one
        bwd_dq = _flash.make_bwd_dq_kernel(b, h, hkv, sq, d, BASE, bq=bq,
                                           bkv=bkv, causal=causal,
                                           window=window, scale=scale, sk=sk,
                                           interpret=_interpret())
        bwd_dkv = _flash.make_bwd_dkv_kernel(b, h, hkv, sq, d, rbwd, bq=bq,
                                             bkv=bkv, causal=causal,
                                             window=window, scale=scale,
                                             sk=sk, interpret=_interpret())
        delta = jnp.sum(g * o, axis=-1)                # (B,H,Sq) f32
        dq = bwd_dq(q, k, v, g, m, l, delta)
        dk, dv = bwd_dkv(q, k, v, g, m, l, delta)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))

    attn.defvjp(attn_fwd, attn_bwd)
    return jax.jit(attn)


@functools.lru_cache(maxsize=256)
def _sparse_ref_fn(causal, window, global_stride, scale):
    return jax.jit(functools.partial(_sparse.ref_sparse_attention,
                                     causal=causal, window=window,
                                     global_stride=global_stride,
                                     scale=scale))


def flash_attention_sparse(q, k, v, cfg: CoarseningConfig | str = BASE, *,
                           bwd_cfg: CoarseningConfig | str | None = None,
                           bq: int = 128, bkv: int = 128, causal: bool = True,
                           window: int | None = None,
                           global_stride: int | None = None,
                           scale: float | None = None,
                           backend: str = "pallas"):
    """Block-sparse flash attention over a per-q-block live-KV index.
    q: (B,H,Sq,D); k, v: (B,Hkv,Sk,D) -> (B,H,Sq,D) f32.

    Each q-block program walks only the kv blocks with live (q, k) pairs
    under the pattern {``causal``, sliding ``window``, LongFormer-style
    ``global_stride`` columns}; ``cfg`` coarsens over the LIVE-SLOT axis
    (consecutive = adjacent index slots, gapped = slots strided
    max_live/degree apart).  The ``flash_attention_sparse`` tuner family
    keys on the pattern (window/gstride/max_live join the spec), so a 32k
    window=512 instance occupies a different cache row — and picks a
    different winning degree — than the dense family at the same shape.
    backend='ref' is the dense-mask jnp oracle (the parity target)."""
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if backend == "ref":
        return _sparse_ref_fn(causal, window, global_stride, scale)(q, k, v)
    idx = _sparse.build_block_index(sq, sk, bq, bkv, causal=causal,
                                    window=window,
                                    global_stride=global_stride)
    max_live, n_live = int(idx.shape[1]), int((idx >= 0).sum())
    cfg = resolve_cfg(cfg, "flash_attention_sparse", (b, h, hkv, sq, sk, d),
                      dtype=q.dtype.name, backend=backend, bq=bq, bkv=bkv,
                      causal=bool(causal), window=window or 0,
                      gstride=global_stride or 0, max_live=max_live,
                      n_live=n_live)
    if bwd_cfg is None:
        bwd_cfg = "auto"
    # unresolved "auto" rides into the VJP rule exactly as in
    # flash_attention: forward-only callers never pay a bwd-family search
    if isinstance(bwd_cfg, str):
        bwd_cfg = bwd_cfg if bwd_cfg == "auto" \
            else CoarseningConfig.parse(bwd_cfg)
    return _flash_sparse_fn(b, h, hkv, sq, sk, d, cfg, bwd_cfg, bq, bkv,
                            causal, window, global_stride, scale,
                            q.dtype.name)(q, k, v)


@functools.lru_cache(maxsize=256)
def _decode_fn(b, h, hkv, s, d, cfg, bkv, window, scale, backend,
               kv_bits=None):
    if backend == "ref":
        return jax.jit(functools.partial(ref.decode_attention, window=window,
                                         scale=scale))
    return jax.jit(_decode.make_kernel(b, h, hkv, s, d, cfg, bkv=bkv,
                                       window=window, scale=scale,
                                       kv_bits=kv_bits,
                                       interpret=_interpret()))


def decode_attention(q, k_cache, v_cache, pos, cfg: CoarseningConfig | str = BASE,
                     *, bkv: int = 128, window: int | None = None,
                     scale: float | None = None, backend: str = "pallas",
                     k_scale=None, v_scale=None):
    """Split-KV decode attention.  q: (B,1,H,D); caches: (B,S,Hkv,D);
    pos: (B,) int32 -> (B,1,H,D).  The coarsening axis is the kv-block
    axis (each program owns cfg.degree kv blocks of bkv rows).

    Passing ``k_scale``/``v_scale`` (B,S,Hkv) selects the int8 KV-cache
    mode: the caches are int8 payloads and the dequant is fused into the
    kernel's VMEM pass (``kv_bits=8`` on the tuner spec — a distinct cache
    key from the bf16 instance of the same geometry)."""
    b, _, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    quant = k_scale is not None
    kv_bits = 8 if quant else None
    params = dict(bkv=bkv, window=window or 0)
    if quant:
        params["kv_bits"] = 8
    cfg = resolve_cfg(cfg, "decode_attention", (b, h, hkv, s, d),
                      dtype=k_cache.dtype.name, backend=backend, **params)
    if backend == "ref" and quant:
        from repro.quant.qtypes import dequantize_kv
        k_cache = dequantize_kv(k_cache, k_scale)
        v_cache = dequantize_kv(v_cache, v_scale)
        quant = False
    fn = _decode_fn(b, h, hkv, s, d, cfg, bkv, window, scale, backend,
                    kv_bits if backend != "ref" else None)
    if quant:
        return fn(q, k_cache, v_cache, k_scale, v_scale, pos)
    return fn(q, k_cache, v_cache, pos)


@functools.lru_cache(maxsize=256)
def _paged_decode_fn(b, h, hkv, n_pages, npp, d, cfg, page_size, window,
                     scale, backend, kv_bits=None):
    if backend == "ref":
        def run(q, k_pool, v_pool, bt, pos):
            # gather-to-contiguous oracle: resolve the block table on the
            # host-visible (XLA) side, then dense full-length attention
            return ref.decode_attention(q, ref.paged_view(k_pool, bt),
                                        ref.paged_view(v_pool, bt), pos,
                                        window=window, scale=scale)
        return jax.jit(run)
    return jax.jit(_decode.make_paged_kernel(b, h, hkv, n_pages, npp, d, cfg,
                                             page_size=page_size,
                                             window=window, scale=scale,
                                             kv_bits=kv_bits,
                                             interpret=_interpret()))


def paged_decode_attention(q, k_pool, v_pool, block_table, pos,
                           cfg: CoarseningConfig | str = BASE, *,
                           window: int | None = None,
                           scale: float | None = None,
                           backend: str = "pallas",
                           k_scale=None, v_scale=None):
    """Split-KV decode attention through a per-slot block table.

    q: (B,1,H,D); pools: (P, Hkv, page_size, D) shared by all slots;
    block_table: (B, npp) int32 logical->physical page map (NULL-padded);
    pos: (B,) int32 -> (B,1,H,D).  The coarsening axis is the LOGICAL-PAGE
    axis (each program owns cfg.degree pages, resolved through the table —
    the gapped strided-pane DMA with the stride replaced by a lookup).

    ``k_scale``/``v_scale`` (P, Hkv, page_size) select the int8 pool mode
    (kv_bits=8 joins the tuner key, as does the page size)."""
    b, _, h, d = q.shape
    n_pages, hkv, page_size, _ = k_pool.shape
    npp = block_table.shape[1]
    quant = k_scale is not None
    params = dict(page_size=page_size, window=window or 0)
    if quant:
        params["kv_bits"] = 8
    cfg = resolve_cfg(cfg, "decode_attention_paged", (b, h, hkv, npp, d),
                      dtype=k_pool.dtype.name, backend=backend, **params)
    if backend == "ref" and quant:
        from repro.quant.qtypes import dequantize_kv
        k_pool = dequantize_kv(k_pool, k_scale)
        v_pool = dequantize_kv(v_pool, v_scale)
        quant = False
    fn = _paged_decode_fn(b, h, hkv, n_pages, npp, d, cfg, page_size,
                          window, scale, backend,
                          8 if quant and backend != "ref" else None)
    if quant:
        return fn(q, k_pool, v_pool, k_scale, v_scale, block_table, pos)
    return fn(q, k_pool, v_pool, block_table, pos)


@functools.lru_cache(maxsize=256)
def _verify_fn(b, h, hkv, t, n_pages, npp, d, cfg, page_size, window,
               scale, backend, kv_bits=None):
    if backend == "ref":
        def run(q, k_pool, v_pool, bt, pos0):
            # gather-to-contiguous oracle, same shape as the paged-decode
            # ref path: resolve the table on the XLA side, then the dense
            # per-row verify oracle
            return ref.verify_attention(q, ref.paged_view(k_pool, bt),
                                        ref.paged_view(v_pool, bt), pos0,
                                        window=window, scale=scale)
        return jax.jit(run)
    return jax.jit(_decode.make_verify_kernel(b, h, hkv, t, n_pages, npp, d,
                                              cfg, page_size=page_size,
                                              window=window, scale=scale,
                                              kv_bits=kv_bits,
                                              interpret=_interpret()))


def flash_attention_verify(q, k_pool, v_pool, block_table, pos0,
                           cfg: CoarseningConfig | str = BASE, *,
                           window: int | None = None,
                           scale: float | None = None,
                           backend: str = "pallas",
                           k_scale=None, v_scale=None):
    """Batched-verify attention through a per-slot block table (the
    speculative-decode short-q flash geometry).

    q: (B,T,H,D) — T drafted rows per slot, row t at cache position
    ``pos0[b] + t``; pools: (P, Hkv, page_size, D); block_table: (B, npp)
    int32; pos0: (B,) int32 -> (B,T,H,D).  The coarsening axis is the
    LOGICAL-PAGE axis as in `paged_decode_attention`, but the tuner family
    (``flash_attention_verify``) is distinct: scoring T*G rows per fetched
    page moves the memory/compute crossover, so the winning degree differs
    from both the decode and prefill families.

    ``k_scale``/``v_scale`` (P, Hkv, page_size) select the int8 pool mode
    (kv_bits=8 joins the tuner key)."""
    b, t, h, d = q.shape
    n_pages, hkv, page_size, _ = k_pool.shape
    npp = block_table.shape[1]
    quant = k_scale is not None
    params = dict(page_size=page_size, window=window or 0)
    if quant:
        params["kv_bits"] = 8
    cfg = resolve_cfg(cfg, "flash_attention_verify", (b, h, hkv, t, npp, d),
                      dtype=k_pool.dtype.name, backend=backend, **params)
    if backend == "ref" and quant:
        from repro.quant.qtypes import dequantize_kv
        k_pool = dequantize_kv(k_pool, k_scale)
        v_pool = dequantize_kv(v_pool, v_scale)
        quant = False
    fn = _verify_fn(b, h, hkv, t, n_pages, npp, d, cfg, page_size,
                    window, scale, backend,
                    8 if quant and backend != "ref" else None)
    if quant:
        return fn(q, k_pool, v_pool, k_scale, v_scale, block_table, pos0)
    return fn(q, k_pool, v_pool, block_table, pos0)


@functools.lru_cache(maxsize=256)
def _moe_ffn_fn(e, cap, d, f, cfg, backend):
    if backend == "ref":
        return jax.jit(ref.moe_ffn)
    return jax.jit(_moe_ffn.make_kernel(e, cap, d, f, cfg,
                                         interpret=_interpret()))


def moe_ffn(xe, w1, w3, w2, wts, cfg: CoarseningConfig | str = BASE, *,
            backend: str = "pallas"):
    """Grouped-expert fused gate/up/down FFN over the padded MoE dispatch
    buffer.  xe: (E,C,d); w1,w3: (E,d,F); w2: (E,F,d); wts: (E,C) combine
    weights -> (E,C,d) float32.  The coarsening axis is the EXPERT axis
    (each program owns cfg.degree experts; consecutive = one wide weight
    DMA per operand, gapped = degree strided DMAs)."""
    e, cap, d = xe.shape
    f = w1.shape[-1]
    cfg = resolve_cfg(cfg, "moe_ffn", (e, cap, d, f), dtype=xe.dtype.name,
                      backend=backend)
    return _moe_ffn_fn(e, cap, d, f, cfg, backend)(xe, w1, w3, w2, wts)


@functools.lru_cache(maxsize=256)
def _quant_moe_ffn_fn(e, cap, d, f, cfg, bits, group, backend):
    if backend == "ref":
        return jax.jit(ref.moe_ffn)
    return jax.jit(_moe_ffn.make_qkernel(e, cap, d, f, cfg, bits=bits,
                                         group=group,
                                         interpret=_interpret()))


def quant_moe_ffn(xe, qw1, qw3, qw2, wts, cfg: CoarseningConfig | str = BASE,
                  *, backend: str = "pallas"):
    """Grouped-expert fused FFN with QTensor expert weights: the packed
    w1/w3/w2 panes of each program's ``degree`` experts are DMA'd (one wide
    packed pane per operand for consecutive, strided for gapped) and
    dequantized in VMEM once, then the fused gate/up/down chain runs as in
    ``moe_ffn``.  backend='ref' is the dense-dequant einsum oracle."""
    e, cap, d = xe.shape
    f = qw1.shape[-1]
    cfg = resolve_cfg(cfg, "moe_ffn", (e, cap, d, f), dtype=xe.dtype.name,
                      backend=backend, wbits=qw1.bits, group=qw1.group)
    if backend == "ref":
        from repro.quant.qtypes import dequantize
        return _quant_moe_ffn_fn(e, cap, d, f, cfg, qw1.bits, qw1.group,
                                 backend)(xe, dequantize(qw1),
                                          dequantize(qw3), dequantize(qw2),
                                          wts)
    return _quant_moe_ffn_fn(e, cap, d, f, cfg, qw1.bits, qw1.group, backend)(
        xe, qw1.q, qw1.scale, qw3.q, qw3.scale, qw2.q, qw2.scale, wts)


@functools.lru_cache(maxsize=256)
def _ssd_fn(b, h, g, s, p, n, cfg, chunk, backend):
    if backend == "ref":
        def run(x, dt, a, bmat, cmat):
            # kernel layout (B,H,S,P) -> ref layout (B,S,H,P)
            y = ref.ssd(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), a,
                        bmat.transpose(0, 2, 1, 3), cmat.transpose(0, 2, 1, 3))
            return y.transpose(0, 2, 1, 3)
        return jax.jit(run)
    return jax.jit(_ssd.make_kernel(b, h, g, s, p, n, cfg, chunk=chunk,
                                     interpret=_interpret()))


def ssd(x, dt, a, bmat, cmat, cfg: CoarseningConfig | str = BASE, *,
        chunk: int = 64, backend: str = "pallas"):
    """x:(B,H,S,P) dt:(B,H,S) a:(H,) bmat/cmat:(B,G,S,N)."""
    b, h, s, p = x.shape
    g, n = bmat.shape[1], bmat.shape[3]
    cfg = resolve_cfg(cfg, "ssd", (b, h, g, s, p, n), dtype=x.dtype.name,
                      backend=backend, chunk=chunk)
    return _ssd_fn(b, h, g, s, p, n, cfg, chunk, backend)(x, dt, a, bmat, cmat)


@functools.lru_cache(maxsize=256)
def _embed_fn(n, vocab, d, cfg, block):
    from repro.kernels import embed_gather as _eg
    return jax.jit(_eg.make_kernel(n, vocab, d, cfg, block=block,
                                   interpret=_interpret()))


def embed_gather(ids, table, cfg: CoarseningConfig | str = BASE, *,
                 block: int = 256):
    cfg = resolve_cfg(cfg, "embed_gather",
                      (ids.shape[0], table.shape[0], table.shape[1]),
                      dtype=table.dtype.name, block=block)
    return _embed_fn(ids.shape[0], table.shape[0], table.shape[1], cfg,
                     block)(ids, table)


@functools.lru_cache(maxsize=256)
def _rglru_fn(b, s, d, cfg, block_d, block_t, backend):
    if backend == "ref":
        return jax.jit(ref.rglru)
    return jax.jit(_rglru.make_kernel(b, s, d, cfg, block_d=block_d,
                                      block_t=block_t,
                                      interpret=_interpret()))


def rglru(x, r, i, a_param, cfg: CoarseningConfig | str = BASE, *,
          block_d: int = 128, block_t: int = 64, backend: str = "pallas"):
    b, s, d = x.shape
    cfg = resolve_cfg(cfg, "rglru", (b, s, d), dtype=x.dtype.name,
                      backend=backend, block_d=block_d, block_t=block_t)
    return _rglru_fn(b, s, d, cfg, block_d, block_t, backend)(x, r, i, a_param)
