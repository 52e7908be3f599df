"""Prefill and single-token decode for every architecture family.

``serve_step`` (one token against a seq_len cache) is what the decode input
shapes lower; prefill builds the cache. Decode caches follow kvcache.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import kvcache
from repro.models import mamba2 as mb
from repro.models import moe as moe_lib
from repro.models import rwkv6 as rw
from repro.models.common import (default_mrope_positions, gelu_mlp_apply,
                                 mlp_apply)
from repro.models.stacks import (
    _embed_tokens, _layer_theta_window, _norm, _sinusoid,
    _unembed, encode_source)


def _write_seq(cache_arr, new, start):
    """Write (L,B,S_new,...) into (L,B,S_max,...) at seq offset ``start``."""
    zeros = (0,) * (cache_arr.ndim - 3)
    return jax.lax.dynamic_update_slice(cache_arr, new.astype(cache_arr.dtype),
                                        (0, 0, start, *zeros))


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------
def stack_prefill(p, cfg: ModelConfig, tokens, cache, *, frontend=None):
    """Full-sequence forward that fills ``cache``; returns (last_logits, cache)."""
    B, S = tokens.shape
    x = _embed_tokens(p, cfg, tokens, frontend)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
    mrope_pos = (default_mrope_positions(B, S, cfg.num_frontend_tokens)
                 if cfg.mrope else None)
    fam = cfg.family

    if fam in ("dense", "vlm", "moe"):
        theta_l, window_l = _layer_theta_window(cfg)

        def body(x, xs):
            lp, theta, window = xs
            h = _norm(lp["ln1"], x, cfg)
            if cfg.attention == "mla":
                a = attn.mla_apply_full(lp["attn"], cfg, h, positions)
                ckv, krope = attn._mla_kv_compress(lp["attn"], cfg, h, positions)
                kv = (ckv, krope)
            else:
                q, k, v = attn.gqa_project_qkv(lp["attn"], cfg, h, positions,
                                               rope_theta=theta,
                                               mrope_positions=mrope_pos)
                out = attn.multi_head_attention(q, k, v, positions[0],
                                                positions[0], causal=True,
                                                window=window)
                a = jnp.einsum("bshe,hed->bsd", out, lp["attn"]["wo"])
                kv = (k, v)
            x = x + a
            h = _norm(lp["ln2"], x, cfg)
            if "moe" in lp:
                f, _ = moe_lib.moe_apply(lp["moe"], cfg, h)
            else:
                f = mlp_apply(lp["mlp"], h)
            return x + f, kv

        n_moe = cfg.num_layers - cfg.first_dense_layers if cfg.is_moe else 0
        n_dense = cfg.num_layers - n_moe
        kv_parts = []
        if cfg.is_moe and cfg.first_dense_layers:
            x, kv_d = jax.lax.scan(body, x, (p["dense_layers"],
                                             theta_l[:n_dense], window_l[:n_dense]))
            x, kv_m = jax.lax.scan(body, x, (p["layers"],
                                             theta_l[n_dense:], window_l[n_dense:]))
            kv = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), kv_d, kv_m)
        else:
            x, kv = jax.lax.scan(body, x, (p["layers"], theta_l, window_l))
        if cfg.attention == "mla":
            cache["ckv"] = _write_seq(cache["ckv"], kv[0], 0)
            cache["krope"] = _write_seq(cache["krope"], kv[1], 0)
        else:
            cache["k"] = _write_seq(cache["k"], kv[0], 0)
            cache["v"] = _write_seq(cache["v"], kv[1], 0)

    elif fam == "ssm":
        def body(x, lp):
            h = _norm(lp["ln1"], x, cfg)
            o, (ax, wkv) = rw.rwkv6_time_mix_full(lp["mix"], cfg, h)
            x = x + o
            h = _norm(lp["ln2"], x, cfg)
            o, fx = rw.rwkv6_channel_mix(lp["mix"], cfg, h)
            return x + o, (ax, fx, wkv)
        x, (ax, fx, wkv) = jax.lax.scan(body, x, p["layers"])
        cache["att_x"], cache["ffn_x"] = ax.astype(cache["att_x"].dtype), fx.astype(cache["ffn_x"].dtype)
        cache["wkv"] = wkv

    elif fam == "hybrid":
        shared = p["shared_attn"]
        every = cfg.hybrid_attn_every

        def shared_block(x):
            h = _norm(shared["ln1"], x, cfg)
            q, k, v = attn.gqa_project_qkv(shared["attn"], cfg, h, positions)
            out = attn.multi_head_attention(q, k, v, positions[0], positions[0])
            x = x + jnp.einsum("bshe,hed->bsd", out, shared["attn"]["wo"])
            h = _norm(shared["ln2"], x, cfg)
            return x + mlp_apply(shared["mlp"], h), k, v

        def body(x, xs):
            lp, idx = xs
            h = _norm(lp["norm"], x, cfg)
            m, (conv, ssm) = mb.mamba2_apply_full(lp["mamba"], cfg, h)
            x = x + m
            hd = cfg.resolved_head_dim
            dummy = jnp.zeros((B, S, cfg.num_kv_heads, hd), x.dtype)
            x, k, v = jax.lax.cond((idx + 1) % every == 0, shared_block,
                                   lambda y: (y, dummy, dummy), x)
            return x, (conv, ssm, k, v)

        x, (conv, ssm, k, v) = jax.lax.scan(
            body, x, (p["layers"], jnp.arange(cfg.num_layers)))
        cache["conv"], cache["ssm"] = conv.astype(cache["conv"].dtype), ssm
        k_occ, v_occ = k[every - 1::every], v[every - 1::every]
        Sa = cache["attn_k"].shape[2]
        if S > Sa:
            k_occ, v_occ = k_occ[:, :, -Sa:], v_occ[:, :, -Sa:]
        cache["attn_k"] = _write_seq(cache["attn_k"], k_occ, 0)
        cache["attn_v"] = _write_seq(cache["attn_v"], v_occ, 0)

    elif fam == "audio":
        enc = encode_source(p, cfg, frontend)
        # precompute cross K/V per decoder layer
        def cross_kv(cp):
            ek = jnp.einsum("bsd,dhe->bshe", enc, cp["attn"]["wk"])
            ev = jnp.einsum("bsd,dhe->bshe", enc, cp["attn"]["wv"])
            if cfg.qkv_bias:
                ek, ev = ek + cp["attn"]["bk"], ev + cp["attn"]["bv"]
            return ek, ev
        ck, cv = jax.lax.map(cross_kv, p["cross"])
        cache["cross_k"], cache["cross_v"] = ck.astype(cache["cross_k"].dtype), cv.astype(cache["cross_v"].dtype)
        x = x + _sinusoid(S, cfg.d_model).astype(x.dtype)

        def body(x, xs):
            lp, cp, ekl, evl = xs
            h = _norm(lp["ln1"], x, cfg)
            q, k, v = attn.gqa_project_qkv(lp["attn"], cfg, h, positions,
                                           rope_theta=0.0)
            out = attn.multi_head_attention(q, k, v, positions[0], positions[0])
            x = x + jnp.einsum("bshe,hed->bsd", out, lp["attn"]["wo"])
            h = _norm(cp["ln"], x, cfg)
            x = x + attn.gqa_apply_cross(cp["attn"], cfg, h, ekl, evl)
            h = _norm(lp["ln2"], x, cfg)
            return x + gelu_mlp_apply(lp["mlp"], h), (k, v)

        x, (k, v) = jax.lax.scan(body, x, (p["layers"], p["cross"], ck, cv))
        cache["k"] = _write_seq(cache["k"], k, 0)
        cache["v"] = _write_seq(cache["v"], v, 0)
    else:
        raise ValueError(fam)

    cache["index"] = jnp.asarray(S, jnp.int32)
    logits = _unembed(p, cfg, x[:, -1:])
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# Decode (one token)
# ---------------------------------------------------------------------------
def stack_decode_step(p, cfg: ModelConfig, token, cache, *, ring: bool = False):
    """token: (B,1) int32. Returns (logits (B,V), cache')."""
    index = cache["index"]
    x = jnp.take(p["embed"], token, axis=0)
    if cfg.family == "dense" and cfg.local_global_ratio:
        x = x * (cfg.d_model ** 0.5)
    fam = cfg.family
    B = token.shape[0]
    mrope_pos = None
    if cfg.mrope:  # single-position ids consistent with default_mrope_positions
        F = cfg.num_frontend_tokens
        side = max(int(F ** 0.5), 1)
        is_img = index < F
        h = jnp.where(is_img, index // side, index)
        w = jnp.where(is_img, index % side, index)
        tt = jnp.where(is_img, 0, index - F + 1)
        mrope_pos = jnp.broadcast_to(
            jnp.stack([tt, h, w])[:, None, None], (3, B, 1)).astype(jnp.int32)

    if fam in ("dense", "vlm", "moe"):
        theta_l, window_l = _layer_theta_window(cfg, ring=ring)
        if cfg.attention == "mla":
            def body(x, xs):
                lp, ckv_l, krope_l = xs
                h = _norm(lp["ln1"], x, cfg)
                a, ckv_l, krope_l = attn.mla_decode_step(
                    lp["attn"], cfg, h, ckv_l, krope_l, index)
                x = x + a
                h = _norm(lp["ln2"], x, cfg)
                if "moe" in lp:
                    f, _ = moe_lib.moe_apply(lp["moe"], cfg, h)
                else:
                    f = mlp_apply(lp["mlp"], h)
                return x + f, (ckv_l, krope_l)
            kv_names = ("ckv", "krope")
        else:
            def body(x, xs):
                lp, k_l, v_l, theta, window = xs
                h = _norm(lp["ln1"], x, cfg)
                a, k_l, v_l = attn.gqa_decode_step(
                    lp["attn"], cfg, h, k_l, v_l, index, window=window,
                    rope_theta=theta, mrope_positions=mrope_pos, ring=ring)
                x = x + a
                h = _norm(lp["ln2"], x, cfg)
                if "moe" in lp:
                    f, _ = moe_lib.moe_apply(lp["moe"], cfg, h)
                else:
                    f = mlp_apply(lp["mlp"], h)
                return x + f, (k_l, v_l)
            kv_names = ("k", "v")

        n_moe = cfg.num_layers - cfg.first_dense_layers if cfg.is_moe else 0
        n_dense = cfg.num_layers - n_moe
        c0, c1 = (cache[kv_names[0]], cache[kv_names[1]])
        import os as _os
        if _os.environ.get("DRYRUN_UNROLL_DECODE") and not cfg.is_moe \
                and cfg.attention != "mla":
            # §Perf C: unrolled layer loop — each layer's cache update is an
            # independent dynamic-update-slice into the (donated) cache, so
            # XLA updates in place instead of rewriting the scan-carried
            # full stack every iteration.
            theta_l2, window_l2 = theta_l, window_l
            nc0, nc1 = c0, c1
            for li in range(cfg.num_layers):
                lp = jax.tree.map(lambda a: a[li], p["layers"])
                k_l = jax.lax.dynamic_index_in_dim(c0, li, 0, keepdims=False)
                v_l = jax.lax.dynamic_index_in_dim(c1, li, 0, keepdims=False)
                h = _norm(lp["ln1"], x, cfg)
                a, k_l, v_l = attn.gqa_decode_step(
                    lp["attn"], cfg, h, k_l, v_l, index,
                    window=window_l2[li], rope_theta=theta_l2[li],
                    mrope_positions=mrope_pos, ring=ring)
                x = x + a
                h = _norm(lp["ln2"], x, cfg)
                x = x + mlp_apply(lp["mlp"], h)
                nc0 = nc0.at[li].set(k_l.astype(nc0.dtype))
                nc1 = nc1.at[li].set(v_l.astype(nc1.dtype))
            cache[kv_names[0]], cache[kv_names[1]] = nc0, nc1
            cache["index"] = index + 1
            logits = _unembed(p, cfg, x)
            return logits[:, 0], cache
        if cfg.is_moe and cfg.first_dense_layers:
            if cfg.attention == "mla":
                xs_d = (p["dense_layers"], c0[:n_dense], c1[:n_dense])
                xs_m = (p["layers"], c0[n_dense:], c1[n_dense:])
            else:
                xs_d = (p["dense_layers"], c0[:n_dense], c1[:n_dense],
                        theta_l[:n_dense], window_l[:n_dense])
                xs_m = (p["layers"], c0[n_dense:], c1[n_dense:],
                        theta_l[n_dense:], window_l[n_dense:])
            x, kv_d = jax.lax.scan(body, x, xs_d)
            x, kv_m = jax.lax.scan(body, x, xs_m)
            kv = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), kv_d, kv_m)
        else:
            if cfg.attention == "mla":
                xs = (p["layers"], c0, c1)
            else:
                xs = (p["layers"], c0, c1, theta_l, window_l)
            x, kv = jax.lax.scan(body, x, xs)
        cache[kv_names[0]], cache[kv_names[1]] = kv

    elif fam == "ssm":
        def body(x, xs):
            lp, ax, fx, wkv = xs
            h = _norm(lp["ln1"], x, cfg)
            o, ax, wkv = rw.rwkv6_time_mix_step(lp["mix"], cfg, h, ax, wkv)
            x = x + o
            h = _norm(lp["ln2"], x, cfg)
            o, fx = rw.rwkv6_channel_mix(lp["mix"], cfg, h, fx)
            return x + o, (ax, fx, wkv)
        x, (ax, fx, wkv) = jax.lax.scan(
            body, x, (p["layers"], cache["att_x"], cache["ffn_x"], cache["wkv"]))
        cache["att_x"], cache["ffn_x"], cache["wkv"] = (
            ax.astype(cache["att_x"].dtype), fx.astype(cache["ffn_x"].dtype), wkv)

    elif fam == "hybrid":
        shared = p["shared_attn"]
        every = cfg.hybrid_attn_every
        Sa = cache["attn_k"].shape[2]
        attn_ring = ring
        window = jnp.asarray(Sa, jnp.int32) if attn_ring else None

        def body(carry, xs):
            x, ak, av = carry
            lp, idx = xs
            h = _norm(lp["norm"], x, cfg)
            m, conv, ssm = mb.mamba2_decode_step(
                lp["mamba"], cfg, h, lp["_conv"], lp["_ssm"])
            x = x + m
            occ = idx // every

            def do_attn(op):
                x, ak, av = op
                k_l = jax.lax.dynamic_index_in_dim(ak, occ, 0, keepdims=False)
                v_l = jax.lax.dynamic_index_in_dim(av, occ, 0, keepdims=False)
                h = _norm(shared["ln1"], x, cfg)
                a, k_l, v_l = attn.gqa_decode_step(
                    shared["attn"], cfg, h, k_l, v_l, index,
                    window=window, ring=attn_ring)
                x = x + a
                h = _norm(shared["ln2"], x, cfg)
                x = x + mlp_apply(shared["mlp"], h)
                ak = jax.lax.dynamic_update_index_in_dim(ak, k_l, occ, 0)
                av = jax.lax.dynamic_update_index_in_dim(av, v_l, occ, 0)
                return x, ak, av

            x, ak, av = jax.lax.cond((idx + 1) % every == 0, do_attn,
                                     lambda op: op, (x, ak, av))
            return (x, ak, av), (conv, ssm)

        layers_xs = dict(p["layers"])
        layers_xs["_conv"], layers_xs["_ssm"] = cache["conv"], cache["ssm"]
        (x, ak, av), (conv, ssm) = jax.lax.scan(
            body, (x, cache["attn_k"], cache["attn_v"]),
            (layers_xs, jnp.arange(cfg.num_layers)))
        cache["conv"], cache["ssm"] = conv.astype(cache["conv"].dtype), ssm
        cache["attn_k"], cache["attn_v"] = ak, av

    elif fam == "audio":
        x = x + _sinusoid(1, cfg.d_model, offset=index).astype(x.dtype)

        def body(x, xs):
            lp, cp, k_l, v_l, ck_l, cv_l = xs
            h = _norm(lp["ln1"], x, cfg)
            a, k_l, v_l = attn.gqa_decode_step(lp["attn"], cfg, h, k_l, v_l,
                                               index, rope_theta=0.0)
            x = x + a
            h = _norm(cp["ln"], x, cfg)
            x = x + attn.gqa_apply_cross(cp["attn"], cfg, h, ck_l, cv_l)
            h = _norm(lp["ln2"], x, cfg)
            return x + gelu_mlp_apply(lp["mlp"], h), (k_l, v_l)

        x, (k, v) = jax.lax.scan(
            body, x, (p["layers"], p["cross"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        cache["k"], cache["v"] = k, v
    else:
        raise ValueError(fam)

    cache["index"] = index + 1
    logits = _unembed(p, cfg, x)
    return logits[:, 0], cache


# ---------------------------------------------------------------------------
# Decode (one token, whole slot pool, Pallas attention)
# ---------------------------------------------------------------------------
def kernel_supported(cfg: ModelConfig) -> bool:
    """Whether :func:`stack_kernel_decode_step` can serve this config.

    Only GQA transformer stacks qualify: SSM/recurrent families carry no
    sequence-shaped KV for the decode kernel to page, and MLA decodes in
    the compressed-KV space (a different kernel entirely)."""
    return cfg.family in ("dense", "vlm", "moe") and cfg.attention != "mla"


def stack_kernel_decode_step(p, cfg: ModelConfig, token, cache, *,
                             tables=None, interpret: bool = True):
    """Batched one-token decode through the Pallas decode-attention kernels.

    The engine-layout counterpart of :func:`stack_decode_step`: instead of
    vmapping a batch=1 model step over slots, one call consumes the whole
    slot pool with a per-slot ``index`` vector and runs
    ``kernels.decode_attention`` (contiguous slot stripes, ``tables=None``)
    or ``kernels.paged_decode_attention`` (block-table pools) per layer.
    In the paged case the block table is scalar-prefetched into the kernel,
    so no gathered contiguous view of the pool is ever materialized.

    token: ``(N, 1)`` int32.  cache: the serving-cache layout —

    * contiguous: ``k``/``v`` ``(L, N, S, Hkv, hd)`` slot stripes,
      ``index`` ``(N,)``;
    * paged (``tables`` ``(N, MB)`` int32): ``k``/``v`` pools
      ``(L, NB+1, bs, Hkv, hd)`` (block 0 = null), optionally int8 with
      per-position ``k_scale``/``v_scale`` pools ``(L, NB+1, bs)``
      (quantize-on-write, dequantized inside the kernel's block loop).

    Dead slots (table rows all 0 / ``index`` past the stripe) write into
    the null block or fall off the stripe — don't-care positions attention
    masks out, same as the vmapped jnp path.  In the paged case a dead
    slot (its table's first entry the null block: ``release`` zeroes the
    row, a live slot always holds its prompt's first block) is passed to
    the kernel as length 0, so it costs no KV reads.  Returns
    ``(logits (N, V) f32, cache')``.
    """
    if not kernel_supported(cfg):
        raise ValueError(
            f"kernel decode step supports dense/vlm/moe GQA stacks only, "
            f"not family={cfg.family!r} attention={cfg.attention!r}")
    from repro.kernels.decode_attention import (decode_attention,
                                                paged_decode_attention)

    index = cache["index"]                              # (N,)
    N = token.shape[0]
    rows = jnp.arange(N)
    x = jnp.take(p["embed"], token, axis=0)             # (N, 1, d)
    if cfg.family == "dense" and cfg.local_global_ratio:
        x = x * (cfg.d_model ** 0.5)
    pos = index[:, None]                                # (N, 1) rope position
    mrope_pos = None
    if cfg.mrope:   # per-slot single-position ids (cf. stack_decode_step)
        F = cfg.num_frontend_tokens
        side = max(int(F ** 0.5), 1)
        is_img = index < F
        h = jnp.where(is_img, index // side, index)
        w = jnp.where(is_img, index % side, index)
        tt = jnp.where(is_img, 0, index - F + 1)
        mrope_pos = jnp.stack([tt, h, w])[:, :, None].astype(jnp.int32)

    quant = "k" + kvcache.SCALE_SUFFIX in cache
    if tables is not None:
        bs = cache["k"].shape[2]
        MB = tables.shape[1]
        blk = jnp.minimum(index // bs, MB - 1)
        pid = tables[rows, blk]            # 0 (null block) when dead/overrun
        off = index % bs
        lengths = jnp.where(tables[:, 0] == 0, 0, index + 1)

    def body(x, xs):
        lp, k_l, v_l, ks_l, vs_l, theta, window = xs
        h = _norm(lp["ln1"], x, cfg)
        q, k_new, v_new = attn.gqa_project_qkv(lp["attn"], cfg, h, pos,
                                               rope_theta=theta,
                                               mrope_positions=mrope_pos)
        kr, vr = k_new[:, 0], v_new[:, 0]               # (N, Hkv, hd)
        if tables is None:
            # out-of-stripe writes (dead slots decoding past max_len) drop
            k_l = k_l.at[rows, index].set(kr.astype(k_l.dtype))
            v_l = v_l.at[rows, index].set(vr.astype(v_l.dtype))
            o = decode_attention(q[:, 0], k_l, v_l, index + 1,
                                 window=window, interpret=interpret)
        else:
            if quant:
                kq, ks = kvcache.quantize_kv(kr, 1)
                vq, vs = kvcache.quantize_kv(vr, 1)
                k_l = k_l.at[pid, off].set(kq)
                v_l = v_l.at[pid, off].set(vq)
                ks_l = ks_l.at[pid, off].set(ks)
                vs_l = vs_l.at[pid, off].set(vs)
            else:
                k_l = k_l.at[pid, off].set(kr.astype(k_l.dtype))
                v_l = v_l.at[pid, off].set(vr.astype(v_l.dtype))
            o = paged_decode_attention(q[:, 0], k_l, v_l, tables, lengths,
                                       window=window, k_scale=ks_l,
                                       v_scale=vs_l, interpret=interpret)
        a = jnp.einsum("bshe,hed->bsd", o[:, None], lp["attn"]["wo"])
        x = x + a.astype(x.dtype)
        h = _norm(lp["ln2"], x, cfg)
        if "moe" in lp:
            f, _ = moe_lib.moe_apply(lp["moe"], cfg, h)
        else:
            f = mlp_apply(lp["mlp"], h)
        return x + f, (k_l, v_l, ks_l, vs_l)

    theta_l, window_l = _layer_theta_window(cfg)
    c0, c1 = cache["k"], cache["v"]
    s0 = cache.get("k" + kvcache.SCALE_SUFFIX)
    s1 = cache.get("v" + kvcache.SCALE_SUFFIX)

    def _sl(t, lo, hi):
        return None if t is None else t[lo:hi]

    n_moe = cfg.num_layers - cfg.first_dense_layers if cfg.is_moe else 0
    n_dense = cfg.num_layers - n_moe
    if cfg.is_moe and cfg.first_dense_layers:
        xs_d = (p["dense_layers"], c0[:n_dense], c1[:n_dense],
                _sl(s0, 0, n_dense), _sl(s1, 0, n_dense),
                theta_l[:n_dense], window_l[:n_dense])
        xs_m = (p["layers"], c0[n_dense:], c1[n_dense:],
                _sl(s0, n_dense, cfg.num_layers),
                _sl(s1, n_dense, cfg.num_layers),
                theta_l[n_dense:], window_l[n_dense:])
        x, kv_d = jax.lax.scan(body, x, xs_d)
        x, kv_m = jax.lax.scan(body, x, xs_m)
        kv = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), kv_d, kv_m)
    else:
        xs = (p["layers"], c0, c1, s0, s1, theta_l, window_l)
        x, kv = jax.lax.scan(body, x, xs)
    cache["k"], cache["v"] = kv[0], kv[1]
    if quant:
        cache["k" + kvcache.SCALE_SUFFIX] = kv[2]
        cache["v" + kvcache.SCALE_SUFFIX] = kv[3]
    cache["index"] = index + 1
    logits = _unembed(p, cfg, x)
    return logits[:, 0], cache
