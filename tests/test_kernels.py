"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels import ref


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,D,causal,window", [
    (2, 128, 4, 2, 64, True, None),
    (1, 100, 4, 4, 32, True, None),       # padding (100 % 64 != 0)
    (2, 256, 8, 2, 64, True, 64),         # sliding window + GQA
    (1, 64, 2, 2, 128, False, None),      # bidirectional (whisper encoder)
    (1, 192, 6, 3, 32, True, None),       # G = 2, odd head count
])
def test_flash_attention_sweep(B, S, H, Hkv, D, causal, window, dtype,
                               rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64)
    expected = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 5e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Hkv,D,length,bk", [
    (2, 1024, 8, 2, 64, 700, 128),
    (1, 512, 4, 4, 128, 512, 256),
    (3, 256, 16, 8, 32, 1, 64),           # single live token
    (1, 130, 4, 2, 64, 77, 64),           # ragged padding
])
def test_decode_attention_sweep(B, S, H, Hkv, D, length, bk, dtype, rng_key):
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = decode_attention(q, k, v, length, block_k=bk)
    expected = ref.decode_attention_ref(q, k, v, length)
    tol = 5e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.slow
@pytest.mark.parametrize("B,S,H,Dk,Dv,chunk", [
    (2, 100, 3, 16, 16, 32),
    (1, 64, 2, 64, 64, 64),
    (2, 130, 4, 32, 16, 32),              # ragged padding
    (1, 33, 2, 8, 8, 16),
])
def test_rwkv6_scan_sweep(B, S, H, Dk, Dv, chunk, rng_key):
    ks = jax.random.split(rng_key, 5)
    r = jax.random.normal(ks[0], (B, S, H, Dk))
    k = jax.random.normal(ks[1], (B, S, H, Dk))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, Dk)))
    u = jax.random.normal(ks[4], (H, Dk))
    y, st = rwkv6_scan(r, k, v, lw, u, chunk=chunk)
    y_ref, st_ref = ref.rwkv6_scan_ref(r, k, v, lw, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-3, atol=5e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=2e-3, atol=5e-4)


def test_kernel_matches_model_attention_path(rng_key):
    """The Pallas flash kernel and the model's blockwise jnp path agree."""
    from repro.models.attention import multi_head_attention
    B, S, H, Hkv, D = 1, 128, 4, 2, 32
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos = jnp.arange(S)
    a = multi_head_attention(q, k, v, pos, pos, force_blockwise=True)
    b = flash_attention(q, k, v, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("B,S,H,N,hd,chunk", [
    (2, 100, 3, 16, 32, 32),
    (1, 64, 2, 64, 64, 64),
    (2, 130, 4, 8, 16, 32),               # ragged padding
])
def test_mamba2_scan_sweep(B, S, H, N, hd, chunk, rng_key):
    from repro.kernels.mamba2_scan import mamba2_scan
    ks = jax.random.split(rng_key, 4)
    r = jax.random.normal(ks[0], (B, S, H, N))
    k = jax.random.normal(ks[1], (B, S, H, N))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, 1)))
    y, st = mamba2_scan(r, k, v, lw, chunk=chunk)
    y_ref, st_ref = ref.mamba2_scan_ref(r, k, v, lw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-3, atol=5e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=2e-3, atol=5e-4)


def _paged_case(B, H, Hkv, D, bs, MB, lengths, *, dtype=jnp.float32,
                quant=False, shared=0, seed=0):
    """Pools, disjoint block tables (zero tails) and lengths for one paged
    decode case; ``shared`` leading blocks of row 0's table reappear at the
    head of every other live row's (one prompt prefix, many rows)."""
    NB = B * MB + 1                       # pool + null block
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    scales = {}
    if quant:
        k_pool, v_pool = (jax.random.randint(
            kk, (NB, bs, Hkv, D), -127, 128, jnp.int32).astype(jnp.int8)
            for kk in ks[1:3])
        scales = {"k_scale": jax.random.uniform(ks[3], (NB, bs),
                                                minval=1e-3, maxval=2e-2),
                  "v_scale": jax.random.uniform(ks[4], (NB, bs),
                                                minval=1e-3, maxval=2e-2)}
    else:
        k_pool = jax.random.normal(ks[1], (NB, bs, Hkv, D), dtype)
        v_pool = jax.random.normal(ks[2], (NB, bs, Hkv, D), dtype)
    perm = np.random.default_rng(seed).permutation(np.arange(1, NB))
    tables = np.zeros((B, MB), np.int32)
    for b, n in enumerate(lengths):
        nb = -(-n // bs)
        tables[b, :nb] = perm[b * MB:b * MB + nb]
        if b and n and shared:
            tables[b, :shared] = tables[0, :shared]
    return q, k_pool, v_pool, tables, np.asarray(lengths, np.int32), scales


# (B, H, Hkv, D, bs, MB, lengths, options).  K pages of 64 KB in float32
# make P = 8 pages (128 positions) a chunk unless a case says otherwise;
# cases of one shape share one compile.
PAGED_CASES = {
    "ragged": (4, 16, 8, 128, 16, 24, (300, 17, 129, 200), {}),
    "full_table": (4, 16, 8, 128, 16, 24, (384, 100, 0, 5), {}),
    "length_one": (4, 16, 8, 128, 16, 24, (1, 1, 250, 0), {}),
    "shared_prefix": (4, 16, 8, 128, 16, 24, (300, 290, 0, 310),
                      {"shared": 12}),
    "all_dead": (4, 16, 8, 128, 16, 24, (0, 0, 0, 0), {}),
    "window": (4, 16, 8, 128, 16, 24, (300, 40, 383, 0), {"window": 70}),
    "int8": (4, 16, 8, 128, 16, 40, (600, 0, 33, 129),
             {"quant": True, "dtype": jnp.bfloat16}),
    "bf16": (4, 16, 8, 128, 16, 20, (300, 0, 129, 17),
             {"dtype": jnp.bfloat16}),
    "mha": (4, 4, 4, 128, 16, 40, (600, 0, 129, 1), {}),
    "one_kv_head": (4, 4, 1, 128, 64, 24, (1100, 129, 0, 64), {}),
    "head_dim_256": (4, 8, 4, 256, 16, 12, (150, 0, 65, 192), {}),
    "table_narrower_than_chunk": (3, 4, 2, 32, 8, 3, (24, 5, 0), {}),
}


@pytest.mark.parametrize("case", list(PAGED_CASES))
def test_paged_decode_attention_cases(case):
    """The paged kernel against its gather-then-attend oracle on every live
    row, and finite zeros on every row of length 0."""
    from repro.kernels.decode_attention import (_pages_per_step,
                                                paged_decode_attention)
    B, H, Hkv, D, bs, MB, lengths, opts = PAGED_CASES[case]
    opts = dict(opts)
    window = opts.pop("window", None)
    q, k_pool, v_pool, tables, lengths, scales = _paged_case(
        B, H, Hkv, D, bs, MB, lengths, **opts)
    pp = _pages_per_step(bs, Hkv, D, k_pool.dtype.itemsize, MB)
    if case == "table_narrower_than_chunk":
        assert pp == MB < _pages_per_step(bs, Hkv, D, 4, 10 ** 6)
    elif case != "all_dead":             # rows of more than one chunk
        assert max(-(-n // bs) for n in lengths) > pp
    if case == "ragged":
        assert all(n % (pp * bs) for n in lengths)
    if case == "full_table":
        assert lengths.max() == MB * bs
    out = np.asarray(paged_decode_attention(
        q, k_pool, v_pool, tables, lengths, window=window, **scales),
        np.float32)
    expect = np.asarray(ref.paged_decode_attention_ref(
        q, k_pool, v_pool, tables, lengths, window=window, **scales),
        np.float32)
    live = lengths > 0
    tol = 5e-5 if q.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out[live], expect[live], rtol=tol, atol=tol)
    assert np.all(out[~live] == 0.0)


def _pallas_names(jaxpr) -> list[str]:
    """The ``name`` of every ``pallas_call`` in ``jaxpr``, nested ones too."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e.params["name"])
        for p in e.params.values():
            inner = getattr(p, "jaxpr", p)
            if hasattr(inner, "eqns"):
                out += _pallas_names(inner)
    return out


def _kernel_call(name):
    from repro.kernels.decode_attention import paged_decode_attention
    from repro.kernels.mamba2_scan import mamba2_scan
    from repro.kernels.sampling import greedy_sample, topk_values
    x = jnp.zeros
    if name == "paged_decode_attention":
        return paged_decode_attention, (x((2, 4, 8)), x((5, 4, 2, 8)),
                                        x((5, 4, 2, 8)),
                                        x((2, 2), jnp.int32),
                                        x((2,), jnp.int32))
    if name == "decode_attention":
        return decode_attention, (x((2, 4, 8)), x((2, 16, 2, 8)),
                                  x((2, 16, 2, 8)), 5)
    if name == "flash_attention":
        return flash_attention, (x((1, 16, 4, 8)), x((1, 16, 2, 8)),
                                 x((1, 16, 2, 8)))
    if name == "greedy_sample":
        return greedy_sample, (x((2, 64)),)
    if name == "topk_values":
        return (lambda v: topk_values(v, 4)), (x((2, 64)),)
    if name == "rwkv6_scan":
        return rwkv6_scan, (x((1, 16, 2, 8)),) * 4 + (x((2, 8)),)
    if name == "mamba2_scan":
        return mamba2_scan, (x((1, 16, 2, 8)),) * 3 + (x((1, 16, 2, 1)),)
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "paged_decode_attention", "decode_attention", "flash_attention",
    "greedy_sample", "topk_values", "rwkv6_scan", "mamba2_scan"])
def test_kernel_has_stable_name(name):
    """Each Pallas kernel's call carries a ``name`` of its own, which the
    profiler's trace and the benchmark's readers find it by."""
    fn, args = _kernel_call(name)
    assert _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr) == [name]
