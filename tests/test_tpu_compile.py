"""Ahead-of-time compiles for a described TPU v5e at internlm2-1.8b's
published widths.

Interpret mode runs every kernel through XLA on the CPU and so cannot see
the TPU compiler's tiling and VMEM rules; these compiles can, at no chip
time.  Each kernel of the serving main path (its lowered call carrying
the kernel's stable name), the paged engine's jitted decode block, and
the GRPO train step at ``chip_smoke``'s depth cut are compiled for one
chip of a ``v5e:2x2`` topology.  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports every
test file.
"""
import dataclasses
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import build_model
from repro.serve.blocks import blocks_for

ARCH = "internlm2-1.8b"
B, BS, S = 8, 16, 288          # slots, KV block size, prompt + decode budget
VOCAB = 92544
HBM_BYTES = 16 * 2 ** 30       # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def sds(one_chip):
    """Shape tree -> ShapeDtypeStructs placed on the described chip."""
    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    return place


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_case(name):
    from repro.kernels.decode_attention import (decode_attention,
                                                paged_decode_attention)
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.sampling import greedy_sample
    cfg = get_config(ARCH)
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    MB = blocks_for(S, BS)
    NB = B * MB + 1
    S_ = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    paged = [S_((B, H, D), bf16), S_((NB, BS, Hkv, D), bf16),
             S_((NB, BS, Hkv, D), bf16), S_((B, MB), jnp.int32),
             S_((B,), jnp.int32)]
    if name in ("paged_bf16", "paged_f32"):
        if name == "paged_f32":
            paged[:3] = [S_(a.shape, jnp.float32) for a in paged[:3]]
        return (lambda *a: paged_decode_attention(*a, interpret=False),
                paged)
    if name == "paged_int8":
        q8 = S_((NB, BS, Hkv, D), jnp.int8)
        sc = S_((NB, BS), jnp.float32)
        return (lambda q, k, v, t, n, ks, vs: paged_decode_attention(
                    q, k, v, t, n, k_scale=ks, v_scale=vs, interpret=False),
                [paged[0], q8, q8, *paged[3:], sc, sc])
    if name == "decode_bf16":
        kv = S_((B, S, Hkv, D), bf16)
        return (lambda *a: decode_attention(*a, interpret=False),
                [paged[0], kv, kv, paged[4]])
    if name.startswith("greedy_sample"):
        rows = 1 if name.endswith("b1") else B
        return (lambda x: greedy_sample(x, interpret=False),
                [S_((rows, VOCAB), jnp.float32)])
    if name == "flash_bf16":
        q, kv = S_((1, S, H, D), bf16), S_((1, S, Hkv, D), bf16)
        return (lambda *a: flash_attention(*a, interpret=False),
                [q, kv, kv])
    raise KeyError(name)


# each case's kernel, by the stable name its pallas_call carries
KERNEL_NAMES = {"paged_f32": "paged_decode_attention",
                "paged_bf16": "paged_decode_attention",
                "paged_int8": "paged_decode_attention",
                "decode_bf16": "decode_attention",
                "greedy_sample_b1": "greedy_sample",
                "greedy_sample": "greedy_sample",
                "flash_bf16": "flash_attention"}


@pytest.mark.parametrize("name", list(KERNEL_NAMES))
def test_kernel_compiles_for_v5e(name, sds):
    fn, args = _kernel_case(name)
    lowered = jax.jit(fn).lower(*sds(args))
    assert f'kernel_name = "{KERNEL_NAMES[name]}"' in lowered.as_text()
    compiled = lowered.compile().as_text()
    assert "tpu_custom_call" in compiled
    # the operation a profile shows is named after the kernel
    assert f"%{KERNEL_NAMES[name]}." in compiled


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_decode_block_compiles_for_v5e(kv_dtype, sds):
    """The engine's jitted decode block over all 24 layers, pallas backend,
    on the bf16 params and the paged pool the engine would hold."""
    from repro.serve.engine import _paged_engine_fns
    model = build_model(ARCH)
    fns = _paged_engine_fns(model, S, BS, 0.0, -1, kernel_backend="pallas",
                            kv_dtype=kv_dtype, interpret=False)
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        B, S, block_size=BS, num_blocks=B * blocks_for(S, BS),
        kv_dtype=kv_dtype))
    S_ = jax.ShapeDtypeStruct
    args = sds((model.init_abstract(), S_((B, VOCAB), jnp.float32), cache,
                S_((B, blocks_for(S, BS)), jnp.int32), S_((B,), jnp.bool_),
                S_((B,), jnp.int32), S_((1, 2), jnp.uint32)))
    compiled = fns["block"].lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# rollout-longtail's engine: 64 slots, max_seq_len 576, a pool of 2,048
# blocks of 16 (the traffic file of the rollout-longtail cell)
CELL_SLOTS, CELL_SEQ, CELL_BLOCKS = 64, 576, 2048


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_paged_decode_block_reads_pool_in_place(kv_dtype, sds):
    """At the benchmark cell's shapes the decode block runs the paged
    kernel under its stable name, and no op of it builds the head-major
    ``(NB, Hkv, bs, D)`` copy of a layer pool: the kernel reads the pool
    in its native ``(NB, bs, Hkv, D)`` layout.  Only bitcasts take the
    kernel's output, so no other op names it among its operands, where a
    profile's search for the kernel by name would count it too."""
    from repro.serve.engine import _paged_engine_fns
    model = build_model(ARCH)
    cfg = model.cfg
    fns = _paged_engine_fns(model, CELL_SEQ, BS, 0.0, -1,
                            kernel_backend="pallas", kv_dtype=kv_dtype,
                            interpret=False)
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        CELL_SLOTS, CELL_SEQ, block_size=BS, num_blocks=CELL_BLOCKS,
        kv_dtype=kv_dtype))
    MB = blocks_for(CELL_SEQ, BS)
    S_ = jax.ShapeDtypeStruct
    args = sds((model.init_abstract(),
                S_((CELL_SLOTS, VOCAB), jnp.float32), cache,
                S_((CELL_SLOTS, MB), jnp.int32),
                S_((CELL_SLOTS,), jnp.bool_), S_((CELL_SLOTS,), jnp.int32),
                S_((1, 2), jnp.uint32)))
    lowered = fns["block"].lower(*args)
    assert 'kernel_name = "paged_decode_attention"' in lowered.as_text()
    compiled = lowered.compile().as_text()
    assert "%paged_decode_attention" in compiled
    NB, Hkv, D = CELL_BLOCKS + 1, cfg.num_kv_heads, cfg.resolved_head_dim
    native = f"{NB},{BS},{Hkv},{D}]"
    transposed = f"{NB},{Hkv},{BS},{D}]"
    assert native in compiled
    assert transposed not in compiled
    kernel_op = re.compile(r"%paged_decode_attention\.\d+\b")
    for line in compiled.splitlines():
        line = line.strip()
        if kernel_op.search(line) and not kernel_op.match(line):
            assert " bitcast(" in line, line


def _rl_step_bytes(layers, sds, rows, cols):
    """What the stream-mux GRPO phase holds at its peak on one chip: the
    train step's arguments, outputs and temps (memory_analysis), the
    previous weights the rollout engine may still hold, and 1 GiB for the
    KV pool and runtime."""
    from repro.rl.train_step import make_train_step
    from repro.train.optimizer import AdamWConfig, adamw_init, warmup_cosine
    model = build_model(dataclasses.replace(get_config(ARCH),
                                            num_layers=layers))
    opt = AdamWConfig(lr=3e-4)
    step = make_train_step(model, opt, lr_schedule=warmup_cosine(3e-4, 10, 3))
    params = model.init_abstract()
    state = {"params": params,
             "opt": jax.eval_shape(lambda: adamw_init(params, opt))}
    i32 = jax.ShapeDtypeStruct((rows, cols), jnp.int32)
    f32 = jax.ShapeDtypeStruct((rows, cols), jnp.float32)
    batch = {"tokens": i32, "labels": i32, "loss_mask": f32,
             "advantages": f32, "behavior_logp": f32}
    mem = _compile(step, *sds((state, batch))).memory_analysis()
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
            + param_bytes + 2 ** 30)


def test_rl_depth_cut_is_deepest_that_fits(sds):
    """``chip_smoke.RL_LAYERS`` is the deepest published-width cut whose
    GRPO phase fits one 16 GiB chip."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    rows = smoke.RL_BATCH * smoke.RL_GROUP
    cols = 16 + smoke.RL_MAX_NEW - 1        # task prompt + completion - 1
    L = smoke.RL_LAYERS
    assert _rl_step_bytes(L, sds, rows, cols) <= HBM_BYTES
    assert _rl_step_bytes(L + 1, sds, rows, cols) > HBM_BYTES
