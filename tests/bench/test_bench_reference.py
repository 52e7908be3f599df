"""The float32 reference against the program at a small size on the CPU:
prefill then paged decode through the serving engine (sampled tokens),
and the GRPO loss and gradient of the train step.  The reference computed one precision
step lower (bfloat16 operands) fails the same comparisons."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, weights
from bench import model as bm
from bench_tiny import tiny_conf

ROOT = Path(__file__).resolve().parents[2]
CONF = tiny_conf(json.loads(
    (ROOT / "bench/configs/internlm2-1.8b.json").read_text()), "float32")
FAMILY = harness.load_family(CONF["model_type"])
Reference = FAMILY.Reference
# float32 program against the float32 reference: both sum in another
# order, so agreement is to float32 rounding over 2 layers, far inside
# 1e-4; one bfloat16 rounding of the operands moves logits by about 1e-3
LOGP_ATOL = 1e-4
GRAD_RTOL = 1e-4


@pytest.fixture(scope="module")
def served():
    """Sampled requests through the paged engine with the Pallas decode
    kernels (interpreted), two of them sharing a prompt prefix."""
    from repro.serve import Request, RolloutSpec
    model = bm.build(FAMILY, CONF)
    w = weights.make(FAMILY, CONF, 5)
    eng = RolloutSpec(num_slots=3, kv_layout="paged", kv_block_size=4,
                      prefix_share=True, kernel_backend="pallas").build_engine(
        model, w, batch=3, max_seq_len=40, eos_id=-1, temperature=1.0,
        rng=weights.key_for(5, 1))
    rng = np.random.default_rng(0)
    p1 = rng.integers(0, CONF["vocab_size"], 8, dtype=np.int32)
    p2 = rng.integers(0, CONF["vocab_size"], 12, dtype=np.int32)
    for rid, (p, n) in enumerate([(p1, 10), (p2, 6), (p1, 12)]):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=n))
    return w, eng.run()


def _ref_logp(ref, w, o):
    seq, targets, valid = check.padded(o.prompt, o.tokens, 40)
    lp = jax.nn.log_softmax(ref.logits(w, seq[None])[0], -1)
    return np.asarray(jnp.take_along_axis(lp, targets[:, None], -1)[:, 0]
                      )[valid]


def test_paged_decode_matches_reference(served):
    w, outs = served
    ref = Reference(CONF)
    for o in outs:
        np.testing.assert_allclose(o.logprobs, _ref_logp(ref, w, o),
                                   atol=LOGP_ATOL)
        gap = check.served(ref, w, o.prompt, o.tokens, o.logprobs, 40)
        assert gap.max() < LOGP_ATOL


def test_bf16_reference_fails_the_decode_comparison(served):
    w, outs = served
    low = Reference(CONF, quant="bf16")
    worst = max(np.abs(np.asarray(o.logprobs) - _ref_logp(low, w, o)).max()
                for o in outs)
    assert worst > 10 * LOGP_ATOL


def _grpo_batch(seed=0, B=4, P=6, T=5, group=2):
    """Rows of prompt + response tokens, shifted by one, with the loss
    mask and GRPO's group-normalised advantages over the response."""
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CONF["vocab_size"], (B, P + T), dtype=np.int32)
    mask = (rng.random((B, T)) > 0.2).astype(np.float32)
    r = rng.random(B).astype(np.float32).reshape(-1, group)
    adv = ((r - r.mean(1, keepdims=True))
           / (r.std(1, keepdims=True) + 1e-4)).reshape(-1, 1)
    zeros = np.zeros((B, P - 1), np.float32)
    blogp = -6.0 + 0.05 * rng.standard_normal((B, P + T - 1))
    return {"tokens": jnp.asarray(tok[:, :-1]),
            "labels": jnp.asarray(tok[:, 1:]),
            "advantages": jnp.asarray(np.concatenate(
                [zeros, np.broadcast_to(adv, mask.shape)], 1)),
            "loss_mask": jnp.asarray(np.concatenate([zeros, mask], 1)),
            "behavior_logp": jnp.asarray(blogp.astype(np.float32))}


def _worst_leaf_gap(grads, r_grads) -> float:
    """Worst leaf's |norm - reference norm| over the reference's norm."""
    return max(abs(float(jnp.linalg.norm(g)) - float(jnp.linalg.norm(r)))
               / float(jnp.linalg.norm(r))
               for g, r in zip(jax.tree.leaves(grads),
                               jax.tree.leaves(r_grads)))


@pytest.mark.parametrize("quant,agree", [(None, True), ("bf16", False)])
def test_grpo_loss_and_gradient_match_reference(quant, agree):
    from repro.rl.train_step import make_loss_fn
    model = bm.build(FAMILY, CONF)
    w = weights.make(FAMILY, CONF, 7)
    batch = _grpo_batch()
    (loss, _), grads = jax.value_and_grad(
        make_loss_fn(model, remat=False), has_aux=True)(w, batch)
    ref = Reference(CONF, quant=quant)
    r_loss, r_grads = ref.loss_and_grad(
        w, batch["tokens"], batch["labels"], batch["behavior_logp"],
        batch["advantages"], 0.2, batch["loss_mask"])
    gap = _worst_leaf_gap(grads, r_grads)
    if agree:
        assert abs(float(loss) - float(r_loss)) < 1e-5
        assert gap < GRAD_RTOL
    else:
        assert gap > GRAD_RTOL
