"""A plain float32 InternLM2 (arXiv:2403.17297) in ``jax.numpy``.

Written from the published description: pre-norm decoder layers with
RMSNorm, rotary position embedding (rotate-half form, base
``rope_theta``), grouped-query attention (query head ``h`` reads key/value
head ``h // (heads / kv_heads)``), a SwiGLU MLP (``down(silu(gate(x)) *
up(x))``) and an untied output head.  No kernel, cache or batching trick:
full causal attention over the whole sequence.  Every matrix product runs
at ``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in bfloat16
passes).

It imports nothing of the program.  It reads the benchmark's weight tree
(``bench/weights.py``) through this mapping onto the published tensors:

=====================  ==============================================
tree leaf              published tensor
=====================  ==============================================
``embed`` (V, d)       ``tok_embeddings.weight``
``lm_head`` (d, V)     ``output.weight`` transposed
``final_norm`` (d,)    ``norm.weight`` minus 1
``layers.ln1`` [l]     ``layers.l.attention_norm.weight`` minus 1
``layers.ln2`` [l]     ``layers.l.ffn_norm.weight`` minus 1
``attn.wq`` [l]        query rows of ``wqkv``, as (d, heads, head_dim)
``attn.wk/wv`` [l]     key/value rows of ``wqkv``, as (d, kv_heads, head_dim)
``attn.wo`` [l]        ``attention.wo.weight`` as (heads, head_dim, d)
``mlp.wi_gate`` [l]    ``feed_forward.w1.weight`` transposed
``mlp.wi_up`` [l]      ``feed_forward.w3.weight`` transposed
``mlp.wo`` [l]         ``feed_forward.w2.weight`` transposed
=====================  ==============================================

Norm gains are stored as offsets from 1 so that a bfloat16 update of a
gain rounds the same quantity in the reference as in a trainer that
stores them so; the forward computes ``x_hat * (1 + offset)``, which is
the published ``x_hat * weight``.

``quant`` is the control: every matrix product takes both operands rounded
to the precision step below the configuration's, ``"fp8"`` (e4m3, scaled
per row of each operand) below bfloat16, ``"bf16"`` below float32.  The
float8 backward passes gradients straight through the rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8_e4m3fn


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fp8(x, axis: int):
    """``x`` rounded to float8 e4m3, scaled so that each slice along
    ``axis`` spans the format's range."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _fp8_fwd(x, axis):
    return _fp8(x, axis), None


def _fp8_bwd(axis, _, g):
    return (g,)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Reference:
    """InternLM2 forward and GRPO loss for one configuration file's
    sizes (``conf``), in float32, or with ``quant`` its matrix products'
    operands rounded to ``"bf16"`` or ``"fp8"``."""

    def __init__(self, conf: dict, quant: str | None = None):
        if quant not in (None, "bf16", "fp8"):
            raise ValueError(f"unknown quant {quant!r}")
        self.conf = conf
        self.quant = quant
        self.d = conf["hidden_size"]
        self.heads = conf["num_attention_heads"]
        self.kv_heads = conf["num_key_value_heads"]
        self.head_dim = self.d // self.heads
        self.eps = conf["rms_norm_eps"]
        self.theta = float(conf["rope_theta"])
        self.layer = jax.jit(self._layer)
        self.head = jax.jit(self._head)

    # ---- pieces ----------------------------------------------------------
    def _mm(self, spec: str, a, b, a_axis: int, b_axis: int):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.quant == "fp8":
            a, b = _fp8(a, a_axis), _fp8(b, b_axis)
        elif self.quant == "bf16":
            a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    def _norm(self, x, offset):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + self.eps)
        return x * (1.0 + offset.astype(jnp.float32))

    def _rope(self, x):
        """x: (B, S, h, e); positions 0..S-1, rotate-half form."""
        S, e = x.shape[1], x.shape[-1]
        inv = 1.0 / self.theta ** (jnp.arange(0, e, 2, dtype=jnp.float32) / e)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
        cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None]
        sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None]
        x1, x2 = x[..., : e // 2], x[..., e // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    def _layer(self, x, lw):
        """One decoder layer. x: (B, S, d) float32; lw: this layer's leaves."""
        B, S, d = x.shape
        H, K, e = self.heads, self.kv_heads, self.head_dim
        a, m = lw["attn"], lw["mlp"]
        h = self._norm(x, lw["ln1"])
        q = self._mm("bsd,dn->bsn", h, a["wq"].reshape(d, H * e), 2, 0)
        k = self._mm("bsd,dn->bsn", h, a["wk"].reshape(d, K * e), 2, 0)
        v = self._mm("bsd,dn->bsn", h, a["wv"].reshape(d, K * e), 2, 0)
        q = self._rope(q.reshape(B, S, H, e))
        k = self._rope(k.reshape(B, S, K, e))
        v = v.reshape(B, S, K, e)
        kv_of = jnp.arange(H) // (H // K)               # query head -> kv head
        k, v = k[:, :, kv_of], v[:, :, kv_of]           # (B, S, H, e)
        s = self._mm("bqhe,bkhe->bhqk", q, k, 3, 3) / jnp.sqrt(float(e))
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = self._mm("bhqk,bkhe->bqhe", p, v, 3, 1).reshape(B, S, H * e)
        x = x + self._mm("bsn,nd->bsd", o, a["wo"].reshape(H * e, d), 2, 0)
        h = self._norm(x, lw["ln2"])
        g = self._mm("bsd,df->bsf", h, m["wi_gate"], 2, 0)
        u = self._mm("bsd,df->bsf", h, m["wi_up"], 2, 0)
        f = jax.nn.silu(g) * u
        return x + self._mm("bsf,fd->bsd", f, m["wo"], 2, 0)

    def _head(self, x, final_norm, lm_head):
        """(B, S, d) -> (B, S, V) float32 logits."""
        return self._mm("bsd,dv->bsv", self._norm(x, final_norm), lm_head,
                        2, 0)

    @staticmethod
    def layer_weights(w, l: int):
        return jax.tree.map(lambda a: a[l], w["layers"])

    def num_layers(self, w) -> int:
        return w["layers"]["ln1"].shape[0]

    # ---- serving ---------------------------------------------------------
    def logits(self, w, tokens):
        """(B, S) int tokens -> (B, S, V) float32 logits; one jitted call per
        layer, so only one layer's weights are upcast at a time."""
        x = jnp.take(w["embed"], jnp.asarray(tokens), axis=0
                     ).astype(jnp.float32)
        for l in range(self.num_layers(w)):
            x = self.layer(x, self.layer_weights(w, l))
        return self.head(x, w["final_norm"], w["lm_head"])

    # ---- training --------------------------------------------------------
    def token_logp(self, w, tokens, labels):
        """log p(label) at every position; differentiable in ``w``."""
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)

        def body(x, lw):
            return jax.checkpoint(self._layer)(x, lw), None

        x, _ = jax.lax.scan(body, x, w["layers"])

        def row_logp(args):
            xr, lr = args
            lg = self._head(xr[None], w["final_norm"], w["lm_head"])[0]
            return (jnp.take_along_axis(lg, lr[:, None], -1)[:, 0]
                    - jax.nn.logsumexp(lg, -1))

        return jax.lax.map(jax.checkpoint(row_logp), (x, labels))

    @staticmethod
    def grpo_loss(logp, behavior_logp, advantages, mask, clip_eps: float):
        """The clipped-ratio policy-gradient loss (GRPO), a mean over the
        masked tokens."""
        ratio = jnp.exp(logp - behavior_logp)
        clipped = jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps)
        pg = jnp.minimum(ratio * advantages, clipped * advantages)
        return -(pg * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    @functools.partial(jax.jit, static_argnums=(0, 6))
    def loss_and_grad(self, w32, tokens, labels, behavior_logp, advantages,
                      clip_eps, mask):
        def f(w):
            return self.grpo_loss(self.token_logp(w, tokens, labels),
                                  behavior_logp, advantages, mask, clip_eps)
        return jax.value_and_grad(f)(w32)

