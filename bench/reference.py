"""Plain reference forward for the served models, and its int8 control.

Written from the published architectures, in ``jax.numpy`` and float32
under ``default_matmul_precision('highest')``, importing nothing of the
program:

- qwen3 (``Qwen3ForCausalLM``): pre-norm decoder, GQA, RMSNorm on each
  head of q and k before RoPE, tied input/output embeddings;
- internlm2 (``InternLM2ForCausalLM``): the same without qk-norm and with
  a separate output head.

Both: rotate-half RoPE over the whole head with the config's theta,
softmax attention scaled by head_dim ** -0.5 with a causal mask, SwiGLU
MLP (silu(x Wg) * (x Wu)) Wd, RMSNorm with the config's epsilon, no
biases.

Weights.  The served weights are random, made from the seed by this law,
which the reference follows on its own: ``jax.random.split(PRNGKey(seed),
n)`` gives one key per parameter, in the order of the sorted parameter
names below; each matrix is ``normal(key, shape, float32) * fan_in **
-0.5`` (the embedding ``* hidden_size ** -0.5``) rounded to bfloat16, and
every norm weight is 1.  Matrices are stacked over layers as
``(layers, in, out)``.

The comparison (:meth:`Reference.gaps`): for a prompt and the tokens the
system served after it, the reference runs once over prompt + served
tokens and reads, at each served position, how far the served token's
logit lies below the reference's best.  Greedy serving gives 0 up to
rounding.  The control puts the same forward in the program's place with
every weight matmul in int8 (weights per output channel, activations per
token, symmetric), and reads the gap of the token it puts first.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

ARCHS = {'Qwen3ForCausalLM': {'qk_norm': True},
         'InternLM2ForCausalLM': {'qk_norm': False}}

BUCKET = 512        # sequences pad to a multiple: one compile per bucket


@dataclass(frozen=True)
class Spec:
    d: int
    f: int
    heads: int
    kv_heads: int
    hd: int
    layers: int
    vocab: int
    theta: float
    eps: float
    tied: bool
    qk_norm: bool


def spec_of(hf: dict) -> Spec:
    arch = ARCHS[hf['architectures'][0]]
    d, h = hf['hidden_size'], hf['num_attention_heads']
    return Spec(d=d, f=hf['intermediate_size'], heads=h,
                kv_heads=hf['num_key_value_heads'],
                hd=hf.get('head_dim') or d // h,
                layers=hf['num_hidden_layers'], vocab=hf['vocab_size'],
                theta=float(hf['rope_theta']), eps=float(hf['rms_norm_eps']),
                tied=bool(hf['tie_word_embeddings']),
                qk_norm=arch['qk_norm'])


def param_shapes(s: Spec) -> dict:
    """name → (shape, fan_in or None for a norm weight), names sorted."""
    L, d, f, q, kv = s.layers, s.d, s.f, s.heads * s.hd, s.kv_heads * s.hd
    shapes = {
        'embed': ((s.vocab, d), d),
        'final_norm': ((d,), None),
        'layers/ln1': ((L, d), None),
        'layers/ln2': ((L, d), None),
        'layers/wd': ((L, f, d), f),
        'layers/wg': ((L, d, f), d),
        'layers/wk': ((L, d, kv), d),
        'layers/wo': ((L, q, d), q),
        'layers/wq': ((L, d, q), d),
        'layers/wu': ((L, d, f), d),
        'layers/wv': ((L, d, kv), d),
    }
    if s.qk_norm:
        shapes['layers/k_norm'] = ((L, s.hd), None)
        shapes['layers/q_norm'] = ((L, s.hd), None)
    if not s.tied:
        shapes['unembed'] = ((d, s.vocab), d)
    return dict(sorted(shapes.items()))


def make_weights(s: Spec, seed: int) -> dict:
    """All weights in bfloat16, from the seed, in one jitted call."""
    shapes = param_shapes(s)

    def gen(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for (name, (shape, fan_in)), k in zip(shapes.items(), keys):
            if fan_in is None:
                out[name] = jnp.ones(shape, jnp.bfloat16)
            else:
                scale = (s.d ** -0.5 if name == 'embed' else fan_in ** -0.5)
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * scale).astype(jnp.bfloat16)
        return out
    return jax.jit(gen)(jax.random.PRNGKey(seed))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, hd), positions 0..S-1; rotate-half."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    c, sn = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _mm_f32(x, w):
    return x @ w.astype(jnp.float32)


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _mm_int8(x, w):
    """W8A8: weights per output channel, activations per token."""
    xq, xs = _q8(x, -1)
    wq, ws = _q8(w.astype(jnp.float32), 0)
    acc = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def forward(s: Spec, w: dict, tokens, mm=_mm_f32):
    """Logits (S, vocab) of one sequence, float32."""
    S = tokens.shape[0]
    h = w['embed'][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    groups = s.heads // s.kv_heads
    layer_w = {k.split('/', 1)[1]: v for k, v in w.items()
               if k.startswith('layers/')}

    def layer(h, lw):
        f32 = {k: v.astype(jnp.float32) for k, v in lw.items()
               if v.ndim == 1}
        x = _rms(h, f32['ln1'], s.eps)
        q = mm(x, lw['wq']).reshape(S, s.heads, s.hd)
        k = mm(x, lw['wk']).reshape(S, s.kv_heads, s.hd)
        v = mm(x, lw['wv']).reshape(S, s.kv_heads, s.hd)
        if s.qk_norm:
            q = _rms(q, f32['q_norm'], s.eps)
            k = _rms(k, f32['k_norm'], s.eps)
        q, k = _rope(q, s.theta), _rope(k, s.theta)
        k, v = jnp.repeat(k, groups, 1), jnp.repeat(v, groups, 1)
        sc = jnp.einsum('qhd,khd->hqk', q, k) * s.hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        o = jnp.einsum('hqk,khd->qhd', p, v).reshape(S, s.heads * s.hd)
        h = h + mm(o, lw['wo'])
        x = _rms(h, f32['ln2'], s.eps)
        h = h + mm(jax.nn.silu(mm(x, lw['wg'])) * mm(x, lw['wu']), lw['wd'])
        return h, None

    h, _ = jax.lax.scan(layer, h, layer_w)
    h = _rms(h, w['final_norm'].astype(jnp.float32), s.eps)
    out = w['embed'].T if s.tied else w['unembed']
    return mm(h, out)


@functools.partial(jax.jit, static_argnames=('s', 'control'))
def _score(s, w, tokens, idx, nxt, control):
    """Gaps at rows ``idx`` of the reference logits: the served token
    ``nxt`` and, with ``control``, the int8 forward's first choice."""
    with jax.default_matmul_precision('highest'):
        ref = forward(s, w, tokens)[idx]
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0]
        if not control:
            return gap, gap
        ctl = forward(s, w, tokens, mm=_mm_int8)[idx].argmax(-1)
        return gap, best - jnp.take_along_axis(ref, ctl[:, None], 1)[:, 0]


class Reference:
    """One model's reference from its published config and the seed."""

    def __init__(self, hf: dict, seed: int):
        self.spec = spec_of(hf)
        self.w = make_weights(self.spec, seed)

    def gaps(self, prompt, served, *, control: bool = False):
        """Per served token: how far its logit lies below the reference's
        best (and the control's gap with ``control``), as numpy arrays."""
        seq = list(prompt) + list(served[:-1])
        n, S = len(served), len(seq)
        pad = -(-S // BUCKET) * BUCKET
        tokens = np.zeros(pad, np.int32)
        tokens[:S] = seq
        idx = np.zeros(pad, np.int32)
        idx[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        nxt = np.zeros(pad, np.int32)
        nxt[:n] = served
        gap, ctl = _score(self.spec, self.w, tokens, idx, nxt, control)
        return np.asarray(gap)[:n], np.asarray(ctl)[:n]
