"""What the served architectures share, in ``jax.numpy`` and float32,
importing nothing of the program: the pieces of a pre-norm decoder, the
weights law, the padded comparison, and the dense decoder built from them
with its counts.

The dense decoder: pre-norm, GQA, optionally RMSNorm on each head of q and
k before RoPE, rotate-half RoPE over the whole head with the config's
theta, softmax attention scaled by head_dim ** -0.5 with a causal mask,
SwiGLU MLP (silu(x Wg) * (x Wu)) Wd, RMSNorm with the config's epsilon, no
biases, the output head tied to the embedding where the config says so.

Weights.  The served weights are random, made from the seed by this law,
which the reference follows on its own: ``jax.random.split(PRNGKey(seed),
n)`` gives one key per parameter, in the order of the sorted parameter
names; each matrix is ``normal(key, shape, float32) * fan_in ** -0.5``
(the embedding ``* hidden_size ** -0.5``) rounded to bfloat16, and every
norm weight is 1.  Matrices are stacked over layers as ``(layers, in,
out)``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

BUCKET = 512        # sequences pad to a multiple: one compile per bucket
BF16 = 2            # bytes of a served weight, activation or KV entry


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


def spec_of(hf: dict, qk_norm: bool = False) -> Spec:
    """The dense decoder's sizes from Hugging Face keys; ``qk_norm`` is the
    architecture's (the counts do not depend on it)."""
    d, h = hf['hidden_size'], hf['num_attention_heads']
    return Spec(d=d, f=hf['intermediate_size'], heads=h,
                kv_heads=hf['num_key_value_heads'],
                hd=hf.get('head_dim') or d // h,
                layers=hf['num_hidden_layers'], vocab=hf['vocab_size'],
                theta=float(hf['rope_theta']), eps=float(hf['rms_norm_eps']),
                tied=bool(hf['tie_word_embeddings']),
                qk_norm=qk_norm)


def program_config(hf: dict, page_size: int, *, qk_norm: bool) -> dict:
    """The program's ``ModelConfig`` arguments for a dense decoder."""
    s = spec_of(hf, qk_norm)
    return dict(family='dense', n_layers=s.layers, d_model=s.d,
                n_heads=s.heads, n_kv_heads=s.kv_heads, d_ff=s.f,
                vocab_size=s.vocab, head_dim=s.hd, qk_norm=s.qk_norm,
                rope_theta=s.theta, norm_eps=s.eps, tie_embeddings=s.tied,
                page_size=page_size)


# ------------------------------------------------------------ weights law

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


def make_weights(shapes: dict, d: int, seed: int, mesh=None) -> dict:
    """All weights of ``shapes`` (as :func:`param_shapes` gives them) in
    bfloat16, from the seed by the law above, in one jitted call; ``d`` is
    the hidden size that scales the embedding.  Over a ``mesh`` each
    matrix's last axis is split over all its devices where it divides;
    the rest, norm weights included, is replicated.  The bits are the
    law's either way (jax's random bits do not depend on the sharding)."""

    def gen(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for (name, (shape, fan_in)), k in zip(shapes.items(), keys):
            if fan_in is None:
                out[name] = jnp.ones(shape, jnp.bfloat16)
            else:
                scale = (d ** -0.5 if name == 'embed' else fan_in ** -0.5)
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * scale).astype(jnp.bfloat16)
        return out
    laid = {} if mesh is None else {'out_shardings': shardings(shapes, mesh)}
    return jax.jit(gen, **laid)(jax.random.PRNGKey(seed))


def shardings(shapes: dict, mesh) -> dict:
    """name → ``NamedSharding``: a matrix's last axis over every axis of
    ``mesh`` where the mesh's size divides it, else replicated."""
    out = {}
    for name, (shape, fan_in) in shapes.items():
        split = fan_in is not None and shape[-1] % mesh.size == 0
        spec = (P(*[None] * (len(shape) - 1), mesh.axis_names) if split
                else P())
        out[name] = NamedSharding(mesh, spec)
    return out


# ----------------------------------------------------------------- pieces

def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, hd), positions 0..S-1; rotate-half."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    c, sn = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def mm_f32(x, w):
    return x @ w.astype(jnp.float32)


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def mm_int8(x, w):
    """W8A8: weights per output channel, activations per token."""
    xq, xs = _q8(x, -1)
    wq, ws = _q8(w.astype(jnp.float32), 0)
    acc = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def attention(s, lw, x, mask, mm, qk_norm=None):
    """GQA self-attention of one layer over ``x`` (S, d), through its output
    projection: ``lw`` holds ``wq``, ``wk``, ``wv``, ``wo``; ``qk_norm``,
    where given, the float32 RMSNorm weights of each head of q and of k;
    ``mask`` (S, S) says which keys each query sees."""
    S = x.shape[0]
    q = mm(x, lw['wq']).reshape(S, s.heads, s.hd)
    k = mm(x, lw['wk']).reshape(S, s.kv_heads, s.hd)
    v = mm(x, lw['wv']).reshape(S, s.kv_heads, s.hd)
    if qk_norm is not None:
        q = rms(q, qk_norm[0], s.eps)
        k = rms(k, qk_norm[1], s.eps)
    q, k = rope(q, s.theta), rope(k, s.theta)
    groups = s.heads // s.kv_heads
    k, v = jnp.repeat(k, groups, 1), jnp.repeat(v, groups, 1)
    sc = jnp.einsum('qhd,khd->hqk', q, k) * s.hd ** -0.5
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
    o = jnp.einsum('hqk,khd->qhd', p, v).reshape(S, s.heads * s.hd)
    return mm(o, lw['wo'])


def swiglu(lw, x, mm):
    """(silu(x Wg) * (x Wu)) Wd."""
    return mm(jax.nn.silu(mm(x, lw['wg'])) * mm(x, lw['wu']), lw['wd'])


def forward(s: Spec, w: dict, tokens, mm=mm_f32):
    """The dense decoder's logits (S, vocab) of one sequence, float32."""
    S = tokens.shape[0]
    h = w['embed'][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    layer_w = {k.split('/', 1)[1]: v for k, v in w.items()
               if k.startswith('layers/')}

    def layer(h, lw):
        f32 = {k: v.astype(jnp.float32) for k, v in lw.items()
               if v.ndim == 1}
        x = rms(h, f32['ln1'], s.eps)
        qk = (f32['q_norm'], f32['k_norm']) if s.qk_norm else None
        h = h + attention(s, lw, x, causal, mm, qk)
        x = rms(h, f32['ln2'], s.eps)
        h = h + swiglu(lw, x, mm)
        return h, None

    h, _ = jax.lax.scan(layer, h, layer_w)
    h = rms(h, w['final_norm'].astype(jnp.float32), s.eps)
    out = w['embed'].T if s.tied else w['unembed']
    return mm(h, out)


# ------------------------------------------------------------- comparison

@functools.partial(jax.jit, static_argnames=('forward', 's', 'control'))
def _score(forward, s, w, tokens, idx, nxt, control):
    """Gaps at rows ``idx`` of the reference logits: the served token
    ``nxt`` and, with ``control``, the int8 forward's first choice."""
    with jax.default_matmul_precision('highest'):
        ref = forward(s, w, tokens)[idx]
        best = ref.max(-1)
        gap = best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0]
        if not control:
            return gap, gap
        ctl = forward(s, w, tokens, mm=mm_int8)[idx].argmax(-1)
        return gap, best - jnp.take_along_axis(ref, ctl[:, None], 1)[:, 0]


def gaps(forward, s, w, prompt, served, *, control: bool = False):
    """Per served token: how far its logit lies below the best of
    ``forward(s, w, tokens, mm)`` over prompt + served tokens (and the
    control's gap with ``control``), as numpy arrays.  ``forward`` and
    ``s`` are hashable: one compile per architecture, size and bucket."""
    seq = list(prompt) + list(served[:-1])
    n, S = len(served), len(seq)
    pad = -(-S // BUCKET) * BUCKET
    tokens = np.zeros(pad, np.int32)
    tokens[:S] = seq
    idx = np.zeros(pad, np.int32)
    idx[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    nxt = np.zeros(pad, np.int32)
    nxt[:n] = served
    gap, ctl = _score(forward, s, w, tokens, idx, nxt, control)
    return np.asarray(gap)[:n], np.asarray(ctl)[:n]


class Reference:
    """A dense decoder's reference from its published config and the
    seed, its weights laid over ``mesh`` where one is given (the forward
    then runs sharded; without one, on the default device)."""

    def __init__(self, hf: dict, seed: int, mesh=None, *, qk_norm: bool):
        self.spec = spec_of(hf, qk_norm)
        self.w = make_weights(param_shapes(self.spec), self.spec.d, seed,
                              mesh)

    def gaps(self, prompt, served, *, control: bool = False):
        return gaps(forward, self.spec, self.w, prompt, served,
                    control=control)


# ----------------------------------------------------------------- counts

def _causal(m: int, window) -> int:
    """Keys the queries at positions 0..m-1 see: each those up to its
    own, at most ``window`` of them where one is given."""
    if window is None or m <= window:
        return m * (m + 1) // 2
    return window * (window + 1) // 2 + (m - window) * window


def attended_keys(prefill, decode_live, window=None) -> int:
    """Keys the queries of one step attend to in one layer: a prefill row
    ``(start, n)`` causally to its context, a decode row to its live
    tokens (its context, the new token included); with a ``window``, each
    query to at most that many."""
    keys = sum(_causal(start + n, window) - _causal(start, window)
               for start, n in prefill)
    return keys + sum(n if window is None else min(n, window)
                      for n in decode_live)


def paged_call(s: Spec, live) -> tuple:
    """(flops, bytes) of one layer's paged-decode attention call over real
    rows that attend to ``live`` tokens each: q in, out back, and the K
    and V of those tokens (not the pages the kernel walks)."""
    rows = len(live)
    flops = 4 * s.heads * s.hd * sum(live)
    qo = 2 * rows * s.heads * s.hd * BF16
    kv = 2 * sum(live) * s.kv_heads * s.hd * BF16
    return flops, qo + kv


def body_params(hf: dict) -> int:
    """Matrix parameters of every layer (norm weights left out)."""
    s = spec_of(hf)
    q, kv = s.heads * s.hd, s.kv_heads * s.hd
    return s.layers * (s.d * (q + 2 * kv) + q * s.d + 3 * s.d * s.f)


def unembed_flops(hf: dict) -> int:
    s = spec_of(hf)
    return 2 * s.d * s.vocab


def attn_flops(hf: dict, prefill, decode_live) -> int:
    """QK^T and PV of one step: every layer attends to every key."""
    s = spec_of(hf)
    return 4 * s.heads * s.hd * s.layers * attended_keys(prefill,
                                                         decode_live)


def paged_decode_calls(hf: dict, live) -> list:
    """Every layer's decode calls the paged decode kernel over the whole
    live context."""
    s = spec_of(hf)
    return [paged_call(s, live)] * s.layers
