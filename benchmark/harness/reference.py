"""The plain reference: Mistral-7B's forward pass in straightforward
``jax.numpy`` and float32, after the published description (RMSNorm, rotary
embedding in the rotate-half convention of the Hugging Face implementation
at the config's ``rope_theta``, grouped-query attention with a causal mask,
SwiGLU). No cache, no kernels, no batching tricks, matmuls at the highest
precision. It imports nothing from ``lzy_tpu.models``: it reads the weights
from the program's parameter tree by name and does its own arithmetic.

Departures from the description, both for memory: weights are upcast one
layer at a time (a float32 copy of the whole model does not fit beside a
serving pool), and attention runs over blocks of queries (the whole score
matrix of a 4096-token sequence for 32 heads is 2 GB)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_QUERY_BLOCK = 1024


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x: [B, T, H, D]; rotate-half: the first and second halves of a head
    are the pairs."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attention(q, k, v, segments):
    """Causal grouped-query attention, [B, T, H, D] x [B, T, KV, D]; queries
    of one segment see keys of the same segment only."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    q = q.reshape(b, t, kv, g, d)
    outs = []
    key_pos = jnp.arange(t)
    for start in range(0, t, _QUERY_BLOCK):
        stop = min(start + _QUERY_BLOCK, t)
        s = jnp.einsum("bqkgd,blkd->bkgql", q[:, start:stop], k) * d ** -0.5
        keep = key_pos[None, :] <= jnp.arange(start, stop)[:, None]
        keep = keep[None, None, None]
        if segments is not None:
            same = segments[:, start:stop, None] == segments[:, None, :]
            keep = keep & same[:, None, None]
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        outs.append(jnp.einsum("bkgql,blkd->bqkgd", p, v))
    return jnp.concatenate(outs, axis=1).reshape(b, t, h * d)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def _layer(x, w, positions, segments, *, theta, eps):
    with jax.default_matmul_precision("highest"):
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        h = _rms_norm(x, w["attn_norm"]["scale"], eps)
        att = w["attn"]
        q = jnp.einsum("bte,ehd->bthd", h, att["q_proj"]["kernel"])
        k = jnp.einsum("bte,ehd->bthd", h, att["k_proj"]["kernel"])
        v = jnp.einsum("bte,ehd->bthd", h, att["v_proj"]["kernel"])
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        o = _attention(q, k, v, segments)
        x = x + o @ att["o_proj"]["kernel"].reshape(o.shape[-1], -1)
        h = _rms_norm(x, w["mlp_norm"]["scale"], eps)
        mlp = w["mlp"]
        gate = jax.nn.silu(h @ mlp["gate_proj"]["kernel"])
        return x + (gate * (h @ mlp["up_proj"]["kernel"])) \
            @ mlp["down_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, norm.astype(jnp.float32), eps)
        return jnp.einsum("bte,ve->btv", x, head.astype(jnp.float32))


def _positions(tokens, segments):
    idx = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    if segments is None:
        return idx
    # positions restart where a document starts
    starts = jnp.concatenate(
        [jnp.ones_like(segments[:, :1], bool),
         segments[:, 1:] != segments[:, :-1]], axis=1)
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)


def features(params, tokens, *, n_layers, theta, eps, segments=None):
    """Hidden states before the final norm, [B, T, E] in float32."""
    positions = _positions(tokens, segments)
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for i in range(n_layers):
        x = _layer(x, params[f"layer_{i}"], positions, segments,
                   theta=theta, eps=eps)
    return x


def logits_at(params, tokens, rows, *, n_layers, theta, eps):
    """Float32 logits of one sequence ``tokens`` [1, T] at positions
    ``rows``: the logits at position i choose token i + 1."""
    x = features(params, tokens, n_layers=n_layers, theta=theta, eps=eps)
    head = params.get("lm_head", params["embed_tokens"])
    return _head(x[:, rows], params["final_norm"]["scale"], head, eps=eps)[0]


@functools.partial(jax.jit, static_argnames=("eps",))
def _nll_sum(x, norm, head, labels, mask, *, eps):
    logits = _head(x, norm, head, eps=eps)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - picked) * mask), jnp.sum(mask)


def loss(params, tokens, segments, *, n_layers, theta, eps):
    """Mean next-token cross-entropy over the positions whose next token
    belongs to the same document (segment 0 is padding and never counts);
    the head runs a row at a time, since float32 logits of a whole batch
    over 32768 words are 4 GB."""
    x = features(params, tokens, n_layers=n_layers, theta=theta, eps=eps,
                 segments=segments)
    head = params.get("lm_head", params["embed_tokens"])
    mask = ((segments[:, 1:] == segments[:, :-1])
            & (segments[:, 1:] != 0)).astype(jnp.float32)
    total = count = 0.0
    for row in range(tokens.shape[0]):
        s, n = _nll_sum(x[row:row + 1, :-1], params["final_norm"]["scale"],
                        head, tokens[row:row + 1, 1:], mask[row:row + 1],
                        eps=eps)
        total, count = total + s, count + n
    return float(total / jnp.maximum(count, 1.0))
