"""Plain reference of the benchmark's dense decoder, in float32 JAX.

It imports nothing of the program under test and takes nothing the program
made.  It reads the sizes from the configuration file, makes the same
random weights from the same seed by the recipe the configuration's file
states (``init``), and computes in float32 with every matrix product at
``Precision.HIGHEST``.  It follows the model as the configuration runs it,
departures included (see the configuration file's ``departures``):

* pre-norm blocks; RMSNorm as ``x * rsqrt(mean(x^2) + eps) * (1 + w)``
  with ``w`` starting at zero;
* grouped-query attention with rotary embeddings on the two halves of each
  head (``theta`` as run), optional RMSNorm of each query and key head,
  causal softmax;
* a SwiGLU feed-forward;
* no bias on the query, key and value projections;
* an output head that starts as a copy of the embedding table and is a
  parameter of its own from then on.

``matmul="fp8"`` is the control: every matrix product's operands are
rounded to float8 (e4m3, one scale per tensor) on the way in, with the
gradient passed straight through.  ``half_batch=True`` plants the fault
"half of the batch left out": the second half of the rows carries no
labels, so the loss is the mean over the first half.

Training memory on one 16 GB chip: parameters, gradients and the two Adam
moments in float32 (four copies of the model) plus one row's activations
at a time; the loss over the vocabulary is taken in chunks of rows.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


class Sizes:
    """The sizes the reference needs, from a configuration file."""

    def __init__(self, config: dict) -> None:
        self.d = config["hidden_size"]
        self.f = config["intermediate_size"]
        self.layers = config["num_hidden_layers"]
        self.heads = config["num_attention_heads"]
        self.kv_heads = config["num_key_value_heads"]
        self.head_dim = config.get("head_dim", self.d // self.heads)
        self.vocab = config["vocab_size"]
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.tie = bool(config["tie_word_embeddings"])
        self.qk_norm = bool(config.get("qk_norm", False))
        self.init = config["init"]


# -- weights -----------------------------------------------------------------

def _trunc(key, shape, std):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32)


def _fan_in(key, shape):
    return _trunc(key, shape, 1.0 / math.sqrt(shape[-2]))


@functools.partial(jax.jit, static_argnums=(0,))
def _init(sz: Sizes, seed):
    """The recipe of ``init`` = ``"split_layers_plus_2"``: the seed's key
    is split into ``layers + 2``; the first makes the embedding (std
    ``embed_std``, cut at 2 std), the next ``layers`` each make one block
    (split into 16, taken in order q, k, v, o, gate, up, down; each
    ``1/sqrt(fan_in)``, cut at 2 std), the last an untied head.  Norm
    weights start at zero."""
    d, f, hd = sz.d, sz.f, sz.head_dim
    keys = jax.random.split(jax.random.PRNGKey(seed), sz.layers + 2)
    embed = _trunc(keys[0], (sz.vocab, d), sz.init["embed_std"])

    def block(key):
        ks = jax.random.split(key, 16)
        p = {"norm_mixer": jnp.zeros((d,), jnp.float32),
             "attn.w_q": _fan_in(ks[0], (d, sz.heads * hd)),
             "attn.w_k": _fan_in(ks[1], (d, sz.kv_heads * hd)),
             "attn.w_v": _fan_in(ks[2], (d, sz.kv_heads * hd)),
             "attn.w_o": _fan_in(ks[3], (sz.heads * hd, d)),
             "norm_ffn": jnp.zeros((d,), jnp.float32),
             "ffn.w_gate": _fan_in(ks[4], (d, f)),
             "ffn.w_up": _fan_in(ks[5], (d, f)),
             "ffn.w_down": _fan_in(ks[6], (f, d))}
        if sz.qk_norm:
            p["attn.q_norm"] = jnp.zeros((hd,), jnp.float32)
            p["attn.k_norm"] = jnp.zeros((hd,), jnp.float32)
        return p

    blocks = jax.vmap(block)(keys[1:1 + sz.layers])
    head = embed.T if sz.tie else _fan_in(keys[-1], (d, sz.vocab))
    return {"embed": embed, "blocks": blocks,
            "final_norm": jnp.zeros((d,), jnp.float32), "head": head}


def init_weights(sz: Sizes, seed: int) -> dict:
    if sz.init["recipe"] != "split_layers_plus_2":
        raise ValueError(f"unknown init recipe {sz.init['recipe']!r}")
    return _init(sz, seed)


def leaf_names(sz: Sizes, params: dict) -> list[tuple[str, tuple]]:
    """(program leaf name, index into ``params``) of every leaf."""
    out = [("embed/embed", ("embed",))]
    for i in range(sz.layers):
        for key in sorted(params["blocks"]):
            out.append((f"block_{i:03d}/{key}", ("blocks", key, i)))
    out += [("head/final_norm", ("final_norm",)), ("head/head", ("head",))]
    return out


@jax.jit
def _sq_top(tree):
    return jax.tree.map(lambda x: jnp.sum(jnp.square(x)), tree)


@jax.jit
def _sq_per_layer(blocks):
    return jax.tree.map(
        lambda x: jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))),
        blocks)


def leaf_norms(sz: Sizes, params: dict) -> dict[str, float]:
    """Frobenius norm of every leaf, under the program's leaf names."""
    top = jax.device_get(_sq_top({k: v for k, v in params.items()
                                  if k != "blocks"}))
    per_layer = jax.device_get(_sq_per_layer(params["blocks"]))
    out = {}
    for name, idx in leaf_names(sz, params):
        if idx[0] == "blocks":
            out[name] = math.sqrt(float(per_layer[idx[1]][idx[2]]))
        else:
            out[name] = math.sqrt(float(top[idx[0]]))
    return out


# -- the model ---------------------------------------------------------------

@jax.custom_vjp
def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _round_fwd(x):
    return _round_fp8(x), None


def _round_bwd(_res, g):
    return (g,)


_round_fp8.defvjp(_round_fwd, _round_bwd)


def _mm(matmul: str):
    if matmul == "fp32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if matmul == "fp8":
        return lambda spec, a, b: jnp.einsum(
            spec, _round_fp8(a), _round_fp8(b), precision=HIGHEST)
    raise ValueError(f"unknown matmul precision {matmul!r}")


def _rms(x, w, eps):
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return x * scale * (1.0 + w)


def _rope(x, theta):
    """x: (B, S, H, D); rotates the two halves of each head."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(sz: Sizes, mm, p, h):
    b, s, _ = h.shape
    hd, rep = sz.head_dim, sz.heads // sz.kv_heads
    x = _rms(h, p["norm_mixer"], sz.eps)
    q = mm("bsd,dk->bsk", x, p["attn.w_q"]).reshape(b, s, sz.heads, hd)
    k = mm("bsd,dk->bsk", x, p["attn.w_k"]).reshape(b, s, sz.kv_heads, hd)
    v = mm("bsd,dk->bsk", x, p["attn.w_v"]).reshape(b, s, sz.kv_heads, hd)
    if sz.qk_norm:
        q = _rms(q, p["attn.q_norm"], sz.eps)
        k = _rms(k, p["attn.k_norm"], sz.eps)
    q, k = _rope(q, sz.theta), _rope(k, sz.theta)
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    att = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, s, sz.heads * hd)
    h = h + mm("bsk,kd->bsd", att, p["attn.w_o"])
    x = _rms(h, p["norm_ffn"], sz.eps)
    gate = mm("bsd,df->bsf", x, p["ffn.w_gate"])
    up = mm("bsd,df->bsf", x, p["ffn.w_up"])
    return h + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["ffn.w_down"])


def _hidden(sz: Sizes, mm, params, tokens):
    """Final-normed hidden states of a (B, S) batch."""
    h = jnp.take(params["embed"], tokens, axis=0)

    def layer(h, p):
        return jax.checkpoint(functools.partial(_block, sz, mm))(p, h), None

    h, _ = jax.lax.scan(layer, h, params["blocks"])
    return _rms(h, params["final_norm"], sz.eps)


def _nll_sum(mm, h, head, labels, chunk):
    """Summed cross entropy over the vocabulary of rows ``h`` (N, d) with
    ``labels`` (N,), in chunks of ``chunk`` rows; labels < 0 count 0."""
    n, d = h.shape
    chunk = math.gcd(n, chunk)
    hs = h.reshape(n // chunk, chunk, d)
    ls = labels.reshape(n // chunk, chunk)

    @jax.checkpoint
    def part(hc, lc):
        logits = mm("nd,dv->nv", hc, head)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[:, None],
                                   axis=-1)[:, 0]
        return jnp.sum((logz - gold) * (lc >= 0))

    def body(total, xs):
        return total + part(*xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    return total


def _loss(sz: Sizes, mm, chunk, params, tokens, labels):
    """Mean cross entropy over the labelled tokens, one row at a time."""

    @jax.checkpoint
    def row(tok, lab):
        h = _hidden(sz, mm, params, tok[None])[0]
        return _nll_sum(mm, h, params["head"], lab, chunk)

    def body(total, xs):
        return total + row(*xs), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (tokens, labels))
    return total / jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _loss_and_grads(sz, matmul, chunk, params, tokens, labels):
    return jax.value_and_grad(functools.partial(_loss, sz, _mm(matmul),
                                                chunk))(params, tokens,
                                                        labels)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(params, m, v, grads, step, hyper):
    lr, b1, b2, eps = hyper

    def one(p, m, v, g):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        denom = jnp.sqrt(v / (1.0 - b2 ** step)) + eps
        return p - lr * ((m / (1.0 - b1 ** step)) / denom), m, v

    out = jax.tree.map(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def train(sz: Sizes, seed: int, batches, adam: dict, *,
          matmul: str = "fp32", half_batch: bool = False,
          loss_chunk: int = 512) -> dict:
    """Adam training from the seed's weights over ``batches`` (a list of
    (tokens, labels)), one step per batch.  Returns each step's loss, the
    norm of every leaf's first gradient, and the norm of every leaf's
    change over all the steps."""
    params = init_weights(sz, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    hyper = tuple(jnp.float32(adam[k]) for k in ("lr", "beta1", "beta2",
                                                 "eps"))
    losses, grad1 = [], None
    for step, (tokens, labels) in enumerate(batches, 1):
        labels = np.array(labels)
        if half_batch:
            labels[labels.shape[0] // 2:] = -100
        loss, grads = _loss_and_grads(sz, matmul, loss_chunk, params,
                                      jnp.asarray(tokens),
                                      jnp.asarray(labels))
        losses.append(float(loss))
        if grad1 is None:
            grad1 = leaf_norms(sz, grads)
        params, m, v = _adam(params, m, v, grads, jnp.float32(step), hyper)
        del grads
    del m, v
    start = init_weights(sz, seed)
    delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        params, start)
    del start, params
    return {"losses": losses, "grad1_norms": grad1,
            "update_norms": leaf_norms(sz, delta)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits_at(sz, matmul, params, tokens, positions):
    mm = _mm(matmul)
    h = _hidden(sz, mm, params, tokens)
    rows = jnp.take_along_axis(h, positions[:, :, None], axis=1)
    return mm("bkd,dv->bkv", rows, params["head"])


def logits_at(sz: Sizes, params: dict, tokens: np.ndarray,
              positions: np.ndarray, *, matmul: str = "fp32") -> np.ndarray:
    """Next-token logits (B, K, vocab) after ``positions`` (B, K) of the
    full forward pass over ``tokens`` (B, T)."""
    return np.asarray(_logits_at(sz, matmul, params, jnp.asarray(tokens),
                                 jnp.asarray(positions)))
