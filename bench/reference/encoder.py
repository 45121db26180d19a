"""Plain reference of the cache's embedder, in `jax.numpy` at float32.

It follows the encoder as the system under test builds it from the
ModernBERT-base sizes (`bench/configs/chat-modernbert149m.json` lists
where that differs from the published model): token table, then per
layer a pre-norm bidirectional multi-head attention with rotary
positions and a pre-norm GeGLU feed-forward (tanh GELU), a final
LayerNorm, a mean over the real tokens and an L2 norm.  Every position
of the padded row attends to every other, padding included, because
the program's encoder passes no attention mask; only the mean pool
skips padding.

Nothing here imports the program.  The tokenizer is a copy of its
hash vocabulary (words to FNV-1a ids), so the reference reads the same
token ids from the same text.

``matmul`` selects the precision of every matrix product:

* ``"f32"``: float32 operands at ``Precision.HIGHEST`` (the reference);
* ``"fp8"``: operands scaled per tensor to the float8_e4m3fn range and
  rounded to it, products accumulated in float32 (the control: the
  step below the configuration's bfloat16 compute).
"""
from __future__ import annotations

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD, BOS, EOS, RESERVED = 0, 1, 2, 4
_WORD_RE = re.compile(r"[a-z0-9]+|[^\sa-z0-9]", re.IGNORECASE)
FP8_MAX = 448.0


def _fnv1a(word: str) -> int:
    h = 0xCBF29CE484222325
    for b in word.encode("utf-8"):
        h ^= b
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def n_tokens(text: str, max_len: int) -> int:
    """Real (unpadded) tokens of one text, BOS and EOS included."""
    return min(len(_WORD_RE.findall(text)) + 2, max_len)


def tokenize(texts, vocab: int, max_len: int):
    """-> (ids (B, max_len) int32, mask (B, max_len) bool)."""
    ids = np.zeros((len(texts), max_len), np.int32)
    mask = np.zeros((len(texts), max_len), bool)
    for i, t in enumerate(texts):
        toks = [RESERVED + _fnv1a(w.lower()) % (vocab - RESERVED)
                for w in _WORD_RE.findall(t)]
        toks = [BOS] + toks[:max_len - 2] + [EOS]
        ids[i, :len(toks)] = toks
        mask[i, :len(toks)] = True
    return ids, mask


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, matmul):
    if matmul == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _layernorm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("eps", "theta", "matmul"))
def encode(params, ids, mask, *, eps: float, theta: float,
           matmul: str = "f32"):
    """(B, S) ids and mask -> (B, D) unit-norm f32 embeddings."""
    x = params["embed"]["table"][ids].astype(jnp.float32)
    lp = params["layers"]["pos0"]

    def layer(x, p):
        h = _layernorm(x, p["norm1"], eps)
        att = p["mixer"]
        q = _rope(_mm("bsd,dhk->bshk", h, att["wq"], matmul), theta)
        k = _rope(_mm("bsd,dhk->bshk", h, att["wk"], matmul), theta)
        v = _mm("bsd,dhk->bshk", h, att["wv"], matmul)
        s = _mm("bqhk,bshk->bhqs", q, k, matmul) / np.sqrt(q.shape[-1])
        w = jax.nn.softmax(s, axis=-1)
        o = _mm("bhqs,bshk->bqhk", w, v, matmul)
        x = x + _mm("bshk,hkd->bsd", o, att["wo"], matmul)
        h = _layernorm(x, p["norm2"], eps)
        f = p["ffn"]
        g = jax.nn.gelu(_mm("bsd,df->bsf", h, f["w_gate"], matmul),
                        approximate=True)
        u = _mm("bsd,df->bsf", h, f["w_up"], matmul)
        return x + _mm("bsf,fd->bsd", g * u, f["w_down"], matmul), None

    x, _ = jax.lax.scan(layer, x, lp)
    x = _layernorm(x, params["final_norm"], eps)
    m = mask.astype(jnp.float32)[..., None]
    e = jnp.sum(x * m, 1) / jnp.maximum(jnp.sum(m, 1), 1.0)
    return e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True), 1e-9)


def embed_texts(params, texts, m: dict, matmul: str = "f32",
                block: int = 64) -> np.ndarray:
    """Embed texts in blocks of ``block`` rows (padded to the block, so
    one program serves every block)."""
    ids, mask = tokenize(texts, m["vocab_size"], m["max_tokens"])
    out = []
    for i in range(0, len(texts), block):
        bi, bm = ids[i:i + block], mask[i:i + block]
        n = len(bi)
        if n < block:
            bi = np.concatenate([bi, np.zeros((block - n,) + bi.shape[1:],
                                              bi.dtype)])
            bm = np.concatenate([bm, np.zeros((block - n,) + bm.shape[1:],
                                              bm.dtype)])
        e = encode(params, bi, bm, eps=float(m["norm_eps"]),
                   theta=float(m["rope_theta"]), matmul=matmul)
        out.append(np.asarray(e)[:n])
    return np.concatenate(out) if out else np.zeros((0, m["hidden_size"]),
                                                    np.float32)
