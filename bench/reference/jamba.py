"""Plain reference of the LLM behind the cache, in `jax.numpy` at float32.

It follows HF's Jamba (``modeling_jamba.py``, the slow path) for the
sizes of a configuration file (`harness.jamba_weights.jamba_sizes`):
token table; per layer a pre-RMSNorm mixer and a pre-RMSNorm gated SiLU
MLP, each added to the residual; layer i is attention iff
i % period == offset, else a Mamba-1 mixer; a final RMSNorm and the
tied table as the output head.

* attention: grouped-query, causal, no positional encoding, no bias;
* Mamba: in_proj to x and gate z; a causal depthwise conv of width
  d_conv with bias, SiLU; x_proj to dt, B and C, each RMSNormed (Jamba's
  dt/b/c_layernorm); dt = softplus(dt_proj(dt)); A = -exp(A_log); a
  sequential selective scan h_t = exp(dt A) h_{t-1} + dt B_t x_t,
  y_t = C_t h_t + D x_t; y * SiLU(z); out_proj.

Rows are right-padded to one length: the model is causal, so a row's
logits at its real positions are those it gets alone.  Weights stay in
the dtype they come in (bfloat16 on the chip) and each layer is upcast
to float32 inside its own call, so the reference fits beside nothing
but its input.  Nothing here imports the program.

``matmul`` selects the precision of every matrix product:

* ``"f32"``: float32 operands at ``Precision.HIGHEST`` (the reference);
* ``"fp8"``: operands scaled per tensor to the float8_e4m3fn range and
  rounded to it, products accumulated in float32 (the control: the
  step below the configuration's bfloat16 compute).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference.encoder import _mm


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _mlp(p, x, eps, matmul):
    h = _rms(x, p["norm2"]["scale"], eps)
    f = p["ffn"]
    g = jax.nn.silu(_mm("bsd,df->bsf", h, f["w_gate"], matmul))
    u = _mm("bsd,df->bsf", h, f["w_up"], matmul)
    return x + _mm("bsf,fd->bsd", g * u, f["w_down"], matmul)


@partial(jax.jit, static_argnames=("eps", "matmul"))
def attention_layer(x, p, *, eps: float, matmul: str):
    p = _f32(p)
    a = p["mixer"]
    h = _rms(x, p["norm1"]["scale"], eps)
    q = _mm("bsd,dhk->bshk", h, a["wq"], matmul)
    k = _mm("bsd,dhk->bshk", h, a["wk"], matmul)
    v = _mm("bsd,dhk->bshk", h, a["wv"], matmul)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("bqhk,bshk->bhqs", q, k, matmul) / np.sqrt(q.shape[-1])
    S = x.shape[1]
    causal = np.tril(np.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bhqs,bshk->bqhk", w, v, matmul)
    x = x + _mm("bshk,hkd->bsd", o, a["wo"], matmul)
    return _mlp(p, x, eps, matmul)


@partial(jax.jit, static_argnames=("eps", "matmul"))
def mamba_layer(x, p, *, eps: float, matmul: str):
    p = _f32(p)
    mx = p["mixer"]
    h = _rms(x, p["norm1"]["scale"], eps)
    xz = _mm("bsd,de->bse", h, mx["in_proj"], matmul)
    xs, z = jnp.split(xz, 2, axis=-1)
    K, S = mx["conv_w"].shape[0], x.shape[1]
    xp = jnp.pad(xs, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(mx["conv_w"][k] * xp[:, k:k + S] for k in range(K))
    xc = jax.nn.silu(conv + mx["conv_b"])
    R, N = mx["dt_w"].shape[0], mx["A_log"].shape[1]
    dbc = _mm("bse,ef->bsf", xc, mx["x_proj"], matmul)
    dt, B, C = jnp.split(dbc, [R, R + N], axis=-1)
    dt = _rms(dt, mx["dt_norm"], eps)
    B = _rms(B, mx["B_norm"], eps)
    C = _rms(C, mx["C_norm"], eps)
    dt = jax.nn.softplus(_mm("bsr,re->bse", dt, mx["dt_w"], matmul)
                         + mx["dt_b"])
    A = -jnp.exp(mx["A_log"])                              # (E, N)

    def step(hs, t):
        dt_t, b_t, c_t, x_t = t
        hs = jnp.exp(dt_t[..., None] * A) * hs \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return hs, jnp.einsum("ben,bn->be", hs, c_t,
                              precision=jax.lax.Precision.HIGHEST)

    h0 = jnp.zeros((x.shape[0],) + A.shape, jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(jnp.swapaxes(a, 0, 1)
                                         for a in (dt, B, C, xc)))
    y = jnp.swapaxes(ys, 0, 1) + xc * mx["D"]
    y = y * jax.nn.silu(z)
    x = x + _mm("bse,ed->bsd", y, mx["out_proj"], matmul)
    return _mlp(p, x, eps, matmul)


@partial(jax.jit, static_argnames=("eps", "matmul"))
def head(x, table, final_scale, *, eps: float, matmul: str):
    x = _rms(x, final_scale.astype(jnp.float32), eps)
    return _mm("btd,vd->btv", x, table.astype(jnp.float32), matmul)


def forward(params, ids, m: dict, pick, matmul: str = "f32"):
    """Logits (B, T, vocab) f32 at ``pick`` ((B, T) int positions) of the
    right-padded rows ``ids`` (B, S)."""
    eps = float(m["eps"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(ids)].astype(jnp.float32)
        for i in range(m["layers"]):
            # the tree stacks layer i at position i % stack, copy i // stack
            pos, j = i % m["stack"], i // m["stack"]
            p = jax.tree_util.tree_map(lambda a: a[j],
                                       params["layers"][f"pos{pos}"])
            layer = (attention_layer if i % m["period"] == m["offset"]
                     else mamba_layer)
            x = layer(x, p, eps=eps, matmul=matmul)
        x = jnp.take_along_axis(x, jnp.asarray(pick)[..., None], axis=1)
        return head(x, params["embed"]["table"],
                    params["final_norm"]["scale"], eps=eps, matmul=matmul)


def teacher_forced(params, prompts, answers, m: dict,
                   matmul: str = "f32") -> np.ndarray:
    """Each prompt's token ids (a list of ints, unpadded) followed by its
    answer but the last token, in one right-padded batch; -> logits
    (B, len(answer), vocab) at the prompt's last token and at each answer
    token fed back, the positions a greedy decode reads."""
    n_new = len(answers[0])
    rows = [list(p) + list(a[:-1]) for p, a in zip(prompts, answers)]
    S = max(len(r) for r in rows)
    ids = np.zeros((len(rows), S), np.int32)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
    pick = np.stack([len(p) - 1 + np.arange(n_new) for p in prompts])
    return np.asarray(forward(params, ids, m, pick, matmul))
