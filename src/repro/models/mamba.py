"""Mamba (selective SSM) mixer: a chunked associative scan for training
and encoders, a per-token recurrence for serving prefill, O(1)-state
decode.

TPU adaptation (DESIGN.md §3): the CUDA selective-scan kernel is replaced
by two XLA forms of the recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t,
y_t = C_t h_t, which take the same front (in_proj, causal conv, dt/B/C)
and back (D skip, gate, out_proj):

- ``apply_full`` (training, encoders, ``lm_loss``/``forward_lm``): an
  outer ``lax.scan`` over sequence chunks carrying h, with a
  ``lax.associative_scan`` inside each chunk.  It is differentiable at
  log depth over long sequences; its working set is O(chunk · d_inner ·
  d_state), the chunk size the knob the §Perf loop can turn.
- ``apply_prefill`` (serving): a ``lax.scan`` over the tokens that
  carries only h (B, d_inner, d_state) and stacks y_t (B, d_inner), so no
  (B, L, d_inner, d_state) value exists.  At a short prompt the chunk is
  the whole prompt, and the chunked form reads and writes several such
  f32 tensors per layer, over ten times the recurrence's bytes (PERF.md
  §6).  Under ``cfg.unroll_inner`` (the dry-run programs unroll every
  loop so that ``cost_analysis`` counts it, at prompts of 32k tokens,
  where a recurrence of one step a token cannot be unrolled) prefill
  takes the chunked form.
- ``apply_decode``: one step of the recurrence.

The depthwise causal conv is a grouped `conv_general_dilated`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SSMConfig
from repro.models.param import Initializer

MAMBA_CHUNK = 256
# tokens per iteration of the prefill recurrence's loop: at 1 each step
# slices its token's row out of arrays laid out with the sequence as the
# sublane axis.  4 is an estimate from the TPU v5e compiler's cycle counts
# for jamba2-3b's 32 x 24 prefill; no chip run has confirmed it (PERF.md §6)
PREFILL_UNROLL = 4


def _dims(cfg: ModelConfig):
    s = cfg.ssm or SSMConfig()
    d_in = s.expand * cfg.d_model
    dt_rank = s.dt_rank or -(-cfg.d_model // 16)
    return s, d_in, dt_rank


def init_mamba(ini: Initializer, cfg: ModelConfig):
    s, d_in, R = _dims(cfg)
    N, K = s.d_state, s.d_conv
    d = cfg.d_model
    # A = -exp(A_log) with A_log = log([1..N]) per channel: the S4D-real
    # spectrum -[1..N] at init
    a_log = ini.constant((d_in, N), ("mlp", "ssm_state"), value=0.0)
    if not ini.abstract:
        a_log = a_log._replace(value=a_log.value + jnp.log(
            jnp.arange(1, N + 1, dtype=jnp.float32)).astype(ini.dtype))
    p = {
        "in_proj": ini.lecun((d, 2 * d_in), ("embed", "mlp"), fan_in=d),
        "conv_w": ini.lecun((K, d_in), ("conv", "mlp"), fan_in=K),
        "conv_b": ini.zeros((d_in,), ("mlp",)),
        "x_proj": ini.lecun((d_in, R + 2 * N), ("mlp", "ssm"), fan_in=d_in),
        "dt_w": ini.lecun((R, d_in), ("ssm", "mlp"), fan_in=R),
        "dt_b": ini.constant((d_in,), ("mlp",), value=0.5),
        "A_log": a_log,
        "D": ini.ones((d_in,), ("mlp",)),
        "out_proj": ini.lecun((d_in, d), ("mlp", "embed"), fan_in=d_in),
    }
    if s.inner_norms:
        p["dt_norm"] = ini.ones((R,), ("ssm",))
        p["B_norm"] = ini.ones((N,), ("ssm_state",))
        p["C_norm"] = ini.ones((N,), ("ssm_state",))
    return p


def _causal_conv(x, w, b, *, state=None):
    """Depthwise causal conv.  x: (B,S,C); w: (K,C).  If ``state``
    ((B,K-1,C), the trailing inputs of the previous segment) is given it
    is prepended instead of zero padding.  Returns (y, new_state)."""
    B, S, C = x.shape
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)               # (B, S+K-1, C)
    y = jax.lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NLC", "LIO", "NLC"),
        feature_group_count=C)
    new_state = xp[:, S:, :] if K > 1 else state
    return y + b.astype(x.dtype), new_state


def _rms(x, scale, eps):
    """RMSNorm over the last axis, in float32."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _dt_b_c(p, cfg: ModelConfig, x_c):
    """x_c: (..., d_in) post-conv activations -> (dt (..., d_in), B, C
    (..., N)) in float32, each through its norm."""
    s, d_in, R = _dims(cfg)
    N = s.d_state
    f32 = jnp.float32
    xdb = x_c.astype(f32) @ p["x_proj"].astype(f32)        # (...,R+2N)
    dt_raw, Bmat, Cmat = jnp.split(xdb, [R, R + N], axis=-1)
    if s.inner_norms:
        dt_raw = _rms(dt_raw, p["dt_norm"], cfg.norm_eps)
        Bmat = _rms(Bmat, p["B_norm"], cfg.norm_eps)
        Cmat = _rms(Cmat, p["C_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(dt_raw @ p["dt_w"].astype(f32) + p["dt_b"].astype(f32))
    return dt, Bmat, Cmat


def _ssm_inputs(p, cfg: ModelConfig, x_c):
    """x_c: (B,S,d_in) post-conv activations -> (A_bar, Bx, Cmat)."""
    f32 = jnp.float32
    dt, Bmat, Cmat = _dt_b_c(p, cfg, x_c)
    A = -jnp.exp(p["A_log"].astype(f32))                   # (d_in, N)
    A_bar = jnp.exp(dt[..., None] * A)                     # (B,S,d_in,N)
    Bx = (dt * x_c.astype(f32))[..., None] * Bmat[..., None, :]
    return A_bar, Bx, Cmat


def _chunk_scan(A_bar, Bx, h0):
    """Within-chunk associative scan with incoming state h0.
    A_bar/Bx: (B,L,d_in,N); h0: (B,d_in,N) -> (h_all (B,L,d_in,N), h_last)."""

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, a_r * b_l + b_r

    a_cum, b_cum = jax.lax.associative_scan(combine, (A_bar, Bx), axis=1)
    h_all = a_cum * h0[:, None] + b_cum
    return h_all, h_all[:, -1]


def _front(p, x, mask):
    """x: (B,S,d) -> (x_c, z, conv_state): in_proj, the causal conv and
    its SiLU.  ``mask`` ((B,S) bool, None = all valid) marks the real
    tokens of left-padded rows: the conv's input is zeroed at pads and its
    output after it, so pads add nothing to the conv window, and with x_c
    zero there they add nothing to h (which stays zero through the
    leading pads)."""
    dt = x.dtype
    xz = x @ p["in_proj"].astype(dt)
    x_in, z = jnp.split(xz, 2, axis=-1)
    if mask is not None:
        keep = mask[..., None].astype(dt)
        x_in = x_in * keep
    x_conv, conv_state = _causal_conv(x_in, p["conv_w"], p["conv_b"])
    x_c = jax.nn.silu(x_conv)
    if mask is not None:
        x_c = x_c * keep
    return x_c, z, conv_state


def _back(p, y, x_c, z):
    """The scan's y (B,S,d_in) f32 -> the mixer's output: D skip, the
    SiLU gate, out_proj."""
    dt = z.dtype
    y = y + p["D"].astype(jnp.float32) * x_c.astype(jnp.float32)
    return (y.astype(dt) * jax.nn.silu(z)) @ p["out_proj"].astype(dt)


def apply_full(p, cfg: ModelConfig, x, *, return_state: bool = False,
               mask=None):
    """x: (B,S,d).  Chunked scan over the sequence; ``mask`` as in
    ``_front``."""
    s, d_in, _ = _dims(cfg)
    N = s.d_state
    B, S, d = x.shape
    x_c, z, conv_state = _front(p, x, mask)

    chunk = min(MAMBA_CHUNK, S)
    if cfg.unroll_inner:  # bound the unrolled loop at ~32 chunks
        chunk = max(chunk, -(-S // 32))
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    x_cp = jnp.pad(x_c, ((0, 0), (0, pad), (0, 0))) if pad else x_c

    A_bar, Bx, Cmat = _ssm_inputs(p, cfg, x_cp)
    Ab = A_bar.reshape(B, n_chunks, chunk, d_in, N).transpose(1, 0, 2, 3, 4)
    Bk = Bx.reshape(B, n_chunks, chunk, d_in, N).transpose(1, 0, 2, 3, 4)
    Ck = Cmat.reshape(B, n_chunks, chunk, N).transpose(1, 0, 2, 3)

    def body(h, xs):
        a, b, c = xs           # a,b: (B,chunk,d_in,N); c: (B,chunk,N)
        h_all, h_last = _chunk_scan(a, b, h)
        y = jnp.einsum("bldn,bln->bld", h_all, c)
        return h_last, y

    h0 = jnp.zeros((B, d_in, N), jnp.float32)
    if cfg.unroll_inner:
        ys_list = []
        h_last = h0
        for i in range(n_chunks):
            h_last, y_i = body(h_last, (Ab[i], Bk[i], Ck[i]))
            ys_list.append(y_i)
        ys = jnp.stack(ys_list)
    else:
        h_last, ys = jax.lax.scan(body, h0, (Ab, Bk, Ck))
    y = ys.transpose(1, 0, 2, 3).reshape(B, n_chunks * chunk, d_in)[:, :S]
    y = _back(p, y, x_c, z)
    if return_state:
        return y, {"h": h_last, "conv": conv_state}
    return y


def init_state(cfg: ModelConfig, batch: int, abstract: bool = False):
    s, d_in, _ = _dims(cfg)
    shapes = {
        "h": ((batch, d_in, s.d_state), jnp.dtype(jnp.float32)),
        "conv": ((batch, max(s.d_conv - 1, 1), d_in), jnp.dtype(cfg.dtype)),
    }
    if abstract:
        return {n: jax.ShapeDtypeStruct(sh, d) for n, (sh, d) in shapes.items()}
    return {n: jnp.zeros(sh, d) for n, (sh, d) in shapes.items()}


def state_axes():
    return {"h": ("batch", "mlp", "ssm_state"), "conv": ("batch", "conv", "mlp")}


def apply_prefill(p, cfg: ModelConfig, x, mask=None):
    """x: (B,S,d) -> (y, {"h", "conv"}): the prompt through the mixer and
    the decode state after it, ``mask`` as in ``_front``.

    The selective scan is a ``lax.scan`` over the tokens that carries h
    (B, d_in, N) in f32 and stacks y_t (B, d_in), h updated in the
    reference's sequential order, so no (B, S, d_in, N) value exists.
    The engine's prompts are short (chat.jamba2.gen64: 24 tokens), where
    this reads and writes a tenth of the chunked scan's bytes; prompts of
    thousands of tokens take as many serial steps (PERF.md §6 gives the
    prompt lengths it was checked at).  Under ``cfg.unroll_inner`` it is
    ``apply_full``'s chunked scan: the dry-run programs that set it unroll
    every loop, at prompts far too long to unroll one step a token."""
    if cfg.unroll_inner:
        return apply_full(p, cfg, x, return_state=True, mask=mask)
    s, d_in, _ = _dims(cfg)
    f32 = jnp.float32
    x_c, z, conv_state = _front(p, x, mask)
    dt, Bmat, Cmat = _dt_b_c(p, cfg, x_c)
    A = -jnp.exp(p["A_log"].astype(f32))                   # (d_in, N)

    def step(h, xs):
        dt_t, u_t, B_t, C_t = xs      # (B,d_in), (B,d_in), (B,N), (B,N)
        h = jnp.exp(dt_t[..., None] * A) * h + u_t[..., None] * B_t[:, None]
        return h, jnp.einsum("bdn,bn->bd", h, C_t)

    xs = tuple(jnp.swapaxes(v, 0, 1)
               for v in (dt, dt * x_c.astype(f32), Bmat, Cmat))
    h0 = jnp.zeros((x.shape[0], d_in, s.d_state), f32)
    h, ys = jax.lax.scan(step, h0, xs, unroll=PREFILL_UNROLL)
    y = _back(p, jnp.swapaxes(ys, 0, 1), x_c, z)
    return y, {"h": h, "conv": conv_state}


def apply_decode(p, cfg: ModelConfig, x, state):
    """One token.  x: (B,1,d) -> (y, new_state)."""
    s, d_in, _ = _dims(cfg)
    dt = x.dtype
    xz = x @ p["in_proj"].astype(dt)
    x_in, z = jnp.split(xz, 2, axis=-1)                     # (B,1,d_in)
    # conv over [state ; x]
    x_conv, new_conv = _causal_conv(x_in, p["conv_w"], p["conv_b"],
                                    state=state["conv"].astype(dt))
    x_c = jax.nn.silu(x_conv)                               # (B,1,d_in)
    A_bar, Bx, Cmat = _ssm_inputs(p, cfg, x_c)
    h = A_bar[:, 0] * state["h"] + Bx[:, 0]                 # (B,d_in,N)
    y = jnp.einsum("bdn,bn->bd", h, Cmat[:, 0])[:, None]
    y = y + p["D"].astype(jnp.float32) * x_c.astype(jnp.float32)
    y = (y.astype(dt) * jax.nn.silu(z)) @ p["out_proj"].astype(dt)
    return y, {"h": h, "conv": new_conv}
