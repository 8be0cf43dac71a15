"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) block, pure JAX.

Train/prefill use the chunked SSD algorithm (quadratic intra-chunk term +
linear inter-chunk state recurrence via ``lax.scan``); decode uses the O(1)
recurrent step.  ``kernels/ssd_scan.py`` provides the Pallas TPU kernel for
the intra-chunk term; this module is the jnp reference path used under SPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import dense_init, split_tree


def init_mamba(key, cfg: ModelConfig):
    d = cfg.d_model
    din, ns, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads
    g, cw = cfg.ssm_ngroups, cfg.ssm_conv_width
    dt = cfg.storage_dtype
    ks = split_tree(key, 5)
    conv_ch = din + 2 * g * ns
    return {
        "in_proj": dense_init(ks[0], (d, 2 * din + 2 * g * ns + nh), dt),
        "conv_w": dense_init(ks[1], (cw, conv_ch), dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.zeros((nh,), dt),                      # A = -exp(A_log) = -1
        "D": jnp.ones((nh,), dt),
        "dt_bias": jnp.zeros((nh,), dt),
        "out_norm": jnp.ones((din,), dt),
        "out_proj": dense_init(ks[2], (din, d), dt),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    din, ns, g, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups, cfg.ssm_nheads
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:din + din + 2 * g * ns]
    dt = zxbcdt[..., din + din + 2 * g * ns:]
    assert dt.shape[-1] == nh
    return z, xbc, dt


def _causal_conv(xbc, w, b, cfg: ModelConfig):
    """Depthwise causal conv1d, width cfg.ssm_conv_width. xbc: [B,S,C]."""
    cw = cfg.ssm_conv_width
    pad = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i][None, None, :] for i in range(cw))
    return jax.nn.silu(out + b[None, None, :])


def _split_xbc(xbc, cfg: ModelConfig):
    din, ns, g = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups
    b_, s_ = xbc.shape[0], xbc.shape[1]
    x = xbc[..., :din].reshape(b_, s_, cfg.ssm_nheads, cfg.ssm_head_dim)
    B = xbc[..., din:din + g * ns].reshape(b_, s_, g, ns)
    C = xbc[..., din + g * ns:].reshape(b_, s_, g, ns)
    return x, B, C


def _expand_groups(bc, nh, g):
    """[b,...,g,n] -> [b,...,h,n] by repeating each group nh//g times."""
    return jnp.repeat(bc, nh // g, axis=-2)


def ssd_chunked(x, dt, A, B, C, chunk: int, return_state: bool = False):
    """SSD scan.  x:[b,s,h,p] dt:[b,s,h] A:[h] B,C:[b,s,h,n] -> y:[b,s,h,p].

    Implemented as ONE ``lax.scan`` over chunks carrying the SSM state —
    the quadratic intra-chunk term is materialized for a single chunk at a
    time ([b,l,l,h], a few MB), matching what the Pallas kernel holds in
    VMEM; the backward pass recomputes each chunk from its carried state
    instead of keeping every chunk's intermediates.  All decay math in
    f32.
    """
    b, s, h, p = x.shape
    pad = (-s) % chunk
    if pad:  # zero-pad to a chunk multiple (dt=0 ⇒ identity dynamics)
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        x = jnp.pad(x, padw)
        dt = jnp.pad(dt, padw[:3])
        B = jnp.pad(B, padw)
        C = jnp.pad(C, padw)
        s = s + pad
    c, l = s // chunk, chunk
    causal = jnp.tril(jnp.ones((l, l), bool))

    def per_chunk(state, inp):
        xr, dtr, Br, Cr = inp            # [b,l,h,p], [b,l,h], [b,l,h,n] ×2
        dtr = dtr.astype(jnp.float32)
        dA = dtr * A[None, None, :]                         # [b,l,h]
        dA_cs = jnp.cumsum(dA, axis=1)

        # intra-chunk (quadratic) term
        Lmat = dA_cs[:, :, None, :] - dA_cs[:, None, :, :]  # [b,l,m,h]
        # mask before the exp: above the diagonal the exponent grows with
        # the chunk's total decay, and an exp that overflows there would
        # turn the masked gradient into 0 * inf = NaN
        Lmat = jnp.exp(jnp.where(causal[None, :, :, None], Lmat, -jnp.inf))
        CB = jnp.einsum("blhn,bmhn->blmh", Cr.astype(jnp.float32),
                        Br.astype(jnp.float32))
        gate = CB * Lmat * dtr[:, None, :, :]
        y = jnp.einsum("blmh,bmhp->blhp", gate, xr.astype(jnp.float32))

        # inter-chunk contribution from the carried state
        y += jnp.einsum("blhn,bhpn,blh->blhp", Cr.astype(jnp.float32),
                        state, jnp.exp(dA_cs))

        # state update
        decay = jnp.exp(dA_cs[:, -1:, :] - dA_cs)           # [b,l,h]
        new_state = state * jnp.exp(dA_cs[:, -1, :])[:, :, None, None] + \
            jnp.einsum("blhn,blh,blhp->bhpn", Br.astype(jnp.float32),
                       decay * dtr, xr.astype(jnp.float32))
        return new_state, y.astype(x.dtype)

    to_chunks = lambda a: jnp.moveaxis(
        a.reshape((b, c, l) + a.shape[2:]), 1, 0)
    init = jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)
    final_state, ys = jax.lax.scan(
        jax.checkpoint(per_chunk), init,
        (to_chunks(x), to_chunks(dt), to_chunks(B), to_chunks(C)))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)
    if pad:
        y = y[:, :s - pad]
    if return_state:
        return y, final_state
    return y


def _out(z, y, p, cfg: ModelConfig):
    dt = cfg.compute_dtype
    b, s = y.shape[0], y.shape[1]
    y = y.reshape(b, s, cfg.d_inner) * jax.nn.silu(z)
    y32 = y.astype(jnp.float32)
    y32 = y32 * jax.lax.rsqrt(jnp.mean(y32 * y32, -1, keepdims=True) + cfg.norm_eps)
    y = (y32 * (1.0 + p["out_norm"].astype(jnp.float32))).astype(dt)
    return y @ p["out_proj"].astype(dt)


def mamba_forward(p, xin, cfg: ModelConfig, return_cache: bool = False):
    """Full-sequence forward (train / prefill). xin: [B,S,D] -> [B,S,D].

    With ``return_cache`` also returns the decode cache primed at position S
    (final SSM state + last conv_width−1 raw xbc inputs)."""
    dt_c = cfg.compute_dtype
    zxbcdt = xin @ p["in_proj"].astype(dt_c)
    z, xbc_raw, dtv = _split_proj(zxbcdt, cfg)
    xbc = _causal_conv(xbc_raw, p["conv_w"].astype(dt_c),
                       p["conv_b"].astype(dt_c), cfg)
    x, B, C = _split_xbc(xbc, cfg)
    B = _expand_groups(B, cfg.ssm_nheads, cfg.ssm_ngroups)
    C = _expand_groups(C, cfg.ssm_nheads, cfg.ssm_ngroups)
    dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if return_cache:
        y, state = ssd_chunked(x, dtv, A, B, C, cfg.ssm_chunk,
                               return_state=True)
    else:
        y = ssd_chunked(x, dtv, A, B, C, cfg.ssm_chunk)
    y = y + x * p["D"].astype(dt_c)[None, None, :, None]
    out = _out(z, y, p, cfg)
    if return_cache:
        cw = cfg.ssm_conv_width
        conv_tail = xbc_raw[:, -(cw - 1):, :]
        return out, {"state": state, "conv": conv_tail}
    return out


# ---------------------------------------------------------------------------
# decode (recurrent step)
# ---------------------------------------------------------------------------
def init_ssm_cache(cfg: ModelConfig, batch: int, prefix_shape=()):
    nh, pdim, ns = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    conv_ch = cfg.d_inner + 2 * cfg.ssm_ngroups * ns
    return {
        "state": jnp.zeros(prefix_shape + (batch, nh, pdim, ns), jnp.float32),
        "conv": jnp.zeros(prefix_shape + (batch, cfg.ssm_conv_width - 1, conv_ch),
                          cfg.compute_dtype),
    }


def mamba_decode_step(p, xin, cache, cfg: ModelConfig):
    """One-token step. xin: [B,1,D] -> (out [B,1,D], new cache)."""
    dt_c = cfg.compute_dtype
    zxbcdt = xin @ p["in_proj"].astype(dt_c)
    z, xbc, dtv = _split_proj(zxbcdt, cfg)                  # xbc: [B,1,C]
    hist = jnp.concatenate([cache["conv"], xbc], axis=1)    # [B,cw,C]
    w = p["conv_w"].astype(dt_c)
    conv_out = jnp.einsum("bwc,wc->bc", hist, w) + p["conv_b"].astype(dt_c)
    xbc_t = jax.nn.silu(conv_out)[:, None, :]
    new_conv = hist[:, 1:, :]

    x, B, C = _split_xbc(xbc_t, cfg)
    B = _expand_groups(B, cfg.ssm_nheads, cfg.ssm_ngroups)[:, 0]   # [B,h,n]
    C = _expand_groups(C, cfg.ssm_nheads, cfg.ssm_ngroups)[:, 0]
    x = x[:, 0]                                                     # [B,h,p]
    dtv = jax.nn.softplus(dtv[:, 0].astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))       # [B,h]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dtv * A[None, :])                                  # [B,h]
    st = cache["state"] * dA[:, :, None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dtv, x.astype(jnp.float32), B.astype(jnp.float32))
    y = jnp.einsum("bhn,bhpn->bhp", C.astype(jnp.float32), st).astype(dt_c)
    y = y + x * p["D"].astype(dt_c)[None, :, None]
    out = _out(z, y[:, None], p, cfg)
    return out, {"state": st, "conv": new_conv}
