"""Nemotron-H hybrid language model (Nemotron-3-Nano-30B-A3B family) as an
FL client model.

The stack follows ``hybrid_override_pattern``: one mixer per layer, each
``x + mixer(RMSNorm(x))``:

  * ``M`` Mamba2: ``in_proj`` -> (z, xBC, dt); a causal depthwise conv (with
    bias) and SiLU over xBC; dt = softplus(dt + dt_bias), A = -exp(A_log);
    the SSD recurrence (``ssm.ssd_chunked``) with B and C shared by groups of
    heads; y + D·x through a gated RMSNorm taken over each group of
    channels, RMSNorm(y·SiLU(z)); ``out_proj``.
  * ``E`` MoE, routed by token choice: s = sigmoid(x·W_r) over every routed
    expert; the top k chosen on s + b (b, the expert correction bias, has no
    gradient: it only picks); the weights are the chosen s, normalised to
    sum 1 and scaled by ``routed_scale``; each expert is
    down(relu(up(x))²); one shared expert of the same form is added.  The
    layer holds a contiguous share of the experts (``expert_first``,
    ``experts_held``), as one chip of an expert-parallel deployment does: it
    routes over all of them and computes only its own experts' terms, for
    every token routed to them (no capacity: the assignments are sorted by
    expert, and one grouped matmul, a Pallas TPU kernel, multiplies each
    expert's tokens over only the row tiles they fill).
  * ``*`` grouped-query attention, causal, no bias and no positional
    encoding (the Mamba layers carry position).

Then a final RMSNorm and an untied head over the vocabulary the model holds.
Matmul inputs are in ``dtype`` with float32 accumulation; parameters stay in
their storage dtype (float32) and are cast at each use.

Each mixer runs under a named scope (``lm.mamba``, ``lm.moe``,
``lm.attn``; ``lm.head`` for the head and the loss), so that a device
trace's ops can be attributed to it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from .ssm import ssd_chunked

MAMBA, MOE, ATTN = "M", "E", "*"
ATTN_BLOCK = 512       # queries per attention block
# (tm, tk, tn) of the grouped expert matmul, chosen on a v5e by timing one
# MoE layer's forward and backward at the LM cell's shape: 42.1 ms, against
# 42.3 at (512, 512, 512); a gather-only combine read 49.7-51.3 ms
GMM_TILING = (256, 512, 512)


@dataclass(frozen=True)
class HybridConfig:
    """One Nemotron-H stack.  ``vocab_size`` and ``experts_held`` are what
    this model holds: the whole vocabulary and every expert for the
    published model, a slice and a share of them on one chip of a
    deployment."""
    name: str
    pattern: str                 # hybrid_override_pattern, one char a layer
    d_model: int
    vocab_size: int
    norm_eps: float
    # Mamba2
    mamba_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state: int
    conv_kernel: int
    chunk: int
    # attention
    num_heads: int
    num_kv_heads: int
    head_dim: int
    # MoE
    n_experts: int               # the router's width: every routed expert
    top_k: int
    expert_ff: int
    shared_ff: int
    routed_scale: float
    norm_topk: bool
    expert_first: int = 0
    experts_held: int = 0        # 0: all of them
    dtype: str = "bfloat16"      # matmul inputs; accumulation in float32

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


# ---------------------------------------------------------------------------
# mixers
# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _mm(x, w, cfg):
    """x @ w with inputs in the compute dtype, accumulated in float32."""
    dt = cfg.compute_dtype
    return jnp.matmul(x.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32)


def causal_conv(xbc, w, b):
    """Depthwise causal conv over time, then SiLU.  xbc: [B, S, C], w:
    [K, C]: out[t] = sum_i w[i]·x[t - K + 1 + i] + b."""
    k, s = w.shape[0], xbc.shape[1]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + s] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


def mamba_mixer(p, x, cfg: HybridConfig):
    b, s, _ = x.shape
    h, hp, g, n = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups,
                   cfg.ssm_state)
    din = cfg.d_inner
    zxbcdt = _mm(x, p["in_proj"], cfg)
    z, xbc, dt = (zxbcdt[..., :din], zxbcdt[..., din:din + cfg.conv_dim],
                  zxbcdt[..., din + cfg.conv_dim:])
    xbc = causal_conv(xbc, p["conv_w"], p["conv_b"])
    xs = xbc[..., :din].reshape(b, s, h, hp)
    group = lambda a: jnp.repeat(a.reshape(b, s, g, n), h // g, axis=2)
    bm, cm = group(xbc[..., din:din + g * n]), group(xbc[..., din + g * n:])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    y = ssd_chunked(xs, dt, a, bm, cm, cfg.chunk)
    y = y + xs * p["D"][:, None]
    # gated RMSNorm over each group of din / g channels
    y = y.reshape(b, s, din) * jax.nn.silu(z)
    yg = y.reshape(b, s, g, din // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + cfg.norm_eps)
    y = yg.reshape(b, s, din) * p["gate_norm"]
    return _mm(y, p["out_proj"], cfg)


def route(logits, bias, cfg: HybridConfig):
    """Token-choice routing over every routed expert: (experts [T, k],
    weights [T, k]).  The choice is on sigmoid scores plus the correction
    bias; the weights are the chosen scores alone."""
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bias, cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scale


def _relu2(x):
    r = jax.nn.relu(x)
    return r * r


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(x, w, sizes, tiling, interpret):
    """Rows of x [m, k] laid end to end in groups of ``sizes`` rows, group
    g's times w[g] [k, n] (cast to x's dtype), accumulated and returned in
    float32: megablox's Pallas TPU kernel over only the m-tiles the groups
    fill; rows past ``sum(sizes)`` are left unwritten.  In the backward
    pass the same kernel (``transpose_rhs``) gives x's cotangent and its
    transpose (``tgmm``) w's, over the same tiles, from the cotangent in
    x's dtype (as a dot at the default precision takes it)."""
    return gmm(x, w.astype(x.dtype), sizes, jnp.float32, tiling,
               interpret=interpret)


def _grouped_matmul_fwd(x, w, sizes, tiling, interpret):
    w = w.astype(x.dtype)
    return grouped_matmul(x, w, sizes, tiling, interpret), (x, w, sizes)


def _grouped_matmul_bwd(tiling, interpret, res, g):
    x, w, sizes = res
    g = g.astype(x.dtype)
    dx = gmm(g, w, sizes, x.dtype, tiling, transpose_rhs=True,
             interpret=interpret)
    dw = tgmm(x.T, g, sizes, jnp.float32, tiling,
              num_actual_groups=w.shape[0], interpret=interpret)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _expert_tiles(counts, tm: int):
    """m-tiles the grouped matmul runs for groups of ``counts`` rows laid
    end to end: each non-empty group from its start rounded down to a tile
    to its end rounded up, as megablox's group metadata counts them."""
    ends = jnp.cumsum(counts)
    starts = ends - counts
    return jnp.sum(jnp.where(counts > 0, -(-ends // tm) - starts // tm, 0))


def moe_mixer(p, x, cfg: HybridConfig):
    """Returns (y, {"expert_tokens": tokens per held expert [held],
    "expert_tiles": m-tiles the grouped matmul ran, "tokens_dropped":
    assignments to held experts left uncomputed}).

    The assignments to held experts are sorted by expert and their tokens
    gathered into one matrix of a static ``T·min(top_k, held)`` rows (a
    bound no routing exceeds, so there is no capacity); one grouped matmul
    (megablox ``gmm``, a Pallas TPU kernel, in interpret mode off the TPU)
    multiplies each expert's rows by its weights, over only the m-tiles
    that the experts' rows fill, and its custom VJP does the same for the
    gradients.  The kernel leaves rows past the held assignments unwritten,
    in its output and in its input's cotangent, so both sides are masked
    with ``where`` (a multiply by 0 would keep a NaN).  The weighted rows
    are added back into their tokens.  No token is dropped: the row bound
    covers every count, so the third value is 0 by construction, an
    invariant the benchmark's check holds it to."""
    b, s, d = x.shape
    t, k, e = b * s, cfg.top_k, cfg.held
    xt = x.reshape(t, d)
    logits = _mm(xt, p["router"], cfg)
    idx, w = route(logits, p["bias"], cfg)
    local = idx - cfg.expert_first
    mine = (local >= 0) & (local < e)
    group = jnp.where(mine, local, e).reshape(-1)
    order = jnp.argsort(group, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[group].add(1)[:e]
    tm = GMM_TILING[0]
    m = -(-t * min(k, e) // tm) * tm
    order = jnp.pad(order, (0, max(0, m - t * k)))[:m]
    held = jnp.sum(counts)
    live = (jnp.arange(m) < held)[:, None]
    token = order // k
    weight = jnp.where(mine, w, 0.0).reshape(-1)[order]
    dt = cfg.compute_dtype
    mm = partial(grouped_matmul, sizes=counts, tiling=GMM_TILING,
                 interpret=jax.default_backend() != "tpu")
    xs = jnp.where(live, xt.astype(dt)[token], 0)
    y = mm(_relu2(mm(xs, p["up"])).astype(dt), p["down"])
    y = jnp.where(live, y, 0.0) * weight[:, None]
    routed = jnp.zeros((t, d), jnp.float32).at[token].add(y)
    shared = _mm(_relu2(_mm(xt, p["shared_up"], cfg)), p["shared_down"], cfg)
    return (routed + shared).reshape(b, s, d), {
        "expert_tokens": counts, "expert_tiles": _expert_tiles(counts, tm),
        "tokens_dropped": held - jnp.minimum(held, m)}


def attn_mixer(p, x, cfg: HybridConfig):
    """Causal GQA, no positional encoding.  Queries in blocks of
    ``ATTN_BLOCK``; block i attends to keys [0, (i+1)·block), so only the
    causal half is computed; each block is recomputed in the backward pass
    instead of keeping its probabilities."""
    b, s, _ = x.shape
    hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.compute_dtype
    q = _mm(x, p["wq"], cfg).astype(dt).reshape(b, s, hk, hq // hk, hd)
    kk = _mm(x, p["wk"], cfg).astype(dt).reshape(b, s, hk, hd)
    v = _mm(x, p["wv"], cfg).astype(dt).reshape(b, s, hk, hd)
    blk = min(ATTN_BLOCK, s)

    @jax.checkpoint
    def block(qb, kb, vb, q0):
        sc = jnp.einsum("bqkrd,btkd->bkrqt", qb, kb,
                        preferred_element_type=jnp.float32) / math.sqrt(hd)
        qpos = q0 + jnp.arange(qb.shape[1])
        ok = qpos[:, None] >= jnp.arange(kb.shape[1])[None, :]
        pr = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bkrqt,btkd->bqkrd", pr.astype(dt), vb,
                          preferred_element_type=jnp.float32)

    outs = [block(q[:, i:i + blk], kk[:, :i + blk], v[:, :i + blk], i)
            for i in range(0, s, blk)]
    o = jnp.concatenate(outs, axis=1).reshape(b, s, hq * hd)
    return _mm(o, p["wo"], cfg)


# ---------------------------------------------------------------------------
# the stack, loss and accuracy
# ---------------------------------------------------------------------------
def _layer(kind, p, x, cfg):
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    if kind == MAMBA:
        with jax.named_scope("lm.mamba"):
            return x + mamba_mixer(p, h, cfg).astype(x.dtype), None
    if kind == MOE:
        with jax.named_scope("lm.moe"):
            y, aux = moe_mixer(p, h, cfg)
            return x + y.astype(x.dtype), aux
    with jax.named_scope("lm.attn"):
        return x + attn_mixer(p, h, cfg).astype(x.dtype), None


def hidden(params, tokens, cfg: HybridConfig):
    """tokens [B, S] -> (final-normed hidden [B, S, D], the MoE layers'
    counters: ``expert_tokens`` [n_moe, held], ``expert_tiles`` [n_moe],
    ``tokens_dropped`` summed over the layers)."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    auxes = []
    for kind, p in zip(cfg.pattern, params["layers"]):
        # each layer is recomputed in the backward pass
        x, aux = jax.checkpoint(partial(_layer, kind, cfg=cfg))(p, x)
        if aux is not None:
            auxes.append(aux)
    if auxes:
        aux = {f: jnp.stack([a[f] for a in auxes]) for f in auxes[0]}
    else:
        aux = {"expert_tokens": jnp.zeros((0, cfg.held), jnp.int32),
               "expert_tiles": jnp.zeros((0,), jnp.int32),
               "tokens_dropped": jnp.zeros((0,), jnp.int32)}
    aux["tokens_dropped"] = jnp.sum(aux["tokens_dropped"])
    return rmsnorm(x, params["norm_f"], cfg.norm_eps), aux


def logits(params, tokens, cfg: HybridConfig):
    h, _ = hidden(params, tokens, cfg)
    with jax.named_scope("lm.head"):
        return _mm(h, params["head"], cfg)


def loss(params, x, y, w, cfg: HybridConfig):
    """Mean next-token cross-entropy over the tokens of the sequences of
    weight 1 (x, y: [B, S] inputs and targets, w: [B]); returns (loss,
    {"expert_tokens": tokens per held expert [n_moe, held],
    "expert_tiles": m-tiles of the grouped expert matmul [n_moe],
    "tokens_dropped": assignments to held experts left uncomputed})."""
    h, aux = hidden(params, x, cfg)
    with jax.named_scope("lm.head"):
        z = _mm(h, params["head"], cfg)
        lse = jax.nn.logsumexp(z, axis=-1)
        nll = lse - jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
        total = jnp.sum(jnp.sum(nll, -1) * w)
        value = total / jnp.maximum(jnp.sum(w) * y.shape[1], 1.0)
    return value, aux


def accuracy(params, x, y, cfg: HybridConfig, batch: int):
    """Share of next tokens predicted (argmax) over the sequences x, y
    [V, S], ``batch`` sequences at a time."""
    v, s = x.shape

    def hits(xy):
        return jnp.sum(jnp.argmax(logits(params, xy[0], cfg), -1) == xy[1])

    blocks = (x.reshape(v // batch, batch, s), y.reshape(v // batch, batch, s))
    return jnp.sum(jax.lax.map(hits, blocks)) / (v * s)


# ---------------------------------------------------------------------------
# the FL client
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LMClient:
    """The client model of an FL round on token sequences: minibatch SGD of
    ``batch`` sequences a step on the next-token loss, next-token accuracy
    for RONI and the validation metric, and a twin that mirrors tokens with
    each input token replaced by a uniform token of the vocabulary with
    probability ε.  A model this size is trained one client at a time
    (``scan_clients``): a copy of its parameters per client does not fit."""
    cfg: HybridConfig
    batch: int = 2
    scan_clients = True

    def loss(self, params, x, y, w):
        return loss(params, x, y, w, self.cfg)

    def accuracy(self, params, x, y):
        return accuracy(params, x, y, self.cfg, min(self.batch, x.shape[0]))

    def deviate(self, key, tokens, epsilon):
        k_hit, k_tok = jax.random.split(key)
        hit = jax.random.uniform(k_hit, tokens.shape) < epsilon
        other = jax.random.randint(k_tok, tokens.shape, 0,
                                   self.cfg.vocab_size)
        return jnp.where(hit, other, tokens)
