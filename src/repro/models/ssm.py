"""Mamba-2 (SSD — state-space duality) layer, chunked.

The inter-chunk state recurrence runs on ``repro.core.recurrence`` — the same
shared-coefficient first-order engine as the paper's Thomas sweeps (the
"machinery-shared" integration of the paper's technique; DESIGN.md §4).

Per head h with state (P, N):  h_t = exp(a_t) h_{t-1} + dt_t B_t x_t^T,
y_t = C_t . h_t + D x_t, a_t = -exp(A_log) dt_t. Group count G = 1 (B and C
shared across heads), following the mamba2-130m config.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import linear_recurrence
from repro.sharding import ShardingCtx
from .config import ArchConfig
from .params import ParamSpec


def ssm_specs(cfg: ArchConfig) -> dict:
    D, di, H, N = cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.ssm_state
    w = cfg.conv_width
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return {
        "z_proj": ParamSpec((D, di), ("embed", "mlp"), dt),
        "x_proj": ParamSpec((D, di), ("embed", "mlp"), dt),
        "B_proj": ParamSpec((D, N), ("embed", "state"), dt),
        "C_proj": ParamSpec((D, N), ("embed", "state"), dt),
        "dt_proj": ParamSpec((D, H), ("embed", None), dt),
        "dt_bias": ParamSpec((H,), (None,), jnp.float32, init="zeros"),
        "A_log": ParamSpec((H,), (None,), jnp.float32, init="zeros"),
        "D_skip": ParamSpec((H,), (None,), jnp.float32, init="ones"),
        "conv_x": ParamSpec((w, di), ("conv", "mlp"), dt),
        "conv_B": ParamSpec((w, N), ("conv", "state"), dt),
        "conv_C": ParamSpec((w, N), ("conv", "state"), dt),
        "norm": ParamSpec((di,), (None,), jnp.float32, init="zeros"),
        "out_proj": ParamSpec((di, D), ("mlp", "embed"), dt,
                              scale=1.0 / np.sqrt(di)),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv along S. x: (B, S, C); w: (W, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(W):
        out = out + xp[:, i:i + x.shape[1]] * w[i][None, None, :]
    return out


def _conv_step(buf: jax.Array, x_t: jax.Array, w: jax.Array):
    """buf: (B, W-1, C) previous inputs; x_t: (B, C). Returns (y_t, new_buf)."""
    full = jnp.concatenate([buf, x_t[:, None]], axis=1)      # (B, W, C)
    y = jnp.einsum("bwc,wc->bc", full, w)
    return y, full[:, 1:]


def ssd_chunked(xh, dt, A_log, Bm, Cm, chunk: int, method: str = "auto"):
    """Chunked SSD scan.

    xh: (B, S, H, P); dt: (B, S, H) post-softplus; Bm, Cm: (B, S, N).
    ``method`` runs the inter-chunk recurrence (``core.linear_recurrence``;
    the model uses ``"auto"``, the Pallas kernels for float operands).
    Returns (y (B, S, H, P), final_state (B, H, P, N)).
    """
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    xf = xh.astype(jnp.float32)
    a = -jnp.exp(A_log)[None, None, :] * dt                  # (B, S, H) < 0
    ac = a.reshape(B, nc, Q, H)
    cum = jnp.cumsum(ac, axis=2)                             # inclusive
    Xc = xf.reshape(B, nc, Q, H, P)
    dtc = dt.reshape(B, nc, Q, H)
    Bc = Bm.astype(jnp.float32).reshape(B, nc, Q, N)
    Cc = Cm.astype(jnp.float32).reshape(B, nc, Q, N)

    # ---- intra-chunk (quadratic within Q) --------------------------------
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)           # (B, nc, Q, Q)
    # clamp BEFORE exp: for masked pairs j > i the difference is positive and
    # exp overflows to inf, which poisons the backward pass of the where
    # (0 * inf = NaN). Valid pairs have non-positive differences.
    decay = jnp.exp(jnp.minimum(
        cum[:, :, :, None, :] - cum[:, :, None, :, :], 0.0))  # (B,nc,i,j,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    W = jnp.where(tri[None, None, :, :, None], decay, 0.0) \
        * (scores[..., None] * dtc[:, :, None, :, :])
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", W, Xc)

    # ---- chunk states + inter-chunk recurrence (the shared engine) -------
    cum_last = cum[:, :, -1:, :]                             # (B, nc, 1, H)
    wj = jnp.exp(cum_last - cum) * dtc                       # (B, nc, Q, H)
    S_c = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", Bc, wj, Xc)   # (B, nc, H, P, N)
    p_chunk = jnp.exp(cum_last[:, :, 0, :])                  # (B, nc, H)

    p_t = jnp.moveaxis(p_chunk, 1, 0)[..., None, None]       # (nc, B, H, 1, 1)
    q_t = jnp.moveaxis(S_c, 1, 0)                            # (nc, B, H, P, N)
    # auto policy: the engine's gated-recurrence Pallas kernels; the
    # per-chunk decay broadcasts to a full gate operand on dispatch
    # (fp32 carries — everything above is fp32 already)
    S_run = linear_recurrence(p_t, q_t, method=method)       # inclusive prefix
    S_prev = jnp.concatenate([jnp.zeros_like(S_run[:1]), S_run[:-1]], axis=0)
    S_prev = jnp.moveaxis(S_prev, 0, 1)                      # (B, nc, H, P, N)

    y_inter = jnp.einsum("bcin,bchpn->bcihp", Cc, S_prev) * \
        jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(B, S, H, P)
    return y.astype(xh.dtype), S_run[-1]                     # state: (B, H, P, N)


def ssm_apply(p, x, sctx: ShardingCtx, cfg: ArchConfig):
    """Training/prefill. x: (B, S, D) -> (y, final_ssm_state, conv_tails)."""
    B, S, D = x.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    W = cfg.conv_width

    z = jnp.einsum("bsd,de->bse", x, p["z_proj"])
    xr = jnp.einsum("bsd,de->bse", x, p["x_proj"])
    Bm = jnp.einsum("bsd,dn->bsn", x, p["B_proj"])
    Cm = jnp.einsum("bsd,dn->bsn", x, p["C_proj"])
    dt_arg = jnp.einsum("bsd,dh->bsh", x, p["dt_proj"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt_arg + p["dt_bias"][None, None, :])

    # conv tails for decode handoff: the last W-1 *pre-conv* inputs
    conv_tails = {
        "x": xr[:, -(W - 1):],
        "B": Bm[:, -(W - 1):],
        "C": Cm[:, -(W - 1):],
    }

    xr = jax.nn.silu(_causal_conv(xr, p["conv_x"]))
    Bm = jax.nn.silu(_causal_conv(Bm, p["conv_B"]))
    Cm = jax.nn.silu(_causal_conv(Cm, p["conv_C"]))

    xh = xr.reshape(B, S, H, P)
    y, state = ssd_chunked(xh, dt, p["A_log"], Bm, Cm, cfg.ssm_chunk)
    y = y + p["D_skip"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(B, S, H * P)
    y = y * jax.nn.silu(z)
    from .layers import rmsnorm
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return sctx.constrain(out, ("act_batch", "act_res_seq", None)), state, conv_tails


def ssm_decode_step(p, x_t, state, conv_bufs, cfg: ArchConfig):
    """x_t: (B, D); state: (B, H, P, N); conv_bufs dict of (B, W-1, C)."""
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    z = x_t @ p["z_proj"]
    xr = x_t @ p["x_proj"]
    Bm = x_t @ p["B_proj"]
    Cm = x_t @ p["C_proj"]
    dt = jax.nn.softplus((x_t @ p["dt_proj"]).astype(jnp.float32)
                         + p["dt_bias"][None, :])             # (B, H)

    xr, bx = _conv_step(conv_bufs["x"], xr, p["conv_x"])
    Bm, bB = _conv_step(conv_bufs["B"], Bm, p["conv_B"])
    Cm, bC = _conv_step(conv_bufs["C"], Cm, p["conv_C"])
    xr = jax.nn.silu(xr); Bm = jax.nn.silu(Bm); Cm = jax.nn.silu(Cm)

    xh = xr.reshape(-1, H, P).astype(jnp.float32)
    a = jnp.exp(-jnp.exp(p["A_log"])[None, :] * dt)          # (B, H)
    upd = jnp.einsum("bh,bn,bhp->bhpn", dt, Bm.astype(jnp.float32), xh)
    state = a[..., None, None] * state + upd
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), state)
    y = y + p["D_skip"][None, :, None] * xh
    y = y.reshape(-1, H * P).astype(x_t.dtype)
    y = y * jax.nn.silu(z)
    from .layers import rmsnorm
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    out = y @ p["out_proj"]
    return out, state, {"x": bx, "B": bB, "C": bC}
