"""Mamba-2 (SSD, arXiv:2405.21060) block: chunked scan + recurrent decode.

State space:  h_t = exp(dt_t * A_h) h_{t-1} + dt_t * B_t x_t^T,
              y_t = C_t h_t + D_h x_t
with A_h scalar per head, B/C shared across head groups (GVA), x in
(B, L, H, P) heads x head_dim, state (H, P, N).

The chunked (SSD) form splits L into chunks of Q steps: an intra-chunk
quadratic term (masked (C B^T) against decay), a per-chunk state
contribution, and an inter-chunk linear recurrence over chunk states —
``lax.scan`` over L/Q steps (upgradable to ``associative_scan``; see
EXPERIMENTS §Perf). All matmuls are MXU-shaped einsums.

The paper's attention-scheduling technique does not apply here (attention-
free; no K/V ACCs) — this arch is implemented without it, as required by the
assignment (DESIGN.md §Arch-applicability). The *generalized* insight
(iterate so the shared operand stays resident) still shapes the chunk loop:
head-major layout keeps each head's (P, N) state in registers/VMEM across
the whole sequence scan.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import ModelConfig, SSMConfig
from repro.models import layers


def _dims(d_model: int, cfg: SSMConfig):
    d_in = cfg.expand * d_model
    nheads = cfg.num_heads or d_in // cfg.head_dim
    return d_in, nheads, cfg.num_groups, cfg.state_dim, cfg.conv_width


def init_mamba(key, d_model: int, cfg: SSMConfig) -> dict:
    d_in, h, g, n, w = _dims(d_model, cfg)
    conv_ch = d_in + 2 * g * n
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(d_model)
    dt_init = jnp.log(jnp.expm1(jnp.linspace(1e-3, 0.1, h)))  # softplus^-1
    return {
        # order: [z(d_in), x(d_in), B(g*n), C(g*n), dt(h)]
        "win_dm": layers.normal_init(
            ks[0], (d_model, 2 * d_in + 2 * g * n + h), s
        ),
        "conv_w": layers.normal_init(ks[1], (w, conv_ch), 0.1),
        "conv_b_r": jnp.zeros((conv_ch,), layers.default_dtype()),
        "a_log_r": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(layers.default_dtype()),
        "d_skip_r": jnp.ones((h,), layers.default_dtype()),
        "dt_bias_r": dt_init.astype(layers.default_dtype()),
        "norm": layers.init_rmsnorm(d_in),
        "wout_md": layers.normal_init(
            ks[2], (d_in, d_model), 1.0 / math.sqrt(d_in)
        ),
    }


def _split_proj(proj, d_in, g, n, h):
    z = proj[..., :d_in]
    xbc = proj[..., d_in : 2 * d_in + 2 * g * n]
    dt = proj[..., 2 * d_in + 2 * g * n :]
    return z, xbc, dt


def _causal_conv(xbc: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv over (B, L, C) with kernel (W, C)."""
    width = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1], :] * w[i][None, None, :] for i in range(width)
    )
    return jax.nn.silu(out + b[None, None, :])


def ssd_chunked(
    x: jnp.ndarray,      # (B, L, H, P) pre-scaled inputs
    dt: jnp.ndarray,     # (B, L, H) positive step sizes
    a: jnp.ndarray,      # (H,) negative decay rates
    b_mat: jnp.ndarray,  # (B, L, G, N)
    c_mat: jnp.ndarray,  # (B, L, G, N)
    chunk: int,
    h0: jnp.ndarray = None,  # (B, H, P, N) initial state
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,L,H,P), final_state (B,H,P,N))."""
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    q = min(chunk, l)
    pad = (-l) % q
    if pad:
        # dt=0 padding: decay exp(0)=1 and zero update leave the state exact.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    l_pad = l + pad
    nc = l_pad // q
    f32 = jnp.float32

    xc = x.reshape(bsz, nc, q, h, p).astype(f32)
    dtc = dt.reshape(bsz, nc, q, h).astype(f32)
    bc = b_mat.reshape(bsz, nc, q, g, n).astype(f32)
    cc = c_mat.reshape(bsz, nc, q, g, n).astype(f32)
    dtype_in = x.dtype
    bh = jnp.repeat(bc, rep, axis=3)  # (B,nc,q,H,N)
    ch = jnp.repeat(cc, rep, axis=3)

    adt = dtc * a[None, None, None, :]              # (B,nc,q,H) log-decay per step
    acum = jnp.cumsum(adt, axis=2)                  # inclusive cumsum
    xdt = xc * dtc[..., None]                       # dt-scaled input

    # Intra-chunk: Y[i] = sum_{j<=i} C_i.B_j exp(acum_i - acum_j) * xdt_j
    seg = acum[:, :, :, None, :] - acum[:, :, None, :, :]       # (B,nc,i,j,H)
    mask = jnp.tril(jnp.ones((q, q), bool))
    # Mask in log space BEFORE exp: above-diagonal seg is positive and
    # exp() overflows to inf, which would poison gradients via inf*0.
    seg = jnp.where(mask[None, None, :, :, None], seg, -1e30)
    decay = jnp.exp(seg)
    cb = jnp.einsum("bcihn,bcjhn->bcijh", ch, bh)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", cb * decay, xdt)

    # Chunk state: S_c = sum_j exp(acum_last - acum_j) * B_j (x) xdt_j
    last = acum[:, :, -1:, :]                                    # (B,nc,1,H)
    decay_to_end = jnp.exp(last - acum)                          # (B,nc,q,H)
    s_c = jnp.einsum("bcjhn,bcjhp->bchpn", bh * decay_to_end[..., None], xdt)

    # Inter-chunk recurrence over chunk states.
    chunk_decay = jnp.exp(last[:, :, 0, :])                      # (B,nc,H)

    def step(hprev, inp):
        dec, s = inp  # dec (B,H), s (B,H,P,N)
        hnew = hprev * dec[:, :, None, None] + s
        return hnew, hprev  # emit state *entering* the chunk

    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), f32)
    hT, h_in = jax.lax.scan(
        step, h0.astype(f32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(s_c, 1, 0)),
    )
    h_in = jnp.moveaxis(h_in, 0, 1)                              # (B,nc,H,P,N)

    # Inter-chunk output: C_i exp(acum_i) h_in
    y_inter = jnp.einsum("bcihn,bchpn->bcihp", ch * jnp.exp(acum)[..., None], h_in)

    y = (y_intra + y_inter).reshape(bsz, l_pad, h, p)[:, :l]
    return y.astype(dtype_in), hT


def ssd_recurrent_ref(x, dt, a, b_mat, c_mat, h0=None):
    """O(L) exact recurrence — the test oracle for ssd_chunked."""
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    f32 = jnp.float32
    bh = jnp.repeat(b_mat, rep, axis=2).astype(f32)
    ch = jnp.repeat(c_mat, rep, axis=2).astype(f32)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), f32)

    def step(hprev, t):
        dec = jnp.exp(dt[:, t].astype(f32) * a[None, :])         # (B,H)
        upd = jnp.einsum(
            "bhn,bhp->bhpn", bh[:, t], x[:, t].astype(f32) * dt[:, t, :, None].astype(f32)
        )
        hnew = hprev * dec[:, :, None, None] + upd
        y = jnp.einsum("bhn,bhpn->bhp", ch[:, t], hnew)
        return hnew, y

    hT, ys = jax.lax.scan(step, h0.astype(f32), jnp.arange(l))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), hT


def _ssd(cfg: SSMConfig):
    """Dispatch the chunked SSD implementation per config."""
    impl = cfg.impl
    if impl == "auto":
        impl = "pallas" if compat.on_tpu() else "xla"
    if impl == "pallas":
        from repro.kernels import ssd as ssd_kernel

        def f(x, dt, a, b_mat, c_mat, chunk, h0=None):
            return ssd_kernel.ssd_chunked_pallas(
                x, dt, a, b_mat, c_mat, chunk, h0=h0,
                interpret=compat.use_interpret(),
            )

        return f
    return ssd_chunked


def mamba_block(params: dict, x: jnp.ndarray, d_model: int, cfg: SSMConfig
                ) -> jnp.ndarray:
    """Full-sequence Mamba-2 block. x: (B, L, D) -> (B, L, D)."""
    d_in, h, g, n, w = _dims(d_model, cfg)
    proj = x @ params["win_dm"].astype(x.dtype)
    z, xbc, dt_raw = _split_proj(proj, d_in, g, n, h)
    xbc = _causal_conv(xbc, params["conv_w"].astype(x.dtype), params["conv_b_r"].astype(x.dtype))
    xs = xbc[..., :d_in].reshape(*x.shape[:2], h, d_in // h)
    b_mat = xbc[..., d_in : d_in + g * n].reshape(*x.shape[:2], g, n)
    c_mat = xbc[..., d_in + g * n :].reshape(*x.shape[:2], g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias_r"].astype(jnp.float32))
    a = -jnp.exp(params["a_log_r"].astype(jnp.float32))
    y, _ = _ssd(cfg)(xs, dt, a, b_mat, c_mat, cfg.chunk)
    y = y + xs * params["d_skip_r"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(*x.shape[:2], d_in)
    y = layers.rmsnorm(params["norm"], y * jax.nn.silu(z))
    return y @ params["wout_md"].astype(x.dtype)


def init_mamba_cache(d_model: int, cfg: SSMConfig, batch: int, dtype) -> dict:
    d_in, h, g, n, w = _dims(d_model, cfg)
    return {
        "conv": jnp.zeros((batch, w - 1, d_in + 2 * g * n), dtype),
        "ssm": jnp.zeros((batch, h, d_in // h, n), jnp.float32),
    }


def mamba_decode(params: dict, x: jnp.ndarray, d_model: int, cfg: SSMConfig,
                 cache: dict) -> Tuple[jnp.ndarray, dict]:
    """One-token step. x: (B, 1, D)."""
    d_in, h, g, n, w = _dims(d_model, cfg)
    bsz = x.shape[0]
    proj = x[:, 0] @ params["win_dm"].astype(x.dtype)             # (B, ...)
    z, xbc, dt_raw = _split_proj(proj, d_in, g, n, h)
    # conv over [cache, new]
    hist = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)  # (B, w, C)
    wgt = params["conv_w"].astype(x.dtype)
    conv_out = jnp.einsum("bwc,wc->bc", hist, wgt) + params["conv_b_r"].astype(x.dtype)
    xbc = jax.nn.silu(conv_out)
    xs = xbc[:, :d_in].reshape(bsz, h, d_in // h)
    b_mat = xbc[:, d_in : d_in + g * n].reshape(bsz, g, n)
    c_mat = xbc[:, d_in + g * n :].reshape(bsz, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias_r"].astype(jnp.float32))
    a = -jnp.exp(params["a_log_r"].astype(jnp.float32))
    rep = h // g
    bh = jnp.repeat(b_mat, rep, axis=1).astype(jnp.float32)
    ch = jnp.repeat(c_mat, rep, axis=1).astype(jnp.float32)
    dec = jnp.exp(dt * a[None, :])
    upd = jnp.einsum("bhn,bhp->bhpn", bh, xs.astype(jnp.float32) * dt[..., None])
    hnew = cache["ssm"] * dec[:, :, None, None] + upd
    y = jnp.einsum("bhn,bhpn->bhp", ch, hnew).astype(x.dtype)
    y = y + xs * params["d_skip_r"].astype(y.dtype)[None, :, None]
    y = y.reshape(bsz, 1, d_in)
    y = layers.rmsnorm(params["norm"], y * jax.nn.silu(z[:, None, :]))
    out = y @ params["wout_md"].astype(x.dtype)
    return out, {"conv": hist[:, 1:], "ssm": hnew}
