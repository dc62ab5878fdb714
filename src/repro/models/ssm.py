"""Mamba2 / SSD (state-space duality) blocks — arXiv:2405.21060.

EMPA mapping of the chunked SSD algorithm: sequence chunks are child QTs —
each computes its chunk-local output and a chunk summary state in
parallel; the parent carries the inter-chunk recurrence (an associative
scan — the latched parent-child chain of §3.5), and children's
contributions stream into the output without materializing the full
(S × S) semiseparable matrix (SUMUP: "eliminate obsolete read/write-back
stages").  The O(1)-state decode step is what makes the 524k-token
`long_500k` shape runnable at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


def _to_heads(bc, nheads: int):
    """(B, S, G, N) group tensor -> broadcast to (B, S, H, N)."""
    b, s, g, n = bc.shape
    rep = nheads // g
    return jnp.broadcast_to(bc[:, :, :, None, :], (b, s, g, rep, n)) \
              .reshape(b, s, nheads, n)


def ssd_chunked(x, dt, a_log, b_mat, c_mat, d_skip, dt_bias,
                chunk: int = 64, init_state=None, valid=None):
    """Chunked SSD scan.

    x: (B, S, H, P); dt: (B, S, H); a_log: (H,); b_mat/c_mat: (B, S, G, N);
    d_skip: (H,); dt_bias: (H,).  Returns (y (B,S,H,P), state (B,H,P,N)).

    ``valid`` (B, S) bool, optional: the positions that advance the
    state.  At the others the step is taken as dt = 0 with no input, so
    the state passes through them exactly (a row's padding, or a row
    with nothing to consume); their outputs are garbage.  A length that
    is not a multiple of ``chunk`` is padded with such positions.
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        pad = -s % chunk
        if valid is None:
            valid = jnp.ones((bsz, s), bool)

        def grow(a):
            return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        y, state = ssd_chunked(grow(x), grow(dt), a_log, grow(b_mat),
                               grow(c_mat), d_skip, dt_bias, chunk=chunk,
                               init_state=init_state, valid=grow(valid))
        return y[:, :s], state
    nc = s // chunk

    f32 = jnp.float32
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))        # (B,S,H)
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
        x = jnp.where(valid[..., None, None], x, jnp.zeros_like(x))
    a = -jnp.exp(a_log.astype(f32))                                    # (H,)
    da = dt * a                                                        # (B,S,H)
    bh = _to_heads(b_mat, h).astype(f32)
    ch = _to_heads(c_mat, h).astype(f32)
    xdt = x.astype(f32) * dt[..., None]                                # (B,S,H,P)

    # chunk views
    da_c = da.reshape(bsz, nc, chunk, h)
    cum = jnp.cumsum(da_c, axis=2)                                     # (B,C,Q,H)
    cum_last = cum[:, :, -1, :]                                        # (B,C,H)
    b_c = bh.reshape(bsz, nc, chunk, h, n)
    c_c = ch.reshape(bsz, nc, chunk, h, n)
    x_c = xdt.reshape(bsz, nc, chunk, h, p)

    # ---- intra-chunk (children's local work) -------------------------
    # decay L[q, t] = exp(cum_q - cum_t) for t <= q
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]                # (B,C,Q,Q,H)
    q_idx = jnp.arange(chunk)
    mask = (q_idx[:, None] >= q_idx[None, :])[None, None, :, :, None]
    l_mat = jnp.where(mask, jnp.exp(seg), 0.0)
    cb = jnp.einsum("bcqhn,bcthn->bcqth", c_c, b_c)                    # (B,C,Q,Q,H)
    y_intra = jnp.einsum("bcqth,bcqth,bcthp->bcqhp", cb, l_mat, x_c)

    # ---- chunk summary states (children's clone-back) ----------------
    decay_to_end = jnp.exp(cum_last[:, :, None, :] - cum)              # (B,C,Q,H)
    states = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", b_c, decay_to_end, x_c)

    # ---- inter-chunk recurrence (the parent's latched chain) ---------
    chunk_decay = jnp.exp(cum_last)                                    # (B,C,H)

    def combine(left, right):
        d1, s1 = left
        d2, s2 = right
        return d1 * d2, s2 + d2[..., None, None] * s1

    dec_scan, st_scan = jax.lax.associative_scan(
        combine, (chunk_decay, states), axis=1)
    # state *before* each chunk: S_before[c] = st_scan[c-1] +
    # (Π decay of chunks 0..c-1) · init_state   (zero-shift the scan)
    if init_state is None:
        init_state = jnp.zeros((bsz, h, p, n), f32)
    else:
        init_state = init_state.astype(f32)
    carry_in = jnp.concatenate(
        [jnp.ones((bsz, 1, h), f32), dec_scan[:, :-1]], axis=1)
    prev = jnp.concatenate([jnp.zeros_like(st_scan[:, :1]),
                            st_scan[:, :-1]], axis=1) \
        + carry_in[..., None, None] * init_state[:, None]

    y_inter = jnp.einsum("bcqhn,bcqh,bchpn->bcqhp",
                         c_c, jnp.exp(cum), prev)

    y = (y_intra + y_inter).reshape(bsz, s, h, p)
    y = y + x.astype(f32) * d_skip.astype(f32)[None, None, :, None]
    final_state = st_scan[:, -1] + dec_scan[:, -1, :, None, None] * init_state
    return y.astype(x.dtype), final_state


def ssd_decode_step(x, dt, a_log, b_vec, c_vec, d_skip, dt_bias, state):
    """O(1) single-token step.

    x: (B, H, P); dt: (B, H); b_vec/c_vec: (B, G, N); state: (B, H, P, N).
    """
    f32 = jnp.float32
    h = x.shape[1]
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))          # (B,H)
    da = jnp.exp(dt * (-jnp.exp(a_log.astype(f32))))                    # (B,H)
    bh = _to_heads(b_vec[:, None], h)[:, 0].astype(f32)                 # (B,H,N)
    ch = _to_heads(c_vec[:, None], h)[:, 0].astype(f32)
    xdt = x.astype(f32) * dt[..., None]                                 # (B,H,P)
    state = state.astype(f32) * da[..., None, None] \
        + jnp.einsum("bhp,bhn->bhpn", xdt, bh)
    y = jnp.einsum("bhn,bhpn->bhp", ch, state)
    y = y + x.astype(f32) * d_skip.astype(f32)[None, :, None]
    return y.astype(x.dtype), state


# ---------------------------------------------------------------------------
# Causal depthwise conv (the Mamba2 local mixer)
# ---------------------------------------------------------------------------

def causal_conv(x, w, b, width: int, state=None):
    """x: (B, S, C); w: (width, C); b: (C,). Causal depthwise conv.
    ``state`` (B, width-1, C): the inputs before ``x`` (zeros if None)."""
    if state is None:
        pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):
        out = out + pad[:, i:i + x.shape[1]].astype(jnp.float32) \
            * w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def causal_conv_step(x, conv_state, w, b):
    """x: (B, C); conv_state: (B, width-1, C) -> (y (B,C), new_state)."""
    width = w.shape[0]
    window = jnp.concatenate([conv_state, x[:, None]], axis=1)  # (B,width,C)
    y = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                   w.astype(jnp.float32)) + b.astype(jnp.float32)
    return y.astype(x.dtype), window[:, 1:]


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------

def _split_proj(zxbcdt, cfg):
    """Split the fused in-projection into (z gate, conv channels, dt)."""
    di = cfg.d_inner
    gn = cfg.ssm_ngroups * cfg.ssm_state
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * gn]
    dt = zxbcdt[..., 2 * di + 2 * gn:]
    return z, xbc, dt


@jax.named_scope("mamba")
def mamba2_block(x, p, cfg, ssd_fn=ssd_chunked):
    """x: (B, S, d_model) -> (B, S, d_model). Training/prefill path."""
    bsz, s, _ = x.shape
    di, n, g = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups
    h, pdim = cfg.ssm_nheads, cfg.ssm_headdim

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["w_in"])
    z, xbc, dt = _split_proj(zxbcdt, cfg)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"], cfg.ssm_conv))
    xs = xbc[..., :di].reshape(bsz, s, h, pdim)
    b_mat = xbc[..., di:di + g * n].reshape(bsz, s, g, n)
    c_mat = xbc[..., di + g * n:].reshape(bsz, s, g, n)

    y, state = ssd_fn(xs, dt, p["a_log"], b_mat, c_mat, p["d_skip"],
                      p["dt_bias"])
    y = y.reshape(bsz, s, di)
    y = layers.gated_rms_norm(y, z, p["norm_w"])
    return jnp.einsum("bsk,kd->bsd", y, p["w_out"]), state


@jax.named_scope("mamba")
def mamba2_chunk(x, p, cfg, conv_state, ssm_state, lengths):
    """x: (B, C, d_model), one left-aligned fragment per row, against each
    row's conv window (B, width-1, conv_dim) and SSM state (B, H, P, N):
    row b advances both over its first ``lengths[b]`` columns only (0
    leaves them as they were), whatever the width C.  Returns (y,
    conv_state, ssm_state); y past a row's length is garbage."""
    bsz, c, _ = x.shape
    di, n, g = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups
    h, pdim, width = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_conv

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["w_in"])
    z, xbc, dt = _split_proj(zxbcdt, cfg)
    window = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"], width,
                                  state=conv_state))
    # the window after a row's last valid input: its width-1 inputs
    # ending there (a row of length 0 keeps the window it came with)
    idx = lengths[:, None] + jnp.arange(width - 1)[None, :]
    conv_state = jnp.take_along_axis(window, idx[:, :, None], axis=1)
    xs = xbc[..., :di].reshape(bsz, c, h, pdim)
    b_mat = xbc[..., di:di + g * n].reshape(bsz, c, g, n)
    c_mat = xbc[..., di + g * n:].reshape(bsz, c, g, n)
    valid = jnp.arange(c)[None, :] < lengths[:, None]
    y, ssm_state = ssd_chunked(xs, dt, p["a_log"], b_mat, c_mat,
                               p["d_skip"], p["dt_bias"],
                               chunk=cfg.ssm_chunk, init_state=ssm_state,
                               valid=valid)
    y = layers.gated_rms_norm(y.reshape(bsz, c, di), z, p["norm_w"])
    return jnp.einsum("bsk,kd->bsd", y, p["w_out"]), conv_state, ssm_state


@jax.named_scope("mamba")
def mamba2_decode(x, p, cfg, conv_state, ssm_state, advance=None):
    """x: (B, d_model) single token -> (y, conv_state, ssm_state).
    ``advance`` (B,) bool, optional: the rows whose states the step
    advances; the others keep theirs."""
    old_conv, old_ssm = conv_state, ssm_state
    bsz = x.shape[0]
    di, n, g = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups
    h, pdim = cfg.ssm_nheads, cfg.ssm_headdim

    zxbcdt = jnp.einsum("bd,dk->bk", x, p["w_in"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * g * n]
    dt = zxbcdt[..., 2 * di + 2 * g * n:]
    xbc, conv_state = causal_conv_step(xbc, conv_state, p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(bsz, h, pdim)
    b_vec = xbc[..., di:di + g * n].reshape(bsz, g, n)
    c_vec = xbc[..., di + g * n:].reshape(bsz, g, n)
    y, ssm_state = ssd_decode_step(xs, dt, p["a_log"], b_vec, c_vec,
                                   p["d_skip"], p["dt_bias"], ssm_state)
    y = y.reshape(bsz, di)
    y = layers.gated_rms_norm(y, z, p["norm_w"])
    if advance is not None:
        conv_state = jnp.where(advance[:, None, None], conv_state, old_conv)
        ssm_state = jnp.where(advance[:, None, None, None], ssm_state,
                              old_ssm)
    return jnp.einsum("bk,kd->bd", y, p["w_out"]), conv_state, ssm_state


def conv_dim(cfg) -> int:
    return cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state


def proj_dim(cfg) -> int:
    return 2 * cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state + cfg.ssm_nheads


def ssd_flops(batch, seq, cfg, chunk: int = 64) -> float:
    h, p, n = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    nc = seq // chunk
    intra = 2.0 * batch * nc * chunk * chunk * h * (n + p)
    states = 4.0 * batch * seq * h * p * n
    return intra + states
