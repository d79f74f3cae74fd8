"""RWKV-6 "Finch" block (arXiv:2404.05892, Section 4; RWKV-LM's
``RWKV_Tmix_x060`` and ``RWKV_CMix_x060``): time-mix + channel-mix.

Time-mix.  Each of the five inputs w, k, v, r, g mixes x_t with x_{t-1}
by a per-channel amount that depends on the data ("ddlerp"):

    dx  = x_{t-1} - x_t
    m_* = (tanh((x_t + dx * maa_x) W1) W2)_*          (LoRA, rank 32)
    x_* = x_t + dx * (maa_* + m_*)

The decay is data-dependent too, w_t = exp(-exp(w0 + tanh(x_w A) B))
(LoRA, rank 64, in f32), and the recurrence per head (state S in
R^{hd x hd}, f32) is

    S_t[i,j] = w_t[i] * S_{t-1}[i,j] + k_t[i] * v_t[j]
    y_t[j]   = sum_i r_t[i] * (S_{t-1}[i,j] + u[i] * k_t[i] * v_t[j])

followed by a per-head GroupNorm (eps = norm_eps * head_size_divisor²),
the silu(g) gate and the output projection.  Channel-mix: static token
shift, squared-relu key, sigmoid receptance gate.

Prefill and training run the recurrence in its exact chunked form
(:func:`wkv`): no loop over tokens, one sequential step per chunk.  Decode
takes one token at a time (:func:`wkv_step`).  The Pallas kernel
``repro.kernels.wkv6`` computes the same chunked form in VMEM
(``Ctx(rnn_impl="pallas")``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.sharding import constrain

# chunk: tokens per sequential step of the state recurrence; block: tokens
# whose pairwise decays are taken directly (chunk is a multiple of block).
# A 4096-token prefill took 345 ms on a v5e with 64 and 16, and 371 ms with
# 128 and 16 (r, k, v kept in bf16), though the WKV alone ran faster at 128
# (6.5 against 7.8 ms a layer).
CHUNK = 64
BLOCK = 16


def _uniform(key, shape, lo, hi, dtype):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)


def timemix_params(key, cfg, dtype):
    """Initial values in RWKV-LM's ranges: shift mixes in [0, 1], decay
    base w0 in [-6, -1] (a token's weight kept 0.69 to 0.9975 a step),
    bonus u in [-0.1, 1.1], LoRAs near 0."""
    D, H = cfg.d_model, cfg.n_heads
    hd = D // H
    rm, rd = cfg.mix_lora_rank, cfg.decay_lora_rank
    ks = jax.random.split(key, 13)
    return {
        "maa_x": _uniform(ks[0], (D,), 0.0, 1.0, dtype),
        "maa": _uniform(ks[1], (5, D), 0.0, 1.0, dtype),    # w, k, v, r, g
        "maa_w1": _uniform(ks[2], (D, 5 * rm), -1e-4, 1e-4, dtype),
        "maa_w2": _uniform(ks[3], (5, rm, D), -0.01, 0.01, dtype),
        "w0": _uniform(ks[4], (D,), -6.0, -1.0, jnp.float32),
        "wA": _uniform(ks[5], (D, rd), -1e-4, 1e-4, jnp.float32),
        "wB": _uniform(ks[6], (rd, D), -0.01, 0.01, jnp.float32),
        "u": _uniform(ks[7], (H, hd), -0.1, 1.1, jnp.float32),
        "wr": L.dense_init(ks[8], (D, D), dtype),
        "wk": L.dense_init(ks[9], (D, D), dtype),
        "wv": L.dense_init(ks[10], (D, D), dtype),
        "wg": L.dense_init(ks[11], (D, D), dtype),
        "wo": L.dense_init(ks[12], (D, D), dtype),
        "ln_scale": jnp.ones((D,), dtype),
        "ln_bias": jnp.zeros((D,), dtype),
    }


def timemix_axes(cfg):
    return {"maa_x": ("embed",), "maa": (None, "embed"),
            "maa_w1": ("embed", None), "maa_w2": (None, None, "embed"),
            "w0": ("embed",), "wA": ("embed", None),
            "wB": (None, "embed"), "u": ("heads", "head_dim"),
            "wr": ("embed", "heads"), "wk": ("embed", "heads"),
            "wv": ("embed", "heads"), "wg": ("embed", "heads"),
            "wo": ("heads", "embed"),
            "ln_scale": ("embed",), "ln_bias": ("embed",)}


def channelmix_params(key, cfg, dtype):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    return {
        "maa_k": _uniform(ks[0], (D,), 0.0, 1.0, dtype),
        "maa_r": _uniform(ks[1], (D,), 0.0, 1.0, dtype),
        "wk": L.dense_init(ks[2], (D, F), dtype),
        "wv": L.dense_init(ks[3], (F, D), dtype, fan_in=F),
        "wr": L.dense_init(ks[4], (D, D), dtype),
    }


def channelmix_axes(cfg):
    return {"maa_k": ("embed",), "maa_r": ("embed",), "wk": ("embed", "ffn"),
            "wv": ("ffn", "embed"), "wr": ("embed", "heads")}


def _shift(x, x_prev=None):
    """x_{t-1} along time; first step uses x_prev (decode) or zeros."""
    if x_prev is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
    return jnp.concatenate([x_prev[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(params, x, x_prev):
    """The five data-dependent token shifts, in the order w, k, v, r, g."""
    B, S, _ = x.shape
    dx = x_prev - x
    m = jnp.tanh((x + dx * params["maa_x"]) @ params["maa_w1"])
    m = jnp.einsum("bsfr,frd->fbsd", m.reshape(B, S, 5, -1),
                   params["maa_w2"])
    return [x + dx * (params["maa"][i] + m[i]) for i in range(5)]


def _within(r, k, v, lw, u):
    """The terms of :func:`wkv` inside each chunk.

    r, k, v, lw: [B, n, c, H, hd] f32, n chunks of c tokens.  Returns y
    from the chunks' own tokens [B, n, c, H, hd], cum_x (each token's
    Lx_t), and per chunk what it adds to the state [n, B, H, hd, hd] and
    the state's decay across it [n, B, H, hd].
    """
    B, n, c, H, hd = r.shape
    b = BLOCK
    nb = c // b
    cum = jnp.cumsum(lw, axis=2)
    cum_x = jnp.pad(cum, ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))[:, :, :-1]
    end = cum[:, :, -1]                                    # [B, n, H, hd]

    def blocks(a):
        return a.reshape(B, n, nb, b, H, hd)

    rb, kb, vb, cb, cxb = (blocks(a) for a in (r, k, v, cum, cum_x))
    # pairs within a block
    ti = jnp.arange(b)
    lower = (ti[:, None] > ti[None, :])[:, :, None, None]      # s < t
    expo = cxb[:, :, :, :, None] - cb[:, :, :, None, :]  # [.., t, s, H, hd]
    a_diag = jnp.sum(rb[:, :, :, :, None] * kb[:, :, :, None, :]
                     * jnp.exp(jnp.where(lower, expo, -jnp.inf)), axis=-1)
    y = jnp.einsum("bnjtsh,bnjshe->bnjthe", a_diag, vb).reshape(
        B, n, c, H, hd)
    # pairs across blocks of one chunk
    if nb > 1:
        e_blk = cb[:, :, :, -1]                            # [B, n, nb, H, hd]
        k_hat = kb * jnp.exp(e_blk[:, :, :, None] - cb)
        later = (jnp.arange(c)[None, :] // b
                 > jnp.arange(nb)[:, None])[:, :, None, None]   # [J, t]
        q_hat = r[:, :, None] * jnp.exp(jnp.where(
            later, cum_x[:, :, None] - e_blk[:, :, :, None], -jnp.inf))
        a_off = jnp.einsum("bnjthd,bnjshd->bnthjs", q_hat, k_hat)
        y = y + jnp.einsum("bnthjs,bnjshe->bnthe", a_off, vb)
    # the bonus of the current token
    y = y + jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    ds = jnp.einsum("bnshi,bnshj->nbhij", k * jnp.exp(end[:, :, None] - cum),
                    v)
    return y, cum_x, ds, jnp.exp(end).transpose(1, 0, 2, 3)


def wkv(r, k, v, lw, u, s0, rules=None, remat=False):
    """Exact WKV6 over a sequence, in chunks: no loop over tokens.

    r, k, v: [B, S, H, hd]; lw: [B, S, H, hd], the log of the decay
    (<= 0); u: [H, hd]; s0: [B, H, hd, hd] f32.  Returns
    (y [B, S, H, hd] f32, s_last).

    Time is cut into chunks of ``CHUNK`` tokens and those into blocks of
    ``BLOCK``.  With L_t the decay's log summed from the chunk's start to t
    (inclusive) and Lx_t = L_{t-1}, a token s < t of the same chunk reaches
    t decayed by exp(Lx_t - L_s):
      * within one block directly, pair by pair;
      * from an earlier block J (its last log-sum E_J) as
        exp(Lx_t - E_J) * exp(E_J - L_s), a product of two matrices;
      * from earlier chunks through the state at the chunk's start, read
        out by r_t exp(Lx_t); the state steps chunk by chunk,
        S' = exp(L_end) S + sum_s (k_s exp(L_end - L_s)) v_s^T.
    Every exponent is a log-decay summed over an interval of time, so it is
    <= 0 and nothing overflows however strong the decay.  S need not be a
    multiple of the chunk: the tail is padded with tokens that decay
    nothing and add nothing.

    Prefill takes every chunk's own terms at once.  With ``remat``
    (training) one chunk at a time, recomputed in the backward pass, so
    that the pairwise decays of one chunk, not of the whole sequence, are
    held at once.
    """
    B, S, H, hd = r.shape
    c = min(CHUNK, -(-S // BLOCK) * BLOCK)
    nc = -(-S // c)
    pad = nc * c - S

    def chunks(a):
        a = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return a.reshape(B, nc, c, H, hd)

    def step(s, inp):
        a, d = inp
        s = constrain(s, rules, ("batch", "heads", None, None))
        return a[..., None] * s + d, s

    def read(r, cum_x, starts):
        """What each chunk's tokens read of the state at its start."""
        return jnp.einsum("bnthi,nbhij->bnthj", r * jnp.exp(cum_x), starts)

    r, k, v, lw = (chunks(a) for a in (r, k, v, lw))
    if remat:
        @jax.checkpoint
        def body(s, xs):
            y, cum_x, ds, decay = _within(*(a[:, None] for a in xs), u)
            s_next, s = step(s, (decay[0], ds[0]))
            return s_next, (y + read(xs[0][:, None], cum_x, s[None]))[:, 0]

        s_last, y = jax.lax.scan(body, s0.astype(jnp.float32),
                                 tuple(a.swapaxes(0, 1) for a in (r, k, v, lw)))
        y = y.swapaxes(0, 1)
    else:
        y, cum_x, ds, decay = _within(r, k, v, lw, u)
        s_last, starts = jax.lax.scan(step, s0.astype(jnp.float32),
                                      (decay, ds))
        y = y + read(r, cum_x, starts)
    return y.reshape(B, nc * c, H, hd)[:, :S], s_last


def wkv_step(r, k, v, w, u, s):
    """One decode step; args [B, H, hd]."""
    kv = k[..., :, None] * v[..., None, :]
    y = jnp.einsum("bhi,bhij->bhj", r, s + u[..., None] * kv)
    s = w[..., None] * s + kv
    return y, s


def _heads(x, H):
    B, S, D = x.shape
    return x.reshape(B, S, H, D // H)


def apply_timemix(params, x, *, cfg, rules, state=None, impl="xla",
                  interpret=False, remat=False):
    """x: [B, S, D] -> (y, new_state dict(x_tm [B,D], s [B,H,hd,hd])).

    ``remat``: training's WKV, one chunk at a time (see :func:`wkv`)."""
    B, S, D = x.shape
    H, hd = cfg.n_heads, D // cfg.n_heads
    xs = _shift(x, None if state is None else state["x_tm"])
    with jax.named_scope("ddlerp"):
        xw, xk, xv, xr, xg = _ddlerp(params, x, xs)
    r = _heads(xr @ params["wr"], H)
    k = _heads(xk @ params["wk"], H)
    v = _heads(xv @ params["wv"], H)
    g = xg @ params["wg"]
    with jax.named_scope("decay"):
        # log w = -exp(w0 + lora), in f32
        lora = jnp.tanh(xw.astype(jnp.float32) @ params["wA"]) @ params["wB"]
        lw = _heads(-jnp.exp(params["w0"] + lora), H)
    r = constrain(r, rules, ("batch", "seq", "heads", None))
    s0 = state["s"] if state is not None else \
        jnp.zeros((B, H, hd, hd), jnp.float32)
    with jax.named_scope("wkv"):
        if state is not None and S == 1:
            y, s_last = wkv_step(r[:, 0].astype(jnp.float32),
                                 k[:, 0].astype(jnp.float32),
                                 v[:, 0].astype(jnp.float32),
                                 jnp.exp(lw[:, 0]), params["u"], s0)
            y = y[:, None]
        elif impl == "pallas":
            from repro.kernels import ops as kops
            y, s_last = kops.wkv6(r, k, v, jnp.exp(lw), params["u"], s0,
                                  interpret=interpret)
        else:
            y, s_last = wkv(r, k, v, lw, params["u"], s0, rules=rules,
                            remat=remat)
    # per-head GroupNorm, silu(g) gate, output proj
    eps = cfg.norm_eps * cfg.head_size_divisor ** 2
    yf = y.reshape(B, S, H, hd)
    mu_y = yf.mean(-1, keepdims=True)
    var = jnp.square(yf - mu_y).mean(-1, keepdims=True)
    yf = (yf - mu_y) * jax.lax.rsqrt(var + eps)
    yf = yf.reshape(B, S, D) * params["ln_scale"].astype(jnp.float32) \
        + params["ln_bias"].astype(jnp.float32)
    out = (yf.astype(x.dtype) * jax.nn.silu(g)) @ params["wo"]
    out = constrain(out, rules, ("batch", "seq", "embed"))
    new_state = {"x_tm": x[:, -1, :], "s": s_last}
    return out, new_state


def apply_channelmix(params, x, *, cfg, rules, state=None):
    """x: [B, S, D] -> (y, x_last for the shift state)."""
    with jax.named_scope("channel_mix"):
        xs = _shift(x, None if state is None else state["x_cm"])
        dx = xs - x
        xk = x + dx * params["maa_k"]
        xr = x + dx * params["maa_r"]
        kk = jnp.square(jax.nn.relu(xk @ params["wk"]))
        y = jax.nn.sigmoid(xr @ params["wr"]) * (kk @ params["wv"])
    return constrain(y, rules, ("batch", "seq", "embed")), x[:, -1, :]


def init_state(cfg, batch, dtype):
    H, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    return {"x_tm": jnp.zeros((batch, cfg.d_model), dtype),
            "x_cm": jnp.zeros((batch, cfg.d_model), dtype),
            "s": jnp.zeros((batch, H, hd, hd), jnp.float32)}


def state_axes(cfg):
    return {"x_tm": ("batch", "embed"), "x_cm": ("batch", "embed"),
            "s": ("batch", "heads", None, None)}
