"""The chunked WKV of RWKV-6 prefill against the recurrence token by token,
and the prefill program's loops: none over the prompt's tokens."""
import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scaled_down
from repro.models import model as M
from repro.models import rwkv6 as RW


def _token_loop(r, k, v, lw, u, s0):
    s, ys = s0, []
    for t in range(r.shape[1]):
        y, s = RW.wkv_step(r[:, t], k[:, t], v[:, t], jnp.exp(lw[:, t]), u, s)
        ys.append(y)
    return jnp.stack(ys, 1), s


def _inputs(S, lw_range, seed):
    B, H, hd = 2, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks[:3])
    lw = jax.random.uniform(ks[3], (B, S, H, hd), minval=lw_range[0],
                            maxval=lw_range[1])
    u = jax.random.normal(ks[4], (H, hd))
    s0 = jax.random.normal(ks[5], (B, H, hd, hd))
    return r, k, v, lw, u, s0


# (S, chunk, block, log-decay range): S never a multiple of the chunk but in
# the last case; decays near 1 (a token kept 1 - 1e-6 a step), near 0
# (e^-14 to e^-7 a step), wide, and strong enough that a chunk's log-sum
# reaches -2500, where exp(-L) alone would overflow float32
CASES = [
    (37, 16, 4, (-1e-6, -1e-7)),
    (37, 16, 4, (-14.0, -7.0)),
    (100, 64, 16, (-3.0, -0.01)),
    (5, 64, 16, (-1.0, -0.1)),
    (128, 64, 16, (-40.0, -20.0)),
]


def _chunked(monkeypatch, chunk, block, *args, remat=False):
    monkeypatch.setattr(RW, "CHUNK", chunk)
    monkeypatch.setattr(RW, "BLOCK", block)
    with jax.default_matmul_precision("highest"):
        return RW.wkv(*args, remat=remat)


def _check_against_token_loop(y, s, r, k, v, lw, u, s0):
    with jax.default_matmul_precision("highest"):
        y_ref, s_ref = _token_loop(r, k, v, lw, u, s0)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(s).all())
    # float32 summed in another order: relative 2e-6 of the largest value,
    # 50x the rounding that the sums of up to 128 terms can give
    # (measured: 4e-5 absolute at outputs near 84)
    tol = 2e-6 * float(jnp.abs(y_ref).max())
    np.testing.assert_allclose(y, y_ref, atol=tol, rtol=0)
    np.testing.assert_allclose(s, s_ref,
                               atol=2e-6 * float(jnp.abs(s_ref).max()), rtol=0)


@pytest.mark.parametrize("S,chunk,block,lw_range", CASES)
def test_chunked_wkv_matches_token_loop(S, chunk, block, lw_range,
                                        monkeypatch):
    args = _inputs(S, lw_range, seed=S)
    _check_against_token_loop(*_chunked(monkeypatch, chunk, block, *args),
                              *args)


@pytest.mark.parametrize("S,chunk,block,lw_range", CASES)
def test_training_wkv_matches_token_loop(S, chunk, block, lw_range,
                                         monkeypatch):
    """Training's form, one chunk at a time, gives the same y and state."""
    args = _inputs(S, lw_range, seed=S)
    _check_against_token_loop(
        *_chunked(monkeypatch, chunk, block, *args, remat=True), *args)


def test_training_wkv_gradient_matches_prefill_form(monkeypatch):
    """The gradients of the two forms agree (S not a multiple of the chunk,
    so the padded tail is differentiated too)."""
    args = _inputs(100, (-3.0, -0.01), seed=7)
    y_w, s_w = (jax.random.normal(jax.random.PRNGKey(9), a.shape)
                for a in (args[0], args[5]))

    def loss(remat, *a):
        y, s = _chunked(monkeypatch, 64, 16, *a, remat=remat)
        return jnp.sum(y * y_w) + jnp.sum(s * s_w)

    g_pre = jax.grad(lambda *a: loss(False, *a), argnums=range(6))(*args)
    g_tr = jax.grad(lambda *a: loss(True, *a), argnums=range(6))(*args)
    for a, b in zip(g_tr, g_pre):
        assert bool(jnp.isfinite(a).all())
        # float32 in another order, as above
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=2e-6 * float(jnp.abs(b).max()))


def _scan_lengths(jaxpr, out):
    """Every scan's trip count in ``jaxpr`` and the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else [p]):
                if isinstance(sub, jcore.ClosedJaxpr):
                    _scan_lengths(sub.jaxpr, out)
                elif isinstance(sub, jcore.Jaxpr):
                    _scan_lengths(sub, out)
    return out


def test_prefill_loops_over_chunks_not_tokens():
    cfg = scaled_down(get_config("rwkv6-3b"))
    S = 512
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, S), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t: M.prefill(cfg, p, t, S))(shapes, tokens)
    lengths = _scan_lengths(jaxpr.jaxpr, [])
    # the scan over the layers, and in it one over the prompt's chunks
    assert sorted(set(lengths)) == sorted({cfg.n_units, S // RW.CHUNK})


def _chunk_scans(jaxpr, nc, out):
    """(length, holds a checkpoint) of every scan in ``jaxpr``, nested."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == nc:
            body = eqn.params["jaxpr"].jaxpr
            out.append(any(e.primitive.name in ("checkpoint", "remat2")
                           for e in body.eqns))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else [p]):
                if isinstance(sub, jcore.ClosedJaxpr):
                    _chunk_scans(sub.jaxpr, nc, out)
                elif isinstance(sub, jcore.Jaxpr):
                    _chunk_scans(sub, nc, out)
    return out


@pytest.mark.parametrize("remat", [True, False])
def test_training_recomputes_one_chunk_at_a_time(remat):
    """With ``Ctx(remat=True)`` training's WKV steps chunk by chunk inside a
    checkpoint, so the backward pass holds one chunk's pairwise decays at a
    time; without it, and in prefill, the chunks' own terms are taken at
    once and the chunk scan carries the state alone."""
    cfg = scaled_down(get_config("rwkv6-3b"))
    S = 256
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k, jnp.float32),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, S), jnp.int32)
    ctx = M.Ctx(remat=remat, ce_chunk=0)
    jaxpr = jax.make_jaxpr(
        lambda p, t: M.lm_loss(cfg, p, t, t, ctx)[0])(shapes, tokens)
    assert _chunk_scans(jaxpr.jaxpr, S // RW.CHUNK, []) == [remat]
