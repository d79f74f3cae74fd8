"""Training loop: grad accumulation, compression hooks, checkpoints, metrics.

``make_train_step`` builds the jitted step for a (config, context, optimizer)
triple.  Microbatch gradient accumulation runs as a ``lax.scan`` so the
bucketed gradient reduction of microbatch *i* overlaps the compute of
*i+1* under XLA's scheduler (compute/comm overlap at the step level).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.optim import grad_compress as GC
from repro.optim.optimizers import Optimizer


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray
    err_state: Any = None          # gradient-compression error feedback

    def tree(self):
        t = {"params": self.params, "opt_state": self.opt_state,
             "step": self.step}
        if self.err_state is not None:
            t["err_state"] = self.err_state
        return t


def init_state(cfg, key, optimizer: Optimizer, dtype=jnp.float32,
               max_seq=4096, compress: Optional[str] = None) -> TrainState:
    params = M.init_params(cfg, key, dtype, max_seq=max_seq)
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
        err_state=GC.init_error_state(params) if compress else None)


def make_train_step(cfg, ctx: M.Ctx, optimizer: Optimizer,
                    accum_steps: int = 1, compress: Optional[str] = None,
                    media_fn: Optional[Callable] = None):
    """Returns step(state_tree, tokens, labels, *extras) -> (state, metrics).

    tokens/labels: [accum, B_micro, S] when accum_steps > 1, else [B, S].
    """
    def loss_fn(params, tokens, labels, extras):
        kwargs = dict(extras)
        return M.lm_loss(cfg, params, tokens, labels, ctx, **kwargs)

    def step(state: Dict, tokens, labels, extras):
        params = state["params"]

        if accum_steps > 1:
            def micro(acc, inp):
                tok, lab = inp
                (loss, mets), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, tok, lab, extras)
                acc_g, acc_l = acc
                acc_g = jax.tree.map(jnp.add, acc_g, grads)
                return (acc_g, acc_l + loss), mets

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), mets = jax.lax.scan(
                micro, (zeros, jnp.zeros(())), (tokens, labels))
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss_sum / accum_steps
            metrics = {k: v[-1] for k, v in mets.items()}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, tokens, labels, extras)

        if compress:
            grads, err = GC.compress_grads(grads, state["err_state"],
                                           mode=compress)
        new_params, new_opt, opt_mets = optimizer.update(
            grads, state["opt_state"], params, state["step"])
        out = {"params": new_params, "opt_state": new_opt,
               "step": state["step"] + 1}
        if compress:
            out["err_state"] = err
        metrics = {"loss": loss, **metrics, **opt_mets}
        return out, metrics

    return step


def train_loop(cfg, state: TrainState, step_fn, data_iter, n_steps: int,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
               log_every: int = 10, extras: Optional[Dict] = None,
               log_fn=print):
    """Simple host-side loop used by examples/ and launch/train.py.

    The state tree is donated to each step, so its buffers are reused for
    the next state (the arrays in ``state`` are invalid afterwards; use the
    returned tree).  Returns (tree, last metrics), with the per-step losses
    as ``metrics["loss_history"]`` [n_steps].
    """
    from repro.ckpt import checkpoint as CK
    jitted = jax.jit(step_fn, donate_argnums=(0,))
    tree = state.tree()
    pending = None
    losses = []
    t0 = time.time()
    for i in range(n_steps):
        tokens, labels = next(data_iter)
        tree, metrics = jitted(tree, tokens, labels, extras or {})
        losses.append(metrics["loss"])
        if log_every and (i + 1) % log_every == 0:
            loss = float(metrics["loss"])
            rate = (i + 1) / (time.time() - t0)
            log_fn(f"step {int(tree['step'])}: loss={loss:.4f} "
                   f"({rate:.2f} steps/s)")
        if ckpt_dir and ckpt_every and (i + 1) % ckpt_every == 0:
            if pending is not None:
                pending.join()
            pending = CK.save_async(ckpt_dir, tree, int(tree["step"]))
    if pending is not None:
        pending.join()
    return tree, {**metrics, "loss_history": jnp.stack(losses)}
