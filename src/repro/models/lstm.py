"""Single-layer LSTM glucose predictor (the paper's model, §3.2).

A univariate CGM history (B, L) is embedded per step, run through one
LSTM layer (lax.scan of a fused cell), and the last hidden state is
projected to the H-step-ahead glucose level.

The cell math lives in ``repro.kernels.lstm_cell``'s reference path so the
Pallas kernel and the model share one definition; the model defaults to
the pure-jnp path (CPU) and can be switched to the Pallas kernel with
``use_kernel=True`` (interpret mode on CPU, compiled on TPU).

The backward pass of the default path is written out (``lstm_last_h``, a
``jax.custom_vjp``).  Its forward is the same scan of ``lstm_cell_ref``,
which also keeps, per step, ``h_{t-1}``, ``c_{t-1}``, the four gates and
``tanh(c_t)``: as many stacks as autodiff of the scan keeps.  The reverse
loop carries only ``(dh, dc)``: each step forms the gate gradient
``dz_t`` (B, 4H), passes ``dz_t @ Whᵀ`` back, and emits ``dz_t`` with its
two batch reductions (``x_tᵀ dz_t`` and ``Σ_b dz_t``, each (I or 1, 4H)).
``dWh = Σ_t h_{t-1}ᵀ dz_t`` is then one contraction over time × batch,
and ``dWx`` and ``db`` are sums of the L small rows.  Autodiff of the scan
would instead carry ``dWx``, ``dWh`` and ``db`` through the reverse loop,
so every step reads and rewrites a weight-shaped accumulator, per node
once the loss is vmapped over a federation; the contraction reads each
``dz_t`` once and writes ``dWh`` once.  The ``use_kernel`` path is
forward-only and differentiates, where it can, by autodiff.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.base import Model


def _cell(x_t, h, c, wx, wh, b):
    """``lstm_cell_ref``, and the activations its backward needs:
    the gates ``(i, f, g, o)`` and ``tanh(c_new)``."""
    z = x_t @ wx + h @ wh + b
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c_new = f * c + i * g
    tc = jnp.tanh(c_new)
    return o * tc, c_new, (i, f, g, o, tc)


def lstm_cell_ref(x_t, h, c, wx, wh, b):
    """One LSTM step: gates ordered (i, f, g, o).  Shapes:
    x_t (B, I), h/c (B, H), wx (I, 4H), wh (H, 4H), b (4H,).
    """
    h_new, c_new, _ = _cell(x_t, h, c, wx, wh, b)
    return h_new, c_new


@jax.custom_vjp
def lstm_last_h(wx, wh, b, xs):
    """The last hidden state (B, H) of an LSTM run from zero state over
    ``xs`` (L, B, I): a scan of ``lstm_cell_ref``."""
    h = jnp.zeros((xs.shape[1], wh.shape[0]), xs.dtype)

    def step(carry, x_t):
        return lstm_cell_ref(x_t, *carry, wx, wh, b), None

    (h, _), _ = jax.lax.scan(step, (h, h), xs)
    return h


def _lstm_last_h_fwd(wx, wh, b, xs):
    h = jnp.zeros((xs.shape[1], wh.shape[0]), xs.dtype)

    def step(carry, x_t):
        h_prev, c_prev = carry
        h, c, acts = _cell(x_t, h_prev, c_prev, wx, wh, b)
        return (h, c), (h_prev, c_prev) + acts

    (h, _), stacks = jax.lax.scan(step, (h, h), xs)
    return h, (wx, wh, xs, stacks)


def _lstm_last_h_bwd(res, dh):
    wx, wh, xs, stacks = res
    h_prev, acts = stacks[0], stacks[1:]

    def step(carry, t):
        dh, dc = carry
        c_prev, i, f, g, o, tc = (a[t] for a in acts)
        dc = dc + dh * o * (1 - tc * tc)
        dz = jnp.concatenate([
            dc * g * i * (1 - i),
            dc * c_prev * f * (1 - f),
            dc * i * (1 - g * g),
            dh * tc * o * (1 - o),
        ], axis=-1)
        return (dz @ wh.T, dc * f), (dz, xs[t].T @ dz, dz.sum(0))

    # Steps L-1 … 0, with the outputs stacked in that order: a scan with
    # ``reverse=True`` would write its stack back to front, and XLA fills
    # such a stack with zeros first.
    steps = jnp.arange(xs.shape[0] - 1, -1, -1)
    _, (dz, dwx, db) = jax.lax.scan(step, (dh, jnp.zeros_like(dh)), steps)
    return (dwx.sum(0),
            jnp.einsum("lbh,lbg->hg", h_prev[::-1], dz),
            db.sum(0),
            jnp.einsum("lbg,ig->lbi", dz, wx)[::-1])


lstm_last_h.defvjp(_lstm_last_h_fwd, _lstm_last_h_bwd)


@dataclass(frozen=True)
class LSTMModel:
    history_len: int = 12
    hidden: int = 128
    input_size: int = 1
    use_kernel: bool = False

    def init(self, key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        H, I = self.hidden, self.input_size
        scale_x = 1.0 / jnp.sqrt(I)
        scale_h = 1.0 / jnp.sqrt(H)
        b = jnp.zeros((4 * H,))
        # forget-gate bias 1.0 (standard LSTM init)
        b = b.at[H : 2 * H].set(1.0)
        return {
            "wx": jax.random.normal(k1, (I, 4 * H)) * scale_x,
            "wh": jax.random.normal(k2, (H, 4 * H)) * scale_h,
            "b": b,
            "w_out": jax.random.normal(k3, (H, 1)) * scale_h,
            "b_out": jnp.zeros((1,)),
        }

    def apply(self, params, x):
        """x: (B, L) normalized glucose -> (B,) prediction."""
        xs = jnp.swapaxes(x[..., None], 0, 1)  # (L, B, 1) univariate input
        if self.use_kernel:
            from repro.kernels.ops import lstm_cell as cell_op

            def step(carry, x_t):
                h, c = carry
                h, c = cell_op(x_t, h, c, params["wx"], params["wh"], params["b"])
                return (h, c), None

            h = jnp.zeros((x.shape[0], self.hidden), x.dtype)
            (h, _), _ = jax.lax.scan(step, (h, h), xs)
        else:
            h = lstm_last_h(params["wx"], params["wh"], params["b"], xs)
        out = h @ params["w_out"] + params["b_out"]
        return out[:, 0]

    def as_model(self) -> Model:
        return Model("lstm", self.init, self.apply)
