"""Plain reference of a GluADFL federation training a one-layer LSTM.

Written from the paper's Algorithm 1 and the program's stated semantics,
importing nothing of the program: each round draws the participating
nodes (Bernoulli, at least one), a random graph (each node picks B peers
by the top scores of a uniform draw, made undirected), keeps each active
node's B lowest-index active neighbours, mixes uniformly over
``{self} + kept`` as a dense row-stochastic matrix, then takes one Adam
step per active node on a with-replacement batch of its own windows,
with the gradient taken at the pre-mix parameters and applied to the
mixed ones.  Inactive nodes keep their parameters and optimizer state.

The random draws follow the same ``jax.random`` key schedule as the
program, so both see the same nodes, graphs and batches.  Everything is
computed in ``dtype`` (float32 by default) with matmuls at ``highest``
precision; ``dtype=jnp.bfloat16`` gives the lower-precision control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

B1, B2, EPS = 0.9, 0.999, 1e-8


def lstm_forward(p, x, precision):
    """(Bt, L) histories -> (Bt,) forecasts; gates ordered i, f, g, o."""
    hidden = p["wh"].shape[0]
    h = jnp.zeros((x.shape[0], hidden), x.dtype)
    c = jnp.zeros((x.shape[0], hidden), x.dtype)
    for t in range(x.shape[1]):
        z = (
            jnp.dot(x[:, t : t + 1], p["wx"], precision=precision)
            + jnp.dot(h, p["wh"], precision=precision)
            + p["b"]
        )
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return (jnp.dot(h, p["w_out"], precision=precision) + p["b_out"])[:, 0]


def mixing_matrix(adj, active, comm_batch):
    """Dense row-stochastic mixing: active rows average self and their B
    lowest-index active neighbours, inactive rows are identity rows."""
    n = adj.shape[0]
    eye = jnp.eye(n, dtype=jnp.float32)
    neigh = adj * active[None, :]
    kept = neigh * (jnp.cumsum(neigh, axis=1) <= comm_batch)
    w = kept + eye
    return jnp.where(active[:, None] > 0, w / jnp.sum(w, axis=1, keepdims=True), eye)


def make_round(config, traffic, dtype=jnp.float32):
    """One federation round, ``(state, x, y, counts) -> (state, loss)``,
    with ``state = (params, adam_m, adam_v, adam_step, key)``."""
    n = config["num_nodes"]
    b = config["comm_batch"]
    bs = config["batch_size"]
    lr = config["lr"]
    ratio = traffic["inactive_ratio"]
    degree = min(b, n - 1)
    prec = jax.lax.Precision.HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT

    def node_update(k, p_pre, p_mix, m, v, step, xn, yn, count):
        idx = jax.random.randint(jax.random.split(k, 1)[0], (bs,), 0, jnp.maximum(count, 1))
        bx, by = xn[idx], yn[idx]
        loss, g = jax.value_and_grad(
            lambda p: jnp.mean(jnp.square(lstm_forward(p, bx, prec) - by))
        )(p_pre)
        t = step + 1
        m = jax.tree.map(lambda m_, g_: B1 * m_ + (1 - B1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: B2 * v_ + (1 - B2) * jnp.square(g_), v, g)
        # the bias corrections are schedule constants: float32, then dtype
        bc1 = (1 - B1 ** t.astype(jnp.float32)).astype(dtype)
        bc2 = (1 - B2 ** t.astype(jnp.float32)).astype(dtype)
        p_new = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + EPS)),
            p_mix, m, v,
        )
        return p_new, m, v, t, loss

    blocks = config.get("reference_blocks", 1)
    split = lambda a: a.reshape((blocks, n // blocks) + a.shape[1:])
    merge = lambda a: a.reshape((n,) + a.shape[2:])

    def one_round(carry, x, y, counts):
        p, m, v, step, k = carry
        k, k_act, k_top, k_batch = jax.random.split(k, 4)
        if ratio > 0:
            u = jax.random.uniform(k_act, (n,))
            active = (u >= ratio).astype(jnp.float32)
            active = jnp.where(jnp.max(active) > 0, active,
                               jax.nn.one_hot(jnp.argmax(u), n, dtype=jnp.float32))
        else:
            active = jnp.ones((n,), jnp.float32)
        scores = jax.random.uniform(k_top, (n, n)) - 2.0 * jnp.eye(n)
        _, peers = jax.lax.top_k(scores, degree)
        adj = jnp.zeros((n, n), jnp.float32).at[jnp.arange(n)[:, None], peers].set(1.0)
        adj = jnp.maximum(adj, adj.T)
        mix = mixing_matrix(adj, active, b).astype(dtype)

        def block(args):
            # nodes in blocks, so that the backward pass's activations of
            # all nodes never live at once
            mix_b, keys_b, p_b, m_b, v_b, step_b, x_b, y_b, c_b, act_b = args
            mixed_b = jax.tree.map(
                lambda l: jnp.tensordot(mix_b, l, axes=1, precision=prec).astype(dtype), p
            )
            new = jax.vmap(node_update)(keys_b, p_b, mixed_b, m_b, v_b, step_b, x_b, y_b, c_b)
            keep = lambda nw, od: jnp.where(
                act_b.reshape(act_b.shape + (1,) * (nw.ndim - 1)) > 0, nw, od
            )
            kept = tuple(jax.tree.map(keep, nw, od)
                         for nw, od in zip(new[:4], (p_b, m_b, v_b, step_b)))
            return kept + (new[4].astype(jnp.float32),)

        args = (mix, jax.random.split(k_batch, n), p, m, v, step, x, y, counts, active)
        out = jax.lax.map(block, jax.tree.map(split, args))
        p2, m2, v2, step2, losses = jax.tree.map(merge, out)
        loss = jnp.sum(losses * active) / jnp.maximum(jnp.sum(active), 1.0)
        return (p2, m2, v2, step2, k), loss

    return one_round


def run_rounds(params, key, x, y, counts, *, rounds, config, traffic, dtype=jnp.float32):
    """``rounds`` federation rounds from stacked ``params`` (leaves
    ``(N, ...)``, donated) and the round key ``key``.  Returns ``(losses
    (rounds,), params, adam_m)``, all float32."""
    cast = lambda t: jax.tree.map(lambda l: l.astype(dtype), t)
    params, x, y = cast(params), x.astype(dtype), y.astype(dtype)
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)
    step0 = jnp.zeros((config["num_nodes"],), jnp.int32)
    # one compiled round per call, its state donated: the reference's
    # state lives once on the chip
    round_fn = jax.jit(make_round(config, traffic, dtype), donate_argnums=0)
    carry = (params, zeros(), zeros(), step0, jnp.array(key, copy=True))
    losses = []
    for _ in range(rounds):
        carry, loss = round_fn(carry, x, y, counts)
        losses.append(loss)
    f32 = lambda t: jax.tree.map(lambda l: l.astype(jnp.float32), t)
    return jnp.stack(losses), f32(carry[0]), f32(carry[1])
