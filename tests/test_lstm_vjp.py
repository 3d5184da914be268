"""The LSTM's written-out backward (``lstm_last_h``, a custom VJP)
against autodiff of the plain scan of ``lstm_cell_ref``: the same
gradients, the same forward, and no weight-shaped carry in the reverse
loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import LSTMModel
from repro.models.lstm import lstm_cell_ref, lstm_last_h

NODES = 3
SHAPES = [(8, 12, 16, 1), (5, 3, 8, 2), (64, 12, 128, 1)]


def plain_last_h(wx, wh, b, xs):
    """``lstm_last_h``'s forward as a plain scan, left to autodiff."""
    h = jnp.zeros((xs.shape[1], wh.shape[0]), xs.dtype)

    def step(carry, x_t):
        return lstm_cell_ref(x_t, *carry, wx, wh, b), None

    (h, _), _ = jax.lax.scan(step, (h, h), xs)
    return h


def loss_of(last_h):
    """The model's squared error on histories ``xs`` (L, B, I), with the
    LSTM run by ``last_h``."""
    def loss(params, xs, y):
        h = last_h(params["wx"], params["wh"], params["b"], xs)
        out = (h @ params["w_out"] + params["b_out"])[:, 0]
        return jnp.mean((out - y) ** 2)

    return loss


def plain_apply(params, x):
    """``LSTMModel.apply`` with the plain scan."""
    h = plain_last_h(params["wx"], params["wh"], params["b"], jnp.swapaxes(x[..., None], 0, 1))
    return (h @ params["w_out"] + params["b_out"])[:, 0]


def setup(B, L, H, I, nodes=None):
    """Parameters, histories (L, B, I) and targets, per node if ``nodes``."""
    init = LSTMModel(history_len=L, hidden=H, input_size=I).init
    kp, kx, ky = jax.random.split(jax.random.PRNGKey(0), 3)
    lead = () if nodes is None else (nodes,)
    params = init(kp) if nodes is None else jax.vmap(init)(jax.random.split(kp, nodes))
    xs = jax.random.normal(kx, lead + (L, B, I))
    y = jax.random.normal(ky, lead + (B,))
    return params, xs, y


def assert_close(got, want, rtol=1e-5):
    """Every leaf within ``rtol`` of the largest magnitude of ``want``'s."""
    assert set(got) == set(want)
    for leaf in want:
        g, w = np.asarray(got[leaf]), np.asarray(want[leaf])
        assert g.shape == w.shape, leaf
        gap = np.max(np.abs(g - w)) / np.max(np.abs(w))
        assert gap <= rtol, (leaf, gap)


@pytest.mark.parametrize("B,L,H,I", SHAPES)
@pytest.mark.parametrize("mode", ["jit", "vmap"])
def test_gradients_match_autodiff_of_the_plain_scan(B, L, H, I, mode):
    """All five leaves, under ``jit`` and under ``vmap`` over nodes."""
    params, xs, y = setup(B, L, H, I, nodes=NODES if mode == "vmap" else None)
    got_fn, want_fn = jax.grad(loss_of(lstm_last_h)), jax.grad(loss_of(plain_last_h))
    if mode == "vmap":
        got_fn, want_fn = jax.vmap(got_fn), jax.vmap(want_fn)
    got = jax.jit(got_fn)(params, xs, y)
    assert set(got) == {"wx", "wh", "b", "w_out", "b_out"}
    assert_close(got, jax.jit(want_fn)(params, xs, y))


@pytest.mark.parametrize("B,L,H,I", SHAPES[:2])
def test_input_gradient_matches_autodiff(B, L, H, I):
    """The cotangent of the histories, which training never asks for."""
    params, xs, y = setup(B, L, H, I)
    got = jax.grad(loss_of(lstm_last_h), argnums=1)(params, xs, y)
    want = jax.grad(loss_of(plain_last_h), argnums=1)(params, xs, y)
    assert_close({"xs": got}, {"xs": want})


@pytest.mark.parametrize("mode", ["eager", "jit"])
def test_forward_is_bitwise_the_plain_scan(mode):
    """``LSTMModel.apply``, the serving forward, is unchanged bit for bit."""
    model = LSTMModel(hidden=32)
    params = model.init(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 12))
    fwd, plain = model.apply, plain_apply
    if mode == "jit":
        fwd, plain = jax.jit(fwd), jax.jit(plain)
    np.testing.assert_array_equal(np.asarray(fwd(params, x)), np.asarray(plain(params, x)))


def scan_carries(jaxpr):
    """The carry shapes of every ``scan`` in ``jaxpr``, nested jaxprs
    included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            found.append([v.aval.shape for v in eqn.invars[nc:nc + nk]])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(scan_carries(sub))
    return found


@pytest.mark.parametrize("B,L,H", [(8, 12, 16), (4, 5, 8)])
def test_no_loop_carries_a_weight_shaped_leaf(B, L, H):
    """The gradient of ``LSTMModel.apply``'s loss has two loops, forward
    and reverse, and both carry ``(h, c)``-shaped leaves only; autodiff of
    the plain scan, the control, carries the ``(H, 4H)`` weight gradient
    through its reverse loop."""
    model = LSTMModel(history_len=L, hidden=H)
    params = model.init(jax.random.PRNGKey(0))
    x, y = jnp.zeros((B, L)), jnp.zeros((B,))

    def carries(apply):
        loss = lambda p: jnp.mean((apply(p, x) - y) ** 2)
        return scan_carries(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)

    mine, control = carries(model.apply), carries(plain_apply)
    assert mine == [[(B, H), (B, H)]] * 2, mine
    assert any((H, 4 * H) in shapes for shapes in control), control
