"""Driver of the federation-training cells.

Set-up makes the CGM twin and the weights from the seed, builds one
``GluADFL`` with the plan ``launch/train.py`` resolves, and drives its
compiled ``train_chunk`` once: that call compiles and runs the first
chunk of rounds, which the correctness check compares with the plain
reference.  The same trainer and state then run whole chunks, each
ending in a host sync of its losses, until ``seconds`` have passed.
``rounds_per_s`` is the rounds of those chunks over their time.

Checked against ``bench/reference/gluadfl_lstm.py`` after the window:
the first chunk's per-round losses, and per leaf the norm of Adam's
first moment (the gradient as the optimizer holds it) and of the
parameters' change over the chunk.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import cgm, harness

WEIGHTS, ROUNDS = 0, 1  # seed streams


def init_weights(key, num_nodes: int, hidden: int, input_size: int):
    """Per-node LSTM weights (the program's layout and init scales):
    normal projections over sqrt(fan-in), zero biases but the forget
    gate's at 1."""
    import jax
    import jax.numpy as jnp

    def one(k):
        k1, k2, k3, _ = jax.random.split(k, 4)
        b = jnp.zeros((4 * hidden,)).at[hidden : 2 * hidden].set(1.0)
        return {
            "wx": jax.random.normal(k1, (input_size, 4 * hidden)) / np.sqrt(input_size),
            "wh": jax.random.normal(k2, (hidden, 4 * hidden)) / np.sqrt(hidden),
            "b": b,
            "w_out": jax.random.normal(k3, (hidden, 1)) / np.sqrt(hidden),
            "b_out": jnp.zeros((1,)),
        }

    return jax.vmap(one)(jax.random.split(key, num_nodes))


def leaf_norms(tree) -> dict:
    import jax.numpy as jnp

    return {k: jnp.linalg.norm(v.reshape(-1)) for k, v in tree.items()}


def norm_gap(prog: dict, ref: dict, ref_grad: dict) -> float:
    """Worst leaf's gap between the two norms, over the reference's norm
    of that leaf or of the median leaf, whichever is larger.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out."""
    med = float(np.median(list(ref.values())))
    gmed = float(np.median(list(ref_grad.values())))
    keep = [k for k in ref if ref_grad[k] >= 1e-3 * gmed]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def make_trainer(cell: harness.Cell):
    """The ``GluADFL`` trainer with the plan ``launch/train.py`` resolves."""
    from repro.config.base import FLConfig
    from repro.core import GluADFL
    from repro.launch.mesh import choose_gossip_repr
    from repro.models import LSTMModel
    from repro.optim import adam

    cfg, tr = cell.config, cell.traffic
    model = LSTMModel(history_len=cfg["history_len"], hidden=cfg["hidden"],
                      input_size=cfg["input_size"]).as_model()
    fl = FLConfig(
        topology=tr["topology"], num_nodes=cfg["num_nodes"], comm_batch=cfg["comm_batch"],
        local_steps=cfg["local_steps"], inactive_ratio=tr["inactive_ratio"],
        schedule=tr["schedule"],
    )
    repr_ = cfg["gossip_repr"]
    if repr_ == "auto":
        repr_ = choose_gossip_repr(cfg["num_nodes"], cfg["comm_batch"])
    return GluADFL(model, adam(cfg["lr"]), fl, mixer=cfg["mixer"],
                   gossip_impl=cfg["gossip_impl"], gossip_repr=repr_)


def make_inputs(cell: harness.Cell, trainer, seed: int):
    """Device data, initial state and keys, all from the seed."""
    import jax
    import jax.numpy as jnp
    from repro.core.gluadfl import FLState

    cfg = cell.config
    fed = cgm.federation(
        cfg["dataset"], num_nodes=cfg["num_nodes"], days=cfg["num_days"], seed=seed,
        history_len=cfg["history_len"], horizon=cfg["horizon"],
    )
    data = tuple(jax.device_put(a) for a in (fed.x, fed.y, fed.counts))

    def make_state(kw, kr):
        params = init_weights(kw, cfg["num_nodes"], cfg["hidden"], cfg["input_size"])
        return FLState(
            params=params,
            opt_state=jax.vmap(trainer.optimizer.init)(params),
            staleness=jnp.zeros((cfg["num_nodes"],), jnp.float32),
            round=jnp.zeros((), jnp.int32),
            key=kr,
        )

    keys = harness.seed_key(seed, WEIGHTS), harness.seed_key(seed, ROUNDS)
    return data, jax.jit(make_state)(*keys), keys


def reference(cell: harness.Cell, keys, data, rounds: int, dtype=None):
    """The plain reference's ``rounds`` rounds from the same seed: losses
    and per-leaf norms of Adam's first moment and of the change."""
    import jax
    import jax.numpy as jnp
    from bench.reference.gluadfl_lstm import run_rounds

    cfg = cell.config
    kw, kr = keys
    init = jax.jit(lambda k: init_weights(k, cfg["num_nodes"], cfg["hidden"], cfg["input_size"]))
    losses, p, m = run_rounds(
        init(kw), kr, *data, rounds=rounds, config=cfg, traffic=cell.traffic,
        dtype=dtype or jnp.float32,
    )
    norms = jax.jit(lambda p, m, k: (
        leaf_norms(m), leaf_norms(jax.tree.map(jnp.subtract, p, init(k)))))
    return jax.device_get((losses,) + norms(p, m, kw))


def compare(cell: harness.Cell, prog: tuple, ref: tuple) -> dict:
    """The numbers compared, each beside its limit."""
    (pl, pm, pd), (rl, rm, rd) = prog, ref
    rl = np.asarray(rl, np.float64)
    loss_gap = float(np.max(np.abs(np.asarray(pl, np.float64) - rl) / np.abs(rl)))
    lim = cell.limits
    return {
        "loss_gap": harness.check(loss_gap, lim["loss_gap"]),
        "grad_gap": harness.check(norm_gap(pm, rm, rm), lim["grad_gap"]),
        "update_gap": harness.check(norm_gap(pd, rd, rm), lim["update_gap"]),
    }


def first_chunk(cell: harness.Cell, trainer, data, state, keys):
    """Drive the window's own call once from the seed: the compile and the
    checked rounds.  Returns the state and the program's readings."""
    import jax
    import jax.numpy as jnp

    cfg = cell.config
    state, losses = trainer.train_chunk(state, *data, batch_size=cfg["batch_size"],
                                        chunk=cfg["chunk"])
    losses = np.asarray(losses)

    @jax.jit
    def readings(params, m, kw):
        p0 = init_weights(kw, cfg["num_nodes"], cfg["hidden"], cfg["input_size"])
        return leaf_norms(m), leaf_norms(jax.tree.map(jnp.subtract, params, p0))

    m_norms, d_norms = jax.device_get(readings(state.params, state.opt_state["m"], keys[0]))
    return state, (losses, m_norms, d_norms)


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool, t0: float,
        devices) -> dict:
    cfg = cell.config
    chunk, bs = cfg["chunk"], cfg["batch_size"]
    trainer = make_trainer(cell)
    data, state, keys = make_inputs(cell, trainer, seed)
    state, prog = first_chunk(cell, trainer, data, state, keys)
    setup_s = time.perf_counter() - t0

    tracer = harness.Tracer(trace)
    rounds, bad = 0, 0
    with harness.CompileCounter() as compiles:
        compiles.armed = True
        tracer.start()
        start = time.perf_counter()
        while True:
            with tracer.span("chunk.dispatch"):
                state, losses = trainer.train_chunk(state, *data, batch_size=bs, chunk=chunk)
            with tracer.span("chunk.sync"):
                losses = np.asarray(losses)
            rounds += chunk
            bad += int(np.sum(~np.isfinite(losses)))
            elapsed = time.perf_counter() - start
            if elapsed >= seconds:
                break
        tracer.stop()
        compiles.armed = False
    peak = harness.memory_peak(devices)
    reduced = tracer.reduce(cell.chips)
    del state, trainer
    print(f"bench: {rounds} rounds in {elapsed:.3f} s, {compiles.count} compiles in the window",
          file=sys.stderr)

    ref = reference(cell, keys, data, chunk)
    return {
        "end_to_end": {"rounds_per_s": rounds / elapsed, "setup_s": setup_s},
        "ctx": {"train_window": {"rounds": rounds, "seconds": elapsed}},
        "trace": reduced,
        "memory_peak_bytes": peak,
        "attempted": rounds,
        "failed": bad,
        "checks": compare(cell, prog, ref),
    }


def readings(cell: harness.Cell, seed: int) -> dict:
    """The checked numbers of one seed, without a measured window, and
    what they are made of: each round's relative loss gap and each leaf's
    gaps."""
    trainer = make_trainer(cell)
    data, state, keys = make_inputs(cell, trainer, seed)
    state, prog = first_chunk(cell, trainer, data, state, keys)
    del state, trainer
    ref = reference(cell, keys, data, cell.config["chunk"])
    out = {k: c["value"] for k, c in compare(cell, prog, ref).items()}
    (pl, pm, pd), (rl, rm, rd) = prog, ref
    rl = np.asarray(rl, np.float64)
    out["round_loss_gaps"] = (np.abs(np.asarray(pl, np.float64) - rl) / np.abs(rl)).tolist()
    out["leaf_gaps"] = {
        k: [float(abs(pm[k] - rm[k]) / rm[k]), float(abs(pd[k] - rd[k]) / rd[k]),
            float(rm[k]), float(rd[k])]
        for k in rm
    }
    return out
