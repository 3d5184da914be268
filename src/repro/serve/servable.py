"""The glucose servable: checkpoint -> personalize -> forecast, with
every device-side method compiled once per padded batch-size bucket.

:class:`GlucoseServable` owns

  * the **population model** (row 0 of the param store) loaded from a
    federation checkpoint (:func:`load_population` infers the LSTM
    width from the flat parameter count, same recovery the checkpoint
    tests use);
  * the **param store** — a stacked pytree of per-patient parameter
    rows.  Cold-start patients are added by
    :meth:`GlucoseServable.personalize`, which runs
    ``core.personalize.personalize_batch`` (one ``lax.scan``-compiled,
    ``vmap``-batched program for the whole cohort) and appends the
    personalized rows;
  * the **forecast method** — ONE ``jax.jit`` whose cache holds exactly
    one executable per configured bucket: requests are padded to the
    smallest fitting bucket (windows with zeros, param rows with the
    last real row) before entering the compiled program, and sliced
    back after.  Rows are independent, so padding never changes a real
    row's forecast — bitwise, pinned by ``tests/test_serve.py`` and the
    launcher's ``--selfcheck``.

The compiled batch runs as ``lax.map`` of the EXACT single-request
program by default (``batch_mode="map"``): XLA lowers a ``vmap``-batched
LSTM differently (batched matmuls, ~1e-8 drift vs a B=1 apply), and the
serving contract here is bit-reproducibility — a forecast must not
depend on who else happened to share the batch.  ``batch_mode="vmap"``
trades that guarantee for row-parallel throughput.  The contract holds
on the CPU and on a TPU v5e alike (``chip_smoke.py`` checks it there at
hidden 128, ``launch/serve.py --selfcheck`` at the checkpoint's width).

The batching POLICY (queueing, admission, timeouts) lives in
``serve.batcher``; :func:`replay` is the deterministic driver that
marries the two for the selfcheck, the latency bench, and the CLI demo.
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.base import Model
from repro.optim import Optimizer, adam
from repro.serve.batcher import MicroBatcher, Request, bucket_for
from repro.utils.pytree import tree_to_vector, vector_to_tree

PyTree = Any

# widths the checkpoint loader tries when recovering the LSTM hidden
# size from a flat parameter count (matches tests/test_checkpoint.py)
KNOWN_HIDDEN = (4, 8, 16, 32, 64, 128, 256)

DEFAULT_BUCKETS = (1, 4, 16, 64)


def load_population(
    path, *, hidden: int | None = None, history_len: int = 12
) -> tuple[Model, PyTree]:
    """Load a federation checkpoint (``launch/train.py`` .npz format:
    flat ``vec`` + shape ``meta``) into ``(model, population_params)``.

    With ``hidden=None`` the LSTM width is recovered from the flat
    parameter count (the checkpoint stores only shapes, not configs) by
    trying :data:`KNOWN_HIDDEN`; a count matching no known width raises
    instead of guessing.
    """
    from repro.models import LSTMModel

    vec = np.load(Path(path), allow_pickle=False)["vec"]
    if hidden is None:
        for h in KNOWN_HIDDEN:
            m = LSTMModel(history_len=history_len, hidden=h).as_model()
            like = m.init(jax.random.PRNGKey(0))
            if int(tree_to_vector(like).shape[0]) == len(vec):
                return m, vector_to_tree(jnp.asarray(vec), like)
        raise ValueError(
            f"{path}: {len(vec)} params match no LSTM width in "
            f"{KNOWN_HIDDEN} — pass hidden= explicitly"
        )
    model = LSTMModel(history_len=history_len, hidden=hidden).as_model()
    like = model.init(jax.random.PRNGKey(0))
    if int(tree_to_vector(like).shape[0]) != len(vec):
        raise ValueError(
            f"{path}: {len(vec)} params but LSTMModel(hidden={hidden}) "
            f"has {int(tree_to_vector(like).shape[0])}"
        )
    return model, vector_to_tree(jnp.asarray(vec), like)


class GlucoseServable:
    """A loaded population model served through padded-bucket batching.

    ``buckets`` are the ONLY batch shapes the jitted forecast method
    ever compiles: a request batch of size n runs at the smallest
    bucket >= n (padded), and batches beyond the largest bucket are
    split.  ``personalize_steps``/``personalize_batch_size`` configure
    the cold-start fine-tune (``core.personalize`` semantics: uniform
    with-replacement draws from the patient's real windows, batch
    clamped to short histories).  ``batch_mode`` picks the batch
    lowering: ``"map"`` (default) is bitwise the single-request apply,
    ``"vmap"`` is the row-parallel throughput variant (~1e-8 drift).
    """

    def __init__(
        self,
        model: Model,
        population_params: PyTree,
        *,
        optimizer: Optimizer | None = None,
        buckets: tuple[int, ...] = DEFAULT_BUCKETS,
        personalize_steps: int = 100,
        personalize_batch_size: int = 32,
        batch_mode: str = "map",
    ):
        buckets = tuple(sorted({int(b) for b in buckets}))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"need >= 1 positive bucket size, got {buckets!r}")
        if batch_mode not in ("map", "vmap"):
            raise ValueError(f"batch_mode must be 'map' or 'vmap', got {batch_mode!r}")
        self.batch_mode = batch_mode
        self.model = model
        self.buckets = buckets
        self.optimizer = optimizer or adam(5e-4)
        self.personalize_steps = personalize_steps
        self.personalize_batch_size = personalize_batch_size
        # param store: row 0 is ALWAYS the population model (the
        # brand-new-patient fallback); personalize() appends rows
        self._store: PyTree = jax.tree.map(lambda l: l[None], population_params)
        self._names: dict[Any, int] = {"population": 0}
        # one jit object = one cache; it compiles exactly once per
        # (bucket,) padded shape.  compiled_buckets tracks which padded
        # shapes have entered the cache (introspection for tests/ops).
        self._forecast_jit = jax.jit(self._forecast_impl)
        self.compiled_buckets: set[int] = set()
        self._personalize_fns: dict[int, Callable] = {}

    # --------------------------------------------------------- params
    @property
    def population(self) -> PyTree:
        return jax.tree.map(lambda l: l[0], self._store)

    @property
    def num_rows(self) -> int:
        return int(jax.tree.leaves(self._store)[0].shape[0])

    def row_of(self, name) -> int:
        """Param-store row of a personalized patient (KeyError if the
        patient was never personalized — callers wanting the population
        fallback use ``.get``-style ``row_of_or_population``)."""
        return self._names[name]

    def row_of_or_population(self, name) -> int:
        return self._names.get(name, 0)

    def params_rows(self, rows) -> PyTree:
        """Gather (B,)-indexed param rows from the store — the eager
        pre-processing step; the gathered stack is what enters the
        compiled forecast."""
        rows = jnp.asarray(rows)
        return jax.tree.map(lambda l: l[rows], self._store)

    # ----------------------------------------------------- personalize
    def personalize(self, names, keys, x, y, counts) -> PyTree:
        """Cold-start a cohort: fine-tune the population model on each
        patient's own (padded) history as ONE compiled batched program,
        append the personalized rows to the param store, and return the
        stacked params.

        ``names`` label the cohort for :meth:`row_of`; ``keys (P, 2)``,
        ``x (P, M, L)``, ``y (P, M)``, ``counts (P,)`` follow the
        federation layout.  The per-(M, P) jitted program is cached, so
        cohort after cohort of the same shape compiles once.
        """
        from repro.core.personalize import personalize_batch_fn

        x = jnp.asarray(x)
        m = x.shape[1]
        if m not in self._personalize_fns:
            self._personalize_fns[m] = personalize_batch_fn(
                self.model,
                self.optimizer,
                steps=self.personalize_steps,
                batch_size=self.personalize_batch_size,
                n_rows=m,
            )
        params, _ = self._personalize_fns[m](
            self.population, jnp.asarray(keys), x, jnp.asarray(y),
            jnp.asarray(counts),
        )
        base = self.num_rows
        self._store = jax.tree.map(
            lambda s, p: jnp.concatenate([s, p], axis=0), self._store, params
        )
        for i, name in enumerate(names):
            self._names[name] = base + i
        return params

    # -------------------------------------------------------- forecast
    def _forecast_impl(self, params_batch: PyTree, windows: jnp.ndarray):
        """(B, ...) per-request params x (B, L) windows -> (B,) BG
        forecasts; rows are independent, which is what makes pad rows
        inert.  ``batch_mode="map"`` lowers each row as the EXACT B=1
        apply (bitwise the direct call); ``"vmap"`` lowers one batched
        program (faster, ~1e-8 drift on the LSTM matmuls)."""

        def one(p, w):
            return self.model.apply(p, w[None, :])[0]

        if self.batch_mode == "vmap":
            return jax.vmap(one)(params_batch, windows)
        return jax.lax.map(lambda pw: one(*pw), (params_batch, windows))

    def _pad_forecast(self, params_batch: PyTree, windows: jnp.ndarray, n: int):
        b = bucket_for(n, self.buckets)
        if n < b:
            pad = b - n
            windows = jnp.concatenate(
                [windows, jnp.zeros((pad,) + windows.shape[1:], windows.dtype)]
            )
            params_batch = jax.tree.map(
                lambda l: jnp.concatenate(
                    [l, jnp.broadcast_to(l[-1:], (pad,) + l.shape[1:])]
                ),
                params_batch,
            )
        self.compiled_buckets.add(b)
        return self._forecast_jit(params_batch, windows)[:n]

    def forecast(self, params_batch: PyTree, windows) -> jnp.ndarray:
        """BG forecasts for a batch of (per-request params row, CGM
        window) pairs, padded to the smallest fitting bucket; batches
        larger than the biggest bucket are split into full-bucket
        chunks.  Returns the (B,) normalized forecasts (denormalize
        with the dataset's mean/sd for mg/dL)."""
        windows = jnp.asarray(windows)
        if windows.ndim != 2:
            raise ValueError(f"windows must be (B, L), got {windows.shape}")
        n = windows.shape[0]
        cap = self.buckets[-1]
        if n <= cap:
            return self._pad_forecast(params_batch, windows, n)
        outs = []
        for lo in range(0, n, cap):
            hi = min(lo + cap, n)
            chunk = jax.tree.map(lambda l: l[lo:hi], params_batch)
            outs.append(self._pad_forecast(chunk, windows[lo:hi], hi - lo))
        return jnp.concatenate(outs)

    def forecast_rows(self, rows, windows) -> jnp.ndarray:
        """Convenience: gather store rows, then :meth:`forecast`."""
        return self.forecast(self.params_rows(rows), jnp.asarray(windows))

    def warmup(self, history_len: int = 12) -> None:
        """Pre-compile the forecast executable for EVERY bucket so the
        first real request never pays a trace (saxml-style).  The LSTM
        scans any window length, but the compiled SHAPE is per-L — pass
        the history length real requests will carry."""
        for b in self.buckets:
            rows = jnp.zeros((b,), jnp.int32)
            self._pad_forecast(
                self.params_rows(rows), jnp.zeros((b, history_len), jnp.float32), b
            )


def replay(
    servable: GlucoseServable,
    batcher: MicroBatcher,
    requests: Iterable[Request],
    *,
    drain: bool = True,
) -> dict[int, float]:
    """Deterministic serving loop: submit the request stream in order,
    run every batch the batcher forms (pad-to-bucket inside
    ``servable.forecast``), and return ``{rid: forecast}``.

    Batches execute synchronously as they form, so ``max_live_batches``
    never blocks here — this driver exercises formation, padding, and
    accounting (the admission edge cases are unit-tested with a fake
    clock instead).  With ``drain=True`` the queued tail is flushed
    after the stream ends, timeout or not.
    """
    preds: dict[int, float] = {}

    def run(batch):
        rows = [r.patient for r in batch]
        windows = np.stack([r.window for r in batch])
        out = np.asarray(servable.forecast_rows(rows, windows))
        batcher.complete(batch)
        for r, p in zip(batch, out):
            preds[r.rid] = float(p)

    for req in requests:
        batcher.submit(req)
        while (batch := batcher.ready()) is not None:
            run(batch)
    while drain and (batch := batcher.flush()) is not None:
        run(batch)
    return preds
