"""The CGM twin generator, copied into the benchmark as its yardstick.

A copy of ``repro.data.synth.generate_patient_series``, the windowing of
``repro.data.windowing`` and the assembly of
``repro.data.pipeline.load_federated_dataset``, so that what the benchmark
feeds the program cannot change with the program.  Two departures, both
in the assembly and neither in the values:

* node arrays are padded to a fixed row count (the most train windows a
  series of the given length can give), not to the largest count of the
  draw, so every seed yields the same shapes and compiles the same
  programs;
* the test split is kept per patient as windows and raw targets only
  where a caller asks for it.

The two per-sample recurrences (the insulin-like correction and the AR(1)
noise) run as ``scipy.signal.lfilter`` over the whole series: the same
multiply and add in the same order as the original loops, bit for bit,
about forty times faster.  ``bench/tests/test_bench_cgm.py`` pins this copy
against the program's loader.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
from scipy.signal import lfilter

SAMPLES_PER_DAY = 288  # 5-minute CGM sampling


@dataclass(frozen=True)
class DatasetSpec:
    name: str
    num_patients: int
    num_days: int
    mean_bg: float
    mean_bg_sd: float
    sd_bg: float
    sd_bg_sd: float
    missing_rate: float
    meal_irregularity: float
    seed_base: int


# the paper's Table 1 population statistics
DATASET_SPECS: dict[str, DatasetSpec] = {
    "ohiot1dm": DatasetSpec("ohiot1dm", 12, 54, 159.35, 16.34, 58.11, 6.15, 0.04, 0.6, 101),
    "abc4d": DatasetSpec("abc4d", 25, 168, 156.66, 24.24, 60.52, 14.47, 0.05, 1.0, 202),
    "ctr3": DatasetSpec("ctr3", 30, 163, 151.37, 13.34, 55.29, 8.24, 0.03, 0.5, 303),
    "replace-bg": DatasetSpec("replace-bg", 226, 251, 160.69, 21.18, 60.33, 11.65, 0.04, 0.7, 404),
}


def patient_series(spec: DatasetSpec, patient: int, *, days: int, seed: int) -> np.ndarray:
    """One patient's CGM trace in mg/dL, shape (days*288,), NaN = missing."""
    rng = np.random.default_rng(np.random.SeedSequence([spec.seed_base, patient, seed]))
    n = days * SAMPLES_PER_DAY
    t = np.arange(n) / SAMPLES_PER_DAY

    basal = rng.normal(spec.mean_bg, spec.mean_bg_sd)
    target_sd = max(20.0, rng.normal(spec.sd_bg, spec.sd_bg_sd))
    phase = rng.uniform(0, 2 * np.pi)
    circ_amp = rng.uniform(5.0, 15.0)
    g = basal + circ_amp * np.sin(2 * np.pi * t + phase) + 0.4 * circ_amp * np.sin(
        4 * np.pi * t + 1.7 * phase
    )

    # meals: gamma-shaped responses to ~3 impulses a day
    k = np.arange(48, dtype=np.float64)
    kernel = (k / 5.0) ** 2 * np.exp(-k / 14.0)
    kernel /= kernel.max()
    impulses = np.zeros(n)
    for day in range(days):
        n_meals = max(1, rng.poisson(3))
        if spec.meal_irregularity > 0.8:
            base_times = rng.uniform(0, 1, size=n_meals)
        elif n_meals <= 3:
            base_times = np.array([0.3, 0.55, 0.8])[:n_meals] + rng.normal(
                0, 0.03 * spec.meal_irregularity, size=min(n_meals, 3)
            )
        else:
            base_times = rng.uniform(0.2, 0.9, size=n_meals) + rng.normal(
                0, 0.03 * spec.meal_irregularity, size=n_meals
            )
        for bt in np.atleast_1d(base_times):
            idx = int((day + min(max(float(bt), 0.0), 0.999)) * SAMPLES_PER_DAY)
            impulses[idx] += rng.gamma(4.0, 20.0) * (0.7 + 0.6 * spec.meal_irregularity)
    meal_bg = np.convolve(impulses, kernel)[:n]

    # insulin-like correction toward basal
    alpha = 0.015 * (1.5 - 0.5 * spec.meal_irregularity)
    level = lfilter([1.0], [1.0, -(1 - alpha)], meal_bg * alpha * 2.2)
    corrected = meal_bg - np.minimum(level, meal_bg * 0.8)
    g = g + corrected

    # AR(1) noise
    eps = rng.normal(0, 1, n)
    ar = lfilter([1.0], [1.0, -0.92], eps)
    g = g + ar * np.sqrt(1 - 0.92**2) * 12.0

    g = (g - g.mean()) * (target_sd / max(g.std(), 1e-6)) + basal
    g = np.clip(g, 40.0, 400.0)

    # sensor dropouts: contiguous gaps of 6 samples
    miss = rng.uniform(0, 1, n) < spec.missing_rate / 6
    g[np.convolve(miss.astype(float), np.ones(6))[:n] > 0] = np.nan
    return g.astype(np.float32)


def split_by_time(series: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """60/20/20 train/val/test by time."""
    n = len(series)
    a, b = int(n * 0.6), int(n * 0.8)
    return series[:a], series[a:b], series[b:]


def windows(norm: np.ndarray, raw: np.ndarray, history_len: int, horizon: int):
    """Sliding (M, L) histories, the normalized target ``horizon`` samples
    after each, and its raw value; windows whose target is missing are
    dropped, missing history stays zero."""
    m = len(norm) - history_len - horizon + 1
    if m <= 0:
        z = np.zeros((0,), np.float32)
        return np.zeros((0, history_len), np.float32), z, z
    idx = np.arange(m)[:, None] + np.arange(history_len)[None, :]
    tgt = np.arange(m) + history_len + horizon - 1
    valid = ~np.isnan(raw[tgt])
    return (
        norm[idx][valid].astype(np.float32),
        norm[tgt][valid].astype(np.float32),
        raw[tgt][valid].astype(np.float32),
    )


def max_train_windows(days: int, history_len: int, horizon: int) -> int:
    """The most train windows a ``days``-long series can give: the fixed
    padded row count of every draw."""
    return int(days * SAMPLES_PER_DAY * 0.6) - history_len - horizon + 1


@dataclass
class Federation:
    x: np.ndarray        # (N, M, L) float32, M = max_train_windows
    y: np.ndarray        # (N, M) float32
    counts: np.ndarray   # (N,) int32 real rows per node
    test_x: list         # per patient (Mte, L) normalized histories
    mean: float
    sd: float


def federation(
    dataset: str, *, num_nodes: int, days: int, seed: int,
    history_len: int = 12, horizon: int = 6,
) -> Federation:
    """The twin of ``dataset``: ``num_nodes`` patients of ``days`` days,
    z-scored with the train splits' pooled mean and SD (NaN -> 0 after
    normalizing), windowed and padded to a fixed row count."""
    spec = DATASET_SPECS[dataset]
    raw = [patient_series(spec, p, days=days, seed=seed) for p in range(num_nodes)]
    splits = [split_by_time(s) for s in raw]
    pooled = np.concatenate([tr for tr, _, _ in splits])
    mean = float(np.nanmean(pooled))
    sd = max(float(np.nanstd(pooled)), 1e-6)
    norm = lambda s: np.nan_to_num((s - mean) / sd, nan=0.0)

    m = max_train_windows(days, history_len, horizon)
    x = np.zeros((num_nodes, m, history_len), np.float32)
    y = np.zeros((num_nodes, m), np.float32)
    counts = np.zeros((num_nodes,), np.int32)
    test_x = []
    for i, (tr, _, te) in enumerate(splits):
        wx, wy, _ = windows(norm(tr), tr, history_len, horizon)
        x[i, : len(wx)] = wx
        y[i, : len(wy)] = wy
        counts[i] = len(wx)
        test_x.append(windows(norm(te), te, history_len, horizon)[0])
    return Federation(x, y, counts, test_x, mean, sd)
