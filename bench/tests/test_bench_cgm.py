"""The benchmark's copy of the CGM twin is deterministic per seed and
equals the program's loader, padding aside."""
import numpy as np
import pytest

from bench import cgm


def test_deterministic_per_seed():
    a = cgm.federation("ohiot1dm", num_nodes=3, days=2, seed=2**33 + 5)
    b = cgm.federation("ohiot1dm", num_nodes=3, days=2, seed=2**33 + 5)
    c = cgm.federation("ohiot1dm", num_nodes=3, days=2, seed=5)
    assert np.array_equal(a.x, b.x) and np.array_equal(a.counts, b.counts)
    assert not np.array_equal(a.x, c.x)
    assert a.x.shape == c.x.shape == (3, cgm.max_train_windows(2, 12, 6), 12)


@pytest.mark.parametrize("seed,fast,patients", [
    (0, True, 20), (77, True, 20), (2**33 + 7, False, 3),
])
def test_copy_equals_program_loader(seed, fast, patients):
    """``fast`` is the program's 6-day series; without it the full 251 days
    the benchmark's configurations hold."""
    from repro.data.pipeline import load_federated_dataset

    days = 6 if fast else cgm.DATASET_SPECS["replace-bg"].num_days
    ref = load_federated_dataset("replace-bg", fast=fast, max_patients=patients, seed=seed)
    got = cgm.federation("replace-bg", num_nodes=patients, days=days, seed=seed)
    m = ref.x.shape[1]
    assert np.array_equal(got.x[:, :m], ref.x)
    assert np.array_equal(got.y[:, :m], ref.y)
    assert not got.x[:, m:].any() and not got.y[:, m:].any()
    assert np.array_equal(got.counts, ref.counts)
    assert (got.mean, got.sd) == (ref.mean, ref.sd)
    for tx, p in zip(got.test_x, ref.patients):
        assert np.array_equal(tx, p.test_x)
