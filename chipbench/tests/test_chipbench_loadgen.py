"""The copied load generator: deterministic, a fixed count of requests,
and the program's Zipf seed distribution."""
import numpy as np

from chipbench.lib import loadgen

MIX = {"zipf": 1.1, "hot_fraction": 0.05}


def test_same_seed_same_stream():
    a = loadgen.open_loop(5000, MIX, 300.0, 4.0, 2**31 + 11)
    b = loadgen.open_loop(5000, MIX, 300.0, 4.0, 2**31 + 11)
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.seeds, b.seeds)


def test_every_seed_offers_the_same_count_in_the_window():
    for seed in (0, 1, 2**33 + 5):
        s = loadgen.open_loop(5000, MIX, 300.0, 4.0, seed)
        assert len(s.due) == 1200
        assert np.all(np.diff(s.due) >= 0)
        assert s.due[0] >= 0 and s.due[-1] < 4.0
    a = loadgen.open_loop(5000, MIX, 300.0, 4.0, 1)
    b = loadgen.open_loop(5000, MIX, 300.0, 4.0, 2)
    assert not np.array_equal(a.seeds, b.seeds)


def test_bursts_fall_in_the_on_phases():
    mix = {**MIX, "burst": {"on_s": 0.5, "off_s": 1.5}}
    s = loadgen.open_loop(5000, mix, 200.0, 8.0, 3)
    assert len(s.due) == 1600
    assert np.all(np.mod(s.due, 2.0) < 0.5)


def test_zipf_seeds_match_the_program_generator():
    from repro.serving.loadgen import zipf_seeds

    ours = loadgen.zipf_seeds(10_000, 500, zipf=1.1, hot_fraction=0.05,
                              rng=np.random.default_rng(7))
    np.testing.assert_array_equal(ours, zipf_seeds(10_000, 500, seed=7))
    counts = np.bincount(ours)
    assert counts.max() > 20          # a hot set dominates
