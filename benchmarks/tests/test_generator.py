"""The generator is a pure function of (configuration, seed), and a seed
changes the order of the work, never its sizes."""
import numpy as np

from benchmarks.gen import mesh_history


def _arrays(ds):
    return [ds.src, ds.dst, *ds.features, *ds.target_latency, *ds.target_anomaly, *ds.node_mask]


def test_same_seed_same_history(tiny_config):
    a = mesh_history.generate(tiny_config, 2**31 + 7)
    b = mesh_history.generate(tiny_config, 2**31 + 7)
    assert all(np.array_equal(x, y) for x, y in zip(_arrays(a), _arrays(b)))


def test_other_seed_other_order_same_sizes(tiny_config):
    a = mesh_history.generate(tiny_config, 1)
    b = mesh_history.generate(tiny_config, 2)
    assert not np.array_equal(a.features[0], b.features[0])
    assert not np.array_equal(a.dst, b.dst)
    n = tiny_config["endpoints"]
    for ds in (a, b):
        assert len(ds.features) == tiny_config["slots"]
        assert ds.features[0].shape == (n, tiny_config["num_features"])
        assert ds.features[0].dtype == np.float32
        assert isinstance(ds.features[0], np.ndarray)  # host arrays, not device
    # the degree multisets are the configuration's, whatever the seed
    out_deg, in_deg = mesh_history.degree_sequences(n, tiny_config["edges"], tiny_config["assumed"])
    for ds in (a, b):
        assert np.array_equal(np.sort(np.bincount(ds.src, minlength=n))[::-1], out_deg)
        assert np.array_equal(np.sort(np.bincount(ds.dst, minlength=n))[::-1], in_deg)


def test_edges_distinct_and_grouped_by_caller(tiny_config):
    ds = mesh_history.generate(tiny_config, 3)
    pairs = set(zip(ds.src.tolist(), ds.dst.tolist()))
    assert len(pairs) == tiny_config["edges"] == len(ds.src)
    assert not np.any(ds.src == ds.dst)
    assert np.all(np.diff(ds.src) >= 0)


def test_every_slot_has_the_same_counts_so_pos_weight_is_seed_free(tiny_config):
    from benchmarks.reference.train import pos_weight

    weights = set()
    for seed in (1, 2, 3):
        ds = mesh_history.generate(tiny_config, seed)
        for mask, anomaly in zip(ds.node_mask, ds.target_anomaly):
            assert mask.sum() == 243  # round(0.95 * 256)
            assert (anomaly * mask).sum() == anomaly.sum() == 24  # round(0.10 * 243)
        weights.add(pos_weight(ds))
        weights.add(pos_weight(mesh_history.head(ds, 3)))
    assert weights == {243 / 24}


def test_skew_a_few_callees_collect_most_edges():
    assumed = {"in_degree": {"exponent": 1.0, "offset": 10}, "out_degree": {"sigma": 1.0}}
    out_deg, in_deg = mesh_history.degree_sequences(100_000, 500_000, assumed)
    assert out_deg.sum() == in_deg.sum() == 500_000
    assert in_deg[:1000].sum() > 0.5 * 500_000  # 1% of endpoints, half the in-edges
    assert in_deg[0] > 1000 * in_deg[50_000]
