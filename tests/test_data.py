import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import (ClientDataset, dirichlet_partition,
                        heterogeneity_stats, make_classification,
                        make_lm_domains)


@given(alpha=st.sampled_from([0.1, 1.0, 10.0]), n=st.sampled_from([4, 8, 16]),
       seed=st.integers(0, 20))
@settings(max_examples=12, deadline=None)
def test_partition_disjoint_and_complete(alpha, n, seed):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=800)
    parts = dirichlet_partition(labels, n, alpha, seed=seed)
    allidx = np.concatenate(parts)
    assert len(allidx) == len(labels)
    assert len(np.unique(allidx)) == len(labels)  # disjoint
    assert all(len(p) >= 2 for p in parts)


def test_partition_unsatisfiable_min_raises():
    """Regression: the old ``while True`` looped forever when
    ``min_per_client`` could not be met.  n_clients > n_samples /
    min_per_client must raise immediately instead of hanging."""
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 3, size=10)
    with pytest.raises(ValueError, match="unsatisfiable"):
        dirichlet_partition(labels, n_clients=8, alpha=0.1, min_per_client=2)


def test_partition_bounded_retries_report_best_minimum():
    """Satisfiable-in-principle but practically unreachable draws terminate
    after ``max_retries`` with the achieved minimum in the message."""
    rng = np.random.default_rng(1)
    labels = rng.integers(0, 2, size=20)
    with pytest.raises(ValueError, match="achieved minimum"):
        dirichlet_partition(labels, n_clients=10, alpha=0.005,
                            min_per_client=2, max_retries=3)


def test_partition_retry_seed_reproducible():
    """Same seed -> same partition, including across the retry path."""
    rng = np.random.default_rng(2)
    labels = rng.integers(0, 10, size=400)
    a = dirichlet_partition(labels, 8, 0.1, seed=7)
    b = dirichlet_partition(labels, 8, 0.1, seed=7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_alpha_controls_heterogeneity():
    """Smaller alpha -> more skewed clients (higher mean TV distance)."""
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=4000)
    tvs = {}
    for alpha in (0.1, 1.0, 10.0):
        parts = dirichlet_partition(labels, 16, alpha, seed=1)
        tvs[alpha] = heterogeneity_stats(labels, parts)["mean_tv"]
    assert tvs[0.1] > tvs[1.0] > tvs[10.0]


def test_client_dataset_batches():
    x, y = make_classification(n=256, hw=8)
    parts = dirichlet_partition(y, 4, 1.0, seed=0)
    ds = ClientDataset((x, y), parts, batch=16)
    xb, yb = ds.next_batch()
    assert xb.shape == (4, 16, 8, 8, 3)
    assert yb.shape == (4, 16)
    # batches reshuffle across epochs without error even for small parts
    for _ in range(30):
        ds.next_batch()


def test_classification_learnable_structure():
    x, y = make_classification(n=512, hw=8, noise=0.1)
    # nearest-prototype classification on clean-ish data beats chance by a lot
    protos = np.stack([x[y == c].mean(0) for c in range(10)])
    d = ((x[:, None] - protos[None]) ** 2).sum((2, 3, 4))
    acc = (d.argmin(1) == y).mean()
    assert acc > 0.9


def test_lm_domains_distinct():
    toks, dom = make_lm_domains(n_domains=3, vocab=64, seq_len=32,
                                n_seq_per_domain=32)
    assert toks.shape == (96, 33)
    assert toks.max() < 64 and toks.min() >= 0
    # different domains produce different bigram statistics
    def big(d):
        t = toks[dom == d]
        m = np.zeros((64, 64))
        for row in t:
            for a, b in zip(row[:-1], row[1:]):
                m[a, b] += 1
        return m / m.sum()
    tv01 = 0.5 * np.abs(big(0) - big(1)).sum()
    assert tv01 > 0.3


def _lm_domains_row_scan(n_domains, *, vocab, seq_len, n_seq_per_domain,
                         skew=8.0, seed=0):
    """The generator as first written: each next token by a pass over its
    whole row of the cumulative transition table."""
    rng = np.random.default_rng(seed)
    tokens, domain = [], []
    for d in range(n_domains):
        cum = np.cumsum(rng.dirichlet(np.full(vocab, 1.0 / skew),
                                      size=vocab), axis=1)
        toks = np.empty((n_seq_per_domain, seq_len + 1), np.int32)
        cur = rng.integers(0, vocab, size=n_seq_per_domain)
        toks[:, 0] = cur
        u = rng.random(size=(n_seq_per_domain, seq_len))
        for t in range(seq_len):
            cur = np.minimum((cum[cur] < u[:, t:t + 1]).sum(axis=1),
                             vocab - 1)
            toks[:, t + 1] = cur
        tokens.append(toks)
        domain.append(np.full(n_seq_per_domain, d, np.int32))
    return np.concatenate(tokens), np.concatenate(domain)


@pytest.mark.parametrize("kw", [
    dict(n_domains=3, vocab=64, seq_len=32, n_seq_per_domain=16, seed=1),
    dict(n_domains=2, vocab=4096, seq_len=16, n_seq_per_domain=64, seed=5),
    dict(n_domains=2, vocab=1000, seq_len=40, n_seq_per_domain=7, seed=2,
         skew=200.0),
])
def test_lm_domains_search_matches_row_scan(kw):
    """The binary search over each transition row gives the very tokens the
    row scan gave, so every seeded stream is unchanged."""
    want = _lm_domains_row_scan(**kw)
    got = make_lm_domains(**kw)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
