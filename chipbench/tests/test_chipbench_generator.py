"""The on-device R-MAT generator, run on the CPU: the same seed gives the
same graph, another seed the same structure under other labels, or under
the configuration's own fixed labels where it names a `label_seed`."""
import numpy as np
import pytest

from chipbench.generators import rmat

CONFIG = {"scale": 10, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
          "d": 0.05, "weights": [1, 65535], "symmetrize": False,
          "structure_seed": 1}


def as_set(edges):
    return set(zip(edges.src.tolist(), edges.dst.tolist()))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_same_seed_same_graph(seed):
    one, two = rmat.generate(CONFIG, seed), rmat.generate(CONFIG, seed)
    assert one.num_vertices == 1024
    for a, b in ((one.src, two.src), (one.dst, two.dst),
                 (one.weight, two.weight)):
        np.testing.assert_array_equal(a, b)


def test_seeds_relabel_one_structure():
    base = rmat.generate(CONFIG, 7)
    for seed in (8, 7 + 2**32, 2**31 + 7):
        other = rmat.generate(CONFIG, seed)
        assert other.num_edges == base.num_edges
        assert not np.array_equal(other.src, base.src)
        # the same multiset of degrees: one structure, other labels
        np.testing.assert_array_equal(
            np.sort(np.bincount(other.src, minlength=1024)),
            np.sort(np.bincount(base.src, minlength=1024)))
        assert not np.array_equal(other.weight, base.weight)


def hub(edges):
    return int(np.argmax(np.bincount(edges.src, minlength=1024)))


def test_every_label_is_permuted():
    """No label is pinned: the Kronecker hub lands on another label for
    each seed."""
    hubs = {hub(rmat.generate(CONFIG, seed)) for seed in range(6)}
    assert len(hubs) >= 5


def test_a_label_seed_fixes_the_instance():
    fixed = {**CONFIG, "label_seed": 1}
    base = rmat.generate(fixed, 7)
    for seed in (8, 2**31 + 7):
        other = rmat.generate(fixed, seed)
        np.testing.assert_array_equal(other.src, base.src)
        np.testing.assert_array_equal(other.dst, base.dst)
        assert not np.array_equal(other.weight, base.weight)
    assert hub(rmat.generate({**CONFIG, "label_seed": 2}, 7)) != hub(base)


def test_simple_graph_with_weights_in_range():
    e = rmat.generate(CONFIG, 3)
    assert e.src.dtype == np.int32 and e.weight.dtype == np.float32
    assert np.all(e.src != e.dst)
    assert len(as_set(e)) == e.num_edges
    assert 0 <= e.src.min() and max(e.src.max(), e.dst.max()) < 1024
    assert e.weight.min() >= 1 and e.weight.max() <= 65535
    assert np.all(e.weight == np.round(e.weight))
    keys = e.src.astype(np.int64) * 1024 + e.dst
    assert np.all(np.diff(keys) > 0), "sorted by (src, dst)"


def test_symmetrized_copy_of_the_same_graph():
    seed = 2**31 + 9
    directed = rmat.generate(CONFIG, seed)
    sym = rmat.generate({**CONFIG, "symmetrize": True, "weights": None},
                        seed)
    assert sym.weight is None
    pairs = as_set(sym)
    assert len(pairs) == sym.num_edges
    assert pairs == {(v, u) for u, v in pairs}
    assert pairs == as_set(directed) | {(v, u) for u, v in as_set(directed)}


def test_kronecker_probabilities():
    """The Kronecker vertex 0, under whatever label, has the largest raw
    out- and in-degree, m (a+b)^scale and m (a+c)^scale (the next is
    under a third of it): every bit picked its quadrant with the
    configured probabilities."""
    scale, m = 14, 16 << 14
    src, dst, keep = (np.asarray(x) for x in rmat.rmat_edges(
        rmat.seed_key(1), rmat.seed_key(2), scale=scale, edge_factor=16,
        a=0.57, b=0.19, c=0.19, symmetrize=False))
    assert src.shape == (m,) and keep.mean() > 0.8
    expect = m * 0.76 ** scale                  # ~5600, sd ~75
    assert abs(np.bincount(src).max() - expect) < 0.05 * expect
    assert abs(np.bincount(dst).max() - expect) < 0.05 * expect
