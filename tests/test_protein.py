"""The synthetic protein-backbone generator (``data/protein.py``): seeding,
shapes, Cα bond lengths, and the 10 Å radius graph it is trained on."""
import numpy as np

from repro.data.protein import generate_protein_dataset
from repro.data.radius_graph import radius_graph

BOND = 3.8  # Å, the Cα-Cα distance the chain is built at
CUTOFF = 10.0  # Å, the paper's protein cutoff


def _bonds(x):
    return np.linalg.norm(np.diff(x, axis=0), axis=-1)


def test_same_seed_same_arrays_other_seed_other_arrays():
    a, b, c = (generate_protein_dataset(2, n_res=64, seed=s) for s in (3, 3, 4))
    for sa, sb, sc in zip(a, b, c):
        for f in sa._fields:
            np.testing.assert_array_equal(getattr(sa, f), getattr(sb, f))
        assert not np.array_equal(sa.x0, sc.x0)
        assert not np.array_equal(sa.x1, sc.x1)


def test_shapes_and_finiteness_at_default_size():
    data = generate_protein_dataset(3)
    assert len(data) == 3
    for s in data:
        n = s.x0.shape[0]
        assert s.x0.shape == s.v0.shape == s.x1.shape == (n, 3)
        assert s.h.shape == (n, 4)
        for f in s._fields:
            arr = getattr(s, f)
            assert arr.dtype == np.float32 and np.all(np.isfinite(arr)), f
        # one residue type each
        np.testing.assert_array_equal(s.h.sum(axis=1), np.ones(n, np.float32))
    # frames form a trajectory: each frame starts where the last one ended
    for prev, nxt in zip(data, data[1:]):
        np.testing.assert_array_equal(nxt.x0, prev.x1)


def test_consecutive_residues_about_a_bond_apart():
    data = generate_protein_dataset(2)
    # the first frame is the chain as built, at the bond length exactly
    np.testing.assert_allclose(_bonds(data[0].x0), BOND, atol=1e-4)
    # the relaxation after each displacement keeps the bonds near it
    for s in data:
        for x in (s.x0, s.x1):
            b = _bonds(x)
            assert np.all(np.abs(b - BOND) < 0.75), (b.min(), b.max())
            assert abs(float(b.mean()) - BOND) < 0.1, b.mean()


def test_radius_graph_holds_every_backbone_bond():
    for s in generate_protein_dataset(2):
        for x in (s.x0, s.x1):
            snd, rcv = radius_graph(x, CUTOFF)
            assert not np.any(snd == rcv)
            edges = set(zip(snd.tolist(), rcv.tolist()))
            n = x.shape[0]
            for i in range(n - 1):
                assert (i, i + 1) in edges and (i + 1, i) in edges, i
