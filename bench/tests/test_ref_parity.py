"""The benchmark's copies agree bit for bit with the program's originals
at a small tree, so a change to either shows here."""
import numpy as np
import pytest

import run_cell
from ref import intervals, orion, reducers
from ref.tokens import Tokens

GEN = {**run_cell.cell_spec("hdep_orion_r512")["config"]["generator"],
       "max_level": 6}

SPECS = [
    {"kind": "lod", "id": "lod4", "max_level": 4},
    {"kind": "slice", "field": "density", "axis": 2, "position": 0.5,
     "resolution": 64},
    {"kind": "slice", "field": "density", "axis": 2, "position": 0.5,
     "resolution": 64, "source": "lod4"},
    {"kind": "projection", "field": "density", "axis": 2, "resolution": 64},
    {"kind": "hist", "field": "density", "bins": 32},
    {"kind": "slice", "field": "pressure", "axis": 0, "position": 0.3,
     "resolution": 32},
    {"kind": "projection", "field": "velocity_y", "axis": 1,
     "resolution": 128},
]


@pytest.fixture(scope="module")
def snapshot():
    return orion.generate(GEN, jitter_seed=2**31 + 11)


def test_generator_matches_program(snapshot):
    from repro.sim import amrgen, fields
    tree = amrgen.generate_tree(
        fields.orion(seed=GEN["realization"]), min_level=GEN["min_level"],
        max_level=GEN["max_level"], threshold=GEN["threshold"],
        level_factor=GEN["level_factor"], rng_jitter=GEN["refine_jitter"],
        seed=2**31 + 11)
    want = tree.to_arrays()
    magnetic = {f"field:{n}" for n in orion.B_NAMES}
    assert set(want) == set(snapshot) - magnetic
    for k, v in want.items():
        assert v.dtype == snapshot[k].dtype, k
        assert v.tobytes() == snapshot[k].tobytes(), k


def test_reducers_match_program(snapshot):
    from drivers.hdep import program_reducer
    from repro.insitu.reducers import ReducerDAG
    from repro.insitu.staging import Snapshot
    by_id, progs = {}, []
    for s in SPECS:
        r = program_reducer(s, by_id)
        progs.append(r)
        if s.get("id"):
            by_id[s["id"]] = r
    for arrays in orion.variants(snapshot, 2, 0.1, 5):
        got = ReducerDAG(progs).run(Snapshot(step=1, kind="amr",
                                             arrays=arrays))
        want = reducers.run_dag(SPECS, arrays)
        for r, w in zip(progs, want):
            g = got[r.name]
            assert set(g) == set(w), r.name
            for k in w:
                a, b = np.asarray(g[k]), np.asarray(w[k])
                assert a.dtype == b.dtype and a.shape == b.shape, (r.name, k)
                assert a.tobytes() == b.tobytes(), (r.name, k)


def test_magnetic_field_is_face_centred_and_divergence_free(snapshot):
    """A leaf's net flux through its six faces is the divergence of the
    modes to third order in the cell size (each is divergence-free), so
    it falls ~8x a level; a face that two leaves of one level share
    carries one value; the mean field is there."""
    f = {n: snapshot[f"field:{n}"] for n in orion.B_NAMES}
    leaves = ~snapshot["refine"]
    levels = reducers._levels(snapshot)
    net = sum(f[f"B_{a}_right"] - f[f"B_{a}_left"] for a in "xyz")
    worst = [np.abs(net[leaves & (levels == lvl)]).max() for lvl in (5, 6)]
    assert worst[1] < worst[0] / 4
    coords, lvl = snapshot["coords"], 6
    finest = np.flatnonzero(leaves & (levels == lvl))
    where = {tuple(c): i for i, c in zip(finest, coords[finest])}
    pairs = [(i, where[(c[0] + 1, c[1], c[2])]) for i, c in
             zip(finest, coords[finest]) if (c[0] + 1, c[1], c[2]) in where]
    assert pairs
    i, j = np.array(pairs).T
    assert np.allclose(f["B_x_right"][i], f["B_x_left"][j], rtol=0,
                       atol=1e-12)
    assert np.isclose(f["B_x_left"][leaves].mean(), 1.0, atol=0.3)


def test_variants_keep_structure(snapshot):
    vs = orion.variants(snapshot, 3, 0.1, 9)
    for v in vs:
        for k in ("refine", "owner", "level_offsets", "coords"):
            assert v[k] is snapshot[k]
        assert np.all(v["field:density"] > 0)
    assert not np.array_equal(vs[0]["field:density"], vs[1]["field:density"])
    ratio = vs[0]["field:B_y_left"] / snapshot["field:B_y_left"]
    assert np.allclose(ratio, ratio[0]) and not np.isclose(ratio[0], 1.0)
    again = orion.variants(snapshot, 3, 0.1, 9)
    assert all(a["field:pressure"].tobytes() == b["field:pressure"].tobytes()
               for a, b in zip(vs, again))


def test_tokens_match_program():
    from repro.data.pipeline import DataConfig, TokenPipeline
    seed = 2**31 + 3
    pipe = TokenPipeline(DataConfig(vocab_size=1000, seq_len=16,
                                    global_batch=4, seed=seed))
    mine = Tokens(1000, 16, 4, seed)
    for step in (0, 1, 7):
        a, b = pipe.batch(step), mine.batch_at(step)
        for k in ("tokens", "labels"):
            assert np.array_equal(a[k], b[k]) and a[k].dtype == b[k].dtype


def test_union_matches_program():
    from repro.obs.attrib import union_seconds
    ivs = [(0.0, 5.0), (3.0, 9.0), (12.0, 14.0), (13.5, 13.7), (20.0, 21.0)]
    assert intervals.union(ivs) == pytest.approx(union_seconds(ivs) * 1e6)
    assert intervals.gaps(ivs, 0.0, 22.0) == [(9.0, 12.0), (14.0, 20.0),
                                              (21.0, 22.0)]
    assert intervals.self_time((0.0, 10.0), [(2.0, 4.0), (3.0, 5.0)]) == 7.0
