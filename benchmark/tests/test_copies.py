"""The benchmark's copies of the engine's generator, references and
comparator agree with the originals, and its peaks with the engine's
table, so that the yardstick starts where the engine's tests stand."""

import numpy as np
import pandas as pd
import pytest

import compare
import run
import tpch_gen
from drivers import tpch_stream

SF = 0.01
QUERIES = ("q1", "q3", "q5", "q6")


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 12_345_678_901])
def test_generator_matches_dbgen(seed):
    from cylon_tpu.tpch import dbgen

    mine, theirs = tpch_gen.generate(SF, seed), dbgen.generate(SF, seed)
    assert mine.keys() == theirs.keys()
    for t in mine:
        assert mine[t].keys() == theirs[t].keys(), t
        for c in mine[t]:
            assert mine[t][c].dtype == theirs[t][c].dtype, (t, c)
            assert np.array_equal(mine[t][c], theirs[t][c]), (t, c)


@pytest.mark.parametrize("q", QUERIES)
def test_reads_match_the_engines_manifest(q):
    from cylon_tpu.tpch.manifest import MANIFEST

    reads = tpch_stream._query(q).READS
    assert {t: set(c) for t, c in reads.items()} == {
        t: set(c) for t, c in MANIFEST[q].items()}


@pytest.mark.parametrize("q", QUERIES)
def test_references_match_the_engines(q):
    from cylon_tpu.testing import compare_results
    from cylon_tpu.tpch import reference

    data = tpch_gen.generate(SF, 11)
    mine = tpch_stream.references(data, [q])[q]
    theirs = getattr(reference, f"{q}_pandas")(tpch_gen.to_pandas(data))
    diff, err = compare_results(mine, theirs, rtol=0.0)
    assert diff is None and err == 0.0


@pytest.mark.parametrize("got,want", [
    (pd.DataFrame({"k": [1, 2], "v": [1.0, 2.0]}),
     pd.DataFrame({"k": [1, 2], "v": [1.0, 2.0 + 1e-12]})),
    (pd.DataFrame({"k": [1, 2], "v": [1.0, 2.0]}),
     pd.DataFrame({"k": [1, 3], "v": [1.0, 2.0]})),
    (pd.DataFrame({"s": ["a", None], "v": [1.0, 2.0]}),
     pd.DataFrame({"s": ["a", None], "v": [1.0, 2.5]})),
    (pd.DataFrame({"k": [1]}), pd.DataFrame({"k": [1, 2]})),
    (3.0, 3.0 + 3e-9),
])
def test_comparator_matches_the_engines(got, want):
    from cylon_tpu.testing import compare_results

    mine = compare.compare_frames(got, want, 1e-9)
    theirs = compare_results(got, want, rtol=1e-9)
    assert (mine[0] is None) == (theirs[0] is None)
    if theirs[0] is None or not isinstance(want, pd.DataFrame):
        assert mine[1] == pytest.approx(theirs[1])


def test_peaks_match_the_engines_table():
    from cylon_tpu.telemetry.export import PEAKS

    peaks = run.load_json(run.HERE, "peaks.json")
    for kind, p in PEAKS.items():
        assert peaks[kind]["hbm_bytes_per_s"] == p["hbm_bytes_per_sec"]
        assert peaks[kind]["ici_bits_per_s"] / 8 / 4 == \
            p["ici_link_bytes_per_sec"]
        assert peaks[kind]["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.NoChip, match="no published peaks"):
        run.peaks_for("TPU v99")

