"""The split of a trace's device time by operator scope: the wire reader
of each operation's ``tf_op``, the key it gives, and the sums, on a
trace built by hand and on two recorded on a TPU v5e:
``data/tiny_v5e.xplane.pb`` (a jitted sort + gather, no scopes) and
``data/scoped_v5e.xplane.pb`` (one call of the compiled TPC-H Q6 at
SF 0.01 under a ``bench.window`` annotation, its operators scoped)."""

import os
import subprocess
import sys

import pytest

import trace_reduce
import trace_scopes
from test_trace_reduce import HOST, _plane

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
SCOPED = os.path.join(HERE, "data", "scoped_v5e.xplane.pb")
KCUSTOM = ("%fusion = f32[1048576]{0:T(1024)S(1)} fusion(f32[1048576]"
           "{0:T(1024)S(1)} %get-tuple-element, s32[1048576]{0:T(1024)S(1)}"
           " %fusion.1), kind=kCustom, calls=%fused_computation")


def test_wire_reader_finds_tf_op_on_the_recorded_trace():
    with open(TINY, "rb") as f:
        ops = trace_scopes.read_tf_ops(f.read())
    events = ops["/device:TPU:0"]
    assert len(events) == 80  # every event of the XLA Ops line
    assert {tf for name, tf in events if name == KCUSTOM} == {
        "jit(<lambda>)/gather:"}


def test_recorded_trace_splits_the_seconds_trace_reduce_sums():
    scopes = trace_scopes.reduce_scopes(TINY)
    ops = trace_reduce.reduce_file(TINY).ops
    assert sum(scopes.values()) == pytest.approx(sum(ops.values()))
    # nothing was scoped; the sorts and gathers keep their primitive
    assert trace_scopes.scope_seconds(scopes, trace_scopes.UNSCOPED) == \
        pytest.approx(sum(ops.values()))
    assert trace_scopes.scope_seconds(scopes, primitive="sort") == \
        pytest.approx(ops["sort"])
    # the kCustom gathers and the loop fusion of their indices
    assert trace_scopes.scope_seconds(scopes, primitive="gather") == \
        pytest.approx(ops["fusion:kCustom"] + 6.25e-06)


def test_recorded_scoped_query():
    scopes = trace_scopes.reduce_scopes(SCOPED)
    total = sum(scopes.values())
    busy = trace_reduce.reduce_file(SCOPED).busy_s
    assert total == pytest.approx(busy, rel=1e-6)
    filt = trace_scopes.scope_seconds(scopes, "cylon.filter")
    assert filt > 0.9 * total
    assert scopes[("cylon.filter", "compact", "gather")] > 0
    assert trace_scopes.scope_seconds(scopes, "cylon.aggregate") > 0
    unscoped = trace_scopes.scope_seconds(scopes, trace_scopes.UNSCOPED)
    assert unscoped < 0.05 * total


def test_summary_carries_the_scopes():
    """``reduce_file`` hands the readers the same split as
    ``reduce_scopes``."""
    summary = trace_reduce.reduce_file(SCOPED)
    assert summary.scopes == trace_scopes.reduce_scopes(SCOPED)
    assert trace_scopes.scope_seconds(summary.scopes, "cylon.filter") > 0


@pytest.mark.parametrize("tf_op,key", [
    ("jit(q1)/jit(filter_table)/cylon.filter/compact/gather:",
     ("cylon.filter", "compact", "gather")),
    ("jit(q3)/cylon.join/jit(_join_compiled)/match/jit(group_sort)/sort",
     ("cylon.join", "match", "sort")),
    ("jit(q3)/jit(_join_compiled)/cylon.join/jit(_sort_compiled)/"
     "cylon.sort/permute/sort", ("cylon.join", "", "sort")),
    ("jit(q1)/cylon.take/min", ("cylon.take", "", "min")),
    ("jit(<lambda>)/gather:", ("(unscoped)", "", "gather")),
    ("", ("(unscoped)", "", "")),
])
def test_scope_key(tf_op, key):
    assert trace_scopes.scope_key(tf_op) == key


SAME_HLO = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kCustom"
Q1_GATHER = "jit(q1)/jit(filter_table)/cylon.filter/compact/gather:"
Q3_GATHER = "jit(q3)/jit(_join_compiled)/cylon.join/gather/gather"


def _scoped_space():
    """One device plane on which the same HLO text ran in two programs:
    metadata 1 names q1's filter by a string stat, metadata 2 q3's join
    by a reference to a stat's metadata; a copy has no ``tf_op``."""
    from jax.profiler import ProfileData

    metas = [(1, SAME_HLO, 'str_value: "%s"' % Q1_GATHER),
             (2, SAME_HLO, "ref_value: 9"),
             (3, "%copy-start.4 = (f32[8]{0}) copy-start(f32[8]{0} %c)",
              None)]
    meta_text = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
        + (f"stats {{ metadata_id: 7 {v} }} " if v else "") + "} }\n"
        for i, n, v in metas)
    stat_text = ('stat_metadata { key: 7 value { id: 7 name: "tf_op" } }\n'
                 f'stat_metadata {{ key: 9 value {{ id: 9 name: "{Q3_GATHER}"'
                 " } }\n")
    # (metadata, start us, duration us); the window is [0, 100)
    events = [(1, 0, 10), (2, 10, 30), (3, 40, 5), (1, 50, 5),
              (2, 120, 10)]  # the last runs after the window
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {s * 1_000_000} "
                  f"duration_ps: {d * 1_000_000} }}\n" for m, s, d in events)
    device = ('planes { id: 2 name: "/device:TPU:0"\n'
              f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{evs}}}\n'
              f"{meta_text}{stat_text}}}\n")
    return ProfileData.text_proto_to_serialized_xspace(
        _plane(1, "/host:CPU", "main", HOST) + device)


def test_each_event_takes_its_own_metadatas_tf_op(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_scoped_space())
    scopes = trace_scopes.reduce_scopes(str(path))
    assert scopes == pytest.approx({
        ("cylon.filter", "compact", "gather"): 15e-6,
        ("cylon.join", "gather", "gather"): 30e-6,
        ("(unscoped)", "", ""): 5e-6})
    assert trace_reduce.reduce_file(str(path)).ops == pytest.approx(
        {"fusion:kCustom": 45e-6, "copy-start": 5e-6})
    assert trace_scopes.scope_seconds(scopes, "cylon.join") == \
        pytest.approx(30e-6)
    assert trace_scopes.scope_seconds(scopes, primitive="gather") == \
        pytest.approx(45e-6)
    table = trace_scopes.scope_table(scopes, passes=3)
    assert "cylon.join/gather" in table
    assert "cylon.join: 0.000030 s, 60.00%, 0.010 ms a pass" in table
    assert "(unscoped): 0.000005 s, 10.00%" in table


def test_tf_ops_out_of_step_is_an_error():
    from jax.profiler import ProfileData

    data = _scoped_space()
    ops = trace_scopes.read_tf_ops(data)
    ops["/device:TPU:0"] = ops["/device:TPU:0"][1:]
    with pytest.raises(ValueError, match="out of step"):
        trace_scopes.scopes_of(ProfileData.from_serialized_xspace(data), ops)


def test_command_prints_the_split(tmp_path):
    """Given the directory a trace was written into."""
    logdir = tmp_path / "plugins" / "profile" / "run"
    logdir.mkdir(parents=True)
    (logdir / "host.xplane.pb").write_bytes(open(SCOPED, "rb").read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(HERE),
                                      "trace_scopes.py"),
         str(tmp_path), "--passes", "1"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "cylon.filter/compact" in proc.stdout
    assert "ms a pass" in proc.stdout
