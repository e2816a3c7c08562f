"""Device programs and operators carry names a profiler trace can read.

Every device operation's metadata holds the JAX name stack it was
traced under (a trace shows it as the operation's ``tf_op``). These
tests lower the TPC-H queries of the chip benchmark at a tiny scale and
read those names back from the StableHLO: each program is named after
its query, no wrapper's name (``keyed``, ``traced``, ``<lambda>``)
hides the operator, and every sort and gather sits under a ``cylon.``
operator scope from ``utils.tracing.SCOPES``. The TPU's branches
(gather paths, group-sort widths) are traced with
``on_platform("tpu")`` and the Pallas kernels off, so the lowering
stays on this CPU.
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cylon_tpu import plan, tpch
from cylon_tpu.platform import on_platform, platform_jit
from cylon_tpu.utils import tracing

QUERIES = ("q1", "q3", "q5", "q6")
HEAVY = ("stablehlo.sort", "stablehlo.gather", "stablehlo.dynamic_gather")

_LOC = re.compile(r'^(#loc\d*) = loc\((.*)\)$')
_FUNC = re.compile(r'func\.func (?:public |private )?@([\w.$-]+)\(')
_OP = re.compile(r'^\s*(?:%[^=]+ = )?"?([\w.]+)"?[ (<{].*'
                 r'loc\((#loc\d*|"[^"]*")\)\s*$')
_CALL = re.compile(r'\bcall @([\w.$-]+)\(')


def op_names(text):
    """``[(operation, full op_name)]`` of every operation a lowered
    module runs, from ``as_text(debug_info=True)``. A nested program is
    a function called from its caller, with op_names relative to the
    call; the full name is the call's name followed by the callee's,
    as XLA writes it when it inlines the call."""
    locs = {}
    for line in text.splitlines():
        m = _LOC.match(line)
        if m:
            q = re.match(r'"((?:[^"\\]|\\.)*)"', m.group(2))
            locs[m.group(1)] = q.group(1) if q else ""
    funcs, cur = collections.defaultdict(list), None
    for line in text.splitlines():
        f = _FUNC.search(line)
        if f:
            cur = f.group(1)
            continue
        m = _OP.match(line) if cur else None
        if m:
            ref = m.group(2)
            name = ref.strip('"') if ref.startswith('"') else locs[ref]
            call = _CALL.search(line)
            funcs[cur].append((m.group(1), name,
                               call.group(1) if call else None))
    out = []

    def walk(fn, prefix):
        for op, name, callee in funcs[fn]:
            full = "/".join(p for p in (prefix, name) if p)
            if callee:
                walk(callee, full)
            else:
                out.append((op, full))

    walk("main", "")
    return out


@pytest.fixture(scope="module")
def frames():
    return tpch.ingest(tpch.generate(0.002, 3))


@pytest.fixture(params=["cpu", "tpu"])
def platform(request, monkeypatch):
    if request.param == "tpu":
        monkeypatch.setenv("CYLON_PALLAS", "0")
    return request.param


def _lowered(q, frames, platform):
    # a fresh program: the shared plan cache keys nothing on platform
    cq = plan.CompiledQuery(getattr(tpch, q))
    with on_platform(platform):
        return cq.lower(frames).as_text(debug_info=True)


@pytest.mark.parametrize("q", QUERIES)
def test_query_programs_name_their_operators(q, frames, platform):
    text = _lowered(q, frames, platform)
    assert re.search(r"^module @jit_" + q + r"\b", text, re.M)
    names = op_names(text)
    assert names and all(n.startswith(f"jit({q})") for _, n in names if n)
    hidden = [n for _, n in names
              if re.search(r"jit\((keyed|traced)\)|<lambda>", n)]
    assert not hidden, hidden[:5]
    heavy = [(op, n) for op, n in names if op in HEAVY]
    bare = [(op, n) for op, n in heavy if "/cylon." not in f"/{n}"]
    assert not bare, bare[:5]
    if q != "q6":  # q6 filters and sums: no sort or gather of its own
        assert heavy
    ops = {p for _, n in names for p in n.split("/")
           if p.startswith("cylon.")}
    assert ops <= {f"cylon.{o}" for o in tracing.SCOPES}
    assert "cylon.filter" in ops


STAGES = {
    # q3 runs every heavy operator; its groupby's values ride the sort
    "q3": {"join": ("keys", "sort", "match", "order", "gather"),
           "groupby": ("sort", "reduce"), "sort": ("keys", "permute"),
           "filter": ("mask", "compact")},
    # q1's wide groupby gathers its values into group order on the TPU
    "q1": {"groupby": ("sort", "permute", "reduce")},
}


@pytest.mark.parametrize("q,where", [("q3", "cpu"), ("q1", "tpu")])
def test_stages_land_inside_their_operator(q, where, frames, monkeypatch):
    monkeypatch.setenv("CYLON_PALLAS", "0")
    joined = "\n".join(n for _, n in op_names(_lowered(q, frames, where)))
    jits = {"join": "_join_compiled", "groupby": "_groupby_compiled",
            "sort": "_sort_compiled", "filter": "filter_table"}
    for op, stages in STAGES[q].items():
        assert f"jit({jits[op]})/cylon.{op}/" in joined, op
        for st in stages:
            assert f"cylon.{op}/{st}/" in joined, (op, st)


def test_platform_jit_keeps_the_operators_name():
    def my_operator(x, *, k):
        return x * k

    run = platform_jit(my_operator, static_argnames=("k",))
    assert run.__name__ == "my_operator"
    text = jax.jit(lambda x: run(x, k=2)).lower(
        jnp.arange(8.0)).as_text(debug_info=True)
    names = [n for _, n in op_names(text)]
    assert any(n.endswith("jit(my_operator)/mul") for n in names)
    assert not any("keyed" in n for n in names)


def test_result_slicer_is_named_and_scoped(frames):
    cq = plan.CompiledQuery(tpch.q1)
    cq(frames)  # the first call observes the result sizes
    raw, _ = cq._jitted(1, cq._row_hint([frames], {}), (), (), (frames,))
    (buckets,) = cq._size_memo.values()
    text = cq._slicer.lower(buckets, raw).as_text(debug_info=True)
    assert re.search(r"^module @jit_q1_slice\b", text, re.M)
    names = [n for _, n in op_names(text) if n]
    assert names and all("/cylon.plan/" in n for n in names)


def test_a_lambda_query_program_is_not_named_lambda(frames):
    cq = plan.CompiledQuery(lambda d: tpch.q6(d))
    text = cq.lower(frames).as_text(debug_info=True)
    assert re.search(r"^module @jit_query\b", text, re.M)
    assert "<lambda>" not in "".join(n for _, n in op_names(text))


def test_exchange_stages_in_a_distributed_join(env8):
    """The exchange of a ``dist_join`` on the 8-device CPU mesh enters
    each of its stages' scopes (the padded path: the CPU has no ragged
    all-to-all)."""
    from cylon_tpu import Table
    from cylon_tpu.parallel import dist_join, scatter_table

    rng = np.random.default_rng(0)
    left = scatter_table(env8, Table.from_pydict(
        {"k": rng.integers(0, 50, 400), "a": rng.random(400)}))
    right = scatter_table(env8, Table.from_pydict(
        {"k": rng.integers(0, 50, 300), "b": rng.random(300)}))

    def join_query(lt, rt):
        return dist_join(env8, lt, rt, on="k")

    text = plan.CompiledQuery(join_query).lower(left, right).as_text(
        debug_info=True)
    names = "\n".join(n for _, n in op_names(text))
    for stage in tracing.SCOPES["exchange"]:
        assert f"cylon.exchange/{stage}/" in names, stage
    assert "cylon.join/" in names


def test_scope_vocabulary():
    with tracing.scope("join"), tracing.scope("join", "match"):
        pass
    for bad in (("joins",), ("join", "probe"), ("take", "gather")):
        with pytest.raises(ValueError, match="SCOPES"):
            tracing.scope(*bad)


_SCATTER = re.compile(r'"stablehlo\.scatter"')
_REDUCER = re.compile(r'stablehlo\.(add|minimum|maximum)\b')


def scatter_set(text):
    """``Counter((reduction, element type))`` of every scatter in a
    lowered module: the reduction is read from the scatter's combiner
    region, the element type from the region's first argument."""
    lines = text.splitlines()
    found = collections.Counter()
    for i, line in enumerate(lines):
        if not _SCATTER.search(line):
            continue
        j = next(k for k in range(i, len(lines)) if "^bb0" in lines[k])
        ty = re.search(r"tensor<(\w+)>", lines[j]).group(1)
        k = next(k for k in range(j, len(lines))
                 if "stablehlo.return" in lines[k])
        red = next(m.group(1) for m in map(_REDUCER.search, lines[j:k])
                   if m)
        found[red, ty] += 1
    return found


@pytest.mark.parametrize("q", ["q1", "q3", "q5"])
def test_groupby_reduces_each_channel_once(q, frames):
    """The segment path (the CPU's, and the TPU's above
    ``SEGSCAN_MAX_ROWS``) runs one scatter per distinct channel. Q1
    sums l_extendedprice once for its sum and its mean, l_quantity in
    i64 for the sum and in f64 for the mean, and counts in int32: 5 f64
    and 1 i64 scatter-adds, a few i32 counts, and the i32 scatter-min of
    the group leaders. Q3 and Q5 sum one f64 column each."""
    found = scatter_set(_lowered(q, frames, "cpu"))
    adds = {ty: n for (red, ty), n in found.items() if red == "add"}
    if q == "q1":
        assert adds.pop("f64") == 5 and adds.pop("i64") == 1, found
        assert 1 <= adds.pop("i32") <= 3, found
        assert found["minimum", "i32"] == 1, found
    else:
        assert adds.pop("f64") == 1, found
    assert not adds, found
