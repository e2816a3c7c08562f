"""Fleet-router unit tests (ISSUE 15): journal lock/fencing, the
closing-503 introspection fix, the value codec, tenant-affinity
routing, fleet-scoped idempotency dedup, and in-process failover with
journal replay — everything that does not need an interpreter spawn
(the subprocess SIGKILL scenario lives in tests/test_fleet_chaos.py).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pytest

from cylon_tpu import Table, catalog, telemetry
from cylon_tpu.errors import (DataLossError, FailedPrecondition,
                              InvalidArgument)
from cylon_tpu.serve import ServeEngine, ServePolicy
from cylon_tpu.serve.durability import (JournalLock, RequestJournal,
                                        fence_journal)
from cylon_tpu.serve.fleet import (EngineUnavailable, FleetLayout,
                                   FleetRouter, LocalEngineClient,
                                   _affinity_order, decode_value,
                                   encode_value)


@pytest.fixture(autouse=True)
def _clean():
    catalog.clear()
    telemetry.reset("serve.")
    telemetry.reset("fleet.")
    yield
    catalog.clear()
    telemetry.reset("serve.")
    telemetry.reset("fleet.")


# ------------------------------------------------- journal lock / fence
def test_second_live_engine_cannot_own_a_journal(tmp_path):
    """The multi-engine fence: two live engines pointed at ONE durable
    dir would silently interleave journal lines — the second must fail
    loudly at construction instead."""
    j = RequestJournal(str(tmp_path))
    with pytest.raises(FailedPrecondition, match="owned by a live"):
        RequestJournal(str(tmp_path))
    j.close()
    # released lock: the dir is adoptable again
    j2 = RequestJournal(str(tmp_path))
    j2.close()


def test_stale_lock_dead_pid_is_broken_on_acquire(tmp_path):
    """A lock held by a dead pid (the killed engine) is stale — the
    next acquire (recover()'s path) breaks it instead of refusing."""
    p = subprocess.run([sys.executable, "-c", "print('x')"],
                       capture_output=True)
    dead_pid = None
    # find a pid that is certainly not alive: the just-reaped child
    # (subprocess.run waits) — re-derive it via a fresh child
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    dead_pid = proc.pid
    assert p.returncode == 0
    lock = tmp_path / JournalLock.FILE
    lock.write_text(json.dumps({
        "pid": dead_pid, "host": __import__("socket").gethostname(),
        "owner": "engine", "token": "stale", "acquired": 0}))
    j = RequestJournal(str(tmp_path))  # breaks the stale lock
    j.admit(rid=1, key="k", name="q")
    j.close()


def test_expired_heartbeat_is_stale_when_ttl_armed(tmp_path,
                                                   monkeypatch):
    """The TTL rule covers the pid-uncheckable (cross-host) case: an
    OTHER-host owner with an expired heartbeat is breakable once
    CYLON_TPU_FLEET_LOCK_TTL is armed, and refused without it. A
    SAME-host owner whose pid is provably alive is NEVER stale — an
    idle engine appends nothing (its heartbeat ages), and the TTL
    must not break a live owner (review fix; fencing a wedged-but-
    alive engine is fence_journal's deliberate act)."""
    lock = tmp_path / JournalLock.FILE

    def write_lock(host):
        lock.write_text(json.dumps({
            "pid": os.getpid(), "host": host,
            "owner": "engine", "token": "old", "acquired": 0}))
        old = time.time() - 3600
        os.utime(lock, (old, old))

    # cross-host owner: TTL decides
    write_lock("some-other-host")
    monkeypatch.delenv("CYLON_TPU_FLEET_LOCK_TTL", raising=False)
    with pytest.raises(FailedPrecondition):
        JournalLock(str(tmp_path)).acquire()
    monkeypatch.setenv("CYLON_TPU_FLEET_LOCK_TTL", "10")
    lk = JournalLock(str(tmp_path)).acquire()
    lk.release()
    # same-host ALIVE owner: liveness vetoes the TTL, however old the
    # heartbeat — an idle healthy engine keeps its journal
    write_lock(__import__("socket").gethostname())
    with pytest.raises(FailedPrecondition):
        JournalLock(str(tmp_path)).acquire()


def test_fence_blocks_owner_appends_but_not_adoption(tmp_path):
    """fence_journal() replaces the lock token: the fenced owner's
    next append raises (it can no longer race a failover replay), and
    its close() releases nothing it doesn't own — while a NEW engine
    adopts the dir normally (the fence marker is breakable)."""
    j = RequestJournal(str(tmp_path))
    j.admit(rid=1, key="a", name="q")
    fence_journal(str(tmp_path), owner="router:test")
    with pytest.raises(FailedPrecondition, match="FENCED"):
        j.admit(rid=2, key="b", name="q")
    j.close()
    assert (tmp_path / JournalLock.FILE).exists()  # fence survives
    j2 = RequestJournal(str(tmp_path))  # adoption breaks the fence
    j2.admit(rid=3, key="c", name="q")
    j2.close()
    keys = [e.get("key") for e in RequestJournal.read(str(tmp_path))]
    assert keys == ["a", "c"]  # the fenced append never landed


def test_fenced_engine_retires_locally_without_journaling(tmp_path):
    """A live engine whose journal gets fenced mid-flight still
    retires its in-flight request (the local client gets the answer);
    only the done line is suppressed — logged, not raised."""
    eng = ServeEngine(policy=ServePolicy(max_queue=4),
                      durable_dir=str(tmp_path))
    gate = threading.Event()

    def gated():
        while not gate.is_set():
            yield
            time.sleep(0.001)
        return 7

    eng.register_query("g", gated)
    tk = eng.submit_named("g", idempotency_key="k", tenant="a")
    fence_journal(str(tmp_path), owner="router:test")
    gate.set()
    assert tk.result(30) == 7  # retirement survived the fence
    done = [e for e in RequestJournal.read(str(tmp_path))
            if e["kind"] == "done"]
    assert done == []  # ...but never raced the replay with a done line
    eng.close()


# ------------------------------------------------- closing-503 fix
def test_health_probes_return_503_closing_during_drain(monkeypatch):
    """ISSUE 15 satellite: /health and /healthz polled while close()
    drains answer a clean 503 {"status": "closing"} instead of racing
    the scheduler teardown into a 500."""
    monkeypatch.setenv("CYLON_TPU_SERVE_HTTP_PORT", "0")
    eng = ServeEngine(policy=ServePolicy(max_queue=4))
    base = "http://%s:%d" % eng.http_address
    gate = threading.Event()

    def gated():
        while not gate.is_set():
            yield
            time.sleep(0.001)
        return 1

    tk = eng.submit(gated, tenant="a")
    closer = threading.Thread(target=lambda: eng.close(wait=True))
    closer.start()
    deadline = time.monotonic() + 10
    codes = set()
    while time.monotonic() < deadline:
        for path in ("/healthz", "/health"):
            try:
                with urllib.request.urlopen(base + path,
                                            timeout=5) as r:
                    codes.add((path, r.status))
            except urllib.error.HTTPError as e:
                assert e.code == 503, (path, e.code)
                body = json.loads(e.read())
                assert body["status"] == "closing", body
                codes.add((path, 503))
        if {("/healthz", 503), ("/health", 503)} <= codes:
            break
        time.sleep(0.02)
    gate.set()
    closer.join(30)
    assert tk.result(30) == 1  # the drain completed the request
    assert {("/healthz", 503), ("/health", 503)} <= codes, codes


# ------------------------------------------------- value codec
def test_value_codec_round_trips_frames_scalars_bytes():
    df = pd.DataFrame({
        "i": np.asarray([1, 2, 3], dtype=np.int64),
        "f": np.asarray([1.5, float("nan"), float("inf")]),
        "s": ["a", "b", None],
        "b": [b"\x00\xff", b"ok", None],
        "d": np.asarray(["2024-01-01", "2024-06-01", "2024-12-31"],
                        dtype="datetime64[ns]"),
    })
    env = encode_value(df)
    text = json.dumps(env, allow_nan=False)  # strict JSON end to end
    back = decode_value(json.loads(text))
    assert list(back.columns) == list(df.columns)
    assert back["i"].tolist() == [1, 2, 3]
    # non-finite floats survive EXACTLY (inf must not decode as NaN)
    assert back["f"][0] == 1.5 and np.isnan(back["f"][1])
    assert back["f"][2] == float("inf")
    assert back["s"].tolist() == ["a", "b", None]
    assert back["b"].tolist() == [b"\x00\xff", b"ok", None]
    assert back["d"].astype("int64").tolist() == \
        df["d"].astype("int64").tolist()
    # scalars and arrays
    assert decode_value(json.loads(json.dumps(
        encode_value(3.75)))) == 3.75
    arr = decode_value(json.loads(json.dumps(
        encode_value(np.asarray([1.0, 2.0])))))
    assert arr.tolist() == [1.0, 2.0]


# ------------------------------------------------- chip pinning
def test_engines_pin_one_chip_each_and_refuse_extra(monkeypatch):
    from cylon_tpu.errors import InvalidArgument
    from cylon_tpu.serve import fleet

    class Proc:
        def __init__(self, alive=True):
            self.alive = alive

        def poll(self):
            return None if self.alive else 0

    monkeypatch.setattr(fleet, "_host_chips", lambda: 2)
    monkeypatch.setattr(fleet, "_PINNED", {})
    assert fleet._pin_chip({"JAX_PLATFORMS": "cpu"}) is None
    envs = [{}, {}]
    for i, env in enumerate(envs):
        assert fleet._pin_chip(env) == i
        fleet._PINNED[i] = Proc()
        assert env["TPU_VISIBLE_CHIPS"] == str(i)
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert envs[0]["TPU_MESH_CONTROLLER_PORT"] != \
        envs[1]["TPU_MESH_CONTROLLER_PORT"]
    with pytest.raises(InvalidArgument, match="one engine per chip"):
        fleet._pin_chip({})
    fleet._PINNED[1].alive = False          # a dead engine frees its chip
    assert fleet._pin_chip({}) == 1


# ------------------------------------------------- affinity
def test_affinity_order_is_stable_and_spreads():
    names = ["e0", "e1", "e2"]
    assert _affinity_order("alice", names) == \
        _affinity_order("alice", names)
    assert sorted(_affinity_order("alice", names)) == sorted(names)
    starts = {_affinity_order(f"tenant{i}", names)[0]
              for i in range(64)}
    assert starts == set(names), (
        "64 tenants all hashed to the same engine — affinity is not "
        "spreading")


def _mk_local_fleet(tmp_path, record_execs=None):
    """Two in-process engines over one FleetLayout tree, each with a
    'q' query that records which engine executed it."""
    lay = FleetLayout(str(tmp_path))
    engines, clients = {}, []
    for name in ("a0", "a1"):
        eng = ServeEngine(policy=ServePolicy(max_queue=16),
                          durable_dir=lay.engine_dir(name))

        def mk(n):
            def q(x):
                if record_execs is not None:
                    record_execs.append((n, x))
                return x * 2
            return q

        eng.register_query("q", mk(name))
        engines[name] = eng
        clients.append(LocalEngineClient(eng, name))
    return lay, engines, clients


def test_router_routes_by_affinity_and_dedups(tmp_path):
    execs = []
    lay, engines, clients = _mk_local_fleet(tmp_path, execs)
    router = FleetRouter(clients, poll_interval=0.1,
                         fail_threshold=2, unhealthy_dwell=1.0)
    try:
        t1 = router.submit("q", 21, tenant="alice",
                           idempotency_key="k1")
        assert t1.result(30) == 42
        expected = _affinity_order("alice", ["a0", "a1"])[0]
        assert t1.engine == expected
        assert telemetry.counter("fleet.routed", engine=expected,
                                 tenant="alice").value == 1
        # fleet-scoped dedup: same key → same ticket, no execution
        t2 = router.submit("q", 21, tenant="alice",
                           idempotency_key="k1")
        assert t2 is t1 and t2.result(30) == 42
        assert execs == [(expected, 21)]
        assert telemetry.total("fleet.deduped") == 1
    finally:
        router.close()
        for e in engines.values():
            e.close()


class _MortalClient(LocalEngineClient):
    """A LocalEngineClient with a kill switch: once dead, every call
    raises EngineUnavailable — the in-process stand-in for a killed
    engine process (the real one lives in test_fleet_chaos.py)."""

    def __init__(self, engine, name):
        super().__init__(engine, name)
        self.dead = threading.Event()

    def _check(self):
        if self.dead.is_set():
            raise EngineUnavailable(
                f"engine {self.name!r} is (simulated) dead")

    def submit(self, *a, **kw):
        self._check()
        return super().submit(*a, **kw)

    def result(self, *a, **kw):
        self._check()
        return super().result(*a, **kw)

    def health(self):
        self._check()
        return super().health()


def test_failover_replays_incomplete_on_peer_exactly_once(tmp_path):
    """THE in-process failover proof: an acknowledged,
    journaled-but-incomplete request on a 'dead' engine is fenced,
    replayed on the surviving peer under its ORIGINAL key, and the
    blocked RouterTicket.result() delivers the peer's answer — with
    the zombie's late completion fenced out of the journal and a
    client retry deduped, never double-executed."""
    lay = FleetLayout(str(tmp_path))
    execs = []
    gate = threading.Event()
    e0 = ServeEngine(policy=ServePolicy(max_queue=16),
                     durable_dir=lay.engine_dir("a0"))
    e1 = ServeEngine(policy=ServePolicy(max_queue=16),
                     durable_dir=lay.engine_dir("a1"))

    def gated_q(x):  # a0's version: wedges until the gate opens
        while not gate.is_set():
            yield
            time.sleep(0.001)
        return x * 2

    def fast_q(x):  # a1's version: answers immediately
        execs.append(("a1", x))
        return x * 2

    e0.register_query("q", gated_q)
    e1.register_query("q", fast_q)
    c0, c1 = _MortalClient(e0, "a0"), _MortalClient(e1, "a1")
    # tenant whose affinity ring starts at a0
    tenant = next(t for t in (f"t{i}" for i in range(64))
                  if _affinity_order(t, ["a0", "a1"])[0] == "a0")
    router = FleetRouter([c0, c1], poll_interval=0.05,
                         fail_threshold=2, unhealthy_dwell=1.0)
    try:
        tk = router.submit("q", 21, tenant=tenant,
                           idempotency_key="K")
        assert tk.engine == "a0"
        # journaled (write-ahead) and incomplete on a0
        assert [e["key"] for e in
                RequestJournal.incomplete(lay.engine_dir("a0"))[0]] \
            == ["K"]
        c0.dead.set()  # the engine "dies" with the request in flight
        got = tk.result(60)  # blocked client just... gets the answer
        assert got == 42
        assert tk.engine == "a1"
        assert execs == [("a1", 21)]
        assert telemetry.total("fleet.failovers") == 1
        assert telemetry.total("fleet.replayed") == 1
        assert telemetry.total("fleet.lost_acks") == 0
        # the dead engine's journal is fenced: its zombie completion
        # cannot append a done line that races the replay
        gate.set()
        time.sleep(0.3)  # let a0's scheduler retire the zombie step
        done_a0 = [e for e in
                   RequestJournal.read(lay.engine_dir("a0"))
                   if e["kind"] == "done"]
        assert done_a0 == []
        # an idempotent retry after failover dedups through the router
        t2 = router.submit("q", 21, tenant=tenant,
                           idempotency_key="K")
        assert t2 is tk and t2.result(10) == 42
        assert execs == [("a1", 21)]  # never double-executed
        # exactly one done(state=done) across the fleet for K
        done_all = [e for n in ("a0", "a1") for e in
                    RequestJournal.read(lay.engine_dir(n))
                    if e["kind"] == "done" and e.get("state") == "done"
                    and e.get("key") == "K"]
        assert len(done_all) == 1
    finally:
        gate.set()
        router.close()
        e0.close()
        e1.close()


def test_unhealthy_dwell_triggers_failover(tmp_path):
    """An engine that stays unhealthy (here: closing) past the dwell
    is failed over even though its HTTP surface still answers."""
    lay = FleetLayout(str(tmp_path))
    e0 = ServeEngine(policy=ServePolicy(max_queue=4),
                     durable_dir=lay.engine_dir("a0"))
    e1 = ServeEngine(policy=ServePolicy(max_queue=4),
                     durable_dir=lay.engine_dir("a1"))
    for name, e in (("a0", e0), ("a1", e1)):
        e.register_query("q", lambda: 1)
    c0, c1 = LocalEngineClient(e0, "a0"), LocalEngineClient(e1, "a1")
    router = FleetRouter([c0, c1], poll_interval=0.05,
                         fail_threshold=99, unhealthy_dwell=0.2)
    try:
        e0.close()  # now c0.health() reports {"status": "closing"}
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if telemetry.total("fleet.failovers") >= 1:
                break
            time.sleep(0.05)
        assert telemetry.total("fleet.failovers") == 1
        dead = [s for s in router.engines() if s["dead"]]
        assert [s["name"] for s in dead] == ["a0"]
        # routing keeps working on the survivor
        assert router.submit("q", tenant="x").result(30) == 1
    finally:
        router.close()
        e1.close()


def test_no_surviving_peer_counts_lost_acks(tmp_path):
    """A fleet of one: when the only engine dies with an acknowledged
    request in flight, the ticket is reported LOST (DataLossError +
    fleet.lost_acks) — loud, never a silent hang."""
    lay = FleetLayout(str(tmp_path))
    eng = ServeEngine(policy=ServePolicy(max_queue=4),
                      durable_dir=lay.engine_dir("solo"))
    gate = threading.Event()

    def gated():
        while not gate.is_set():
            yield
            time.sleep(0.001)
        return 1

    eng.register_query("q", gated)
    c = _MortalClient(eng, "solo")
    router = FleetRouter([c], poll_interval=0.05, fail_threshold=2,
                         unhealthy_dwell=1.0)
    try:
        tk = router.submit("q", tenant="t", idempotency_key="K")
        c.dead.set()
        with pytest.raises(DataLossError, match="LOST"):
            tk.result(30)
        assert telemetry.total("fleet.lost_acks") >= 1
    finally:
        gate.set()
        router.close()
        eng.close()


def test_shared_snapshot_store_concurrent_init_is_safe(tmp_path):
    """Verify-drive regression: two engines constructing the SHARED
    snapshot store on a fresh dir concurrently must not race the
    first-manifest write against the peer's stale-state sweep (which
    unlinks manifest tmp files — pre-fix this threw FileNotFoundError
    out of atomic_write_json). The init mutex serializes them."""
    from cylon_tpu.serve.durability import CatalogSnapshot

    errors = []
    barrier = threading.Barrier(8)

    def build():
        try:
            barrier.wait(10)
            CatalogSnapshot(str(tmp_path))
        except Exception as e:  # noqa: BLE001 - collected for assert
            errors.append(e)

    threads = [threading.Thread(target=build) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert errors == [], errors
    snap = CatalogSnapshot(str(tmp_path))
    assert snap.tables == []
    assert not os.path.exists(os.path.join(
        snap.root, CatalogSnapshot.INIT_LOCK))


def test_submit_reroutes_on_connection_refusal(tmp_path):
    """A submit whose affinity engine REFUSES the connection (here: a
    closing engine — nothing was admitted) walks the ring to the peer
    instead of erroring the client; an ambiguous failure against a
    live engine would raise instead (the double-execution guard)."""
    lay = FleetLayout(str(tmp_path))
    e0 = ServeEngine(policy=ServePolicy(max_queue=4),
                     durable_dir=lay.engine_dir("a0"))
    e1 = ServeEngine(policy=ServePolicy(max_queue=4),
                     durable_dir=lay.engine_dir("a1"))
    execs = []
    e0.register_query("q", lambda: execs.append("a0") or 0)
    e1.register_query("q", lambda: execs.append("a1") or 1)
    tenant = next(t for t in (f"t{i}" for i in range(64))
                  if _affinity_order(t, ["a0", "a1"])[0] == "a0")
    router = FleetRouter(
        [LocalEngineClient(e0, "a0"), LocalEngineClient(e1, "a1")],
        poll_interval=5.0, fail_threshold=99, unhealthy_dwell=99.0,
        start=False)
    try:
        e0.close()  # refuses: LocalEngineClient raises refused=True
        tk = router.submit("q", tenant=tenant, idempotency_key="K")
        assert tk.result(30) == 1 and tk.engine == "a1"
        assert execs == ["a1"]
    finally:
        router.close()
        e1.close()


def test_router_refuses_duplicate_engine_names(tmp_path):
    eng = ServeEngine(policy=ServePolicy(max_queue=4))
    try:
        with pytest.raises(InvalidArgument, match="unique"):
            FleetRouter([LocalEngineClient(eng, "x"),
                         LocalEngineClient(eng, "x")], start=False)
    finally:
        eng.close()


# ------------------------------------------- ISSUE 19: dedup @ fleet
def _put_shared(n=8):
    catalog.put_table("shared", Table.from_pydict({
        "k": np.arange(n, dtype=np.int64),
        "v": np.arange(n, dtype=np.float64)}))


def test_killed_leader_followers_rerun_on_peer_zero_lost_acks(
        tmp_path):
    """ISSUE 19 oracle: three identical in-flight requests coalesce
    engine-side (one leader op, two attached followers) — each with
    its OWN journaled admit line. When the leader's engine dies
    mid-flight, failover replays all three keys on the surviving peer:
    every blocked RouterTicket gets the answer, 0 lost acks."""
    lay = FleetLayout(str(tmp_path))
    _put_shared()
    gate = threading.Event()
    execs = []
    e0 = ServeEngine(policy=ServePolicy(max_queue=16),
                     durable_dir=lay.engine_dir("a0"))
    e1 = ServeEngine(policy=ServePolicy(max_queue=16),
                     durable_dir=lay.engine_dir("a1"))

    def wedge(x):  # a0: spins until the gate — never answers in time
        while not gate.is_set():
            yield
            time.sleep(0.001)
        return x * 2

    def fast(x):  # a1: answers immediately
        execs.append(("a1", x))
        return x * 2

    e0.register_query("q", wedge, tables=("shared",))
    e1.register_query("q", fast, tables=("shared",))
    c0, c1 = _MortalClient(e0, "a0"), _MortalClient(e1, "a1")
    tenant = next(t for t in (f"t{i}" for i in range(64))
                  if _affinity_order(t, ["a0", "a1"])[0] == "a0")
    router = FleetRouter([c0, c1], poll_interval=0.05,
                         fail_threshold=2, unhealthy_dwell=1.0)
    try:
        tks = [router.submit("q", 21, tenant=tenant,
                             idempotency_key=f"K{i}")
               for i in range(3)]
        # K1/K2 attached to K0's in-flight op instead of queuing
        assert telemetry.total("serve.coalesced") == 2
        inc, _ = RequestJournal.incomplete(lay.engine_dir("a0"))
        assert sorted(e["key"] for e in inc) == ["K0", "K1", "K2"]
        c0.dead.set()  # the leader's engine dies with all 3 in flight
        assert [tk.result(60) for tk in tks] == [42, 42, 42]
        assert {tk.engine for tk in tks} == {"a1"}
        assert telemetry.total("fleet.lost_acks") == 0
        assert telemetry.total("fleet.replayed") == 3
        assert execs and set(execs) == {("a1", 21)}
    finally:
        gate.set()
        router.close()
        e0.close()
        e1.close()


def test_router_cache_survives_engine_death(tmp_path):
    """The fleet-scoped half of the ISSUE 19 cache: the router learns
    the (fingerprint, version-vector) key from the done reply and
    serves repeats from ITS OWN cache — so a repeat lands even after
    the origin engine dies, touching no engine at all; an append
    invalidates precisely and the recompute routes to the survivor."""
    lay = FleetLayout(str(tmp_path))
    _put_shared()
    execs = []
    e0 = ServeEngine(policy=ServePolicy(max_queue=16),
                     durable_dir=lay.engine_dir("a0"))
    e1 = ServeEngine(policy=ServePolicy(max_queue=16),
                     durable_dir=lay.engine_dir("a1"))

    def mk(n):
        def q(x):
            execs.append((n, x))
            return x * 2
        return q

    e0.register_query("q", mk("a0"), tables=("shared",))
    e1.register_query("q", mk("a1"), tables=("shared",))
    c0, c1 = _MortalClient(e0, "a0"), _MortalClient(e1, "a1")
    tenant = next(t for t in (f"t{i}" for i in range(64))
                  if _affinity_order(t, ["a0", "a1"])[0] == "a0")
    router = FleetRouter([c0, c1], poll_interval=0.05,
                         fail_threshold=2, unhealthy_dwell=1.0)
    try:
        t1 = router.submit("q", 21, tenant=tenant)
        assert t1.result(30) == 42 and t1.engine == "a0"
        assert execs == [("a0", 21)]
        c0.dead.set()  # the engine that computed the answer is gone
        t2 = router.submit("q", 21, tenant=tenant)
        assert t2.result(30) == 42
        assert execs == [("a0", 21)]  # served by the ROUTER's cache
        assert telemetry.total("fleet.result_cache_hits") == 1
        # precise invalidation: an append bumps the vector -> miss ->
        # the recompute runs on the SURVIVOR with fresh data versions
        # (wait for the health poller's death verdict first — a miss
        # routed at a not-yet-declared-dead a0 is an ambiguous failure
        # the router correctly refuses to re-route)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not router._is_dead("a0"):
            time.sleep(0.05)
        assert router._is_dead("a0")
        catalog.append("shared", {
            "k": np.asarray([100], dtype=np.int64),
            "v": np.asarray([1.0], dtype=np.float64)})
        t3 = router.submit("q", 21, tenant=tenant)
        assert t3.result(30) == 42
        assert execs == [("a0", 21), ("a1", 21)]
        assert telemetry.total("fleet.result_cache_invalidations") >= 1
    finally:
        router.close()
        e0.close()
        e1.close()
