"""Observability subsystem (repro.obs): determinism, cross-transport
span equivalence, counter exactness, and the zero-overhead-when-off
contract (ISSUE 7 acceptance)."""
import json
import threading

import pytest

from repro.obs import export, metrics, txtrace


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts from an empty, disabled obs state and leaves it
    that way (tracing must never leak into the rest of the suite)."""
    txtrace.disable()
    txtrace.reset()
    metrics.reset()
    yield
    txtrace.disable()
    txtrace.reset()
    metrics.reset()


# --------------------------------------------------------------------------- #
# primitives                                                                   #
# --------------------------------------------------------------------------- #
def test_ring_buffer_orders_and_drops():
    t = txtrace.Tracer("node:test", clock=lambda: 0.0, capacity=4)
    for i in range(6):
        t.emit("k", float(i), 0.0, detail=str(i))
    evs = t.events()
    assert [e["detail"] for e in evs] == ["2", "3", "4", "5"]   # oldest gone
    assert [e["idx"] for e in evs] == [2, 3, 4, 5]              # stable idx
    assert t.dropped() == 2


def test_histogram_percentiles_log_linear():
    h = metrics.Histogram()
    for us in range(1, 1001):
        h.record(us)
    assert h.count == 1000 and h.max == 1000
    # log-linear buckets: ~6% relative quantile error
    assert abs(h.percentile(0.5) - 500) <= 500 * 0.07
    assert abs(h.percentile(0.99) - 990) <= 990 * 0.07
    snap = h.snapshot()
    assert snap["count"] == 1000 and snap["max_us"] == 1000


def test_per_thread_oneway_counter_is_exact():
    """Satellite (a): the racy ``n_oneway += 1`` is gone — per-thread
    cells make concurrent increments exact, and the bench's
    reset-by-assignment still works through the property."""
    from repro.net.transport import _PerThreadCounter

    c = _PerThreadCounter()
    N, T = 20_000, 8

    def worker():
        for _ in range(N):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == N * T          # the unlocked += would drop some
    c.set(0)
    assert c.value() == 0
    c.inc()
    assert c.value() == 1


def test_transport_n_oneway_property_reset():
    from repro.net.transport import Transport

    t = Transport.__new__(Transport)
    Transport.__init__(t, "addr:0")
    t._oneway.inc()
    t._oneway.inc()
    assert t.n_oneway == 2
    t.n_oneway = 0                     # eigenbench-style counter reset
    assert t.n_oneway == 0


# --------------------------------------------------------------------------- #
# determinism: same sim seed => byte-identical merged trace                    #
# --------------------------------------------------------------------------- #
def _sim_bank_trace(tmp_path, tag):
    import benchmarks.eigenbench as eb

    txtrace.reset()
    metrics.reset()
    txtrace.enable()
    cfg = eb.EigenConfig(nodes=2, clients_per_node=2, arrays_per_node=4,
                         txns_per_client=2, workload="bank", chain_len=3,
                         seed=1234)
    r = eb.run_benchmark("optsva-cf", cfg, transport="sim")
    out = tmp_path / f"trace_{tag}.json"
    n = export.write_trace(str(out))
    txtrace.disable()
    return r, n, out.read_bytes()


def test_sim_trace_byte_identical_per_seed(tmp_path):
    r1, n1, b1 = _sim_bank_trace(tmp_path, "a")
    r2, n2, b2 = _sim_bank_trace(tmp_path, "b")
    assert n1 == n2 > 0
    assert (r1.commits, r1.rpcs_per_txn, r1.oneways_per_txn) == \
           (r2.commits, r2.rpcs_per_txn, r2.oneways_per_txn)
    assert b1 == b2, "same seed must replay to byte-identical trace JSON"


def test_sim_trace_has_cross_node_flows(tmp_path):
    """Acceptance: a bank transaction under ``--transport sim`` produces
    flow links that visit client then home node (then chain nodes)."""
    _r, _n, raw = _sim_bank_trace(tmp_path, "flow")
    doc = json.loads(raw)
    evs = doc["traceEvents"]
    pids = {e["pid"]: e["args"]["name"] for e in evs
            if e["ph"] == "M" and e["name"] == "process_name"}
    flows = {}
    for e in evs:
        if e["ph"] in ("s", "t"):
            flows.setdefault(e["id"], []).append(pids[e["pid"]])
    multi = [chain for chain in flows.values()
             if chain[0].startswith("client")
             and any(s.startswith("node") for s in chain[1:])]
    assert multi, "expected client -> node flow chains in the merged trace"
    assert any(len({s for s in chain if s.startswith("node")}) >= 2
               for chain in flows.values()), \
        "expected at least one flow spanning two nodes (chained commit)"


# --------------------------------------------------------------------------- #
# cross-transport span-sequence equivalence                                    #
# --------------------------------------------------------------------------- #
_LIFECYCLE = ("dispense", "commit", "txn", "abort")


def _client_lifecycle(events):
    """The ordered client-side lifecycle signature: kinds + outcome
    details, txn uids normalized by first appearance."""
    seq, ids = [], {}
    for e in events:
        if e["kind"] not in _LIFECYCLE or not e["site"].startswith("client"):
            continue
        t = ids.setdefault(e["txn"], f"T{len(ids) + 1}")
        detail = e["detail"] if e["kind"] in ("commit", "txn") else ""
        seq.append((t, e["kind"], detail))
    return seq


def _collect_client_events():
    evs = []
    for t in txtrace.all_tracers():
        if t.site.startswith("client"):
            evs.extend(t.events())
    # Emission order, not span-start order: a txn span opens at begin()
    # but is emitted at its end. The schedule is a single client thread,
    # so per-ring idx order IS the lifecycle order.
    evs.sort(key=lambda e: (e["site"], e["ring"], e["idx"]))
    return evs


def test_cross_transport_lifecycle_span_equivalence():
    """The equivalence schedule (tests/test_net_equivalence.py) emits the
    same ordered client lifecycle spans on inproc, tcp, and sim."""
    from tests.test_net_equivalence import (_run_schedule, _run_schedule_sim,
                                            _topology_inproc, _topology_tcp)

    sigs = {}
    for name, make in (("inproc", _topology_inproc), ("tcp", _topology_tcp)):
        txtrace.reset()
        txtrace.enable()
        reg, down = make()
        try:
            _run_schedule(reg)
        finally:
            down()
            txtrace.disable()
        sigs[name] = _client_lifecycle(_collect_client_events())

    txtrace.reset()
    txtrace.enable()
    try:
        _run_schedule_sim()
    finally:
        txtrace.disable()
    sigs["sim"] = _client_lifecycle(_collect_client_events())

    assert sigs["inproc"], "schedule must produce lifecycle spans"
    assert sigs["inproc"] == sigs["tcp"] == sigs["sim"], (
        f"lifecycle spans diverged:\n inproc={sigs['inproc']}\n "
        f"tcp={sigs['tcp']}\n sim={sigs['sim']}")


# --------------------------------------------------------------------------- #
# zero overhead when off                                                       #
# --------------------------------------------------------------------------- #
def test_disabled_tracing_changes_no_wire_metrics():
    """Acceptance: with tracing disabled, the bench wire metrics are
    EXACTLY unchanged — and enabling it adds zero protocol messages (the
    rings are in-process; export pulls explicitly)."""
    import benchmarks.eigenbench as eb

    cfg = eb.EigenConfig(nodes=2, clients_per_node=2, arrays_per_node=4,
                         txns_per_client=2, workload="bank", chain_len=3,
                         seed=77)

    txtrace.disable()
    r_off = eb.run_benchmark("optsva-cf", cfg, transport="sim")
    assert not any(t.events() for t in txtrace.all_tracers()), \
        "disabled tracing must record nothing"

    txtrace.reset()
    txtrace.enable()
    r_on = eb.run_benchmark("optsva-cf", cfg, transport="sim")
    txtrace.disable()
    assert any(t.events() for t in txtrace.all_tracers())

    assert (r_off.rpcs_per_txn, r_off.oneways_per_txn,
            r_off.replication_oneways_per_txn, r_off.commits) == \
           (r_on.rpcs_per_txn, r_on.oneways_per_txn,
            r_on.replication_oneways_per_txn, r_on.commits), \
        "tracing must add zero protocol messages"


def test_tracereport_phases_sum_to_total(tmp_path):
    """Acceptance: the per-phase decomposition partitions each txn's
    client window exactly (residual well under the 1% bound)."""
    import benchmarks.tracereport as tr

    _r, n, raw = _sim_bank_trace(tmp_path, "phases")
    assert n > 0
    path = tmp_path / "phases.json"
    path.write_bytes(raw)
    agg = tr.report(str(path))
    assert agg["total"] > 0
    assert agg["residual_pct"] < 1.0
    # the sim clock charges wire latency; it must show up somewhere
    assert agg["wire"] > 0


def test_stats_rpc_carries_metrics_snapshot():
    """The existing ``stats`` op now ships the node's metric registry —
    no new message type."""
    from repro.net.simnet import build_simnet

    txtrace.enable()
    try:
        net = build_simnet(5, 1)
        setup = net.client_registry("setup")
        node = setup.nodes[0]
        from repro.net.demo import Account
        node.bind("A", Account(10))
        out = {}

        def client():
            reg = net.client_registry("c0")
            from repro.core import Transaction
            t = Transaction(reg)
            p = t.reads(reg.locate("A"), 1)
            t.start(lambda tt: p.balance())
            out["stats"] = reg.nodes[0].client.call("stats")

        net.spawn(client, "c0")
        net.run()
        net.shutdown()
    finally:
        txtrace.disable()
    m = out["stats"]["metrics"]
    assert m["site"].startswith("node:")
    assert "counters" in m and "histograms" in m


# --------------------------------------------------------------------------- #
# compile log                                                                  #
# --------------------------------------------------------------------------- #
def test_compile_log_counts_a_new_shape_once_and_a_repeat_never():
    import jax
    import jax.numpy as jnp

    from repro.obs import compiles
    from repro.runtime import profiling

    def double_plus_one(x):
        return x * 2 + 1

    f = jax.jit(double_plus_one)
    built = lambda: [r for r in compiles.LOG.records
                     if r[0] == "jit(double_plus_one)" and r[1] == "compile"]
    stages = lambda: [r[1] for r in compiles.LOG.records
                      if "double_plus_one" in r[0]]
    profiling.watch_compiles()
    compiles.LOG.reset()
    try:
        txtrace.enable()
        f(jnp.ones(3)).block_until_ready()
        assert [r[1] for r in built()] == ["compile"]
        assert stages() == ["trace", "lower", "compile"]
        f(jnp.ones(3) * 5).block_until_ready()
        assert len(built()) == 1 and len(stages()) == 3
        f(jnp.ones(4)).block_until_ready()
        assert [r[1] for r in built()] == ["compile", "compile"]
        assert all(r[2] > 0 and r[3] is None for r in built())
        txtrace.disable()
        f(jnp.ones(5)).block_until_ready()     # kept with tracing off too
        assert len(built()) == 3
    finally:
        compiles.LOG.reset()


def test_compile_log_tells_a_cache_load_from_a_compile():
    from repro.obs import compiles
    from repro.runtime import profiling

    compiles.LOG.reset()
    try:
        profiling._on_event(profiling.CACHE_HIT_EVENT)
        profiling._on_duration(profiling.COMPILE_EVENT, 0.5, fun_name="jit(g)")
        profiling._on_duration(profiling.COMPILE_EVENT, 2.0, fun_name="jit(h)")
        profiling._on_duration("/jax/other", 9.0, fun_name="jit(k)")
        assert [r[:4] for r in compiles.LOG.records] == [
            ("jit(g)", "load", 0.5, None), ("jit(h)", "compile", 2.0, None)]
        t = compiles.LOG.records[0][4]
        # the two intervals overlap (recorded a moment apart): the union
        compiled, loaded, seconds = compiles.LOG.between(t, float("inf"))
        assert (compiled, loaded) == (1, 1)
        assert seconds == pytest.approx(2.0, abs=0.01)
        assert compiles.LOG.between(float("-inf"), t) == (0, 0, 0.0)
    finally:
        compiles.LOG.reset()


def test_compile_log_counts_nested_stages_once():
    """A nested function's trace lies inside its caller's: the seconds are
    the union of the records' intervals, and only compiles and loads are
    counted as programs."""
    from repro.obs import compiles
    from repro.runtime import profiling

    trace, lower = sorted(profiling.STAGE_EVENTS,
                          key=profiling.STAGE_EVENTS.get, reverse=True)
    compiles.LOG.reset()
    try:
        profiling._on_duration(trace, 0.1, fun_name="inner")
        profiling._on_duration(lower, 0.2, fun_name="jit(outer)")
        assert [r[:2] for r in compiles.LOG.records] == [
            ("inner", "trace"), ("jit(outer)", "lower")]
        log = compiles.CompileLog()
        log.records = [("inner", "trace", 1.0, None, 11.0),
                       ("outer", "trace", 3.0, None, 12.0),
                       ("jit(outer)", "lower", 2.0, None, 14.0),
                       ("jit(outer)", "compile", 5.0, None, 20.0)]
        assert log.between(0.0, 30.0) == (1, 0, 10.0)
        assert log.between(0.0, 13.0) == (0, 0, 3.0)
    finally:
        compiles.LOG.reset()
