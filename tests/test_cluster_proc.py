"""Multi-process cluster: RPC frames, real worker processes, SIGKILL
fault paths, and cross-process first-settlement-wins.

The distributed contract under test (docs/cluster.md):

* the RPC frame protocol is versioned — a peer speaking a different
  version gets an explicit error frame, never a misparse;
* a SIGKILLed worker's heartbeats stop, the keeper expires it, and its
  leased events requeue (attempt bumped) to the survivors — every
  submitted invocation settles, none stranded (parity with the sim's
  ``kill-node`` semantics in tests/test_faults.py);
* redelivery is bounded: past ``max_attempts`` the master settles a
  permanent ``retries exhausted`` error record;
* settlement is first-wins *across processes*: duplicate and unknown
  settle records are refused, and a master restarted from a snapshot
  still refuses ids settled in its previous life;
* :class:`ClusterBackend` is transport-agnostic — the in-process
  transport drives the same master surface the RPC transport does.
"""
import os
import re
import socket
import subprocess
import sys
import threading
import time

import pytest

from repro.cluster import (ClusterBackend, InProcTransport, Master,
                           RpcClient, RpcError, start_cluster)
from repro.cluster.rpc import (RPC_VERSION, inv_from_wire, inv_to_wire,
                               recv_frame, send_frame)
from repro.core.events import Invocation
from repro.faults import inject
from repro.gateway import (EngineBackend, Gateway,
                           InvocationRetriesExhausted, Workflow)

EXHAUSTED_RE = re.compile(r"^retries exhausted after \d+ attempt\(s\): ")

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
SLEEP_SPEC = "repro.cluster.runtimes:sleep_runtime"
ADD_SPEC = "repro.cluster.runtimes:add_runtime"


# ------------------------------------------------------------ RPC frames
def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        msg = {"v": RPC_VERSION, "id": 7, "op": "take",
               "blob": "aGk=", "nested": {"x": [1, 2, 3]}}
        send_frame(a, msg)
        assert recv_frame(b) == msg
        b.close()                       # orderly EOF
        assert recv_frame(a) is None
    finally:
        a.close()


def test_invocation_wire_roundtrip_preserves_identity_and_chain():
    inv = Invocation(runtime_id="rt", data_ref="d", config={"k": 1},
                     r_start=1.0)
    inv.n_start, inv.e_start, inv.e_end = 1.5, 2.0, 3.0
    inv.attempt, inv.tenant, inv.workflow = 2, "paid", "wf0"
    out = inv_from_wire(inv_to_wire(inv))
    assert out.inv_id == inv.inv_id     # submitting client's id wins
    for f in ("runtime_id", "data_ref", "config", "r_start", "n_start",
              "e_start", "e_end", "attempt", "tenant", "workflow"):
        assert getattr(out, f) == getattr(inv, f), f


def test_version_mismatch_refused_with_explicit_error_frame():
    master = Master()
    addr = master.serve()
    try:
        cli = RpcClient(addr)
        # a well-formed frame from a future protocol version
        with cli._lock:
            send_frame(cli._sock, {"v": RPC_VERSION + 1, "id": 1,
                                   "op": "stats"})
            rsp = recv_frame(cli._sock)
        assert rsp["ok"] is False
        assert "version mismatch" in rsp["error"]
        cli.close()
    finally:
        master.stop()


# ------------------------------------------- real worker processes
def test_two_workers_serve_and_results_carry_distinct_pids():
    h = start_cluster(2, heartbeat_timeout_s=10.0)
    try:
        gw = Gateway(h.backend)
        rid = h.backend.register_spec(SLEEP_SPEC, {"sleep_s": 0.01})
        futs = gw.map(rid, [{"i": i} for i in range(12)])
        results = [f.result() for f in futs]
        assert [r["echo"]["i"] for r in results] == list(range(12))
        assert len({r["pid"] for r in results}) == 2    # both processes
        m = gw.metrics
        assert len(m.completed) == 12 and m.r_success() == 12
        assert all(i.check_monotone() for i in m.completed)
        st = h.backend.stats()
        assert st["settled"] == 12 and st["duplicate_settles"] == 0
    finally:
        h.close()


def test_sigkill_mid_batch_requeues_lease_and_all_settle():
    """Real process death while holding a lease: the keeper expires the
    worker, the event redelivers to the survivor with attempt bumped —
    the sim kill-node contract, on actual SIGKILL."""
    h = start_cluster(2, heartbeat_timeout_s=0.8, keeper_interval_s=0.1,
                      heartbeat_s=0.2)
    try:
        gw = Gateway(h.backend)
        rid = h.backend.register_spec(SLEEP_SPEC, {"sleep_s": 0.3})
        futs = gw.map(rid, [{"i": i} for i in range(6)])
        time.sleep(0.1)                 # both workers now mid-sleep
        assert h.launcher.kill(0)       # SIGKILL, no cleanup
        results = [f.result() for f in futs]
        assert len(results) == 6        # none stranded
        m = gw.metrics
        assert m.r_success() == 6
        retried = [i for i in m.completed if i.attempt > 0]
        assert retried, "the kill must have lost leased work"
        surviving_pid = results[0]["pid"]
        for inv in retried:
            assert inv.node == "w1"     # fresh placement on the survivor
        assert all(r["pid"] == surviving_pid for r in results[-4:])
        st = h.backend.stats()
        assert st["workers_lost"] == 1 and st["requeued"] >= 1
    finally:
        h.close()


def test_sigkill_without_retries_settles_exhausted_error_records():
    """max_attempts=1 turns the lost delivery into a permanent error
    record with the same shape the sim and engine produce."""
    h = start_cluster(1, heartbeat_timeout_s=0.8, keeper_interval_s=0.1,
                      heartbeat_s=0.2)
    try:
        gw = Gateway(h.backend)
        rid = h.backend.register_spec(
            SLEEP_SPEC, {"sleep_s": 5.0, "max_attempts": 1})
        fut = gw.invoke(rid, {"i": 0})
        # kill only once the lone worker holds the lease (mid-sleep): a
        # kill before the take would leave the event queued, not lost
        deadline = time.monotonic() + 60.0
        while h.master.op_stats()["leased"] < 1:
            assert time.monotonic() < deadline, "the worker never took it"
            time.sleep(0.02)
        assert h.launcher.kill(0)
        with pytest.raises(InvocationRetriesExhausted):
            fut.result()
        inv = fut.invocation
        assert inv.r_end is not None and not inv.success
        assert inv.retries_exhausted and not inv.rejected
        assert EXHAUSTED_RE.match(inv.error)
        assert inv.attempt == 0         # never redelivered (bound 1)
        rec = h.backend.store.get_outcome(f"result:inv{inv.inv_id}")
        assert rec["ok"] is False and rec["value"] is None
        assert EXHAUSTED_RE.match(rec["error"])
    finally:
        h.close()


def test_cluster_ops_rejected_elsewhere_and_vice_versa():
    eb = EngineBackend()
    with pytest.raises(ValueError):
        inject(eb, [{"at": 0.0, "op": "kill-worker-process", "worker": 0}])
    eb.shutdown()
    master = Master()
    backend = ClusterBackend(InProcTransport(master))

    class _FakeLauncher:
        def kill(self, idx):
            return False

    backend.launcher = _FakeLauncher()
    with pytest.raises(ValueError):
        inject(backend, [{"at": 0.0, "op": "kill-node", "node": "x"}])
    with pytest.raises(ValueError):
        inject(backend, [{"at": 0.0, "op": "crash-worker", "worker": 0}])
    backend.shutdown()
    master.stop()


# --------------------------------- first-settlement-wins across processes
def _wire_settle(inv, blob=b"x", **fields):
    from repro.cluster.rpc import encode_blob
    import pickle
    from repro.core.storage import make_outcome
    payload = pickle.dumps(make_outcome(inv, {"ok": True}, None))
    rec = {"inv_id": inv.inv_id, "blob": encode_blob(payload),
           "fields": dict({"e_start": 0.1, "e_end": 0.2, "success": True,
                           "node": "w0"}, **fields)}
    return rec


def test_duplicate_and_unknown_settlements_refused():
    master = Master(lease_s=30.0)
    rsp = master.op_register(spec=SLEEP_SPEC, kwargs={"sleep_s": 0.0})
    rid = rsp["runtime_id"]
    inv = Invocation(runtime_id=rid, data_ref="", r_start=0.0)
    master.op_submit(event=inv_to_wire(inv))
    take = master.op_take(worker="w0", supported=[rid], max_batch=1,
                          timeout_s=1.0)
    taken = inv_from_wire(take["events"][0])

    first = master.op_settle(worker="w0",
                             records=[_wire_settle(taken)])
    assert first["results"][0]["accepted"]
    dup = master.op_settle(worker="w1", records=[_wire_settle(taken)])
    assert not dup["results"][0]["accepted"]
    assert "already settled" in dup["results"][0]["reason"]

    ghost = Invocation(runtime_id=rid, data_ref="", r_start=0.0)
    unknown = master.op_settle(worker="w0",
                               records=[_wire_settle(ghost)])
    assert not unknown["results"][0]["accepted"]
    assert "unknown" in unknown["results"][0]["reason"]
    assert master.op_stats()["duplicate_settles"] == 2
    master.stop()


def test_master_restart_refuses_resettlement_of_snapshot_ids():
    """A settle that raced a master restart must not double-apply: the
    restarted master's snapshot remembers settled ids and refuses."""
    m1 = Master(lease_s=30.0)
    rid = m1.op_register(spec=SLEEP_SPEC,
                         kwargs={"sleep_s": 0.0})["runtime_id"]
    inv = Invocation(runtime_id=rid, data_ref="", r_start=0.0)
    m1.op_submit(event=inv_to_wire(inv))
    take = m1.op_take(worker="w0", supported=[rid], max_batch=1,
                      timeout_s=1.0)
    taken = inv_from_wire(take["events"][0])
    assert m1.op_settle(
        worker="w0", records=[_wire_settle(taken)])["results"][0]["accepted"]
    snap = m1.snapshot()
    m1.stop()

    m2 = Master(lease_s=30.0, snapshot=snap)    # restarted master
    late = m2.op_settle(worker="w1", records=[_wire_settle(taken)])
    assert not late["results"][0]["accepted"]
    assert "already settled" in late["results"][0]["reason"]
    m2.stop()


# ----------------------------------------------- transport equivalence
def test_inproc_transport_drives_same_surface_as_rpc():
    """ClusterBackend over InProcTransport: submit through the backend,
    settle by driving the master's op surface directly (a synthetic
    worker), and the settlement pump resolves the future — no sockets
    anywhere."""
    master = Master(lease_s=30.0)
    backend = ClusterBackend(InProcTransport(master))
    gw = Gateway(backend)
    rid = backend.register_spec(SLEEP_SPEC, {"sleep_s": 0.0})

    def synthetic_worker():
        take = master.op_take(worker="wT", supported=[rid], max_batch=4,
                              timeout_s=5.0)
        events = [inv_from_wire(e) for e in take["events"]]
        master.op_settle(worker="wT",
                         records=[_wire_settle(e) for e in events])

    t = threading.Thread(target=synthetic_worker, daemon=True)
    t.start()
    fut = gw.invoke(rid, {"i": 1})
    assert fut.result() == {"ok": True}
    t.join(timeout=5.0)
    assert len(gw.metrics.completed) == 1
    assert gw.metrics.completed[0].check_monotone()
    backend.shutdown()
    master.stop()


# ------------------------------------------------- workflows over cluster
def test_workflow_chain_composes_across_worker_processes():
    h = start_cluster(2, heartbeat_timeout_s=10.0)
    try:
        gw = Gateway(h.backend)
        add1 = h.backend.register_spec(
            ADD_SPEC, {"runtime_id": "add1", "add": 1})
        add10 = h.backend.register_spec(
            ADD_SPEC, {"runtime_id": "add10", "add": 10})
        wf = Workflow("chain")
        a = wf.step("s1", add1, payload=5)
        b = wf.step("s2", add10, after=a)
        wf.step("s3", add1, after=b)
        out = gw.submit_workflow(wf).result()
        assert out == 17                # ((5+1)+10)+1
        tagged = [i for i in gw.metrics.completed if i.workflow == "chain"]
        assert len(tagged) == 3
        assert {i.step for i in tagged} == {"s1", "s2", "s3"}
    finally:
        h.close()


# ------------------------------------------------------- one chip each
def test_launcher_gives_each_jax_worker_its_own_chip(monkeypatch):
    from repro.cluster import backend as cb
    monkeypatch.setattr(cb, "host_tpu_chips", lambda: 2)
    launcher = cb.WorkerLauncher("127.0.0.1:1", pin_chips=True)
    assert launcher._free_chips(2) == [0, 1]
    env = launcher._env(1)
    assert env["TPU_VISIBLE_CHIPS"] == "1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    with pytest.raises(RuntimeError, match="2 TPU chip"):
        launcher.spawn(3)           # refused before any process starts
    assert launcher.alive() == []
    # workers that serve no JAX are held off the chips altogether
    plain = cb.WorkerLauncher("127.0.0.1:1")
    assert plain._free_chips(3) == [None] * 3
    assert plain._env()["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in plain._env()


def test_host_without_tpu_pins_nothing(monkeypatch):
    from repro.cluster import backend as cb
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert cb.host_tpu_chips() == 0
    launcher = cb.WorkerLauncher("127.0.0.1:1", pin_chips=True)
    assert launcher._free_chips(8) == [None] * 8


def test_serve_cluster_parent_never_initialises_jax():
    """``launch/serve.py --cluster``: real generation in a worker process
    while the parent (master + client) stays off every JAX backend."""
    code = ("from jax._src import xla_bridge\n"
            "from repro.launch.serve import main\n"
            "rc = main(['--cluster', '1', '--arch', 'granite-3-2b-smoke',\n"
            "           '--events', '2', '--max-new-tokens', '2'])\n"
            "print('rc', rc, 'jax', xla_bridge.backends_are_initialized())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "2/2 events succeeded" in out.stdout
    assert out.stdout.strip().splitlines()[-1] == "rc 0 jax False"
