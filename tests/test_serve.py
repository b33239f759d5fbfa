"""Serving-layer tests (DESIGN.md §18): concurrent session flushes against
shared caches (bitwise vs serial, no lost stats increments), the
disk-backed plan store's warm start and fault-injection matrix, the
snapshot/reset thread-visibility regression, admission control, and
cross-request micro-batching."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import lazy as bh
from repro.core.lazy import Runtime, fresh_runtime
from repro.core.obs import trace
from repro.core.serve import (AdmissionController, PlanStore,
                              SERVE_STORE_VERSION, Server, ServeRejected)
from repro.testing.tapegen import TapeProgram, _assert_bitwise

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")


def _run_threads(n, target):
    errors = []

    def wrap(i):
        try:
            target(i)
        except BaseException as e:      # noqa: BLE001 — surfaced below
            errors.append((i, e))

    threads = [threading.Thread(target=wrap, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, f"worker failures: {errors}"


def _store_file(root):
    files = [os.path.join(root, n) for n in os.listdir(root)
             if n.endswith(".json")]
    assert files, f"no store entries in {root}"
    return files[0]


def _counter(rt, name):
    return rt.executor.metrics.counter(name).get()


def _warm_program():
    a = bh.arange(256)
    b = a * 2.0 + 1.0
    c = bh.sqrt(b) + a * 0.5
    return c.numpy()


# ---------------------------------------------------------------------------
# concurrent sessions
# ---------------------------------------------------------------------------

class TestConcurrentSessions:
    N = 6

    def test_concurrent_flushes_bitwise_vs_serial(self):
        progs = [TapeProgram(900 + i, n_actions=10) for i in range(self.N)]
        refs = [p.run() for p in progs]
        rt = Runtime(loop_fusion=False)
        sessions = [rt.session() for _ in range(self.N)]
        results = [None] * self.N
        barrier = threading.Barrier(self.N)

        def worker(i):
            barrier.wait()
            with sessions[i].activate():
                results[i] = progs[i].run_current()

        _run_threads(self.N, worker)
        for i in range(self.N):
            _assert_bitwise(refs[i], results[i], f"tenant {i}")

    def test_no_lost_stats_increments(self):
        """N sessions x M flushes of one structure: exact dispatch totals.
        ``st[k] += 1`` read-modify-write races would lose counts here."""
        prog = TapeProgram(41, n_actions=8)
        with fresh_runtime(loop_fusion=False) as solo:
            prog.run_current()
            expected = solo.executor.stats.snapshot()["blocks_run"]
        rounds = 3
        rt = Runtime(loop_fusion=False)
        sessions = [rt.session() for _ in range(self.N)]
        barrier = threading.Barrier(self.N)

        def worker(i):
            barrier.wait()
            with sessions[i].activate():
                for _ in range(rounds):
                    prog.run_current()

        _run_threads(self.N, worker)
        st = rt.executor.stats.snapshot()
        assert st["blocks_run"] == expected * self.N * rounds
        # every work-block dispatch probed the executable cache exactly once
        assert (st["exec_cache_hits"] + st["exec_cache_misses"]
                == st["blocks_run"])

    def test_concurrent_merge_hits_match_warm_serial_rate(self):
        """Against a pre-warmed merge cache, EVERY concurrent flush must
        hit — the shared cache's hit rate is no worse than a serial warm
        replay's."""
        prog = TapeProgram(77, n_actions=8)
        rt = Runtime(loop_fusion=False)
        with rt.activate():
            prog.run_current()          # cold: populates the merge cache
        h0, m0 = rt.cache.hits, rt.cache.misses
        with rt.activate():
            prog.run_current()          # serial warm replay
        warm_hits = rt.cache.hits - h0
        assert warm_hits > 0 and rt.cache.misses == m0
        sessions = [rt.session() for _ in range(self.N)]
        barrier = threading.Barrier(self.N)

        def worker(i):
            barrier.wait()
            with sessions[i].activate():
                prog.run_current()

        h1, m1 = rt.cache.hits, rt.cache.misses
        _run_threads(self.N, worker)
        assert rt.cache.hits - h1 >= warm_hits * self.N
        assert rt.cache.misses == m1

    def test_fresh_runtime_is_thread_local(self):
        """Two threads' fresh runtimes must not observe each other."""
        seen = {}
        barrier = threading.Barrier(2)

        def worker(i):
            with fresh_runtime() as rt:
                barrier.wait()
                x = bh.full((8,), float(i))
                seen[i] = (rt, x.rt, float(x.numpy()[0]))

        _run_threads(2, worker)
        assert seen[0][0] is seen[0][1] and seen[1][0] is seen[1][1]
        assert seen[0][0] is not seen[1][0]
        assert seen[0][2] == 0.0 and seen[1][2] == 1.0

    def test_session_shares_caches_not_tape(self):
        rt = Runtime(loop_fusion=False)
        s1, s2 = rt.session(), rt.session()
        assert s1.scheduler is rt.scheduler
        assert s1.executor is rt.executor
        assert s1.cache is s2.cache
        assert s1.tape is not s2.tape and s1.buffers is not s2.buffers


# ---------------------------------------------------------------------------
# stats snapshot / reset thread visibility
# ---------------------------------------------------------------------------

class TestStatsThreadVisibility:
    def test_snapshot_is_consistent_under_concurrent_flushes(self):
        """A snapshot racing live flushes must never tear: the invariant
        hits + misses == blocks_run holds inside every snapshot, and the
        final totals are exact."""
        prog = TapeProgram(13, n_actions=6)
        rt = Runtime(loop_fusion=False)
        with rt.activate():
            prog.run_current()
        per_run = rt.executor.stats.snapshot()["blocks_run"]
        rt.executor.reset_stats()
        stop = threading.Event()
        torn = []

        def snapshotter():
            while not stop.is_set():
                st = rt.executor.snapshot_stats()
                if (st["exec_cache_hits"] + st["exec_cache_misses"]
                        != st["blocks_run"]):
                    torn.append(dict(st))

        snap_t = threading.Thread(target=snapshotter)
        snap_t.start()
        try:
            sessions = [rt.session() for _ in range(4)]

            def worker(i):
                with sessions[i].activate():
                    for _ in range(3):
                        prog.run_current()

            _run_threads(4, worker)
        finally:
            stop.set()
            snap_t.join()
        assert not torn, f"torn snapshots: {torn[:3]}"
        assert rt.executor.stats.snapshot()["blocks_run"] == per_run * 12

    def test_snapshot_blocks_while_reset_holds_the_lock(self):
        rt = Runtime(loop_fusion=False)
        order = []
        entered = threading.Event()

        def snap():
            entered.set()
            rt.executor.snapshot_stats()
            order.append("snapshot")

        with rt.executor.metrics.lock:
            t = threading.Thread(target=snap)
            t.start()
            entered.wait(2.0)
            time.sleep(0.05)
            order.append("holder")
        t.join(2.0)
        assert order == ["holder", "snapshot"]

    def test_reset_mid_run_never_yields_negative_history_deltas(self):
        prog = TapeProgram(29, n_actions=6)
        rt = Runtime(loop_fusion=False)
        sess = rt.session()
        stop = threading.Event()

        def resetter():
            while not stop.is_set():
                rt.executor.reset_stats()

        t = threading.Thread(target=resetter)
        t.start()
        try:
            with sess.activate():
                for _ in range(3):
                    prog.run_current()
        finally:
            stop.set()
            t.join()

        def no_negatives(d):
            for v in d.values():
                if isinstance(v, dict):
                    no_negatives(v)
                else:
                    assert v >= 0, d

        for entry in sess.history:
            no_negatives(entry["exec"])


# ---------------------------------------------------------------------------
# plan store: warm start + fault injection
# ---------------------------------------------------------------------------

class TestPlanStore:
    def test_cold_run_writes_warm_runtime_hits(self, tmp_path):
        store_dir = str(tmp_path)
        rt1 = Runtime(plan_store=store_dir, loop_fusion=False)
        with rt1.activate():
            ref = _warm_program()
        assert _counter(rt1, "cache.plan_store.write") >= 1
        assert len(os.listdir(store_dir)) >= 1

        tr = trace.enable()
        try:
            rt2 = Runtime(plan_store=store_dir, loop_fusion=False)
            with rt2.activate():
                got = _warm_program()
        finally:
            trace.disable()
        assert np.array_equal(ref, got)
        assert _counter(rt2, "cache.plan_store.hit") >= 1
        names = {e["name"] for e in tr.events}
        assert "stage.partition" not in names   # graph/partition skipped
        assert "cache.plan_store" in names

    def test_warm_start_in_fresh_process(self, tmp_path):
        """The acceptance proof: populate the store, then a genuinely new
        process hits it — ``cache.plan_store.hit`` >= 1 and no
        ``stage.partition`` span."""
        store_dir = str(tmp_path)
        script = (
            "import sys, json\n"
            "from repro.core.lazy import Runtime\n"
            "from repro.core import lazy as bh\n"
            "from repro.core.obs import trace\n"
            "tr = trace.enable()\n"
            "rt = Runtime(plan_store=sys.argv[1], loop_fusion=False)\n"
            "with rt.activate():\n"
            "    a = bh.arange(256)\n"
            "    c = (bh.sqrt(a * 2.0 + 1.0) + a * 0.5).numpy()\n"
            "m = rt.executor.metrics\n"
            "print(json.dumps({\n"
            "    'hit': m.counter('cache.plan_store.hit').get(),\n"
            "    'write': m.counter('cache.plan_store.write').get(),\n"
            "    'partition': sum(1 for e in tr.events\n"
            "                     if e['name'] == 'stage.partition'),\n"
            "    'checksum': float(c.sum())}))\n")
        env = dict(os.environ, PYTHONPATH=_SRC, JAX_PLATFORMS="cpu")
        outs = []
        for _ in range(2):
            p = subprocess.run([sys.executable, "-c", script, store_dir],
                               capture_output=True, text=True, env=env,
                               timeout=240)
            assert p.returncode == 0, p.stderr
            outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
        cold, warm = outs
        assert cold["write"] >= 1 and cold["partition"] >= 1
        assert warm["hit"] >= 1 and warm["partition"] == 0
        assert warm["checksum"] == cold["checksum"]

    def _populate(self, store_dir):
        rt = Runtime(plan_store=store_dir, loop_fusion=False)
        with rt.activate():
            ref = _warm_program()
        return ref

    def _reload(self, store_dir):
        rt = Runtime(plan_store=store_dir, loop_fusion=False)
        with rt.activate():
            got = _warm_program()
        return rt, got

    @pytest.mark.parametrize("doctor,counter", [
        (lambda raw: raw[: len(raw) // 2], "serve.store.corrupt"),  # truncated
        (lambda raw: b"\x00\xffgarbage not json", "serve.store.corrupt"),
        (lambda raw: json.dumps(
            {**json.loads(raw), "version": SERVE_STORE_VERSION + 1}
        ).encode(), "serve.store.stale"),                # foreign format
        (lambda raw: json.dumps(
            {**json.loads(raw), "cost_registry_version": -1}
        ).encode(), "serve.store.stale"),                # old cost registry
        (lambda raw: json.dumps(
            {**json.loads(raw), "epoch_sensitive": True,
             "calibration_epoch": -12345}
        ).encode(), "serve.store.stale"),                # stale calibration
        (lambda raw: json.dumps(
            {**json.loads(raw), "blocks": [["not", "ints"]]}
        ).encode(), "serve.store.corrupt"),              # schema violation
    ])
    def test_fault_injection_is_a_clean_counted_miss(self, tmp_path, doctor,
                                                     counter):
        store_dir = str(tmp_path)
        ref = self._populate(store_dir)
        path = _store_file(store_dir)
        raw = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(doctor(raw))
        rt, got = self._reload(store_dir)      # must not raise
        assert np.array_equal(ref, got)
        assert _counter(rt, counter) >= 1
        assert _counter(rt, "cache.plan_store.hit") == 0
        # the bad entry was re-planned and re-persisted
        assert _counter(rt, "cache.plan_store.write") >= 1

    def test_partition_backend_is_a_distinct_store_identity(self, tmp_path):
        """Fault-matrix sibling for ISSUE 9: a store populated by the
        greedy backend must be a clean (counted) miss for an ilp runtime
        — the backend is part of the plan key, so neither run can ever
        be served the other's blocks — and the greedy entry must survive
        untouched for later greedy warm starts."""
        store_dir = str(tmp_path)
        ref = self._populate(store_dir)        # greedy populates the store
        n_greedy = len(os.listdir(store_dir))
        rt = Runtime(plan_store=store_dir, loop_fusion=False,
                     partition_backend="ilp")
        with rt.activate():
            got = _warm_program()
        assert np.array_equal(ref, got)
        assert _counter(rt, "cache.plan_store.hit") == 0
        assert _counter(rt, "cache.plan_store.miss") >= 1
        # the ilp plan was persisted under its own key, not over greedy's
        assert _counter(rt, "cache.plan_store.write") >= 1
        assert len(os.listdir(store_dir)) > n_greedy
        rt2, got2 = self._reload(store_dir)    # greedy still warm-starts
        assert np.array_equal(ref, got2)
        assert _counter(rt2, "cache.plan_store.hit") >= 1
        # and the ilp runtime now warm-starts off its own entry too
        rt3 = Runtime(plan_store=store_dir, loop_fusion=False,
                      partition_backend="ilp")
        with rt3.activate():
            got3 = _warm_program()
        assert np.array_equal(ref, got3)
        assert _counter(rt3, "cache.plan_store.hit") >= 1

    def test_crash_during_write_leaves_old_entry_readable(self, tmp_path,
                                                          monkeypatch):
        store_dir = str(tmp_path)
        ref = self._populate(store_dir)
        path = _store_file(store_dir)
        before = open(path, "rb").read()

        # simulate dying before the rename: the tmp file exists, the
        # publish never happens
        def crash(src, dst):
            raise OSError("simulated crash before rename")

        store = PlanStore(store_dir)
        monkeypatch.setattr(os, "replace", crash)
        ok = store.store(("k",) * 3, ((0,),), None)
        monkeypatch.undo()
        assert ok is False
        assert store._metrics.counter("serve.store.write_error").get() == 1
        assert open(path, "rb").read() == before   # old entry untouched
        rt, got = self._reload(store_dir)
        assert np.array_equal(ref, got)
        assert _counter(rt, "cache.plan_store.hit") >= 1

    def test_concurrent_writers_race_cleanly(self, tmp_path):
        store = PlanStore(str(tmp_path))
        key = ("greedy", "bohrium", (), (), ("xla",), (), ("sig",))
        blocks = ((0, 1), (2,))

        def worker(i):
            for _ in range(20):
                assert store.store(key, blocks, None)
                loaded = store.load(key)
                assert loaded is not None and loaded[0] == blocks

        _run_threads(4, worker)
        assert store._metrics.counter("serve.store.corrupt").get() == 0
        assert store._metrics.counter("serve.store.stale").get() == 0
        # no orphaned temp files leaked past the atomic publish
        assert all(n.endswith(".json") for n in os.listdir(str(tmp_path)))

    def test_store_survives_unwritable_directory(self, tmp_path):
        store_dir = str(tmp_path / "sub")
        store = PlanStore(store_dir)
        os.chmod(store_dir, 0o500)
        try:
            ok = store.store(("k",) * 3, ((0,),), None)
        finally:
            os.chmod(store_dir, 0o700)
        if os.getuid() == 0:
            pytest.skip("running as root: chmod does not deny writes")
        assert ok is False
        assert store._metrics.counter("serve.store.write_error").get() == 1


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_backpressure_then_reject_on_timeout(self):
        adm = AdmissionController(max_pending=1)
        adm.acquire("a")
        t0 = time.perf_counter()
        with pytest.raises(ServeRejected):
            adm.acquire("b", timeout=0.05)
        assert time.perf_counter() - t0 >= 0.05
        m = adm._metrics
        assert m.counter("serve.admission.backpressure_waits").get() == 1
        assert m.counter("serve.admission.rejected",
                         ("tenant",)).get(("b",)) == 1
        adm.release("a")
        adm.acquire("b", timeout=0.05)     # slot freed: admitted
        adm.release("b")
        assert m.gauge("serve.queue_depth").get() == 0

    def test_backpressure_wakes_waiter(self):
        adm = AdmissionController(max_pending=1)
        adm.acquire("a")
        admitted = threading.Event()

        def waiter():
            adm.acquire("b", timeout=5.0)
            admitted.set()

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        assert not admitted.is_set()       # parked behind the full queue
        adm.release("a")
        assert admitted.wait(2.0)
        t.join()

    def test_per_tenant_cap_keeps_other_tenants_admissible(self):
        adm = AdmissionController(max_pending=8, per_tenant=1)
        adm.acquire("greedy")
        with pytest.raises(ServeRejected):
            adm.acquire("greedy", timeout=0.01)
        adm.acquire("other", timeout=0.01)  # unaffected by greedy's cap
        adm.release("greedy")
        adm.release("other")

    def test_server_rejects_when_full(self):
        srv = Server(batching=False, max_pending=1)
        release = threading.Event()
        started = threading.Event()

        def slow(tenant):
            def fn():
                started.set()
                release.wait(5.0)
                return bh.full((8,), 1.0)
            return srv.submit(tenant, fn)

        t = threading.Thread(target=slow, args=("a",))
        t.start()
        assert started.wait(2.0)
        with pytest.raises(ServeRejected):
            srv.submit("b", lambda: bh.full((8,), 2.0), timeout=0.05)
        release.set()
        t.join()
        out = srv.submit("b", lambda: bh.full((8,), 2.0), timeout=1.0)
        assert float(out[0]) == 2.0


# ---------------------------------------------------------------------------
# micro-batching server
# ---------------------------------------------------------------------------

def _make_request(data, with_random=False):
    def fn():
        a = bh.asarray(data)
        b = bh.floor((a * 2.0 + 3.0) % 1021.0)
        c = bh.maximum(b, a) + b.sum().broadcast_to(a.shape)
        if with_random:
            c = c + bh.floor(bh.random(a.shape) * 8.0)
        return c
    return fn


class TestServerBatching:
    TENANTS = 4

    def _datas(self, seed=3):
        rng = np.random.default_rng(seed)
        return [np.floor(rng.random(64) * 16.0) for _ in range(self.TENANTS)]

    def _concurrent(self, srv, datas, rounds=1, **req_kw):
        out = {}
        barrier = threading.Barrier(self.TENANTS)

        def worker(i):
            for r in range(rounds):
                barrier.wait()
                out[(i, r)] = srv.submit(i, _make_request(datas[i], **req_kw))

        _run_threads(self.TENANTS, worker)
        return out

    @pytest.mark.parametrize("with_random", [False, True])
    def test_batched_equals_serial_bitwise(self, with_random):
        datas = self._datas()
        ref_srv = Server(batching=False)
        refs = {}
        for r in range(2):
            for i in range(self.TENANTS):
                refs[(i, r)] = ref_srv.submit(
                    i, _make_request(datas[i], with_random=with_random))
        srv = Server(window_s=0.25, max_batch=self.TENANTS)
        out = self._concurrent(srv, datas, rounds=2,
                               with_random=with_random)
        for k in refs:
            assert refs[k].tobytes() == out[k].tobytes(), f"request {k}"
        m = srv.metrics
        assert m.counter("serve.batched_requests").get() >= self.TENANTS
        assert m.counter("serve.batches").get() >= 1

    def test_batch_sustains_four_tenants(self):
        """The acceptance floor: >= 4 concurrent tenants, coalesced into
        shared dispatches, bitwise identical to the unbatched path."""
        datas = self._datas(seed=11)
        ref_srv = Server(batching=False)
        refs = [ref_srv.submit(i, _make_request(datas[i]))
                for i in range(self.TENANTS)]
        srv = Server(window_s=0.5, max_batch=self.TENANTS)
        out = self._concurrent(srv, datas)
        for i in range(self.TENANTS):
            assert refs[i].tobytes() == out[(i, 0)].tobytes()
        assert srv.metrics.counter("serve.batch.requests").get() \
            == self.TENANTS
        assert srv.metrics.counter("serve.batch.dispatches").get() == 1

    def test_adopted_grid_round_trip(self):
        """Requests that adopt a 2-D grid and read it whole, transposed and
        in slices: the batched dispatch stacks the grids in their own shape
        and every answer is the unbatched one, and NumPy's."""
        rng = np.random.default_rng(5)
        grids = [np.floor(rng.random((8, 12)) * 16.0)
                 for _ in range(self.TENANTS)]

        def request(grid):
            def fn():
                a = bh.asarray(grid)
                return (a * 2.0 + a.T.T)[1:-1, 2:] + a[:-2, :-2]
            return fn

        want = [(g * 2.0 + g)[1:-1, 2:] + g[:-2, :-2] for g in grids]
        ref_srv = Server(batching=False)
        refs = [ref_srv.submit(i, request(g)) for i, g in enumerate(grids)]
        srv = Server(window_s=0.5, max_batch=self.TENANTS)
        out = {}
        barrier = threading.Barrier(self.TENANTS)

        def worker(i):
            barrier.wait()
            out[i] = srv.submit(i, request(grids[i]))

        _run_threads(self.TENANTS, worker)
        for i in range(self.TENANTS):
            np.testing.assert_array_equal(refs[i], want[i])
            assert refs[i].tobytes() == out[i].tobytes()
        assert srv.metrics.counter("serve.batch.requests").get() \
            == self.TENANTS

    def test_structurally_distinct_requests_do_not_coalesce(self):
        srv = Server(window_s=0.05, max_batch=4)
        outs = {}
        barrier = threading.Barrier(2)

        def worker(i):
            barrier.wait()
            scale = float(i + 2)           # different literal => different

            def fn():                      # structure => no shared group
                a = bh.arange(32)
                return a * scale + 1.0
            outs[i] = srv.submit(i, fn)

        _run_threads(2, worker)
        for i in range(2):
            assert np.array_equal(outs[i],
                                  np.arange(32) * float(i + 2) + 1.0)
        assert srv.metrics.counter("serve.batches").get() == 0
        assert srv.metrics.counter("serve.singles").get() == 2

    def test_request_fn_may_materialize_early(self):
        srv = Server(window_s=0.01)

        def fn():
            a = bh.arange(16)
            s = float(a.sum().numpy())     # early sync: batching forfeited
            return a + s
        out = srv.submit("t", fn)
        assert np.array_equal(out, np.arange(16) + 120.0)
        assert srv.metrics.counter("serve.singles").get() == 1

    def test_tenant_state_isolated_across_requests(self):
        srv = Server(batching=False)
        a = srv.submit("x", lambda: bh.full((4,), 1.0))
        b = srv.submit("y", lambda: bh.full((4,), 2.0))
        a2 = srv.submit("x", lambda: bh.full((4,), 1.0))
        assert float(a[0]) == 1.0 and float(b[0]) == 2.0
        assert np.array_equal(a, a2)

    def test_server_with_plan_store_end_to_end(self, tmp_path):
        datas = self._datas(seed=7)
        srv1 = Server(store=str(tmp_path), window_s=0.1,
                      max_batch=self.TENANTS)
        out1 = self._concurrent(srv1, datas)
        assert _counter(srv1.runtime, "cache.plan_store.write") >= 1
        srv2 = Server(store=str(tmp_path), window_s=0.1,
                      max_batch=self.TENANTS)
        out2 = self._concurrent(srv2, datas)
        assert _counter(srv2.runtime, "cache.plan_store.hit") >= 1
        for k in out1:
            assert out1[k].tobytes() == out2[k].tobytes()
