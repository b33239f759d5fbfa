"""Observability subsystem tests (DESIGN.md §17): the span tracer and its
Chrome export, the metrics registry + legacy StatsView facade, per-flush
stat deltas (including the reset-mid-defer clamp regression), trace-id
propagation across loop-fused drains, and the explain report."""

import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import lazy as bh
from repro.core.executor import stats_delta
from repro.core.lazy import fresh_runtime
from repro.core.obs import ExplainReport, MetricsRegistry, explain, trace
from repro.core.obs.metrics import StatsView

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:                  # for tools.check_trace
    sys.path.insert(0, _ROOT)


@pytest.fixture
def tracer():
    """Install a fresh tracer for the test, always uninstalling after."""
    tr = trace.enable()
    try:
        yield tr
    finally:
        trace.disable()


def _chain(rt, n=32):
    x = bh.asarray(np.linspace(0.0, 1.0, n))
    y = (bh.sin(x) * 0.5 + x * 0.25) * 2.0
    return float(y.sum().numpy())


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_disabled_span_is_shared_noop_singleton(self):
        assert trace.active() is None
        s1 = trace.span("a", k=1)
        s2 = trace.span("b")
        assert s1 is s2                    # no allocation on the fast path
        with s1 as s:
            s.set(x=1)                     # all no-ops
        trace.instant("nothing")           # no-op, no error

    def test_disabled_overhead_is_small(self):
        ns = trace.disabled_span_overhead_ns(iterations=20_000, repeats=3)
        assert 0.0 <= ns < 1000.0          # CI sanity; bench gates at 100

    def test_span_and_instant_record_chrome_events(self, tracer):
        with trace.span("outer", a=1) as sp:
            sp.set(b=2)
            trace.instant("tick", n=3)
        assert [e["name"] for e in tracer.events] == ["tick", "outer"]
        tick, outer = tracer.events
        assert tick["ph"] == "i" and tick["s"] == "t"
        assert tick["args"] == {"n": 3}
        assert outer["ph"] == "X" and outer["dur"] >= 0
        assert outer["args"] == {"a": 1, "b": 2}
        for ev in tracer.events:
            for fld in ("name", "ph", "ts", "pid", "tid"):
                assert fld in ev

    def test_context_overlay_merges_and_restores(self, tracer):
        with trace.context(flush=7):
            trace.instant("inner")
            with trace.context(flush=8, extra="x"):
                trace.instant("nested")
        trace.instant("outside")
        by_name = {e["name"]: e["args"] for e in tracer.events}
        assert by_name["inner"] == {"flush": 7}
        assert by_name["nested"] == {"flush": 8, "extra": "x"}
        assert by_name["outside"] == {}

    def test_async_pair(self, tracer):
        tracer.async_begin("win", "id-1")
        tracer.async_end("win", "id-1", {"n": 4})
        b, e = tracer.events
        assert (b["ph"], e["ph"]) == ("b", "e")
        assert b["id"] == e["id"] == "id-1"

    def test_max_events_stops_recording(self):
        tr = trace.Tracer(max_events=2)
        for i in range(5):
            tr.instant(f"e{i}")
        assert len(tr.events) == 2 and tr.dropped == 3
        assert tr.to_chrome()["otherData"]["dropped_events"] == 3

    def test_export_chrome_roundtrip(self, tracer, tmp_path):
        trace.instant("x")
        path = str(tmp_path / "t.json")
        tracer.export_chrome(path)
        doc = json.loads(open(path).read())
        assert doc["traceEvents"][0]["name"] == "x"
        assert doc["displayTimeUnit"] == "ms"

    def test_enable_returns_installed_disable_returns_it(self):
        tr = trace.enable()
        try:
            assert trace.active() is tr
        finally:
            assert trace.disable() is tr
        assert trace.active() is None


# ---------------------------------------------------------------------------
# pipeline instrumentation
# ---------------------------------------------------------------------------

STAGES = ("stage.trace", "stage.graph", "stage.partition",
          "stage.schedule", "stage.lower", "stage.execute")


class TestPipelineSpans:
    def test_single_flush_emits_all_six_stages(self, tracer):
        with fresh_runtime(algorithm="greedy") as rt:
            _chain(rt)
        names = {e["name"] for e in tracer.events}
        for stage in STAGES:
            assert stage in names, f"missing {stage}"
        assert "flush" in names and "block" in names and "build" in names
        assert "plan.lookup" in names and "cache.exec" in names

    def test_events_validate_against_chrome_schema(self, tracer):
        from tools.check_trace import check_events
        with fresh_runtime(algorithm="greedy") as rt:
            _chain(rt)
        assert check_events(tracer.events) == []

    def test_flush_ids_distinct_per_flush(self, tracer):
        with fresh_runtime(algorithm="greedy", loop_fusion=False) as rt:
            _chain(rt)
            _chain(rt)
        ids = {e["args"]["flush"] for e in tracer.events
               if e["name"] == "flush"}
        assert len(ids) >= 2

    def test_trace_id_propagates_into_loop_drain(self, tracer):
        """A drain triggered by a LATER flush (here: the empty sync flush)
        inherits that flush's trace id on every event it emits."""
        with fresh_runtime(algorithm="greedy", loop_threshold=2,
                           loop_unroll=16) as rt:
            x = bh.asarray(np.linspace(0.0, 1.0, 32))
            bh.flush()
            for _ in range(6):
                y = x * 0.99 + bh.sin(x) * 0.01
                x.delete()
                x = y
                bh.flush()
            final = float(x.sum().numpy())    # drains the queue
        assert np.isfinite(final)
        drains = [e for e in tracer.events if e["name"] == "loop.drain"]
        assert drains, "loop fusion never drained"
        drain_fid = drains[-1]["args"]["flush"]
        loop_execs = [e for e in tracer.events
                      if e["name"] == "stage.execute"
                      and e["args"].get("loop")]
        assert loop_execs and loop_execs[-1]["args"]["flush"] == drain_fid
        defer_fids = {e["args"]["flush"] for e in tracer.events
                      if e["name"] == "loop.defer"}
        assert drain_fid not in defer_fids   # the drain is a later flush

    def test_loop_async_window_brackets_defers(self, tracer):
        with fresh_runtime(algorithm="greedy", loop_threshold=2,
                           loop_unroll=16) as rt:
            x = bh.asarray(np.linspace(0.0, 1.0, 32))
            bh.flush()
            for _ in range(5):
                y = x * 0.5 + 0.1
                x.delete()
                x = y
                bh.flush()
            float(x.sum().numpy())
        phases = [e["ph"] for e in tracer.events
                  if e["name"] == "loop.deferred"]
        assert phases == ["b", "e"]


# ---------------------------------------------------------------------------
# host-side spans: the live trace stage, the host<->device edge, the
# merge-cache probe, block names and the profiler mirror
# ---------------------------------------------------------------------------

class _ExitLog(trace.Tracer):
    """A tracer subclass that overrides ``span()``, as a profiler mirror
    does, and logs every span it opens and the thread each one exits on."""

    def __init__(self):
        super().__init__()
        self.opened = []
        self.exits = []

    def span(self, name, args=None):
        inner = super().span(name, args)
        self.opened.append(name)
        exits = self.exits

        class _CM:
            def __enter__(self):
                return inner.__enter__()

            def __exit__(self, *exc):
                exits.append((name, threading.get_ident()))
                return inner.__exit__(*exc)
        return _CM()


def _events(tracer, name):
    return [e for e in tracer.events if e["name"] == name]


class TestHostSpans:
    def test_stage_trace_is_live_from_first_record_to_flush_start(
            self, tracer):
        with fresh_runtime(loop_fusion=False) as rt:
            x = bh.asarray(np.linspace(0.0, 1.0, 32))     # no record
            t_first = time.perf_counter_ns()
            y = bh.sin(x) * 2.0
            assert rt._trace_stage is not None            # open now
            assert not _events(tracer, "stage.trace")
            time.sleep(0.02)
            y.numpy()
        st, fl = _events(tracer, "stage.trace")[0], _events(tracer,
                                                             "flush")[0]
        assert st["ph"] == "X"
        assert st["ts"] >= (t_first - tracer._epoch_ns) / 1000.0 - 0.001
        assert st["dur"] >= 20_000.0
        assert st["ts"] + st["dur"] <= fl["ts"] + 0.002   # ends first
        assert st["args"] == {"flush": fl["args"]["flush"],
                              "n_ops": fl["args"]["n_ops"]}

    def test_stage_trace_exits_on_its_own_thread(self):
        tr = trace.enable(_ExitLog())
        try:
            with fresh_runtime(loop_fusion=False) as rt:
                _chain(rt)
        finally:
            trace.disable()
        me = threading.get_ident()
        assert ("stage.trace", me) in tr.exits
        assert "stage.trace" in tr.opened
        assert _events(tr, "stage.trace")

    def test_stage_trace_falls_back_to_retroactive_across_threads(self):
        """Recording starts on one thread and the flush runs on another:
        the span is recorded with ``complete`` and its context manager is
        never exited off the thread that opened it."""
        tr = trace.enable(_ExitLog())
        try:
            rt = bh.Runtime(loop_fusion=False)
            with rt.activate():
                x = bh.asarray(np.linspace(0.0, 1.0, 32))
            got = {}

            def first_records():
                with rt.activate():
                    got["y"] = bh.sin(x) * 2.0
            t = threading.Thread(target=first_records)
            t.start()
            t.join()
            with rt.activate():
                out = (got["y"] + 1.0).numpy()
        finally:
            trace.disable()
        np.testing.assert_allclose(
            out, np.sin(np.linspace(0.0, 1.0, 32)) * 2.0 + 1.0)
        assert not [e for e in tr.exits if e[0] == "stage.trace"]
        (st,) = _events(tr, "stage.trace")
        fl = _events(tr, "flush")[0]
        assert st["ph"] == "X" and st["dur"] > 0
        assert st["ts"] + st["dur"] <= fl["ts"] + 0.002
        assert st["args"] == {"flush": fl["args"]["flush"],
                              "n_ops": fl["args"]["n_ops"]}

    def test_stage_trace_of_a_tape_begun_before_tracing(self):
        """A tape whose first record() came before ``enable`` still gets
        its stage, from that record() to the flush, once."""
        with fresh_runtime(loop_fusion=False) as rt:
            x = bh.asarray(np.linspace(0.0, 1.0, 32))
            t_first = time.perf_counter_ns()
            y = bh.sin(x) * 2.0
            tr = trace.enable()
            try:
                y.numpy()
            finally:
                trace.disable()
        (st,) = _events(tr, "stage.trace")
        fl = _events(tr, "flush")[0]
        first = (t_first - tr._epoch_ns) / 1000.0
        assert first - 0.001 <= st["ts"] <= first + 1000.0
        assert st["ts"] < 0                 # before the tracer existed
        assert st["args"] == {"flush": fl["args"]["flush"],
                              "n_ops": fl["args"]["n_ops"]}

    def test_host_edge_and_lookup_spans_carry_flush_ids_and_args(
            self, tracer):
        with fresh_runtime(loop_fusion=False) as rt:
            data = np.arange(24, dtype=np.float32).reshape(4, 6)
            x = rt.adopt(data)
            out = (x * 2.0).sum(axis=1).numpy()
        np.testing.assert_allclose(out, (data * 2.0).sum(axis=1))
        fid = _events(tracer, "flush")[0]["args"]["flush"]
        (adopt,) = _events(tracer, "adopt")
        assert adopt["ph"] == "X"
        assert adopt["args"] == {"flush": fid, "bytes": data.nbytes}
        (read,) = _events(tracer, "sync.read")
        assert read["args"] == {"flush": fid, "bytes": out.nbytes}
        (lookup,) = _events(tracer, "plan.lookup")
        assert lookup["args"]["flush"] == fid
        assert lookup["args"]["hit"] == "miss"
        assert re.fullmatch(r"[0-9a-f]{16}", lookup["args"]["key"])
        assert not _events(tracer, "cache.merge")

    def test_repeated_tape_second_lookup_hits_memory(self, tracer):
        with fresh_runtime(loop_fusion=False) as rt:
            for _ in range(3):
                _chain(rt)
        lookups = [e["args"] for e in _events(tracer, "plan.lookup")]
        assert lookups[0]["hit"] == "miss"
        # the later tapes start with the DELs of the one before: the same
        # structure from the second on, so the third replays the second
        assert lookups[2]["hit"] == "memory"
        assert lookups[2]["key"] == lookups[1]["key"]

    def test_plan_store_lookup_reads_disk(self, tracer, tmp_path):
        with fresh_runtime(loop_fusion=False, plan_store=str(tmp_path)) \
                as rt:
            _chain(rt)
        with fresh_runtime(loop_fusion=False, plan_store=str(tmp_path)) \
                as rt:
            _chain(rt)
        first, second = _events(tracer, "plan.lookup")[:2]
        assert (first["args"]["hit"], second["args"]["hit"]) == ("miss",
                                                                 "disk")
        assert first["args"]["key"] == second["args"]["key"]

    def test_span_overriding_subclass_sees_every_new_span(self):
        tr = trace.enable(_ExitLog())
        try:
            with fresh_runtime(loop_fusion=False) as rt:
                _chain(rt)
        finally:
            trace.disable()
        for name in ("stage.trace", "adopt", "sync.read", "plan.lookup",
                     "flush", "stage.execute", "block"):
            assert name in tr.opened, name
            assert _events(tr, name), name

    def test_annotate_mirrors_spans_into_the_profiler(self, tmp_path):
        import jax
        from bench import tracefile

        with fresh_runtime(loop_fusion=False) as rt:
            _chain(rt)                          # compile outside the trace
            bh.flush()                          # and run its DELs
            tr = trace.enable(trace.Tracer(annotate=True))
            jax.profiler.start_trace(str(tmp_path))
            try:
                with jax.profiler.TraceAnnotation(tracefile.WINDOW):
                    _chain(rt)
            finally:
                jax.profiler.stop_trace()
                trace.disable()
        host = {e.name for e in tracefile.load(tmp_path).host}
        for name in ("stage.trace", "adopt", "sync.read", "plan.lookup",
                     "flush", "stage.execute"):
            assert "repro." + name in host, name
            assert _events(tr, name), name

    def test_disabled_record_opens_no_span(self):
        assert trace.active() is None
        with fresh_runtime(loop_fusion=False) as rt:
            x = bh.asarray(np.ones(8))
            y = x * 2.0
            assert rt.tape and rt._trace_stage is None
            y.numpy()

    def test_disabled_span_costs_about_an_empty_call(self):
        """The disabled fast path adds one global load and an ``is None``
        test to the call itself.  Timed against an empty function of the
        same signature, by the same method, interleaved (the least of
        several readings: noise only ever adds time); the absolute
        100 ns bar is ``benchmarks/run_all.py --compare``'s, on its host."""
        def empty(name, /, **args):
            return None

        def cost(fn, iterations=100_000, repeats=5):
            r = range(iterations)
            best = base = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in r:
                    fn("bench")
                best = min(best, time.perf_counter() - t0)
                t0 = time.perf_counter()
                for _ in r:
                    pass
                base = min(base, time.perf_counter() - t0)
            return (best - base) / iterations * 1e9

        assert trace.active() is None
        spans, empties = [], []
        for _ in range(4):
            spans.append(cost(trace.span))
            empties.append(cost(empty))
        assert min(spans) <= 1.5 * min(empties) + 10.0

    def test_block_names_are_stable_and_cold_marks_the_compile(
            self, tracer):
        names = []
        for _ in range(2):
            with fresh_runtime(loop_fusion=False) as rt:
                for _ in range(3):
                    _chain(rt)
                for fn, _donates, name, _views in rt.executor._cache.values():
                    assert fn.__name__ == name
            blocks = [e["args"] for e in tracer.events
                      if e["name"] == "block"]
            tracer.events.clear()
            seen = set()
            for b in blocks:
                assert re.fullmatch(r"repro_block_xla_[0-9a-f]{8}",
                                    b["name"])
                assert b["cold"] == (b["name"] not in seen)
                seen.add(b["name"])
            names.append([b["name"] for b in blocks])
        assert names[0] == names[1]          # the same in a new executor

    def test_block_executable_takes_its_name_as_the_module_name(self):
        import jax
        import jax.numpy as jnp
        from repro.core.executor import _named

        fn = jax.jit(_named(lambda a: a + 1.0, "repro_block_xla_0123abcd"))
        text = fn.lower(jnp.ones(4)).as_text()
        assert "@jit_repro_block_xla_0123abcd" in text

    def test_batched_server_path_ends_each_request_trace_stage(
            self, tracer):
        from repro.core.serve import Server

        srv = Server(window_s=0.25, max_batch=2)
        barrier = threading.Barrier(2)

        def worker(i):
            barrier.wait()
            srv.submit(i, lambda: bh.arange(32) * 2.0 + 1.0)
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert srv.metrics.counter("serve.batches").get() == 1
        # each request's tape left its session for the batch: its trace
        # stage ended there, on the thread that recorded it
        stages = [e for e in _events(tracer, "stage.trace")
                  if e["args"]["n_ops"] > 0]
        assert len(stages) == 2

    def test_solo_server_request_has_one_trace_stage(self, tracer):
        """A group of one runs its tape through the session's own flush
        after the tape left and came back: its stage is recorded once."""
        from repro.core.serve import Server

        srv = Server(window_s=0.0, max_batch=2)
        out = srv.submit(0, lambda: bh.arange(32) * 2.0 + 1.0)
        np.testing.assert_allclose(out, np.arange(32) * 2.0 + 1.0)
        assert srv.metrics.counter("serve.singles").get() == 1
        assert len(_events(tracer, "stage.trace")) == 1


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestBlockViewLowerings:
    """``block`` spans carry how the ``xla`` lowering read and wrote the
    block's views: ``permutes`` (a transpose or broadcast of one slice)
    and ``gathers`` (a static index gather)."""

    def test_lm_prefill_permutes_its_head_views_and_gathers_none(
            self, tracer):
        import jax
        from repro.models import transformer as T
        from repro.models.config import ModelConfig
        from repro.models.lazy_transformer import LazyTransformer

        cfg = ModelConfig(name="lm_views", family="dense", n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                          vocab_size=97, dtype="float32",
                          param_dtype="float32", norm_plus_one=True,
                          tie_embeddings=False)
        params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
        lt = LazyTransformer(params, cfg)
        lt.prefill(np.arange(16, dtype=np.int32)[None], 16)
        blocks = [e["args"] for e in _events(tracer, "block")]
        assert blocks
        assert sum(b["gathers"] for b in blocks) == 0
        # q, k and v to heads-major for the two attention matmuls, and
        # the attention output back, in every layer
        assert sum(b["permutes"] for b in blocks) >= 4 * cfg.n_layers
        assert all(b["permutes"] == b["gathers"] == 0 for b in blocks
                   if b["backend"] != "xla")

    def test_reversed_view_reports_a_gather(self, tracer):
        with fresh_runtime(loop_fusion=False):
            x = bh.asarray(np.arange(8.0))
            out = (x[::-1] * 2.0).numpy()
        np.testing.assert_array_equal(out, np.arange(8.0)[::-1] * 2.0)
        (block,) = _events(tracer, "block")
        assert block["args"]["backend"] == "xla"
        assert block["args"]["gathers"] >= 1
        assert block["args"]["permutes"] == 0


class TestMetricsRegistry:
    def test_counter_labels_and_get_or_create(self):
        reg = MetricsRegistry()
        c = reg.counter("x.total", ("kind",))
        c.inc(labels=("a",))
        c.inc(2, labels=("a",))
        assert c.get(("a",)) == 3 and c.get(("b",)) == 0
        assert reg.counter("x.total", ("kind",)) is c

    def test_kind_and_label_conflicts_raise(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(TypeError):
            reg.gauge("m")
        with pytest.raises(ValueError):
            reg.counter("m", ("unexpected",))

    def test_gauge_moves_both_ways(self):
        g = MetricsRegistry().gauge("q.depth")
        g.inc(5)
        g.dec(2)
        assert g.get() == 3

    def test_histogram_summary(self):
        h = MetricsRegistry().histogram("t.wall_s")
        for v in (0.005, 0.02, 0.02):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 3
        assert s["min"] == pytest.approx(0.005)
        assert s["max"] == pytest.approx(0.02)
        assert sum(s["buckets"].values()) == 3

    def test_snapshot_is_json_serializable(self):
        reg = MetricsRegistry()
        reg.counter("a.b", ("l",)).inc(labels=("x",))
        reg.histogram("a.h").observe(0.5)
        json.dumps(reg.snapshot())


class TestStatsView:
    def make(self):
        reg = MetricsRegistry()
        st = StatsView(reg, prefix="t")
        st.declare_scalar("n")
        st.declare_group("per_backend", ("backend",),
                         presets=("pallas", "xla"))
        st.declare_group("fallbacks", ("backend", "reason"),
                         presets=("pallas", "xla"))
        return st

    def test_legacy_idioms(self):
        st = self.make()
        st["n"] += 2                                    # scalar +=
        st["per_backend"]["pallas"] = 5                 # leaf assign
        bb = st["per_backend"]
        bb["xla"] = bb.get("xla", 0) + 1                # get-or-zero inc
        fr = st["fallbacks"].setdefault("pallas", {})   # nested setdefault
        fr["opcode"] = fr.get("opcode", 0) + 1
        assert dict(st)["n"] == 2
        assert st["per_backend"] == {"pallas": 5, "xla": 1}
        assert st["fallbacks"]["pallas"]["opcode"] == 1
        assert st["fallbacks"]["xla"] == {}             # preset empty
        assert st.to_dict() == {
            "n": 2, "per_backend": {"pallas": 5, "xla": 1},
            "fallbacks": {"pallas": {"opcode": 1}, "xla": {}}}

    def test_declare_on_first_scalar_write(self):
        st = self.make()
        st["new_metric"] = 7
        assert st["new_metric"] == 7 and "new_metric" in dict(st)

    def test_group_wholesale_replace(self):
        st = self.make()
        st["per_backend"]["pallas"] = 3
        st["per_backend"] = {"echo": 9}
        assert st["per_backend"] == {"echo": 9}
        st["fallbacks"] = {"echo": {"x": 1}}
        assert st["fallbacks"] == {"echo": {"x": 1}}

    def test_missing_key_raises(self):
        st = self.make()
        with pytest.raises(KeyError):
            st["absent"]
        with pytest.raises(KeyError):
            st["per_backend"]["never_seen"]

    def test_truthiness_of_empty_group(self):
        st = self.make()
        assert not st["fallbacks"]["pallas"]            # legacy `or "none"`
        st["fallbacks"]["pallas"]["r"] = 1
        assert st["fallbacks"]["pallas"]


# ---------------------------------------------------------------------------
# stats deltas
# ---------------------------------------------------------------------------

class TestStatsDelta:
    def test_missing_keys_in_before(self):
        before = {"a": 1, "g": {"xla": 1}}
        after = {"a": 2, "b": 5, "g": {"xla": 2, "pallas": 3}}
        assert stats_delta(before, after) == {
            "a": 1, "b": 5, "g": {"xla": 1, "pallas": 3}}

    def test_clamped_at_zero(self):
        before = {"a": 5, "g": {"xla": {"r": 4}}}
        after = {"a": 2, "g": {"xla": {"r": 1}}}
        assert stats_delta(before, after) == {"a": 0, "g": {"xla": {"r": 0}}}

    def test_new_backend_between_snapshots_live_views(self):
        with fresh_runtime(algorithm="greedy") as rt:
            before = rt.executor.snapshot_stats()
            _chain(rt)
            d = stats_delta(before, rt.executor.stats)
        assert d["blocks_run"] >= 1
        assert all(v >= 0 for v in d["backend_blocks"].values())
        json.dumps(d)                       # plain dicts all the way down

    def test_reset_mid_defer_deltas_stay_nonnegative(self):
        """Regression (ISSUE 7 satellite): reset_stats() while iterations
        sit in the deferred loop queue used to yield negative
        loop_iterations deltas in the drain's history entry."""
        with fresh_runtime(algorithm="greedy", loop_threshold=2,
                           loop_unroll=4) as rt:
            x = bh.asarray(np.linspace(0.0, 1.0, 32))
            bh.flush()
            for _ in range(9):              # several drains at unroll=4
                y = x * 0.99 + bh.sin(x) * 0.01
                x.delete()
                x = y
                bh.flush()
            assert rt._loop.pending         # mid-defer right now
            snap = rt.executor.snapshot_stats()
            assert snap["loop_iterations"] > 0
            rt.executor.reset_stats()
            float(x.sum().numpy())          # drains the remaining queue
            d = stats_delta(snap, rt.executor.stats)

            def check(m):
                for v in m.values():
                    if isinstance(v, dict):
                        check(v)
                    else:
                        assert v >= 0, (m, d)
            check(d)
            drain = [h for h in rt.history if h.get("loop_drain")][-1]
            assert drain["exec"]["loop_iterations"] >= 0

    def test_snapshot_survives_reset_shape_change(self):
        with fresh_runtime(algorithm="greedy") as rt:
            _chain(rt)
            snap = rt.executor.snapshot_stats()
            rt.executor.reset_stats()
            assert rt.executor.stats["blocks_run"] == 0
            _chain(rt)
            d = stats_delta(snap, rt.executor.stats)
            assert d["blocks_run"] >= 0


# ---------------------------------------------------------------------------
# executor metrics backing
# ---------------------------------------------------------------------------

class TestExecutorMetrics:
    def test_stats_is_registry_backed(self):
        with fresh_runtime(algorithm="greedy") as rt:
            _chain(rt)
            ex = rt.executor
            assert isinstance(ex.stats, StatsView)
            c = ex.metrics.get("executor.blocks_run")
            assert c is not None and c.get() == ex.stats["blocks_run"]
            assert "executor.backend_blocks" in ex.metrics.names()

    def test_history_exec_deltas_sum_to_live_stats(self):
        with fresh_runtime(algorithm="greedy", loop_fusion=False) as rt:
            _chain(rt)
            _chain(rt)
            total = sum(h["exec"]["blocks_run"] for h in rt.history
                        if "exec" in h)
            assert total == rt.executor.stats["blocks_run"]


# ---------------------------------------------------------------------------
# explain
# ---------------------------------------------------------------------------

def _decision_program(rt):
    """Fusible chain + fuse-forbidden shifted write + pallas-opaque matmul:
    one flush with merges taken, a priced rejected merge and a per-backend
    decline."""
    x = bh.asarray(np.linspace(0.0, 1.0, 256))
    t = bh.sin(x) * 0.5 + x * 0.25
    w = t * 2.0
    x[1:] = w[:-1]
    out = x + w          # reads x after the shifted write: merge rejected
    a = bh.asarray(np.arange(64.0).reshape(8, 8))
    mm = bh.matmul(a, a)
    rt.flush()
    return out, mm


class TestExplain:
    def test_requires_a_flush(self):
        with fresh_runtime(algorithm="greedy") as rt:
            with pytest.raises(ValueError):
                explain(rt)

    def test_report_contents(self):
        with fresh_runtime(algorithm="greedy",
                           backend=("pallas", "xla")) as rt:
            _decision_program(rt)
            rep = explain(rt)
        assert isinstance(rep, ExplainReport)
        assert rep.n_blocks == len(rep.blocks) > 0
        assert rep.taken_merges(), "chain should merge"
        rej = rep.rejected_merges()
        assert rej and all(m.saving > 0 for m in rej)
        assert all(m.reason in ("fuse-forbidden", "dependency-cycle")
                   for m in rej)
        # every work block carries a verdict per policy backend, and the
        # matmul block shows pallas's decline reason
        declined = []
        for b in rep.blocks:
            if b.backend is None:
                continue
            assert {v.backend for v in b.verdicts} == {"pallas", "xla"}
            assert sum(v.winner for v in b.verdicts) == 1
            declined += [v for v in b.verdicts if not v.claimed]
        assert any(v.reason == "opcode" for v in declined)
        assert rep.cache["resident"] is True

    def test_replay_does_not_perturb_cache_counters(self):
        with fresh_runtime(algorithm="greedy") as rt:
            _decision_program(rt)
            h0, m0 = rt.cache.hits, rt.cache.misses
            explain(rt)
            assert (rt.cache.hits, rt.cache.misses) == (h0, m0)

    def test_json_and_text_render(self):
        with fresh_runtime(algorithm="greedy") as rt:
            _decision_program(rt)
            rep = explain(rt)
        doc = json.loads(rep.to_json())
        assert doc["schema"] == "repro_explain_v1"
        assert doc["merges"] and doc["blocks"]
        text = rep.format_text()
        assert "rejected" in text and "declined" not in text.split()[0]
        assert "merge cache" in text

    def test_loop_events_in_report(self):
        with fresh_runtime(algorithm="greedy", loop_threshold=2,
                           loop_unroll=8) as rt:
            x = bh.asarray(np.linspace(0.0, 1.0, 32))
            bh.flush()
            for _ in range(5):
                y = x * 0.5 + 0.1
                x.delete()
                x = y
                bh.flush()
            float(x.sum().numpy())
            rep = explain(rt)
        kinds = {e["event"] for e in rep.loop}
        assert {"arm", "defer", "drain"} <= kinds

    def test_explain_matches_executed_backends(self):
        """The replayed winners agree with what actually ran."""
        with fresh_runtime(algorithm="greedy",
                           backend=("pallas", "xla")) as rt:
            _decision_program(rt)
            executed = dict(rt.executor.stats["backend_blocks"])
            rep = explain(rt)
        replayed: dict = {}
        for b in rep.blocks:
            if b.backend:
                replayed[b.backend] = replayed.get(b.backend, 0) + 1
        for name, n in replayed.items():
            assert executed.get(name, 0) == n
