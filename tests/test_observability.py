"""Observability layer tests: span/trace model, tail-sampled buffer,
Chrome trace-event export, end-to-end serving traces (queue_wait ->
decode -> device -> respond covering the request wall), Prometheus
text-exposition grammar, structured JSON logging, the metrics
thread-safety hammer, and the metrics()-vs-swap() consistent-snapshot
regression.
"""

import json
import logging
import math
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.metrics import DriftMonitor, LatencyHistogram
from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.core.trace import (
    Span, TraceBuffer, Tracer, current_span, to_chrome_trace, use_span,
)
from mmlspark_tpu.serving.server import serve_model
from mmlspark_tpu.stages.basic import Lambda


# ---------------------------------------------------------------------------
# Prometheus text-format grammar validator (format 0.0.4)
# ---------------------------------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL = r"[a-zA-Z_][a-zA-Z0-9_]*"


def _parse_labels(s):
    labels = {}
    i = 0
    while i < len(s):
        m = re.match(_LABEL, s[i:])
        assert m, f"bad label name at {s[i:]!r}"
        name = m.group(0)
        i += m.end()
        assert s[i] == "=", f"expected '=' at {s[i:]!r}"
        i += 1
        assert s[i] == '"', f"expected opening quote at {s[i:]!r}"
        i += 1
        val = []
        while True:
            c = s[i]
            if c == "\\":
                nxt = s[i + 1]
                assert nxt in '\\"n', f"illegal escape \\{nxt}"
                val.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
                i += 2
            elif c == '"':
                i += 1
                break
            else:
                assert c != "\n", "raw newline in label value"
                val.append(c)
                i += 1
        labels[name] = "".join(val)
        if i < len(s):
            assert s[i] == ",", f"expected ',' at {s[i:]!r}"
            i += 1
    return labels


def validate_prom_text(text):
    """Grammar-check one exposition: HELP/TYPE lines, sample syntax,
    label escaping, histogram bucket ordering/monotonicity and the
    +Inf == _count contract. Returns (types, samples)."""
    types, helps, samples = {}, set(), []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            m = re.match(rf"# HELP ({_NAME}) .*$", line)
            assert m, f"bad HELP line: {line!r}"
            helps.add(m.group(1))
            continue
        if line.startswith("# TYPE "):
            m = re.match(
                rf"# TYPE ({_NAME}) "
                r"(counter|gauge|histogram|summary|untyped)$", line)
            assert m, f"bad TYPE line: {line!r}"
            types[m.group(1)] = m.group(2)
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = re.match(
            rf"^({_NAME})(?:\{{(.*)\}})? (\S+)(?: (\d+))?$", line)
        assert m, f"bad sample line: {line!r}"
        name, labelstr, value = m.group(1), m.group(2), m.group(3)
        labels = _parse_labels(labelstr) if labelstr else {}
        if value == "+Inf":
            v = math.inf
        elif value == "-Inf":
            v = -math.inf
        else:
            v = float(value)   # raises on malformed numbers
        samples.append((name, labels, v))

    def family(name):
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in types:
                return name[:-len(suffix)]
        return name

    for name, labels, _v in samples:
        base = family(name)
        assert base in types, f"sample {name} has no # TYPE"
        assert base in helps, f"sample {name} has no # HELP"

    for hist_name in [n for n, t in types.items() if t == "histogram"]:
        groups, counts = {}, {}
        for name, labels, v in samples:
            if name == hist_name + "_bucket":
                key = tuple(sorted((k, lv) for k, lv in labels.items()
                                   if k != "le"))
                groups.setdefault(key, []).append((labels["le"], v))
            elif name == hist_name + "_count":
                counts[tuple(sorted(labels.items()))] = v
        assert groups, f"histogram {hist_name} has no buckets"
        for key, buckets in groups.items():
            les = [math.inf if le == "+Inf" else float(le)
                   for le, _ in buckets]
            vals = [v for _, v in buckets]
            assert les == sorted(les), \
                f"{hist_name}{key}: le not ascending: {les}"
            assert math.isinf(les[-1]), \
                f"{hist_name}{key}: missing +Inf bucket"
            assert all(a <= b for a, b in zip(vals, vals[1:])), \
                f"{hist_name}{key}: cumulative counts not monotone"
            assert counts.get(key) == vals[-1], \
                f"{hist_name}{key}: _count != +Inf bucket"
    return types, samples


# ---------------------------------------------------------------------------
# span/trace model
# ---------------------------------------------------------------------------


class TestSpanModel:
    def test_ids_unique_and_trace_assembly(self):
        tracer = Tracer(enabled=True)
        tr = tracer.new_trace("request")
        s1 = tracer.start_span("queue_wait", tr)
        s2 = tracer.start_span("device", tr)
        ids = {tr.root.span_id, s1.span_id, s2.span_id}
        assert len(ids) == 3
        assert s1.trace_id == tr.trace_id
        assert s1.parent_id == tr.root.span_id
        assert [s.name for s in tr.spans()] == \
            ["request", "queue_wait", "device"]

    def test_incoming_trace_id_honored_and_clamped(self):
        tracer = Tracer(enabled=True)
        assert tracer.new_trace("r", trace_id="abc-123").trace_id \
            == "abc-123"
        long = "x" * 500
        assert len(tracer.new_trace("r", trace_id=long).trace_id) == 64

    def test_finish_idempotent_and_duration(self):
        tracer = Tracer(enabled=True)
        tr = tracer.new_trace("op", start=100.0)
        tr.root.finish(100.25)
        tr.root.finish(999.0)   # second finish is a no-op
        assert tr.duration_ms == pytest.approx(250.0)
        tracer.finish(tr)
        tracer.finish(tr)       # idempotent: buffered once
        assert tracer.buffer.stats()["added"] == 1

    def test_error_and_links(self):
        tracer = Tracer(enabled=True)
        tr = tracer.new_trace("request")
        span = tracer.start_span("device", tr)
        span.link("t1", "s1").link("t2", "s2")
        span.error("boom").finish()
        assert span.status == "error"
        assert span.attrs["error"] == "boom"
        assert span.links == [("t1", "s1"), ("t2", "s2")]

    def test_current_span_context(self):
        tracer = Tracer(enabled=True)
        tr = tracer.new_trace("op")
        assert current_span() is None
        with use_span(tr.root):
            assert current_span() is tr.root
        assert current_span() is None

    def test_emit_retroactive_span(self):
        tracer = Tracer(enabled=True)
        tr = tracer.new_trace("gbdt.train")
        span = tracer.emit("bin", 10.0, 10.5, trace=tr,
                           attrs={"rows": 7})
        assert span.duration_ms == pytest.approx(500.0)
        assert span.attrs["rows"] == 7
        # standalone emit buffers a single-span trace
        tracer.emit("automl.featurize_fit", time.perf_counter() - 0.01)
        assert tracer.buffer.stats()["added"] == 1

    def test_disabled_tracer_emits_nothing(self):
        tracer = Tracer(enabled=False)
        assert tracer.emit("x", 0.0) is None
        with tracer.trace_block("y") as tr:
            assert tr is None
        assert tracer.buffer.stats()["added"] == 0


class TestTraceBuffer:
    @staticmethod
    def _mk(tracer, dur_ms, error=False):
        tr = tracer.new_trace("t", start=0.0)
        if error:
            tr.root.error()
        tracer.finish(tr, end=dur_ms / 1e3)
        return tr

    def test_capacity_bound(self):
        tracer = Tracer(enabled=True,
                        buffer=TraceBuffer(capacity=32))
        for _ in range(300):
            self._mk(tracer, 1.0)
        stats = tracer.buffer.stats()
        assert stats["added"] == 300
        assert stats["buffered"] <= 32 + 8   # main ring + protected cap

    def test_error_traces_survive_eviction(self):
        tracer = Tracer(enabled=True, buffer=TraceBuffer(capacity=16))
        err = self._mk(tracer, 1.0, error=True)
        for _ in range(200):
            self._mk(tracer, 1.0)
        kept = tracer.buffer.traces()
        assert any(t is err for t in kept), \
            "error trace evicted by bulk traffic"
        assert tracer.buffer.stats()["errors_kept"] == 1

    def test_slow_tail_kept(self):
        tracer = Tracer(enabled=True, buffer=TraceBuffer(
            capacity=16, slow_percentile=90.0))
        for _ in range(64):         # establish the 1 ms baseline
            self._mk(tracer, 1.0)
        slow = self._mk(tracer, 500.0)
        for _ in range(100):        # bulk traffic evicts the main ring
            self._mk(tracer, 1.0)
        assert any(t is slow for t in tracer.buffer.traces()), \
            "slow-percentile trace evicted"
        assert tracer.buffer.stats()["slow_kept"] >= 1

    def test_limit_and_clear(self):
        tracer = Tracer(enabled=True, buffer=TraceBuffer(capacity=64))
        for _ in range(10):
            self._mk(tracer, 1.0)
        assert len(tracer.buffer.traces(limit=3)) == 3
        assert tracer.buffer.traces(limit=0) == []
        tracer.buffer.clear()
        assert tracer.buffer.traces() == []


class TestChromeExport:
    def test_export_structure_and_json_round_trip(self):
        tracer = Tracer(enabled=True)
        tr = tracer.new_trace("request")
        tracer.start_span("device", tr).set("rows", 4).finish()
        tracer.finish(tr)
        payload = to_chrome_trace(tracer.buffer.traces())
        text = json.dumps(payload)       # must be JSON-serializable
        loaded = json.loads(text)
        events = loaded["traceEvents"]
        assert loaded["displayTimeUnit"] == "ms"
        assert len(events) == 2
        for ev in events:
            # the Chrome trace-event contract for complete events
            assert ev["ph"] == "X"
            assert isinstance(ev["name"], str) and ev["name"]
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            assert isinstance(ev["pid"], int)
            assert isinstance(ev["tid"], int)
            assert "trace_id" in ev["args"]

    def test_shared_batch_span_deduped(self):
        tracer = Tracer(enabled=True)
        tr1 = tracer.new_trace("request")
        tr2 = tracer.new_trace("request")
        shared = tracer.start_span("device", tr1)
        shared.link(tr1.trace_id, tr1.root.span_id)
        shared.link(tr2.trace_id, tr2.root.span_id)
        tr2.add(shared)
        shared.finish()
        tracer.finish(tr1)
        tracer.finish(tr2)
        events = to_chrome_trace(tracer.buffer.traces())["traceEvents"]
        assert len([e for e in events if e["name"] == "device"]) == 1
        device = next(e for e in events if e["name"] == "device")
        assert len(device["args"]["links"]) == 2


# ---------------------------------------------------------------------------
# end-to-end serving traces
# ---------------------------------------------------------------------------


def _scoring_pipeline(sleep_s=0.002):
    """A split-pipeline echo scorer (no jax): decode parses JSON on the
    batcher thread, execute 'scores' on the worker — shaped like
    json_scoring_pipeline so the queue_wait/decode/device/respond span
    chain is exercised."""
    def decode(table):
        return [json.loads(r["entity"].decode())["x"]
                for r in table["request"]]

    def execute(table, xs):
        time.sleep(sleep_s)
        return table.with_column("reply", [{"y": v * 2} for v in xs])

    lam = Lambda.apply(lambda t: execute(t, decode(t)))
    lam.prepare_batch = decode
    lam.execute_prepared = execute
    lam.jit_cache_miss_count = lambda: 0
    lam.bucket_for = lambda rows: 8
    return lam


def _post(addr, payload, headers=None, timeout=10):
    req = urllib.request.Request(
        addr, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


def _union_coverage(trace):
    """Fraction of the root interval covered by the union of child
    span intervals (shared batch spans count once)."""
    root = trace.root
    ivs = sorted(
        (max(s.start, root.start), min(s.end, root.end))
        for s in trace.spans()
        if s is not root and s.end is not None)
    covered, cur_a, cur_b = 0.0, None, None
    for a, b in ivs:
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                covered += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        covered += cur_b - cur_a
    dur = root.end - root.start
    return covered / dur if dur > 0 else 0.0


@pytest.fixture()
def traced_engine():
    tracer = Tracer(enabled=True)
    engine = serve_model(_scoring_pipeline(), port=19460, batch_size=8,
                         max_wait_ms=20.0, tracer=tracer, version="v3")
    yield engine, tracer
    engine.stop()


class TestServingTracing:
    def _spray(self, engine, n=16):
        threads = [threading.Thread(
            target=_post, args=(engine.source.address, {"x": i}))
            for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        time.sleep(0.2)

    def test_trace_id_propagation(self, traced_engine):
        engine, tracer = traced_engine
        status, body, headers = _post(
            engine.source.address, {"x": 21},
            headers={"X-Trace-Id": "trace-prop-1"})
        assert status == 200 and body == {"y": 42}
        assert headers.get("X-Trace-Id") == "trace-prop-1"
        time.sleep(0.2)
        ids = [t.trace_id for t in tracer.buffer.traces()]
        assert "trace-prop-1" in ids
        # server-issued ids also flow back to the client
        _, _, headers2 = _post(engine.source.address, {"x": 1})
        assert headers2.get("X-Trace-Id")

    def test_span_chain_covers_request_wall(self, traced_engine):
        """The acceptance bar: spans (queue_wait -> decode -> device ->
        respond) account for >= 90% of the request's measured wall."""
        engine, tracer = traced_engine
        self._spray(engine, 16)
        traces = [t for t in tracer.buffer.traces()
                  if t.root.name == "request" and not t.is_error]
        assert traces, "no completed request traces"
        names_required = {"queue_wait", "decode", "device", "respond"}
        checked = 0
        for tr in traces:
            names = {s.name for s in tr.spans()}
            assert names_required <= names, \
                f"missing spans: {names_required - names}"
            cov = _union_coverage(tr)
            assert cov >= 0.90, (
                f"span chain covers only {cov:.1%} of the request wall "
                f"({[(s.name, round(s.duration_ms, 3)) for s in tr.spans()]})")
            checked += 1
        assert checked >= 16

    def test_batch_join_span_shared_with_version(self, traced_engine):
        engine, tracer = traced_engine
        self._spray(engine, 16)
        traces = [t for t in tracer.buffer.traces()
                  if t.root.name == "request"]
        by_device = {}
        for tr in traces:
            for s in tr.spans():
                if s.name == "device":
                    by_device.setdefault(s.span_id, []).append(tr)
                    assert s.attrs["model_version"] == "v3"
                    assert s.attrs["bucket"] == 8
                    assert "jit_cache_miss" in s.attrs
        # with 16 concurrent requests into batch_size=8 / 20 ms windows,
        # at least one micro-batch joined >1 request
        multi = {sid: trs for sid, trs in by_device.items()
                 if len(trs) > 1}
        assert multi, "no multi-request micro-batch formed"
        for sid, trs in multi.items():
            span = next(s for s in trs[0].spans() if s.span_id == sid)
            assert span.attrs["rows"] == len(trs), \
                "device span rows != joined traces"
            assert len(span.links) == len(trs), \
                "device span must link every joined request root"
            root_ids = {t.root.span_id for t in trs}
            assert {s for _, s in span.links} == root_ids

    def test_error_trace_kept_and_marked(self, traced_engine):
        engine, tracer = traced_engine
        bad = Lambda.apply(lambda t: (_ for _ in ()).throw(
            RuntimeError("kaboom")))
        engine.pipeline = bad
        with pytest.raises(urllib.error.HTTPError):
            _post(engine.source.address, {"x": 1})
        time.sleep(0.2)
        errs = [t for t in tracer.buffer.traces() if t.is_error]
        assert errs, "500 request produced no error trace"
        assert errs[-1].root.attrs.get("http_status", 500) >= 500

    def test_debug_traces_endpoint(self, traced_engine):
        engine, tracer = traced_engine
        self._spray(engine, 8)
        raw = urllib.request.urlopen(
            engine.source.address + "/debug/traces", timeout=5).read()
        payload = json.loads(raw)
        assert payload["displayTimeUnit"] == "ms"
        assert len(payload["traceEvents"]) > 0
        limited = json.loads(urllib.request.urlopen(
            engine.source.address + "/debug/traces?limit=2",
            timeout=5).read())
        # count ROOT events: shared batch spans carry their primary
        # trace's id, so counting distinct arg ids would over-count
        roots = [e for e in limited["traceEvents"]
                 if e["name"] == "request"]
        assert 0 < len(roots) <= 2

    def test_tracing_disabled_is_silent(self):
        engine = serve_model(_scoring_pipeline(), port=19480,
                             batch_size=8, tracing=False)
        try:
            status, body, headers = _post(engine.source.address, {"x": 2})
            assert status == 200 and body == {"y": 4}
            assert "X-Trace-Id" not in headers
            assert engine.traces() == []
            payload = json.loads(urllib.request.urlopen(
                engine.source.address + "/debug/traces",
                timeout=5).read())
            assert payload["traceEvents"] == []
        finally:
            engine.stop()


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


class TestMetricsExposition:
    def test_engine_metrics_endpoint_grammar(self, traced_engine):
        engine, _tracer = traced_engine
        # drift monitor riding the pipeline -> drift gauges on /metrics
        monitor = DriftMonitor.from_matrix(
            np.random.default_rng(0).normal(size=(64, 4)))
        monitor.observe(np.random.default_rng(1).normal(size=(32, 4)))
        engine._active.pipeline.drift_monitor = monitor
        for _ in range(4):
            _post(engine.source.address, {"x": 5})
        # make sure the process-wide phase families have content
        from mmlspark_tpu.core import metrics as MC
        MC.gbdt_train_histograms()["bin"].observe(3.0)
        MC.automl_histograms()["tune_trials"].observe(8.0)
        raw = urllib.request.urlopen(
            engine.source.address + "/metrics", timeout=5)
        assert raw.headers.get("Content-Type", "").startswith(
            "text/plain")
        text = raw.read().decode()
        types, samples = validate_prom_text(text)
        names = {n for n, _l, _v in samples}
        for required in (
                "serving_requests_answered_total",
                "serving_batches_processed_total",
                "serving_swaps_completed_total",
                "serving_swaps_rolled_back_total",
                "serving_model_info",
                "serving_queue_wait_ms_bucket",
                "serving_pipeline_ms_bucket",
                "serving_jit_cache_misses_total",
                "serving_drift_max_abs_mean_delta_sigma",
                "gbdt_train_phase_ms_bucket",
                "automl_phase_ms_bucket",
                "trace_buffer_traces",
        ):
            assert required in names, f"/metrics missing {required}"
        assert types["serving_queue_wait_ms"] == "histogram"
        info = next(l for n, l, _v in samples
                    if n == "serving_model_info")
        assert info["version"] == "v3"
        assert info["swap_state"] == "idle"
        # the trace_* series must report the ENGINE's tracer buffer
        # (this fixture uses an isolated Tracer, not the global one)
        added = next(v for n, _l, v in samples
                     if n == "trace_traces_added_total")
        assert added > 0

    def test_zoo_metrics_grammar_and_cardinality_cap_at_256(self):
        """The multi-model plane's families pass the grammar validator,
        and the per-model label space stays HARD-CAPPED with 256
        registered models: at most ``label_cardinality_cap`` named
        latency series (+ ``_other``), at most that many
        ``serving_model_info{model=...}`` rows, while
        ``serving_zoo_*`` state gauges still count all 256."""
        from mmlspark_tpu.serving import ModelZoo, ServingEngine
        from mmlspark_tpu.serving.server import HTTPSource
        cap = 64
        zoo = ModelZoo(max_resident=16, memory_probe=None,
                       label_cardinality_cap=cap)
        for i in range(256):
            zoo.register_factory(
                f"m{i:03d}", f"v{i % 8}",
                (lambda i=i: _scoring_pipeline()))
        # a few models actually resident + latency observed for ALL
        # 256 names (the worst-case label pressure)
        for i in range(4):
            zoo.get(f"m{i:03d}")
        for i in range(256):
            zoo.observe_latency(f"m{i:03d}", 1.0 + i % 7)
        source = HTTPSource(port=19690)
        engine = ServingEngine(source, zoo=zoo, tracing=False).start()
        try:
            text = urllib.request.urlopen(
                engine.source.address + "/metrics",
                timeout=5).read().decode()
        finally:
            engine.stop()
            zoo.close()
        types, samples = validate_prom_text(text)
        assert types["serving_model_latency_ms"] == "histogram"
        lat_models = {l["model"] for n, l, _v in samples
                      if n == "serving_model_latency_ms_bucket"}
        assert "_other" in lat_models
        assert len(lat_models) <= cap + 1, len(lat_models)
        info_models = {l["model"] for n, l, _v in samples
                       if n == "serving_model_info" and "model" in l}
        assert 0 < len(info_models) <= cap
        # resident rows always have labeled series (they're the ones
        # an operator is debugging)
        for i in range(4):
            assert f"m{i:03d}" in info_models
        # the full population is still countable — by state, uncapped
        by_state = {l["state"]: v for n, l, v in samples
                    if n == "serving_zoo_models"}
        assert sum(by_state.values()) == 256
        registered = next(v for n, _l, v in samples
                          if n == "serving_zoo_registered_models")
        assert registered == 256

    def test_fleet_metrics_text_grammar(self):
        from mmlspark_tpu.serving.fleet import ServingFleet
        tracer = Tracer(enabled=True)
        fleet = ServingFleet(_scoring_pipeline(), n_engines=2,
                             base_port=19500, batch_size=8,
                             tracer=tracer)
        try:
            for i in range(6):
                fleet.post({"x": i})
            text = fleet.metrics_text()
        finally:
            fleet.stop_all()
        types, samples = validate_prom_text(text)
        names = {n for n, _l, _v in samples}
        assert "serving_fleet_transport_errors_total" in names
        engines = {l.get("engine") for n, l, _v in samples
                   if n == "serving_requests_answered_total"}
        assert engines == {"0", "1"}
        # fleet traces: the shared tracer saw both engines' traffic
        chrome = fleet.traces()
        assert len(chrome["traceEvents"]) > 0

    def test_label_escaping(self):
        from mmlspark_tpu.core.prometheus import PromRenderer
        r = PromRenderer()
        r.info("weird_info", "escaping check",
               {"v": 'a"b\\c\nd', "ok": "plain"})
        types, samples = validate_prom_text(r.render())
        assert samples[0][1]["v"] == 'a"b\\c\nd'

    def test_histogram_rendering_exact(self):
        from mmlspark_tpu.core.prometheus import PromRenderer
        hist = LatencyHistogram()
        for v in (0.04, 0.6, 3.0, 3.0, 1e9):
            hist.observe(v)
        r = PromRenderer()
        r.histogram("lat_ms", "check", hist)
        types, samples = validate_prom_text(r.render())
        buckets = [(l["le"], v) for n, l, v in samples
                   if n == "lat_ms_bucket"]
        assert buckets[0] == ("0.05", 1)
        assert buckets[-1] == ("+Inf", 5)
        total = next(v for n, _l, v in samples if n == "lat_ms_count")
        assert total == 5
        s = next(v for n, _l, v in samples if n == "lat_ms_sum")
        assert s == pytest.approx(0.04 + 0.6 + 6.0 + 1e9)


# ---------------------------------------------------------------------------
# structured logging
# ---------------------------------------------------------------------------


class TestStructuredLogging:
    def test_json_formatter_plain_record(self):
        from mmlspark_tpu.core.logging_utils import JsonFormatter
        rec = logging.LogRecord("mmlspark_tpu.serving", logging.WARNING,
                                __file__, 1, "shed %d rows", (7,), None)
        out = json.loads(JsonFormatter().format(rec))
        assert out["msg"] == "shed 7 rows"
        assert out["level"] == "WARNING"
        assert out["logger"] == "mmlspark_tpu.serving"
        assert "\n" not in JsonFormatter().format(rec)
        assert "trace_id" not in out

    def test_json_formatter_carries_trace_and_version(self):
        from mmlspark_tpu.core.logging_utils import JsonFormatter
        tracer = Tracer(enabled=True)
        tr = tracer.new_trace("request", trace_id="log-corr-1")
        span = tracer.start_span("device", tr)
        span.set("model_version", "v12")
        rec = logging.LogRecord("mmlspark_tpu.serving", logging.INFO,
                                __file__, 1, "batch ok", (), None)
        with use_span(span):
            out = json.loads(JsonFormatter().format(rec))
        assert out["trace_id"] == "log-corr-1"
        assert out["span_id"] == span.span_id
        assert out["model_version"] == "v12"

    def test_log_format_config_switch(self):
        from mmlspark_tpu.core import config
        from mmlspark_tpu.core.logging_utils import (
            JsonFormatter, configure,
        )
        root = logging.getLogger("mmlspark_tpu")

        def owned():
            # configure() only restyles handlers it created — an
            # embedder's handlers keep their own formatters
            return [h for h in root.handlers
                    if getattr(h, "_mmlspark_tpu_owned", False)]

        foreign = logging.StreamHandler()
        foreign_fmt = logging.Formatter("APP %(message)s")
        foreign.setFormatter(foreign_fmt)
        root.addHandler(foreign)
        config.set_config("log_format", "json")
        try:
            configure(force=True)
            assert owned(), "configure() created no owned handler"
            assert all(isinstance(h.formatter, JsonFormatter)
                       for h in owned())
            assert foreign.formatter is foreign_fmt, \
                "embedder's formatter was clobbered"
        finally:
            root.removeHandler(foreign)
            config.set_config("log_format", "text")
            configure(force=True)
        assert not any(isinstance(h.formatter, JsonFormatter)
                       for h in owned())

    def test_json_formatter_exception_one_line(self):
        import sys
        from mmlspark_tpu.core.logging_utils import JsonFormatter
        try:
            raise ValueError("inner")
        except ValueError:
            rec = logging.LogRecord("mmlspark_tpu", logging.ERROR,
                                    __file__, 1, "failed", (),
                                    sys.exc_info())
        line = JsonFormatter().format(rec)
        assert "\n" not in line
        assert "inner" in json.loads(line)["exc"]


# ---------------------------------------------------------------------------
# thread-safety hammer (satellite: core/metrics audit)
# ---------------------------------------------------------------------------


class TestMetricsThreadSafety:
    N_THREADS, N_OBS = 8, 4000

    def _hammer(self, fn):
        threads = [threading.Thread(target=fn, args=(t,))
                   for t in range(self.N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def test_histogram_concurrent_observe_no_lost_updates(self):
        hist = LatencyHistogram()

        def work(seed):
            for i in range(self.N_OBS):
                hist.observe(float((i + seed) % 97))

        self._hammer(work)
        snap = hist.snapshot()
        total = self.N_THREADS * self.N_OBS
        assert snap["count"] == total
        assert sum(snap["counts"]) == total
        # all values are small integers -> the f64 sum is exact
        expected = sum(float((i + s) % 97) for s in range(self.N_THREADS)
                       for i in range(self.N_OBS))
        assert snap["sum"] == expected

    def test_snapshot_internally_consistent_under_load(self):
        hist = LatencyHistogram()
        stop = threading.Event()
        bad = []

        def reader():
            while not stop.is_set():
                snap = hist.snapshot()
                if sum(snap["counts"]) != snap["count"]:
                    bad.append(snap)
                summary = hist.summary()
                if summary.get("count") and summary["p50"] > \
                        summary["max"] + 1e-9:
                    bad.append(summary)

        rt = threading.Thread(target=reader)
        rt.start()

        def work(seed):
            for i in range(self.N_OBS):
                hist.observe(float(i % 53))

        self._hammer(work)
        stop.set()
        rt.join()
        assert not bad, f"inconsistent snapshots: {bad[:3]}"

    def test_concurrent_merge_and_reset(self):
        src = [LatencyHistogram() for _ in range(self.N_THREADS)]
        agg = LatencyHistogram()

        def work(t):
            for i in range(self.N_OBS):
                src[t].observe(1.0)

        self._hammer(work)
        threads = [threading.Thread(target=agg.merge, args=(h,))
                   for h in src]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert agg.snapshot()["count"] == self.N_THREADS * self.N_OBS
        agg.reset()
        assert agg.snapshot()["count"] == 0

    def test_drift_monitor_concurrent_observe(self):
        monitor = DriftMonitor(np.zeros(4), np.ones(4))
        rows_per = 50

        def work(seed):
            rng = np.random.default_rng(seed)
            for _ in range(rows_per):
                monitor.observe(rng.normal(size=(4, 4)))

        self._hammer(work)
        snap = monitor.snapshot()
        assert snap["rows"] == self.N_THREADS * rows_per * 4


# ---------------------------------------------------------------------------
# satellite fix: consistent metrics()/healthz snapshot under swap()
# ---------------------------------------------------------------------------


class TestSwapMetricsConsistency:
    def test_snapshot_never_tears_under_swap_loop(self):
        """Hammer metrics() while swaps cut over: in every snapshot the
        (model_version, swap_state, swaps_completed) triple must be
        mutually consistent — version vK with state idle implies
        exactly K completed swaps; draining implies K-1."""
        from mmlspark_tpu.serving.lifecycle import CanaryPolicy

        def echo(table):
            return table.with_column(
                "reply", [b"ok" for _ in table["id"]])

        engine = serve_model(Lambda.apply(echo), port=19520,
                             batch_size=4, tracing=False, version="v0")
        violations = []
        stop = threading.Event()

        def poll():
            while not stop.is_set():
                m = engine.metrics()
                state = m["swap_state"]
                k = int(m["model_version"][1:])
                done = m["swaps_completed"]
                if state in ("idle", "warming", "canary") and done != k:
                    violations.append((state, k, done))
                elif state == "draining" and done != k - 1:
                    violations.append((state, k, done))

        pollers = [threading.Thread(target=poll) for _ in range(3)]
        for t in pollers:
            t.start()
        try:
            policy = CanaryPolicy(fraction=0.0, drain_timeout_s=1.0)
            for i in range(1, 120):
                res = engine.swap(Lambda.apply(echo), f"v{i}",
                                  policy=policy)
                assert res.completed, res
        finally:
            stop.set()
            for t in pollers:
                t.join()
            engine.stop()
        assert not violations, \
            f"{len(violations)} torn snapshots, e.g. {violations[:5]}"


# ---------------------------------------------------------------------------
# training-side traces
# ---------------------------------------------------------------------------


class TestTrainingTraces:
    def test_gbdt_train_emits_phase_spans(self):
        from mmlspark_tpu.core import trace as trace_mod
        from mmlspark_tpu.gbdt.booster import train
        tracer = Tracer(enabled=True)
        trace_mod.set_tracer(tracer)
        try:
            rng = np.random.default_rng(0)
            X = rng.normal(size=(400, 5)).astype(np.float32)
            y = (X[:, 0] > 0).astype(np.float64)
            train({"objective": "binary", "num_iterations": 3,
                   "num_leaves": 7, "max_bin": 15}, X, y)
        finally:
            trace_mod.set_tracer(None)
        traces = [t for t in tracer.buffer.traces()
                  if t.root.name == "gbdt.train"]
        assert traces, "train() produced no trace"
        names = {s.name for s in traces[-1].spans()}
        assert "bin" in names and "fetch" in names
        assert "first_iter" in names or "boost" in names
        assert "bin_path" in traces[-1].root.attrs

    def test_automl_featurize_and_tune_emit_spans(self):
        from mmlspark_tpu.automl.featurize import Featurize
        from mmlspark_tpu.core import trace as trace_mod
        tracer = Tracer(enabled=True)
        trace_mod.set_tracer(tracer)
        try:
            rng = np.random.default_rng(0)
            table = DataTable({
                "a": rng.normal(size=200),
                "color": [f"c{i % 3}" for i in range(200)]})
            model = Featurize(featureColumns=["a", "color"]).fit(table)
            model.transform(table)
        finally:
            trace_mod.set_tracer(None)
        names = [t.root.name for t in tracer.buffer.traces()]
        assert "automl.featurize_fit" in names
        assert "automl.featurize_transform" in names

    def test_learner_fit_emits_step_spans(self):
        from mmlspark_tpu.core import trace as trace_mod
        from mmlspark_tpu.models.learner import TPULearner
        tracer = Tracer(enabled=True)
        trace_mod.set_tracer(tracer)
        try:
            rng = np.random.default_rng(0)
            x = rng.normal(size=(64, 8)).astype(np.float32)
            y = rng.integers(0, 2, 64).astype(np.int64)
            learner = TPULearner(
                networkSpec={"type": "mlp", "features": [8],
                             "num_classes": 2},
                epochs=1, batchSize=32, logEvery=1000,
                computeDtype="float32", memoryStatsEvery=1)
            learner.fit(DataTable({"features": x, "label": y}))
        finally:
            trace_mod.set_tracer(None)
        fits = [t for t in tracer.buffer.traces()
                if t.root.name == "learner.fit"]
        assert fits, "fit() produced no trace"
        steps = [s for s in fits[-1].spans() if s.name == "learner.step"]
        assert len(steps) == 2    # 64 rows / batch 32
        assert fits[-1].root.attrs["feed"] == "host"
        # CPU backends report no memory stats; the sampler must be a
        # silent no-op there (samples appear on real accelerators)
        assert isinstance(learner.memory_samples, list)
