"""The stage clock (core.trace.phase / record / STAGES): a served
request's stages tile its time in the engine, a span and the histogram
of one stage measure one interval, a phase costs next to nothing when
nobody listens, and no hot path times a stage the tuple does not name.
What the engine says of its own occupancy rides the same clock: the
worker's wait for a batch is one phase, a batch carries how long its
oldest request had waited, the scorer's phases carry the bucket beside
the rows.
"""

import json
import os
import re
import threading
import time
import urllib.request

import pytest

from mmlspark_tpu.core.metrics import LatencyHistogram
from mmlspark_tpu.core.trace import (
    ARRIVAL_WAITS, HOST_PHASES, REQUEST_STAGES, STAGES, Tracer, phase,
    record,
)
from mmlspark_tpu.serving.server import _BatchCtx, serve_model
from mmlspark_tpu.stages.basic import Lambda

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_PY = os.path.join(ROOT, "mmlspark_tpu", "core", "trace.py")


def _slow_pipeline(sleep_s):
    """A split echo scorer whose device stage is a sleep."""
    def decode(table):
        return [json.loads(r["entity"].decode())["x"]
                for r in table["request"]]

    def execute(table, xs):
        time.sleep(sleep_s)
        return table.with_column("reply", [{"y": v * 2} for v in xs])

    lam = Lambda.apply(lambda t: execute(t, decode(t)))
    lam.prepare_batch = decode
    lam.execute_prepared = execute
    return lam


def _post(addr, x, delay_s=0.0):
    time.sleep(delay_s)
    req = urllib.request.Request(
        addr, data=json.dumps({"x": x}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=20) as r:
        assert r.status == 200 and json.loads(r.read()) == {"y": 2 * x}


def _serve(n, batch_size, sleep_s, stagger_s):
    """``n`` requests, one every ``stagger_s``, through an engine with
    one worker and two in-flight tokens; its traces and histograms."""
    tracer = Tracer(enabled=True, capacity=1024)
    engine = serve_model(_slow_pipeline(sleep_s), port=0,
                         batch_size=batch_size, max_wait_ms=2.0,
                         workers=1, pipeline_depth=2, tracer=tracer)
    try:
        threads = [threading.Thread(target=_post, args=(
            engine.source.address, i, i * stagger_s)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        deadline = time.time() + 5
        while len(tracer.buffer.traces()) < n and time.time() < deadline:
            time.sleep(0.01)      # the handlers buffer after they reply
    finally:
        engine.stop()
    traces = [t for t in tracer.buffer.traces() if t.root.name == "request"]
    assert len(traces) == n
    return traces, engine.hists


def _stages(trace):
    """The request's stage spans in the order of REQUEST_STAGES."""
    by_name = {}
    for s in trace.spans():
        if s is not trace.root:
            assert s.name not in by_name, f"two {s.name} spans"
            by_name[s.name] = s
    assert tuple(sorted(by_name)) == tuple(sorted(REQUEST_STAGES))
    return [by_name[name] for name in REQUEST_STAGES]


@pytest.fixture(scope="module")
def saturated():
    """Ten batches of four behind a 30 ms stage: a third batch is sealed
    while one runs and one lies in the dispatch queue."""
    return _serve(40, 4, 0.03, 0.0)


@pytest.fixture(scope="module")
def topped_up():
    """A request every 7 ms behind a 40 ms stage: a lone sealed request
    waits for a token and takes in those that arrive meanwhile."""
    return _serve(14, 8, 0.04, 0.007)


@pytest.mark.parametrize("load", ["saturated", "topped_up"])
def test_stages_tile_the_request(load, request):
    traces, _ = request.getfixturevalue(load)
    for tr in traces:
        chain = _stages(tr)
        for before, after in zip(chain, chain[1:]):
            assert before.end == after.start, (before, after)
        wall = chain[-1].end - chain[0].start
        assert wall > 0 and tr.root.end == chain[-1].end
        assert tr.root.start <= chain[0].start
        assert abs(sum(s.end - s.start for s in chain) - wall) < 1e-6
        assert all(s.end >= s.start for s in chain)


def test_token_wait_and_dispatch_wait_are_seen(saturated):
    traces, _ = saturated
    ms = {name: [_stages(tr)[i].duration_ms for tr in traces]
          for i, name in enumerate(REQUEST_STAGES)}
    # one stage of 30 ms at a time and two tokens: most batches wait for
    # the worker about one stage and for their token about as long
    assert sorted(ms["dispatch_wait"])[len(traces) // 2] > 10
    assert sorted(ms["token_wait"])[len(traces) // 2] > 10
    assert min(ms["device"]) >= 30


def test_a_topped_up_request_collects_for_no_time(topped_up):
    traces, hists = topped_up
    late = [tr for tr in traces
            if _stages(tr)[1].end == _stages(tr)[1].start]
    assert late, "no request was taken in by a top-up"
    for tr in late:
        # it waits for the token from its own dequeue on
        assert _stages(tr)[2].duration_ms > 0
    assert hists["batch_rows"].snapshot()["max"] > 1


@pytest.mark.parametrize("load", ["saturated", "topped_up"])
def test_a_span_and_the_histogram_of_its_stage_agree(load, request):
    traces, hists = request.getfixturevalue(load)
    n = len(traces)

    def spans_ms(name, shared):
        spans = [_stages(tr)[REQUEST_STAGES.index(name)] for tr in traces]
        if shared:          # a batch-join span counts once a batch
            spans = list({s.span_id: s for s in spans}.values())
        return len(spans), sum(s.end - s.start for s in spans) * 1e3

    batches = hists["batch_rows"].snapshot()["count"]
    for name, hist, shared in [
            ("queue_wait", "queue_wait_ms", False),
            ("collect_wait", "collect_wait_ms", False),
            ("token_wait", "token_wait_ms", False),
            ("decode", "decode_ms", True),
            ("dispatch_wait", "dispatch_wait_ms", False),
            ("device", "pipeline_ms", True)]:
        count, total = spans_ms(name, shared)
        snap = hists[hist].snapshot()
        assert snap["count"] == count == (batches if shared else n), name
        assert snap["sum"] == pytest.approx(total, rel=1e-9, abs=1e-6), name
    # the batch's respond_ms ends after its last request's respond span
    _, respond = spans_ms("respond", False)
    snap = hists["respond_ms"].snapshot()
    assert snap["count"] == batches
    assert respond / n <= snap["sum"] / batches + 1e-6


def test_phase_emits_span_histogram_and_stamps():
    tracer = Tracer(enabled=True)
    tr = tracer.new_trace("fit")
    hist = LatencyHistogram()
    with phase("learner.chunk", trace=tr, hist=hist, step=3) as ph:
        time.sleep(0.002)
    assert ph.span.name == "learner.chunk" and ph.span.attrs == {"step": 3}
    assert (ph.span.start, ph.span.end) == (ph.start, ph.end)
    assert hist.snapshot()["sum"] == pytest.approx(ph.ms) and ph.ms >= 2
    # a span under another name, from the stamp the stage before ended at
    with phase("serve.execute", span="device", trace=tr, start=ph.end,
               batch=7) as ex:
        with phase("tpu_model.readback") as inner:
            pass
    assert ex.span.name == "device" and ex.start == ph.end
    assert inner.attrs == {"batch": 7} and inner.span is None
    # a batch-join span: one span in every trace, linking each root
    other = tracer.new_trace("request")
    with phase("serve.decode", span="decode", trace=[tr, other]) as dec:
        pass
    assert dec.span in tr.spans() and dec.span in other.spans()
    assert {s for _, s in dec.span.links} == {tr.root.span_id,
                                              other.root.span_id}


def test_phase_that_raises_marks_its_span_and_observes_nothing():
    tracer = Tracer(enabled=True)
    tr = tracer.new_trace("request")
    hist = LatencyHistogram()
    with pytest.raises(RuntimeError):
        with phase("serve.execute", span="device", trace=tr,
                   hist=hist) as ph:
            raise RuntimeError("poison")
    assert ph.span.status == "error" and ph.span.end == ph.end
    assert hist.snapshot()["count"] == 0


def test_record_takes_explicit_stamps():
    tracer = Tracer(enabled=True)
    tr = tracer.new_trace("request")
    hist = LatencyHistogram()
    span = record("dispatch_wait", 10.0, 10.25, trace=tr, hist=hist, rows=2)
    assert (span.start, span.end, span.attrs) == (10.0, 10.25, {"rows": 2})
    assert hist.snapshot()["sum"] == pytest.approx(250.0)
    assert record("dispatch_wait", 1.0, 2.0, hist=hist) is None
    assert hist.snapshot()["count"] == 2


def test_phase_with_nobody_listening_costs_next_to_nothing(monkeypatch):
    """No tracer, no histogram, no profiler session: a phase opens no
    span, observes no histogram and its annotation is one the profiler
    does not record. (What that costs in time is a chip-host reading:
    PERF.md section 6.)"""
    import jax

    from mmlspark_tpu.core import trace as trace_mod
    opened, observed = [], []
    monkeypatch.setattr(trace_mod, "_open_span",
                        lambda *a, **kw: opened.append(a))
    monkeypatch.setattr(LatencyHistogram, "observe",
                        lambda self, ms: observed.append(ms))
    for i in range(100):
        with phase("serve.execute", batch=i, rows=8) as ph:
            with phase("tpu_model.pad") as inner:
                pass
        assert ph.span is None and inner.span is None
        assert inner.attrs == {"batch": i} and ph.end >= inner.end
    assert opened == [] and observed == []
    assert not jax.profiler.TraceAnnotation.is_enabled()


def test_trace_module_needs_no_jax():
    """core/trace.py imports without jax, and a phase in a process that
    has not imported jax does not import it."""
    import subprocess
    import sys
    code = (
        "import sys, importlib.util as u\n"
        "sys.modules['jax'] = None      # importing jax would raise\n"
        f"spec = u.spec_from_file_location('stage_clock', {TRACE_PY!r})\n"
        "m = u.module_from_spec(spec); sys.modules['stage_clock'] = m\n"
        "spec.loader.exec_module(m)\n"
        "with m.phase('serve.execute', rows=1) as ph: pass\n"
        "assert ph.end >= ph.start and sys.modules['jax'] is None\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_the_stage_tuple_is_what_the_hot_paths_use():
    """Grep-style guard: a stage that the tuple does not name cannot be
    timed, and a name that nothing times cannot stay in the tuple."""
    used = set()
    for rel in ("serving/server.py", "models/tpu_model.py",
                "models/learner.py"):
        src = open(os.path.join(ROOT, "mmlspark_tpu", rel)).read()
        used |= set(re.findall(
            r'\b(?:phase|record|_stage)\(\s*"([^"]+)"', src))
        used |= set(re.findall(r'\bspan="([^"]+)"', src))
    assert used == set(STAGES)
    assert STAGES == REQUEST_STAGES + HOST_PHASES + ARRIVAL_WAITS
    # a thread that waits for arrivals covers every gap and explains
    # none: the benchmark charges gaps to HOST_PHASES alone
    assert "serve.idle" in HOST_PHASES and ARRIVAL_WAITS == ("serve.collect",)
    assert len(set(STAGES)) == len(STAGES)
    # ... and the documents enumerate it
    for doc in ("docs/observability.md", "PERF.md"):
        text = open(os.path.join(ROOT, doc)).read()
        missing = [s for s in STAGES if f"`{s}`" not in text]
        assert not missing, f"{doc} does not name {missing}"


# ------------------------------------------------ what the engine says it held

class _Recorded:
    """Stands where ``TraceAnnotation`` does and keeps what a profiler
    session would: (name, attrs, thread, start, end) of every phase."""

    def __init__(self):
        self.events = []

    def __call__(self, name, attrs):
        return _RecordedPhase(self.events, name, dict(attrs))

    def named(self, name):
        return [e for e in self.events if e[0] == name]


class _RecordedPhase:
    def __init__(self, events, name, attrs):
        self.events, self.name, self.attrs = events, name, attrs

    def __enter__(self):
        self.start = time.perf_counter()

    def __exit__(self, *exc):
        self.events.append((self.name, self.attrs, threading.get_ident(),
                            self.start, time.perf_counter()))


@pytest.fixture()
def recorded(monkeypatch):
    from mmlspark_tpu.core import trace as trace_mod
    rec = _Recorded()
    monkeypatch.setattr(trace_mod, "_annotation", rec)
    return rec


def test_the_worker_lies_in_serve_idle_while_its_queue_is_empty(recorded):
    engine = serve_model(_slow_pipeline(0.03), port=0, batch_size=4,
                         max_wait_ms=2.0, workers=1, pipeline_depth=2,
                         tracing=False)
    try:
        time.sleep(0.25)        # five polls of an empty dispatch queue
        _post(engine.source.address, 1)
    finally:
        engine.stop()
    idle, executes = recorded.named("serve.idle"), \
        recorded.named("serve.execute")
    assert len(executes) == 1
    # one phase from asking to having, not one a poll; the second ends
    # with the engine and no batch
    assert len(idle) == 2 and idle[0][4] - idle[0][3] >= 0.2
    # the batch's device stage starts where the wait ended, on the
    # worker's thread, and nothing of the worker's own work is inside
    assert idle[0][2] == executes[0][2]
    assert idle[0][4] <= executes[0][3] + 1e-3 < idle[1][3] + 1e-3
    snap = engine.hists["worker_idle_ms"].snapshot()
    assert snap["count"] == 2
    assert snap["sum"] == pytest.approx(
        sum(e[4] - e[3] for e in idle) * 1e3, abs=2.0)
    # the batcher's wait for arrivals is a phase of its own thread, and
    # each is one drain: the poll or the first arrival, then max_wait_ms
    collects = recorded.named("serve.collect")
    assert collects and {e[2] for e in collects} != {idle[0][2]}
    assert len({e[2] for e in collects}) == 1
    assert max(e[4] - e[3] for e in collects) < 0.05 + 0.002 + 0.05


def _parked(rid, enqueued_at, dequeued_at):
    import types
    return types.SimpleNamespace(id=rid, trace=None,
                                 enqueued_at=enqueued_at,
                                 dequeued_at=dequeued_at)


def test_a_batch_carries_how_long_its_oldest_request_waited():
    parked = [_parked("a", 10.000, 10.200), _parked("b", 10.050, 10.200),
              _parked("c", 10.150, 10.201)]
    tctx = _BatchCtx(7, parked, sealed_at=10.205, granted_at=10.300)
    tctx.dispatched_at = 10.310
    sums = tctx.wait_sums_us(10.400)
    assert sums["oldest_wait_us"] == pytest.approx(400000.0)
    assert sums["queue_wait_us"] == pytest.approx(401000.0)
    assert sums["dispatch_wait_us"] == pytest.approx(3 * 90000.0)
    assert set(sums) == {"queue_wait_us", "collect_wait_us",
                         "token_wait_us", "dispatch_wait_us",
                         "oldest_wait_us"}
    # cut down to the requests that survived decode, it is the oldest
    # of those
    tctx.keep([1, 2], ["b", "c"])
    assert tctx.wait_sums_us(10.400)["oldest_wait_us"] == \
        pytest.approx(350000.0)


def test_serve_execute_carries_the_oldest_wait_into_the_profile(recorded):
    engine = serve_model(_slow_pipeline(0.01), port=0, batch_size=4,
                         max_wait_ms=2.0, workers=1, pipeline_depth=2,
                         tracing=False)
    try:
        _post(engine.source.address, 1)
    finally:
        engine.stop()
    (_, attrs, *_), = recorded.named("serve.execute")
    (_, _, _, decode_start, decode_end), = recorded.named("serve.decode")
    assert attrs["rows"] == 1
    # one request: it is the oldest, and that wait is its four waits and
    # the decode between the third and the fourth
    waits = sum(attrs[w + "_us"] for w in ("queue_wait", "collect_wait",
                                           "token_wait", "dispatch_wait"))
    decode_us = (decode_end - decode_start) * 1e6
    assert waits < attrs["oldest_wait_us"] < waits + decode_us + 2000.0


def test_the_scorer_says_the_bucket_and_counts_its_fill(recorded):
    import jax
    import numpy as np

    from mmlspark_tpu.core.prometheus import PromRenderer, pipeline_families
    from mmlspark_tpu.core.table import DataTable
    from mmlspark_tpu.models.tpu_model import TPUModel
    from mmlspark_tpu.parallel import mesh as mesh_lib
    model = TPUModel.from_fn(lambda w, ins: ins["input"] * w["k"],
                             {"k": np.float32(2.0)}, inputCol="x",
                             outputCol="y", batchSize=16)
    # one device: the CI mesh of 8 would pad every batch to 8 anyway
    model.set_mesh(mesh_lib.make_mesh({"data": 1},
                                      devices=[jax.devices()[0]]))
    assert (model.metrics()["rows_real"], model.metrics()["rows_bucket"]) \
        == (0, 0)
    x = np.arange(37 * 3, dtype=np.float32).reshape(37, 3)
    out = model.transform(DataTable({"x": x}))["y"]     # 16, 16 and 5 rows
    assert np.array_equal(out, 2 * x)
    model.transform(DataTable({"x": x[:3]}))            # the serving path
    model.transform(DataTable({"x": x[:11]}))
    want = [(16, 16), (16, 16), (5, 8), (3, 8), (11, 16)]
    for name in ("tpu_model.pad", "tpu_model.dispatch"):
        assert [(a["rows"], a["bucket"])
                for _, a, *_ in recorded.named(name)] == want, name
    assert all(a["bucket"] == model.bucket_for(a["rows"])
               for _, a, *_ in recorded.named("tpu_model.dispatch"))
    m = model.metrics()
    assert (m["rows_real"], m["rows_bucket"]) == (51, 64)
    r = PromRenderer()
    pipeline_families(r, model)
    text = r.render()
    assert "serving_model_rows_real_total 51" in text
    assert "serving_model_rows_bucket_total 64" in text


def test_the_new_families_pass_the_exposition_audit():
    from tools.check_metrics import DYNAMIC_OK, main
    assert main() == 0
    engine = serve_model(_slow_pipeline(0.0), port=0, batch_size=4,
                         max_wait_ms=2.0, workers=1, tracing=False)
    try:
        _post(engine.source.address, 1)
        text = engine.metrics_text()
        names = {f"serving_{k}" for k in engine.hists}
    finally:
        engine.stop()
    assert "serving_worker_idle_ms" in names
    assert names <= set(DYNAMIC_OK["serving_{}"])
    assert "serving_worker_idle_ms_count" in text


def test_one_primitive():
    """No TraceAnnotation outside core/trace.py, and none of what it
    replaced."""
    for dirpath, _, files in os.walk(os.path.join(ROOT, "mmlspark_tpu")):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            src = open(path).read()
            if path != TRACE_PY:
                assert "TraceAnnotation" not in src, path
            for gone in ("annotate(", "traceAnnotations", "MemorySampler"):
                assert gone not in src, (path, gone)
