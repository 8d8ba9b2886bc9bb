"""The in-flight gate seals a batch when a worker can take it.

With the default ``pipeline_depth=None`` the batch that runs ahead of
the workers gets its token as the running batch is about to end, by the
handle's own stage estimates, so that it does not lie sealed in the
dispatch queue for a whole model step; an explicit depth keeps the
counting semaphore. The stub pipeline sleeps for its device stage. The
assertions are on orderings, counters and generous ratios: the sandbox
stalls threads for tens of milliseconds at a time.
"""

from __future__ import annotations

import json
import statistics
import threading
import time
import urllib.error
import urllib.request

import pytest

from mmlspark_tpu.core.trace import REQUEST_STAGES, Tracer
from mmlspark_tpu.serving import HTTPSource, ModelZoo, ServingEngine
from mmlspark_tpu.serving.fleet import ServingFleet
from mmlspark_tpu.serving.server import _InflightGate, serve_model
from mmlspark_tpu.stages.basic import Lambda

STEP_S = 0.06
NO_COUNTS = {"held": 0, "early_free": 0, "full": 0, "no_estimate": 0,
             "prep_bound": 0}


class Stub:
    """A split pipeline: ``decode`` on the batcher, ``execute`` sleeping
    for the device stage on the worker; ``calls`` holds each execute's
    (start, end). ``step_s`` may be a function of the call's number."""

    def __init__(self, step_s=STEP_S, decode_s=0.0):
        self.step_s = step_s if callable(step_s) else (lambda i: step_s)
        self.decode_s = decode_s
        self.calls = []

    def decode(self, table):
        time.sleep(self.decode_s)
        return [json.loads(r["entity"].decode())["x"]
                for r in table["request"]]

    def execute(self, table, xs):
        start = time.perf_counter()
        time.sleep(self.step_s(len(self.calls)))
        self.calls.append((start, time.perf_counter()))
        return table.with_column("reply", [{"y": v * 2} for v in xs])

    def stage(self):
        lam = Lambda.apply(lambda t: self.execute(t, self.decode(t)))
        lam.prepare_batch = self.decode
        lam.execute_prepared = self.execute
        return lam


def _engine(stub, path="single", depth=None, batch_size=8, tracer=None):
    """An engine with one worker in front of ``stub``: the single-model
    path (``_dispatch_parked``) or, behind a zoo, the ``_pump``."""
    kw = dict(batch_size=batch_size, max_wait_ms=2.0, workers=1,
              pipeline_depth=depth, tracer=tracer,
              tracing=tracer is not None, slo=False,
              flight_recorder=False)
    if path == "single":
        return serve_model(stub.stage(), port=0, **kw)
    zoo = ModelZoo(memory_probe=None)
    zoo.register_pipeline("m", "v1", stub.stage())
    return ServingEngine(HTTPSource(port=0), zoo=zoo, **kw).start()


def _post(engine, x, delay_s=0.0, timeout=20.0):
    time.sleep(delay_s)
    headers = {"Content-Type": "application/json"}
    if engine.zoo is not None:
        headers["X-Model"] = "m"
    req = urllib.request.Request(
        engine.source.address, data=json.dumps({"x": x}).encode(),
        headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.status == 200 and json.loads(r.read()) == {"y": 2 * x}


def _offer(engine, n, stagger_s, first=0):
    """``n`` requests, one every ``stagger_s``; all are answered."""
    threads = [threading.Thread(target=_post, args=(
        engine, first + i, i * stagger_s)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)


def _warm(engine):
    """One request through and done: a process's first takes a second
    of imports, and the handle has its estimates after it."""
    _post(engine, -1)
    assert _until(lambda: engine._inflight.held == 0)


def _stop(engine):
    engine.stop()
    if engine.zoo is not None:
        engine.zoo.close()


def _stage_ms(tracer, n):
    """Each request's milliseconds in each stage, by stage name."""
    deadline = time.time() + 5
    while len(tracer.buffer.traces()) < n and time.time() < deadline:
        time.sleep(0.01)          # the handlers buffer after they reply
    traces = [t for t in tracer.buffer.traces()
              if t.root.name == "request"]
    assert len(traces) == n
    return {name: [s.duration_ms for t in traces for s in t.spans()
                   if s.name == name] for name in REQUEST_STAGES}


def _until(cond, timeout=5.0):
    deadline = time.time() + timeout
    while not cond() and time.time() < deadline:
        time.sleep(0.005)
    return cond()


# -- the gate alone ----------------------------------------------------------


def test_explicit_depth_is_a_counting_semaphore():
    gate = _InflightGate(workers=1, depth=2)
    assert gate.tokens == 2
    assert gate.acquire(full=False) and gate.acquire(full=False)
    gate.plan(1, time.perf_counter() + 60)      # no plan is kept
    t0 = time.perf_counter()
    assert not gate.acquire(full=False, timeout=0.02)
    assert time.perf_counter() - t0 >= 0.02
    gate.release(1)
    assert gate.acquire(full=False)
    assert gate.held == 2
    assert gate.counters.snapshot() == NO_COUNTS


def test_run_ahead_waits_for_the_planned_time():
    gate = _InflightGate(workers=1, depth=None)
    assert gate.tokens == 2
    assert gate.acquire(full=False)             # a free worker: at once
    due = time.perf_counter() + 0.15
    gate.plan(1, due)
    for _ in range(3):                          # the batcher's slices
        assert not gate.acquire(full=False, timeout=0.005)
    assert gate.counters.snapshot() == {**NO_COUNTS, "held": 1}
    assert gate.acquire(full=False, timeout=2.0)
    assert time.perf_counter() >= due
    assert gate.held == 2
    assert not gate.acquire(full=True, timeout=0.005)   # no token left
    assert gate.counters.snapshot() == {**NO_COUNTS, "held": 1}


def test_a_held_batch_that_fills_is_granted_and_counted_once():
    gate = _InflightGate(workers=1, depth=None)
    assert gate.acquire(full=False)
    gate.plan(1, time.perf_counter() + 60)
    assert not gate.acquire(full=False, timeout=0.005)
    assert gate.acquire(full=True)
    assert gate.counters.snapshot() == {**NO_COUNTS, "held": 1}


def test_a_worker_that_frees_early_wakes_the_gate():
    gate = _InflightGate(workers=1, depth=None)
    assert gate.acquire(full=False)
    gate.plan(1, time.perf_counter() + 60)
    assert not gate.acquire(full=False, timeout=0.005)
    threading.Timer(0.05, gate.release, args=(1,)).start()
    t0 = time.perf_counter()
    assert gate.acquire(full=False, timeout=10.0)
    assert time.perf_counter() - t0 < 5.0       # not the timer's minute
    assert gate.held == 1
    assert gate.counters.snapshot() == {**NO_COUNTS, "held": 1,
                                        "early_free": 1}


@pytest.mark.parametrize("reason, full, plans", [
    ("full", True, {1: 60.0}),
    ("no_estimate", False, {1: None}),
    ("no_estimate", False, {}),                 # put, not yet planned
    ("prep_bound", False, {1: -0.001}),
])
def test_gate_grants_at_once_by_reason(reason, full, plans):
    gate = _InflightGate(workers=1, depth=None)
    assert gate.acquire(full=False)
    for seq, after in plans.items():
        gate.plan(seq, None if after is None
                  else time.perf_counter() + after)
    assert gate.acquire(full=full)
    assert gate.counters.snapshot() == {**NO_COUNTS, reason: 1}


def test_two_workers_plan_by_the_one_that_frees_first():
    gate = _InflightGate(workers=2, depth=None)
    assert gate.tokens == 3
    assert gate.acquire(full=False) and gate.acquire(full=False)
    now = time.perf_counter()
    gate.plan(1, now + 60)
    gate.plan(2, now + 0.05)
    assert not gate.acquire(full=False, timeout=0.005)
    assert gate.acquire(full=False, timeout=2.0)
    assert time.perf_counter() >= now + 0.05
    assert gate.counters.snapshot() == {**NO_COUNTS, "held": 1}


# -- the engine under saturation ---------------------------------------------


@pytest.fixture(scope="module", params=[
    ("single", None), ("single", 2), ("zoo", None)],
    ids=["single-default", "single-depth2", "zoo-default"])
def saturated(request):
    """A request every 12 ms behind a 60 ms step: batches of about
    five of eight rows run back to back, as in the serving cell."""
    path, depth = request.param
    tracer = Tracer(enabled=True, capacity=1024)
    engine = _engine(Stub(), path, depth, tracer=tracer)
    try:
        _warm(engine)
        _offer(engine, 60, 0.012)
        metrics = engine.metrics()
        ms = _stage_ms(tracer, 61)
    finally:
        _stop(engine)
    return path, depth, ms, metrics


def test_sealed_as_the_worker_frees(saturated):
    path, depth, ms, metrics = saturated
    step_ms = STEP_S * 1e3
    dispatch = statistics.median(ms["dispatch_wait"])
    held = metrics["run_ahead_held_total"]
    if depth is not None:
        # the sealed batch lies in the queue for most of a step
        assert dispatch > 0.6 * step_ms
        assert held == metrics["run_ahead_early_free_total"] == 0
        assert not any(metrics["run_ahead_immediate_total"].values())
        return
    assert dispatch < 0.4 * step_ms
    assert held > 0.5 * metrics["batches_processed"]
    # the wait has not gone, it is where the batch is still open: the
    # token wait of a sealed batch, the collecting of a pump's group
    before = statistics.median(
        ms["token_wait" if path == "single" else "collect_wait"])
    assert before > 0.2 * step_ms and before > dispatch


def test_default_depth_seals_later_than_depth_two():
    """The same load through both gates: the default's batches wait in
    the dispatch queue for a fraction of what depth 2's do, and no
    throughput is lost for it."""
    got = {}
    for depth in (None, 2):
        tracer = Tracer(enabled=True, capacity=1024)
        engine = _engine(Stub(), depth=depth, tracer=tracer)
        try:
            _warm(engine)
            t0 = time.perf_counter()
            _offer(engine, 60, 0.012)
            wall = time.perf_counter() - t0
            ms = _stage_ms(tracer, 61)
        finally:
            _stop(engine)
        got[depth] = (statistics.median(ms["dispatch_wait"]), wall)
    assert got[None][0] < 0.5 * got[2][0]
    assert got[None][1] < 1.5 * got[2][1]


def test_lone_request_on_an_idle_engine_waits_for_no_plan():
    tracer = Tracer(enabled=True, capacity=64)
    engine = _engine(Stub(), tracer=tracer)
    try:
        _warm(engine)
        t0 = time.perf_counter()
        _post(engine, 1)
        wall_ms = (time.perf_counter() - t0) * 1e3
        ms = _stage_ms(tracer, 2)
        counts = engine._inflight.counters.snapshot()
        text = engine.metrics_text()
    finally:
        _stop(engine)
    # max_wait_ms + one step, and what the sandbox adds
    assert wall_ms < 2.0 + STEP_S * 1e3 + 100
    assert max(ms["token_wait"]) < 20 and max(ms["dispatch_wait"]) < 20
    assert counts == NO_COUNTS          # a free worker is no run-ahead
    for line in ("serving_run_ahead_held_total 0",
                 "serving_run_ahead_early_free_total 0",
                 'serving_run_ahead_immediate_total{reason="full"} 0',
                 'serving_run_ahead_immediate_total{reason="no_estimate"} 0',
                 'serving_run_ahead_immediate_total{reason="prep_bound"} 0'):
        assert line in text.splitlines()


@pytest.mark.parametrize("reason", ["full", "no_estimate", "prep_bound"])
def test_engine_grants_at_once_by_reason(reason):
    if reason == "full":
        # bursts of four rows at a time: every pending batch is full
        stub, batch_size, n, stagger = Stub(), 4, 24, 0.0
    elif reason == "no_estimate":
        # the second batch asks while the first, the handle's first
        # ever, still runs
        stub, batch_size, n, stagger = Stub(0.15), 8, 2, 0.03
    else:
        # a step shorter than its decode: the run-ahead hides the
        # decode, and the gate keeps the pipeline an engine had
        stub, batch_size, n, stagger = Stub(0.005, 0.02), 64, 40, 0.003
    tracer = Tracer(enabled=True, capacity=256)
    engine = _engine(stub, batch_size=batch_size, tracer=tracer)
    try:
        _offer(engine, n, stagger)
        metrics = engine.metrics()
        ms = _stage_ms(tracer, n)
    finally:
        _stop(engine)
    counts = metrics["run_ahead_immediate_total"]
    assert counts[reason] >= (1 if reason == "no_estimate" else 2)
    assert metrics["run_ahead_early_free_total"] == 0
    if reason == "no_estimate":
        # granted at once, so it lay in the queue for the rest of the
        # first batch's step
        assert max(ms["dispatch_wait"]) > 0.5 * 150
    if reason == "prep_bound":
        assert metrics["run_ahead_held_total"] == 0


def test_engine_does_not_wait_out_the_timer_when_a_step_ends_early():
    """Four steps of 150 ms teach the estimate, then steps of 40 ms:
    the batch held for the first short step's planned end is let
    through when the worker frees, 110 ms before the timer."""
    long_s, short_s, n_long = 0.15, 0.04, 4
    stub = Stub(lambda i: long_s if i < n_long else short_s)
    engine = _engine(stub, batch_size=64)
    try:
        _offer(engine, 70, 0.015)
        metrics = engine.metrics()
    finally:
        _stop(engine)
    assert metrics["run_ahead_early_free_total"] >= 1
    assert len(stub.calls) > n_long + 1
    gap = stub.calls[n_long + 1][0] - stub.calls[n_long][1]
    assert gap < 0.5 * (long_s - short_s)


# -- no token is lost --------------------------------------------------------


def _post_quietly(engine, x, timeout=2.0):
    """A request whose reply may never come."""
    def run():
        try:
            _post(engine, x, timeout=timeout)
        except (OSError, AssertionError, urllib.error.URLError):
            pass
    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


@pytest.mark.parametrize("path", ["single", "zoo"])
def test_stop_during_a_hold_leaves_no_token_out(path):
    stub = Stub(0.15)
    engine = _engine(stub, path)
    try:
        _warm(engine)
        clients = [_post_quietly(engine, 1)]
        assert _until(lambda: engine._inflight.held == 1)
        clients.append(_post_quietly(engine, 2))    # pending, held
        assert _until(lambda: engine._inflight.counters.snapshot()
                      ["held"] == 1)
    finally:
        _stop(engine)
    for t in clients:
        t.join(timeout=5)
    assert engine._inflight.held == engine._dispatch_q.qsize() == 0
    assert engine._inflight.counters.snapshot()["early_free"] == 0


@pytest.mark.parametrize("path", ["single", "zoo"])
def test_assembly_failure_gives_the_token_back(path, monkeypatch):
    engine = _engine(Stub(0.01), path)
    build, failed = engine._build_item, []

    def build_once(*args, **kw):
        if not failed:
            failed.append(True)
            raise RuntimeError("no table today")
        return build(*args, **kw)

    monkeypatch.setattr(engine, "_build_item", build_once)
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(engine, 0)
        assert err.value.code == 500
        assert _until(lambda: engine._inflight.held == 0)
        _offer(engine, 6, 0.002, first=1)
        assert _until(lambda: engine._inflight.held == 0)
    finally:
        _stop(engine)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_dying_worker_gives_the_token_back():
    stub = Stub(0.01)
    execute = stub.execute

    def die_once(table, xs):
        if not stub.calls:
            stub.calls.append((0.0, 0.0))
            raise SystemExit("worker killed")
        return execute(table, xs)

    stub.execute = die_once
    engine = _engine(stub)
    try:
        lost = _post_quietly(engine, 0, timeout=1.0)
        assert _until(lambda: engine.workers_restarted == 1)
        assert engine._inflight.held == 0
        _offer(engine, 6, 0.002, first=1)
        assert _until(lambda: engine._inflight.held == 0)
        lost.join(timeout=5)
    finally:
        _stop(engine)


# -- the option --------------------------------------------------------------


def test_depth_is_the_engines_to_decide_unless_named():
    for depth, tokens in ((None, 2), (1, 1), (3, 3)):
        engine = ServingEngine(HTTPSource(port=0), Stub().stage(),
                               pipeline_depth=depth, slo=False,
                               flight_recorder=False)
        try:
            assert engine.pipeline_depth == depth
            assert engine._inflight.adaptive is (depth is None)
            assert engine._inflight.tokens == tokens
        finally:
            engine.source.close()
    fleet = ServingFleet(Stub().stage(), n_engines=2, base_port=0,
                         slo=False, flight_recorder=False)
    try:
        assert [e.pipeline_depth for e in fleet.engines] == [None, None]
    finally:
        fleet.stop_all()
