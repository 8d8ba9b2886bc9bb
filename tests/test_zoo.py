"""Multi-model serving plane suite (serving/zoo.py + admission.py):
model-key routing, lazy activation, LRU eviction under count/bytes/
memory pressure, registry lookup/list consistency under concurrent
churn, tenant quotas + priority shedding, the mixed-tenant model-churn
chaos drill, and the warmup-example validation satellite.

The 256-model floor (bounded p99 under churn, zero steady-state
recompiles on resident models) is slow-marked.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.table import DataTable
from mmlspark_tpu.core.warmup import (
    check_warmup_example, warn_warmup_example,
)
from mmlspark_tpu.serving import (
    AdmissionController, HTTPSource, ModelRegistry, ModelZoo,
    ServingEngine, ServingFleet, TenantQuota,
)
from mmlspark_tpu.serving.admission import request_identity
from mmlspark_tpu.serving.fleet import ServingUnavailable
from mmlspark_tpu.serving.zoo import (
    FAILED, LOADING, RESIDENT, UNLOADED, model_key_of,
)
from mmlspark_tpu.stages.basic import Lambda


def echo_stage(tag, delay=0.0, batch_log=None):
    """A tiny serving stage that stamps its model tag into every reply
    (and optionally logs each batch it sees) — the instrument for the
    no-mixed-model and routing assertions."""
    def handle(table):
        if delay:
            time.sleep(delay)
        if batch_log is not None:
            batch_log.append((tag, len(table)))
        replies = []
        for r in table["request"]:
            row = json.loads(r["entity"].decode()) if r.get("entity") \
                else {}
            replies.append({"served_by": tag, "x": row.get("x")})
        return table.with_column("reply", replies)
    return Lambda.apply(handle)


def fresh_zoo(n_models=4, max_resident=None, delay=0.0,
              batch_log=None, **kw):
    kw.setdefault("memory_probe", None)
    zoo = ModelZoo(max_resident=max_resident, **kw)
    for i in range(n_models):
        zoo.register_factory(
            f"m{i}", "v1",
            (lambda i=i: echo_stage(f"m{i}", delay=delay,
                                    batch_log=batch_log)))
    return zoo


def post(addr, body, headers=None, path="/", timeout=30.0):
    """(status, parsed body, response headers) — HTTPError unwrapped."""
    req = urllib.request.Request(
        addr + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        try:
            body = json.loads(e.read())
        except Exception:  # noqa: BLE001
            body = {}
        return e.code, body, dict(e.headers)


# ---------------------------------------------------------------------------
# request routing keys
# ---------------------------------------------------------------------------


class TestModelKeyOf:
    def test_header_case_insensitive(self):
        req = {"requestLine": {"uri": "/"},
               "headers": {"x-MoDeL": "m@v3"}}
        assert model_key_of(req) == "m@v3"

    def test_url_path(self):
        req = {"requestLine": {"uri": "/models/scorer@v2"}, "headers": {}}
        assert model_key_of(req) == "scorer@v2"

    def test_url_path_urlencoded(self):
        req = {"requestLine": {"uri": "/models/scorer%40v2"},
               "headers": {}}
        assert model_key_of(req) == "scorer@v2"

    def test_query_param(self):
        req = {"requestLine": {"uri": "/?model=m1"}, "headers": {}}
        assert model_key_of(req) == "m1"

    def test_header_wins_over_path(self):
        req = {"requestLine": {"uri": "/models/b@v1"},
               "headers": {"X-Model": "a@v1"}}
        assert model_key_of(req) == "a@v1"

    def test_unkeyed(self):
        assert model_key_of({"requestLine": {"uri": "/"},
                             "headers": {}}) is None
        assert model_key_of(None) is None


# ---------------------------------------------------------------------------
# registry lookup/list consistency (the race-hardening satellite)
# ---------------------------------------------------------------------------


class TestRegistryConsistency:
    def test_lookup_triple_and_list(self):
        reg = ModelRegistry()
        reg.register("v1", echo_stage("a"), metadata={"note": "n"})
        obj, state, meta = reg.lookup("v1")
        assert obj is not None and state == "registered"
        assert meta["note"] == "n" and meta["precision"] == "f32"
        rows = reg.list()
        assert rows[0]["version"] == "v1" and rows[0]["loaded"]
        with pytest.raises(KeyError):
            reg.lookup("nope")

    def test_base_registry_hammer(self):
        """lookup/list racing register must always see complete
        entries (metadata carries the auto precision/aot keys the
        moment the version is visible at all)."""
        reg = ModelRegistry()
        stop = threading.Event()
        errors = []

        def writer():
            for i in range(200):
                reg.register(f"v{i}", echo_stage(f"v{i}"))
            stop.set()

        def reader():
            while not stop.is_set():
                for row in reg.list():
                    if "precision" not in row["metadata"]:
                        errors.append(f"torn metadata: {row}")
                for v in reg.versions():
                    obj, state, meta = reg.lookup(v)
                    if obj is None or state != "registered" \
                            or "precision" not in meta:
                        errors.append(f"torn lookup: {v}")

        threads = [threading.Thread(target=reader) for _ in range(3)]
        threads.append(threading.Thread(target=writer))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors[:3]
        assert len(reg.versions()) == 200

    def test_zoo_lookup_hammer_under_churn(self):
        """The zoo's (handle, state, metadata) triples stay consistent
        while models churn through load/evict: RESIDENT always comes
        with a live handle, every other state with none."""
        zoo = fresh_zoo(n_models=6, max_resident=2)
        stop = threading.Event()
        errors = []

        def churn():
            for i in range(60):
                zoo.get(f"m{i % 6}", timeout=30)
            stop.set()

        def reader():
            while not stop.is_set():
                for i in range(6):
                    handle, state, meta = zoo.lookup(f"m{i}@v1")
                    if state == RESIDENT:
                        if handle is None or handle.pipeline is None:
                            errors.append(f"resident without handle m{i}")
                    elif handle is not None:
                        errors.append(f"{state} with handle m{i}")
                for row in zoo.list():
                    if row["loaded"] != (row["state"] == RESIDENT):
                        errors.append(f"torn list row: {row}")

        threads = [threading.Thread(target=reader) for _ in range(3)]
        threads.append(threading.Thread(target=churn))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        try:
            assert not errors, errors[:3]
            assert zoo.evictions > 0          # churn actually churned
            assert zoo.evictions_with_outstanding == 0
        finally:
            zoo.close()


# ---------------------------------------------------------------------------
# the zoo cache itself
# ---------------------------------------------------------------------------


class TestModelZoo:
    def test_lazy_load_states_and_audit(self):
        zoo = fresh_zoo(n_models=2)
        try:
            assert zoo.lookup("m0@v1")[1] == UNLOADED
            stage = zoo.get("m0")
            assert stage is not None
            assert zoo.lookup("m0@v1")[1] == RESIDENT
            kinds = [e.kind for e in zoo.events]
            assert kinds.count("register") == 2
            assert kinds.count("activate") == 1
            ev = [e for e in zoo.events if e.kind == "activate"][0]
            assert ev.model == "m0" and "ms" in ev.stats
        finally:
            zoo.close()

    def test_unknown_and_bare_name_latest(self):
        zoo = fresh_zoo(n_models=1)
        zoo.register_factory("m0", "v2", lambda: echo_stage("m0v2"))
        try:
            assert zoo.resolve("m0") == "m0@v2"     # latest wins
            assert zoo.resolve("m0@v1") == "m0@v1"
            assert zoo.resolve("nope") is None
            with pytest.raises(KeyError):
                zoo.get("nope")
        finally:
            zoo.close()

    def test_lru_eviction_count_cap(self):
        zoo = fresh_zoo(n_models=4, max_resident=2)
        try:
            for i in range(3):
                zoo.get(f"m{i}")
            zoo.enforce()
            # m0 is the LRU victim; m1/m2 stay
            assert zoo.lookup("m0@v1")[1] == UNLOADED
            assert zoo.lookup("m1@v1")[1] == RESIDENT
            assert zoo.lookup("m2@v1")[1] == RESIDENT
            evs = [e for e in zoo.events if e.kind == "evict"]
            assert len(evs) == 1 and evs[0].model == "m0"
            assert evs[0].reason == "lru:count_cap"
            # an evicted model reloads on demand (and re-evicts the
            # new LRU)
            assert zoo.get("m0") is not None
            assert zoo.lookup("m0@v1")[1] == RESIDENT
        finally:
            zoo.close()

    def test_bytes_cap_eviction(self):
        zoo = ModelZoo(max_resident_bytes=250, memory_probe=None)
        for i in range(3):
            zoo.register_factory(f"m{i}", "v1",
                                 (lambda i=i: echo_stage(f"m{i}")),
                                 metadata={"cost_bytes": 100})
        try:
            zoo.get("m0"), zoo.get("m1")
            assert zoo.stats()["resident_bytes"] == 200
            zoo.get("m2")                     # 300 > 250: LRU evicts
            zoo.enforce()
            assert zoo.lookup("m0@v1")[1] == UNLOADED
            assert zoo.stats()["resident_bytes"] == 200
        finally:
            zoo.close()

    def test_memory_pressure_probe_eviction(self):
        pressure = {"on": False}

        def probe():
            if pressure["on"]:
                return {"bytes_in_use": 95, "bytes_limit": 100}
            return {"bytes_in_use": 10, "bytes_limit": 100}

        zoo = ModelZoo(memory_probe=probe, memory_headroom=0.9)
        for i in range(3):
            zoo.register_factory(f"m{i}", "v1",
                                 (lambda i=i: echo_stage(f"m{i}")))
        try:
            for i in range(3):
                zoo.get(f"m{i}")
            zoo.enforce()
            assert zoo.stats()["by_state"][RESIDENT] == 3   # no pressure
            pressure["on"] = True
            zoo.enforce()
            # sheds down to (but never below) ONE resident model
            assert zoo.stats()["by_state"][RESIDENT] == 1
            assert zoo.lookup("m2@v1")[1] == RESIDENT       # MRU kept
            reasons = {e.reason for e in zoo.events
                       if e.kind == "evict"}
            assert reasons == {"lru:memory_pressure"}
        finally:
            zoo.close()

    def test_eviction_never_hits_outstanding(self):
        zoo = fresh_zoo(n_models=2, max_resident=1)
        try:
            zoo.get("m0")
            handle, state, _ = zoo.acquire("m0")   # a batch in flight
            assert state == RESIDENT
            zoo.get("m1")                          # over the cap
            zoo.enforce()
            # m0 (LRU) has an outstanding batch: m1 is the only
            # eligible victim even though it is MRU
            assert zoo.lookup("m0@v1")[1] == RESIDENT
            handle.release()
            zoo.enforce()
            assert zoo.lookup("m0@v1")[1] == UNLOADED
            assert zoo.evictions_with_outstanding == 0
        finally:
            zoo.close()

    def test_eviction_never_hits_awaited_model(self):
        # regression for the demand > capacity livelock: a model with
        # requests parked AWAITING its activation must not be the LRU
        # victim the instant it activates — it would evict before the
        # batcher's flush poll, reload, and starve its requests to the
        # activation timeout (seen as 280 load/evict events per second
        # in the churn drill under host contention)
        zoo = fresh_zoo(n_models=3, max_resident=1)
        try:
            zoo.add_waiter("m0")   # a batcher parks BEFORE activation
            zoo.get("m0")
            zoo.get("m1")          # 2 residents > cap; m1's post-load
            zoo.enforce()          # enforce must spare awaited m0
            # m0 is LRU but awaited; m1 is MRU: neither evictable
            assert zoo.lookup("m0@v1")[1] == RESIDENT
            assert not zoo.evict("m0")     # manual evict refuses too
            zoo.remove_waiter("m0")
            zoo.enforce()
            assert zoo.lookup("m0@v1")[1] == UNLOADED
            assert zoo.evictions_with_outstanding == 0
        finally:
            zoo.close()

    def test_pin_exempts_from_eviction(self):
        zoo = fresh_zoo(n_models=3, max_resident=1)
        try:
            zoo.get("m0")
            zoo.pin("m0")
            zoo.get("m1")
            zoo.get("m2")
            zoo.enforce()
            assert zoo.lookup("m0@v1")[1] == RESIDENT
            assert not zoo.evict("m0")    # manual evict refuses too
            zoo.pin("m0", pinned=False)
            assert zoo.evict("m0")
        finally:
            zoo.close()

    def test_memory_probe_none_disables_live_signal(self):
        # regression: memory_probe=None must mean the live signal is
        # OFF (CPU tests, hosts where preallocation makes bytes_in_use
        # meaningless) — it used to silently substitute the default
        # device_memory_stats probe
        zoo = ModelZoo(memory_probe=None)
        try:
            assert zoo.memory_probe is None
        finally:
            zoo.close()
        zoo2 = ModelZoo()          # default: the live probe is wired
        try:
            assert zoo2.memory_probe is not None
        finally:
            zoo2.close()

    def test_event_log_bounded_under_churn(self):
        # regression: the inherited registry event log was append-only
        # — a churning cache in an always-on process must not grow the
        # audit trail forever
        zoo = fresh_zoo(n_models=2, max_resident=1)
        zoo.events_cap = 16
        try:
            for _ in range(30):
                zoo.get("m0")
                zoo.enforce()
                zoo.get("m1")
                zoo.enforce()
            assert len(zoo.events) <= 16
            assert zoo.events[-1].kind in ("activate", "evict")
        finally:
            zoo.close()

    def test_scan_orders_versions_naturally(self, tmp_path):
        # regression: lexicographic os.listdir order registers v9
        # AFTER v12, so bare-name latest would silently serve v9
        for v in ("v1", "v9", "v10", "v12"):
            d = tmp_path / "m" / v
            d.mkdir(parents=True)
            (d / "manifest.json").write_text(
                '{"kind": "model", "precision": "f32", "buckets": [8]}')
        zoo = ModelZoo(artifact_root=str(tmp_path), memory_probe=None)
        try:
            assert zoo.resolve("m") == "m@v12"
        finally:
            zoo.close()

    def test_lost_load_requeued_by_watchdog(self):
        # regression: an entry stuck LOADING (queued load lost to a
        # loader death or a close() race) must recover — acquire's
        # watchdog requeues overdue loads instead of 503ing forever
        zoo = fresh_zoo(n_models=1)
        try:
            with zoo._lock:
                e = zoo._entries["m0@v1"]
                e.state = LOADING          # simulate the lost load
                e.loading_since = time.monotonic() - 999
            assert zoo.get("m0", timeout=10) is not None
        finally:
            zoo.close()

    def test_single_oversized_model_never_self_evicts(self):
        # regression: a SOLE resident model whose cost exceeds a cap
        # must not evict itself right after every activation — a
        # load/evict livelock that never serves the request that
        # triggered the load. Brief overshoot beats thrash.
        zoo = ModelZoo(max_resident_bytes=100, memory_probe=None)
        zoo.register_factory("big", "v1", lambda: echo_stage("big"),
                             metadata={"cost_bytes": 500})
        try:
            assert zoo.get("big", timeout=10) is not None
            for _ in range(3):
                zoo.enforce()
            assert zoo.lookup("big@v1")[1] == RESIDENT
            assert zoo.evictions == 0
            assert zoo.activations == 1
        finally:
            zoo.close()

    def test_load_failure_cooldown_and_retry(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("weights corrupt")
            return echo_stage("ok")

        zoo = ModelZoo(memory_probe=None, failure_cooldown_s=0.1)
        zoo.register_factory("m", "v1", flaky)
        try:
            with pytest.raises(RuntimeError, match="weights corrupt"):
                zoo.get("m", timeout=10)
            assert zoo.lookup("m@v1")[1] == FAILED
            assert zoo.load_failures == 1
            assert [e.kind for e in zoo.events].count("load_failed") == 1
            time.sleep(0.15)                  # cooldown over: retried
            assert zoo.get("m", timeout=10) is not None
            assert zoo.lookup("m@v1")[1] == RESIDENT
        finally:
            zoo.close()


# ---------------------------------------------------------------------------
# the model-routed engine
# ---------------------------------------------------------------------------


@pytest.fixture
def zoo_engine():
    zoo = fresh_zoo(n_models=4)
    source = HTTPSource(port=19700)
    engine = ServingEngine(source, zoo=zoo, batch_size=8,
                           max_wait_ms=2.0, tracing=False).start()
    yield engine, zoo, source.address
    engine.stop()
    zoo.close()


class TestZooEngine:
    def test_routes_by_header_and_path(self, zoo_engine):
        engine, zoo, addr = zoo_engine
        code, body, headers = post(addr, {"x": 1}, {"X-Model": "m1"})
        assert code == 200 and body["served_by"] == "m1"
        assert headers.get("X-Model") == "m1@v1"
        code, body, headers = post(addr, {"x": 2}, path="/models/m2@v1")
        assert code == 200 and body["served_by"] == "m2"
        assert headers.get("X-Model") == "m2@v1"

    def test_unkeyed_400_unknown_404(self, zoo_engine):
        engine, zoo, addr = zoo_engine
        code, body, _ = post(addr, {"x": 1})
        assert code == 400 and "no model specified" in body["error"]
        code, body, _ = post(addr, {"x": 1}, {"X-Model": "ghost"})
        assert code == 404 and "unknown model" in body["error"]
        with engine._stats_lock:
            rej = dict(engine.rejections)
        assert rej == {"no_model": 1, "unknown_model": 1}

    def test_zoo_fault_rejects_group_alone(self, zoo_engine):
        # regression: a zoo fault while acquiring ONE model's handle
        # (e.g. the loader thread failing to spawn) must 500 that
        # group alone — other models keep serving and the batcher
        # thread survives
        engine, zoo, addr = zoo_engine
        real = zoo.acquire

        def flaky(spec):
            if spec.startswith("m3"):
                raise RuntimeError("loader thread spawn failed")
            return real(spec)

        zoo.acquire = flaky
        try:
            code, body, _ = post(addr, {"x": 1}, {"X-Model": "m3"})
            assert code == 500 and "routing error" in body["error"]
            code, body, _ = post(addr, {"x": 2}, {"X-Model": "m1"})
            assert code == 200 and body["served_by"] == "m1"
            with engine._stats_lock:
                assert engine.rejections.get("routing_error") == 1
        finally:
            zoo.acquire = real

    def test_default_pipeline_serves_unkeyed(self):
        zoo = fresh_zoo(n_models=1)
        source = HTTPSource(port=19710)
        engine = ServingEngine(source, echo_stage("default"), zoo=zoo,
                               tracing=False).start()
        try:
            code, body, headers = post(source.address, {"x": 1})
            assert code == 200 and body["served_by"] == "default"
            assert "X-Model" not in headers    # default path: no label
            code, body, _ = post(source.address, {"x": 1},
                                 {"X-Model": "m0"})
            assert code == 200 and body["served_by"] == "m0"
        finally:
            engine.stop()
            zoo.close()

    def test_no_mixed_model_batches_under_concurrency(self):
        batch_log = []
        zoo = fresh_zoo(n_models=4, batch_log=batch_log, delay=0.002)
        source = HTTPSource(port=19720)
        engine = ServingEngine(source, zoo=zoo, batch_size=8,
                               max_wait_ms=4.0, workers=2,
                               tracing=False).start()
        results = []
        lock = threading.Lock()

        def client(tid):
            for i in range(10):
                model = f"m{(tid + i) % 4}"
                code, body, headers = post(source.address, {"x": i},
                                           {"X-Model": model})
                with lock:
                    results.append((model, code, body, headers))

        try:
            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert len(results) == 80
            for model, code, body, headers in results:
                assert code == 200
                # the reply-mixing check: every reply's model/version
                # matches its request, body AND header
                assert body["served_by"] == model
                assert headers.get("X-Model") == f"{model}@v1"
            # micro-batches really batched (not all 1-row) yet never
            # mixed: each stage only ever saw its own tag
            assert all(tag in (f"m{i}" for i in range(4))
                       for tag, _n in batch_log)
            assert any(n > 1 for _tag, n in batch_log)
        finally:
            engine.stop()
            zoo.close()

    def test_cold_activation_does_not_block_resident_models(self):
        zoo = ModelZoo(memory_probe=None)
        zoo.register_factory("fast", "v1", lambda: echo_stage("fast"))
        zoo.register_factory(
            "cold", "v1",
            lambda: (time.sleep(0.8), echo_stage("cold"))[1])
        source = HTTPSource(port=19730)
        engine = ServingEngine(source, zoo=zoo, max_wait_ms=2.0,
                               tracing=False).start()
        try:
            assert post(source.address, {"x": 0},
                        {"X-Model": "fast"})[0] == 200
            cold_result = {}

            def cold_client():
                cold_result["r"] = post(source.address, {"x": 1},
                                        {"X-Model": "cold"},
                                        timeout=30)

            t = threading.Thread(target=cold_client)
            t.start()
            time.sleep(0.05)          # the cold activation is in flight
            lat = []
            for i in range(5):
                t0 = time.perf_counter()
                code, body, _ = post(source.address, {"x": i},
                                     {"X-Model": "fast"})
                lat.append(time.perf_counter() - t0)
                assert code == 200 and body["served_by"] == "fast"
            # resident traffic never waits behind the 0.8s activation
            assert max(lat) < 0.5, lat
            t.join(timeout=30)
            code, body, _ = cold_result["r"]
            assert code == 200 and body["served_by"] == "cold"
        finally:
            engine.stop()
            zoo.close()

    def test_activation_timeout_sheds_503(self):
        zoo = ModelZoo(memory_probe=None)
        zoo.register_factory(
            "slow", "v1",
            lambda: (time.sleep(1.5), echo_stage("slow"))[1])
        source = HTTPSource(port=19740)
        engine = ServingEngine(source, zoo=zoo, max_wait_ms=2.0,
                               activation_timeout_s=0.2,
                               tracing=False).start()
        try:
            code, body, headers = post(source.address, {"x": 1},
                                       {"X-Model": "slow"}, timeout=30)
            assert code == 503 and "activating" in body["error"]
            assert headers.get("Retry-After")
            # the activation itself still completes in the background;
            # a later request is served
            zoo.get("slow", timeout=30)
            code, body, _ = post(source.address, {"x": 2},
                                 {"X-Model": "slow"})
            assert code == 200 and body["served_by"] == "slow"
        finally:
            engine.stop()
            zoo.close()


# ---------------------------------------------------------------------------
# admission: tenant quotas + priority tiers
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_request_identity(self):
        req = {"headers": {"x-tenant": "acme", "X-PRIORITY": "0"}}
        assert request_identity(req) == ("acme", 0)
        assert request_identity({"headers": {}}) == ("default", 1)
        # malformed priority keeps the default; out-of-range clamps
        assert request_identity(
            {"headers": {"X-Priority": "zz"}})[1] == 1
        assert request_identity(
            {"headers": {"X-Priority": "99"}})[1] == 2

    def test_decide_quota_and_priority(self):
        adm = AdmissionController(
            quotas={"noisy": TenantQuota(0.0, burst=2)},
            priority_pressure_limits={2: 0})
        assert adm.decide("noisy", 1, 0) is None
        assert adm.decide("noisy", 1, 0) is None
        assert adm.decide("noisy", 1, 0) == "quota"   # burst spent
        assert adm.decide("calm", 1, 0) is None       # unlimited
        assert adm.decide("calm", 2, 1) == "priority"  # pressure > 0
        assert adm.decide("calm", 2, 0) is None       # at the limit: ok
        assert adm.decide("calm", 0, 10**6) is None   # high never sheds
        stats = adm.stats()
        assert stats["shed"] == {"quota": 1, "priority": 1}
        assert stats["shed_by_tenant"]["noisy"] == 1

    def test_quota_429_over_http_no_failover(self):
        zoo = fresh_zoo(n_models=1)
        adm = AdmissionController(
            quotas={"noisy": TenantQuota(0.0, burst=1)})
        fleet = ServingFleet(n_engines=2, base_port=19750, zoo=zoo,
                             admission=adm, tracing=False)
        try:
            assert fleet.post({"x": 1}, model="m0",
                              tenant="noisy")["served_by"] == "m0"
            # quota spent: 429 surfaces (a fleet-wide quota must NOT
            # fail over — the next replica would just spend it too)
            with pytest.raises(urllib.error.HTTPError) as err:
                fleet.post({"x": 2}, model="m0", tenant="noisy")
            assert err.value.code == 429
            # another tenant is unaffected
            assert fleet.post({"x": 3}, model="m0",
                              tenant="calm")["served_by"] == "m0"
            total_rej = sum(e.rejections.get("quota", 0)
                            for e in fleet.engines)
            assert total_rej == 1
        finally:
            fleet.stop_all()
            zoo.close()

    def test_pressure_counts_source_backlog(self):
        # regression: the dispatch queue alone is bounded by the
        # in-flight token count (workers + pipeline_depth - 1), which
        # left the default tier-2 pressure limit (8) unreachable; the
        # source-queue backlog is where real overload shows
        from mmlspark_tpu.serving.server import _ParkedRequest
        zoo = fresh_zoo(n_models=1)
        source = HTTPSource(port=19767)
        engine = ServingEngine(source, zoo=zoo, tracing=False)
        try:
            assert engine._pressure() == 0
            for i in range(10):
                source.queue.put_nowait(
                    _ParkedRequest(f"r{i}", {"headers": {}}))
            assert engine._pressure() == 10    # > the default limit 8
        finally:
            source.close()
            zoo.close()

    def test_unknown_model_does_not_spend_quota(self):
        # regression: routing runs BEFORE admission — a burst of
        # mistyped model names answers 404 without draining the
        # tenant's token bucket, so its well-formed traffic still
        # serves
        zoo = fresh_zoo(n_models=1)
        adm = AdmissionController(
            quotas={"acme": TenantQuota(0.0, burst=1)})
        source = HTTPSource(port=19765)
        engine = ServingEngine(source, zoo=zoo, admission=adm,
                               tracing=False).start()
        try:
            for i in range(3):
                code, _body, _ = post(source.address, {"x": i},
                                      {"X-Model": "ghost",
                                       "X-Tenant": "acme"})
                assert code == 404
            # the single burst token is still there for a real model
            code, body, _ = post(source.address, {"x": 9},
                                 {"X-Model": "m0", "X-Tenant": "acme"})
            assert code == 200 and body["served_by"] == "m0"
            # ... and spent now: the next request is the 429
            code, _body, _ = post(source.address, {"x": 10},
                                  {"X-Model": "m0",
                                   "X-Tenant": "acme"})
            assert code == 429
        finally:
            engine.stop()
            zoo.close()

    def test_low_priority_sheds_503_under_pressure(self):
        zoo = fresh_zoo(n_models=1)
        # limit -1: any pressure (>= 0) sheds tier 2 — the
        # deterministic stand-in for a saturated dispatch queue
        adm = AdmissionController(priority_pressure_limits={2: -1})
        source = HTTPSource(port=19760)
        engine = ServingEngine(source, zoo=zoo, admission=adm,
                               tracing=False).start()
        try:
            code, body, headers = post(
                source.address, {"x": 1},
                {"X-Model": "m0", "X-Priority": "2"})
            assert code == 503 and "priority" in body["error"]
            assert headers.get("Retry-After")
            code, body, _ = post(source.address, {"x": 1},
                                 {"X-Model": "m0", "X-Priority": "0"})
            assert code == 200
        finally:
            engine.stop()
            zoo.close()


# ---------------------------------------------------------------------------
# the chaos drill: model churn under mixed-tenant load
# ---------------------------------------------------------------------------


class TestZooChurnDrill:
    def test_churn_mixed_tenants_availability_and_no_mixing(self):
        """Models churn in and out (cache 3 of 12) under mixed-tenant
        concurrent load: availability >= 99%, every reply's
        model/version matches its request, and no eviction ever hits a
        model with outstanding batches."""
        zoo = fresh_zoo(n_models=12, max_resident=3, delay=0.001)
        fleet = ServingFleet(n_engines=2, base_port=19770, zoo=zoo,
                             batch_size=8, max_wait_ms=2.0,
                             tracing=False)
        results = []
        lock = threading.Lock()
        rng = np.random.default_rng(7)
        picks = rng.integers(0, 12, size=240)

        def client(tid):
            tenant = "alpha" if tid % 2 == 0 else "beta"
            for i in range(30):
                model = f"m{picks[tid * 30 + i]}"
                try:
                    body = fleet.post({"x": i}, model=model,
                                      tenant=tenant, timeout=60)
                    with lock:
                        results.append((model, 200, body))
                except urllib.error.HTTPError as e:
                    with lock:
                        results.append((model, e.code, None))
                except ServingUnavailable:
                    # fleet-level unavailability (both circuits open)
                    # is a FAILED request, measured by the
                    # availability floor — not a dead client thread
                    with lock:
                        results.append((model, 503, None))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(8)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert len(results) == 240
            ok = [r for r in results if r[1] == 200]
            availability = len(ok) / len(results)
            assert availability >= 0.99, (
                f"availability {availability:.3f}; "
                f"failures {[r for r in results if r[1] != 200][:5]}")
            # zero cross-model mixing: every reply names its request's
            # model
            for model, _code, body in ok:
                assert body["served_by"] == model, (model, body)
            # the drill actually churned, and no eviction ever touched
            # a model with batches in flight
            assert zoo.evictions > 0
            assert zoo.evictions_with_outstanding == 0
            # the cache may briefly overshoot the cap while waiter/
            # outstanding protection covers just-activated models
            # (documented: overshoot beats livelock); once traffic
            # stops, enforce converges it back under the cap
            for _ in range(20):
                zoo.enforce()
                if zoo.stats()["by_state"].get(RESIDENT, 0) <= 3:
                    break
                time.sleep(0.05)
            assert zoo.stats()["by_state"].get(RESIDENT, 0) <= 3
        finally:
            fleet.stop_all()
            zoo.close()


# ---------------------------------------------------------------------------
# the AOT artifact store as the distribution format
# ---------------------------------------------------------------------------


class TestZooAOTArtifacts:
    def test_artifact_scan_activate_serve(self, tmp_path):
        """An AOT artifact directory (serving/aot.py) is the zoo's
        distribution format: scan() discovers it, first request
        activates via the AOT load path (zero jit traces at request
        time), and the activation wall is recorded in the audit
        event."""
        import jax
        from mmlspark_tpu.models.networks import build_network
        from mmlspark_tpu.models.tpu_model import TPUModel
        from mmlspark_tpu.serving.aot import export_model

        module = build_network({"type": "mlp", "features": [8],
                                "num_classes": 3})
        x0 = np.zeros((1, 4), np.float32)
        weights = {"params": module.init(jax.random.PRNGKey(0),
                                         x0)["params"]}
        # from_flax: the model fn must survive pickling into the
        # artifact's lazy fallback (a test-local lambda would not)
        model = TPUModel.from_flax(module, weights,
                                   inputCol="features",
                                   outputCol="scores", batchSize=8)
        art_dir = tmp_path / "scorer" / "v1"
        export_model(model, {"features": x0}, str(art_dir),
                     version="v1")

        zoo = ModelZoo(artifact_root=str(tmp_path), memory_probe=None)
        try:
            assert zoo.resolve("scorer") == "scorer@v1"
            _handle, state, meta = zoo.lookup("scorer@v1")
            assert state == UNLOADED and meta["aot"] is True
            assert meta["buckets"] == [8]
            assert zoo.stats()["models"][0]["cost_bytes"] > 0

            source = HTTPSource(port=19780)
            engine = ServingEngine(source, zoo=zoo,
                                   tracing=False).start()
            try:
                code, body, headers = post(
                    source.address, {"features": [0.5, 0.1, 0.2, 0.9]},
                    {"X-Model": "scorer"}, timeout=120)
                assert code == 200 and "prediction" in body
                assert headers.get("X-Model") == "scorer@v1"
                misses_after_activate = None
                for e in zoo.events:
                    if e.kind == "activate":
                        assert e.stats["aot"] is True
                        assert e.stats["ms"] > 0
                        misses_after_activate = True
                assert misses_after_activate
                # steady state: more requests, zero new jit traces on
                # the AOT-loaded replica
                loaded = zoo.get("scorer")
                misses0 = loaded.jit_cache_miss_count()
                for i in range(4):
                    code, _b, _h = post(
                        source.address,
                        {"features": [0.1 * i] * 4},
                        {"X-Model": "scorer@v1"})
                    assert code == 200
                assert loaded.jit_cache_miss_count() == misses0
            finally:
                engine.stop()
        finally:
            zoo.close()


# ---------------------------------------------------------------------------
# warmup-example validation (the PR 11 footnote satellite)
# ---------------------------------------------------------------------------


class _DummyWarmupModel:
    """Pure-host stand-in exposing the warmup_transform contract."""

    jit_cache_misses = 0

    def bucket_sizes(self):
        return [4]

    def transform(self, table):
        return table


class TestWarmupExampleValidation:
    def test_all_none_column_flagged(self):
        table = DataTable({"a": [None], "b": [1.5]})
        msgs = check_warmup_example(table)
        assert len(msgs) == 1 and "'a'" in msgs[0]
        assert "OBJECT" in msgs[0] and "nan" in msgs[0].lower()

    def test_mixed_none_is_fine(self):
        # None mixed with real values infers the value dtype — only
        # ALL-None columns poison the warmed schema
        table = DataTable({"a": [None, 1.5], "b": ["x", None]})
        assert check_warmup_example(table) == []

    def test_live_column_mismatch_flagged(self):
        table = DataTable({"a": [1.0], "zz": [2.0]})
        msgs = check_warmup_example(table, live_columns=["a", "b"])
        assert len(msgs) == 2
        assert any("missing live request column(s) ['b']" in m
                   for m in msgs)
        assert any("['zz'] never seen" in m for m in msgs)

    def test_clean_example_silent(self):
        import warnings as W
        table = DataTable({"a": [1.0], "b": ["s"]})
        with W.catch_warnings():
            W.simplefilter("error")
            assert warn_warmup_example(
                table, live_columns=["a", "b"]) == []

    def test_warmup_transform_warns_at_warmup_time(self):
        from mmlspark_tpu.core.warmup import warmup_transform
        with pytest.warns(RuntimeWarning, match="all-None"):
            warmup_transform(_DummyWarmupModel(),
                             {"a": [None], "b": [1.0]})


# ---------------------------------------------------------------------------
# the CI-feasible scale floor
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestZooFloor:
    def test_256_models_one_fleet_bounded_p99(self):
        """>= 256 distinct versioned models behind one fleet under
        mixed traffic: availability >= 99%, bounded p99, evictions
        under a 64-model cache with zero availability loss, zero
        steady-state recompiles on a resident jitted model."""
        import jax
        from mmlspark_tpu.models.networks import build_network
        from mmlspark_tpu.models.tpu_model import TPUModel
        from mmlspark_tpu.serving.fleet import json_scoring_pipeline

        zoo = ModelZoo(max_resident=64, memory_probe=None,
                       label_cardinality_cap=64)
        n_models = 256
        for i in range(n_models):
            zoo.register_factory(f"m{i:03d}", f"v{i % 4}",
                                 (lambda i=i: echo_stage(f"m{i:03d}")))
        # one REAL jitted model rides along: the recompile guard
        module = build_network({"type": "mlp", "features": [16],
                                "num_classes": 4})
        x0 = np.zeros((1, 8), np.float32)
        weights = {"params": module.init(jax.random.PRNGKey(0),
                                         x0)["params"]}
        model = TPUModel(
            modelFn=lambda w, ins: module.apply(
                {"params": w["params"]}, list(ins.values())[0]),
            weights=weights, inputCol="features", outputCol="scores",
            batchSize=8, computeDtype="float32")
        zoo.register_factory(
            "jitted", "v1", lambda: json_scoring_pipeline(model),
            metadata={"warmup_example": {"features": x0}})
        zoo.pin("jitted")       # resident model: must never recompile
        zoo.get("jitted", timeout=120)
        misses_warm = int(model.jit_cache_misses)

        fleet = ServingFleet(n_engines=2, base_port=19800, zoo=zoo,
                             batch_size=8, max_wait_ms=2.0,
                             tracing=False)
        results = []
        lock = threading.Lock()
        rng = np.random.default_rng(3)
        picks = rng.integers(0, n_models, size=960)

        def client(tid):
            tenant = f"t{tid % 3}"
            for i in range(60):
                idx = picks[tid * 60 + i]
                if i % 10 == 5:
                    model_key, payload = "jitted", {
                        "features": [0.1] * 8}
                else:
                    model_key = f"m{idx:03d}"
                    payload = {"x": int(idx)}
                t0 = time.perf_counter()
                try:
                    body = fleet.post(payload, model=model_key,
                                      tenant=tenant, timeout=120)
                    with lock:
                        results.append(
                            (model_key, 200, body,
                             time.perf_counter() - t0))
                except urllib.error.HTTPError as e:
                    with lock:
                        results.append((model_key, e.code, None,
                                        time.perf_counter() - t0))
                except ServingUnavailable:
                    with lock:
                        results.append((model_key, 503, None,
                                        time.perf_counter() - t0))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(16)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            assert len(results) == 960
            ok = [r for r in results if r[1] == 200]
            availability = len(ok) / len(results)
            assert availability >= 0.99, f"availability {availability}"
            distinct = {m for m, c, _b, _l in ok if m.startswith("m")}
            assert len(distinct) >= 200       # the zoo really multiplexed
            for model_key, _c, body, _l in ok:
                if model_key == "jitted":
                    assert "prediction" in body
                else:
                    assert body["served_by"] == model_key
            lat = sorted(r[3] for r in ok)
            p99 = lat[int(0.99 * len(lat))]
            # CI-feasible bound on this throttled 2-core container
            assert p99 < 30.0, f"p99 {p99:.2f}s"
            assert zoo.evictions > 0
            assert zoo.evictions_with_outstanding == 0
            # zero steady-state recompiles on the resident jitted model
            assert int(model.jit_cache_misses) == misses_warm
        finally:
            fleet.stop_all()
            zoo.close()
