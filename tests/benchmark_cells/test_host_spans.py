"""benchmark/host_spans.py and the five readers built on it: CPU only.

A profile written by hand (a device plane and a host plane, as text)
checks the window against ``trace_reduce``, the charge of each gap to
the innermost phase, and that clocks which do not agree silence every
reader. A profile recorded here, on the CPU, around a stub engine checks
that the sums the program writes into its ``serve.execute`` phases come
back as the histograms' means, and that a sleep inside a named phase is
charged to it.
"""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run_host_spans")
import host_spans     # noqa: E402  (run.py put benchmark/ on the path)
import trace_reduce   # noqa: E402

MS = 1_000_000        # nanoseconds
NEW = ["serve_token_wait_ms", "serve_dispatch_wait_ms",
       "serve_worker_host_ms", "device_idle_serve_named",
       "device_idle_train_named"]
CELL_OF = {name: "gpt2m_train" if "train" in name else "gpt2xl_serve_steady"
           for name in NEW}


# ------------------------------------------------- a profile written by hand

def batch_phases(k, run_start, run_len, rows):
    """What the worker does around batch ``k``'s device execution, in
    ms: (name, start, length, stats). The execution starts as the
    dispatch ends; the blocked read ends 0.1 ms after it."""
    ex0 = run_start - 2.0
    read1 = run_start + run_len + 0.1
    stats = {"batch": k, "rows": rows}
    return [
        ("serve.execute", ex0, read1 + 0.1 - ex0, {
            **stats, "queue_wait_us": 1000.0 * rows,
            "collect_wait_us": 500.0 * rows * k,
            "token_wait_us": 100000.0 * rows,
            "dispatch_wait_us": 200000.0 * rows}),
        ("tpu_model.pad", ex0, 1.5, stats),
        ("tpu_model.dispatch", ex0 + 1.5, 0.5, stats),
        ("tpu_model.readback", run_start, read1 - run_start, stats),
        ("serve.respond", read1 + 0.1, 1.5, stats),
    ]


# three batches of 20 ms, 4 ms apart; a short program of another name
RUNS = [(10.0, 20.0), (34.0, 20.0), (58.0, 20.0)]
ROWS = [8, 4, 6]


def xspace_text(shift_host_ms=0.0, main="jit_tpu_model_forward",
                with_phases=True):
    def ps(ms):
        return int(round(ms * 1e9))
    dev_mods, dev_ops, meta = [], [], {}

    def mid(name):
        return meta.setdefault(name, len(meta) + 1)
    for start, length in RUNS:
        dev_mods.append((mid(main + "(77)"), start, length))
        # two operations back to back, and a while around them
        dev_ops.append((mid("%while.1 = (s32[]) while(%t)"), start, length))
        dev_ops.append((mid("%fusion.1 = f32[8] fusion(%p)"), start,
                        length / 2))
        dev_ops.append((mid("%fusion.2 = f32[8] fusion(%q)"),
                        start + length / 2, length / 2))
        dev_mods.append((mid("jit_convert(5)"), start + length + 0.01, 0.02))
    host, hmeta, smeta = [], {}, {}
    phases = [p for k, ((s, n), r) in enumerate(zip(RUNS, ROWS), 1)
              for p in batch_phases(k, s, n, r)] if with_phases else []
    for name, start, length, stats in phases:
        host.append((7, hmeta.setdefault(name, len(hmeta) + 1),
                     start + shift_host_ms, length, stats))
    if with_phases:     # the batcher waits for a token all along
        host.append((8, hmeta.setdefault("serve.token_wait", len(hmeta) + 1),
                     1.0 + shift_host_ms, 90.0, {"batch": 3, "rows": 1}))
    host.append((8, hmeta.setdefault("$some python frame", len(hmeta) + 1),
                 30.0, 4.0, {}))

    def events(rows):
        return " ".join(
            f"events {{ metadata_id: {m} offset_ps: {ps(s)} "
            f"duration_ps: {ps(n)} {st} }}" for m, s, n, st in rows)

    def stat_text(stats):
        out = []
        for key, val in stats.items():
            sid = smeta.setdefault(key, len(smeta) + 1)
            kind = "double_value" if isinstance(val, float) else "int64_value"
            out.append(f"stats {{ metadata_id: {sid} {kind}: {val} }}")
        return " ".join(out)

    lines = {}
    for line, m, s, n, stats in host:
        lines.setdefault(line, []).append((m, s, n, stat_text(stats)))
    host_lines = " ".join(
        f'lines {{ id: {line} name: "thread-{line}" {events(rows)} }}'
        for line, rows in lines.items())

    def metadata(kind, table):
        return " ".join(f'{kind} {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                        for n, i in table.items())
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules"
    {events([(m, s, n, "") for m, s, n in dev_mods])} }}
  lines {{ id: 2 name: "XLA Ops"
    {events([(m, s, n, "") for m, s, n in dev_ops])} }}
  {metadata("event_metadata", meta)} }}
planes {{ id: 2 name: "/host:CPU" {host_lines}
  {metadata("event_metadata", hmeta)} {metadata("stat_metadata", smeta)} }}
'''


def write_profile(trace_dir, **kw):
    from jax.profiler import ProfileData
    d = os.path.join(str(trace_dir), "plugins", "profile", "t0")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            xspace_text(**kw)))
    return str(trace_dir)


@pytest.fixture()
def written(tmp_path):
    host_spans.load.cache_clear()
    return host_spans.load(write_profile(tmp_path))


def test_load_reads_the_phases_and_the_device_window(written):
    spans = written
    assert spans["runs"] == [(s * MS, (s + n) * MS) for s, n in RUNS]
    assert spans["window"] == (10 * MS, 78 * MS)
    assert spans["gaps"] == [(30 * MS, 34 * MS), (54 * MS, 58 * MS)]
    names = [p["name"] for p in spans["phases"]]
    assert names.count("serve.execute") == 3 and "$some python frame" \
        not in names and len(names) == 16
    first = next(p for p in spans["phases"] if p["name"] == "serve.execute")
    assert first["stats"]["batch"] == 1 and first["stats"]["rows"] == 8
    assert first["stats"]["token_wait_us"] == 800000.0
    assert host_spans.clock_gap_ms(spans) == pytest.approx(0.1)


@pytest.mark.parametrize("skip", [0, 1])
def test_window_is_the_one_trace_reduce_cuts(tmp_path, written, skip):
    reduced = trace_reduce.reduce_trace(str(tmp_path), skip)
    spans = host_spans.skip_first(written, skip)
    w0, w1 = spans["window"]
    assert (w1 - w0) / 1e9 == pytest.approx(reduced["window_s"])
    assert len(spans["runs"]) == reduced["module_runs"]
    assert [((s - w0) / 1e9, (e - s) / 1e9) for s, e in spans["gaps"]] == \
        pytest.approx(reduced["gaps"])
    idle = sum(e - s for s, e in spans["gaps"]) / 1e9
    assert reduced["window_s"] - reduced["busy_s"] == pytest.approx(idle)


def test_a_gap_is_charged_to_the_innermost_phase(written):
    table = host_spans.gap_table(written)
    assert [(g0, n) for g0, n, _ in table] == [(30 * MS, 4 * MS),
                                               (54 * MS, 4 * MS)]
    for _, _, cover in table:
        # the read's tail, the reply, 0.3 ms of nothing, pad, dispatch;
        # the batcher's token wait covers it all and is charged nothing
        assert cover == pytest.approx({
            "tpu_model.readback": 0.1 * MS, "serve.execute": 0.1 * MS,
            "serve.respond": 1.5 * MS, host_spans.UNNAMED: 0.3 * MS,
            "tpu_model.pad": 1.5 * MS, "tpu_model.dispatch": 0.5 * MS})
    assert host_spans.idle_named_percent(written) == pytest.approx(92.5)


def test_what_it_offers_by_hand(written):
    means = host_spans.request_means_ms(written)
    assert means == pytest.approx({
        "queue_wait": 1.0, "collect_wait": 0.5 * (8 + 8 + 18) / 18,
        "token_wait": 100.0, "dispatch_wait": 200.0})
    batch = {"serve.execute": 22.2, "tpu_model.pad": 1.5,
             "tpu_model.dispatch": 0.5, "tpu_model.readback": 20.1,
             "serve.respond": 1.5}
    assert host_spans.batches(written) == [pytest.approx(b) for b in (
        batch, batch, {**batch, "serve.token_wait": 90.0})]
    assert host_spans.worker_host_ms(written) == pytest.approx(3.6)
    assert "named 92.5" in host_spans.report(written)


def _ctx(root, cell, module_runs=3):
    return {"cell": {"root": str(root), "name": cell},
            "trace": {"module_runs": module_runs}, "counters": {}}


def _reader(name):
    return run.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


WANT = {"serve_token_wait_ms": 100.0, "serve_dispatch_wait_ms": 200.0,
        "serve_worker_host_ms": 3.6, "device_idle_serve_named": 92.5,
        "device_idle_train_named": 92.5}


@pytest.mark.parametrize("name", NEW)
def test_new_reader_by_hand(written, name):
    assert _reader(name).read({"host_spans": written}) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_new_reader_reads_the_run_s_profile(tmp_path, name):
    """As run.py calls it: the profile lies under the checkout."""
    host_spans.load.cache_clear()
    cell = CELL_OF[name]
    write_profile(tmp_path / ".bench_trace" / cell)
    assert _reader(name).read(_ctx(tmp_path, cell)) == \
        pytest.approx(WANT[name])
    # ... and where the reduction skipped the first execution
    assert _reader(name).read(_ctx(tmp_path, cell, 2)) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_new_reader_with_nothing_to_read_returns_nothing(tmp_path, name):
    host_spans.load.cache_clear()
    cell = CELL_OF[name]
    read = _reader(name).read
    assert read({"cell": {}, "trace": None, "counters": {}}) is None
    assert read(_ctx(tmp_path, cell)) is None            # no trace kept
    # the parent's program: the profile holds no phase of the tuple
    write_profile(tmp_path / ".bench_trace" / cell, with_phases=False)
    assert read(_ctx(tmp_path, cell)) is None
    assert read(_ctx(tmp_path, cell, 9)) is None


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("shift,reads", [
    # a host clock ahead: the read seems to end long after the device
    (7.0, 7.1),
    # one behind: it seems to end before the execution it waited for,
    # and the last one that ended before it is a period away
    (-1.0, 23.1)], ids=["ahead", "behind"])
def test_clocks_that_do_not_agree_silence_the_reader(tmp_path, name,
                                                     shift, reads):
    host_spans.load.cache_clear()
    cell = CELL_OF[name]
    write_profile(tmp_path / ".bench_trace" / cell, shift_host_ms=shift)
    spans = host_spans.load(str(tmp_path / ".bench_trace" / cell))
    assert host_spans.clock_gap_ms(spans) == pytest.approx(reads)
    assert host_spans.for_run({"host_spans": spans}) is None
    assert _reader(name).read(_ctx(tmp_path, cell)) is None


def test_a_program_without_the_stage_clock_reads_nothing(tmp_path,
                                                         monkeypatch):
    host_spans.load.cache_clear()
    monkeypatch.setattr(host_spans, "stage_names", lambda: None)
    assert host_spans.load(write_profile(tmp_path)) is None
    host_spans.load.cache_clear()


def test_a_read_of_what_was_long_ready_is_no_clock_sample():
    """A log flush that did not wait ends at no particular time."""
    spans = {"runs": [(10 * MS, 30 * MS)], "window": (10 * MS, 30 * MS),
             "phases": [
                 {"name": "learner.flush_logs", "start": 17 * MS,
                  "end": 17.2 * MS, "stats": {}},
                 {"name": "learner.final_wait", "start": 18 * MS,
                  "end": 30.3 * MS, "stats": {}}]}
    assert host_spans.clock_gap_ms(spans) == pytest.approx(0.3)
    spans["phases"].pop()
    assert host_spans.clock_gap_ms(spans) is None


# ------------------------------------------ a profile recorded on the CPU

def _stub_engine():
    """A split echo scorer whose device stage is a read-back phase
    around a sleep, behind one worker and two tokens."""
    from mmlspark_tpu.core.trace import phase
    from mmlspark_tpu.serving.server import serve_model
    from mmlspark_tpu.stages.basic import Lambda

    def decode(table):
        return [json.loads(r["entity"].decode())["x"]
                for r in table["request"]]

    def execute(table, xs):
        with phase("tpu_model.pad", rows=len(xs)):
            time.sleep(0.01)
        with phase("tpu_model.readback", rows=len(xs)):
            time.sleep(0.02)
        return table.with_column("reply", [{"y": v * 2} for v in xs])

    lam = Lambda.apply(lambda t: execute(t, decode(t)))
    lam.prepare_batch = decode
    lam.execute_prepared = execute
    return serve_model(lam, port=0, batch_size=4, max_wait_ms=2.0,
                       workers=1, pipeline_depth=2, tracing=False)


def _post(addr, x):
    req = urllib.request.Request(
        addr, data=json.dumps({"x": x}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=20) as r:
        assert r.status == 200


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(phases of a profile recorded around 24 requests, the engine's
    histograms over the same requests)."""
    import jax
    from jax.profiler import ProfileData
    trace_dir = str(tmp_path_factory.mktemp("recorded"))
    engine = _stub_engine()
    try:
        _post(engine.source.address, 0)         # the session opens idle
        for h in engine.hists.values():
            h.reset()
        jax.profiler.start_trace(trace_dir)
        try:
            threads = [threading.Thread(target=_post, args=(
                engine.source.address, i)) for i in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.stop()
    data = ProfileData.from_file(trace_reduce.find_trace(trace_dir))
    return (host_spans.host_phases(data, host_spans.stage_names()),
            engine.hists)


def test_the_profile_gives_the_histograms_means(recorded):
    phases, hists = recorded
    means = host_spans.request_means_ms({"phases": phases})
    assert set(means) == set(host_spans.WAITS)
    for wait in host_spans.WAITS:
        snap = hists[wait + "_ms"].snapshot()
        assert snap["count"] == 24
        assert means[wait] == pytest.approx(snap["sum"] / 24, rel=0.01,
                                            abs=1e-3), wait
    # saturated: two tokens, one worker, 30 ms a batch
    assert means["dispatch_wait"] > 5 and means["token_wait"] > 5
    # every phase of a batch carries its number, the model's too
    executes = [p for p in phases if p["name"] == "serve.execute"]
    reads = [p for p in phases if p["name"] == "tpu_model.readback"]
    assert len(executes) == len(reads) == hists["batch_rows"].snapshot()[
        "count"]
    assert sorted(p["stats"]["batch"] for p in reads) == \
        sorted(p["stats"]["batch"] for p in executes)
    assert sum(p["stats"]["rows"] for p in executes) == 24


def test_a_sleep_inside_a_named_phase_is_charged_to_it(recorded):
    """The stub's device runs while ``tpu_model.readback`` sleeps: what
    lies between two of them is the device's gap, and the 10 ms sleep
    of ``tpu_model.pad`` is the most of it."""
    phases, _ = recorded
    runs = sorted((p["start"], p["end"]) for p in phases
                  if p["name"] == "tpu_model.readback")
    gaps = [(a[1], b[0]) for a, b in zip(runs, runs[1:])]
    spans = {"phases": phases, "runs": runs, "gaps": gaps,
             "window": (runs[0][0], runs[-1][1])}
    table = host_spans.gap_table(spans)
    assert len(table) == len(runs) - 1
    for _, length, cover in table:
        assert max(cover, key=cover.get) == "tpu_model.pad"
        assert cover["tpu_model.pad"] <= length
        assert cover["tpu_model.pad"] >= 9 * MS     # by the profiler's clock
        assert "serve.token_wait" not in cover
    # reply, pad and the worker's own code are all under a phase (what
    # is not: the worker's turn of its loop, and its waits for the
    # interpreter's lock there)
    assert host_spans.idle_named_percent(spans) > 60
    assert host_spans.clock_gap_ms(spans) == 0.0
