"""benchmark/occupancy.py and the three readers built on it: CPU only.

A profile written by hand (a device plane and a host plane with the
worker's and the batcher's lines, as text) checks the split of each
device gap into pending and starved, the charge of the pending part to
the batcher's and to the worker's innermost phase, the fill, and that a
profile of the parent's program, a profile whose clocks disagree and no
profile at all silence every reader.

Four of the checks here are those of ``test_mellum2_cell.py`` tests that
pin the per-layer entries each cell reports and the end of the list, and
are marked expected failures from tests/conftest.py since ISSUE 38
appended three entries that every serve cell reports: see PERF.md, Open
questions 0i. Each repeat holds every assertion of the test it stands
for and changes one thing, marked.
"""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load(os.path.join(BENCH_DIR, "run.py"), "bench_run_occupancy")
import host_spans     # noqa: E402  (run.py put benchmark/ on the path)
import occupancy      # noqa: E402

MS = 1_000_000        # nanoseconds
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW = ["device_idle_serve_pending", "device_idle_serve_starved",
       "serve_bucket_fill"]
SERVE_CELLS = ["gpt2xl_serve_steady", "glm52_score_8k_steady",
               "lfm2_score_8k_steady", "mellum2_score_16k_steady"]
CELL = "mellum2_score_16k_steady"


def reader(name):
    return run.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


# ------------------------------------------------- a profile written by hand
#
# Three batches of 20 ms on the device at 10, 40 and 70 ms, and a short
# program of another name at 34 ms that cuts the first idle stretch in
# two. Batch 1's requests came before the profile's window. Batch 2's
# oldest request arrives at 36 ms into an empty engine: the worker lies
# in ``serve.idle`` and the batcher in ``serve.collect`` until then.
# Batch 3's arrives at 45 ms, while batch 2 runs, and the gate holds it
# (``serve.token_wait``) until 66 ms, 6 ms after the device went idle.

RUNS = [(10.0, 20.0), (40.0, 20.0), (70.0, 20.0)]
OTHER = (34.0, 0.02)
WORKER, BATCHER = 7, 8


def worker_phases(program="change"):
    def stats(k, rows, oldest_ms=None):
        st = {"batch": k, "rows": rows}
        if program == "change":
            st["bucket"] = 4
        return st, ({**{key: v for key, v in st.items() if key != "bucket"},
                     "queue_wait_us": 1000.0 * rows,
                     "collect_wait_us": 0.0, "token_wait_us": 0.0,
                     "dispatch_wait_us": 100.0 * rows,
                     **({"oldest_wait_us": oldest_ms * 1000.0}
                        if program == "change" else {})})
    out = []
    for k, rows, ex0, pad0, disp0, oldest in (
            (1, 1, 8.0, 8.1, 9.0, 5.0),
            (2, 2, 38.1, 38.2, 39.0, 2.1),      # arrived at 36.0
            (3, 4, 67.1, 67.2, 69.0, 22.1)):    # arrived at 45.0
        run0 = RUNS[k - 1][0]
        st, ex = stats(k, rows, oldest)
        out += [("serve.execute", ex0, run0 + 20.2 - ex0, ex),
                ("tpu_model.pad", pad0, disp0 - pad0, st),
                ("tpu_model.dispatch", disp0, 0.5, st),
                ("tpu_model.readback", run0, 20.1, st),
                ("serve.respond", run0 + 20.2, 1.8, st)]
    if program == "change":
        out += [("serve.idle", 32.0, 6.1, {}), ("serve.idle", 62.5, 4.6, {})]
    return out


def batcher_phases(program="change"):
    out = [("serve.token_wait", 37.0, 0.2, {"batch": 2, "rows": 2}),
           ("serve.decode", 37.2, 0.8, {"batch": 2, "rows": 2}),
           ("serve.token_wait", 46.0, 20.0, {"batch": 3, "rows": 4}),
           ("serve.decode", 66.0, 1.0, {"batch": 3, "rows": 4})]
    if program == "change":
        out += [("serve.collect", 30.5, 6.5, {}),
                ("serve.collect", 38.0, 8.0, {}),
                ("serve.collect", 67.0, 23.0, {})]
    return out


def xspace_text(program="change", shift_host_ms=0.0):
    def ps(ms):
        return int(round(ms * 1e9))
    meta, hmeta, smeta = {}, {}, {}

    def mid(table, name):
        return table.setdefault(name, len(table) + 1)
    mods, ops = [], []
    for start, length in RUNS:
        mods.append((mid(meta, "jit_tpu_model_forward(77)"), start, length))
        ops.append((mid(meta, "%fusion.1 = f32[8] fusion(%p)"), start,
                    length))
    mods.append((mid(meta, "jit_convert(5)"), *OTHER))
    ops.append((mid(meta, "%convert.3 = f32[8] convert(%q)"), *OTHER))

    def stat_text(stats):
        out = []
        for key, val in stats.items():
            kind = "double_value" if isinstance(val, float) else "int64_value"
            out.append(f"stats {{ metadata_id: {mid(smeta, key)} "
                       f"{kind}: {val} }}")
        return " ".join(out)

    def events(rows):
        return " ".join(
            f"events {{ metadata_id: {m} offset_ps: {ps(s)} "
            f"duration_ps: {ps(n)} {st} }}" for m, s, n, st in rows)

    def host_line(line, phases):
        return f'lines {{ id: {line} name: "thread-{line}" ' + events(
            [(mid(hmeta, name), start + shift_host_ms, length,
              stat_text(stats))
             for name, start, length, stats in phases]) + " }"

    def metadata(kind, table):
        return " ".join(f'{kind} {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                        for n, i in table.items())
    host = host_line(WORKER, worker_phases(program)) + " " + \
        host_line(BATCHER, batcher_phases(program))
    return f'''
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules"
    {events([(m, s, n, "") for m, s, n in mods])} }}
  lines {{ id: 2 name: "XLA Ops"
    {events([(m, s, n, "") for m, s, n in ops])} }}
  {metadata("event_metadata", meta)} }}
planes {{ id: 2 name: "/host:CPU" {host}
  {metadata("event_metadata", hmeta)} {metadata("stat_metadata", smeta)} }}
'''


def write_profile(trace_dir, **kw):
    from jax.profiler import ProfileData
    d = os.path.join(str(trace_dir), "plugins", "profile", "t0")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            xspace_text(**kw)))
    host_spans.load.cache_clear()
    occupancy.arrival_phases.cache_clear()
    return str(trace_dir)


def _ctx(root, cell=CELL, module_runs=3):
    return {"cell": {"root": str(root), "name": cell},
            "trace": {"module_runs": module_runs}, "counters": {}}


@pytest.fixture()
def written(tmp_path):
    write_profile(tmp_path / ".bench_trace" / CELL)
    return occupancy.for_run(_ctx(tmp_path))


def ms(cover):
    return {name: t / MS for name, t in cover.items()}


def test_for_run_adds_the_arrival_waits_of_the_same_profile(written):
    assert written["window"] == (10 * MS, 90 * MS)
    assert [(s, e) for s, e in written["gaps"]] == [
        (30 * MS, 34 * MS), (34.02 * MS, 40 * MS), (60 * MS, 70 * MS)]
    assert "serve.collect" not in {p["name"] for p in written["phases"]}
    assert "serve.idle" in {p["name"] for p in written["phases"]}
    assert [(p["name"], p["start"]) for p in written["arrivals"]] == [
        ("serve.collect", 30.5 * MS), ("serve.collect", 38 * MS),
        ("serve.collect", 67 * MS)]
    # both reads number the host plane's lines alike
    batcher = {p["thread"] for p in written["phases"]
               if p["name"] == "serve.decode"}
    assert {p["thread"] for p in written["arrivals"]} == batcher


def test_a_batch_is_pending_from_its_oldest_arrival_to_its_dispatch(written):
    # ... and in the batcher's hands until 0.1 ms (``dispatch_wait_us``
    # over ``rows``) before the worker takes it
    assert occupancy.pending_intervals(written) == [
        pytest.approx((3.0 * MS, 7.9 * MS, 9.5 * MS)),
        pytest.approx((36.0 * MS, 38.0 * MS, 39.5 * MS)),
        pytest.approx((45.0 * MS, 67.0 * MS, 69.5 * MS))]


def test_each_gap_is_split_into_pending_and_starved(written):
    got = occupancy.split(written)
    first, second, third = got["gaps"]
    # wholly before any request: the engine is empty until 36 ms
    assert first[:3] == (30 * MS, 4 * MS, 0.0)
    assert first[3] == first[4] == {}
    # starved until the request arrives, pending until its batch is
    # dispatched, starved again after it: nothing else waits
    assert second[:2] == (34.02 * MS, pytest.approx(5.98 * MS))
    assert second[2] == pytest.approx(3.5 * MS)
    # in the batcher's hands until it is in the dispatch queue at 38 ms
    # (the batcher's next collect, from 38 ms on, is charged nothing) ...
    assert ms(second[3]) == pytest.approx({
        "serve.collect": 1.0, "serve.token_wait": 0.2, "serve.decode": 0.8})
    # ... and the worker's from then on (its idle wait until 38 ms is not)
    assert ms(second[4]) == pytest.approx({
        "serve.idle": 0.1, "serve.execute": 0.1, "tpu_model.pad": 0.8,
        "tpu_model.dispatch": 0.5})
    # under a request that waited since 45 ms: pending from the first
    # idle instant, and the gate's 6 ms are the batcher's token wait;
    # what the worker did meanwhile (the reply, its idle wait) held
    # nothing up
    assert third[2] == pytest.approx(9.5 * MS)
    assert ms(third[3]) == pytest.approx({
        "serve.token_wait": 6.0, "serve.decode": 1.0})
    assert ms(third[4]) == pytest.approx({
        "serve.idle": 0.1, "serve.execute": 0.1, "tpu_model.pad": 1.8,
        "tpu_model.dispatch": 0.5})
    # pending and starved are the idle time of the gaps over the floor
    assert got["idle_ns"] == pytest.approx(19.98 * MS)
    assert got["pending_ns"] == pytest.approx(13.0 * MS)
    assert got["starved_ns"] == pytest.approx(6.98 * MS)
    assert got["pending_ns"] + got["starved_ns"] == pytest.approx(
        sum(e - s for s, e in written["gaps"]))
    assert got["window_ns"] == 80 * MS
    assert sum(got["by_batcher"].values()) + sum(got["by_worker"].values()) \
        == pytest.approx(got["pending_ns"])
    assert ms(got["by_batcher"]) == pytest.approx({
        "serve.collect": 1.0, "serve.token_wait": 6.2, "serve.decode": 1.8})
    assert sum(got["by_worker"].values()) == pytest.approx(4.0 * MS)


def test_a_retried_row_and_a_gap_under_the_floor_are_left_out(written):
    retry = {"name": "serve.execute", "start": 33 * MS, "end": 33.5 * MS,
             "thread": WORKER, "stats": {"rows": 1, "retry": 1, "batch": 9,
                                         "oldest_wait_us": 9000.0}}
    spans = {**written, "phases": written["phases"] + [retry],
             "gaps": written["gaps"] + [(90 * MS, 90.4 * MS)]}
    assert len(occupancy.pending_intervals(spans)) == 3
    got = occupancy.split(spans)
    assert got["pending_ns"] == pytest.approx(13.0 * MS)
    assert got["idle_ns"] == pytest.approx(19.98 * MS)


def test_fill_by_hand(written):
    # 1, 2 and 4 rows in buckets of 4: the first dispatch ends 0.5 ms
    # before the window that starts with its execution
    assert occupancy.fill_percent(written) == pytest.approx(100 * 7 / 12)
    assert occupancy.fill_percent(host_spans.skip_first(written, 1)) == \
        pytest.approx(100 * 6 / 8)


WANT = {"device_idle_serve_pending": 100 * 13.0 / 80,
        "device_idle_serve_starved": 100 * 6.98 / 80,
        "serve_bucket_fill": 100 * 7 / 12}


@pytest.mark.parametrize("name", NEW)
def test_new_reader_reads_the_run_s_profile(tmp_path, name):
    """As run.py calls it: the profile lies under the checkout."""
    write_profile(tmp_path / ".bench_trace" / CELL)
    assert reader(name).read(_ctx(tmp_path)) == pytest.approx(WANT[name])
    # ... and spans that a test put into the context
    spans = occupancy.for_run(_ctx(tmp_path))
    assert reader(name).read({"host_spans": spans}) == \
        pytest.approx(WANT[name])


def test_pending_and_starved_add_up_to_the_device_s_idle_share(tmp_path):
    """``device_idle_serve`` less the gaps under the floor (none here)."""
    import trace_reduce
    write_profile(tmp_path / ".bench_trace" / CELL)
    reduced = trace_reduce.reduce_trace(
        str(tmp_path / ".bench_trace" / CELL), 0)
    idle = trace_reduce.idle_percent({"trace": reduced})
    got = [reader(n).read(_ctx(tmp_path)) for n in NEW[:2]]
    assert sum(got) == pytest.approx(idle) == pytest.approx(100 * 19.98 / 80)


@pytest.mark.parametrize("name", NEW)
def test_new_reader_with_nothing_to_read_returns_nothing(tmp_path, name):
    read = reader(name).read
    assert read({"cell": {}, "trace": None, "counters": {}}) is None
    assert read(_ctx(tmp_path)) is None                  # no trace kept
    # the parent's program under these files: no ``oldest_wait_us``, no
    # ``bucket``, no ``serve.idle`` and no ``serve.collect``
    write_profile(tmp_path / ".bench_trace" / CELL, program="parent")
    assert host_spans.for_run(_ctx(tmp_path)) is not None
    assert read(_ctx(tmp_path)) is None
    assert read(_ctx(tmp_path, module_runs=9)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_arrival_waits_reads_nothing(tmp_path, name,
                                                       monkeypatch):
    write_profile(tmp_path / ".bench_trace" / CELL)
    monkeypatch.setattr(occupancy, "arrival_names", lambda: None)
    assert reader(name).read(_ctx(tmp_path)) is None
    occupancy.arrival_phases.cache_clear()


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("shift", [7.0, -1.0], ids=["ahead", "behind"])
def test_clocks_that_do_not_agree_silence_the_reader(tmp_path, name, shift):
    write_profile(tmp_path / ".bench_trace" / CELL, shift_host_ms=shift)
    assert host_spans.load(str(tmp_path / ".bench_trace" / CELL))
    assert reader(name).read(_ctx(tmp_path)) is None


def test_the_report_prints_the_split(tmp_path, written, capsys):
    text = occupancy.report(written)
    assert "pending 13.000 ms = 16.250 %" in text
    assert "starved 6.980 ms = 8.725 %" in text
    assert "by its phase, ms: serve.token_wait 6.200" in text
    assert "by the worker's phase, ms: tpu_model.pad 2.600" in text
    assert "gap at 0.050 s, 10.000 ms: starved 0.500, pending 9.500" in text
    # as a command, on the directory run.py would have kept
    assert occupancy.main(
        ["occupancy.py", str(tmp_path / ".bench_trace" / CELL)]) == 0
    out = capsys.readouterr().out
    assert "clock check 0." in out and "bucket fill 58.33" in out
    write_profile(tmp_path / ".bench_trace" / CELL, program="parent")
    assert "does not say" in occupancy.report(
        {**host_spans.load(str(tmp_path / ".bench_trace" / CELL)),
         "arrivals": []})


def test_the_program_s_names_are_the_ones_read():
    """The helper finds things by these names and stats: the tuples of
    the stage clock hold them, and the program's sources write them."""
    from mmlspark_tpu.core.trace import ARRIVAL_WAITS, HOST_PHASES
    assert set(occupancy.BATCHER) <= set(HOST_PHASES) | set(ARRIVAL_WAITS)
    assert occupancy.arrival_names() == ("serve.collect",)
    assert "serve.idle" in host_spans.stage_names()
    assert "serve.collect" not in host_spans.stage_names()
    server = open(os.path.join(ROOT, "mmlspark_tpu", "serving",
                               "server.py")).read()
    model = open(os.path.join(ROOT, "mmlspark_tpu", "models",
                              "tpu_model.py")).read()
    assert '"oldest_wait"' in server and "bucket=bucket" in model


# ------------------------------- BENCHMARK.json as it stands after ISSUE 38

MELLUM2_METRICS = ["mellum2_forward_mfu", "mellum2_experts_roofline",
                   "mellum2_flash_roofline", "swa_flash_roofline",
                   "swa_attend_share"]
LFM2_METRICS = ["lfm2_forward_mfu", "lfm2_experts_roofline",
                "lfm2_flash_roofline", "moe_dispatch_share",
                "short_conv_gate_share"]
GLM_METRICS = ["glm_forward_mfu", "dsa_attend_roofline", "dsa_select_share",
               "moe_experts_roofline", "moe_load_max_over_mean"]
# test_mellum2_cell.py's list, every name of it ...
GENERIC = ["serve_queue_wait_ms", "scorer_device_wait_ms",
           "device_idle_serve", "serve_token_wait_ms",
           "serve_dispatch_wait_ms", "serve_worker_host_ms",
           "device_idle_serve_named", "moe_load_max_over_mean"]
LFM2_CELL = "lfm2_score_8k_steady"
GLM_CELL = "glm52_score_8k_steady"


def test_the_three_entries_as_issue_38_asks():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [(entries[n]["better"], entries[n]["layer"]) for n in NEW] == [
        ("lower", "serving"), ("lower", "device"), ("higher", "scorer")]
    for name in NEW:
        m = entries[name]
        assert m == {"name": name, "unit": "%", "better": m["better"],
                     "source": "program_span", "layer": m["layer"],
                     "moves": "serve_p95_ms", "workloads": SERVE_CELLS}
    for cell in SERVE_CELLS:
        loaded = run.load_cell(ROOT, cell)
        assert [m["name"] for m in loaded["per_layer"]][-3:] == NEW
    train = run.load_cell(ROOT, "gpt2m_train")
    assert not set(NEW) & {m["name"] for m in train["per_layer"]}


def test_the_mellum2_cell_and_what_it_reports():
    """``test_mellum2_cell.py::test_the_cell_and_what_it_reports`` as the
    benchmark stands, every assertion of it, changed in one place: the
    cell reports ISSUE 38's three readers beside the generic ones."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "mellum2-12b-a2.5b-stage", "chips": 1,
                    "traffic": "poisson_steady_16k_mellum2"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(GENERIC + ["moe_dispatch_share"] + MELLUM2_METRICS
               + NEW)                           # the one change
    for m in BENCH["per_layer"]:
        # the other families' step readers stay theirs
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline",
                         "glm_forward_mfu", "dsa_attend_roofline",
                         "dsa_select_share", "moe_experts_roofline",
                         "lfm2_forward_mfu", "lfm2_experts_roofline",
                         "lfm2_flash_roofline", "short_conv_gate_share"):
            assert CELL not in m["workloads"]
        if m["name"] in MELLUM2_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC + ["moe_dispatch_share"]:
            assert m["workloads"][-1] == CELL
    layer_of = {m["name"]: (m["layer"], m["better"])
                for m in BENCH["per_layer"]}
    assert [layer_of[n] for n in MELLUM2_METRICS] == [
        ("model step", "higher"), ("kernels", "higher"),
        ("kernels", "higher"), ("kernels", "higher"),
        ("model step", "lower")]
    mix = loaded["traffic_file"]
    assert mix["driver"] == "serve_mellum2" and mix["batch_size"] == 2
    assert (mix["max_wait_ms"], mix["workers"], mix["warm_requests"],
            mix["sample_requests"], mix["trace_window_s"],
            mix["reply_timeout_s"]) == (5.0, 1, 4, 8, 12, 120)
    assert isinstance(mix["arrivals"]["gap_seed"], int)
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * mix["knee_per_s"], rel=0.02)
    assert set(mix["limits"]) == {"class_gap", "logit_rel_l2", "route_gap",
                                  "route_miss", "swa_rel_l2",
                                  "full_rel_l2", "served_not_model",
                                  "unanswered"}
    assert set(mix["limits_why"]) == set(mix["limits"])
    assert all(len(why) > 20 for why in mix["limits_why"].values())
    # every sampled row decides: none is set aside, so no margin
    assert "near_tie_margin" not in mix
    assert 0 < mix["limits"]["route_gap"] < 0.05
    assert 0 < mix["limits"]["route_miss"] < 0.2
    assert 0 < mix["limits"]["logit_rel_l2"] < 0.1
    # layer 0 is the window's arithmetic alone; layer 3 lies after three
    # expert layers
    assert 0 < mix["limits"]["swa_rel_l2"] \
        <= mix["limits"]["full_rel_l2"] < 0.15
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]
    assert mix["knee_why"] and mix["who"] and mix["what"]


def test_the_lfm2_cell_reports_what_it_did():
    """``test_mellum2_cell.py::test_the_lfm2_cell_reports_what_it_did``
    as the benchmark stands, every assertion of it, changed in one
    place: the cell reports ISSUE 38's three readers beside the generic
    ones."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == LFM2_CELL)
    assert cell == {**cell, "config": "lfm2-24b-a2b-stage", "chips": 1,
                    "traffic": "poisson_steady_8k_lfm2"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, LFM2_CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in loaded["per_layer"]) == \
        sorted(GENERIC + LFM2_METRICS + NEW)    # the one change
    for m in BENCH["per_layer"]:
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline",
                         "glm_forward_mfu", "dsa_attend_roofline",
                         "dsa_select_share", "moe_experts_roofline"):
            assert LFM2_CELL not in m["workloads"]
        if m["name"] in LFM2_METRICS:
            assert m["workloads"] == (
                [LFM2_CELL, CELL] if m["name"] == "moe_dispatch_share"
                else [LFM2_CELL])
            assert m["moves"] == "serve_p95_ms"
            assert m["unit"] == "%" and m["source"] == "device_trace"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        if m["name"] in GENERIC:
            assert m["workloads"][-2:] == [LFM2_CELL, CELL]
    layer_of = {m["name"]: (m["layer"], m["better"])
                for m in BENCH["per_layer"]}
    assert [layer_of[n] for n in LFM2_METRICS] == [
        ("model step", "higher"), ("kernels", "higher"),
        ("kernels", "higher"), ("experts", "lower"),
        ("model step", "lower")]
    mix = loaded["traffic_file"]
    assert mix["driver"] == "serve_hybrid_lm" and mix["batch_size"] == 4
    assert (mix["max_wait_ms"], mix["workers"], mix["warm_requests"],
            mix["sample_requests"], mix["trace_window_s"]) == (
        5.0, 1, 4, 8, 12)
    assert isinstance(mix["arrivals"]["gap_seed"], int)
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * mix["knee_per_s"], rel=0.02)
    assert set(mix["limits"]) == {"class_gap", "logit_rel_l2", "route_gap",
                                  "route_miss", "attn_rel_l2",
                                  "attn_late_rel_l2", "served_not_model",
                                  "unanswered"}
    assert set(mix["limits_why"]) == set(mix["limits"])
    assert all(len(why) > 20 for why in mix["limits_why"].values())
    assert "near_tie_margin" not in mix
    assert 0 < mix["limits"]["route_gap"] < 0.05
    assert 0 < mix["limits"]["route_miss"] < 0.1
    assert mix["limits"]["class_gap"] == 0.05
    assert 0 < mix["limits"]["logit_rel_l2"] < 0.1
    assert 0 < mix["limits"]["attn_rel_l2"] \
        < mix["limits"]["attn_late_rel_l2"] < 0.15
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]
    assert mix["knee_why"] and mix["who"] and mix["what"]


def test_the_glm_cell_reports_what_it_did():
    """``test_mellum2_cell.py::test_the_glm_cell_reports_what_it_did`` as
    the benchmark stands, every assertion of it, changed in one place:
    ISSUE 38's three readers follow the cell's own at the list's end."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == GLM_CELL)
    assert cell == {**cell, "config": "glm-5.2-ep16", "chips": 1,
                    "traffic": "poisson_steady_8k"}
    assert 1 <= len(cell["why"]) <= 200
    loaded = run.load_cell(ROOT, GLM_CELL)
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "serve_p50_ms", "serve_p95_ms", "setup_s"]
    assert [m["name"] for m in loaded["per_layer"]] == \
        GENERIC[:-1] + GLM_METRICS + NEW        # the one change
    # the GPT-2 step's readers stay GPT-2's
    for m in BENCH["per_layer"]:
        if m["name"] in ("serve_forward_mfu", "flash_serve_roofline"):
            assert GLM_CELL not in m["workloads"]
        if m["name"] in GLM_METRICS:
            assert m["workloads"] == (
                [GLM_CELL, LFM2_CELL, CELL]
                if m["name"] == "moe_load_max_over_mean"
                else [GLM_CELL])
            assert m["moves"] == "serve_p95_ms"
            assert set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    mix = loaded["traffic_file"]
    assert mix["driver"] == "serve_lm" and mix["batch_size"] == 4
    assert (mix["max_wait_ms"], mix["workers"], mix["warm_requests"],
            mix["sample_requests"], mix["reply_timeout_s"]) == (
        5.0, 1, 4, 8, 120)
    assert mix["arrivals"]["gap_seed"] == 20260930
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * mix["knee_per_s"], rel=0.02)
    assert set(mix["limits"]) == {"class_gap", "logit_rel_l2",
                                  "near_tie_rows", "select_miss",
                                  "served_not_model", "unanswered"}
    assert 0 < mix["limits"]["select_miss"] < 1
    assert 0 <= mix["limits"]["near_tie_rows"] <= mix["sample_requests"] - 2
    assert 0 < mix["near_tie_margin"] < 0.01
    assert mix["limits"]["served_not_model"] == 0 == \
        mix["limits"]["unanswered"]


def test_benchmark_json_is_still_well_formed():
    """``test_mellum2_cell.py::test_benchmark_json_is_still_well_formed``
    as the benchmark stands, every assertion of it, changed in one
    place: the list of per-layer entries has ISSUE 38's three at its end,
    PR 36's and PR 34's before them."""
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells == ["gpt2m_train", "gpt2xl_serve_steady", GLM_CELL,
                     LFM2_CELL, CELL]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for m in BENCH["per_layer"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py")), m["name"]
        assert set(m["workloads"]) <= set(cells)
    # ... and what PR 38 may not have moved
    assert [c["name"] for c in BENCH["configs"]] == [
        "gpt2-medium", "gpt2-xl", "glm-5.2-ep16", "lfm2-24b-a2b-stage",
        "mellum2-12b-a2.5b-stage"]
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    assert BENCH["run_seconds"] == 40
    assert [m["name"] for m in BENCH["per_layer"]][-13:] == \
        LFM2_METRICS + MELLUM2_METRICS + NEW    # the one change
    bounds = {m["name"]: m["bound"] for m in BENCH["end_to_end"]}
    assert bounds == {"train_tokens_per_s": 0.01, "serve_p50_ms": 0.03,
                      "serve_p95_ms": 0.07, "setup_s": 0.1}
