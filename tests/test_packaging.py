"""Installable-artifact tests: wheel build, clean-venv install, CLI.

The reference ships installable artifacts as a first-class output
(ref: src/project/build.scala:86-97 — sbt packages/publishes every
module; src/codegen/src/main/scala/CodeGen.scala:44-92 zips the
PySpark and R packages). The parity bar here: `pip wheel` from the
checkout produces a wheel (native .so compiled in when the toolchain
exists), the wheel installs into a CLEAN venv, and the installed
package — imported far from the repo — runs a pipeline, loads the
native library, and exposes the console scripts.

The wheel build + venv install run ONCE per session (session-scoped
fixture); the CLI tests drive the installed console scripts, which is
also the manifest-consumer contract (VERDICT r4 #8)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def installed_venv(tmp_path_factory):
    """Build the wheel, create a clean venv (system-site so the baked-in
    jax/numpy resolve without network), pip-install the wheel."""
    root = tmp_path_factory.mktemp("pkg")
    dist = root / "dist"
    r = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", REPO, "-w", str(dist),
         "--no-deps", "--no-build-isolation", "-q"],
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"wheel build failed:\n{r.stderr[-3000:]}"
    wheels = list(dist.glob("mmlspark_tpu-*.whl"))
    assert len(wheels) == 1, list(dist.iterdir())

    venv = root / "venv"
    subprocess.run(
        [sys.executable, "-m", "venv", str(venv)],
        check=True, timeout=300)
    py = venv / "bin" / "python"
    # the image's deps (jax, numpy, ...) live in the PARENT environment
    # (itself a virtualenv, so --system-site-packages would skip it);
    # expose them to the clean venv via a .pth — our package itself is
    # still imported only from the wheel install
    parent_sites = [p for p in sys.path if p.endswith("site-packages")]
    site_dir = subprocess.run(
        [str(py), "-c",
         "import sysconfig; print(sysconfig.get_paths()['purelib'])"],
        capture_output=True, text=True, check=True,
        timeout=60).stdout.strip()
    with open(os.path.join(site_dir, "parent-deps.pth"), "w") as f:
        f.write("\n".join(parent_sites) + "\n")
    r = subprocess.run(
        [str(py), "-m", "pip", "install", "--no-deps", "-q",
         str(wheels[0])],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"pip install failed:\n{r.stderr[-3000:]}"
    return venv, wheels[0]


def _run_in_venv(venv, code=None, argv=None, cwd=None, timeout=300):
    """Run python-code or a console script inside the venv, from a
    NON-repo cwd so imports cannot leak from the checkout."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"   # keep CLI tests off the chip
    if code is not None:
        cmd = [str(venv / "bin" / "python"), "-c", code]
    else:
        cmd = [str(venv / "bin" / argv[0])] + list(argv[1:])
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout,
        cwd=cwd or str(venv), env=env)


def test_wheel_contains_native_sources(installed_venv):
    """The wheel must carry the native sources (self-provision path);
    the .so itself is present when the build toolchain compiled it."""
    import zipfile
    _venv, wheel = installed_venv
    names = zipfile.ZipFile(wheel).namelist()
    assert any(n.endswith("native/src/mml_native.cpp") for n in names)
    assert any(n.endswith("native/CMakeLists.txt") for n in names)
    # when the image has the build toolchain, the compiled library must
    # be inside the wheel, not left behind in the checkout; toolchainless
    # images ship sources only (loader falls back to numpy)
    import shutil
    if shutil.which("cmake") is not None:
        assert any(n.endswith("native/lib/libmml_native.so")
                   for n in names), "native .so missing from wheel"


def test_installed_package_runs_pipeline(installed_venv):
    """Import from the INSTALLED location (repo not on sys.path), fit
    and apply a small pipeline, confirm the native lib binds."""
    venv, _ = installed_venv
    code = """
import jax
jax.config.update("jax_platforms", "cpu")
import os, sys
assert not any(p.startswith("%s") for p in sys.path if p), sys.path
import numpy as np
import mmlspark_tpu as mt
assert "%s" not in os.path.abspath(mt.__file__)
from mmlspark_tpu.stages.dataprep import CleanMissingData
t = mt.DataTable({"f0": np.asarray([1.0, np.nan, 3.0], np.float32),
                  "label": np.asarray([0, 1, 0], np.int32)})
m = CleanMissingData(inputCols=["f0"], cleaningMode="Mean").fit(t)
out = m.transform(t)
assert not np.isnan(np.asarray(out["f0"])).any()
from mmlspark_tpu.native.loader import get_lib
lib = get_lib()
print("native:", "loaded" if lib is not None else "fallback")
print("OK", mt.__file__)
""" % (REPO, REPO)
    r = _run_in_venv(venv, code=code)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"
    assert "OK" in r.stdout
    # when the wheel carries the .so, the installed copy must bind it;
    # toolchainless images legitimately run the numpy fallback
    import shutil
    if shutil.which("cmake") is not None:
        assert "native: loaded" in r.stdout, r.stdout
    else:
        assert "native:" in r.stdout, r.stdout


def test_console_script_stages_and_describe(installed_venv):
    venv, _ = installed_venv
    r = _run_in_venv(venv, argv=["mmlspark-tpu", "stages"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "TPUBoostClassifier" in r.stdout
    r = _run_in_venv(venv, argv=["mmlspark-tpu", "describe",
                                 "TPUBoostClassifier"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "numLeaves" in r.stdout


def test_console_script_codegen(installed_venv, tmp_path):
    venv, _ = installed_venv
    out = tmp_path / "gen"
    r = _run_in_venv(venv, argv=["mmlspark-tpu-codegen", str(out)])
    assert r.returncode == 0, r.stderr[-2000:]
    counts = json.loads(r.stdout.strip().splitlines()[-1])
    assert counts["stages"] > 50
    assert (out / "manifest.json").exists()


def test_cli_run_score_roundtrip(installed_venv, tmp_path):
    """Train + save + score the flagship pipeline shape from a JSON
    spec and CSV data — no Python written by the user."""
    venv, _ = installed_venv
    rng = np.random.default_rng(0)
    n = 400
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.int32)
    csv_path = tmp_path / "train.csv"
    with open(csv_path, "w") as f:
        f.write("f0,f1,f2,f3,label\n")
        for i in range(n):
            f.write(",".join(str(v) for v in x[i]) + f",{y[i]}\n")
    spec = {
        "pipeline": [
            {"stage": "FastVectorAssembler",
             "params": {"inputCols": ["f0", "f1", "f2", "f3"],
                        "outputCol": "features"}},
            {"stage": "TPUBoostClassifier",
             "params": {"featuresCol": "features", "labelCol": "label",
                        "numIterations": 5, "numLeaves": 7}},
        ]
    }
    spec_path = tmp_path / "pipe.json"
    spec_path.write_text(json.dumps(spec))
    model_dir = tmp_path / "model"
    r = _run_in_venv(venv, argv=[
        "mmlspark-tpu", "run", str(spec_path), "--data", str(csv_path),
        "--save", str(model_dir)], timeout=600)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"
    assert model_dir.exists()

    out_csv = tmp_path / "scored.csv"
    r = _run_in_venv(venv, argv=[
        "mmlspark-tpu", "score", "--model", str(model_dir),
        "--data", str(csv_path), "--out", str(out_csv)], timeout=600)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"
    with open(out_csv) as f:
        header = f.readline().strip().split(",")
    assert "prediction" in header
    # sanity: the model actually learned the synthetic rule
    import csv as _csv
    with open(out_csv) as f:
        rows = list(_csv.DictReader(f))
    preds = np.asarray([float(r["prediction"]) for r in rows])
    assert (preds == y[:len(preds)]).mean() > 0.9


def test_cli_serve_scores_over_http(installed_venv, tmp_path):
    """`mmlspark-tpu serve` on a saved model answers HTTP scoring
    requests — the zero-Python serving path."""
    import time
    import urllib.request
    venv, _ = installed_venv
    # build + save a tiny model through the CLI itself
    rng = np.random.default_rng(1)
    n = 200
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    csv_path = tmp_path / "t.csv"
    with open(csv_path, "w") as f:
        f.write("a,b,c,label\n")
        for i in range(n):
            f.write(",".join(str(v) for v in x[i]) + f",{y[i]}\n")
    spec_path = tmp_path / "p.json"
    spec_path.write_text(json.dumps({"pipeline": [
        {"stage": "FastVectorAssembler",
         "params": {"inputCols": ["a", "b", "c"],
                    "outputCol": "features"}},
        {"stage": "TPUBoostClassifier",
         "params": {"featuresCol": "features", "labelCol": "label",
                    "numIterations": 3, "numLeaves": 5}},
    ]}))
    model_dir = tmp_path / "m"
    r = _run_in_venv(venv, argv=[
        "mmlspark-tpu", "run", str(spec_path), "--data", str(csv_path),
        "--save", str(model_dir)], timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]

    port = 18931
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [str(venv / "bin" / "mmlspark-tpu"), "serve",
         "--model", str(model_dir), "--port", str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=str(venv), env=env)
    try:
        deadline = time.time() + 120
        body = json.dumps(
            {"a": 1.5, "b": 0.0, "c": 0.0, "label": 0}).encode()
        last = None
        while time.time() < deadline:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/", data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as resp:
                    reply = json.loads(resp.read())
                break
            except OSError as e:
                last = e
                time.sleep(1.0)
        else:
            raise AssertionError(f"server never answered: {last}")
        # the engine replies with the reply column's VALUE per row
        assert float(reply) == 1.0, reply
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_cli_import_onnx_then_score(installed_venv, tmp_path):
    """ONNX file -> saved stage -> scored table, all through console
    scripts from the installed wheel (zero Python written)."""
    from tests import onnx_writer as ow
    venv, _ = installed_venv
    rng = np.random.default_rng(5)
    w = rng.normal(scale=0.3, size=(4, 3)).astype(np.float32)
    b = rng.normal(size=3).astype(np.float32)
    nodes = [ow.node("Gemm", ["input", "w", "b"], ["output"],
                     alpha=1.0, beta=1.0)]
    onnx_path = tmp_path / "lin.onnx"
    onnx_path.write_bytes(ow.model(
        nodes, {"w": w, "b": b}, ("input", 1, ["N", 4]), "output"))

    model_dir = tmp_path / "onnx_model"
    r = _run_in_venv(venv, argv=[
        "mmlspark-tpu", "import-onnx", str(onnx_path),
        "--out", str(model_dir)], timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    info = json.loads(r.stdout.strip().splitlines()[0])
    assert info["ops"] == {"Gemm": 1}

    x = rng.normal(size=(6, 4)).astype(np.float32)
    npz_path = tmp_path / "in.npz"   # vector columns ship as npz
    np.savez(npz_path, images=x)
    out_dir = tmp_path / "scored"
    r = _run_in_venv(venv, argv=[
        "mmlspark-tpu", "score", "--model", str(model_dir),
        "--data", str(npz_path), "--out", str(out_dir)], timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    scored = np.load(out_dir / "columns.npz")["scores"]
    np.testing.assert_allclose(scored, x @ w + b, rtol=1e-5, atol=1e-6)
