"""`mmlspark-tpu` — the framework usable without writing Python.

The reference generates a complete non-host-language surface for every
stage (R wrappers, ref: src/codegen/src/main/scala/
WrapperGenerator.scala:204; PySpark wrappers, PySparkWrapper.scala:17):
anything the registry exposes is drivable without touching Scala. The
TPU-native analog is this CLI: it is driven ENTIRELY by the codegen
manifest (codegen.stage_manifest) — stages are looked up by registry
name, params validated by the Param DSL, pipelines described as plain
JSON — so every registered stage is automatically scriptable from a
shell with no Python required.

Pipeline spec (JSON)::

    {
      "pipeline": [
        {"stage": "CleanMissingData",
         "params": {"inputCols": ["f0"], "cleaningMode": "Mean"}},
        {"stage": "GBDTClassifier",
         "params": {"featuresCol": "features", "labelCol": "label"}}
      ]
    }

Data files: a DataTable directory (schema.json + columns.npz), an
``.npz`` of named columns, or a ``.csv`` with a header row (numeric
columns parse as float32; everything else stays string).

Commands::

    mmlspark-tpu stages [--json]          list the registered surface
    mmlspark-tpu describe <Stage>         param table for one stage
    mmlspark-tpu codegen <out_dir>        docs + manifest + smoke tests
    mmlspark-tpu run <spec> --data D --save M [--score-out P]
    mmlspark-tpu score --model M --data D --out P
    mmlspark-tpu serve --model M [--host H] [--port N]
    mmlspark-tpu import-onnx model.onnx --out M
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import sys
from typing import Any, Dict, List


# ---------------------------------------------------------------------------
# data IO
# ---------------------------------------------------------------------------


def load_table(path: str):
    """DataTable from a table directory, .npz, or headered .csv."""
    import numpy as np
    from mmlspark_tpu.core.table import DataTable

    if os.path.isdir(path):
        return DataTable.load(path)
    if path.endswith(".npz"):
        npz = np.load(path, allow_pickle=False)
        return DataTable({k: npz[k] for k in npz.files})
    if path.endswith(".csv"):
        with open(path, newline="") as f:
            reader = csv.reader(f)
            header = next(reader)
            rows = list(reader)
        cols: Dict[str, Any] = {}
        for i, name in enumerate(header):
            vals = [r[i] for r in rows]
            try:
                cols[name] = np.asarray(
                    [float(v) for v in vals], dtype=np.float32)
            except ValueError:
                cols[name] = vals
        return DataTable(cols)
    raise SystemExit(
        f"unrecognized data path {path!r}: expected a DataTable "
        f"directory, .npz, or .csv")


def save_table(table, path: str) -> None:
    """Table directory (default) or .csv when the path says so."""
    import numpy as np

    if path.endswith(".csv"):
        names = table.column_names
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(names)
            for row in table.rows():
                w.writerow([
                    row[n].tolist() if isinstance(row[n], np.ndarray)
                    else row[n] for n in names])
    else:
        table.save(path)


# ---------------------------------------------------------------------------
# pipeline spec
# ---------------------------------------------------------------------------


def build_pipeline(spec: Dict[str, Any]):
    """JSON spec -> Pipeline, resolving stages from the codegen
    registry and validating params through the Param DSL."""
    from mmlspark_tpu.codegen import load_all_stages
    from mmlspark_tpu.core.stage import Pipeline

    registry = load_all_stages()
    stages = []
    entries: List[Dict[str, Any]] = spec.get("pipeline", [])
    if not entries:
        raise SystemExit("spec has no 'pipeline' list")
    for i, entry in enumerate(entries):
        name = entry.get("stage")
        cls = registry.get(name)
        if cls is None:
            close = [k for k in sorted(registry)
                     if name and name.lower() in k.lower()]
            hint = f" (did you mean: {', '.join(close[:5])}?)" \
                if close else ""
            raise SystemExit(
                f"pipeline[{i}]: unknown stage {name!r}{hint} — run "
                f"`mmlspark-tpu stages` for the full list")
        try:
            stages.append(cls(**entry.get("params", {})))
        except (KeyError, TypeError, ValueError) as e:
            raise SystemExit(f"pipeline[{i}] ({name}): {e}") from e
    return Pipeline(stages=stages)


def _read_spec(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SystemExit(f"cannot read pipeline spec {path!r}: {e}")


# ---------------------------------------------------------------------------
# commands
# ---------------------------------------------------------------------------


def cmd_stages(args) -> int:
    from mmlspark_tpu.codegen import stage_manifest
    manifest = stage_manifest()
    if args.json:
        json.dump(manifest, sys.stdout, indent=1)
        print()
        return 0
    for name, info in sorted(manifest["stages"].items()):
        first = (info["doc"] or "").split("\n")[0]
        print(f"{name:32s} {info['kind']:12s} {first[:70]}")
    print(f"\n{len(manifest['stages'])} stages "
          f"(v{manifest['version']})")
    return 0


def cmd_describe(args) -> int:
    from mmlspark_tpu.codegen import load_all_stages, stage_markdown
    registry = load_all_stages()
    cls = registry.get(args.stage)
    if cls is None:
        raise SystemExit(f"unknown stage {args.stage!r} — run "
                         f"`mmlspark-tpu stages`")
    print(stage_markdown(args.stage, cls))
    return 0


def cmd_codegen(args) -> int:
    from mmlspark_tpu.codegen import generate_artifacts
    counts = generate_artifacts(args.out_dir)
    print(json.dumps(counts))
    return 0


def cmd_run(args) -> int:
    spec = _read_spec(args.spec)
    pipeline = build_pipeline(spec)
    table = load_table(args.data)
    print(f"fitting {len(spec['pipeline'])} stage(s) on "
          f"{table.num_rows} rows", file=sys.stderr)
    model = pipeline.fit(table)
    if args.save:
        model.save(args.save)
        print(f"model saved to {args.save}", file=sys.stderr)
    if args.score_out:
        scored = model.transform(table)
        save_table(scored, args.score_out)
        print(f"scored table written to {args.score_out}",
              file=sys.stderr)
    return 0


def cmd_score(args) -> int:
    from mmlspark_tpu.core.serialize import load_stage
    model = load_stage(args.model)
    table = load_table(args.data)
    out = model.transform(table)
    save_table(out, args.out)
    print(f"scored {table.num_rows} rows -> {args.out}", file=sys.stderr)
    return 0


def cmd_import_onnx(args) -> int:
    from mmlspark_tpu.importers.onnx_import import import_onnx_model
    model = import_onnx_model(
        args.onnx, batch_size=args.batch_size,
        input_shape=json.loads(args.input_shape)
        if args.input_shape else None)
    model.save(args.out)
    # summarize from the model just built — re-parsing the protobuf
    # would decode every initializer a second time
    apply_fn = model.get("modelFn")
    ops: Dict[str, int] = {}
    for node in apply_fn.nodes:
        ops[node.op_type] = ops.get(node.op_type, 0) + 1
    print(json.dumps({"saved": args.out, "ops": dict(sorted(ops.items())),
                      "opset": apply_fn.opset,
                      "inputs": apply_fn.input_names}))
    print(f"model saved to {args.out} — score it with "
          f"`mmlspark-tpu score --model {args.out} ...` or serve it "
          f"with `mmlspark-tpu serve --model {args.out}`",
          file=sys.stderr)
    return 0


def cmd_serve(args) -> int:
    from mmlspark_tpu.core.serialize import load_stage
    from mmlspark_tpu.serving.fleet import json_row_scoring_pipeline
    from mmlspark_tpu.serving.server import serve_model

    model = load_stage(args.model)
    # requests arrive as an HTTP-request struct column; wrap the saved
    # tabular pipeline so JSON-object bodies score as table rows
    scorer = json_row_scoring_pipeline(model, reply_col=args.reply_col)
    engine = serve_model(scorer, host=args.host, port=args.port,
                         batch_size=args.batch_size,
                         workers=args.workers)
    print(f"serving {os.path.basename(os.path.abspath(args.model))} "
          f"on http://{args.host}:{args.port} "
          f"(POST JSON rows; Ctrl-C to stop)", flush=True)
    try:
        import threading
        threading.Event().wait()
    except KeyboardInterrupt:
        print("stopping", file=sys.stderr)
    finally:
        engine.stop()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="mmlspark-tpu",
        description="Manifest-driven CLI over the stage registry: "
                    "list/describe stages, fit+score JSON-spec "
                    "pipelines, serve saved models.")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("stages", help="list registered stages")
    p.add_argument("--json", action="store_true",
                   help="full machine-readable manifest")
    p.set_defaults(fn=cmd_stages)

    p = sub.add_parser("describe", help="param table for one stage")
    p.add_argument("stage")
    p.set_defaults(fn=cmd_describe)

    p = sub.add_parser("codegen",
                       help="emit docs + manifest + smoke tests")
    p.add_argument("out_dir")
    p.set_defaults(fn=cmd_codegen)

    p = sub.add_parser("run", help="fit a JSON pipeline spec")
    p.add_argument("spec")
    p.add_argument("--data", required=True)
    p.add_argument("--save", help="directory to save the fitted model")
    p.add_argument("--score-out",
                   help="also transform the data and write it here")
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser("score", help="transform data with a saved model")
    p.add_argument("--model", required=True)
    p.add_argument("--data", required=True)
    p.add_argument("--out", required=True)
    p.set_defaults(fn=cmd_score)

    p = sub.add_parser(
        "import-onnx",
        help="ONNX file -> saved TPUModel stage (then score/serve it)")
    p.add_argument("onnx")
    p.add_argument("--out", required=True,
                   help="directory to save the imported model stage")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--input-shape",
                   help='JSON per-row shape, e.g. "[3,224,224]" or '
                        '{"user": [6]} for multi-input graphs '
                        '(default: inferred from the graph)')
    p.set_defaults(fn=cmd_import_onnx)

    p = sub.add_parser("serve", help="HTTP-serve a saved model")
    p.add_argument("--model", required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8899)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--workers", type=int, default=1)
    p.add_argument("--reply-col", default="prediction",
                   help="output column returned as the HTTP reply "
                        "body (default: prediction)")
    p.set_defaults(fn=cmd_serve)

    args = ap.parse_args(argv)
    if args.fn in (cmd_run, cmd_score, cmd_serve):
        # the commands that compile; the rest stay jax-free
        from mmlspark_tpu.utils.compile_cache import \
            configure_compile_cache
        configure_compile_cache()
    try:
        return args.fn(args)
    except BrokenPipeError:          # output piped into head/less
        try:
            sys.stdout.close()
        except Exception:
            pass
        return 0


if __name__ == "__main__":
    sys.exit(main())
