"""The one general load generator: an open loop that offers requests on
a schedule fixed by a data file of parameters and the seed, from a
process of its own so that it cannot hold the server's interpreter lock.

    arrivals: {"rate_per_s": r, "gap_seed": g}

The arrivals are Poisson: exponential gaps, drawn once from ``gap_seed``
and scaled to fill the window exactly, so every seed replays the same
schedule and draws only its weights and its token rows. A request is
timed from when it was due, not from when it was sent, and how late the
generator ran is reported beside the latencies.

As a program it is the client: it prepares the bodies, prints ``ready``,
reads one JSON line {"address": ...} from standard input, offers the
load, waits for every reply (``reply_timeout_s`` past the close at the
most) and prints one JSON line of what it saw. It imports no jax.
"""

from __future__ import annotations

import json
import math
import sys
import threading
import time

import numpy as np


def fold_seed(seed: int) -> int:
    """``--seed`` may pass 2**31 and the program hands seeds, with small
    constants added, to int32 keys: fold it, the same way every time."""
    return int(seed) % (2 ** 31 - 1024)


def schedule(arrivals: dict, seconds: float) -> np.ndarray:
    """Due times in seconds from the window's start; the last is due as
    the window closes."""
    n = max(1, int(round(arrivals["rate_per_s"] * seconds)))
    gaps = np.random.default_rng(arrivals["gap_seed"]).exponential(
        1.0, size=n)
    return np.cumsum(gaps * (seconds / gaps.sum()))


def token_rows(seed: int, n: int, seq: int, vocab: int) -> np.ndarray:
    return np.random.default_rng(fold_seed(seed) + 1).integers(
        0, vocab, size=(n, seq))


def percentile(values, q: float) -> float:
    """The q-th percentile by rank (no interpolation past the data): a
    failed request is ``inf`` and so sorts after every reply."""
    vals = sorted(values)
    if not vals:
        return math.nan
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def latencies_ms(result: dict, failed_ms: float) -> list:
    """Reply time minus due time; a request that failed, was refused or
    never answered counts as slower than any."""
    out = []
    for due, done, status in zip(result["due"], result["done"],
                                 result["status"]):
        ok = status == 200 and done is not None
        out.append((done - due) * 1e3 if ok else failed_ms)
    return out


def offer(address: str, bodies: list, due: np.ndarray, threads: int,
          timeout: float) -> dict:
    import http.client
    from concurrent.futures import ThreadPoolExecutor
    from urllib.parse import urlparse
    url = urlparse(address)
    n = len(bodies)
    sent, done = [None] * n, [None] * n
    status, answer = [0] * n, [None] * n

    def one(i, t0):
        try:
            conn = http.client.HTTPConnection(url.hostname, url.port,
                                              timeout=timeout)
            sent[i] = time.monotonic() - t0
            conn.request("POST", url.path or "/", body=bodies[i],
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
            done[i] = time.monotonic() - t0
            status[i] = resp.status
            conn.close()
            if resp.status == 200:
                answer[i] = json.loads(payload)
        except Exception as e:  # noqa: BLE001 — a failure is a result
            status[i] = -1
            answer[i] = repr(e)[:200]

    pool = ThreadPoolExecutor(max_workers=threads)
    started = time.time() + 0.25
    t0 = time.monotonic() + 0.25
    futures = []
    for i in range(n):
        wait = t0 + due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        futures.append(pool.submit(one, i, t0))
    closed = time.monotonic()
    for f in futures:
        f.result(timeout=max(0.0, closed + timeout - time.monotonic())
                 + 1.0)
    pool.shutdown(wait=True)
    late = [s - d for s, d in zip(sent, due) if s is not None]
    return {"started_epoch": started, "due": [float(d) for d in due],
            "sent": sent, "done": done, "status": status,
            "answer": answer,
            "late_ms_p50": percentile(late, 50) * 1e3 if late else None,
            "late_ms_max": max(late) * 1e3 if late else None}


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seq", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--field", default="features")
    args = ap.parse_args()
    with open(args.traffic) as f:
        traffic = json.load(f)
    due = schedule(traffic["arrivals"], args.seconds)
    rows = token_rows(args.seed, len(due), args.seq, args.vocab)
    bodies = [json.dumps({args.field: r.tolist()}).encode() for r in rows]
    print("ready", flush=True)
    go = json.loads(sys.stdin.readline())
    result = offer(go["address"], bodies, due, traffic["client_threads"],
                   traffic["reply_timeout_s"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    threading.stack_size(256 * 1024)
    sys.exit(main())
