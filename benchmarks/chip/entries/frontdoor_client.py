#!/usr/bin/env python3
"""The load generator of the HTTP entry, run as a child process that
imports no JAX, so that it never touches the chip and the server keeps
the host's interpreter to itself.

    python3 frontdoor_client.py --seed <n> --seconds <s> [--rate <r>]

It reads the cell's configuration and traffic as one JSON line on
standard input, builds the pool (the same systems the parent builds) and encodes every request body before the window. It then prints
`ready`, waits for `go <url>` on standard input, sends one request per
bucket in turn to warm the front door, prints `start` and runs an open
loop: requests fall due at the traffic file's rate, with exponential
gaps drawn from the configuration's design seed (the same gaps for
every run seed, in the run seed's order), each sent at its due time over
one of a pool of keep-alive connections. Every answer is printed as a
JSON line with its due, send and answer times on the host's monotonic
clock, then `end`."""
import argparse
import http.client
import json
import os
import queue
import sys
import threading
import time
import urllib.parse

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import bench  # noqa: E402

ROUTE = "/v1/solve:sync"


def schedule(config: dict, traffic: dict, seconds: float, seed: int,
             rate=None):
    """[(due offset s, pool index)] of the window's requests."""
    rate = float(rate if rate is not None else traffic["rate"])
    design = np.random.default_rng(int(config["assumed"]["design_seed"])
                                   + 1)
    n = int(np.ceil(rate * seconds * 2)) + 16
    gaps = design.exponential(1.0 / rate, size=n)
    rng = np.random.default_rng(seed)
    due = np.cumsum(gaps[rng.permutation(n)])
    pool = int(traffic["pool"])
    order = np.concatenate([rng.permutation(pool)
                            for _ in range(n // pool + 1)])
    return [(float(t), int(order[k])) for k, t in enumerate(due)
            if t < seconds]


def body(system: dict, i: int) -> bytes:
    return json.dumps({"A": system["A"].tolist(), "b": system["b"].tolist(),
                       "x_true": system["x_true"].tolist(),
                       "request_id": str(i)}).encode()


def post(conn_box, url, payload: bytes):
    """(code, parsed body) over a kept-alive connection, reconnecting
    once if the server closed it."""
    u = urllib.parse.urlsplit(url)
    for attempt in (0, 1):
        if conn_box[0] is None:
            conn_box[0] = http.client.HTTPConnection(u.hostname, u.port,
                                                     timeout=120)
        try:
            conn_box[0].request("POST", ROUTE, payload,
                                {"Content-Type": "application/json"})
            r = conn_box[0].getresponse()
            data = r.read()
            return r.status, json.loads(data) if data else {}
        except (http.client.HTTPException, OSError):
            conn_box[0].close()
            conn_box[0] = None
            if attempt:
                return 0, {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    cell = json.loads(sys.stdin.readline())
    cfg, tr = cell["config"], cell["traffic"]
    pool = bench.make_pool(cfg, int(tr["pool"]))
    bodies = [body(s, i) for i, s in enumerate(pool)]
    plan = schedule(cfg, tr, args.seconds, args.seed, args.rate)
    out_lock = threading.Lock()

    def emit(obj):
        with out_lock:
            sys.stdout.write(json.dumps(obj) + "\n")
            sys.stdout.flush()

    emit({"event": "ready", "requests": len(plan)})
    line = sys.stdin.readline().split()
    if not line or line[0] != "go":
        return 1
    url = line[1]
    step, lo = cfg["batcher"]["bucket_step"], cfg["batcher"]["min_bucket"]
    warm = {}
    for i, s in enumerate(pool):
        warm.setdefault(max(lo, -(-s["n"] // step) * step), i)
    box = [None]
    for i in warm.values():
        post(box, url, bodies[i])
    work = queue.Queue()

    def worker():
        conn = [None]
        while True:
            item = work.get()
            if item is None:
                return
            due, i = item
            sent = time.perf_counter()
            code, resp = post(conn, url, bodies[i])
            done = time.perf_counter()
            out = resp.get("outcome", {})
            emit({"event": "answer", "i": i, "due": due, "sent": sent,
                  "done": done, "code": code,
                  "status": resp.get("status"),
                  "action_names": resp.get("action_names"),
                  "action": resp.get("action"),
                  "bucket": resp.get("bucket"),
                  "latency_s": resp.get("latency_s"),
                  "rid": resp.get("request_id"),
                  "outcome": {k: out.get(k) for k in
                              ("status", "ferr", "nbe", "n_gmres",
                               "n_cg")}})

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(tr["connections"]))]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    emit({"event": "start", "t": t0})
    for off, i in plan:
        wait = t0 + off - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        work.put((t0 + off, i))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join(timeout=max(0.0, t0 + args.seconds + 60 - time.perf_counter()))
    emit({"event": "end", "t": time.perf_counter(),
          "unfinished": sum(t.is_alive() for t in threads)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
