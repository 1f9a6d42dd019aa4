"""Open-loop runner: traversal requests over HTTP to ``BFSService``.

The service runs behind the program's HTTP front-end (``serve_http``)
in this process, on an ephemeral port.  The schedule is drawn from the
seed before the window: ``round(rate * seconds)`` requests whose gaps
are the quantiles of the exponential distribution (Poisson arrivals),
scaled to fill the window, and whose sizes are a fixed multiset, both
in seeded order; every seed thus offers the same gaps and sizes.
Sources are Zipf-popular over a seeded order of the vertices of degree
>= 1.  Each request is
sent at its time whether or not earlier ones have finished, and timed
from that time to its decoded body.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait

import numpy as np

from harness import graphgen, reference
from harness.common import Annotator, Stopwatch, rng, say

LANE = "graph"
REPLY_WAIT_S = 60.0          # how long past the window a reply may come


class HttpRunner:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, devices):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.seconds = float(seconds)
        self.devices = list(devices)
        self.record = {"kind": "http"}
        self.httpd = None

    # -------------------------------------------------------------- set-up
    def setup(self, cache_events) -> None:
        from repro.core import BFSOptions
        from repro.graphs import shard_graph
        from repro.serve.bfs_service import BFSService
        from repro.serve.engine_cache import EngineCache
        from repro.serve.frontend import serve_http

        sw = Stopwatch("setup")
        with sw.part("generate"):
            self.src, self.dst, self.n = graphgen.generate(
                self.config, self.seed)
        degree = np.bincount(self.src, minlength=self.n)
        with sw.part("shard_graph"):
            graph = shard_graph(self.src, self.dst, self.n, 1,
                                e_cap=int(self.config["edge_capacity"]))
        ladder = tuple(int(b) for b in self.config["serve"]["buckets"])
        self.service = BFSService(
            opts=BFSOptions(**self.config["options"]),
            batch_buckets=ladder, cache=EngineCache())
        self.service.add_graph(LANE, graph)
        self.httpd, _ = serve_http(self.service, "127.0.0.1", 0,
                                   log=lambda *a, **k: None)
        self.server = threading.Thread(target=self.httpd.serve_forever,
                                       name="bench-http", daemon=True)
        self.server.start()
        self.port = self.httpd.server_address[1]
        self.schedule = make_schedule(self.traffic, degree, self.seed,
                                      self.seconds)
        before = cache_events.snapshot()
        # warm-up: one request per rung of the ladder compiles and runs
        # each bucket engine (sizes 1 and the largest rung)
        warm = np.flatnonzero(degree)[:ladder[-1]].tolist()
        with sw.part("warm_up"):
            for size in ladder:
                status, _, _ = self._post(warm[:size])
                if status != 200:
                    raise RuntimeError(f"warm-up request of {size} "
                                       f"sources -> HTTP {status}")
        after = cache_events.snapshot()
        say("setup", edges=int(self.src.size), n=self.n, ladder=ladder,
            requests=len(self.schedule),
            rate_per_s=self.traffic["rate_per_s"],
            cache_requests=after["requests"] - before["requests"],
            cache_hits=after["hits"] - before["hits"])
        self.record["setup_parts"] = sw.parts

    def _post(self, sources, annotate=Annotator(False)):
        body = json.dumps({"graph": LANE, "sources": sources}).encode()
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=REPLY_WAIT_S + 300)
        try:
            with annotate("bench.http_send"):
                conn.request("POST", "/v1/traverse", body,
                              {"Content-Type": "application/json"})
            with annotate("bench.http_receive"):
                resp = conn.getresponse()
                data = resp.read()
                payload = json.loads(data) if resp.status == 200 else None
            return resp.status, payload, time.perf_counter()
        finally:
            conn.close()

    # -------------------------------------------------------------- window
    def window(self, seconds: float, annotate) -> None:
        sched = self.schedule
        out = [None] * len(sched)
        workers = int(self.traffic["client_threads"])
        lateness = []

        def send(i, t_due):
            lateness.append(time.perf_counter() - t_due)
            try:
                status, payload, t_done = self._post(sched[i]["sources"],
                                                     annotate)
            except OSError as exc:
                status, payload, t_done = repr(exc), None, None
            out[i] = (status, payload, t_done)

        with annotate("bench.window"), \
                ThreadPoolExecutor(max_workers=workers) as pool:
            t0 = time.perf_counter()
            futures = []
            for i, req in enumerate(sched):
                t_due = t0 + req["at_s"]
                delay = t_due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                futures.append(pool.submit(send, i, t_due))
            wait(futures, timeout=max(0.0, t0 + seconds + REPLY_WAIT_S
                                      - time.perf_counter()))
        requests = []
        for req, res in zip(sched, out):
            status, payload, t_done = res if res else ("no reply", None,
                                                      None)
            ok = status == 200
            requests.append({
                "sources": req["sources"], "status": status,
                "latency_s": (t_done - t0 - req["at_s"]) if ok
                else float("inf"),
                "done_s": (t_done - t0) if ok else float("inf"),
                "timing_ms": payload["timing_ms"] if ok else None,
                "bucket": payload["bucket"] if ok else None,
                "levels": payload["stats"]["levels"] if ok else None,
                "depths": (np.asarray(payload["depths"], dtype=np.int64)
                           if ok else None)})
        done = [t for _, _, t in filter(None, out) if t is not None]
        self.record["window_s"] = (max(done) - t0) if done else seconds
        self.record["requests"] = requests
        self.record["lateness_s"] = lateness
        self.record["attempted"] = len(requests)
        self.record["failed"] = sum(r["status"] != 200 for r in requests)
        say("window", requests=len(requests),
            failed=self.record["failed"],
            window_s=f"{self.record['window_s']:.4f}",
            lateness_max_s=f"{max(lateness, default=0.0):.4f}",
            bucket_1=sum(r["bucket"] == 1 for r in requests),
            bucket_other=sum(r["bucket"] not in (None, 1) for r in requests))

    def release(self) -> None:
        if self.httpd is not None:
            self.httpd.drain_and_stop(timeout_s=REPLY_WAIT_S)
            self.server.join(timeout=REPLY_WAIT_S)
            self.httpd.server_close()
            if self.server.is_alive():
                raise RuntimeError("the HTTP server thread did not stop")
            self.httpd = None
        self.service = None

    # --------------------------------------------------------------- check
    def check(self) -> dict:
        """Every reply of the window against the reference."""
        reqs = self.record["requests"]
        used = sorted({s for r in reqs if r["depths"] is not None
                       for s in r["sources"]})
        col = {s: j for j, s in enumerate(used)}
        want = reference.Graph(self.src, self.dst, self.n).depths(used)
        mismatched = 0
        for r in reqs:
            if r["depths"] is None:
                continue
            rows = want[:, [col[s] for s in r["sources"]]].T
            got = r.pop("depths")
            mismatched += (rows.size if got.shape != rows.shape
                           else int((got != rows).sum()))
        return {"mismatched_depths": (mismatched, 0),
                "unanswered": (self.record["failed"], 0)}


def make_schedule(traffic: dict, degree: np.ndarray, seed: int,
                  seconds: float) -> list:
    """The window's requests: arrival time, and distinct sources."""
    n_req = int(round(float(traffic["rate_per_s"]) * seconds))
    r_time, r_size, r_src = (rng(seed, k) for k in (2, 3, 4))
    gaps = -np.log1p(-(np.arange(n_req) + 0.5) / n_req)
    gaps = r_time.permutation(gaps * seconds / gaps.sum())
    at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    sizes = []
    for size, share in traffic["size_shares"]:
        sizes += [size] * int(round(share * n_req))
    sizes = (sizes + [1] * n_req)[:n_req]
    sizes = _expand_sizes(sizes, r_size)
    cand = r_src.permutation(np.flatnonzero(degree))
    pop = 1.0 / np.arange(1, cand.size + 1) ** float(traffic["zipf_s"])
    cdf = np.cumsum(pop) / pop.sum()
    out = []
    for t, size in zip(at, sizes):
        chosen = []
        while len(chosen) < size:
            rank = min(int(np.searchsorted(cdf, r_src.random())),
                       cand.size - 1)
            v = int(cand[rank])
            if v not in chosen:
                chosen.append(v)
        out.append({"at_s": float(t), "sources": chosen})
    return out


def _expand_sizes(sizes, gen) -> list:
    """Turn each ``[lo, hi]`` size range into a fixed cycle of its sizes
    before the seeded shuffle, so every seed sends the same multiset."""
    flat, cycle = [], {}
    for s in sizes:
        if isinstance(s, list):
            lo, hi = s
            k = cycle.get((lo, hi), 0)
            cycle[(lo, hi)] = k + 1
            flat.append(lo + k % (hi - lo + 1))
        else:
            flat.append(int(s))
    return [flat[i] for i in gen.permutation(len(flat))]
