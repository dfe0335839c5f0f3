"""The load generator: one process, standard library only.

It never imports JAX, so it neither takes the chip nor shares the
server's interpreter lock. `run.py` starts it during set-up and hands it
a plan as one JSON line on stdin: the statement templates, the set-up
statements (warm-up and prefill, sent one after another) and, for each
client, the list of requests the traffic generator drew from the seed.
It prints `{"ready": ...}`, waits for the line `go`, drives the window
and prints one JSON line of results: every request with the times it was
sent and answered on `time.monotonic()` (one clock for every process of
the machine), its rows and query id, how late the generator ran and the
share of one core it used.

Closed loop: a client sends its next request when the last answer is
drained, and starts no new cycle of `cycle` requests after the deadline;
requests under way finish. The clients each have a list of their own, or
(`queue: shared`) draw in turn from one list, as a pool of connections
works through one stream of panel queries. Open loop: requests go out at their due
times whatever the answers do, and latency is taken from the due time.
"""

from __future__ import annotations

import http.client
import json
import socket
import sys
import threading
import time
from urllib.parse import quote


class Conn:
    """POST /v1/statement and follow nextUri to the end, on one
    persistent connection; reconnect once when an idle connection was
    closed under us (the StatementClientV1 behaviour)."""

    def __init__(self, host: str, port: int, user: str):
        self.host, self.port, self.user = host, port, user
        self.conn = None

    def _request(self, method: str, path: str, body=None, headers=None):
        for attempt in range(2):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=600)
                self.conn.connect()
                # as urllib3, the JDBC driver and the CLI do: without it
                # the header and body segments wait on a delayed ACK
                self.conn.sock.setsockopt(socket.IPPROTO_TCP,
                                          socket.TCP_NODELAY, 1)
            try:
                self.conn.request(method, path, body=body,
                                  headers=headers or {})
                resp = self.conn.getresponse()
                return json.loads(resp.read()), resp
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise

    def get(self, path: str):
        return self._request("GET", path)[0]

    def statement(self, sql: str, headers: dict) -> dict:
        """-> {"qid", "rows", "error", "added_prepare", "polls"}: never raises for
        a failed query or a lost connection."""
        out = {"qid": None, "rows": [], "error": None, "added_prepare": None,
               "polls": 0}
        hdrs = {"X-Trino-User": self.user, **headers}
        try:
            payload, resp = self._request("POST", "/v1/statement",
                                          body=sql.encode(), headers=hdrs)
            while True:
                out["rows"].extend(payload.get("data", []))
                out["added_prepare"] = resp.getheader(
                    "X-Trino-Added-Prepare") or out["added_prepare"]
                if "nextUri" not in payload:
                    break
                path = payload["nextUri"].split(f":{self.port}", 1)[1]
                payload, resp = self._request("GET", path)
                out["polls"] += 1
            out["qid"] = payload.get("id")
            if payload.get("error") is not None:
                out["error"] = str(payload["error"].get("message",
                                                        payload["error"]))
            elif payload.get("stats", {}).get("state") != "FINISHED":
                out["error"] = f"ended {payload.get('stats')}"
        except (http.client.HTTPException, OSError, ValueError,
                KeyError, IndexError) as e:
            out["error"] = f"{type(e).__name__}: {e}"
        return out

    def close(self):
        if self.conn is not None:
            try:
                self.conn.close()
            except OSError:
                pass
            self.conn = None


def session_header(props: dict) -> dict:
    if not props:
        return {}
    return {"X-Trino-Session": ",".join(
        f"{k}={quote(str(v))}" for k, v in sorted(props.items()))}


class Statements:
    """Turns (shape, params) into the text and headers to send."""

    def __init__(self, plan: dict):
        self.shapes = plan["shapes"]
        self.prepared = plan["statement"] == "prepared"
        self.session = plan["session"]
        self.prepared_header = {}

    def prepare_all(self, conn: Conn, per_run: dict) -> None:
        """PREPARE each shape once; the stateless protocol hands the
        statement back and every later request re-sends it."""
        parts = []
        for name, shape in sorted(self.shapes.items()):
            text = shape["prepared"].format(**per_run.get(name, {}))
            got = conn.statement(f"PREPARE bench_{name} FROM {text}",
                                 session_header(self.session))
            if got["error"] or not got["added_prepare"]:
                raise RuntimeError(f"PREPARE {name} failed: {got}")
            parts.append(got["added_prepare"])
        self.prepared_header = {"X-Trino-Prepared-Statement": ",".join(parts)}

    def build(self, shape: str, params: dict, session=None):
        headers = session_header(self.session if session is None
                                 else session)
        if self.prepared:
            using = self.shapes[shape]["using"].format(**params)
            return (f"EXECUTE bench_{shape} USING {using}",
                    {**headers, **self.prepared_header})
        return self.shapes[shape]["sql"].format(**params), headers


class SharedQueue:
    """One list of requests that every client draws from in turn."""

    def __init__(self, requests: list):
        self.requests = iter(requests)
        self.lock = threading.Lock()

    def __iter__(self):
        return self

    def __next__(self):
        with self.lock:
            return next(self.requests)


def _closed_client(idx, plan, stmts, requests, t_go, out, gaps):
    conn = Conn(plan["host"], plan["port"], f"bench-{idx}")
    deadline = t_go + plan["seconds"]
    cycle = plan["cycle"]
    last_done = None
    try:
        for i, (shape, params) in enumerate(requests):
            if i % cycle == 0 and time.monotonic() >= deadline:
                return
            sql, headers = stmts.build(shape, params)
            t0 = time.monotonic()
            if last_done is not None:
                gaps.append(t0 - last_done)
            got = conn.statement(sql, headers)
            last_done = time.monotonic()
            out.append({"client": idx, "shape": shape, "params": params,
                        "t_due": t0, "t_send": t0, "t_done": last_done,
                        "qid": got["qid"], "rows": got["rows"],
                        "polls": got["polls"], "error": got["error"]})
        out.append({"client": idx, "shape": "exhausted", "params": None,
                    "t_due": last_done, "t_send": last_done,
                    "t_done": last_done, "qid": None, "rows": [], "polls": 0,
                    "error": "the client ran out of generated requests"})
    finally:
        conn.close()


def _open_worker(idx, plan, stmts, queue, lock, t_go, out, gaps):
    """Open loop: `clients` sender threads share one schedule; each takes
    the next due request, sleeps until it is due, and sends it."""
    conn = Conn(plan["host"], plan["port"], f"bench-{idx}")
    try:
        while True:
            with lock:
                if not queue:
                    return
                shape, params, due = queue.pop(0)
            t_due = t_go + due
            if due >= plan["seconds"]:
                return
            delay = t_due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            sql, headers = stmts.build(shape, params)
            t0 = time.monotonic()
            gaps.append(t0 - t_due)
            got = conn.statement(sql, headers)
            out.append({"client": idx, "shape": shape, "params": params,
                        "t_due": t_due, "t_send": t0,
                        "t_done": time.monotonic(), "qid": got["qid"],
                        "rows": got["rows"], "polls": got["polls"],
                        "error": got["error"]})
    finally:
        conn.close()


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    stmts = Statements(plan)
    conn = Conn(plan["host"], plan["port"], "bench-setup")
    setup = []
    if stmts.prepared:
        stmts.prepare_all(conn, plan["per_run"])
    for step in plan["setup"]:
        sql, headers = stmts.build(step["shape"], step["params"],
                                   step.get("session"))
        t0 = time.monotonic()
        got = conn.statement(sql, headers)
        setup.append({"phase": step["phase"], "shape": step["shape"],
                      "qid": got["qid"], "error": got["error"],
                      "wall_s": time.monotonic() - t0})
    conn.close()
    print(json.dumps({"ready": True, "setup": setup}), flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1

    t_go = time.monotonic()
    cpu0 = time.process_time()
    out, gaps = [], []          # list.append is atomic under the GIL
    if plan["loop"] == "closed":
        sources = plan["clients"]
        if plan["queue"] == "shared":
            sources = [SharedQueue(sources[0])] * plan["n_clients"]
        threads = [threading.Thread(
            target=_closed_client,
            args=(i, plan, stmts, reqs, t_go, out, gaps))
            for i, reqs in enumerate(sources)]
    else:
        queue = sorted((r for reqs in plan["clients"] for r in reqs),
                       key=lambda r: r[2])
        lock = threading.Lock()
        threads = [threading.Thread(
            target=_open_worker,
            args=(i, plan, stmts, queue, lock, t_go, out, gaps))
            for i in range(plan["n_clients"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_end = time.monotonic()
    gaps.sort()
    print(json.dumps({
        "t_go": t_go, "t_end": t_end, "requests": out,
        "generator": {
            # closed loop: answer drained -> next request sent;
            # open loop: due time -> request sent
            "late_mean_ms": 1e3 * sum(gaps) / len(gaps) if gaps else 0.0,
            "late_p99_ms": 1e3 * gaps[int(0.99 * (len(gaps) - 1))]
            if gaps else 0.0,
            "late_max_ms": 1e3 * gaps[-1] if gaps else 0.0,
            "cpu_share_of_one_core":
                (time.process_time() - cpu0) / max(t_end - t_go, 1e-9),
        }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
