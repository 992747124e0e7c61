"""The load generator: a child process that only speaks HTTP, so that it shares
no GIL with the server. Stdlib only; never imports jax or the program.

Started by run.py before the parent touches JAX. It reads one JSON command a
line on stdin and answers each with one JSON line on stdout:

  {"cmd": "window", "url", "pool": [sql...], "walks": [[pool index...]...],
   "clients", "seconds", "timeout_s"}
      closed loop: one thread and one keep-alive connection a client, each
      sending its walk's next query when the last one is answered, until
      `seconds` have passed; queries in flight at the close are waited for.
      Client c walks `walks[c % len(walks)]`; clients that share a walk each
      take its next query (one walk for all: a shared queue).
      -> {"t0", "t_close", "records": [...]}
  {"cmd": "one", "url", "sql", "timeout_s"}   one query, alone -> {"record"}
  {"cmd": "quit"}

A record is {"client", "pool", "sent", "done", "ok", "error", "response"}:
times on this process's monotonic clock, relative to the window's start; the
response is the broker's JSON object as it came.
"""

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse


def post_query(conn, sql: str):
    body = json.dumps({"sql": sql}).encode()
    conn.request("POST", "/query", body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    if resp.status != 200:
        raise RuntimeError(f"HTTP {resp.status}: {data[:300]!r}")
    return json.loads(data)


def connect(url: str, timeout_s: float):
    u = urlparse(url)
    return http.client.HTTPConnection(u.hostname, u.port, timeout=timeout_s)


def send(conn_box, url, timeout_s, sql):
    """One query on the client's connection; a dropped keep-alive connection
    is opened again, the failed query is reported as failed."""
    rec = {"ok": True, "error": "", "response": None}
    try:
        if conn_box[0] is None:
            conn_box[0] = connect(url, timeout_s)
        rec["response"] = post_query(conn_box[0], sql)
    except Exception as e:   # the record carries it; the harness fails the run
        rec.update(ok=False, error=f"{type(e).__name__}: {e}")
        try:
            conn_box[0].close()
        except Exception:
            pass
        conn_box[0] = None
    return rec


def window(cmd: dict) -> dict:
    url, pool, walks = cmd["url"], cmd["pool"], cmd["walks"]
    seconds, timeout_s = float(cmd["seconds"]), float(cmd["timeout_s"])
    clients = int(cmd.get("clients", len(walks)))
    records, lock = [], threading.Lock()
    cursors = [0] * len(walks)
    start = threading.Barrier(clients + 1)
    t0_box = [0.0]

    def client(c: int):
        box = [None]
        try:
            box[0] = connect(url, timeout_s)
            box[0].connect()
        except Exception:
            box[0] = None
        mine = []
        start.wait()
        t0 = t0_box[0]
        w = c % len(walks)      # fewer walks than clients: a shared queue
        while True:
            sent = time.perf_counter() - t0
            if sent >= seconds:
                break
            with lock:
                k, cursors[w] = cursors[w], cursors[w] + 1
            p = walks[w][k % len(walks[w])]
            rec = send(box, url, timeout_s, pool[p])
            rec.update(client=c, pool=p, sent=sent,
                       done=time.perf_counter() - t0)
            mine.append(rec)
        if box[0] is not None:
            box[0].close()
        with lock:
            records.extend(mine)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    t0_box[0] = time.perf_counter()
    start.wait()
    for t in threads:
        t.join()
    return {"t0": t0_box[0], "t_close": seconds, "records": records}


def main() -> int:
    out = sys.stdout
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "quit":
            break
        if cmd["cmd"] == "window":
            reply = window(cmd)
        elif cmd["cmd"] == "one":
            box = [None]
            t0 = time.perf_counter()
            rec = send(box, cmd["url"], float(cmd["timeout_s"]), cmd["sql"])
            rec.update(client=0, pool=cmd.get("pool", -1), sent=0.0,
                       done=time.perf_counter() - t0)
            if box[0] is not None:
                box[0].close()
            reply = {"record": rec}
        else:
            reply = {"error": f"unknown command {cmd['cmd']!r}"}
        out.write(json.dumps(reply) + "\n")
        out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
