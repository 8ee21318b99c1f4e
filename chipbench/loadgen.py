"""The load generator: a child process that never imports JAX (the chip
belongs to the parent, which runs the server). Reads one JSON job from
standard input, sends the requests over HTTP, writes one JSON result to
standard output.

Job: ``{"url", "mode": "open"|"closed", "start": epoch seconds, "seconds",
"clients", "grace_s", "requests": [{"due", "prompt", "max_new_tokens"}]}``.
Result: ``{"requests": [{"i", "due", "sent", "done", "status", "tokens"}]}``
with times as offsets from ``start``; ``due`` of a closed-loop request is
when its client was free to send it.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse


def _post(conn_box: list, host: str, port: int, path: str, body: bytes,
          timeout: float):
    """One POST over this thread's kept-alive connection."""
    for attempt in (0, 1):
        if not conn_box:
            conn_box.append(http.client.HTTPConnection(host, port,
                                                       timeout=timeout))
        conn = conn_box[0]
        try:
            conn.request("POST", path, body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            return resp.status, data
        except (http.client.HTTPException, OSError):
            conn.close()
            conn_box.clear()
            if attempt:
                raise


def run(job: dict) -> dict:
    u = urlparse(job["url"])
    start, seconds = float(job["start"]), float(job["seconds"])
    reqs = job["requests"]
    out = [None] * len(reqs)
    timeout = seconds + float(job["grace_s"])
    lock = threading.Lock()
    cursor = [0]

    def send(i: int, due: float, box: list):
        r = reqs[i]
        body = json.dumps({"prompt_tokens": [r["prompt"]],
                           "max_new_tokens": r["max_new_tokens"]}).encode()
        sent = time.time() - start
        try:
            status, data = _post(box, u.hostname, u.port, u.path, body,
                                 timeout)
            tokens = json.loads(data)["tokens"][0] if status == 200 else None
        except Exception as e:  # noqa: BLE001 — a failed request is a result
            status, tokens = repr(e), None
        out[i] = {"i": i, "due": due, "sent": sent,
                  "done": time.time() - start, "status": status,
                  "tokens": tokens}

    def closed_client():
        box: list = []
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            now = time.time() - start
            if i >= len(reqs) or now >= seconds:
                return
            send(i, now, box)

    def open_worker():
        box: list = []
        while True:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= len(reqs):
                return
            due = reqs[i]["due"]
            delay = start + due - time.time()
            if delay > 0:
                time.sleep(delay)
            send(i, due, box)

    target = closed_client if job["mode"] == "closed" else open_worker
    threads = [threading.Thread(target=target, daemon=True)
               for _ in range(int(job["clients"]))]
    delay = start - time.time()
    if delay > 0:
        time.sleep(delay)
    for t in threads:
        t.start()
    deadline = start + timeout + 5
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.time()))
    return {"requests": [r for r in out if r is not None],
            "never_sent": sum(1 for r in out if r is None)}


if __name__ == "__main__":
    json.dump(run(json.load(sys.stdin)), sys.stdout)
