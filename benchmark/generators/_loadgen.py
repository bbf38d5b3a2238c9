"""Closed-loop HTTP load generator: a process of its own, standard library
only. It never imports JAX: the parent holds the chip.

Started by a generator as `python _loadgen.py <spec.json>`. The spec names
the URL, the pool of request bodies (a pickle of a list of bytes), the
number of clients, the warm and measured seconds, and where to write the
result. Protocol on stdout/stdin, one line each:

    child:  READY            pool loaded, threads can start
    parent: GO
    child:  OPEN <t>         the measured window opens (time.time())
    child:  CLOSE <t>        it closes; requests in flight finish, uncounted
    child:  DONE             the result file is written

Each client posts the next body of the pool (one shared counter, so the
order over all clients is fixed) as soon as its previous reply has arrived.
A reply is counted (`attempted`, `failed`, latency) where it ARRIVES inside
the window; its latency runs from just before the request is sent to the last
byte of the reply. `arrivals` are the arrival times of the good replies, for
the rate, and `arrival_after_close` that of the first good reply after the
window's nominal close (requests in flight at the close are answered, so
there is one): the rate's window runs from a reply to a reply, as a train
cell's runs from a fence to a fence. `answered_work` counts replies as work
done inside the window, a request that straddles an edge by the share of its
send-to-reply time inside (whole replies come a batch at a time, so their
bare count over a fixed window moves in steps of a batch).
"""

import http.client
import json
import os
import pickle
import sys
import threading
import time
import urllib.parse


def main() -> int:
    with open(sys.argv[1], encoding="utf-8") as f:
        spec = json.load(f)
    with open(spec["pool_path"], "rb") as f:
        pool = pickle.load(f)
    url = urllib.parse.urlparse(spec["url"])
    keep_upto = int(spec["keep_replies_upto"])
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 2

    t_go = time.time()
    t_open = t_go + float(spec["warm_seconds"])
    t_close = t_open + float(spec["seconds"])
    lock = threading.Lock()
    counter = [0]
    rows = []            # (t_send, t_done, index, status, ok)
    kept = []            # (t_done, index, classes, probs) for index < keep_upto

    def client():
        while True:
            with lock:
                index = counter[0] % len(pool)
                counter[0] += 1
            t_send = time.time()
            if t_send >= t_close:
                return
            status, ok, reply = 0, False, None
            try:
                conn = http.client.HTTPConnection(url.hostname, url.port,
                                                  timeout=120)
                conn.request("POST", url.path, body=pool[index],
                             headers={"Content-Type": "image/jpeg"})
                resp = conn.getresponse()
                raw = resp.read()
                status = resp.status
                conn.close()
                if status == 200:
                    reply = json.loads(raw)
                    probs = reply["probs"]
                    ok = (len(reply["classes"]) == len(probs) > 0
                          and all(0.0 < p <= 1.0 for p in probs)
                          and all(a >= b for a, b in zip(probs, probs[1:])))
            except Exception:  # noqa: BLE001 - any failure is a failed request
                ok = False
            t_done = time.time()
            with lock:
                rows.append((t_send, t_done, index, status, ok))
                if ok and index < keep_upto:
                    kept.append((t_done, index, reply["classes"],
                                 reply["probs"]))

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(int(spec["clients"]))]
    for t in threads:
        t.start()
    time.sleep(max(t_open - time.time(), 0.0))
    cpu_open = sum(os.times()[:2])
    print(f"OPEN {t_open}", flush=True)
    time.sleep(max(t_close - time.time(), 0.0))
    cpu_close = sum(os.times()[:2])
    print(f"CLOSE {t_close}", flush=True)
    for t in threads:
        t.join(timeout=150)
    inside = [r for r in rows if t_open < r[1] <= t_close]
    work = sum(max(min(r[1], t_close) - max(r[0], t_open), 0.0)
               / max(r[1] - r[0], 1e-9) for r in rows if r[4])
    result = {
        "t_open": t_open, "t_close": t_close,
        "attempted": len(inside),
        "failed": sum(not r[4] for r in inside),
        "answered_work": work,
        "arrivals": sorted(r[1] for r in inside if r[4]),
        "arrival_after_close": min(
            (r[1] for r in rows if r[4] and r[1] > t_close), default=None),
        "latency_s": sorted(r[1] - r[0] for r in inside if r[4]),
        "statuses": sorted({r[3] for r in inside}),
        "kept": [k for k in kept if t_open < k[0] <= t_close],
        "cpu_s": cpu_close - cpu_open,
        "still_running": sum(t.is_alive() for t in threads),
    }
    with open(spec["result_path"], "w", encoding="utf-8") as f:
        json.dump(result, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
