"""The load generator: a child process that never imports jax (the chip is
the parent's) and so shares no interpreter lock with the engine.

    python3 benchmark/loadgen.py <plan.json> <result.json>

The plan names the controller, the model, the requests in sending order, and
the wall-clock time at which the window opens. Every request goes through the
controller's streaming ``/generate`` as a user's would; times are taken here,
at the client: a request is timed from when it was *due*, and how late it was
sent is reported beside it.

The clock is the wall clock as it stood when this process started, advanced
by the monotonic clock: a step of the machine's wall clock inside the window
(a fresh virtual machine's gets corrected now and then) must not read as a
second of waiting. What the wall clock moved against it, and every send more
than 50 ms late, go to standard error."""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse


def anchored_clock():
    """``now()``: the wall clock at this call, advanced monotonically; and
    ``moved()``: how far the wall clock has since moved against it, s."""
    wall0, mono0 = time.time(), time.monotonic()

    def now() -> float:
        return wall0 + (time.monotonic() - mono0)

    return now, lambda: time.time() - now()


def _one(url, model_id: str, req: dict, t_open: float, due: float,
         timeout: float, seconds: float, clock) -> dict:
    """Send one request and read its stream to the end. Times are seconds
    from the window's opening; ``in_window`` counts the tokens that arrived
    before it closed."""
    body = json.dumps({
        "model_id": model_id, "prompts": [req["prompt"]],
        "max_new_tokens": req["max_new"], "temperature": 0.0,
        "stream": True}).encode()
    rec = {"id": req["id"], "due": due, "prompt_tokens": len(req["prompt"]),
           "max_new": req["max_new"], "sent": None, "first": None,
           "last": None, "tokens": [], "in_window": 0, "error": None}
    conn = http.client.HTTPConnection(url.hostname, url.port, timeout=timeout)
    try:
        rec["sent"] = clock() - t_open
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json",
                              "Connection": "close"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"http {resp.status}: {resp.read(300)!r}"
            return rec
        done = False
        while not done:
            line = resp.readline()
            if not line:
                break
            line = line.strip()
            if not line:
                continue
            item = json.loads(line)
            now = clock() - t_open
            if "error" in item:
                rec["error"] = str(item["error"])[:300]
                return rec
            if item.get("done"):
                done = True
            elif item.get("tokens"):
                if rec["first"] is None:
                    rec["first"] = now
                rec["last"] = now
                rec["tokens"].extend(int(t) for t in item["tokens"])
                if now <= seconds:
                    rec["in_window"] = len(rec["tokens"])
        if not done:
            rec["error"] = "stream ended without its done record"
        elif len(rec["tokens"]) != req["max_new"]:
            rec["error"] = (f"{len(rec['tokens'])} tokens for "
                            f"{req['max_new']} asked")
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        conn.close()
    return rec


def run(plan: dict) -> list:
    url = urlparse(plan["url"])
    t_open = float(plan["t_open"])
    seconds = float(plan["seconds"])
    timeout = float(plan["request_timeout"])
    reqs = plan["requests"]
    records, lock = [], threading.Lock()
    clock, moved = anchored_clock()

    def send(req, due):
        rec = _one(url, plan["model_id"], req, t_open, due, timeout, seconds,
                   clock)
        with lock:
            records.append(rec)

    threads = []
    delay = t_open - clock()
    if delay > 0:
        time.sleep(delay)
    if plan["kind"] == "open_loop":
        for req in reqs:
            delay = t_open + req["due_s"] - clock()
            if delay > 0:
                time.sleep(delay)
            woke = clock() - t_open
            t = threading.Thread(target=send, args=(req, req["due_s"]))
            t.start()
            threads.append(t)
            if woke - req["due_s"] > 0.05:
                print(f"[loadgen] request {req['id']} due at "
                      f"{req['due_s']:.3f} s: woke {woke:.3f}, thread "
                      f"started {clock() - t_open:.3f}", file=sys.stderr,
                      flush=True)
    else:
        nxt = iter(reqs)

        def client():
            while True:
                with lock:
                    req = next(nxt, None)
                now = clock() - t_open
                if req is None or now >= seconds:
                    return
                send(req, now)

        threads = [threading.Thread(target=client)
                   for _ in range(int(plan["clients"]))]
        for t in threads:
            t.start()
    deadline = t_open + seconds + float(plan["drain_seconds"])
    for t in threads:
        t.join(max(0.0, deadline - clock()) + timeout)
    if abs(moved()) > 0.005:
        print(f"[loadgen] the wall clock moved {1000.0 * moved():.1f} ms "
              f"against the monotonic clock since the generator started",
              file=sys.stderr, flush=True)
    with lock:
        return sorted(records, key=lambda r: r["id"])


def main(argv) -> int:
    plan = json.loads(open(argv[1]).read())
    records = run(plan)
    with open(argv[2], "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
