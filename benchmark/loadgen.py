"""The load generator: a child process of stdlib Python that never imports jax.

The parent (``run.py``) holds the chip and the server; this process only
makes HTTP requests against ``POST /v1/completions`` on localhost and writes
down when things happened. Started as

    python3 loadgen.py <spec.json> <out.json>

``spec`` holds: ``host``, ``port``, ``mix`` (a traffic file's content),
``seed``, ``stream``, ``vocab``, ``seconds``, ``t0`` (``time.monotonic()`` of
the window's start; Linux's monotonic clock is shared by processes), and
``mode`` with ``rate`` (``open``), ``clients`` (``closed``) or ``n``
(``burst``, the warm-up); ``sample_hz`` > 0 also scrapes ``/metrics`` at that rate. ``out`` receives one JSON object:
``records`` (one per request, times in seconds from ``t0``), ``samples`` and
``stalls`` (when this process itself stood still, see ``heartbeat``).

A record: ``i``, ``stream_id``, ``prompt_len``, ``max_tokens``, ``due``
(open loop), ``sent``, ``first`` (first token frame, streaming), ``frames``
(every token frame, streaming), ``end``, ``status`` (HTTP status; 0 = no
answer yet when the run ended, -1 = connection error), ``tokens``.

The run ends ``grace_seconds`` after the window. A streaming request that is
still receiving tokens then is cut off by the generator (``cut``: true; its
frames so far are kept), which is not a failure of the server: at today's
speed a 384-token answer streams for a minute, and a run cannot wait for it.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trafficgen  # noqa: E402


class Client:
    def __init__(self, spec: dict):
        self.spec = spec
        self.t0 = float(spec["t0"])
        self.records: list = []
        self._lock = threading.Lock()

    def now(self) -> float:
        return time.monotonic() - self.t0

    def body(self, req: dict) -> bytes:
        ids = trafficgen.prompt_tokens(self.spec["seed"], req["stream_id"],
                                       req["i"], req["prompt_len"],
                                       self.spec["vocab"])
        return json.dumps({"prompt": ids, "max_tokens": req["max_tokens"],
                           "stream": req["stream"]}).encode()

    def send(self, req: dict, body: bytes, timeout: float) -> dict:
        """One request, start to end; the record is kept whatever happens."""
        rec = {**req, "sent": None, "first": None, "frames": [], "end": None,
               "status": 0, "tokens": None, "cut": False}
        with self._lock:
            self.records.append(rec)
        conn = http.client.HTTPConnection(self.spec["host"], self.spec["port"],
                                          timeout=timeout)
        try:
            rec["sent"] = self.now()
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                resp.read()
                final = None
            elif req["stream"]:
                final = self._read_sse(resp, rec)
            else:
                final = json.loads(resp.read())
            rec["end"] = self.now()
            if final is not None:
                rec["tokens"] = final["choices"][0]["tokens"]
            rec["status"] = resp.status
        except (OSError, http.client.HTTPException, ValueError, KeyError):
            rec["status"] = -1
        finally:
            conn.close()
        return rec

    def _read_sse(self, resp, rec: dict):
        """Stamp each token frame as its line arrives; parse only the last
        frame (the full response), so a frame costs one substring test."""
        final = None
        while True:
            line = resp.readline()
            if not line:
                return final
            if not line.startswith(b"data:"):
                continue
            if b'"token"' in line:
                t = self.now()
                if rec["first"] is None:
                    rec["first"] = t
                rec["frames"].append(t)
            elif b'"choices"' in line:
                final = json.loads(line[5:])


def run_open(client: Client) -> None:
    spec = client.spec
    sched = trafficgen.open_schedule(spec["mix"], spec["rate"],
                                     spec["stream"], spec["seconds"])
    bodies = [client.body(r) for r in sched]  # made before anything is due
    deadline = spec["seconds"] + spec["mix"]["grace_seconds"]
    threads = []
    for req, body in zip(sched, bodies):
        wait = req["due"] - client.now()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=client.send,
                              args=(req, body, deadline - req["due"] + 5.0),
                              daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=max(0.0, deadline - client.now()))


def run_closed(client: Client) -> None:
    spec = client.spec
    lead = spec["mix"]["lead_seconds"]
    deadline = spec["seconds"] + spec["mix"]["grace_seconds"]
    counter = iter(range(10**9))
    lock = threading.Lock()

    def worker(k: int) -> None:
        # clients start spread over the first part of the lead-in, so the
        # pool does not send its first documents in one burst
        time.sleep(max(0.0, -lead + k * (lead / 2) / spec["clients"]
                       - client.now()))
        while client.now() < spec["seconds"]:
            with lock:
                i = next(counter)
            req = trafficgen.request(spec["mix"], spec["seed"], spec["stream"], i)
            client.send(req, client.body(req), deadline - client.now() + 5.0)

    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(spec["clients"])]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=max(0.0, deadline - client.now()))


def run_burst(client: Client) -> None:
    """``n`` requests of the stream at once, each waited for (the warm-up)."""
    spec = client.spec
    reqs = [trafficgen.request(spec["mix"], spec["seed"], spec["stream"], i)
            for i in range(spec["n"])]
    for r in reqs:  # the warm-up needs the paths, not the lengths
        r["max_tokens"] = min(r["max_tokens"], spec["mix"]["warm_max_tokens"])
    threads = [threading.Thread(target=client.send,
                                args=(r, client.body(r), 300.0), daemon=True)
               for r in reqs]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300.0)


def sampler(client: Client, samples: list, stop: threading.Event) -> None:
    """Scrape the two gauges at ``sample_hz`` (traced runs only)."""
    spec = client.spec
    period = 1.0 / spec["sample_hz"]
    want = ("serving_queue_depth", "serving_inflight")
    while not stop.wait(period):
        conn = http.client.HTTPConnection(spec["host"], spec["port"], timeout=5)
        try:
            conn.request("GET", "/metrics")
            text = conn.getresponse().read().decode()
        except (OSError, http.client.HTTPException):
            continue
        finally:
            conn.close()
        row = {"t": client.now()}
        for line in text.splitlines():
            name = line.split("{")[0].split(" ")[0]
            if name in want:
                row[name] = float(line.rsplit(" ", 1)[1])
        samples.append(row)


def heartbeat(client: Client, stalls: list, stop: threading.Event,
              period: float = 0.02, report: float = 0.1) -> None:
    """Write down every time this process itself stood still: a thread that
    sleeps ``period`` seconds at a time and notes when it overslept by more
    than ``report``. The generator does next to nothing, so only the machine
    can hold it up (a virtual machine paused by its host, a host with no core
    to spare). The server stands still with it and every clock reading
    across the pause is the machine's, not the server's; the parent decides
    what a window is worth then (``serve_cell.frozen``)."""
    last = client.now()
    while not stop.wait(period):
        t = client.now()
        if t - last - period > report:
            stalls.append({"at": last, "seconds": t - last - period})
        last = t


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    client = Client(spec)
    samples: list = []
    stalls: list = []
    stop = threading.Event()
    threading.Thread(target=heartbeat, args=(client, stalls, stop),
                     daemon=True).start()
    if spec.get("sample_hz"):
        threading.Thread(target=sampler, args=(client, samples, stop),
                         daemon=True).start()
    {"open": run_open, "closed": run_closed, "burst": run_burst}[spec["mode"]](client)
    stop.set()
    with client._lock:
        out = {"records": [dict(r) for r in client.records],
               "samples": samples, "stalls": list(stalls)}
    for r in out["records"]:  # still streaming when the run ended
        r["cut"] = r["status"] == 0 and r["first"] is not None
    tmp = argv[2] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, argv[2])
    sys.stdout.flush()
    # request threads still blocked past the deadline are daemons: their
    # records say status 0; leave without waiting for their sockets
    os._exit(0)


if __name__ == "__main__":
    main(sys.argv)
