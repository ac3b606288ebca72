"""The load generator: streamed ``POST /v1/completions`` requests, in a
closed or an open loop. Standard library only, one process, one thread
per request in flight.

The loops are those of ``cake_tpu/tools/loadgen.py``, copied here so that
a PR to the program cannot change the yardstick, and repaired: an open
loop times a request from when it was DUE, not from when its thread got
to run; how late the generator sent is reported; arrivals come from the
schedule (``traffic.py``), not from a Poisson draw made here; every
token's arrival time is kept, so the reduction (``metrics.py``) works on
the whole timeline and decides itself what falls inside the window.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
import urllib.error
import urllib.request


def get_json(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def one_request(url: str, prompt_ids: list[int], max_tokens: int,
                due: float | None = None, timeout: float = 300.0) -> dict:
    """Send one streamed request and follow it to its end. Returns the
    record ``metrics.py`` reads, plus ``ids``."""
    body = {"prompt_ids": prompt_ids, "max_tokens": max_tokens,
            "stream": True, "temperature": 0.0}
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    sent = time.perf_counter()
    rec = {"due": sent if due is None else due, "sent": sent, "times": [],
           "ids": [], "asked": max_tokens, "prompt_len": len(prompt_ids),
           "finish_reason": None, "error": None}
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            for raw in resp:
                if not raw.startswith(b"data: "):
                    continue
                data = raw[6:].strip()
                if data == b"[DONE]":
                    break
                ev = json.loads(data)
                if "token" in ev:
                    rec["times"].append(time.perf_counter())
                    rec["ids"].append(ev["token"])
                elif "error" in ev:
                    rec["error"] = f"{ev.get('status')}: {ev['error']}"
                    break
                elif ev.get("done"):
                    rec["finish_reason"] = ev.get("finish_reason")
    except urllib.error.HTTPError as e:
        rec["error"] = f"HTTP {e.code}"
    except (OSError, ValueError) as e:  # refused, reset, timeout, bad frame
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["end"] = time.perf_counter()
    rec["ok"] = (rec["error"] is None and len(rec["ids"]) == max_tokens
                 and rec["finish_reason"] == "length")
    return rec


def run_closed(url: str, schedule, clients: int, seconds: float,
               drain_limit_s: float) -> tuple[list[dict], tuple]:
    """``clients`` clients, each sending its next request when its last
    ended, for ``seconds``; requests in flight when the window closes
    are followed to their end. Returns (records, (t0, t1))."""
    records: list[dict] = []
    lock = threading.Lock()
    counter = itertools.count()
    t0 = time.perf_counter()
    t1 = t0 + seconds

    def client() -> None:
        while time.perf_counter() < t1:
            with lock:
                k = next(counter)
            r = schedule.request(k)
            rec = one_request(url, r["prompt_ids"], r["max_tokens"],
                              timeout=seconds + drain_limit_s)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for th in threads:
        th.start()
    _join(threads, t1 + drain_limit_s, records, lock)
    return records, (t0, t1)


def run_open(url: str, schedule, seconds: float,
             drain_limit_s: float) -> tuple[list[dict], tuple]:
    """Every request of the schedule sent when it is due, whatever has
    finished; each is followed to its end."""
    records: list[dict] = []
    lock = threading.Lock()
    threads = []
    t0 = time.perf_counter()
    t1 = t0 + seconds

    def fire(r: dict, due: float) -> None:
        rec = one_request(url, r["prompt_ids"], r["max_tokens"], due=due,
                          timeout=seconds + drain_limit_s)
        with lock:
            records.append(rec)

    for k in range(schedule.count):
        r = schedule.request(k)  # made before its due time, not after
        due = t0 + r["due"]
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=fire, args=(r, due), daemon=True)
        th.start()
        threads.append(th)
    _join(threads, t1 + drain_limit_s, records, lock)
    return records, (t0, t1)


def _join(threads, deadline: float, records: list, lock) -> None:
    """Wait for the requests in flight until the drain limit; a request
    that has not come back by then is recorded as not drained."""
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.perf_counter()))
    stuck = sum(th.is_alive() for th in threads)
    with lock:
        for _ in range(stuck):
            records.append({"due": deadline, "sent": deadline, "times": [],
                            "ids": [], "asked": 0, "ok": False,
                            "error": "not drained by the drain limit"})


class Poller:
    """Polls ``/healthz`` four times a second while the window is open:
    (time, queued, running) samples."""

    def __init__(self, url: str, every_s: float = 0.25):
        self.url, self.every_s = url, every_s
        self.samples: list[tuple[float, int, int]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        while not self._stop.wait(self.every_s):
            try:
                h = get_json(self.url + "/healthz", timeout=2.0)
            except (OSError, ValueError):
                continue
            self.samples.append((time.perf_counter(), h.get("queued", 0),
                                 h.get("running", 0)))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)
