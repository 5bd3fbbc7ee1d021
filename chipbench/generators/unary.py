"""The general generator for unary traffic: one request, one answer.

Everything that distinguishes one mix from another is a parameter of its
traffic file:

- ``protocol``: ``grpc`` (``InferenceServerClient.infer``, tensors in the
  message);
- ``loop``: ``closed`` (each of ``callers`` threads sends its next request
  when its last one has come back) or ``open`` (requests fall due at fixed
  times, ``rate_per_s`` a second, ``arrivals`` ``poisson`` or ``constant``,
  whatever the server does; ``callers`` threads carry them, and a request
  that finds them all busy waits, its latency counted from when it fell
  due);
- ``request_batch``: rows a request; the tensors themselves come from the
  configuration's request-maker.

An open loop gives every seed the same set of gaps between arrivals, in
another order, and as many requests.  The worker loop is
``perf_analyzer._worker_impl``'s with raw times kept in place of a
histogram.
"""

from __future__ import annotations

import threading
import time

import numpy as np

LATE_ANSWER_S = 60.0
_GAPS_KEY = 0xA221


def arrival_offsets(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Seconds after the window's start at which each request of an open
    loop falls due: ``round(rate * seconds)`` of them, the first at 0, the
    same gaps under every seed in an order drawn from the seed."""
    rate = float(traffic["rate_per_s"])
    count = max(1, int(round(rate * seconds)))
    kind = traffic.get("arrivals", "poisson")
    if kind == "constant":
        gaps = np.full(count, seconds / count)
    elif kind == "poisson":
        gaps = np.random.default_rng([_GAPS_KEY, count]).exponential(
            1.0 / rate, count)
        gaps *= seconds / gaps.sum()
        gaps = gaps[np.random.default_rng([seed, _GAPS_KEY]).permutation(
            count)]
    else:
        raise ValueError(f"arrivals {kind!r}: 'poisson' or 'constant'")
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


class Generator:
    def __init__(self, url: str, cfg: dict, traffic: dict, seed: int,
                 make_request):
        if traffic["protocol"] != "grpc":
            raise ValueError("the unary generator drives protocol 'grpc'; "
                             f"the traffic file says {traffic['protocol']!r}")
        if traffic["loop"] not in ("closed", "open"):
            raise ValueError(f"loop {traffic['loop']!r}: 'closed' or 'open'")
        import triton_client_tpu.grpc as grpcclient

        self._grpc = grpcclient
        self._cfg = cfg
        self._served = cfg["served"]
        self._traffic = traffic
        self._make = make_request
        self._batch = int(traffic["request_batch"])
        self._seed = seed
        self._lock = threading.Lock()
        self._next = 0
        self.records = []   # (index, sent or due, done, ok)
        self.answers = {}   # index -> {output name: array}
        self.first_error = None
        self._clients = [grpcclient.InferenceServerClient(url)
                         for _ in range(int(traffic["callers"]))]
        # connect every channel now, as set-up: the first request on a cold
        # channel pays the connection, and an open loop's first ``callers``
        # requests would all be such, over a hundredth of its window
        for client in self._clients:
            if not client.is_model_ready(self._served["model"]):
                raise RuntimeError(f"{self._served['model']} is not ready")

    def _one(self, client, index: int, due: float = None):
        served = self._served
        tensors = self._make(self._cfg, self._seed, index, self._batch)
        inputs = []
        for spec in served["inputs"]:
            x = tensors[spec["name"]]
            inp = self._grpc.InferInput(spec["name"], list(x.shape),
                                        spec["datatype"])
            inp.set_data_from_numpy(x)
            inputs.append(inp)
        sent = time.time() if due is None else due
        try:
            result = client.infer(served["model"], inputs,
                                  client_timeout=LATE_ANSWER_S)
            out = {o["name"]: result.as_numpy(o["name"])
                   for o in served["outputs"]}
            done = time.time()
            ok = all(v is not None for v in out.values())
        except Exception as e:  # noqa: BLE001 - a failed request is counted
            done, ok, out = time.time(), False, None
            with self._lock:
                if self.first_error is None:
                    self.first_error = f"{type(e).__name__}: {e}"
        with self._lock:
            self.records.append((index, sent, done, ok))
            if ok:
                self.answers[index] = out

    def _take(self) -> int:
        with self._lock:
            index = self._next
            self._next += 1
        return index

    def _closed(self, client, close: float):
        while time.time() < close:
            self._one(client, self._take())

    def _open(self, client, due: np.ndarray, give_up: float):
        while True:
            index = self._take()
            if index >= len(due) or time.time() > give_up:
                return
            wait = due[index] - time.time()
            if wait > 0:
                time.sleep(wait)
            self._one(client, index, due[index])

    def run(self, seconds: float) -> dict:
        """Drive the window; returns its ``start`` and ``close`` on this
        process's ``time.time()``, how many requests never got an answer,
        the records and the answers."""
        start = time.time() + 0.05
        close = start + seconds
        deadline = close + LATE_ANSWER_S + 5.0
        if self._traffic["loop"] == "open":
            due = start + arrival_offsets(self._traffic, self._seed, seconds)
            args = (due, close + LATE_ANSWER_S)
            target, planned = self._open, len(due)
        else:
            args, target, planned = (close,), self._closed, None
        threads = [threading.Thread(target=target, args=(c,) + args,
                                    daemon=True) for c in self._clients]
        time.sleep(max(0.0, start - time.time()))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.time()))
        never = sum(t.is_alive() for t in threads)
        if planned is not None:
            with self._lock:
                never = max(never, planned - len(self.records))
        for c in self._clients:
            c.close()
        return {"start": start, "close": close, "never": never,
                "records": self.records, "answers": self.answers,
                "first_error": self.first_error}
