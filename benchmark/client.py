"""The load generator: SSE clients over the wire, open or closed loop.

Runs in the benchmark's parent process, which never imports jax. One
thread per request in flight; every request is timed from when it was due
and every token span is stamped with its receive time. Send times never
wait on replies in the open loop; in the closed loop each client sends its
next request when the last one completed.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import socket
import threading
import time

from . import traffic

REQUEST_TIMEOUT_S = 120.0
OK, FAILED, ABORTED = "ok", "failed", "aborted"


@dataclasses.dataclass
class Record:
    index: int
    cls: int
    prompt_len: int
    max_new: int
    due: float                      # monotonic seconds, like every time here
    sent: float = 0.0
    first: float = 0.0              # first token span received
    done: float = 0.0               # done event received
    spans: list = dataclasses.field(default_factory=list)  # [(t, n_tokens)]
    n_tokens: int = 0
    status: str = ""                # ok | failed | aborted; "" = in flight
    error: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def sse_events(resp):
    """The JSON objects of an SSE response's ``data:`` lines, as they come."""
    for raw in resp:
        if raw.startswith(b"data:"):
            yield json.loads(raw[5:])


class Load:
    """Drives one mix against ``/api/v0.1/generate`` until stopped."""

    def __init__(self, port: int, mix: dict, seed: int, vocab: int, slots: int):
        self.port, self.mix, self.seed, self.vocab = port, mix, seed, vocab
        self.slots = slots
        self.records: list = []
        self._lock = threading.Lock()
        self._next = 0
        self._stop = threading.Event()      # no further request is sent
        self._cut = threading.Event()       # streams in flight are being cut
        self._socks: set = set()
        self._threads: list = []
        self.t_start = 0.0

    # -- one request -------------------------------------------------------

    def _claim(self) -> int:
        with self._lock:
            i = self._next
            self._next += 1
            return i

    def _request(self, i: int, due: float | None) -> Record:
        k, n_prompt, n_new, body = traffic.request_body(
            self.mix, self.seed, i, self.vocab
        )
        rec = Record(i, k, n_prompt, n_new, due if due is not None else 0.0)
        with self._lock:
            self.records.append(rec)
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=REQUEST_TIMEOUT_S)
        sock = None
        try:
            conn.connect()
            # the socket itself: http.client lets go of it once the
            # response (Connection: close) has taken it over
            sock = conn.sock
            with self._lock:
                self._socks.add(sock)
            rec.sent = time.monotonic()
            if due is None:         # closed loop: due when the client is free
                rec.due = rec.sent
            conn.request("POST", "/api/v0.1/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise ValueError(f"status {resp.status}: {resp.read()[:200]!r}")
            self._read_stream(resp, rec)
        except (OSError, ValueError, http.client.HTTPException) as e:
            # a socket shut down by stop() ends here too: not a failure
            rec.status = ABORTED if self._cut.is_set() else FAILED
            rec.error = repr(e)[:300]
        finally:
            with self._lock:
                self._socks.discard(sock)
            conn.close()
        return rec

    def _read_stream(self, resp, rec: Record) -> None:
        got: list = []
        for event in sse_events(resp):
            now = time.monotonic()
            if event.get("done"):
                rec.done = now
                full = event["tokens"]
                if full[rec.prompt_len:] != got:
                    raise ValueError("done event differs from the spans")
                if len(got) != rec.max_new:
                    raise ValueError(f"{len(got)} tokens, wanted {rec.max_new}")
                if not all(isinstance(t, int) and 0 <= t < self.vocab
                           for t in got):
                    raise ValueError("token outside the vocabulary")
                rec.status = OK
                return
            toks = event["tokens"]
            if not rec.first:
                rec.first = now
            rec.spans.append((now, len(toks)))
            rec.n_tokens += len(toks)
            got.extend(toks)
        raise ValueError("stream ended without a done event")

    # -- loops -------------------------------------------------------------

    def start(self) -> None:
        self.t_start = time.monotonic()
        if self.mix["loop"] == "open":
            self._spawn(self._dispatch)
        elif self.mix["loop"] == "closed":
            for _ in range(traffic.n_clients(self.mix, self.slots)):
                self._spawn(self._client)
        else:
            raise ValueError(f"loop {self.mix['loop']!r}: open or closed")

    def _spawn(self, target, *args) -> None:
        t = threading.Thread(target=target, args=args, daemon=True)
        with self._lock:
            self._threads.append(t)
        t.start()

    def _dispatch(self) -> None:
        while not self._stop.is_set():
            i = self._claim()
            due = self.t_start + traffic.arrival(self.mix, self.seed, i)
            if self._stop.wait(max(0.0, due - time.monotonic())):
                return
            self._spawn(self._request, i, due)

    def _client(self) -> None:
        while not self._stop.is_set():
            self._request(self._claim(), None)

    def in_flight(self) -> int:
        with self._lock:
            return sum(1 for r in self.records if r.sent and not r.status)

    def _alive(self) -> list:
        with self._lock:
            return [t for t in self._threads if t.is_alive()]

    def stop(self, finish_s: float = 0.0) -> None:
        """End the load. No further request is sent; streams in flight get
        ``finish_s`` seconds to run to their end (after the window, so that
        the replica is stopped idle and not under a mass cancel), and what
        is left then is cut at the socket, which also frees its decode
        lane on the server. Nothing of this is inside the window."""
        self._stop.set()
        deadline = time.monotonic() + finish_s
        while time.monotonic() < deadline:
            alive = self._alive()
            if not alive:
                break
            alive[0].join(timeout=0.05)
        self._cut.set()
        with self._lock:
            socks = list(self._socks)
        for sock in socks:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        deadline = time.monotonic() + 30.0
        while True:
            alive = self._alive()
            if not alive or time.monotonic() > deadline:
                break
            alive[0].join(timeout=0.2)
        if alive:
            raise RuntimeError(f"{len(alive)} client threads did not end")


def generate_once(port: int, prompt: list, max_new: int) -> list:
    """One greedy request outside any load; returns the new tokens."""
    body = json.dumps({"jsonData": {"prompt_tokens": [prompt],
                                    "max_new_tokens": max_new,
                                    "temperature": 0.0}}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=REQUEST_TIMEOUT_S)
    try:
        conn.request("POST", "/api/v0.1/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise ValueError(f"status {resp.status}: {resp.read()[:200]!r}")
        for event in sse_events(resp):
            if event.get("done"):
                return event["tokens"][len(prompt):]
        raise ValueError("stream ended without a done event")
    finally:
        conn.close()
