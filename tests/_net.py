"""Socket helpers shared by the socket-level tests.

A plain module (not conftest) so it stays importable under
``--import-mode=importlib``.
"""

import json
import socket
import threading

from seldon_core_tpu.testing import free_port


class FixedResponseServer:
    """Minimal HTTP server that answers every POST with one fixed JSON body.

    Stands in for a remote microservice when a test needs a response the
    builtin units can't produce (e.g. ragged ndarrays)."""

    def __init__(self, body: dict):
        self.raw = json.dumps(body).encode()
        self.port = free_port()
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", self.port))
        self._srv.listen(8)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,), daemon=True).start()

    def _serve(self, conn):
        try:
            buf = b""
            while not self._stop.is_set():
                while b"\r\n\r\n" not in buf:
                    chunk = conn.recv(65536)
                    if not chunk:
                        return
                    buf += chunk
                head, _, rest = buf.partition(b"\r\n\r\n")
                clen = 0
                for line in head.split(b"\r\n"):
                    if line.lower().startswith(b"content-length:"):
                        clen = int(line.split(b":")[1])
                while len(rest) < clen:
                    chunk = conn.recv(65536)
                    if not chunk:
                        return
                    rest += chunk
                buf = rest[clen:]
                conn.sendall(
                    b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
                    b"Content-Length: " + str(len(self.raw)).encode() + b"\r\n\r\n" + self.raw
                )
        except OSError:
            pass
        finally:
            conn.close()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._srv.close()


def post_predictions(port: int, body: bytes,
                     content_type: str = "application/json"):
    """POST ``body`` to an engine's predictions route -> (status, bytes)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/api/v0.1/predictions", body,
                     {"Content-Type": content_type})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def grpc_predict(port: int, request: bytes) -> bytes:
    """One ``Seldon/Predict`` call, serialized SeldonMessage in and out."""
    import grpc

    from seldon_core_tpu.proto.services import method_path

    with grpc.insecure_channel(f"127.0.0.1:{port}") as ch:
        rpc = ch.unary_unary(method_path("Seldon", "Predict"),
                             request_serializer=lambda b: b,
                             response_deserializer=lambda b: b)
        return rpc(request, timeout=120.0)


def wait_port(port: int, timeout: float = 5.0) -> None:
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            s = socket.create_connection(("127.0.0.1", port), 0.2)
            s.close()
            return
        except OSError:
            time.sleep(0.02)
    raise TimeoutError(f"port {port} never opened")


def serve_on_thread(serve_coro, port=None):
    """Run a ``serve_forever``-style coroutine on its own event-loop thread.

    Returns a ``stop()`` callable. Teardown CANCELS the serve task (so its
    finally blocks run) instead of ``loop.stop()`` — a bare stop leaves
    ``run_until_complete`` raising "Event loop stopped before Future
    completed" into the thread, which pytest reports as
    PytestUnhandledThreadExceptionWarning at whatever later test happens to
    trigger the GC.
    """
    import asyncio

    loop = asyncio.new_event_loop()
    box = {}
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)
        box["task"] = loop.create_task(serve_coro)
        started.set()
        try:
            loop.run_until_complete(box["task"])
        except asyncio.CancelledError:
            pass
        finally:
            try:
                loop.close()
            except Exception:
                pass

    t = threading.Thread(target=run, daemon=True)
    t.start()
    started.wait(5)
    if port is not None:
        wait_port(port)

    def stop():
        task = box.get("task")
        if task is not None and not loop.is_closed():
            try:
                loop.call_soon_threadsafe(task.cancel)
            except RuntimeError:
                pass  # loop already closed
        t.join(timeout=5)

    return stop
