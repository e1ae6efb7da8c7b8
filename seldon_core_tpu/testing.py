"""Helpers for driving the served stack from tests and smoke scripts.

``free_port`` (the control plane's own) hands out a loopback port,
``write_model_dir`` lays out the ``jax_config.json`` directory jaxserver
loads, and ``EngineHarness`` serves an ``EngineApp`` over an in-process
unit on real sockets. Used by the socket-level tests, the
``tools/*_smoke.py`` scripts CI runs, and ``chip_smoke.py``, whose parent
must stay off jax: importing this module must not import jax.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
from typing import Any, Dict, Optional

from .controlplane.runtime import free_port

__all__ = ["free_port", "write_model_dir", "EngineHarness"]


def write_model_dir(root: str, family: str, config: Dict[str, Any]) -> str:
    """Materialise a jax_config.json model dir (random-init params, the
    layout jaxserver loads via the storage path)."""
    model_dir = os.path.join(root, family)
    os.makedirs(model_dir, exist_ok=True)
    with open(os.path.join(model_dir, "jax_config.json"), "w") as f:
        json.dump({"family": family, "config": config}, f)
    return model_dir


class EngineHarness:
    """EngineApp over an in-process unit, served on real sockets from a
    background event-loop thread."""

    def __init__(
        self,
        component=None,
        unit_name: str = "model",
        name: str = "bench",
        batching: Optional[Dict[str, Any]] = None,
        annotations: Optional[Dict[str, str]] = None,
        faults=None,
        graph: Optional[Dict[str, Any]] = None,
        registry: Optional[Dict[str, Any]] = None,
        metrics=None,
    ):
        # ``batching`` is ONE unit's MicroBatcher kwargs (max_batch/
        # timeout_ms/...); it is wrapped as {unit_name: batching} for
        # EngineApp, which takes the per-unit mapping form. ``faults`` is
        # a resilience.FaultInjector for degraded-mode scenarios.
        # ``graph``/``registry`` serve multi-unit graphs (the RAG/fusion
        # smoke); the default stays the single in-process MODEL node.
        from .graph.service import EngineApp
        from .graph.spec import PredictorSpec, default_predictor

        spec = default_predictor(
            PredictorSpec.from_dict(
                {
                    "name": name,
                    "graph": graph or {"name": unit_name, "type": "MODEL"},
                    **({"annotations": annotations} if annotations else {}),
                }
            )
        )
        self.app = EngineApp(
            spec,
            registry=registry if registry is not None else {unit_name: component},
            batching={unit_name: batching} if batching else None,
            faults=faults,
            # side-by-side engines (the fusion smoke's fused vs plain vs
            # chaos trio) need isolated registries or one engine's
            # counters leak into another's /metrics assertions
            **({"metrics": metrics} if metrics is not None else {}),
        )
        self.http_port = free_port()
        self.grpc_port = free_port()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._stopped = threading.Event()

    def start(self) -> "EngineHarness":
        started = threading.Event()

        def run():
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            self._loop = loop
            stop = asyncio.Event()
            self._stop_event = stop

            async def amain():
                http = self.app.rest_app()
                await http.start("127.0.0.1", self.http_port)
                gsrv = self.app.grpc_server()
                gsrv.add_insecure_port(f"127.0.0.1:{self.grpc_port}")
                await gsrv.start()
                started.set()
                await stop.wait()
                http.close()
                await gsrv.stop(grace=0.1)
                await self.app.executor.close()

            loop.run_until_complete(amain())
            loop.close()
            self._stopped.set()

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        if not started.wait(120.0):
            raise RuntimeError("engine harness failed to start within 120s")
        return self

    def stop(self) -> None:
        if self._loop is not None and not self._stopped.is_set():
            self._loop.call_soon_threadsafe(self._stop_event.set)
            self._stopped.wait(10.0)
