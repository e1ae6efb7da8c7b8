"""Test env: force JAX onto a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; all sharding tests run against
``--xla_force_host_platform_device_count=8`` (the driver separately
dry-runs the multi-chip path via __graft_entry__.dryrun_multichip).
Must run before jax is imported anywhere.
"""

import os

# Runtime thread-role assertions for the WHOLE tier-1 run: the
# @scheduler_only/@caller_thread decorators (analysis/roles.py) check the
# executing thread on every decorated call, so a scheduler-thread
# violation fails a test loudly instead of corrupting device state.
# Must be set before any seldon_core_tpu import (the decorators read it
# at import time); set here, it covers every test module.
os.environ.setdefault("SELDON_DEBUG_THREADS", "1")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on the CPU whatever the environment says (the chip is reached
# only through chip_smoke.py): pin the platform before any backend is
# initialized.
import jax

jax.config.update("jax_platforms", "cpu")

# XLA compilation cache for the whole tier-1 run: the suite's wall clock
# is dominated by XLA re-compiling IDENTICAL tiny-model executables —
# every ContinuousBatcher instance closes over fresh param references,
# so jit's in-memory cache (keyed on the function object) never hits
# across instances, while the persistent cache keys on the HLO
# fingerprint and does. One process-lifetime directory (override with
# SELDON_TEST_JAX_CACHE to share across runs); same HLO -> same binary,
# so cached executables are bit-identical to cold compiles and the
# byte-identity contracts are unaffected.
import atexit as _atexit
import shutil as _shutil
import tempfile as _tempfile

_jax_cache = os.environ.get("SELDON_TEST_JAX_CACHE")
if not _jax_cache:
    # process-lifetime scratch dir: removed at exit so repeated runs on
    # long-lived runners don't accumulate compiled binaries in /tmp
    _jax_cache = _tempfile.mkdtemp(prefix="seldon-jax-cache-")
    _atexit.register(_shutil.rmtree, _jax_cache, ignore_errors=True)
jax.config.update("jax_compilation_cache_dir", _jax_cache)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import asyncio
import json as _json

import pytest

from seldon_core_tpu.http_server import Request


class RestTestClient:
    """In-process REST client (no sockets), like flask's test_client
    (reference tests: python/tests/test_model_microservice.py:1-40)."""

    def __init__(self, app):
        self.app = app

    def call(self, path: str, body=None, method: str = "POST", query: str = "",
             headers=None):
        raw = _json.dumps(body).encode() if body is not None else b""
        hdrs = {"content-type": "application/json"} if raw else {}
        hdrs.update(headers or {})
        req = Request(method, path, query, hdrs, raw)
        resp = asyncio.run(self.app._dispatch(req))
        payload = _json.loads(resp.body) if resp.body else None
        return resp.status, payload


@pytest.fixture
def rest_client():
    return RestTestClient


# make tests/ importable as top-level modules (``from _net import ...``)
# under any pytest import mode
import sys as _sys
_sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
