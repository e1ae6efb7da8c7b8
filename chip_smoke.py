#!/usr/bin/env python3
"""On-chip smoke: the 1.26B decoder served through ``engine_main`` on the TPU.

    python chip_smoke.py                 # the on-chip check (needs a TPU)
    python chip_smoke.py --rehearse-cpu  # builder's CPU rehearsal, tiny model

Drives the repo's main path — ``engine_main`` -> ``EngineApp`` ->
``GenerateServer`` -> ``ContinuousBatcher`` -> ``DecoderLM`` — once, at the
full width of the ``llm-1.26b`` flagship (random weights from a seed, the
model's own 1024-token cache), over the wire a user would use: unary REST,
SSE and gRPC. It reports what it observed and claims no speed.

One process per chip. This parent never imports ``jax``. Each leg is a child
that takes the chip, finishes and releases it before the next starts:

* ``kernel``: names the device, jit-compiles the Pallas flash kernel at the
  flagship's shapes (unequal tiles, a lead tile, and once behind a visible
  prefix), compares it with the XLA reference on the same device, and
  checks that the compiled prefill executable of every >=128 bucket holds
  the Mosaic call.
* ``serve_one_chip``: the engine child on one chip, every request checked.
* ``serve_four_chips``: with >=4 chips, the same through the
  ``data=1,model=4`` serving mesh in ONE process; greedy tokens must equal
  the one-chip leg's and all four chips must hold their share of the bytes.

No leg shares a compilation with another, so no persistent compile cache is
set up. Everything the run needs (model dir, spec, logs) is generated under
``--out`` from seeds and tracked files. Children run with ``JAX_PLATFORMS``
set explicitly, so JAX raises where it would otherwise carry on on the CPU.

The last line of stdout is one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
``ok`` is true only when every leg that could run passed. The line before
it, ``chip_smoke summary: {...}``, carries the versions, one entry per leg
and the run's observations. Without a TPU the run exits non-zero within
seconds, before any model is built, and prints no result.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import functools
import http.client
import inspect
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
T0 = time.monotonic()

# the 1.26B flagship (head_dim 128, 2:1 grouped queries); the seed picks
# the random weights (jaxserver reads config["seed"])
FLAGSHIP = {
    "vocab_size": 32000, "d_model": 2048, "n_layers": 24,
    "n_heads": 16, "n_kv_heads": 8, "d_ff": 5632,
    "max_seq": 1024, "residual_scale": 0.05, "seed": 21,
}
# --rehearse-cpu only: same head_dim (128) and cache length, 4-way shardable
TINY = {
    "vocab_size": 256, "d_model": 512, "n_layers": 2,
    "n_heads": 4, "n_kv_heads": 4, "d_ff": 512,
    "max_seq": 1024, "residual_scale": 0.05, "seed": 21,
}
SLOTS = 4
MAX_NEW = 16
# one prompt under 128 (bucket 32, XLA attention), one at 128 and one in the
# 1024 bucket (Pallas kernel); also the warm-up lengths the spec declares
PROMPT_LENS = (24, 128, 700)
# flash_attention leg: H and Dh from FLAGSHIP, these sequence lengths
# (attention()'s tile: 128 x 128 at 128, 256 query rows x 512 keys from 512,
# behind a lead tile of 256 at 1792), and the last behind a prefix of
# KERNEL_PREFIX rows of which KERNEL_VISIBLE are seen (it ends mid-tile)
KERNEL_LENS = (128, 512, 1024, 1792)
KERNEL_PREFIX, KERNEL_VISIBLE = 896, 300
# bf16 tolerance: max|kernel - xla| <= KERNEL_TOL * max(1, max|xla|), about
# two and a half bf16 ulps (2^-7 relative each) at the output's magnitude
KERNEL_TOL = 2e-2
MESH_SHAPE = "data=1,model=4"
# after load, the fullest of the four chips may hold at most this many times
# the emptiest one's bytes_in_use, and none may be near zero
MESH_BYTES_FACTOR = 1.5
# a compile this long after /ready means a request waited on an executable
# the declared warm-up lengths should have covered
SLOW_COMPILE_S = 1.0

KERNEL_LEG_TIMEOUT_S = 300
READY_TIMEOUT_S = 720
REQUEST_TIMEOUT_S = 120
SHUTDOWN_TIMEOUT_S = 60


class LegFailed(Exception):
    """A check of the current leg did not hold."""


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def child_env(rehearse: bool) -> dict:
    """Environment of a child that takes the chip. A parent that had touched
    JAX would hold the chip itself, so the child would fail or hang."""
    if "jax" in sys.modules:
        raise RuntimeError("chip_smoke's parent imported jax; it must stay off it")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
    if rehearse:
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_LOG_COMPILES"] = "1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ---------------------------------------------------------------------------
# kernel leg — runs in a child that owns the chip
# ---------------------------------------------------------------------------

def child_kernel(rehearse: bool) -> int:
    """Name the device; on the chip, compile and check the flash kernel.
    Prints one JSON line. The only function here that imports jax."""
    import importlib.metadata

    import jax
    import jaxlib

    try:
        devices = jax.devices()  # JAX_PLATFORMS=tpu: raises when there is none
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no TPU: {e}", file=sys.stderr)
        return 1
    report = {
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu"),
        },
    }
    ok = True
    if not rehearse:
        if devices[0].platform != "tpu":
            print(f"chip_smoke: JAX runs on {devices[0].platform!r}, not a TPU",
                  file=sys.stderr)
            return 1
        report["kernel"] = _kernel_checks()
        report["prefill_mosaic"] = _prefill_mosaic_checks()
        ok = all(r["ok"] for r in report["kernel"]) and all(
            r["ok"] for r in report["prefill_mosaic"]
        )
    print(json.dumps(report), flush=True)
    return 0 if ok else 1


def _kernel_checks() -> list:
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.ops.flash_attention import (
        _prefixed_attention, _xla_attention, attention)

    heads = FLAGSHIP["n_heads"]
    head_dim = FLAGSHIP["d_model"] // heads
    rows = []
    for t, prefix in [(t, None) for t in KERNEL_LENS] + [
            (KERNEL_LENS[-1], KERNEL_PREFIX)]:
        q, k, v = (
            jax.random.normal(key, (1, heads, n, head_dim), jnp.bfloat16)
            for key, n in zip(jax.random.split(jax.random.PRNGKey(t), 3),
                              (t, t + (prefix or 0), t + (prefix or 0)))
        )
        # attention() itself, so the tile is the one serving gets
        if prefix is None:
            kernel = attention
            reference = functools.partial(_xla_attention, causal=True)
        else:
            kernel = functools.partial(
                attention, prefix=prefix, prefix_len=jnp.int32(KERNEL_VISIBLE))
            reference = functools.partial(
                _prefixed_attention, prefix=prefix,
                prefix_len=jnp.int32(KERNEL_VISIBLE))
        compiled = jax.jit(kernel).lower(q, k, v).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        got = compiled(q, k, v).astype(jnp.float32)
        ref = jax.jit(reference)(q, k, v).astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - ref)))
        bound = KERNEL_TOL * max(1.0, float(jnp.max(jnp.abs(ref))))
        finite = bool(jnp.isfinite(got).all())
        rows.append({
            "T": t, "prefix": prefix, "mosaic": mosaic, "finite": finite,
            "max_abs_err": err, "bound": bound,
            "ok": mosaic and finite and err <= bound,
        })
    return rows


def _prefill_mosaic_checks() -> list:
    """Compile DecoderLM.prefill at the flagship's width for each prompt
    bucket the smoke sends and look for the Mosaic call in the optimised
    HLO: attention() picks its XLA branch silently, this does not trust it."""
    import jax
    import jax.numpy as jnp

    from seldon_core_tpu.models.llm import DecoderLM

    model = DecoderLM(**FLAGSHIP)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: model.init_params(0)),
    )
    rows = []
    for bucket in sorted({bucket_of(n) for n in PROMPT_LENS}):
        prompt = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        compiled = jax.jit(
            lambda p, t, b=bucket: model.prefill(p, t, b)
        ).lower(params, prompt).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        rows.append({
            "bucket": bucket, "mosaic": mosaic,
            "ok": mosaic == (bucket >= 128),
        })
    return rows


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------

def bucket_of(n: int) -> int:
    """The prefill bucket ContinuousBatcher pads an n-token prompt to."""
    from seldon_core_tpu.serving.continuous import ContinuousBatcher

    buckets = inspect.signature(
        ContinuousBatcher.__init__
    ).parameters["prefill_buckets"].default
    return next(b for b in sorted(buckets) if n <= b)


def run_kernel_leg(out: str, rehearse: bool) -> dict:
    """Spawn the kernel child; returns its report. Raises LegFailed."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child-kernel"]
    if rehearse:
        cmd.append("--rehearse-cpu")
    err_path = os.path.join(out, "kernel.stderr.log")
    with open(err_path, "w") as err:
        proc = subprocess.run(
            cmd, cwd=REPO, env=child_env(rehearse), stdout=subprocess.PIPE,
            stderr=err, text=True, timeout=KERNEL_LEG_TIMEOUT_S,
        )
    lines = proc.stdout.strip().splitlines()
    report = json.loads(lines[-1]) if lines else None
    if proc.returncode != 0:
        detail = json.dumps(report) if report else log_tail(err_path, 3)
        raise LegFailed(f"kernel child exited {proc.returncode}: {detail}")
    return report


def write_model_and_spec(out: str, config: dict, mesh_shape: str = "") -> str:
    """Model dir by the normal jax_config.json route plus a GENERATE_SERVER
    predictor spec; every knob default except slots and the warm-up
    lengths (and the mesh on the sharded leg). Returns the spec path."""
    from seldon_core_tpu.graph.spec import (
        PredictorSpec, default_predictor, validate_predictor,
    )
    from seldon_core_tpu.testing import write_model_dir

    model_dir = write_model_dir(out, "llm", config)
    parameters = [
        {"name": "slots", "value": str(SLOTS), "type": "INT"},
        {"name": "warmup_prompt_lens", "type": "STRING",
         "value": ",".join(str(n) for n in PROMPT_LENS)},
        {"name": "warmup_max_new_tokens", "value": str(MAX_NEW), "type": "INT"},
    ]
    if mesh_shape:
        parameters.append(
            {"name": "mesh_shape", "value": mesh_shape, "type": "STRING"}
        )
    spec = {
        "name": "chip-smoke",
        "graph": {
            "name": "lm", "type": "MODEL",
            "implementation": "GENERATE_SERVER",
            "modelUri": model_dir, "parameters": parameters,
        },
    }
    validate_predictor(default_predictor(PredictorSpec.from_dict(spec)))
    path = os.path.join(out, "spec.json")
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)
    return path


def make_prompts(vocab: int) -> list:
    rng = random.Random(FLAGSHIP["seed"])
    return [[rng.randrange(vocab) for _ in range(n)] for n in PROMPT_LENS]


def gen_body(prompt: list) -> dict:
    return {"jsonData": {"prompt_tokens": [prompt], "max_new_tokens": MAX_NEW,
                         "temperature": 0.0}}


def check_tokens(what: str, prompt: list, tokens: list, vocab: int) -> list:
    """prompt echoed, exactly MAX_NEW new tokens, all inside the vocabulary."""
    if tokens[:len(prompt)] != prompt:
        raise LegFailed(f"{what}: prompt not echoed")
    new = tokens[len(prompt):]
    if len(new) != MAX_NEW:
        raise LegFailed(f"{what}: {len(new)} new tokens, wanted {MAX_NEW}")
    if not all(isinstance(t, int) and 0 <= t < vocab for t in new):
        raise LegFailed(f"{what}: token outside [0, {vocab}): {new}")
    return new


def rest_generate(port: int, prompt: list) -> list:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v0.1/predictions",
        data=json.dumps(gen_body(prompt)).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as resp:
            return json.load(resp)["jsonData"]["tokens"][0]
    except urllib.error.HTTPError as e:  # any status other than 2xx
        raise LegFailed(
            f"rest[{len(prompt)}]: status {e.code}: {e.read()[:300]!r}"
        ) from e
    except (urllib.error.URLError, OSError) as e:
        raise LegFailed(f"rest[{len(prompt)}]: {e!r}") from e


def sse_generate(port: int, prompt: list) -> tuple:
    """Returns (concatenated token spans, the done event's full list)."""
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=REQUEST_TIMEOUT_S)
    try:
        conn.request("POST", "/api/v0.1/generate",
                     body=json.dumps(gen_body(prompt)),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise LegFailed(f"sse: status {resp.status}: {resp.read()[:300]!r}")
        spans: list = []
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            event = json.loads(line[len("data:"):])
            if event.get("done"):
                return spans, event["tokens"]
            spans.extend(event["tokens"])
        raise LegFailed("sse: stream ended without a done event")
    finally:
        conn.close()


def grpc_generate(port: int, prompt: list) -> list:
    import grpc

    from seldon_core_tpu.proto import prediction_pb2 as pb
    from seldon_core_tpu.proto.services import method_path

    request = pb.SeldonMessage(json_data=json.dumps(gen_body(prompt)["jsonData"]))
    with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
        rpc = channel.unary_unary(
            method_path("Seldon", "Predict"),
            request_serializer=pb.SeldonMessage.SerializeToString,
            response_deserializer=pb.SeldonMessage.FromString,
        )
        try:
            out = rpc(request, timeout=REQUEST_TIMEOUT_S)
        except grpc.RpcError as e:
            raise LegFailed(f"grpc: {e.code()}: {e.details()}") from e
    return json.loads(out.json_data)["tokens"][0]


def http_get(port: int, path: str, timeout: float = 5.0) -> tuple:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout
        ) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, ""
    except (urllib.error.URLError, OSError):
        return 0, ""


READY_RE = re.compile(
    r"generateserver: .* ready \(.*\) platform=(?P<platform>\S+) "
    r"device_kind='(?P<kind>[^']*)' visible_devices=(?P<visible>\d+) "
    r"serving_devices=(?P<serving>\d+) bytes_in_use=\[(?P<bytes>[^\]]*)\] "
    r"load_s=(?P<load>[\d.]+) warm_s=(?P<warm>[\d.]+)"
)
COMPILED_RE = re.compile(
    r"Finished XLA compilation of (?P<name>\S+) in (?P<s>[\d.eE+-]+) sec"
)


def log_tail(path: str, n: int = 40) -> str:
    """The last n lines of a child's log, JAX_LOG_COMPILES chatter left out."""
    with open(path, errors="replace") as f:
        lines = [ln.rstrip() for ln in f if ln.strip()
                 and "Compiling jit(" not in ln and "Finished " not in ln]
    return "\n".join(lines[-n:])


def run_serve_leg(name: str, out: str, config: dict, rehearse: bool,
                  mesh_shape: str = "") -> dict:
    """One engine_main child that owns the chip(s): load, warm, answer every
    request, stop. Returns the leg's observations. Raises LegFailed."""
    from seldon_core_tpu.testing import free_port

    leg_dir = os.path.join(out, name)
    os.makedirs(leg_dir)
    spec_path = write_model_and_spec(leg_dir, config, mesh_shape)
    http_port, grpc_port = free_port(), free_port()
    log_path = os.path.join(leg_dir, "engine.log")
    vocab = config["vocab_size"]
    prompts = make_prompts(vocab)
    obs: dict = {}
    t_start = time.monotonic()
    with open(log_path, "w") as engine_log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "seldon_core_tpu.engine_main",
             "--spec", spec_path, "--host", "127.0.0.1",
             "--http-port", str(http_port), "--grpc-port", str(grpc_port)],
            cwd=REPO, env=child_env(rehearse), stdout=engine_log,
            stderr=subprocess.STDOUT,
        )
    try:
        # -- ready ---------------------------------------------------------
        while http_get(http_port, "/ready")[0] != 200:
            if proc.poll() is not None:
                raise LegFailed(f"engine exited {proc.returncode} before /ready")
            if time.monotonic() - t_start > READY_TIMEOUT_S:
                raise LegFailed(f"/ready not 200 after {READY_TIMEOUT_S}s")
            time.sleep(1.0)
        obs["ready_s"] = round(time.monotonic() - t_start, 1)
        with open(log_path, errors="replace") as f:
            ready = next((m for m in map(READY_RE.search, f) if m), None)
        if ready is None:
            raise LegFailed("no ready line naming the device in the engine log")
        obs.update(
            platform=ready["platform"], device_kind=ready["kind"],
            serving_devices=int(ready["serving"]),
            load_s=float(ready["load"]), warm_s=float(ready["warm"]),
        )
        want = "cpu" if rehearse else "tpu"
        if obs["platform"] != want:
            raise LegFailed(f"engine serves on {obs['platform']!r}, not {want!r}")
        log(f"{name}: ready in {obs['ready_s']}s on {obs['platform']} "
            f"{obs['device_kind']!r} x{obs['serving_devices']} "
            f"(load {obs['load_s']}s, warm {obs['warm_s']}s)")
        if mesh_shape:
            obs["bytes_in_use"] = check_mesh_bytes(ready["bytes"])

        # -- unary REST, each prompt twice ----------------------------------
        t0 = time.monotonic()
        first = rest_generate(http_port, prompts[0])
        obs["first_answer_s"] = round(time.monotonic() - t0, 2)
        greedy = [first] + [rest_generate(http_port, p) for p in prompts[1:]]
        for p, toks in zip(prompts, greedy):
            check_tokens(f"rest[{len(p)}]", p, toks, vocab)
            if rest_generate(http_port, p) != toks:
                raise LegFailed(f"rest[{len(p)}]: repeated greedy prompt differs")
        total = 2 * len(prompts) * MAX_NEW
        log(f"{name}: unary REST ok, repeats identical")

        # -- SSE and gRPC agree with unary ----------------------------------
        for p, toks in zip(prompts, greedy):
            spans, done = sse_generate(http_port, p)
            if done != toks or spans != toks[len(p):]:
                raise LegFailed(f"sse[{len(p)}]: stream differs from unary")
            total += MAX_NEW
        if grpc_generate(grpc_port, prompts[1]) != greedy[1]:
            raise LegFailed("grpc: Seldon/Predict differs from unary REST")
        total += MAX_NEW
        log(f"{name}: SSE and gRPC agree with unary")

        # -- concurrent waves: mixed buckets, then one bucket ---------------
        waves = [
            [prompts[0], prompts[0][::-1], prompts[1], prompts[2]],
            [prompts[1][i:] + prompts[1][:i] for i in range(SLOTS)],
        ]
        with concurrent.futures.ThreadPoolExecutor(SLOTS) as pool:
            for wave in waves:
                for p, toks in zip(wave, pool.map(
                    functools.partial(rest_generate, http_port), wave
                )):
                    check_tokens(f"concurrent[{len(p)}]", p, toks, vocab)
                    total += MAX_NEW
        obs["tokens_generated"] = total
        log(f"{name}: {sum(map(len, waves))} concurrent requests ok")

        # -- the scheduler never restarted ----------------------------------
        _status, prom = http_get(http_port, "/prometheus", timeout=30.0)
        restarts = series_values(prom, "seldon_engine_batcher_restarts")
        healthy = series_values(prom, "seldon_engine_batcher_healthy")
        if any(v != 0 for v in restarts) or healthy != [1.0]:
            raise LegFailed(
                f"/prometheus: batcher_restarts={restarts} healthy={healthy}"
            )

        # -- told to stop, it exits 0 ---------------------------------------
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(SHUTDOWN_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise LegFailed(f"engine still up {SHUTDOWN_TIMEOUT_S}s after SIGTERM")
        if rc != 0:
            raise LegFailed(f"engine exited {rc} on SIGTERM")

        # -- no request waited on a compile ---------------------------------
        slow = slow_compiles_after_ready(log_path)
        if slow:
            raise LegFailed(f"compiled after /ready (request waited): {slow}")
        obs["greedy_new_tokens"] = [t[len(p):] for p, t in zip(prompts, greedy)]
        return obs
    except LegFailed:
        print(f"--- {name}: engine log tail ---\n{log_tail(log_path)}",
              file=sys.stderr)
        raise
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def series_values(prom_text: str, series: str) -> list:
    return [
        float(line.rsplit(" ", 1)[1]) for line in prom_text.splitlines()
        if line.startswith(series + "{") or line.startswith(series + " ")
    ]


def slow_compiles_after_ready(log_path: str) -> list:
    after_ready, slow = False, set()
    with open(log_path, errors="replace") as f:
        for line in f:
            after_ready = after_ready or READY_RE.search(line) is not None
            m = COMPILED_RE.search(line) if after_ready else None
            if m and float(m["s"]) >= SLOW_COMPILE_S:
                slow.add((m["name"], round(float(m["s"]), 1)))
    return sorted(slow)


def check_mesh_bytes(raw: str) -> list:
    """Every chip of the mesh holds its share: code that never ran on more
    than one device may have put everything on the first."""
    values = [v.strip() for v in raw.split(",")]
    if "None" in values:  # CPU rehearsal: the backend keeps no memory stats
        return values
    per_chip = [int(v) for v in values]
    if min(per_chip) <= 0 or max(per_chip) > MESH_BYTES_FACTOR * min(per_chip):
        raise LegFailed(
            f"bytes_in_use per chip {per_chip}: not within "
            f"{MESH_BYTES_FACTOR}x of each other"
        )
    return per_chip


def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout. Whoever runs the smoke parses it and refuses
    any other key: the legs and observations go on the summary line."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"]),
    }})


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                      "chip_smoke"))
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="builder's rehearsal: tiny model on the CPU, "
                             "no kernel leg, never prints an ok result")
    parser.add_argument("--child-kernel", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child_kernel:
        return child_kernel(args.rehearse_cpu)

    if not os.path.isdir(os.path.join(REPO, "seldon_core_tpu")):
        print("chip_smoke: no seldon_core_tpu/ beside this script — it "
              "checks the repo it sits in", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    rehearse = args.rehearse_cpu
    config = TINY if rehearse else FLAGSHIP
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    if rehearse:
        log("REHEARSAL on the CPU at a tiny size: proves nothing about the chip")

    try:
        report = run_kernel_leg(args.out, rehearse)
    except LegFailed as e:
        # no accelerator (or no kernel): say so and print no result
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    device = report["device"]
    log(f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} versions={report['versions']}")
    for row in report.get("kernel", []) + report.get("prefill_mosaic", []):
        log(f"kernel: {row}")
    legs = {"kernel": "not run: rehearsal" if rehearse else "pass"}
    ok = False
    summary = {"device": device, "versions": report["versions"], "legs": legs}
    try:
        one = run_serve_leg("serve_one_chip", args.out, config, rehearse)
        legs["serve_one_chip"] = "pass"
        # observations of one smoke run, not metrics
        summary["smoke_observations"] = {
            k: one[k] for k in ("load_s", "warm_s", "first_answer_s",
                                "tokens_generated")
        }
        if device["count"] >= 4:
            four = run_serve_leg("serve_four_chips", args.out, config,
                                 rehearse, mesh_shape=MESH_SHAPE)
            if four["greedy_new_tokens"] != one["greedy_new_tokens"]:
                raise LegFailed(
                    "serve_four_chips: greedy tokens differ from one chip: "
                    f"{four['greedy_new_tokens']} vs {one['greedy_new_tokens']}"
                )
            legs["serve_four_chips"] = "pass"
            summary["four_chip_bytes_in_use"] = four["bytes_in_use"]
        else:
            legs["serve_four_chips"] = (
                f"not run: {device['count']} device(s) visible, needs 4"
            )
        ok = not rehearse
        if rehearse:
            summary["rehearsal"] = "cpu, tiny model: not a result"
    except (LegFailed, subprocess.TimeoutExpired) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        legs["failed"] = str(e)[:300]
    print(f"chip_smoke summary: {json.dumps(summary)}", flush=True)
    print(result_line(ok, device), flush=True)
    return 0 if ok or (rehearse and "failed" not in legs) else 1


if __name__ == "__main__":
    sys.exit(main())
