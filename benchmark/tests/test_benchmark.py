"""The benchmark's own tests: CPU only, seconds each, every wait bounded.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They live here and not under ``tests/`` because the PR that defines the
benchmark may add files only under the benchmark's own directories.
"""

from __future__ import annotations

import copy
import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import endtoend, manifest, trace, traffic  # noqa: E402
from benchmark.client import ABORTED, OK, Load, Record  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


@pytest.fixture(scope="module")
def decoder(man):
    """The ``decoder`` architecture's module, found as a run finds it."""
    return manifest.architecture(ROOT, man, "decoder")


# -- the manifest ------------------------------------------------------------

def test_manifest_is_valid_and_every_file_is_found(man):
    for cell in man["workloads"]:
        cfg = manifest.config(ROOT, man, cell["config"])
        mix = manifest.traffic(ROOT, man, cell["traffic"])
        arch = manifest.architecture(ROOT, man, cfg["architecture"])
        assert arch is manifest.architecture(ROOT, man, cfg["architecture"])
        assert arch.model_kwargs(cfg, 2**31 + 5)["seed"] < 2**31
        assert set(arch.rehearsal(cfg)) <= set(cfg)
        assert traffic.cycle(mix)
        for group in ("end_to_end", "per_layer"):
            assert manifest.metrics_of(man, group, cell["name"])
        for m in manifest.metrics_of(man, "per_layer", cell["name"]):
            assert callable(manifest.layer_reader(ROOT, man, m["name"]))


def _config_file(tmp_path, **change):
    """A copy of the first configuration's file with ``change`` applied
    (``None`` drops the key), for a manifest entry to point at."""
    with open(os.path.join(ROOT, "benchmark", "configs", "internlm2-1.8b.json")) as f:
        cfg = json.load(f)
    for key, value in change.items():
        cfg.pop(key) if value is None else cfg.update({key: value})
    path = tmp_path / "changed.json"
    path.write_text(json.dumps(cfg))
    return str(path)


@pytest.mark.parametrize("mutate, what", [
    (lambda m, _t: m["workloads"][0].update(name="has space"), "bad name"),
    (lambda m, _t: m["end_to_end"][0].update(unit="tokens per second"), "bad unit"),
    (lambda m, _t: m["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda m, _t: m["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda m, _t: m["workloads"][0].update(config="absent"), "no config"),
    (lambda m, _t: m.update(extra=1), "keys"),
    (lambda m, _t: m["end_to_end"].pop(), "setup_s"),
    # a configuration names its architecture, and the name has a file: the
    # file that was looked for is in the message
    (lambda m, t: m["configs"][0].update(file=_config_file(t, architecture=None)),
     r'changed\.json: no "architecture" key'),
    (lambda m, t: m["configs"][0].update(file=_config_file(t, architecture="absent")),
     r"no architectures/absent\.py under \['benchmark'\]"),
])
def test_manifest_refuses(man, tmp_path, mutate, what):
    bad = copy.deepcopy(man)
    mutate(bad, tmp_path)
    with pytest.raises(manifest.ManifestError, match=what):
        manifest.validate(bad)
        for c in bad["configs"]:
            cfg = manifest.config(ROOT, bad, c["name"])
            manifest.architecture(ROOT, bad, cfg["architecture"])


def test_unknown_device_has_no_peaks(man):
    assert manifest.peaks(ROOT, man, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(manifest.ManifestError, match="no default"):
        manifest.peaks(ROOT, man, "cpu")


# -- traffic -------------------------------------------------------------------

@pytest.mark.parametrize("name", ["chat", "batch", "docqa"])
def test_traffic_is_the_same_work_for_every_seed(man, name):
    mix = manifest.traffic(ROOT, man, name)
    n = len(traffic.cycle(mix))
    big = 2**31 + 12345
    assert [traffic.request_body(mix, big, i, 1000) for i in range(2 * n)] == \
           [traffic.request_body(mix, big, i, 1000) for i in range(2 * n)]
    want = sorted(traffic.cycle(mix))
    orders = []
    for seed in (1, big):
        for c in range(3):
            got = [traffic.request_class(mix, seed, c * n + j) for j in range(n)]
            assert sorted(got) == want      # same classes, every cycle
            orders.append(got)
    assert len({tuple(o) for o in orders}) > 1  # in another order
    k, n_prompt, n_new, body = traffic.request_body(mix, 7, 3, 1000)
    sent = json.loads(body)["jsonData"]
    assert len(sent["prompt_tokens"][0]) == n_prompt
    assert sent["max_new_tokens"] == n_new and "eos_id" not in sent


def test_arrivals_are_one_per_slot(man):
    mix = manifest.traffic(ROOT, man, "chat")
    rate = mix["rate_rps"]
    for seed in (3, 2**31 + 9):
        due = [traffic.arrival(mix, seed, i) for i in range(2000)]
        assert due == sorted(due)
        assert all(i / rate <= t < (i + 1) / rate for i, t in enumerate(due))
        for lo in (5.0, 17.3, 60.1):      # any 50 s window: the count +-1
            count = sum(lo <= t < lo + 50.0 for t in due)
            assert abs(count - 50.0 * rate) <= 1


def test_clients_follow_the_lanes():
    assert traffic.n_clients({"clients": {"per_slot": 2, "extra": 0}}, 32) == 64
    assert traffic.n_clients({"clients": {"per_slot": 0, "extra": 1}}, 32) == 1


# -- records to metrics ----------------------------------------------------------

def _rec(i, due, first, spans, done, status="ok", prompt=10):
    r = Record(i, 0, prompt, sum(n for _t, n in spans), due, sent=due + 0.001)
    r.first, r.spans, r.done, r.status = first, spans, done, status
    r.n_tokens = sum(n for _t, n in spans)
    return r


def test_end_to_end_arithmetic_on_hand_made_records():
    recs = [
        # astride the opening edge: only the spans at t >= 10 count
        _rec(0, 9.0, 9.5, [(9.5, 8), (10.5, 8), (11.5, 8)], 11.5),
        # wholly inside: ttft 0.2 s; tpot (12.4 - 11.2) / 15 = 80 ms
        _rec(1, 11.0, 11.2, [(11.2, 8), (12.4, 8)], 12.4),
        # astride the closing edge: due inside, done outside
        _rec(2, 19.0, 19.4, [(19.4, 8), (20.6, 8)], 20.6),
        # due inside, refused
        _rec(3, 15.0, 0.0, [], 0.0, status="failed"),
        # due inside, still queued when the load was stopped
        _rec(4, 19.5, 0.0, [], 0.0, status="aborted"),
    ]
    w = (10.0, 20.0)
    assert endtoend.tokens_in(recs, *w) == 8 + 8 + 8 + 8 + 8
    assert endtoend.compute("tokens_per_s", recs, *w) == pytest.approx(4.0)
    assert sorted(endtoend.ttft_ms(recs, *w)) == pytest.approx([200.0, 400.0])
    assert endtoend.compute("ttft_p50_ms", recs, *w) == pytest.approx(300.0)
    # completed in the window: 0 (started before it) and 1
    assert sorted(endtoend.tpot_ms(recs, *w)) == pytest.approx(
        [1200.0 / 15, 2000.0 / 23])
    assert endtoend.failures(recs, *w, unanswered_fail=False) == (4, 1)
    assert endtoend.failures(recs, *w, unanswered_fail=True) == (4, 2)
    assert endtoend.live_positions(recs, 11.3) == (2, (10 + 16) + (10 + 8))
    assert endtoend.percentile([1, 2, 3, 4], 50) == 2.5
    assert endtoend.percentile([5], 95) == 5


def test_a_failure_after_the_window_is_found():
    """``--trace 2``: the same traffic runs on through the capture, and a
    request due then that failed still makes ``correct`` false; one that
    the stop cut, or one of the window (counted there), does not."""
    recs = [
        _rec(0, 19.0, 19.4, [(19.4, 8)], 19.9),
        _rec(1, 21.0, 0.0, [], 0.0, status="failed"),
        _rec(2, 25.0, 0.0, [], 0.0, status="aborted"),
        _rec(3, 15.0, 0.0, [], 0.0, status="failed"),
    ]
    late = endtoend.failed_in(recs, 20.0, float("inf"), False)
    assert [r.index for r in late] == [1]


# -- the end of the load ---------------------------------------------------------

def _sse_server(gap_s):
    """A front that streams each request's tokens one by one, ``gap_s``
    apart, and then the done event, as ``/api/v0.1/generate`` does."""
    import http.server
    import threading
    import time

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
            prompt = body["jsonData"]["prompt_tokens"][0]
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Connection", "close")
            self.end_headers()
            try:
                new = []
                for k in range(body["jsonData"]["max_new_tokens"]):
                    time.sleep(gap_s)
                    new.append(k % 7)
                    self.wfile.write(b"data: %s\n\n" % json.dumps(
                        {"tokens": [k % 7]}).encode())
                    self.wfile.flush()
                self.wfile.write(b"data: %s\n\n" % json.dumps(
                    {"done": True, "tokens": prompt + new}).encode())
                self.wfile.flush()
            except OSError:
                pass            # the client cut the stream

        def log_message(self, *args):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    server.daemon_threads = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server


@pytest.mark.parametrize("finish_s, want", [(5.0, OK), (0.0, ABORTED)])
def test_stop_lets_streams_finish_or_cuts_them(finish_s, want):
    """After the window no further request is sent; streams in flight run
    to their end inside ``finish_s``, and only what is left then is cut."""
    import time

    mix = {"loop": "closed", "clients": {"per_slot": 0, "extra": 3},
           "classes": [[4, 10, 1]], "temperature": 0.0}
    server = _sse_server(gap_s=0.05)
    try:
        load = Load(server.server_address[1], mix, seed=5, vocab=7, slots=1)
        load.start()
        deadline = time.monotonic() + 10.0
        while load.in_flight() < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert load.in_flight() == 3
        time.sleep(0.6)             # each client is in its second request
        sent = len(load.records)
        load.stop(finish_s)
        assert len(load.records) == sent        # nothing sent after the stop
        assert load.in_flight() == 0
        last = sorted(load.records, key=lambda r: r.index)[-3:]
        assert [r.status for r in last] == [want] * 3
        assert all(r.status == OK for r in load.records[:-3])
    finally:
        server.shutdown()
        server.server_close()


# -- trace reduction ---------------------------------------------------------------

def test_op_and_executable_names():
    assert trace.executable_of("jit_fused_burst(1234)") == "jit_fused_burst"
    hlo = "%fusion.12 = bf16[32,14336]{1,0:T(8,128)(2,1)} fusion(%a), kind=kOutput"
    assert trace.op_label(hlo) == "fusion_kOutput_bf16_32_14336"
    assert trace.op_label("%slice.4 = (bf16[7,8,2048,128]{3,1,2,0}, u32[]) slice-start(%b)") \
        == "slice_bf16_7_8_2048_128"
    assert trace.op_label("copy.3") == "copy"
    assert trace.op_label("%while.6 = (s32[], bf16[2,2]) while(%t)").startswith(trace.CONTAINERS)


def test_reduce_on_hand_made_events():
    events = {"devices": [{
        "name": "/device:TPU:0",
        "modules": [["jit_fused_burst(1)", 0.0, 1.0], ["jit_prefill_one(2)", 2.0, 1.0],
                    ["jit_fused_burst(1)", 3.0, 1.0]],
        "ops": [["fusion.1", 0.0, 0.6, ""], ["fusion.2", 0.5, 0.5, ""],
                ["copy.1", 2.0, 1.0, ""], ["fusion.1", 3.0, 0.5, ""],
                ["fusion.2", 3.5, 0.5, ""]],
    }], "host": [["gen.prefill", 0.9, 1.2], ["outer", 0.0, 4.0]]}
    out = trace.reduce(events)
    assert out["window_s"] == pytest.approx(4.0)
    assert out["busy_s"] == pytest.approx(3.0)      # the overlap counted once
    assert out["modules"]["jit_fused_burst"] == {"runs": 2, "seconds": 2.0}
    assert trace.module_seconds(out, "jit_prefill") == (1.0, 1)
    assert out["device_ops"][0] == ["jit_fused_burst:fusion", pytest.approx(2.1)]
    assert out["idle_gaps"][0] == ["gen.prefill", pytest.approx(1.0)]


FIXTURE = os.path.join(HERE, "trace_fixture.json.gz")


@pytest.mark.skipif(not os.path.exists(FIXTURE), reason="no recorded fixture")
def test_reduce_on_the_recorded_chip_trace():
    """A slice of a real trace of this PR's chip runs (see the fixture's
    ``about``): the numbers below were read off it by hand."""
    with gzip.open(FIXTURE, "rt") as f:
        fixture = json.load(f)
    out = trace.reduce(fixture["events"])
    want = fixture["expect"]
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    for exe, entry in want["modules"].items():
        assert out["modules"][exe]["runs"] == entry["runs"]
        assert out["modules"][exe]["seconds"] == pytest.approx(entry["seconds"], rel=1e-6)
    assert out["device_ops"][0][0] == want["top_op"]
    assert 0 < out["busy_s"] <= out["window_s"]


# -- costs -----------------------------------------------------------------------------

def test_costs_against_the_configs_own_arithmetic(man, decoder):
    costs = decoder
    mistral = manifest.config(ROOT, man, "mistral-7b-v0.3")
    assert costs.layer_matmul_params(mistral) == 218_103_808     # 218.1 M
    assert costs.kv_bytes_per_position(mistral) == mistral["num_hidden_layers"] * 4096
    intern = manifest.config(ROOT, man, "internlm2-1.8b")
    assert costs.kv_bytes_per_position(intern) == 98_304
    w0 = costs.decode_step_bytes(intern, 0, {})
    assert w0 == pytest.approx(2 * (1.889e9 - 92544 * 2048), rel=0.01)
    assert costs.decode_step_bytes(intern, 1000, {}) - w0 == 1000 * 98_304
    # two prompts of one length: the bound on the squares is exact
    one = costs.prefill_flops(mistral, 1792, 1, {})
    assert costs.prefill_flops(mistral, 2 * 1792, 2, {}) == pytest.approx(2 * one)
    # unequal lengths are counted low, never high
    assert costs.prefill_flops(mistral, 1792 + 2048, 2, {}) < one + costs.prefill_flops(
        mistral, 2048, 1, {})


# -- the plain reference against the served model ----------------------------------------

def test_reference_agrees_with_decoderlm_at_a_tiny_size(decoder):
    import jax

    from benchmark.reference import decoder as reference

    model = decoder.SeededDecoderLM(vocab_size=512, d_model=256, n_layers=2, n_heads=2,
                            n_kv_heads=1, d_ff=512, max_seq=256, rope_theta=1e6,
                            norm_eps=1e-5, dtype="bfloat16", residual_scale=0.05)
    params = model.init_params(7)
    assert all(a.dtype == jax.numpy.bfloat16
               for a in jax.tree_util.tree_leaves(params))
    out = decoder.compare_served(model, params, seed=2**31 + 3, prompt_len=128,
                                 decode_steps=3)
    assert out["ok"] and out["ratio"] < decoder.TOLERANCE, out
    # a model that differs in one norm weight must not pass
    params["ln_f"] = params["ln_f"] * 1.5
    ref = reference.logits(params, model.cfg, list(range(8)), [7])
    params["ln_f"] = params["ln_f"] / 1.5
    good = reference.logits(params, model.cfg, list(range(8)), [7])
    assert abs(ref - good).max() / good.std() > decoder.TOLERANCE


# -- the process: no jax in the parent, no result outside the repo, data-driven -----------

def test_the_parent_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import benchmark.run, "
            "benchmark.client, benchmark.traffic, benchmark.endtoend, "
            "benchmark.manifest, benchmark.capture, benchmark.trace, "
            "benchmark.sweep; from benchmark import manifest as m; "
            "man = m.load(%r); [m.architecture(%r, man, m.config(%r, man, "
            "c['name'])['architecture']) for c in man['configs']]; "
            "assert 'jax' not in sys.modules, 'jax imported'"
            % (ROOT, ROOT, ROOT, ROOT))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert done.returncode == 0, done.stderr


def test_no_result_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=NOT_COMMITTED)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "internlm2-1.8b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60)
    assert done.returncode != 0 and done.stdout.strip() == ""


NOT_COMMITTED = shutil.ignore_patterns("_runs", "_cache", "__pycache__")
TINY_MIX = {"loop": "closed", "clients": {"per_slot": 1, "extra": 1}, "ramp_s": 1,
            "drain_s": 0, "classes": [[20, 8, 1], [40, 16, 1]], "temperature": 0.0}


def _copy_of_the_benchmark(tmp_path):
    """``(benchmark/ of a copy beside the program, the manifest as a dict)``:
    what a later PR starts from."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=NOT_COMMITTED)
    os.symlink(os.path.join(ROOT, "seldon_core_tpu"), tmp_path / "seldon_core_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return tmp_path / "benchmark", json.load(f)


def _rehearse(tmp_path, cell, flag):
    """One CPU rehearsal of ``cell`` in the copy: ``(stdout, the line it
    printed in a result's place)``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", str(2**31 + 7), "--seconds", "3", "--trace", flag,
         "--rehearse-cpu"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    # a rehearsal never prints a line that parses as a result
    assert last.startswith("rehearsal (cpu")
    with pytest.raises(ValueError):
        json.loads(last)
    return done.stdout, json.loads(last.split(": ", 1)[1])


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A configuration, a traffic mix, a cell and a per-layer metric added as
    new files and manifest entries in a copy are found and run by the tiny
    CPU rehearsal; nothing that was there is edited."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    cfg = json.load(open(bench / "configs" / "internlm2-1.8b.json"))
    cfg["server"]["slots"] = 2
    (bench / "configs" / "added.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    (bench / "layer_metrics" / "added_requests.py").write_text(
        "def read(run):\n    return float(len(run['records']))\n")
    (bench / "layer_metrics" / "added_nothing.py").write_text(
        "def read(run):\n    return None\n")
    man["configs"].append({"name": "added", "source": "test", "reduced": [],
                           "file": "benchmark/configs/added.json", "why": "test"})
    man["workloads"].append({"name": "added.tiny", "config": "added",
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for name in ("added_requests", "added_nothing"):
        man["per_layer"].append({
            "name": name, "unit": "1", "better": "higher", "source": "host_clock",
            "layer": "load generator", "moves": "tpot_p50_ms",
            "workloads": ["added.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    lines = {flag: _rehearse(tmp_path, "added.tiny", flag)[1]
             for flag in ("0", "1", "2")}
    e2e, layers, both = lines["0"], lines["1"], lines["2"]
    assert set(e2e) == RESULT_KEYS and set(layers) == RESULT_KEYS | {"breakdown"}
    assert e2e["correct"] and e2e["failed"] == 0 and e2e["attempted"] > 0
    assert set(e2e["metrics"]) == {"tpot_p50_ms", "setup_s"}
    assert all(set(v) == {"value", "unit"} for v in e2e["metrics"].values())
    assert layers["metrics"]["added_requests"]["value"] > 0
    assert "added_nothing" not in layers["metrics"]     # found nothing: left out
    assert {"decode_step_device_ms", "load_s", "warm_s"} <= set(layers["metrics"])
    assert set(e2e["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert {"busy_s", "window_s"} <= set(layers["device"])
    # --trace 2: the window measured as under --trace 0, then captured
    assert set(both) == set(layers) and both["correct"] and both["failed"] == 0
    assert set(both["metrics"]) == set(e2e["metrics"]) | set(layers["metrics"])
    assert set(both["device"]) == set(layers["device"])
    assert set(both["breakdown"]) == set(layers["breakdown"])
    # the program's own spans and counters, read from its capture report
    for flag in ("1", "2"):
        got = lines[flag]["metrics"]
        assert 0.0 < got["scheduler_host_share"]["value"] <= 100.0
        assert 0.0 <= got["prefill_device_share"]["value"] <= 100.0
    runs = bench / "_runs" / "added.tiny"
    assert len(list(runs.glob("*/requests.jsonl"))) == 3    # written on every run


# the files a PR brings for a configuration of an architecture the benchmark
# has not seen. The program serves dense decoders only, so this one is a
# dense decoder too, under names of its own for width and depth: what is
# shown is who gets called, not new mathematics
OTHER_ARCHITECTURE = '''
"""A decoder whose published config says ``width`` and ``depth``."""
import sys

FAMILY = "other_family"
STEP_BYTES = 819e3      # with the peak's 819e9 B/s: 1 us a step


def __getattr__(name):
    if name != "OtherLM":
        raise AttributeError(name)
    from seldon_core_tpu.models.llm import DecoderLM

    class OtherLM(DecoderLM):
        def init_params(self, seed=0):
            print(f"other_family draws its weights, seed {seed}",
                  file=sys.stderr, flush=True)
            return super().init_params(seed)

    globals()[name] = OtherLM
    return OtherLM


def register():
    from seldon_core_tpu import models

    models.register(FAMILY, __name__ + ".OtherLM")


def model_kwargs(cfg, seed):
    return {"vocab_size": cfg["vocab_size"], "d_model": cfg["width"],
            "n_layers": cfg["depth"], "n_heads": cfg["width"] // 128,
            "n_kv_heads": 1, "d_ff": 2 * cfg["width"],
            "max_seq": cfg["server"]["max_seq"], "residual_scale": 0.05,
            "seed": seed % 1000}


def rehearsal(cfg):
    return {"width": 256, "depth": 1, "vocab_size": 768}


def compare_served(model, params, seed):
    import jax
    import numpy as np

    from benchmark.reference import other

    tokens = np.random.default_rng(seed).integers(0, model.cfg.vocab_size, 24)
    served, _cache = jax.jit(lambda p, t: model.prefill(p, t, 128))(
        params, jax.numpy.asarray(tokens[None], "int32"))
    ref = other.logits(params, model.cfg, tokens, [23])
    ratio = float(np.max(np.abs(np.asarray(served[0]) - ref[0])) / ref.std())
    finite = bool(np.isfinite(np.asarray(served)).all())
    return {"by": "other_architecture", "ratio": ratio, "tolerance": 0.2,
            "finite": finite, "ok": finite and ratio <= 0.2}


def decode_step_bytes(cfg, live_positions, counters):
    # a sparse model counts what was routed; without counters, nothing
    return STEP_BYTES if counters.get("tokens", 0) > 0 else 0.0


def prefill_flops(cfg, padded_tokens, sequences, counters):
    return float(cfg["width"] * padded_tokens)
'''
OTHER_REFERENCE = '''
"""The plain reference of ``other``: the dense decoder's forward."""
from benchmark.reference.decoder import logits  # noqa: F401
'''
OTHER_READER = '''
from benchmark import capture


def read(run):
    return run["architecture"].prefill_flops(
        run["config"], 10, 1, capture.counters(run))
'''


def test_a_configuration_of_another_architecture_is_added_by_files_alone(tmp_path):
    """An architecture module, its reference, a configuration in that
    architecture's own keys, a cell and a per-layer reader are added to a
    copy as new files and manifest entries. The rehearsal serves the
    module's family with the module's kwargs, takes ``correct`` from its
    ``compare_served`` and the roofline from its bytes, and no file that
    was there is edited."""
    bench, man = _copy_of_the_benchmark(tmp_path)
    (bench / "architectures" / "other.py").write_text(OTHER_ARCHITECTURE)
    (bench / "reference" / "other.py").write_text(OTHER_REFERENCE)
    (bench / "layer_metrics" / "other_prefill_flops.py").write_text(OTHER_READER)
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    (bench / "configs" / "other.json").write_text(json.dumps({
        "source": "test", "architecture": "other", "width": 1024, "depth": 6,
        "vocab_size": 4096, "server": {"slots": 2, "max_seq": 256}}))
    man["configs"].append({"name": "other", "source": "test", "reduced": [],
                           "file": "benchmark/configs/other.json", "why": "test"})
    man["workloads"].append({"name": "other.tiny", "config": "other",
                             "traffic": "tiny", "chips": 1, "why": "test"})
    man["per_layer"].append({
        "name": "other_prefill_flops", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "kernels", "moves": "tpot_p50_ms",
        "workloads": ["other.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    for flag in ("0", "2"):
        out, line = _rehearse(tmp_path, "other.tiny", flag)
        assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
        # the reply of the module's own comparison, not the decoder's
        assert "'by': 'other_architecture'" in out and "'tolerance': 0.2" in out
        run_dir, = (bench / "_runs" / "other.tiny").glob(f"*-trace{flag}-0")
        # the engine was given, and built, the module's family and kwargs
        assert json.load(open(run_dir / "model" / "jax_config.json")) == {
            "family": "other_family",
            "config": {"vocab_size": 768, "d_model": 256, "n_layers": 1,
                       "n_heads": 2, "n_kv_heads": 1, "d_ff": 512, "max_seq": 256,
                       "residual_scale": 0.05, "seed": (2**31 + 7) % 1000}}
        assert (f"other_family draws its weights, seed {(2**31 + 7) % 1000}"
                in (run_dir / "engine.log").read_text())
    got = line["metrics"]
    assert set(got) >= {"tpot_p50_ms", "setup_s", "decode_step_device_ms"}
    # 819e3 B at the table's 819e9 B/s is 1 us: the module's bytes, counted
    # with the capture's counters in hand, over the step's device time
    assert got["decode_hbm_roofline"]["value"] == pytest.approx(
        100.0 * 1e-3 / got["decode_step_device_ms"]["value"])
    assert got["other_prefill_flops"]["value"] == 256 * 10
    # added, not edited: what the repo's benchmark/ holds is there unchanged
    for folder, _dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        if os.path.basename(folder) in ("_runs", "_cache", "__pycache__"):
            _dirs[:] = []
            continue
        for name in files:
            theirs = os.path.join(folder, name)
            mine = os.path.join(bench, os.path.relpath(theirs, os.path.join(ROOT, "benchmark")))
            assert open(mine, "rb").read() == open(theirs, "rb").read(), mine
