"""chip_smoke.py off the chip: what tier-1 can hold it to on the CPU.

The smoke itself needs a TPU (it is run through the chip tool). Here: its
parent stays off jax, the default invocation refuses to run without a TPU
before it builds anything, and what it would serve is the 1.26B flagship
through a valid GENERATE_SERVER spec.
"""

import importlib.util
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_parent_side_never_imports_jax(tmp_path):
    """One process per chip: a parent that touched jax would hold the chip
    its children need. Everything the parent calls runs here, in a fresh
    interpreter, and jax must still be absent afterwards."""
    code = (
        "import sys; sys.path.insert(0, {repo!r}); import chip_smoke as s; "
        "s.write_model_and_spec({out!r}, s.FLAGSHIP, s.MESH_SHAPE); "
        "s.bucket_of(24); s.make_prompts(256); s.child_env(False); "
        "import grpc, seldon_core_tpu.proto, seldon_core_tpu.testing; "
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]; "
        "assert not bad, bad"
    ).format(repo=REPO, out=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr


def test_default_invocation_refuses_without_a_tpu(tmp_path):
    """No TPU in the sandbox: the smoke overrides the inherited
    JAX_PLATFORMS=cpu, fails fast naming the device problem, prints no
    result and builds no model."""
    out = tmp_path / "out"
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(out)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "Unable to initialize backend 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert not any(f == "jax_config.json" for _, _, fs in os.walk(out) for f in fs)


def test_result_line_has_the_contract_keys_and_no_others():
    """The last stdout line is parsed by whoever runs the smoke: exactly
    ``ok`` and ``device`` with ``platform``, ``kind``, ``count``. Legs,
    versions and observations belong on the summary line before it."""
    smoke = _load_smoke()
    line = smoke.result_line(True, {"platform": "tpu", "kind": "TPU v5 lite",
                                    "count": 1, "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_spec_and_model_dir_are_the_flagship(tmp_path):
    from seldon_core_tpu.graph.spec import (
        PredictorSpec, default_predictor, validate_predictor,
    )
    from seldon_core_tpu.models.llm import DecoderLM

    smoke = _load_smoke()
    spec_path = smoke.write_model_and_spec(str(tmp_path), smoke.FLAGSHIP)
    with open(spec_path) as f:
        spec = json.load(f)
    validate_predictor(default_predictor(PredictorSpec.from_dict(spec)))
    graph = spec["graph"]
    assert graph["implementation"] == "GENERATE_SERVER"
    # every GenerateServer knob at its default except slots and the warm-up
    assert [p["name"] for p in graph["parameters"]] == [
        "slots", "warmup_prompt_lens", "warmup_max_new_tokens",
    ]
    with open(os.path.join(graph["modelUri"], "jax_config.json")) as f:
        model = json.load(f)
    assert model["family"] == "llm"
    cfg = model["config"]
    assert {k: v for k, v in cfg.items() if k != "seed"} == {
        "vocab_size": 32000, "d_model": 2048, "n_layers": 24,
        "n_heads": 16, "n_kv_heads": 8, "d_ff": 5632,
        "max_seq": 1024, "residual_scale": 0.05,
    }
    lm = DecoderLM(**cfg)
    assert lm.cfg.head_dim == 128
    assert 1.2e9 < lm.n_params() < 1.3e9
    # the declared warm-up lengths are the prompt lengths, so every bucket
    # the smoke sends is warmed: one XLA-attention bucket, two kernel ones
    assert sorted({smoke.bucket_of(n) for n in smoke.PROMPT_LENS}) == [32, 128, 1024]
