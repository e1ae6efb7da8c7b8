"""`sdctl build`: user code -> servable image context (reference parity:
wrappers/s2i/python/s2i/bin/assemble's MODEL_NAME/API_TYPE/SERVICE_TYPE/
PERSISTENCE contract, without the s2i toolchain)."""

import subprocess

import pytest

from seldon_core_tpu.build import docker_build, write_build_context


@pytest.fixture
def pysrc(tmp_path):
    src = tmp_path / "user"
    src.mkdir()
    (src / "MyModel.py").write_text(
        "import numpy as np\n"
        "class MyModel:\n"
        "    def predict(self, X, names, meta=None):\n"
        "        return np.asarray(X)\n"
    )
    (src / "requirements.txt").write_text("numpy\n")
    return src


def test_python_context(pysrc, tmp_path):
    out = tmp_path / "ctx"
    files = write_build_context(
        str(pysrc), str(out), "MyModel", api_type="BOTH",
        service_type="MODEL", persistence=True,
    )
    assert "Dockerfile" in files
    assert "src/MyModel.py" in files
    assert "src/requirements.txt" in files
    df = (out / "Dockerfile").read_text()
    # the reference assemble's four contract env vars
    assert "MODEL_NAME=MyModel" in df
    assert "API_TYPE=BOTH" in df
    assert "SERVICE_TYPE=MODEL" in df
    assert "PERSISTENCE=1" in df
    assert "seldon-tpu-microservice $MODEL_NAME $API_TYPE" in df
    # persistence resolved at container start from the env var
    assert '"$PERSISTENCE" = "1"' in df


def test_python_missing_module_rejected(tmp_path):
    src = tmp_path / "empty"
    src.mkdir()
    with pytest.raises(FileNotFoundError, match="MODEL_NAME"):
        write_build_context(str(src), str(tmp_path / "ctx"), "Nope")


def test_dotted_model_name_checks_module_file(pysrc, tmp_path):
    files = write_build_context(
        str(pysrc), str(tmp_path / "ctx"), "MyModel.MyModel"
    )
    assert "src/MyModel.py" in files


def test_cpp_context(tmp_path):
    src = tmp_path / "cpp"
    src.mkdir()
    (src / "component.cpp").write_text("int main(){return 0;}\n")
    out = tmp_path / "ctx"
    write_build_context(
        str(src), str(out), "cpp-clf", language="cpp",
    )
    df = (out / "Dockerfile").read_text()
    assert "g++ -O2 -std=c++17" in df
    assert "component.cpp" in df
    assert 'ENTRYPOINT ["/component"]' in df


def test_out_inside_src_rejected(pysrc, tmp_path):
    with pytest.raises(ValueError, match="outside --src"):
        write_build_context(str(pysrc), str(pysrc / "ctx"), "MyModel")


def test_invalid_api_and_service_types(pysrc, tmp_path):
    with pytest.raises(ValueError, match="API_TYPE"):
        write_build_context(str(pysrc), str(tmp_path / "c1"), "MyModel",
                            api_type="SOAP")
    with pytest.raises(ValueError, match="SERVICE_TYPE"):
        write_build_context(str(pysrc), str(tmp_path / "c2"), "MyModel",
                            service_type="ORACLE")


def test_docker_build_invocation_injectable(tmp_path):
    calls = []

    def runner(cmd, check):
        calls.append((cmd, check))

    assert docker_build(str(tmp_path), "repo/img:1", runner=runner)
    assert calls == [
        (["docker", "build", "-t", "repo/img:1", str(tmp_path)], True)
    ]


def test_cli_build(pysrc, tmp_path, capsys):
    from seldon_core_tpu.controlplane.cli import main

    out = tmp_path / "ctx"
    main(["--store-dir", str(tmp_path / "store"), "build",
          "--src", str(pysrc), "--model-name", "MyModel",
          "--api-type", "REST", "--out", str(out)])
    assert (out / "Dockerfile").exists()
    assert "wrote build context" in capsys.readouterr().out


def test_generated_command_actually_serves(pysrc, tmp_path):
    """The CMD the Dockerfile would run, executed directly on this host
    (no docker in the image): the microservice comes up and answers a
    predict — the context is servable, not just well-formed."""
    import json
    import time
    import urllib.request

    from seldon_core_tpu.testing import free_port

    out = tmp_path / "ctx"
    write_build_context(str(pysrc), str(out), "MyModel")
    port = free_port()
    import os
    import re
    import sys

    import seldon_core_tpu

    repo_root = os.path.dirname(os.path.dirname(seldon_core_tpu.__file__))
    # derive the command from the generated Dockerfile's own CMD + ENV
    # lines, so a CMD that a real container would crash on fails HERE
    # (substituting the console script for `python -m` — the image has it
    # on PATH, this host does not)
    df = (out / "Dockerfile").read_text()
    cmd_line = re.search(r"^CMD (.+)$", df, re.M).group(1).strip()
    assert not cmd_line.startswith("["), "python template uses shell-form CMD"
    shell_cmd = cmd_line.replace(
        "seldon-tpu-microservice",
        f"{sys.executable} -m seldon_core_tpu.microservice",
    ) + f" --service-port {port}"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo_root + os.pathsep
           + os.environ.get("PYTHONPATH", ""),
           # the ENV block a container would carry
           "MODEL_NAME": "MyModel", "API_TYPE": "REST",
           "SERVICE_TYPE": "MODEL", "PERSISTENCE": "0"}
    proc = subprocess.Popen(
        ["bash", "-c", shell_cmd],
        cwd=str(out / "src"),
        env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        body = json.dumps({"data": {"ndarray": [[1.0, 2.0]]}}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict", data=body,
            headers={"Content-Type": "application/json"},
        )
        deadline = time.time() + 30
        last = None
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(req, timeout=2) as resp:
                    got = json.loads(resp.read())
                    assert got["data"]["ndarray"] == [[1.0, 2.0]]
                    return
            except Exception as e:  # noqa: BLE001 - booting
                last = e
                time.sleep(0.5)
        raise AssertionError(f"microservice never answered: {last}")
    finally:
        proc.terminate()
        proc.wait(timeout=10)
