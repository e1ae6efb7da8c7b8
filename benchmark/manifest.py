"""BENCHMARK.json and the data files it names.

A cell is ``<config>.<traffic>``. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own, found by name, so a later PR adds a cell by adding files and manifest
entries and edits nothing here:

    <file of the config's entry>              sizes, server settings
    <path>/traffic/<traffic>.json             the mix the generator reads
    <path>/layer_metrics/<metric>.py          a reader: read(run) -> value
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# the driver's switch: cells are measured and traced in one process
# (``--trace 2``); read by nothing here
OPTIONAL_TOP_KEYS = {"trace_in_run"}


class ManifestError(ValueError):
    """BENCHMARK.json, or a file it names, does not hold what it must."""


def load(root: str) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e
    validate(manifest)
    return manifest


def validate(manifest: dict) -> None:
    """The rules a typo breaks: key sets, names, units, references. The
    driver checks the whole contract; this catches a bad entry before a
    run is spent on it."""
    if set(manifest) - OPTIONAL_TOP_KEYS != TOP_KEYS:
        raise ManifestError(f"keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
    seen: set = set()
    configs = {c["name"] for c in manifest["configs"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            name = entry.get("name", "")
            if not NAME_RE.match(name):
                raise ManifestError(f"{group}: bad name {name!r}")
            if (group, name) in seen:
                raise ManifestError(f"{group}: {name!r} appears twice")
            seen.add((group, name))
    cells = set()
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            raise ManifestError(f"cell {w['name']}: no config {w['config']!r}")
        if not NAME_RE.match(w["traffic"]):
            raise ManifestError(f"cell {w['name']}: bad traffic name")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"cell {w['name']}: chips must be 1 or 4")
        cells.add(w["name"])
    e2e = {m["name"] for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end lacks setup_s")
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if not UNIT_RE.match(m.get("unit", "")):
                raise ManifestError(f"{m['name']}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                raise ManifestError(f"{m['name']}: better must be lower|higher")
            if m.get("source") not in SOURCES:
                raise ManifestError(f"{m['name']}: bad source")
            for cell in m.get("workloads", []):
                if cell not in cells:
                    raise ManifestError(f"{m['name']}: no cell {cell!r}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"{m['name']}: end-to-end source")
        if not 0 < m.get("bound", 0) <= 0.1:
            raise ManifestError(f"{m['name']}: bound outside (0, 0.1]")
    for m in manifest["per_layer"]:
        if m.get("moves") not in e2e:
            raise ManifestError(f"{m['name']}: moves {m.get('moves')!r}")
        if not m.get("layer"):
            raise ManifestError(f"{m['name']}: no layer")


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"no cell {name!r}; have {[w['name'] for w in manifest['workloads']]}"
    )


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The cell's metrics of one group: those without a ``workloads`` key,
    and those that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


def config(root: str, manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            cfg = _read_json(os.path.join(root, c["file"]))
            cfg["name"] = name
            return cfg
    raise ManifestError(f"no config {name!r}")


def _find(root: str, manifest: dict, *parts: str) -> str:
    for p in manifest["paths"]:
        path = os.path.join(root, p, *parts)
        if os.path.isfile(path):
            return path
    raise ManifestError(f"no {os.path.join(*parts)} under {manifest['paths']}")


def traffic(root: str, manifest: dict, name: str) -> dict:
    mix = _read_json(_find(root, manifest, "traffic", name + ".json"))
    mix["name"] = name
    return mix


def peaks(root: str, manifest: dict, device_kind: str) -> dict:
    table = _read_json(_find(root, manifest, "peaks.json"))
    if device_kind not in table:
        raise ManifestError(
            f"device {device_kind!r} is not in the table of peaks "
            f"({sorted(table)}): add it with its source, no default is taken"
        )
    return table[device_kind]


def layer_reader(root: str, manifest: dict, metric: str):
    """The ``read(run)`` function of ``layer_metrics/<metric>.py``."""
    path = _find(root, manifest, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + re.sub(r"\W", "_", metric), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def decoder_kwargs(cfg: dict, seed: int) -> dict:
    """The published config's keys as ``DecoderLM`` takes them."""
    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ManifestError(f"{cfg['name']}: hidden != heads x head_dim")
    return {
        "vocab_size": cfg["vocab_size"],
        "d_model": cfg["hidden_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"],
        "max_seq": cfg["server"]["max_seq"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "residual_scale": cfg["weights"]["residual_scale"],
        # PRNGKey takes 32 bits; the driver's seeds are larger
        "seed": seed % (2**31 - 1),
    }
